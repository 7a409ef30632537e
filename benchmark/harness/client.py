"""Keep-alive loopback HTTP client (copied from chip_smoke.py)."""

from __future__ import annotations

import http.client
import json
import urllib.parse


WRITE_HEADERS = {"Content-Encoding": "snappy",
                 "Content-Type": "application/x-protobuf"}


class HTTPFailure(RuntimeError):
    pass


class Client:
    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=300)

    def request(self, method: str, path: str, body=None, headers=None):
        self.conn.request(method, path, body, headers or {})
        resp = self.conn.getresponse()
        return resp.status, resp.read()

    def get_json(self, path: str, **params):
        qs = urllib.parse.urlencode(params)
        status, body = self.request("GET", f"{path}?{qs}" if qs else path)
        if status != 200:
            raise HTTPFailure(
                f"GET {path} {params} -> HTTP {status}: {body[:300]!r}")
        return json.loads(body)

    def remote_write(self, body: bytes) -> None:
        """POST one snappy-framed WriteRequest; raises unless acked."""
        status, resp = self.request("POST", "/api/v1/prom/remote/write",
                                    body, WRITE_HEADERS)
        if not 200 <= status < 300:
            raise HTTPFailure(
                f"remote write -> HTTP {status}: {resp[:200]!r}")

    def close(self) -> None:
        self.conn.close()
