"""Process harness: spawn/kill/restart m3_tpu service roles
(ref: src/cmd/tools/dtest/harness/harness.go + m3em process lifecycle).
"""

from __future__ import annotations

import os
import pathlib
import signal
import socket
import subprocess
import sys
import time
from dataclasses import dataclass, field


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclass
class ServiceProc:
    role: str
    argv: list[str]
    env: dict
    proc: subprocess.Popen | None = None
    endpoint: str = ""
    log: list[str] = field(default_factory=list)

    def start(self, timeout: float = 90.0) -> "ServiceProc":
        import queue
        import threading

        self.proc = subprocess.Popen(
            [sys.executable, "-m", "m3_tpu.services", *self.argv],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=self.env)
        # a reader thread feeds a queue so the startup deadline holds
        # even when the process stays alive but silent (a blocking
        # readline would hang the whole suite past the timeout)
        lines: queue.Queue = queue.Queue()
        proc = self.proc

        def pump():
            for line in proc.stdout:
                lines.put(line)

        threading.Thread(target=pump, daemon=True).start()  # lint: allow-unregistered-thread (test-harness stdout pump, exits with subprocess)
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                line = lines.get(timeout=0.2)
            except queue.Empty:
                if self.proc.poll() is not None:
                    break
                continue
            self.log.append(line.rstrip())
            if " up: " in line:
                self.endpoint = line.strip().split(" up: ")[1]
                return self
        self.kill()
        # drain whatever the pump thread enqueued after the last get —
        # a fast-dying child's traceback usually lands here, and losing
        # it makes every startup failure undebuggable
        time.sleep(0.2)
        while True:
            try:
                self.log.append(lines.get_nowait().rstrip())
            except queue.Empty:
                break
        tail = "\n".join(self.log[-20:])
        raise AssertionError(f"{self.role} never came up:\n{tail}")

    def kill(self, sig: int = signal.SIGKILL) -> None:
        """The fault injector: default SIGKILL (no graceful shutdown,
        no flush — exactly the crash the durability story must cover)."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(sig)
            self.proc.wait(timeout=10)

    def restart(self, timeout: float = 90.0) -> "ServiceProc":
        self.kill()
        return self.start(timeout)

    @property
    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None


class ProcessHarness:
    """Spawns service roles as real processes; tears everything down."""

    def __init__(self, workdir: str):
        self.workdir = pathlib.Path(workdir)
        self.env = dict(os.environ)
        # spawned roles stay off the accelerator: at most one process
        # per host may own a chip, and the harness owns none
        self.env["JAX_PLATFORMS"] = "cpu"
        self.env["PYTHONPATH"] = str(
            pathlib.Path(__file__).resolve().parents[2])
        self.procs: list[ServiceProc] = []

    def spawn(self, role: str, *argv: str,
              env: dict | None = None) -> ServiceProc:
        """``env`` adds/overrides variables for THIS process only —
        fault injection hooks like M3_TPU_EXIT_AT_POINT ride in here.
        Clear them (del p.env[...]) before a restart that must
        survive."""
        p = ServiceProc(role, [role, *argv],
                        {**self.env, **(env or {})}).start()
        self.procs.append(p)
        return p

    def write_config(self, name: str, text: str) -> str:
        path = self.workdir / name
        path.write_text(text)
        return str(path)

    def stop_all(self) -> None:
        for p in self.procs:
            try:
                p.kill(signal.SIGTERM)
            except Exception:  # noqa: BLE001 — teardown is best-effort
                pass
        for p in self.procs:
            try:
                p.kill()
            except Exception:  # noqa: BLE001
                pass
