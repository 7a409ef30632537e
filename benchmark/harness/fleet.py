"""The seeded fleet: counter series of one deployment file.

Series i is instance (i % instances) of job (i // instances); its zone
is instance % zones.  All series share their timestamps.  Increments
are uniform integers 0..99 drawn from default_rng([seed, run, block])
for each run of RUN consecutive series and each block, and block k
starts from k * 100 * points_per_block, so a counter never resets and
any (series range, block) is regenerated on demand, never held.
"""

from __future__ import annotations

import time

import numpy as np

from harness import wire

RUN = 25                 # series per generator draw and per block request


def for_run(run, n_blocks: int) -> "Fleet":
    """The fleet of a run's configuration (at --rehearse sizes where the
    file gives them), anchored at the wall clock."""
    cfg = run.config
    return Fleet(dict(cfg, jobs=run.param(cfg, "jobs"),
                      instances_per_job=run.param(cfg, "instances_per_job")),
                 run.seed, int(time.time()), n_blocks)


class Fleet:
    def __init__(self, cfg: dict, seed: int, now_s: int, n_blocks: int):
        self.cfg, self.seed, self.n_blocks = cfg, int(seed), n_blocks
        self.metric = cfg["metric"]
        self.jobs, self.instances = cfg["jobs"], cfg["instances_per_job"]
        self.zones = cfg["zones"]
        self.n_series = self.jobs * self.instances
        if self.instances % RUN:
            raise ValueError("instances_per_job must be a multiple of 25")
        self.cadence_s, self.block_s = cfg["cadence_s"], cfg["block_s"]
        self.per_block = self.block_s // self.cadence_s
        # newest block the service's own tick can seal right now
        self.seal_end = ((now_s - cfg["buffer_past_s"])
                         // self.block_s) * self.block_s
        self.t0 = self.seal_end - n_blocks * self.block_s
        self._labels: dict[int, bytes] = {}

    def job_name(self, j: int) -> str:
        return f"job-{j:03d}"

    def labels(self, i: int) -> dict[bytes, bytes]:
        inst = i % self.instances
        return {b"__name__": self.metric.encode(),
                b"job": self.job_name(i // self.instances).encode(),
                b"zone": b"zone-%d" % (inst % self.zones),
                b"instance": b"inst-%04d" % inst}

    def block_ts(self, k: int) -> np.ndarray:
        """int64 seconds of block k's samples."""
        start = self.t0 + k * self.block_s
        return np.arange(start, start + self.block_s, self.cadence_s,
                         dtype=np.int64)

    def block_values(self, lo: int, hi: int, k: int) -> np.ndarray:
        """float64 [hi - lo, per_block]; lo and hi multiples of RUN."""
        parts = []
        for run in range(lo // RUN, hi // RUN):
            rng = np.random.default_rng([self.seed, run, k])
            inc = rng.integers(0, 100, size=(RUN, self.per_block))
            parts.append(np.cumsum(inc, axis=1) + k * 100 * self.per_block)
        return np.concatenate(parts).astype(np.float64)

    def job_arrays(self, j: int, blocks=None):
        """(ts_s int64 [T], values float64 [instances, T]) of one job
        over `blocks` (default all): what the reference computes from."""
        blocks = range(self.n_blocks) if blocks is None else blocks
        lo = j * self.instances
        ts = np.concatenate([self.block_ts(k) for k in blocks])
        vs = np.concatenate([self.block_values(lo, lo + self.instances, k)
                             for k in blocks], axis=1)
        return ts, vs

    def block_requests(self, k: int):
        """Series ranges of block k's write requests."""
        return [(lo, lo + RUN) for lo in range(0, self.n_series, RUN)]

    def body(self, lo: int, hi: int, k: int) -> tuple[bytes, int]:
        vals = self.block_values(lo, hi, k)
        blobs = []
        for i in range(lo, hi):
            lb = self._labels.get(i)
            if lb is None:
                lb = self._labels[i] = wire.label_bytes(self.labels(i))
            blobs.append(lb)
        return wire.write_request(blobs, self.block_ts(k) * 1000,
                                  vals), vals.size
