"""The seeded fleet whose instances differ in load: counter series of
one deployment file (configs/m3query-topk.json).

A job has `instances` instances and each exports one series a handler:
series i is handler (i % handlers) of instance ((i % per_job) //
handlers) of job (i // per_job), per_job = instances x handlers; its
zone is instance % zones.  All series share their timestamps.

The load follows Zipf's law with exponent 1 over a job's instances: the
instances are ranked 0..instances-1 by a permutation drawn from
default_rng([seed, job]), and a series' increments are uniform integers
0..M-1 with M = max(2, round(m_top / (1 + rank))); an instance's
handlers share its M.  Increments come from default_rng([seed, run,
block]) for each run of RUN consecutive series and each block, and block
k starts from k * M * points_per_block, so a counter never resets and
any (series range, block) is regenerated on demand, never held, as in
harness/fleet.py, whose clock, requests and wire form this one keeps.
"""

from __future__ import annotations

import time

import numpy as np

from harness.fleet import RUN, Fleet


def for_run(run, n_blocks: int) -> "SkewedFleet":
    """The fleet of a run's configuration (at --rehearse sizes where the
    file gives them), anchored at the wall clock."""
    cfg = run.config
    return SkewedFleet(dict(cfg, **{k: run.param(cfg, k) for k in (
        "jobs", "instances_per_job", "handlers", "increment_top")}),
        run.seed, int(time.time()), n_blocks)


class SkewedFleet(Fleet):
    def __init__(self, cfg: dict, seed: int, now_s: int, n_blocks: int):
        self.cfg, self.seed, self.n_blocks = cfg, int(seed), n_blocks
        self.metric = cfg["metric"]
        self.jobs, self.instances = cfg["jobs"], cfg["instances_per_job"]
        self.handlers, self.zones = cfg["handlers"], cfg["zones"]
        self.per_job = self.instances * self.handlers
        self.n_series = self.jobs * self.per_job
        if self.per_job % RUN:
            raise ValueError("instances_per_job x handlers must be a "
                             "multiple of 25")
        self.cadence_s, self.block_s = cfg["cadence_s"], cfg["block_s"]
        self.per_block = self.block_s // self.cadence_s
        # newest block the service's own tick can seal right now
        self.seal_end = ((now_s - cfg["buffer_past_s"])
                         // self.block_s) * self.block_s
        self.t0 = self.seal_end - n_blocks * self.block_s
        self._labels: dict[int, bytes] = {}
        # [jobs, instances]: an instance's place in its job's order of
        # load, and the bound of its increments
        self.rank = np.stack([
            np.argsort(np.random.default_rng([self.seed, j])
                       .permutation(self.instances))
            for j in range(self.jobs)])
        self.m = np.maximum(2, np.rint(
            cfg["increment_top"] / (1.0 + self.rank))).astype(np.int64)

    def instance_name(self, inst: int) -> str:
        return f"inst-{inst:04d}"

    def instance_of(self, i):
        """The instance (within its job) of series i; arrays too."""
        return i % self.per_job // self.handlers

    def labels(self, i: int) -> dict[bytes, bytes]:
        inst = int(self.instance_of(i))
        return {b"__name__": self.metric.encode(),
                b"job": self.job_name(i // self.per_job).encode(),
                b"zone": b"zone-%d" % (inst % self.zones),
                b"instance": self.instance_name(inst).encode(),
                b"handler": b"/api/h%d" % (i % self.handlers)}

    def series_m(self, lo: int, hi: int) -> np.ndarray:
        """int64 [hi - lo]: the increments' bound of each series."""
        i = np.arange(lo, hi)
        return self.m[i // self.per_job, self.instance_of(i)]

    def block_values(self, lo: int, hi: int, k: int) -> np.ndarray:
        """float64 [hi - lo, per_block]; lo and hi multiples of RUN."""
        parts = []
        for run in range(lo // RUN, hi // RUN):
            m = self.series_m(run * RUN, (run + 1) * RUN)[:, None]
            rng = np.random.default_rng([self.seed, run, k])
            inc = rng.integers(0, m, size=(RUN, self.per_block))
            parts.append(np.cumsum(inc, axis=1) + k * m * self.per_block)
        return np.concatenate(parts).astype(np.float64)

    def job_arrays(self, j: int, blocks=None):
        """(ts_s int64 [T], values float64 [per_job, T]) of one job over
        `blocks` (default all): what the reference computes from."""
        blocks = range(self.n_blocks) if blocks is None else blocks
        lo = j * self.per_job
        ts = np.concatenate([self.block_ts(k) for k in blocks])
        vs = np.concatenate([self.block_values(lo, lo + self.per_job, k)
                             for k in blocks], axis=1)
        return ts, vs
