"""The seeded fleet that exposes Prometheus histograms: the series of
one deployment file (configs/m3query-histogram.json).

An instance exposes the family `metric` as client_golang does with
`prometheus.DefBuckets`: `<metric>_bucket{le}` for the 12 `le` values
of LE, `<metric>_sum` and `<metric>_count`, 14 series.  Series i is
series (i % 14) of instance (i % per_job // 14) of job (i // per_job),
per_job = instances x 14: the buckets in the order of LE, then the sum,
then the count.  Its zone is instance % zones.  All series share their
timestamps.

The law.  At a scrape an instance has served `n` more requests, `n`
uniform on 0..99.  A request's duration is `m * exp(SIGMA * z)` seconds,
z standard normal: log-normal with median `m`, and `m` runs
geometrically from MEDIAN_LO to MEDIAN_HI over the instance's place in
a permutation of its job's instances drawn from default_rng([seed,
job]).  `_bucket{le}` grows by the requests whose duration is <= le
(a multinomial of n over the 12 intervals, cumulated over `le`),
`_count` by n, `_sum` by the float64 total of the n durations.  The
draws of RUN consecutive instances in one block come from
default_rng([seed, job, run, block]): first the [RUN, points] counts n,
then [RUN, points, 99] normals, of which a scrape uses the first n.  A
block's counters start from the totals of the blocks before it (kept
once drawn, drawn again when not), so a counter never resets, `+Inf`
equals `_count` at every sample and any (series range, block) is
regenerated on demand, never held, as in harness/fleet.py, whose clock,
requests and wire form this one keeps.
"""

from __future__ import annotations

import time

import numpy as np

from harness.fleet import Fleet

LE = ("0.005", "0.01", "0.025", "0.05", "0.1", "0.25", "0.5", "1", "2.5",
      "5", "10", "+Inf")      # prometheus.DefBuckets, as Prometheus writes them
BOUNDS = np.array([float(le) for le in LE[:-1]])
PER_INSTANCE = len(LE) + 2    # the buckets, _sum, _count
RUN = 2                       # instances per generator draw and per request
SIGMA = 1.0
MEDIAN_LO, MEDIAN_HI = 0.010, 2.56
N_MAX = 99                    # a scrape's requests: uniform on 0..N_MAX


def for_run(run, n_blocks: int) -> "HistogramFleet":
    """The fleet of a run's configuration (at --rehearse sizes where the
    file gives them), anchored at the wall clock."""
    cfg = run.config
    return HistogramFleet(
        dict(cfg, jobs=run.param(cfg, "jobs"),
             instances_per_job=run.param(cfg, "instances_per_job")),
        run.seed, int(time.time()), n_blocks)


class HistogramFleet(Fleet):
    def __init__(self, cfg: dict, seed: int, now_s: int, n_blocks: int):
        self.cfg, self.seed, self.n_blocks = cfg, int(seed), n_blocks
        self.metric = cfg["metric"]
        self.jobs, self.instances = cfg["jobs"], cfg["instances_per_job"]
        self.zones = cfg["zones"]
        if tuple(cfg["le"]) != LE:
            raise ValueError("the deployment's le values are DefBuckets'")
        if self.instances % RUN:
            raise ValueError(f"instances_per_job must be a multiple of {RUN}")
        self.per_job = self.instances * PER_INSTANCE
        self.n_series = self.jobs * self.per_job
        self.cadence_s, self.block_s = cfg["cadence_s"], cfg["block_s"]
        self.per_block = self.block_s // self.cadence_s
        # newest block the service's own tick can seal right now
        self.seal_end = ((now_s - cfg["buffer_past_s"])
                         // self.block_s) * self.block_s
        self.t0 = self.seal_end - n_blocks * self.block_s
        self._labels: dict[int, bytes] = {}
        # [jobs, instances]: an instance's place in its job's order of
        # latency, and the median of its requests' durations
        self.rank = np.stack([
            np.argsort(np.random.default_rng([self.seed, j])
                       .permutation(self.instances))
            for j in range(self.jobs)])
        self.median = MEDIAN_LO * (MEDIAN_HI / MEDIAN_LO) ** (
            self.rank / max(self.instances - 1, 1))
        # (job, run, block) -> [RUN, 14] counters at the block's end
        self._at_end: dict[tuple[int, int, int], np.ndarray] = {}

    def instance_name(self, inst: int) -> str:
        return f"inst-{inst:04d}"

    def labels(self, i: int) -> dict[bytes, bytes]:
        inst, which = divmod(i % self.per_job, PER_INSTANCE)
        suffix = ("_bucket" if which < len(LE)
                  else "_sum" if which == len(LE) else "_count")
        out = {b"__name__": (self.metric + suffix).encode(),
               b"job": self.job_name(i // self.per_job).encode(),
               b"zone": b"zone-%d" % (inst % self.zones),
               b"instance": self.instance_name(inst).encode()}
        if which < len(LE):
            out[b"le"] = LE[which].encode()
        return out

    def _increments(self, j: int, run: int, k: int) -> np.ndarray:
        """float64 [RUN, 14, per_block]: by how much each series of the
        run's instances grows at each scrape of block k."""
        rng = np.random.default_rng([self.seed, j, run, k])
        shape = (RUN, self.per_block)
        n = rng.integers(0, N_MAX + 1, size=shape)
        z = rng.standard_normal(shape + (N_MAX,))
        median = self.median[j, run * RUN:(run + 1) * RUN]
        d = median[:, None, None] * np.exp(SIGMA * z)
        served = np.arange(N_MAX) < n[..., None]
        # the first bound a duration is <= of; len(BOUNDS) is +Inf, one
        # more holds the draws no request used
        slot = np.where(served, np.searchsorted(BOUNDS, d, side="left"),
                        len(LE))
        cell = np.arange(n.size).reshape(shape + (1,)) * (len(LE) + 1)
        hist = np.bincount((cell + slot).ravel(),
                           minlength=n.size * (len(LE) + 1))
        buckets = np.cumsum(hist.reshape(shape + (len(LE) + 1,))
                            [..., :len(LE)], axis=-1)
        inc = np.empty((RUN, PER_INSTANCE, self.per_block))
        inc[:, :len(LE)] = buckets.transpose(0, 2, 1)
        inc[:, len(LE)] = np.where(served, d, 0.0).sum(axis=-1)
        inc[:, len(LE) + 1] = n
        return inc

    def _run_values(self, j: int, run: int, k: int) -> np.ndarray:
        """float64 [RUN, 14, per_block]: the counters of one run of
        instances over block k."""
        if k == 0:
            start = np.zeros((RUN, PER_INSTANCE))
        else:
            start = self._at_end.get((j, run, k - 1))
            if start is None:
                start = self._run_values(j, run, k - 1)[..., -1]
        values = start[..., None] + np.cumsum(self._increments(j, run, k),
                                              axis=-1)
        self._at_end[j, run, k] = values[..., -1]
        return values

    def block_values(self, lo: int, hi: int, k: int) -> np.ndarray:
        """float64 [hi - lo, per_block]; lo and hi multiples of RUN
        instances' series."""
        width, per_job = RUN * PER_INSTANCE, self.instances // RUN
        return np.concatenate([
            self._run_values(r // per_job, r % per_job, k)
            .reshape(width, self.per_block)
            for r in range(lo // width, hi // width)])

    def job_arrays(self, j: int, blocks=None):
        """(ts_s int64 [T], values float64 [per_job, T]) of one job over
        `blocks` (default all): what the reference computes from."""
        blocks = range(self.n_blocks) if blocks is None else blocks
        lo = j * self.per_job
        ts = np.concatenate([self.block_ts(k) for k in blocks])
        vs = np.concatenate([self.block_values(lo, lo + self.per_job, k)
                             for k in blocks], axis=1)
        return ts, vs

    def job_histograms(self, j: int):
        """One job's series by what they are: (ts_s [T], buckets
        [instances, 12, T] in the order of LE, sums [instances, T],
        counts [instances, T])."""
        ts, vs = self.job_arrays(j)
        vs = vs.reshape(self.instances, PER_INSTANCE, len(ts))
        return ts, vs[:, :len(LE)], vs[:, len(LE)], vs[:, len(LE) + 1]

    def block_requests(self, k: int):
        """Series ranges of block k's write requests."""
        width = RUN * PER_INSTANCE
        return [(lo, lo + width) for lo in range(0, self.n_series, width)]
