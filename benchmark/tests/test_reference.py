"""The numpy reference agrees with the repo's host evaluator on a tiny
database (XLA:CPU): the reference is independent code, and this is the
one place where the two are set side by side."""

import json
import pathlib
import time

import numpy as np

from harness import reference, service
from harness.client import Client
from harness.fleet import Fleet

HERE = pathlib.Path(__file__).resolve().parent


def test_rate_sum_by_zone_agrees_with_host_tier(tmp_path):
    from m3_tpu.query.engine import Engine

    cfg = json.loads((HERE.parent / "configs" / "m3query-fanout.json")
                     .read_text())
    fleet = Fleet(dict(cfg, jobs=2, instances_per_job=50), 11,
                  int(time.time()), 2)
    svc, _ = service.start(tmp_path, cfg["service_config"],
                           cfg["service_overlay"])
    try:
        client = Client(svc.http_port)
        for k in range(2):
            for lo, hi in fleet.block_requests(k):
                client.remote_write(fleet.body(lo, hi, k)[0])
        client.close()
        service.seal(svc)
        host = Engine(svc.db, svc.cfg.unagg_namespace, device_serving=False)
        steps = np.arange(fleet.t0 + 600, fleet.seal_end - 59, 60)
        step_times, mat = host.query_range(
            'sum by (zone)(rate(http_requests_total{job="job-001"}[5m]))',
            int(steps[0]) * 10**9, int(steps[-1]) * 10**9, 60 * 10**9)
    finally:
        svc.stop()
    assert np.array_equal(np.asarray(step_times) // 10**9, steps)
    served = reference.drop_nan(steps, {
        (("zone", ls[b"zone"].decode()),): row
        for ls, row in zip(mat.labels, np.asarray(mat.values))})
    ts, vs = fleet.job_arrays(1)
    by_zone = reference.sum_by(np.arange(50) % 10,
                               reference.rate(ts, vs, steps, 300))
    want = reference.drop_nan(steps, {
        (("zone", f"zone-{z}"),): row for z, row in by_zone.items()})
    assert len(want) == 10
    # the program opens its window 1 ns early (consolidate._range_left),
    # which stretches the extrapolated interval by 1 ns in 300 s: 3.3e-12
    assert reference.max_rel_gap(served, want) < 1e-11


def test_rate_matches_a_hand_worked_window():
    # 4 samples 10 s apart in a 30 s window ending on the last one:
    # increase 30 over 30 s sampled, nothing to extrapolate at the end,
    # at the start the counter's zero lies 10 s back, under the 11 s
    # threshold: (30 + 10) s / 30 s * 30 / 30 s
    ts = np.array([100, 110, 120, 130])
    out = reference.rate(ts, np.array([[10.0, 20.0, 30.0, 40.0]]),
                         np.array([130]), 30)
    assert out.shape == (1, 1)
    assert abs(out[0, 0] - 30.0 * (30.0 / 30.0) / 30.0) < 1e-15
    # one sample in the window: no rate
    assert np.isnan(reference.rate(ts, np.array([[1.0, 2, 3, 4]]),
                                   np.array([100]), 5)[0, 0])
