#!/usr/bin/env python3
"""The controls of dash-live.  Float32, the precision below the float64
that the deployment states, planted in the served program
(test_control_dash.planted_float32), must fail `panel_max_rel_gap`;
and a run whose open rows are dropped on their way to the program (the
panel then answers from the sealed blocks alone) must not be correct.

The scrape's three checks (harness/scrapegen.account) have a control
each, planted from outside the harness.  The database lock held inside
the window for longer than `write_connections` requests take to fall
due (`hold_database_lock`) must fail `scrapes_held_share`: the node
held the loop closed.  The generator's document without the rows of
one tick (`a_tick_unsent`) must fail `scrapes_missing`.  And a run
whose whole process group is stopped and continued inside the window
(`run_frozen`: SIGSTOP and SIGCONT to the group of a run started as a
child) must stay correct, its stall in the generator's witness: the
host's scheduler is reported, not judged.

    python benchmark/tests/test_control_live.py --planted --seeds 1 2 3
    python benchmark/tests/test_control_live.py --dropped --seeds 4
    python benchmark/tests/test_control_live.py --held 8 --at 20 --seconds 50 --seeds 5
    python benchmark/tests/test_control_live.py --unsent --seconds 50 --seeds 6
    python benchmark/tests/test_control_live.py --frozen 1.7 --at 20 --seconds 50 --seeds 7

on the chip, at the cell's own size, prints each run's lines; `--tree
<directory>` runs another checkout's benchmark and program (the
parent's, unpacked beside this one) under the same plant.  The pytest
cases hold them at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import signal
import subprocess
import sys
import threading
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

from test_control_dash import planted_float32  # noqa: E402


def drop_open_rows(engine_module):
    """-> a `_pack_array_rows` for m3_tpu.query.engine.Engine that hands
    the program no open rows."""
    def dropped(pk, parts, bucket):
        pk["open"] = None

    return staticmethod(dropped)


def run_log(tree: pathlib.Path, seed: int) -> pathlib.Path:
    """Where run.py of `tree` logs an untraced dash-live run."""
    return (tree / "chiprun_out" / "benchmark"
            / f"dash-live.seed{seed}.trace0.jsonl")


def hold_database_lock(real, log: pathlib.Path, at_s: float, hold_s: float):
    """-> a `start` for m3_tpu.storage.database.Mediator: the `real`
    one, and a thread that waits until the run's log shows the window
    open, `at_s` more, and then holds the database lock for `hold_s`."""
    def start(self):
        def hold():
            while '"window_opens"' not in log.read_text():
                time.sleep(0.05)
            time.sleep(at_s)
            with self.db._lock:
                time.sleep(hold_s)

        threading.Thread(target=hold, daemon=True).start()
        return real(self)

    return start


def a_tick_unsent(loadgen_live_module):
    """-> a `result` for harness.loadgen_live.Child under which the
    scrape generator's document lacks every row of the tick it sent
    last: what a generator that skipped that tick would say."""
    real = loadgen_live_module.Child.result

    def result(self):
        out = real(self)
        if "to_perf" in out:                    # the writer's
            # a row is [job, tick, due, ...]: the tick of the last due
            tick = max(out["requests"], key=lambda r: r[2])[1]
            out["requests"] = [r for r in out["requests"] if r[1] != tick]
        return out

    return result


def run_frozen(tree: pathlib.Path, seed: int, seconds: float, at_s: float,
               for_s: float, more=(), env=None):
    """One dash-live run of `tree` as a child in a process group of its
    own, the whole group stopped `at_s` into the window for `for_s`
    seconds.  -> (the result line, {phase: its last line})."""
    proc = subprocess.Popen(
        [sys.executable, str(tree / "benchmark" / "run.py"), "--workload",
         "dash-live", "--seed", str(seed), "--seconds", str(seconds),
         "--trace", "0", *more],
        stdout=subprocess.PIPE, text=True, start_new_session=True, env=env)

    def freeze():
        time.sleep(at_s)
        os.killpg(proc.pid, signal.SIGSTOP)
        time.sleep(for_s)
        os.killpg(proc.pid, signal.SIGCONT)

    phases, last = {}, ""
    try:
        for line in proc.stdout:
            sys.stderr.write(line)
            if line.startswith('{"phase"'):
                doc = json.loads(line)
                phases[doc["phase"]] = doc
                if doc["phase"] == "window_opens":
                    threading.Thread(target=freeze, daemon=True).start()
            last = line
        assert proc.wait() == 0
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
        proc.stdout.close()
    return json.loads(last), phases


def test_served_program_in_float32_is_not_correct(run_cell, monkeypatch):
    from m3_tpu.models import query_pipeline

    monkeypatch.setattr(query_pipeline, "_grouped_reduce",
                        planted_float32(query_pipeline))
    query_pipeline.device_grouped_pipeline.clear_cache()
    try:
        line = run_cell("dash-live", 41)
        assert line["correct"] is False
        assert not line["checks"]["panel_max_rel_gap"]["ok"]
    finally:
        query_pipeline.device_grouped_pipeline.clear_cache()


def test_sound_then_open_rows_dropped(run_cell, monkeypatch):
    from m3_tpu.query import engine

    assert run_cell("dash-live", 42)["correct"] is True
    monkeypatch.setattr(engine.Engine, "_pack_array_rows",
                        drop_open_rows(engine))
    line = run_cell("dash-live", 42)
    assert line["correct"] is False
    assert not line["checks"]["panel_max_rel_gap"]["ok"]


def test_database_lock_held_fills_the_connections(run_cell, monkeypatch):
    # at rehearsal size a request falls due every 5 s on one
    # connection: a hold of 11 s keeps one waiting behind another
    from m3_tpu.storage import database

    monkeypatch.setattr(database.Mediator, "start", hold_database_lock(
        database.Mediator.start, run_log(HERE.parent.parent, 44), 0.5, 11.0))
    line = run_cell("dash-live", 44, seconds=13.0)
    assert line["correct"] is False
    checks = line["checks"]
    assert not checks["scrapes_held_share"]["ok"]
    assert checks["scrapes_missing"]["ok"]
    assert checks["failed_writes"]["ok"] and checks["failed_requests"]["ok"]
    assert checks["samples_acked_minus_read_back"]["ok"]


def test_a_tick_the_generator_did_not_send(run_cell, monkeypatch):
    from harness import loadgen_live

    monkeypatch.setattr(loadgen_live.Child, "result",
                        a_tick_unsent(loadgen_live))
    line = run_cell("dash-live", 45, seconds=12.0)
    assert line["correct"] is False
    assert line["checks"]["scrapes_missing"]["value"] >= 1
    assert line["checks"]["scrapes_held_share"]["ok"]


def test_stopped_process_group_is_the_hosts_and_still_correct():
    line, phases = run_frozen(
        HERE.parent.parent, 46, 12.0, 1.0, 0.7, more=["--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert line["correct"] is True
    done = phases["window_done"]
    assert 600 <= done["host_stall_max_ms"] <= 1500
    assert done["scrapes_held"] == 0
    assert done["scrapes_late"] == done["scrapes_woke_late"]


def test_traced_run_reports_its_layers(run_cell):
    line = run_cell("dash-live", 43, trace=1)
    assert line["correct"] is True
    assert {"device_served_pct.live", "fetch_ms.live", "open_read_ms.live",
            "open_rows_pct.live", "pack_ms.live", "h2d_ms.live",
            "device_ms.live", "device_queue_depth.live",
            "write_ack_ms.live"} <= set(line["metrics"])
    assert line["metrics"]["device_served_pct.live"]["value"] == 100.0
    assert abs(line["metrics"]["open_rows_pct.live"]["value"]
               - 100.0 / 3) < 1e-9
    assert line["device"]["busy_s"] > 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--planted", action="store_true")
    ap.add_argument("--dropped", action="store_true")
    ap.add_argument("--unsent", action="store_true")
    ap.add_argument("--held", type=float, metavar="SECONDS")
    ap.add_argument("--frozen", type=float, metavar="SECONDS")
    ap.add_argument("--at", type=float, default=20.0,
                    help="seconds into the window of --held and --frozen")
    ap.add_argument("--tree", type=pathlib.Path, default=HERE.parent.parent)
    ap.add_argument("--seconds", default="8")
    ap.add_argument("--rehearse", action="store_true",
                    help="on another platform than the TPU: never a number")
    args = ap.parse_args()
    tree = args.tree.resolve()
    more = ["--rehearse"] if args.rehearse else []
    if args.frozen:
        for seed in args.seeds:
            line, _ = run_frozen(tree, seed, float(args.seconds), args.at,
                                 args.frozen, more)
            print(json.dumps(line), flush=True)
        sys.exit(0)
    sys.path[:0] = [str(tree / "benchmark"), str(tree)]
    for name in [m for m in sys.modules if m.split(".")[0] == "harness"]:
        del sys.modules[name]       # the tree's own, not this file's
    import run as bench_run
    if args.planted:
        from m3_tpu.models import query_pipeline
        query_pipeline._grouped_reduce = planted_float32(query_pipeline)
    if args.dropped:
        from m3_tpu.query import engine
        engine.Engine._pack_array_rows = drop_open_rows(engine)
    if args.unsent:
        from harness import loadgen_live
        loadgen_live.Child.result = a_tick_unsent(loadgen_live)
    if args.held:
        from m3_tpu.storage import database
        real_start = database.Mediator.start
    for seed in args.seeds:
        if args.held:
            database.Mediator.start = hold_database_lock(
                real_start, run_log(tree, seed), args.at, args.held)
        sys.argv = ["run.py", "--workload", "dash-live", "--seed", str(seed),
                    "--seconds", args.seconds, "--trace", "0", *more]
        bench_run.main()
