"""From the reduced profiler trace (run.trace_summary, see
harness/trace_reduce.py): the device time of one program.

args: program (the name of the jitted program, e.g.
jit_device_grouped_pipeline) and `what`:
  ms_per_call   device milliseconds of the median run of the program (the
                profiler cuts the runs in flight when the trace starts
                and stops, so a mean over the events reads low)
  roofline_pct  bytes the call moves (harness/opmodel.py, from the
                shapes the kernel telemetry sums for `kernel`) over the
                chip's HBM bytes/s, over the median run's device time
"""

from __future__ import annotations

from harness import opmodel


def read(run, args: dict) -> float | None:
    ts = run.trace_summary
    if not ts:
        return None
    prog = ts["programs"].get(args["program"])
    if not prog or not prog["calls"] or prog["device_s"] <= 0:
        return None
    what = args["what"]
    if what == "ms_per_call":
        return 1000.0 * prog["median_s"]
    if what == "roofline_pct":
        if run.peaks is None:
            return None
        nbytes = opmodel.io_bytes_per_call(
            run.kernels.get(args["kernel"], {}))
        if nbytes is None:
            return None
        pct, _bound = opmodel.roofline_pct(nbytes, 0.0, prog["median_s"],
                                           run.peaks)
        return pct
    raise ValueError(f"unknown what {what!r}")
