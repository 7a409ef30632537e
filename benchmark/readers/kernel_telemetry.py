"""From the kernel telemetry's delta over the window (run.kernels: per
kernel name invocations, compiles, compile_s, execute_s, bytes, ...).
`execute_s` is the host's clock around a call fenced by
block_until_ready: under concurrent callers it includes the wait for
the device.

args: kernel, field, per (optional: divide by this field), scale.
"""

from __future__ import annotations


def read(run, args: dict) -> float | None:
    st = run.kernels.get(args["kernel"])
    if not st or not st.get("invocations"):
        return None
    value = st[args["field"]]
    if "per" in args:
        if not st[args["per"]]:
            return None
        value /= st[args["per"]]
    return value * args.get("scale", 1.0)
