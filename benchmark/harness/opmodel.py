"""Operations and bytes of a device program, from its shapes.

The served programs here (M3TSZ decode + merge + rate + group sum) do
no matrix multiplication: their floor on
the chip is the time to move their arguments in and their result out of
HBM once.  The byte counts come from the shapes of the call's arguments
and result, as the kernel telemetry sums them per call (`bytes`,
`result_bytes`: array sizes, not timings).
"""

from __future__ import annotations


def io_bytes_per_call(kernel_stats: dict) -> float | None:
    calls = kernel_stats.get("invocations", 0)
    if not calls:
        return None
    return (kernel_stats["bytes"] + kernel_stats["result_bytes"]) / calls


def roofline_pct(bytes_per_call: float, flops_per_call: float,
                 device_s_per_call: float, peaks: dict) -> tuple[float, str]:
    """(share of the roofline in %, which bound sets the floor)."""
    t_mem = bytes_per_call / peaks["hbm_bytes_per_s"]
    t_flop = flops_per_call / peaks["bf16_flops_per_s"]
    bound = "memory-bound" if t_mem >= t_flop else "compute-bound"
    return 100.0 * max(t_mem, t_flop) / device_s_per_call, bound
