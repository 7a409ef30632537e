"""Test env: two lanes.

Default lane — run everything on a virtual 8-device CPU mesh.  Must run
before jax initializes a backend, hence env vars at import time.
Multi-chip sharding is validated on this virtual mesh (real multi-chip
hardware is exercised by the driver's dryrun_multichip hook).

TPU lane — ``M3_TPU_LANE=1 pytest tests/tpu -q`` leaves the platform
alone so the real accelerator backend is exercised.  This lane exists
because TPU-only lowering failures (e.g. missing X64 rewrites for 64-bit
bitcasts) are invisible on the CPU backend — exactly the class of escape
that crashed BENCH_r02's AOT compile.  Tests under ``tests/tpu`` are
marked ``tpu`` and skipped in the default lane; everything else is
skipped in the TPU lane.
"""

import os

import pytest

# invariant breaches fail the suite loudly; production counts + logs
# (ref: x/instrument/invariant.go PANIC_ON_INVARIANT_VIOLATED)
os.environ.setdefault("M3_PANIC_ON_INVARIANT_VIOLATED", "1")

TPU_LANE = os.environ.get("M3_TPU_LANE") == "1"

if not TPU_LANE:
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402

if not TPU_LANE:
    # tests need the exact-f64 CPU backend (TPU float64 is emulated at
    # reduced precision) plus the 8 virtual devices requested above for
    # mesh coverage — also when a developer runs pytest without
    # JAX_PLATFORMS=cpu in the environment
    jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite's wall time is dominated by
# XLA compiles of the big kernels (tiles, read pipeline), which are
# identical run to run.  Placement rule + the M3_NO_COMPILE_CACHE
# opt-out live in the helper.
from m3_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: runs on the real accelerator backend (M3_TPU_LANE=1)"
    )
    config.addinivalue_line("markers", "slow: larger-scale smoke tests")


def pytest_collection_modifyitems(config, items):
    if TPU_LANE:
        skip = pytest.mark.skip(reason="CPU-lane test (unset M3_TPU_LANE)")
        for item in items:
            if "tpu" not in item.keywords:
                item.add_marker(skip)
    else:
        skip = pytest.mark.skip(reason="TPU-lane test (set M3_TPU_LANE=1)")
        for item in items:
            if "tpu" in item.keywords:
                item.add_marker(skip)
