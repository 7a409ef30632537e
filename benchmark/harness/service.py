"""Start the system under test and drive its seal, as chip_smoke.py
does (copied from its phase_start / phase_seal, PR 22)."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


class NoChip(RuntimeError):
    pass


def device_info(rehearse: bool, chips: int) -> tuple[dict, dict | None]:
    """({platform, kind, count}, the kind's peaks).  Raises NoChip on
    anything but the chips the cell asks for, unless rehearsing."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    peaks = json.loads((pathlib.Path(__file__).parent / "peaks.json")
                       .read_text())["kinds"].get(device["kind"])
    if rehearse:
        return device, peaks
    if device["platform"] != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0].platform is "
                     f"{device['platform']!r}")
    if peaks is None:
        raise NoChip(f"device_kind {device['kind']!r} is not in peaks.json")
    if device["count"] < chips:
        raise NoChip(f"cell needs {chips} chips, JAX sees {device['count']}")
    return device, peaks


def start(out_dir: pathlib.Path, config_file: str, overlay: dict):
    """-> (CoordinatorService started on `config_file` of the repo with
    `overlay` on top, seconds spent building the native libraries)."""
    from m3_tpu.utils import native

    t0 = time.perf_counter()
    for src in sorted((ROOT / "native").glob("*.cc")):
        native.load(src.stem)       # built once per checkout, -O2
    native_s = time.perf_counter() - t0

    data_dir = out_dir / "data"
    shutil.rmtree(data_dir, ignore_errors=True)
    os.environ["M3TPU_DATA"] = str(data_dir)
    os.environ["M3TPU_COORDINATOR_PORT"] = "0"
    os.environ["M3TPU_CARBON_PORT"] = "-1"
    # the background mediator is off and the harness drives the same
    # Database.tick()/flush() it would: a mediator tick in the middle
    # of a backfill seals half-written past blocks (chip_smoke.py)
    overlay_path = out_dir / "overlay.yml"
    overlay_path.write_text("coordinator:\n" + "".join(
        f"  {k}: {json.dumps(v)}\n" for k, v in overlay.items()))

    from m3_tpu.services.config import load_coordinator_config
    from m3_tpu.services.run import CoordinatorService
    cfg = load_coordinator_config(str(ROOT / config_file),
                                  str(overlay_path))
    return CoordinatorService(cfg).start(), native_s


def seal(svc) -> dict:
    """The mediator's tick + flush, timed.  -> tick_s, flush_s, the
    shard-blocks sealed and flushed, block starts flushed (seconds)."""
    ns = svc.cfg.unagg_namespace
    t0 = time.perf_counter()
    sealed = svc.db.tick()
    t1 = time.perf_counter()
    flushed = svc.db.flush()
    t2 = time.perf_counter()
    return {"tick_s": t1 - t0, "flush_s": t2 - t1, "seal_s": t2 - t0,
            "shard_blocks_sealed": len(sealed.get(ns, [])),
            "shard_blocks_flushed": len(flushed.get(ns, [])),
            "block_starts": sorted({b // 10**9
                                    for b in flushed.get(ns, [])})}


def memory_peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
