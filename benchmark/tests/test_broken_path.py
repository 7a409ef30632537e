"""With the timed path broken underneath, a run comes out not correct.

Each case skips the harness's look for a chip (--rehearse) and drives
the rest of a run through run.main(): set-up, window, check, result
line.  The break is made in the program, where the answer is produced.
"""

import numpy as np


def test_dash_sealed_sound_then_altered_device_answer(run_cell, monkeypatch):
    from m3_tpu.models import query_pipeline

    assert run_cell("dash-sealed", 21)["correct"] is True
    real = query_pipeline.device_grouped_pipeline

    def altered(*args, **kwargs):
        out, err = real(*args, **kwargs)
        return out * (1.0 + 1e-7), err        # float32-sized error

    monkeypatch.setattr(query_pipeline, "device_grouped_pipeline", altered)
    assert run_cell("dash-sealed", 21)["correct"] is False


def test_dash_sealed_an_acknowledged_sample_lost_in_the_seal(run_cell,
                                                             monkeypatch):
    from m3_tpu.storage import buffer

    real = buffer.BlockBuffer.consolidated

    def lossy(self):
        lanes, times, values = real(self)
        return lanes[:-1], times[:-1], values[:-1]

    monkeypatch.setattr(buffer.BlockBuffer, "consolidated", lossy)
    assert run_cell("dash-sealed", 22)["correct"] is False


def test_dash_sealed_traced_run_reports_its_layers(run_cell):
    line = run_cell("dash-sealed", 23, trace=1)
    assert line["correct"] is True
    assert {"reply_ms.dash", "fetch_ms.dash", "device_ms.dash",
            "device_served_pct.dash"} <= set(line["metrics"])
    assert np.isfinite(line["metrics"]["device_ms.dash"]["value"])
    assert line["device"]["busy_s"] > 0
