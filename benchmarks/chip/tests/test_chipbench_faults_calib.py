"""``correct`` of the calibration cell comes out false when the fold is
broken underneath, and when its control (the reference at three bfloat16
passes, folded by a plain QR) stands in the program's place."""
import numpy as np

import chipbench_tiny
from benchmarks.chip import harness

CELL = "smollm_135m.calib_stream"
SEED = 2 ** 33 + 78


def _run(control=False, limits=None):
    over = dict(chipbench_tiny.overrides(CELL), limits=limits or {})
    return harness.run_cell(CELL, SEED, 1.5, False, require_chip_=False,
                            overrides=over, control=control)


def test_sound_run_is_correct_and_control_reads_higher():
    """The program passes the cell's limit. At this size, two layers of
    width 64, its sound readings are ~6e-7 and the control's ~1e-5 (the
    real size reads both far higher), so the control is held to a limit
    set between them, ``chipbench_tiny.CALIB_LIMIT``."""
    r = _run()
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "compared"
    assert set(r["metrics"]) == {"setup_s", "calib_tok_s"}
    c = _run(control=True, limits=chipbench_tiny.CALIB_LIMIT)
    assert not c["correct"], c["compared"]
    got = c["info"]["program_fold_gram_gap"]
    assert harness.ok(got, c["compared"]["fold_gram_gap"])
    assert c["compared"]["fold_gram_gap"]["value"] > 3 * got


def test_calib_fold_returns_state_unchanged(monkeypatch):
    from repro.core.tsqr import RStreamer
    update = RStreamer.update

    def stuck(self, chunk):
        if self._r is None:
            update(self, chunk)

    monkeypatch.setattr(RStreamer, "update", stuck)
    assert not _run()["correct"]


def test_calib_half_the_batch_left_out(monkeypatch):
    from repro.core.calibrate import Calibrator
    record = Calibrator.record

    def half(self, path, x):
        flat = np.asarray(x).reshape(-1, x.shape[-1])
        record(self, path, flat[: flat.shape[0] // 2])

    monkeypatch.setattr(Calibrator, "record", half)
    assert not _run()["correct"]
