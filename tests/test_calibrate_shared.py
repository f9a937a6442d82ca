"""One fold per calibration input: linears that read the same activation
(q/k/v, gate/up) take the R their first reader folded, and every path's R
and token count come out bitwise as folding each record on its own gives.

The no-sharing baseline is a ``Calibrator`` whose ``record`` hands on a
fresh copy of every input, so no two records ever see the same array."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import tsqr
from repro.core.calibrate import Calibrator
from repro.models import build_model
from repro.obs import trace

LINEARS_PER_LAYER = 7        # wq wk wv wo gate up down
DISTINCT_PER_LAYER = 4       # attention in, wo in, MLP in, down in
ROWS = 2 * 12                # one batch: 2 sequences of 12 tokens
FOLD_ROWS = 16               # so every record folds twice (16 + 8)


class _Unshared(Calibrator):
    def record(self, path, x):
        super().record(path, jnp.array(x, copy=True))


@pytest.fixture(scope="module")
def smollm():
    cfg = get_smoke_config("smollm_135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(autouse=True)
def _tracing_off():
    trace.disable()
    yield
    trace.disable()


def _batch(cfg, seed):
    rng = np.random.RandomState(seed)
    return {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab_size, (2, ROWS // 2)).astype(np.int32))}


def _calibrate(model, params, cfg, cal, batches=2):
    for b in range(batches):
        model.capture_forward(params, _batch(cfg, b), cal)
    return cal


def _layer(path):
    return path.split("/")[1]


@pytest.mark.parametrize("collect_gram", [False, True])
def test_every_r_and_gram_equal_the_unshared_baseline(smollm, collect_gram):
    cfg, model, params = smollm
    kw = dict(max_tokens_per_record=FOLD_ROWS, collect_gram=collect_gram)
    cal = _calibrate(model, params, cfg, Calibrator(**kw))
    base = _calibrate(model, params, cfg, _Unshared(**kw))
    assert list(cal.streams) == list(base.streams)
    for path, s in cal.streams.items():
        assert np.array_equal(np.asarray(s.r), np.asarray(base.streams[path].r)), path
        assert s.tokens_seen == base.streams[path].tokens_seen == 2 * ROWS
    assert cal.tokens_seen() == base.tokens_seen()
    if collect_gram:
        assert list(cal.grams) == list(base.grams) == list(cal.streams)
        for path, g in cal.grams.items():
            assert np.array_equal(np.asarray(g), np.asarray(base.grams[path])), path
    else:
        assert not cal.grams


def test_one_stream_per_linear_one_fold_per_distinct_input(smollm):
    cfg, model, params = smollm
    cal = Calibrator(max_tokens_per_record=FOLD_ROWS)
    trace.enable()
    model.capture_forward(params, _batch(cfg, 0), cal)
    spans = trace.spans(float("-inf"), float("inf"))
    assert len(cal.streams) == LINEARS_PER_LAYER * cfg.n_layers
    by_layer = {}
    for path, s in cal.streams.items():
        by_layer.setdefault(_layer(path), set()).add(id(s.r))
    assert len(by_layer) == cfg.n_layers
    assert all(len(ids) == DISTINCT_PER_LAYER for ids in by_layer.values())
    recs = [s for s in spans if s.name == "calib.record"]
    folds = [s for s in spans if s.name == "calib.fold"]
    assert len(recs) == len(cal.streams)
    assert sum(not r.args["shared"] for r in recs) \
        == DISTINCT_PER_LAYER * cfg.n_layers
    assert len(folds) == 2 * DISTINCT_PER_LAYER * cfg.n_layers
    shared = {r.args["path"].rsplit("/", 1)[1]
              for r in recs if r.args["shared"]}
    assert shared == {"wk", "wv", "up"}


def _rows(seed, k=24, n=8):
    return jnp.asarray(np.random.RandomState(seed).randn(k, n), jnp.float32)


def _close(r, rows):
    np.testing.assert_allclose(np.asarray(r),
                               np.asarray(tsqr.qr_r(jnp.concatenate(rows))),
                               rtol=1e-5, atol=1e-5)


def test_a_path_folding_its_input_twice_does_not_hand_it_on():
    x = _rows(0)
    cal = Calibrator()
    cal.record("a", x)
    cal.record("a", x)
    cal.record("b", x)
    assert cal.tokens_seen() == {"a": 48, "b": 24}
    _close(cal.streams["a"].r, [x, x])
    _close(cal.streams["b"].r, [x])
    assert cal.streams["b"].r is not cal.streams["a"].r


def test_a_path_whose_input_stops_being_shared_folds_its_own_rows():
    x, y, z = _rows(0), _rows(1), _rows(2)
    cal = Calibrator()
    cal.record("a", x)
    cal.record("b", x)
    assert cal.streams["b"].r is cal.streams["a"].r
    cal.record("a", y)
    cal.record("b", z)
    _close(cal.streams["a"].r, [x, y])
    _close(cal.streams["b"].r, [x, z])
    cal.record("c", z)                  # a new stream: z from nothing
    _close(cal.streams["c"].r, [z])
    assert cal.tokens_seen() == {"a": 48, "b": 48, "c": 24}


def test_reset_forgets_the_last_fold():
    x = _rows(0)
    cal = Calibrator()
    cal.record("a", x)
    cal.reset()
    assert not cal.streams
    trace.enable()
    cal.record("b", x)
    (rec,) = [s for s in trace.spans(float("-inf"), float("inf"))
              if s.name == "calib.record"]
    assert rec.args["shared"] is False
    _close(cal.streams["b"].r, [x])
