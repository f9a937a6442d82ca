"""The capture forward's dense attention runs as one compiled program per
shape and matmul precision.

Calibration's capture forward is eager, so an unjitted attention core
would run each pass over the float32 scores as its own program. These
tests pin the compiled unit down: one cache entry per shape (so the
benchmark's window compiles nothing), and no program shared between the
``"highest"`` scope of calibration and the default precision."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.calibrate import Calibrator
from repro.core.precision import highest_precision
from repro.models import build_model
from repro.models.attention import _dense_sdpa
from repro.models.common import CPU_CTX


@pytest.fixture(scope="module")
def smollm():
    cfg = get_smoke_config("smollm_135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _batch(cfg, seed, t):
    rng = np.random.RandomState(seed)
    return {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab_size, (2, t)).astype(np.int32))}


def test_capture_compiles_dense_attention_once_per_shape(smollm):
    cfg, model, params = smollm
    t = 32
    assert cfg.n_layers > 1 and cfg.n_kv_heads < cfg.n_heads
    assert t <= CPU_CTX.dense_attn_max_seq
    _dense_sdpa.clear_cache()
    cal = Calibrator()
    highest_precision(model.capture_forward)(params, _batch(cfg, 0, t), cal)
    assert _dense_sdpa._cache_size() == 1
    highest_precision(model.capture_forward)(params, _batch(cfg, 1, t), cal)
    assert _dense_sdpa._cache_size() == 1
    assert all(s.tokens_seen == 4 * t for s in cal.streams.values())


def _dot_precisions(text):
    return re.findall(r"stablehlo\.dot_general.*?precision = \[(\w+), (\w+)\]",
                      text)


def test_dense_attention_keeps_the_callers_precision():
    q = jnp.ones((1, 16, 4, 8), jnp.float32)
    kv = jnp.ones((1, 16, 2, 8), jnp.float32)
    kw = dict(q_offset=0, causal=True, window=0, cap=0.0, scale=8 ** -0.5)

    def lowered():
        return _dense_sdpa.lower(q, kv, kv, **kw).as_text()

    high = highest_precision(lowered)()
    assert high.count("stablehlo.dot_general") == 2
    assert _dot_precisions(high) == [("HIGHEST", "HIGHEST")] * 2
    default = lowered()
    assert default.count("stablehlo.dot_general") == 2
    assert "HIGHEST" not in default

    # called eagerly, the two scopes take separate compiled programs
    _dense_sdpa.clear_cache()
    _dense_sdpa(q, kv, kv, **kw)
    highest_precision(_dense_sdpa)(q, kv, kv, **kw)
    assert _dense_sdpa._cache_size() == 2
