"""The plain references of the chip benchmark agree with the program at a
small size on the CPU, in float32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny
from benchmarks.chip import generate, serving, weights
from benchmarks.chip.reference import calibration as ref_calib
from benchmarks.chip.reference import transformer as ref
from benchmarks.chip import loader

calib_cell = loader.kind("calib")


def _conf(name):
    conf = loader.config(name)
    conf.update(model=dict(chipbench_tiny.TINY), init=chipbench_tiny.INIT)
    return conf


@pytest.mark.parametrize("name", ["smollm_135m"])
def test_reference_logits_match_the_program(name):
    """Every position's logits: the program's verify pass over a cache
    (the serving path's row-offset attention) against the plain forward."""
    from repro.models.transformer import LM
    conf = _conf(name)
    model = LM(serving.model_config(conf))
    params = weights.make(model, generate.seed31(3), jnp.float32,
                          conf["init"])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                                conf["model"]["vocab_size"])
    cache = model.init_cache(2, 48, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, _ = model.verify_chunk(
            params, tokens, cache, jnp.zeros((2,), jnp.int32),
            jnp.full((2,), 40, jnp.int32), compute_dtype=jnp.float32)
    want = ref.logits(serving.arch(conf), params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_fp8_control_differs_from_the_reference():
    conf = _conf("smollm_135m")
    from repro.models.transformer import LM
    model = LM(serving.model_config(conf))
    params = weights.make(model, generate.seed31(4), jnp.bfloat16,
                          conf["init"])
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 32), 0, 256)
    a = ref.logits(serving.arch(conf), params, tokens)
    b = ref.logits(serving.arch(conf), params, tokens, quant="fp8")
    gap = float(jnp.max(jnp.abs(a - b)))
    assert 1e-3 < gap < 10.0


def test_reference_grams_match_the_programs_r_factors():
    """RᵀR of each target linear after one batch equals the reference Gram
    of its input."""
    from repro.core.calibrate import Calibrator
    from repro.models.transformer import LM
    conf = _conf("smollm_135m")
    model = LM(serving.model_config(conf))
    params = weights.make(model, generate.seed31(5), jnp.float32,
                          conf["init"])
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, 256)
    cal = Calibrator()
    with jax.default_matmul_precision("highest"):
        model.capture_forward(params, {"tokens": tokens}, cal)
    grams = ref_calib.grams(serving.arch(conf), params, [tokens])
    assert len(cal.streams) == 7 * conf["model"]["n_layers"]
    zero = {p: jnp.zeros_like(s.r) for p, s in cal.streams.items()}
    after = {p: s.r for p, s in cal.streams.items()}
    assert calib_cell.fold_gap(zero, after, grams) < 1e-5
    # a wrong layer's Gram does not pass
    swapped = grams[::-1]
    assert calib_cell.fold_gap(zero, after, swapped) > 1e-2


def test_reference_grams_sum_over_batches_and_control_fold_moves():
    """The Gram of two batches is the sum of theirs; the control's plain QR
    fold of the float32 activations gains that Gram, and its fold of the
    three-pass bfloat16 activations gains a different one."""
    conf = _conf("smollm_135m")
    from repro.models.transformer import LM
    model = LM(serving.model_config(conf))
    params = weights.make(model, generate.seed31(6), jnp.float32,
                          conf["init"])
    a = ref_calib.grams(serving.arch(conf), params,
                        [jax.random.randint(jax.random.PRNGKey(k), (2, 24),
                                            0, 256) for k in (4, 5)])
    b = [ref_calib.grams(serving.arch(conf), params,
                         [jax.random.randint(jax.random.PRNGKey(k), (2, 24),
                                             0, 256)]) for k in (4, 5)]
    for i, layer in enumerate(a):
        for tap, g in layer.items():
            np.testing.assert_allclose(np.asarray(g), np.asarray(
                b[0][i][tap] + b[1][i][tap]), rtol=1e-5, atol=1e-5)
    d = conf["model"]["d_model"]
    paths = [f"blocks/{i}/sub0/mixer/wq" for i in range(2)]
    r0 = {p: jnp.zeros((d, d), jnp.float32) for p in paths}
    toks = [jax.random.randint(jax.random.PRNGKey(4), (2, 24), 0, 256)]
    g = ref_calib.grams(serving.arch(conf), params, toks)
    exact = ref_calib.folds(serving.arch(conf), params, toks, r0, None, 16)
    low = ref_calib.folds(serving.arch(conf), params, toks, r0, "bf16x3", 16)
    assert calib_cell.fold_gap(r0, exact, g) < 1e-5
    assert calib_cell.fold_gap(r0, low, g) > 1e-5
