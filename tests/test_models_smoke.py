"""Per-architecture smoke tests: reduced config, one forward/train step on CPU.

Asserts output shapes and absence of NaNs — per the assignment, the FULL
configs are exercised only via the dry-run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import build_model


def _batch_for(cfg, b=2, t=32, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    batch = {"tokens": jax.random.randint(ks[0], (b, t), 0, cfg.vocab_size)}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            ks[1], (b, cfg.n_audio_frames, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        batch["vision_embeds"] = jax.random.normal(
            ks[2], (b, cfg.n_vision_tokens, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_loss(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg)
    loss, metrics = jax.jit(lambda p, b: model.loss(p, b))(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: loss={loss}"
    assert float(metrics["ce"]) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_no_nans(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    batch = _batch_for(cfg, key=1)

    @jax.jit
    def step(p, b):
        def lf(p):
            return model.loss(p, b)[0]
        loss, grads = jax.value_and_grad(lf)(p)
        return loss, grads

    loss, grads = step(params, batch)
    assert np.isfinite(float(loss))
    leaves = jax.tree.leaves(grads)
    assert leaves, "no grads"
    for g in leaves:
        assert np.all(np.isfinite(np.asarray(g))), f"{arch}: non-finite grad"


@pytest.mark.parametrize("arch", ["smollm_135m", "gemma2_27b", "jamba_v0_1_52b",
                                  "xlstm_1_3b", "deepseek_v2_lite_16b",
                                  "whisper_base", "qwen2_vl_2b"])
def test_decode_matches_forward(arch):
    """prefill + decode_step must agree with the full forward pass."""
    cfg = get_smoke_config(arch)
    if cfg.family == "vlm":
        pytest.skip("vlm decode parity covered via serve tests (vision prefix)")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    b, t = 2, 16
    batch = _batch_for(cfg, b=b, t=t, key=2)
    tokens = batch["tokens"]
    # MoE dispatch is dropless: a token's expert output does not depend on
    # the other tokens, so prefill(15)+decode(1) equals prefill(16)
    from repro.models.common import ParallelCtx
    ctx = ParallelCtx()

    cache = model.init_cache(b, 64, dtype=jnp.float32)
    kw = {"frames": batch["frames"]} if cfg.family == "encdec" else {}
    logits_pre, cache = jax.jit(
        lambda p, tk, c, **k: model.prefill(p, tk, c, ctx=ctx,
                                            compute_dtype=jnp.float32, **k)
    )(params, tokens[:, :t - 1], cache, **kw)

    dec = jax.jit(lambda p, tk, c, pos: model.decode_step(
        p, tk, c, pos, ctx=ctx, compute_dtype=jnp.float32))
    logits_dec, cache = dec(params, tokens[:, t - 1:t], cache,
                            jnp.asarray(t - 1, jnp.int32))
    assert np.all(np.isfinite(np.asarray(logits_dec)))

    # Reference: decode token t-1 by prefilling the full prefix
    cache2 = model.init_cache(b, 64, dtype=jnp.float32)
    logits_ref, _ = jax.jit(
        lambda p, tk, c, **k: model.prefill(p, tk, c, ctx=ctx,
                                            compute_dtype=jnp.float32, **k)
    )(params, tokens, cache2, **kw)
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_ref)[:, -1], rtol=2e-3, atol=2e-3)
