"""DeepSeek-V2-Lite's published layer against the plain float32 reference
(``benchmarks/chip/reference/deepseek_v2.py``) on seeded random weights,
at the SMOKE size: one dense layer, two MoE layers of 8 experts top-2 with
one shared expert, MLA with its latent norm, YaRN rotary.

Tolerances: on the CPU both sides compute in float32, so they differ by
float32 rounding alone, ~1e-6 of the logits' scale through three layers;
``_close`` allows 2e-4 of it. Each fault below (top-k weights
renormalized, the latent's RMSNorm left out, plain rotary in place of
YaRN) moves the logits by a few hundredths of their scale or more.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmarks.chip.reference import deepseek_v2 as ref  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.core.calibrate import Calibrator  # noqa: E402
from repro.models import attention, build_model, ffn  # noqa: E402
from repro.models.common import ParallelCtx  # noqa: E402

CFG = get_smoke_config("deepseek_v2_lite_16b")
B, T = 2, 16


def arch_of(cfg):
    """The reference's architecture (HF keys) of a program config."""
    y = cfg.yarn
    return {
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "kv_lora_rank": cfg.kv_lora_rank, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": None if y is None else {
            "factor": y.factor, "beta_fast": y.beta_fast,
            "beta_slow": y.beta_slow, "mscale": y.mscale,
            "mscale_all_dim": y.mscale_all_dim,
            "original_max_position_embeddings":
                y.original_max_position_embeddings},
        "num_experts_per_tok": cfg.moe.top_k,
        "norm_topk_prob": cfg.moe.norm_topk_prob,
        "routed_scaling_factor": 1.0,
        "first_k_dense_replace": cfg.first_k_dense,
        "e_start": cfg.moe.e_start,
    }


def draw(model, seed=0):
    """Every weight normal: a linear's or an expert's / sqrt(d_in), the
    embedding x 0.5, a norm's ``scale`` x 0.3 (applied as 1 + scale)."""
    shapes = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.PRNGKey(seed)
    leaves = []
    for i, (path, s) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        std = {"embed": 0.5, "scale": 0.3}.get(
            name, 1.0 / np.sqrt(s.shape[-2]) if len(s.shape) >= 2 else 0.0)
        leaves.append(jax.random.normal(jax.random.fold_in(key, i), s.shape)
                      * std)
    return jax.tree_util.tree_unflatten(tree, leaves)


@pytest.fixture(scope="module")
def setup():
    model = build_model(CFG)
    params = draw(model)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (B, T), 0,
                                CFG.vocab_size)
    want = np.asarray(ref.logits(arch_of(CFG), params, tokens))
    return model, params, tokens, want


def _close(got, want):
    err = np.abs(np.asarray(got) - want).max()
    return err <= 2e-4 * np.abs(want).max(), err


def _forward(cfg, params, tokens):
    """Logits of every position: the backbone prefill runs, then the head."""
    model = build_model(cfg)
    x = model._embed(params, tokens).astype(jnp.float32)
    h, _, _ = model._backbone(params, x, ctx=ParallelCtx())
    return model._logits(params, h)


def test_forward_logits_match_reference(setup):
    model, params, tokens, want = setup
    ok, err = _close(_forward(CFG, params, tokens), want)
    assert ok, err


def test_prefill_then_decode_through_the_latent_cache(setup):
    model, params, tokens, want = setup
    k = 4
    cache = model.init_cache(B, 32, dtype=jnp.float32)
    last, cache = model.prefill(params, tokens[:, :T - k], cache,
                                compute_dtype=jnp.float32)
    got = [last[:, 0]]
    for i in range(T - k, T - 1):
        lg, cache = model.decode_step(params, tokens[:, i:i + 1], cache,
                                      jnp.asarray(i, jnp.int32),
                                      compute_dtype=jnp.float32)
        got.append(lg)
    ok, err = _close(jnp.stack(got, 1), want[:, T - k - 1:T - 1])
    assert ok, err


def _renormalized():
    return dataclasses.replace(CFG, moe=dataclasses.replace(
        CFG.moe, norm_topk_prob=True))


@pytest.mark.parametrize("fault", ["renormalized_topk", "no_latent_norm",
                                   "plain_rope"])
def test_each_fault_fails_the_tolerance(setup, monkeypatch, fault):
    _, params, tokens, want = setup
    cfg = CFG
    if fault == "renormalized_topk":
        cfg = _renormalized()
    elif fault == "no_latent_norm":
        monkeypatch.setattr(attention, "rmsnorm", lambda p, x, eps: x)
    else:
        cfg = dataclasses.replace(CFG, yarn=None)
    ok, err = _close(_forward(cfg, params, tokens), want)
    assert not ok, err
    assert err > 10 * 2e-4 * np.abs(want).max()


def _moe_params(cfg, full, e_start, e_count):
    held = dict(full)
    for k in ("w_gate", "w_up", "w_down"):
        held[k] = full[k][e_start:e_start + e_count]
    share = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, e_start=e_start, e_count=e_count))
    return share, held


@pytest.mark.parametrize("e_count", [2, 4])
def test_expert_shares_add_up_to_the_uncut_layer(e_count):
    """Every share's output, the shared expert (computed by each) counted
    once, adds up to the reference's whole layer."""
    full = ffn.moe_init(jax.random.PRNGKey(3), CFG)
    m = jax.random.normal(jax.random.PRNGKey(4), (B, T, CFG.d_model))
    want, _, _ = ref.moe_ffn(arch_of(CFG), full, m.reshape(-1, CFG.d_model))
    shared = ffn.mlp_apply(full["shared"], m, CFG.act)
    total = -(CFG.moe.num_experts // e_count - 1) * shared
    for e0 in range(0, CFG.moe.num_experts, e_count):
        share, held = _moe_params(CFG, full, e0, e_count)
        y, _ = ffn.moe_apply(share, held, m, ctx=ParallelCtx())
        total = total + y
    np.testing.assert_allclose(np.asarray(total).reshape(-1, CFG.d_model),
                               np.asarray(want), rtol=1e-4, atol=1e-5)


def test_dropless_row_alone_equals_row_in_a_padded_batch():
    params = ffn.moe_init(jax.random.PRNGKey(5), CFG)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 8, CFG.d_model))
    batch = x.at[2:].set(0.0)          # padding rows
    run = jax.jit(lambda p, v: ffn.moe_apply(CFG, p, v, ctx=ParallelCtx())[0])
    y_batch = run(params, batch)
    y_alone = run(params, x[1:2, 3:4])
    np.testing.assert_allclose(np.asarray(y_alone)[0, 0],
                               np.asarray(y_batch)[1, 3], rtol=1e-5,
                               atol=1e-6)


def _ref_grams(params, tokens):
    """Per layer, the reference Gram of every tap over ``tokens``."""
    arch = arch_of(CFG)
    items = ref.items(arch)
    out = [dict() for _ in range(CFG.n_layers)]
    for row in np.asarray(tokens):
        x = ref.embed(params["embed"], jnp.asarray(row, jnp.int32))
        for i in range(CFG.n_layers):
            x, g, _, _ = ref.layer_grams(items, ref.is_moe(arch, i), None,
                                         ref.layer_params(params, i), x,
                                         None, 0.0)
            for k, v in g.items():
                out[i][k] = out[i].get(k, 0.0) + np.asarray(v, np.float64)
    return out


def _layer_tap(path):
    parts = path.split("/")
    i = 0 if parts[0] == "prefix" else 1 + int(parts[1])
    tail = "/".join(parts[-2:])
    if "/expert" in path:
        return i, tail
    return i, {"wq": "attn_in", "wkv_a": "attn_in", "wkv_b": "latent",
               "wo": "attn_out", "gate": "mlp_in", "up": "mlp_in",
               "down": "mlp_hidden"}[parts[-1]]


@pytest.mark.parametrize("expert_rows", [2048, 8],
                         ids=["one_chunk", "chunks_of_8"])
def test_grouped_fold_matches_reference_grams_and_compiles_once(
        setup, monkeypatch, expert_rows):
    _, params, _, _ = setup
    model = build_model(CFG)
    cal = Calibrator()
    monkeypatch.setattr(ffn, "EXPERT_ROWS", expert_rows)
    batches = [jax.random.randint(jax.random.PRNGKey(10 + i), (B, T), 0,
                                  CFG.vocab_size) for i in range(2)]
    sizes = []
    for i, tokens in enumerate(batches):
        with jax.default_matmul_precision("highest"):
            model.capture_forward(params, {"tokens": tokens}, cal)
        sizes.append((cal._fold_group._cache_size(),
                      ffn._expert_chunk._cache_size()))
        grams = _ref_grams(params, jnp.concatenate(batches[:i + 1]))
        experts = [p for p in cal.streams if "/expert" in p]
        assert experts
        for p, s in cal.streams.items():
            layer, tap = _layer_tap(p)
            g = grams[layer][tap]
            r = np.asarray(s.r, np.float64)
            rel = np.linalg.norm(r.T @ r - g) / np.linalg.norm(g)
            assert rel < 1e-4, (p, rel)
            if "/expert" in p and i == 0:
                # one batch gives every expert fewer rows than its width
                assert s.tokens_seen < s.n, (p, s.tokens_seen)
    assert sizes[1] == sizes[0], sizes


def test_expert_tokens_seen_are_the_routed_counts(setup):
    _, params, tokens, _ = setup
    cal = Calibrator()
    with jax.default_matmul_precision("highest"):
        build_model(CFG).capture_forward(params, {"tokens": tokens}, cal)
    seen = cal.tokens_seen()
    for r in range(CFG.n_layers - CFG.first_k_dense):
        path = f"blocks/{r}/sub0/ffn"
        total = sum(v for p, v in seen.items()
                    if p.startswith(path + "/expert") and p.endswith("/in"))
        assert total == B * T * CFG.moe.top_k
        for p, v in seen.items():
            if p.startswith(path + "/expert") and p.endswith("/in"):
                assert seen[p[:-3] + "/hid"] == v


def test_kv_a_and_kv_b_are_captured_streams(setup):
    _, params, tokens, _ = setup
    cal = Calibrator()
    build_model(CFG).capture_forward(params, {"tokens": tokens}, cal)
    for layer in ("prefix/0", "blocks/0/sub0", "blocks/1/sub0"):
        for name in ("wq", "wkv_a", "wkv_b", "wo"):
            assert f"{layer}/mixer/{name}" in cal.streams
    assert cal.streams["prefix/0/mixer/wkv_b"].n == CFG.kv_lora_rank
