"""The MoE calibration cell (``kinds/calib_moe.py``) at a CPU size: a sound
run passes and compiles nothing in its window; its control, an expert's
rows left out, renormalized top-k and a missing latent norm all fail; the
near-tie rule takes a flip inside its delta and faults one outside it;
and the cell's three readers read nothing without their spans and exact
values on synthetic ones."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmarks.chip import harness, loader
from benchmarks.chip.reference import deepseek_v2 as ref
from repro.obs import trace as obs_trace

CELL = "deepseek_v2_lite.calib_moe_stream"
SEED = 2 ** 33 + 151
# three layers (one dense), 8 router experts of which 4 held from expert
# 2, top-2, one shared: every part of the real cell at widths a test holds
TINY = {"num_hidden_layers": 3, "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "kv_lora_rank": 32, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_shared_experts": 1,
        "router_experts": 8, "n_routed_experts": 4, "expert_start": 2,
        "num_experts_per_tok": 2, "vocab_size": 256,
        "init": {"embed_std": 0.1, "norm_scale_std": 0.1}}
# two folds a batch and attention in blocks, as at the real size
MIX = {"batch": 2, "seq_len": 32, "fold_rows": 32, "attn_block": 16,
       "trace_seconds": 1}
# the limit at this size: sound runs read 7.6e-7 to 8.9e-7 and the control
# 1.7e-5 to 3.3e-5 over three seeds
LIMIT = {"fold_gram_gap": {"limit": 4e-6, "pass_if": "le"}}


def _run(control=False, limits=None, trace=False):
    over = {"config": TINY, "traffic": MIX, "limits": limits or {}}
    return harness.run_cell(CELL, SEED, 1.5, trace, require_chip_=False,
                            overrides=over, control=control)


@pytest.fixture(scope="module")
def sound():
    return _run(limits=LIMIT)


def test_sound_run_is_correct_and_compiles_nothing_in_its_window(sound):
    assert sound["correct"], sound["compared"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"setup_s", "calib_tok_s"}
    assert sound["info"]["window_compiles"] == 0
    assert sound["compared"]["route_faults"]["value"] == 0
    assert list(sound)[-1] == "compared"


def test_control_fails():
    c = _run(control=True, limits=LIMIT)
    assert not c["correct"], c["compared"]
    got = c["info"]["program_fold_gram_gap"]
    assert got <= LIMIT["fold_gram_gap"]["limit"]
    assert c["compared"]["fold_gram_gap"]["value"] > 3 * got


def test_an_experts_rows_left_out_fail(monkeypatch):
    from repro.core.calibrate import Calibrator
    record = Calibrator.record_experts

    def drop_first(self, path, e_start, stacks, counts):
        counts = np.array(counts)
        counts[0] = 0
        record(self, path, e_start, stacks, counts)

    monkeypatch.setattr(Calibrator, "record_experts", drop_first)
    assert not _run()["correct"]


def test_renormalized_topk_fails(monkeypatch):
    import dataclasses
    kind = loader.kind("calib_moe")
    build = kind.model_config

    def renormalized(conf):
        cfg = build(conf)
        return dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, norm_topk_prob=True))

    monkeypatch.setattr(loader, "kind", lambda name, root=None: kind)
    monkeypatch.setattr(kind, "model_config", renormalized)
    assert not _run()["correct"]


def test_missing_latent_norm_fails(monkeypatch):
    from repro.models import attention
    monkeypatch.setattr(attention, "rmsnorm", lambda p, x, eps: x)
    assert not _run()["correct"]


ARCH = {"num_experts_per_tok": 2, "norm_topk_prob": False,
        "routed_scaling_factor": 1.0}


@pytest.mark.parametrize("gap,delta,taken", [(1e-5, 1e-3, True),
                                             (1e-2, 1e-3, False)],
                         ids=["inside_delta", "outside_delta"])
def test_near_tie_rule(gap, delta, taken):
    """Row 0's second and third logits lie ``gap`` apart, and the program
    chose the third: inside ``delta`` the reference takes that choice (a
    near-tie); outside, it keeps its own and the row is a fault."""
    logits = jnp.array([[3.0, 2.0, 2.0 - gap, 0.0],
                        [0.0, 1.0, 3.0, 2.0]])
    forced = jnp.array([[0, 2], [2, 3]])
    gw, flipped, dist = ref.route(ARCH, logits, forced, delta)
    assert bool(flipped[0]) == taken and not bool(flipped[1])
    assert float(dist[0]) == pytest.approx(gap, rel=1e-2)  # float32
    assert float(dist[1]) == 0.0
    assert (float(gw[0, 2]) > 0) == taken
    assert (float(dist[0]) > delta) == (not taken)


def _span(i, name, **args):
    return obs_trace.Span(name, 1.0 + i, 1.5 + i, i, None, args, 0)


def _read(monkeypatch, name, spans):
    monkeypatch.setattr(obs_trace, "spans", lambda lo, hi: spans)
    rec = {"host_window": (0.0, 100.0)}
    return loader.metric_reader(name)(rec)


def test_expert_row_use_exact(monkeypatch):
    spans = [_span(0, "calib.expert_fold", rows=30, padded_rows=64),
             _span(1, "calib.expert_fold", rows=34, padded_rows=64),
             _span(2, "calib.record", path="a", tokens=64, shared=False)]
    got = _read(monkeypatch, "calib.expert_row_use.calib_moe", spans)
    assert got == pytest.approx(100.0 * 64 / 128)


def test_route_share_exact(monkeypatch):
    # two 0.5-s routes in a 100-s span, one holding a 0.25-s child
    spans = [_span(0, "calib.route", experts=8, tokens=64),
             _span(3, "calib.route", experts=8, tokens=64),
             obs_trace.Span("calib.fold", 4.0, 4.25, 9, 3, {}, 0)]
    got = _read(monkeypatch, "calib.route_share.calib_moe", spans)
    assert got == pytest.approx(100.0 * 0.75 / 100.0)


@pytest.mark.parametrize("name", ["calib.expert_row_use.calib_moe",
                                  "calib.route_share.calib_moe"])
def test_span_readers_without_spans_give_none(monkeypatch, name):
    assert _read(monkeypatch, name, []) is None
    assert _read(monkeypatch, name, [_span(0, "calib.capture")]) is None
    rec = {"host_window": (0.0, time.perf_counter())}
    monkeypatch.delattr(obs_trace, "spans")
    assert loader.metric_reader(name)(rec) is None


def test_mfu_reader_without_counts_gives_none():
    read = loader.metric_reader("mfu.calib_moe")
    assert read({}) is None
    assert read({"trace": None, "peaks": {"bf16_flops_per_s": 1.0}}) is None


def test_mfu_reader_exact_on_a_synthetic_record():
    """One batch of 4 tokens: forward and folds, counted by hand."""
    class Tr:
        window_s = 2.0

    m = {"num_hidden_layers": 2, "first_k_dense_replace": 1,
         "hidden_size": 4, "num_attention_heads": 1, "qk_nope_head_dim": 2,
         "qk_rope_head_dim": 2, "v_head_dim": 2, "kv_lora_rank": 2,
         "intermediate_size": 8, "moe_intermediate_size": 2,
         "n_shared_experts": 1, "router_experts": 4}
    rec = {"trace": Tr(), "peaks": {"bf16_flops_per_s": 1e3},
           "expert_counts": [[[3, 0]]], "model": m,
           "mix": {"batch": 1, "seq_len": 4}, "tokens_per_batch": 4,
           "fold_rows": 8, "fold_widths": [4]}
    # forward: attn linears 2*(4*4 + 4*4 + 2*4 + 2*4) = 96 a token a layer,
    # scores 2*(2+2+2) = 12 a key, 10 keys; dense 6*4*8 = 192 a token; MoE
    # router 2*4*4 + shared 6*4*2 = 80 a token; 3 routed rows 6*4*2 each
    fwd = 2 * (96 * 4 + 12 * 10) + 192 * 4 + 80 * 4 + 48 * 3
    qr = lambda rows, n: 2.0 * (n + rows) * n * n - 2.0 * n ** 3 / 3.0
    folds = qr(4, 4) + qr(3, 4) + qr(3, 2)
    want = 100.0 * (fwd + folds) / (2.0 * 1e3)
    assert loader.metric_reader("mfu.calib_moe")(rec) == pytest.approx(want)


def test_traced_run_reads_the_cells_metrics():
    r = _run(trace=True, limits=LIMIT)
    assert r["correct"], r["compared"]
    for name in ("calib.expert_row_use.calib_moe",
                 "calib.route_share.calib_moe",
                 "calib.dispatch_share.calib_moe"):
        v = r["metrics"][name]["value"]
        assert 0.0 < v <= 100.0, (name, v)
    # per layer: W_q and W_kv_a share their input, as do gate and up, so
    # 2 of the 7 dense records take the fold before them
    assert r["metrics"]["calib.shared_record_share.calib_moe"]["value"] \
        == pytest.approx(100.0 * 2 / 7)
    # a CPU run has no chip peaks and no device operations: the mfu and
    # idle readers read nothing there
    assert "mfu.calib_moe" not in r["metrics"]
    assert "device_idle.calib_moe" not in r["metrics"]


def test_declared_for_the_cell():
    names = [m["name"] for m in
             loader.metrics_for(loader.benchmark(), CELL, "per_layer")]
    assert set(names) == {"mfu.calib_moe", "calib.expert_row_use.calib_moe",
                          "calib.route_share.calib_moe",
                          "device_idle.calib_moe",
                          "calib.dispatch_share.calib_moe",
                          "calib.shared_record_share.calib_moe"}
