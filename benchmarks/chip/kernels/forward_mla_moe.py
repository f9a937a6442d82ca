"""Operations of the capture forward of a DeepSeek-V2-style decoder (MLA
attention, a dense SwiGLU first layers, then MoE layers with shared
experts), for the calibration ``mfu`` of a MoE cell. ``m`` holds the
configuration's HF keys.

Per token, each layer: the MLA linears ``2 d (h (dn + dr) + kl + dr)``
(W_q and W_kv_a), ``2 kl h (dn + dv)`` (W_kv_b) and ``2 h dv d`` (W_o);
attention over ``L`` keys (itself included) ``2 h (dn + dr + dv) L``,
causal: a sequence of ``T`` tokens attends ``T (T + 1) / 2`` keys in all.
A dense layer adds ``6 d ff``; a MoE layer ``2 d E`` (the router over all
``E`` experts) and ``6 d (shared x f)`` per token, and ``6 d f`` for each
row routed to an expert held here, at the true counts. The capture stops
at the final norm: no LM head. Norms, rotary embedding, softmax and the
gathers are not matmuls and are left out.
"""
from typing import Dict, Sequence


def attn_linear_flops(m: Dict) -> float:
    d, h = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    kl = m["kv_lora_rank"]
    return 2.0 * (d * h * (dn + dr) + d * (kl + dr) + kl * h * (dn + dv)
                  + h * dv * d)


def attn_score_flops(m: Dict, keys: int) -> float:
    h = m["num_attention_heads"]
    return 2.0 * h * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                      + m["v_head_dim"]) * keys


def batch_flops(m: Dict, batch: int, seq: int,
                counts: Sequence[Sequence[int]]) -> float:
    """One capture forward of ``batch`` sequences of ``seq`` tokens;
    ``counts``: per MoE layer, the rows routed to each held expert."""
    d = m["hidden_size"]
    tokens = batch * seq
    layers = m["num_hidden_layers"]
    dense = m["first_k_dense_replace"]
    total = layers * (attn_linear_flops(m) * tokens
                      + batch * attn_score_flops(m, seq * (seq + 1) // 2))
    total += dense * 6.0 * d * m["intermediate_size"] * tokens
    f = m["moe_intermediate_size"]
    per_token = 2.0 * d * m["router_experts"] + \
        6.0 * d * m["n_shared_experts"] * f
    total += (layers - dense) * per_token * tokens
    total += 6.0 * d * f * sum(sum(c) for c in counts)
    return total
