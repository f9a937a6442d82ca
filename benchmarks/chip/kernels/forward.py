"""Operations of the decoder's forward pass per token, for the step-level
``mfu`` metrics (dense GQA decoder with a SwiGLU MLP and a tied LM head).

Per token at a position that attends ``L`` keys (itself included):
``2 * P + 4 * layers * heads * head_dim * L``, where ``P`` is the
parameters of every layer's matmuls (Q, K, V, O, gate, up, down). The LM
head, ``2 * d_model * vocab``, is counted once for each token whose logits
are needed: each decoded token, and the last prompt token of a prefill.
Prefill attention is causal: a prompt of ``n`` tokens attends
``n (n + 1) / 2`` keys in all. Norms, rotary embedding and softmax are
not matmuls and are left out.
"""
from typing import Dict, Sequence


def layer_params(m: Dict) -> int:
    d, hd = m["d_model"], m["head_dim"]
    attn = d * hd * (2 * m["n_heads"] + 2 * m["n_kv_heads"])
    return attn + 3 * d * m["d_ff"]


def attn_flops(m: Dict, keys: int) -> float:
    return 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * keys


def head_flops(m: Dict) -> float:
    return 2.0 * m["d_model"] * m["vocab_size"]


def prefill_flops(m: Dict, n: int) -> float:
    body = 2.0 * m["n_layers"] * layer_params(m) * n
    return body + attn_flops(m, n * (n + 1) // 2) + head_flops(m)


def decode_flops(m: Dict, keys: int) -> float:
    return (2.0 * m["n_layers"] * layer_params(m) + attn_flops(m, keys)
            + head_flops(m))


def step_flops(prefill: Sequence[int], decode: Sequence[int], m: Dict
               ) -> float:
    """One engine step: prompts prefilled (lengths) and tokens decoded
    (keys each attends)."""
    return (sum(prefill_flops(m, n) for n in prefill)
            + sum(decode_flops(m, k) for k in decode))


def calib_flops(m: Dict, batch: int, seq: int) -> float:
    """Calibration's capture forward over a batch: every layer, no LM
    head (the capture stops at the final norm)."""
    body = 2.0 * m["n_layers"] * layer_params(m) * batch * seq
    return body + batch * attn_flops(m, seq * (seq + 1) // 2)
