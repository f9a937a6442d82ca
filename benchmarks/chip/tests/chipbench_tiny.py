"""Overrides that run the chip benchmark's cells on the CPU at a size a test
run can hold: two layers, narrow widths, a small pool and short requests.
The embedding is drawn wider than the real cells' (std 0.1, not 0.02), so
that a wrong token stands well above a real cell's limit, while attention
still moves the logits more than the token's own embedding does."""
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]

TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab_size": 256, "max_seq_len": 256,
        "rope_theta": 10000.0, "norm_eps": 1e-5, "tie_embeddings": True}
INIT = {"embed_std": 0.1, "norm_scale_std": 0.1}
ENGINE = {"max_running": 2, "block_size": 16, "num_blocks": 12,
          "prefix_cache": False}
# enough served tokens that the float8 control's widest gap stands clear
# of the cell's limit (0.21-0.26 over three seeds; sound runs <= 0.017)
CHECK = {"min_tokens": 64, "max_requests": 6}
# the calibration cell's limit at this size: sound runs read ~5e-7 here,
# the control (three bfloat16 passes) ~1e-5
CALIB_LIMIT = {"fold_gram_gap": {"limit": 3e-6, "pass_if": "le"}}


def overrides(cell: str):
    if "calib" in cell:
        return {"config": {"model": TINY, "init": INIT},
                "traffic": {"batch": 2, "seq_len": 32, "trace_seconds": 1}}
    # short prompts and longer outputs: most of what a late token attends
    # to was written by decode steps
    lengths = {"prompt": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                          "min": 4, "max": 12},
               "output": {"dist": "lognormal", "median": 16, "sigma": 0.3,
                          "min": 12, "max": 24}}
    mix = dict(lengths, rate_per_s=6.0, preroll_s=0.5, greedy_every=2,
               engine=ENGINE, trace_seconds=1, check=CHECK)
    return {"config": {"model": TINY, "init": INIT}, "traffic": mix}
