"""What every serving kind of traffic (``kinds/<kind>.py``) shares:
building and warming ``ContinuousEngine``, the window's bookkeeping, and
the comparison that decides ``correct``.

The window drives the engine's own ``submit`` / ``step``. Each output token
is stamped when the ``step()`` that produced it returned. The harness also
notes, for every step, the work the step did (prompt tokens prefilled, and
the context length of every decoded token), so that the per-layer readers
can count operations and bytes over exactly the steps a trace covers.

``correct`` (``check``): after the window, a sample drawn from the seed of
the greedy requests served in it, the longest among them, is run through
the plain float32 reference over prompt + served tokens; the number
compared is the widest gap by which a served token's reference logit lies
below the reference's best logit at that position. Its control puts the
reference, with every matmul operand in float8, in the program's place:
the number is then the gap of the token that the float8 reference puts
first, read from the float32 reference's logits.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import generate, weights
from benchmarks.chip.reference import transformer as ref


@dataclasses.dataclass
class Tracked:
    spec: generate.Spec
    req: Any                       # the engine's Request
    due: float                     # perf_counter seconds
    submitted: float
    stamps: List[float] = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def first(self) -> Optional[float]:
        return self.stamps[0] if self.stamps else None


@dataclasses.dataclass
class Step:
    prefill: List[int]             # prompt lengths prefilled in this step
    decode: List[int]              # context length of each decoded token
    prefill_seconds: float         # the engine's prefill timer, this step


class Window:
    """Bookkeeping of one measured window over an engine."""

    def __init__(self, engine, ann: Callable):
        self.engine = engine
        self.ann = ann
        self.tracked: List[Tracked] = []
        self.active: List[Tracked] = []
        self.steps: List[Step] = []
        self.refused = 0

    def submit(self, spec: generate.Spec, due: float) -> Optional[Tracked]:
        try:
            self.engine.submit(spec.prompt, spec.max_new,
                               temperature=spec.temperature, seed=spec.seed)
        except ValueError:
            self.refused += 1
            return None
        t = Tracked(spec, self.engine.scheduler.waiting[-1], due,
                    time.perf_counter())
        self.tracked.append(t)
        self.active.append(t)
        return t

    def step(self) -> List[Tracked]:
        """One engine step; returns the requests that finished in it."""
        seen = [len(t.req.out_tokens) for t in self.active]
        pf0 = self.engine._c_prefill_seconds.value
        with self.ann("engine.step"):
            self.engine.step()
        now = time.perf_counter()
        with self.ann("harness.bookkeeping"):
            prefill, decode, finished, still = [], [], [], []
            for t, n0 in zip(self.active, seen):
                n1 = len(t.req.out_tokens)
                plen = len(t.spec.prompt)
                if n1 > n0:
                    t.stamps.extend([now] * (n1 - n0))
                    if n0 == 0:
                        prefill.append(plen)
                    # output token j >= 1 comes from a decode step whose
                    # query sits at position plen + j - 1: plen + j keys
                    decode.extend(plen + j for j in range(max(n0, 1), n1))
                if t.req.done:
                    t.done = True
                    finished.append(t)
                else:
                    still.append(t)
            self.active = still
            self.steps.append(Step(
                prefill, decode,
                float(self.engine._c_prefill_seconds.value - pf0)))
        return finished


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file: the
    registry's entry for ``arch`` (its family and wiring), with every size
    the file states put in, so that what runs is what the file says."""
    from repro.configs import get_config
    return dataclasses.replace(get_config(conf["arch"]), **conf["model"])


def arch(conf: Dict[str, Any]) -> Dict[str, Any]:
    """What the plain reference needs to know of the architecture."""
    m = conf["model"]
    return {"layers": m["n_layers"], "heads": m["n_heads"],
            "kv_heads": m["n_kv_heads"], "head_dim": m["head_dim"],
            "norm": conf["norm"], "norm_eps": m["norm_eps"],
            "rope_theta": m["rope_theta"]}


def build(conf: Dict[str, Any], mix: Dict[str, Any], seed: int):
    """Model, weights (bfloat16, drawn from the seed) and engine."""
    from repro.models.transformer import LM
    from repro.serve.engine import ContinuousEngine
    model = LM(model_config(conf))
    dt = jnp.dtype(conf["dtypes"]["weights"])
    params = weights.make(model, generate.seed31(seed, 0), dt, conf["init"])
    e = mix["engine"]
    engine = ContinuousEngine(
        model, params, compute_dtype=jnp.dtype(conf["dtypes"]["compute"]),
        cache_dtype=jnp.dtype(conf["dtypes"]["cache"]),
        block_size=e["block_size"], num_blocks=e["num_blocks"],
        max_running=e["max_running"], prefix_cache=e["prefix_cache"])
    return model, params, engine


def warm(engine, mix: Dict[str, Any], vocab: int) -> Dict[str, float]:
    """Every jit signature the mix can reach (``engine.warmup``), then
    short requests that run the host's eager paths ``warmup`` does not
    execute: at each batch bucket, that many requests together, half of
    them greedy (sampling, bookkeeping); and one prompt for each
    power-of-two count of blocks a prompt of the mix can take (the pool
    zeroes a new request's blocks with ids padded to a power of two)."""
    info = engine.warmup(max_len=generate.max_len(mix))
    temp = float(mix["temperature"])
    bs = int(mix["engine"]["block_size"])
    lengths = []
    n = 1
    while True:
        lengths.append(min(n * bs, int(mix["prompt"]["max"])))
        if n * bs >= int(mix["prompt"]["max"]):
            break
        n *= 2
    batches = [[8] * b for b in engine.bucket_sizes] + [[n] for n in lengths]
    for group in batches:
        for i, n in enumerate(group):
            engine.submit(np.arange(n, dtype=np.int32) % vocab, 3,
                          temperature=temp if i % 2 else 0.0, seed=i)
        while engine.has_work():
            engine.step()
    engine.reset_metrics()
    return info


def sample(w: Window, seed: int, min_tokens: int, max_requests: int
           ) -> List[Tracked]:
    """Greedy requests served in the window, drawn from the seed: the one
    with the most served tokens first, then others in the seed's order
    until ``min_tokens`` served tokens or ``max_requests``. A request still
    running at the close counts with the tokens it was served, so that a
    window shorter than the longest outputs still checks them."""
    served = [t for t in w.tracked if t.spec.greedy and t.req.out_tokens]
    if not served:
        return []
    served.sort(key=lambda t: t.spec.index)
    longest = max(served, key=lambda t: len(t.req.out_tokens))
    rest = [t for t in served if t is not longest]
    order = generate.rng_for(seed, 7).permutation(len(rest))
    out, total = [longest], len(longest.req.out_tokens)
    for k in order:
        if total >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[k])
        total += len(rest[k].req.out_tokens)
    return out


def _bucket(n: int, step: int = 256) -> int:
    return -(-n // step) * step


@jax.jit
def _gaps(lg, pos, tok):
    """Gap below the best logit of ``tok`` at each of ``pos``."""
    rows = lg[0, pos]
    return jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, tok[:, None], axis=-1)[:, 0]


def check(conf: Dict[str, Any], params, picked: List[Tracked],
          control: Optional[str] = None) -> Dict[str, float]:
    """Widest gap of a served token below the reference's best logit, over
    every served token of ``picked`` (``served_logit_gap``); with
    ``control`` (a ``quant`` of the reference), also the widest gap of the
    token that the reference in that lower precision puts first at the
    same positions (``control_logit_gap``)."""
    a = arch(conf)
    worst, worst_ctrl, n = 0.0, 0.0, 0
    for t in picked:
        prompt = np.asarray(t.spec.prompt, np.int32)
        out = np.asarray(t.req.out_tokens, np.int32)
        seq = np.concatenate([prompt, out[:-1]])
        padded = np.zeros((1, _bucket(len(seq))), np.int32)
        padded[0, :len(seq)] = seq
        pos = jnp.arange(len(prompt) - 1, len(seq))
        lg = ref.logits(a, params, padded)
        worst = max(worst, float(jnp.max(_gaps(lg, pos, jnp.asarray(out)))))
        if control:
            lq = ref.logits(a, params, padded, quant=control)
            tok_q = jnp.argmax(lq[0, pos], axis=-1)
            worst_ctrl = max(worst_ctrl,
                             float(jnp.max(_gaps(lg, pos, tok_q))))
            del lq
        del lg
        n += len(out)
    res = {"served_logit_gap": worst, "tokens_compared": float(n),
           "requests_compared": float(len(picked))}
    if control:
        res["control_logit_gap"] = worst_ctrl
    return res


def free(engine) -> None:
    """Drop the engine's device state (page stores, jit caches hold no
    buffers) before the reference runs on the chip."""
    engine.pool.token_store = []
    engine.pool.state_store = []
    gc.collect()


def compare(conf: Dict[str, Any], mix: Dict[str, Any], params, w: Window,
            seed: int, control: bool, info: Dict[str, Any]
            ) -> Dict[str, float]:
    """The numbers ``correct`` is decided by, for a window that has closed
    and an engine whose state is freed. With ``control``, the control's
    reading stands in the program's place (so ``correct`` must come out
    false), and the program's own goes to ``info``."""
    chk = mix["check"]
    picked = sample(w, seed, int(chk["min_tokens"]), int(chk["max_requests"]))
    if not picked:
        return {}
    got = check(conf, params, picked,
                control=mix["control"] if control else None)
    served = got.pop("served_logit_gap")
    if control:
        info["program_served_logit_gap"] = served
        served = got.pop("control_logit_gap")
    info.update(got)
    return {"served_logit_gap": served}
