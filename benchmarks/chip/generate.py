"""What every traffic generator shares: seeds, length distributions and
the request a serving mix sends. Each kind of mix (``traffic/*.json``
``kind``) makes its requests in ``kinds/<kind>.py`` from these.

Every seed gets the same set of sizes and inter-arrival gaps, in another
order: lengths and gaps are the quantiles of the mix's distributions at
evenly spaced probabilities, and the seed only permutes them (and draws the
token ids and sampling seeds). So two seeds differ in arrangement, not in
the amount of work, and the spread between runs measures the system rather
than the draw.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def seed31(seed: int, salt: int = 0) -> int:
    """A 31-bit seed for JAX's PRNG keys, from any whole-number seed."""
    state = np.random.SeedSequence([int(seed) % (1 << 64), salt])
    return int(state.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)


def rng_for(seed: int, salt: int = 0) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), salt]))


def stratified(rng: np.random.Generator, values: np.ndarray, strata: int
               ) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which every run of
    ``strata`` consecutive places holds one value of each of ``strata``
    bands of the sorted values (the last run may be short). With
    ``strata`` 1 it is a plain permutation. Over a short window a plain
    permutation can bunch the longest requests, or the shortest gaps,
    into one stretch, so that two seeds load the system differently;
    bands spread them evenly through the window, and every seed still gets
    the same set of values."""
    v = np.sort(np.asarray(values))
    block = np.empty(len(v), np.int64)
    for band in np.array_split(np.arange(len(v)), max(1, int(strata))):
        block[band] = rng.permutation(len(band))
    return v[np.lexsort((rng.random(len(v)), block))]


def quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` values of a length distribution at probabilities (i + 1/2)/n,
    rounded and clipped to ``[min, max]``. ``spec``: ``{"dist":
    "lognormal", "median", "sigma", "min", "max"}`` or ``{"dist":
    "uniform", "min", "max"}``."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass
class Spec:
    """One request as the traffic defines it."""
    index: int
    due_s: float                 # open loop: seconds after the window opens
    prompt: np.ndarray           # (T0,) int32 token ids
    max_new: int
    temperature: float
    seed: int                    # the request's sampling seed

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def request(mix, rng, index, due_s, plen, olen, vocab) -> Spec:
    """Request ``index``: a prompt of ``plen`` random tokens and ``olen``
    tokens to serve; every ``greedy_every``-th request is greedy, the rest
    sample at the mix's ``temperature`` with a seed of their own."""
    every = int(mix.get("greedy_every", 0))
    greedy = every > 0 and index % every == 0
    return Spec(index=index, due_s=float(due_s),
                prompt=rng.integers(0, vocab, int(plen)).astype(np.int32),
                max_new=int(olen),
                temperature=0.0 if greedy else float(mix["temperature"]),
                seed=int(rng.integers(0, 1 << 31)))


def max_len(mix: Dict[str, Any]) -> int:
    """The longest prompt plus output a serving mix can send."""
    return int(mix["prompt"]["max"] + mix["output"]["max"])
