"""The traffic generator and the latency arithmetic of the chip benchmark."""
import os
import sys
import time

import numpy as np
import pytest

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))]

from benchmarks.chip import generate, loader, stats  # noqa: E402

BIG_SEED = 2 ** 33 + 12345
OPEN = loader.kind("open")


def _mix(name):
    return loader.traffic(name)


def test_open_loop_same_seed_same_requests():
    mix = _mix("chat_poisson")
    a = OPEN.requests(mix, BIG_SEED, 30, 49152)
    b = OPEN.requests(mix, BIG_SEED, 30, 49152)
    rate = mix["rate_per_s"]
    assert len(a) == len(b) == (int(rate * mix["preroll_s"])
                                + int(rate * 30))
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert x.temperature == y.temperature and x.seed == y.seed
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_open_loop_seeds_permute_one_set_of_sizes():
    """Every seed sends the window, and the pre-roll, the same sizes and
    gaps in another order."""
    mix = _mix("chat_poisson")
    a = OPEN.requests(mix, 1, 30, 49152)
    b = OPEN.requests(mix, BIG_SEED, 30, 49152)
    for part in (lambda r: r.due_s < 0, lambda r: r.due_s >= 0):
        pa = [r for r in a if part(r)]
        pb = [r for r in b if part(r)]
        assert len(pa) == len(pb) > 0
        key = lambda reqs: (sorted(len(r.prompt) for r in reqs),
                            sorted(r.max_new for r in reqs))
        assert key(pa) == key(pb)
        gaps = lambda reqs: np.diff([r.due_s for r in reqs])
        np.testing.assert_allclose(sorted(gaps(pa)), sorted(gaps(pb)),
                                   atol=5 / mix["rate_per_s"])
    assert [r.max_new for r in a] != [r.max_new for r in b]


def test_open_loop_lengths_and_arrivals_in_range():
    mix = _mix("chat_poisson")
    reqs = OPEN.requests(mix, 7, 30, 49152)
    assert all(mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
               for r in reqs)
    assert all(mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
               for r in reqs)
    due = [r.due_s for r in reqs]
    assert due == sorted(due)
    assert -mix["preroll_s"] < due[0] < 0 < due[-1] <= 30
    assert [r.index for r in reqs] == list(range(len(reqs)))
    greedy = [r for r in reqs if r.greedy]
    assert len(greedy) == -(-len(reqs) // mix["greedy_every"])


def test_seed31_fits_jax_keys():
    for s in (0, 1, 2 ** 31 + 5, 2 ** 40, BIG_SEED):
        v = generate.seed31(s)
        assert 0 <= v < 2 ** 31
    assert generate.seed31(5) == generate.seed31(5)
    assert generate.seed31(5) != generate.seed31(6)


class _Req:
    def __init__(self, n):
        self.out_tokens, self.max_new, self.logits_finite = [], n, True
        self.preemptions = 0

    @property
    def done(self):
        return len(self.out_tokens) >= self.max_new


class _SlowEngine:
    """Stands in for the engine: every step takes ``dt`` seconds and gives
    each running request one token."""

    def __init__(self, dt):
        self.dt = dt
        self.scheduler = type("S", (), {"waiting": []})()
        self._c_prefill_seconds = type("C", (), {"value": 0.0})()
        self.running = []

    def submit(self, prompt, n, temperature=0.0, seed=0):
        r = _Req(n)
        self.scheduler.waiting.append(r)
        self.running.append(r)

    def has_work(self):
        return any(not r.done for r in self.running)

    def step(self):
        time.sleep(self.dt)
        for r in self.running:
            if not r.done:
                r.out_tokens.append(1)


def test_open_loop_times_requests_from_their_due_time():
    """Arrivals come from the clock: requests due while a slow step runs
    wait for it, and their TTFT counts that wait."""
    from benchmarks.chip import trace as chiptrace
    specs = [generate.Spec(i, 0.01 + 0.02 * i, np.zeros(4, np.int32), 2,
                           0.0, i) for i in range(5)]
    eng = _SlowEngine(0.15)
    ann = lambda name: __import__("contextlib").nullcontext()
    res = OPEN.loop(eng, specs, 0.5, ann,
                            chiptrace.Tracer(False, 0.5))
    assert res["attempted"] == 5
    assert max(res["late_s"]) > 0.05
    # the last requests were due during the first step: TTFT > the step
    assert max(res["ttft_s"]) >= 0.15


def test_percentile_and_censoring():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([0, 10], 90) == pytest.approx(9.0)
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    due = [0.0, 1.0, 2.0, 5.0]
    first = [0.5, None, 2.25, None]
    # the request due at 5.0 is past the window (4.0) and not counted; the
    # one due at 1.0 has no first token and counts until the close
    assert stats.ttfts(due, first, 4.0) == [0.5, 3.0, 0.25]
    # a first token after the close is censored at the close too
    assert stats.ttfts([1.0], [9.0], 4.0) == [3.0]


def test_token_gaps_inside_the_window():
    g = stats.token_gaps([[0.5, 1.0, 1.5, 2.5], [1.2, 1.4]], 1.0, 2.0)
    assert sorted(g) == pytest.approx([0.2, 0.5])


@pytest.mark.parametrize("strata", [1, 4])
def test_stratified_order_spreads_each_band(strata):
    rng = generate.rng_for(BIG_SEED, 3)
    n = 37
    v = generate.stratified(rng, np.arange(n)[::-1], strata)
    assert sorted(v) == list(range(n))
    bands = [set(b) for b in np.array_split(np.arange(n), strata)]
    band_of = {x: k for k, b in enumerate(bands) for x in b}
    for i in range(0, n - n % strata, strata):
        assert sorted(band_of[x] for x in v[i:i + strata]) == list(
            range(strata))


@pytest.mark.parametrize("name,q", [("itl_p95.chat", 95), ("itl_p50.chat", 50)])
def test_itl_readers_take_the_window_gaps(name, q):
    read = loader.metric_reader(name)
    assert read({}) is None and read({"itl_s": []}) is None
    gaps = [0.1] * 90 + [0.3] * 10
    assert read({"itl_s": gaps}) == pytest.approx(
        1e3 * np.percentile(gaps, q))
