"""``correct`` of the serving cells comes out false when the timed path is
broken underneath.

Each test drives a whole run of a cell on the CPU, at the small size of
``chipbench_tiny`` and with the cell's own limits, skipping only the
harness's look for a chip; a fault is planted in the program's code for
the length of one run.
"""
import pytest

import chipbench_tiny
from benchmarks.chip import harness

SEED = 2 ** 33 + 77


CELLS = ["smollm_135m.chat_poisson"]


def _run(cell, control=False):
    return harness.run_cell(cell, SEED, 1.5, False, require_chip_=False,
                            overrides=chipbench_tiny.overrides(cell),
                            control=control)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_reads_higher(cell):
    """The program passes; the float8 reference put in its place reads a
    gap well above the program's, and that run is not correct."""
    r = _run(cell)
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "compared"
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert set(r["metrics"]) == {"setup_s", "ttft_p90_ms"}
    c = _run(cell, control=True)
    assert not c["correct"], c["compared"]
    got = c["info"]["program_served_logit_gap"]
    assert harness.ok(got, c["compared"]["served_logit_gap"])
    assert c["compared"]["served_logit_gap"]["value"] > max(3 * got, 0.05)


@pytest.mark.parametrize("cell", CELLS)
def test_token_altered_where_produced(cell, monkeypatch):
    from repro.serve.engine import ContinuousEngine
    sample = ContinuousEngine._sample_tokens

    def altered(self, logits, reqs, pad_to=0):
        toks = sample(self, logits, reqs, pad_to)
        return (toks + 1) % logits.shape[-1]

    monkeypatch.setattr(ContinuousEngine, "_sample_tokens", altered)
    assert not _run(cell)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_decode_returns_cache_unchanged(cell, monkeypatch):
    """The decode step hands back the page stores it was given, without
    the new token's keys and values."""
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import ContinuousEngine
    init = ContinuousEngine.__init__

    def patched(self, *args, **kwargs):
        init(self, *args, **kwargs)
        decode = self._decode_paged

        def stuck(p, tok, cache, pos, tables):
            kept = jax.tree.map(jnp.copy, cache)
            logits, _ = decode(p, tok, cache, pos, tables)
            return logits, kept

        self._decode_paged = stuck

    monkeypatch.setattr(ContinuousEngine, "__init__", patched)
    assert not _run(cell)["correct"]
