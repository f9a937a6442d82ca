"""Continuous-batching serving subsystem: block pool, scheduler, engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serve import BlockPool, ContinuousEngine, Request, Scheduler, \
    ServeEngine


@pytest.fixture(scope="module")
def smollm():
    cfg = get_smoke_config("smollm_135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _pool(model, **kw):
    kw.setdefault("num_blocks", 16)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_requests", 4)
    kw.setdefault("dtype", jnp.float32)
    return BlockPool(model, **kw)


# ---------------------------------------------------------------------------
# Block pool
# ---------------------------------------------------------------------------

class TestBlockPool:
    def test_alloc_extend_free_invariants(self, smollm):
        _, model, _ = smollm
        pool = _pool(model)
        assert pool.free_blocks == 15          # block 0 reserved as trash
        pool.alloc(1, 10)                      # ceil(10/4) = 3 blocks
        assert len(pool.table(1)) == 3
        assert pool.free_blocks == 12
        pool.extend(1, 12)                     # still 3 blocks
        assert len(pool.table(1)) == 3
        pool.extend(1, 13)                     # crosses a block boundary
        assert len(pool.table(1)) == 4
        assert pool.free_blocks == 11
        assert 0 not in pool.table(1)          # trash block never handed out
        pool.alloc(2, 4)
        assert set(pool.table(1)).isdisjoint(pool.table(2))
        pool.free(1)
        pool.free(2)
        assert pool.free_blocks == 15          # everything returned

    def test_exhaustion_raises(self, smollm):
        _, model, _ = smollm
        pool = _pool(model, num_blocks=4)      # 3 usable blocks
        pool.alloc(1, 12)
        assert not pool.can_alloc(1)
        with pytest.raises(MemoryError):
            pool.alloc(2, 4)
        with pytest.raises(MemoryError):
            pool.extend(1, 13)
        pool.free(1)
        assert pool.can_alloc(12)

    def test_slot_exhaustion(self, smollm):
        _, model, _ = smollm
        pool = _pool(model, max_requests=1)
        pool.alloc(1, 4)
        assert not pool.can_alloc(4)           # blocks free, but no slot
        pool.free(1)
        assert pool.can_alloc(4)

    def test_gather_matches_scatter(self, smollm):
        """Round trip: a prefilled contiguous cache survives pool storage."""
        _, model, _ = smollm
        pool = _pool(model)
        pool.alloc(5, 10)
        nb = len(pool.table(5))
        ref = model.init_cache(1, nb * pool.block_size, dtype=jnp.float32)
        ref = jax.tree.map(
            lambda a: jax.random.normal(jax.random.PRNGKey(a.size % 97),
                                        a.shape, jnp.float32), ref)
        pool.scatter_prefill([5], ref, 10)
        got = pool.gather_batch([5])
        for sp, r, g in zip(pool.layout.specs, jax.tree.leaves(ref),
                            jax.tree.leaves(got)):
            if sp.token_axis is None:
                np.testing.assert_allclose(np.asarray(r), np.asarray(g))
            else:
                idx = [slice(None)] * r.ndim
                idx[sp.token_axis] = slice(0, nb * pool.block_size)
                np.testing.assert_allclose(np.asarray(r[tuple(idx)]),
                                           np.asarray(g[tuple(idx)]))

    def test_reused_blocks_read_zero(self, smollm):
        _, model, _ = smollm
        pool = _pool(model)
        pool.alloc(1, 8)
        ref = model.init_cache(1, 8, dtype=jnp.float32)
        ref = jax.tree.map(lambda a: jnp.ones(a.shape, jnp.float32), ref)
        pool.scatter_prefill([1], ref, 8)
        pool.free(1)
        pool.alloc(2, 8)                       # reuses the freed blocks
        got = pool.gather_batch([2])
        for sp, g in zip(pool.layout.specs, jax.tree.leaves(got)):
            if sp.token_axis is not None:
                assert float(jnp.abs(g).max()) == 0.0

    def test_token_store_is_head_major(self, smollm):
        """A stacked K/V leaf (n_rep, B, T, Hkv, hd) is stored as
        (n_rep, num_blocks, Hkv, block_size, hd): one page of one head is
        a whole (block_size, hd) tile, the block the TPU kernels read."""
        cfg, model, _ = smollm
        pool = _pool(model)
        for sp, store in zip([s for s in pool.layout.specs
                              if s.token_axis is not None],
                             pool.token_store):
            assert store.shape[-3:] == (cfg.n_kv_heads, pool.block_size,
                                         cfg.head_dim)
            assert store.shape[sp.blocks_axis] == pool.num_blocks
            assert sp.in_page_axis == store.ndim - 2

    def test_layout_probe_families(self):
        """Probe classifies token-axis vs per-request-state leaves across
        decoder-only, enc-dec, and recurrent cache layouts."""
        n_token = {}
        for arch in ("smollm_135m", "whisper_base", "xlstm_1_3b"):
            model = build_model(get_smoke_config(arch))
            pool = _pool(model)
            n_token[arch] = sum(1 for s in pool.layout.specs
                                if s.token_axis is not None)
        assert n_token["smollm_135m"] > 0      # K/V pages
        assert n_token["whisper_base"] > 0     # self-attn pages (+cross state)
        assert n_token["xlstm_1_3b"] == 0      # purely recurrent state


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------

def _req(rid, t0=4, new=4, **kw):
    return Request(req_id=rid, prompt=np.zeros((t0,), np.int32),
                   max_new_tokens=new, **kw)


class TestScheduler:
    def test_join_and_evict_mixed_lengths(self, smollm):
        _, model, _ = smollm
        pool = _pool(model, num_blocks=32, max_requests=8)
        sched = Scheduler(pool, max_running=2)
        reqs = [_req(i, t0=3 + 5 * i, new=2 + i) for i in range(4)]
        for r in reqs:
            sched.submit(r)
        admitted = sched.admit()
        assert [r.req_id for r in admitted] == [0, 1]   # FIFO, slot cap
        for r in admitted:
            pool.alloc(r.req_id, r.vis_offset + len(r.prompt))
        assert sched.admit() == []                      # running set full
        sched.evict(reqs[0])
        assert reqs[0].state == "finished"
        nxt = sched.admit()
        assert [r.req_id for r in nxt] == [2]           # joins immediately
        pool.alloc(2, len(reqs[2].prompt))
        assert len(sched.running) == 2

    def test_admission_respects_capacity(self, smollm):
        _, model, _ = smollm
        pool = _pool(model, num_blocks=4)               # 3 usable blocks
        sched = Scheduler(pool, max_running=4)
        sched.submit(_req(0, t0=8, new=4))              # budget 12 -> 3 blocks
        sched.submit(_req(1, t0=8, new=4))
        admitted = sched.admit()
        assert [r.req_id for r in admitted] == [0]      # no blocks for #1
        pool.alloc(0, 8)
        assert sched.admit() == []

    def test_preempt_youngest_requeues_front(self, smollm):
        _, model, _ = smollm
        pool = _pool(model, num_blocks=32, max_requests=8)
        sched = Scheduler(pool, max_running=4)
        for i in range(3):
            sched.submit(_req(i))
        for r in sched.admit():
            pool.alloc(r.req_id, 4)
        victim = sched.preempt_youngest()
        assert victim.req_id == 2
        assert victim.preemptions == 1
        assert sched.waiting[0] is victim               # front of the queue
        assert len(sched.running) == 2


# ---------------------------------------------------------------------------
# Engine: greedy token-equivalence with the legacy fixed-batch path
# ---------------------------------------------------------------------------

def _engines(model, params, **kw):
    leg = ServeEngine(model, params, compute_dtype=jnp.float32,
                      cache_dtype=jnp.float32)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_running", 4)
    cont = ContinuousEngine(model, params, compute_dtype=jnp.float32,
                            cache_dtype=jnp.float32, **kw)
    return leg, cont


class TestContinuousEngine:
    def test_greedy_equivalence_uniform_batch(self, smollm):
        cfg, model, params = smollm
        leg, cont = _engines(model, params)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 7), 0,
                                    cfg.vocab_size)
        a = np.asarray(leg.generate(prompt, max_new_tokens=5))
        b = np.asarray(cont.generate(prompt, max_new_tokens=5))
        np.testing.assert_array_equal(a, b)

    def test_greedy_equivalence_mixed_length_trace(self, smollm):
        """Staggered arrivals, varied prompt/output lengths: every request
        must match a solo run of the legacy engine token-for-token."""
        cfg, model, params = smollm
        leg, cont = _engines(model, params, max_running=3)
        rng = np.random.RandomState(0)
        lens, news = [3, 9, 5, 12], [5, 3, 7, 2]
        prompts = [rng.randint(0, cfg.vocab_size, (l,)).astype(np.int32)
                   for l in lens]
        ids = []
        for i, (p, n) in enumerate(zip(prompts, news)):
            ids.append(cont.submit(p, n))
            cont.step()                       # staggered: join mid-decode
        cont.run()
        fin = {r.req_id: r for r in cont.finished}
        for p, n, rid in zip(prompts, news, ids):
            ref = np.asarray(leg.generate(jnp.asarray(p)[None],
                                          max_new_tokens=n))[0, len(p):]
            np.testing.assert_array_equal(
                ref, np.asarray(fin[rid].out_tokens),
                err_msg=f"request {rid} diverged from fixed-batch path")

    def test_preemption_preserves_greedy_tokens(self, smollm):
        """A pool too small for the full load forces preemption; preempted
        requests must still finish on the same greedy trajectory."""
        cfg, model, params = smollm
        leg, cont = _engines(model, params, block_size=2, num_blocks=9,
                             max_running=3)
        rng = np.random.RandomState(1)
        prompts = [rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
                   for _ in range(3)]
        ids = [cont.submit(p, 6) for p in prompts]
        fin = {r.req_id: r for r in cont.run()}
        assert sum(r.preemptions for r in fin.values()) > 0
        for p, rid in zip(prompts, ids):
            ref = np.asarray(leg.generate(jnp.asarray(p)[None],
                                          max_new_tokens=6))[0, 4:]
            np.testing.assert_array_equal(ref,
                                          np.asarray(fin[rid].out_tokens))

    def test_eos_termination_and_metrics(self, smollm):
        cfg, model, params = smollm
        _, cont = _engines(model, params)
        prompt = np.zeros((4,), np.int32)
        # find what greedy emits first, then use it as the EOS id
        probe = cont.submit(prompt, 1)
        first = cont.run()[0].out_tokens[0]
        cont2 = _engines(model, params)[1]
        rid = cont2.submit(prompt, 10, eos_id=first)
        fin = cont2.run()
        assert fin[0].req_id == rid
        assert fin[0].out_tokens[-1] == first
        assert len(fin[0].out_tokens) < 10
        m = cont2.metrics()
        assert m["requests"] == 1
        assert m["mean_ttft_s"] >= 0.0
        assert m["tokens_per_sec"] > 0.0

    def test_greedy_equivalence_vlm(self):
        """Both engines place the vision prefix in the cache identically."""
        cfg = get_smoke_config("qwen2_vl_2b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        leg, cont = _engines(model, params)
        extras = {"vision_embeds": jax.random.normal(
            jax.random.PRNGKey(9), (2, cfg.n_vision_tokens, cfg.d_model))}
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0,
                                    cfg.vocab_size)
        a = np.asarray(leg.generate(prompt, max_new_tokens=4, extras=extras))
        b = np.asarray(cont.generate(prompt, max_new_tokens=4, extras=extras))
        np.testing.assert_array_equal(a, b)

    def test_submit_rejects_impossible_request(self, smollm):
        """A request whose worst case can never fit the pool must fail fast
        at submit, not spin forever in the admission queue."""
        cfg, model, params = smollm
        _, cont = _engines(model, params, block_size=4, num_blocks=2)
        with pytest.raises(ValueError, match="blocks"):
            cont.submit(np.zeros((16,), np.int32), 4)

    def test_nonfinite_logits_are_flagged(self, smollm):
        """Each request records whether every logit it sampled from was
        finite: true for sane weights, false once the output head is NaN."""
        cfg, model, params = smollm
        prompt = np.arange(5, dtype=np.int32)
        _, cont = _engines(model, params)
        cont.submit(prompt, 3)
        assert all(r.logits_finite for r in cont.run())
        bad = jax.tree.map(lambda a: a, params)
        bad["embed"] = bad["embed"].at[:, 0].set(jnp.nan)
        _, cont = _engines(model, bad)
        cont.submit(prompt, 3)
        assert not any(r.logits_finite for r in cont.run())

    def test_per_request_temperature(self, smollm):
        """Greedy and sampled requests coexist in one batch; the greedy row
        stays on the deterministic trajectory."""
        cfg, model, params = smollm
        leg, cont = _engines(model, params)
        rng = np.random.RandomState(2)
        p = rng.randint(0, cfg.vocab_size, (5,)).astype(np.int32)
        gid = cont.submit(p, 5, temperature=0.0)
        sid = cont.submit(p, 5, temperature=1.5, seed=7)
        fin = {r.req_id: r for r in cont.run()}
        ref = np.asarray(leg.generate(jnp.asarray(p)[None],
                                      max_new_tokens=5))[0, 5:]
        np.testing.assert_array_equal(ref, np.asarray(fin[gid].out_tokens))
        assert len(fin[sid].out_tokens) == 5
