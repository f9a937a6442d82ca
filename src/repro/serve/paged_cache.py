"""Paged KV-cache block pool for continuous batching.

Vocabulary (shared with docs/serving.md): a *page* is a physical
``block_size``-token slab of the pooled page stores (page 0 is the
reserved trash page); a *block* is the logical unit — a request's token
stream cut into ``block_size``-token runs, its *block table* mapping block
i to the page holding it; a *slot* holds per-request state that does not
grow with tokens (slot ``max_requests`` is the trash slot); an *intern
chain* is the prefix registry's token-exact key structure; a *bucket* is a
padded jit-signature class (batch rows / block envelope).

The pool owns all KV storage as fixed-size token pages plus a per-request
state store, and exposes two read paths:

  * **paged** (the decode hot path): ``paged_cache()`` hands the model the
    page stores *themselves* — a token leaf's batch axis becomes the page
    axis (``num_blocks``) and its in-page token axis (``block_size``) sits
    second-minor, so a stacked-blocks leaf ``(n_rep, B, T, Hkv, hd)`` is
    stored head-major as ``(n_rep, num_blocks, Hkv, bs, hd)``: one page of
    one head is a whole ``(bs, hd)`` tile, the block the TPU kernels can
    DMA (a ``(bs, 1, hd)`` slice of a token-major page is not), and the
    store slots zero-copy into the model's layer scan. The attention
    layers read the block-table indirection directly
    (``kernels/paged_attention.py``) and write the new token into its page
    in place; ``absorb_paged()`` then just swaps array
    references. No per-step gather or scatter of the cache.
  * **gather** (fallback/oracle): ``gather_batch`` indexes the pool with a
    padded ``(B, nb)`` block-table matrix to assemble exactly the pytree
    ``init_cache`` would have produced, feeding the unmodified jitted
    ``prefill``/``decode_step``; ``scatter_token`` writes back only the page
    each request decoded into.

Which leaf is which is *probed*, not hard-coded: ``CacheLayout`` calls the
model's ``init_cache`` hook at two lengths and two batch sizes and diffs
leaf shapes, so decoder-only, enc-dec, VLM and recurrent layouts all work
unmodified. Token-axis leaves (attention K/V, MLA latents) go to pages;
everything else (mamba/xLSTM recurrent state, whisper cross K/V) lives in a
per-request slot store.

Two trash locations absorb batch padding (shape buckets pad ``B`` and
``nb`` to a closed set of jit signatures): block 0 is the reserved trash
page (table padding points at it), and slot ``max_requests`` is the
reserved trash state slot — padded rows gather/scatter garbage nowhere that
matters, and the per-request causal masks hide whatever they read.

**Prefix caching** (``prefix_cache=True``): blocks are refcounted and a
hash-indexed registry maps *full* blocks of committed tokens to their pages,
so a new request whose prompt shares a block-aligned prefix with anything
served before reuses those pages instead of recomputing them
(``alloc(..., tokens=)`` returns how many prefix tokens were cached).
Registry keys are intern chains — interned ``(parent_prefix,
block_tokens)`` ids — so two prefixes collide only if they are
token-for-token identical and lookups are always token-exact. When a request frees, registered blocks with no
remaining references park in an LRU of *cached* blocks instead of the free
list; allocation evicts from that LRU only under pool pressure. Shared
blocks are never written: writes target the block holding the request's
next position, which ``extend`` guarantees is exclusive by copy-on-write
forking (``fork`` shares a whole table, e.g. best-of-n; the first write to
the shared tail block copies it).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace
from repro.obs.metrics import Registry

_ROOT = -1                      # parent id of a prefix chain's first block


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    batch_axis: int            # axis indexed by request
    token_axis: Optional[int]  # axis that scales with max_len; None => state
    tail: Tuple[int, ...]      # shape with batch (and token) axes removed

    @property
    def blocks_axis(self) -> int:
        """Position of the page axis in the token store (= token axis after
        the batch axis is dropped)."""
        assert self.token_axis is not None
        return self.token_axis - (1 if self.batch_axis < self.token_axis
                                  else 0)

    @property
    def in_page_axis(self) -> int:
        """Position of the in-page token axis in the token store: second-
        minor, or last when no axis follows the token axis."""
        return self.blocks_axis + max(len(self.tail) - self.blocks_axis, 1)

    @property
    def slot_axis(self) -> int:
        """Position of the slot axis in the state store."""
        return self.batch_axis


def _ix(axis: int, idx) -> tuple:
    """Index tuple selecting ``idx`` at ``axis`` (slices before it)."""
    return (slice(None),) * axis + (idx,)


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """Probed structure of a model's cache pytree."""
    treedef: Any
    specs: Tuple[LeafSpec, ...]
    dtypes: Tuple[Any, ...]

    @staticmethod
    def probe(model, dtype=jnp.bfloat16, probe_len: int = 8) -> "CacheLayout":
        """Diff ``init_cache`` shapes across (batch, len) to classify leaves."""
        shapes = lambda c: [x.shape for x in jax.tree.leaves(c)]
        c11 = model.init_cache(1, probe_len, dtype=dtype)
        s11 = shapes(c11)
        s21 = shapes(model.init_cache(2, probe_len, dtype=dtype))
        s12 = shapes(model.init_cache(1, 2 * probe_len, dtype=dtype))
        specs = []
        for a, b, c in zip(s11, s21, s12):
            b_ax = [i for i, (x, y) in enumerate(zip(a, b)) if x != y]
            t_ax = [i for i, (x, y) in enumerate(zip(a, c)) if x != y]
            assert len(b_ax) == 1, f"ambiguous batch axis: {a} vs {b}"
            assert len(t_ax) <= 1, f"ambiguous token axis: {a} vs {c}"
            token_axis = t_ax[0] if t_ax else None
            drop = {b_ax[0]} | ({token_axis} if token_axis is not None else set())
            tail = tuple(s for i, s in enumerate(a) if i not in drop)
            specs.append(LeafSpec(b_ax[0], token_axis, tail))
        return CacheLayout(jax.tree.structure(c11), tuple(specs),
                           tuple(x.dtype for x in jax.tree.leaves(c11)))


def _token_store_shape(sp: LeafSpec, num_blocks: int, block_size: int):
    ax, ip = sp.blocks_axis, sp.in_page_axis
    shape = sp.tail[:ax] + (num_blocks,) + sp.tail[ax:]
    return shape[:ip] + (block_size,) + shape[ip:]


def _state_store_shape(sp: LeafSpec, n_slots: int):
    ax = sp.slot_axis
    return sp.tail[:ax] + (n_slots,) + sp.tail[ax:]


class BlockPool:
    """Free-list block allocator + pooled storage for one model's cache.

    Block 0 and slot ``max_requests`` are reserved (trash, absorb bucket
    padding). ``alloc``/``extend``/``free`` manage the python-side
    accounting; the array ops are jitted and shape-stable in (B, nb).
    """

    def __init__(self, model, *, num_blocks: int, block_size: int,
                 max_requests: int, dtype=jnp.bfloat16,
                 prefix_cache: bool = False, registry=None):
        assert num_blocks >= 2 and block_size >= 1
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.max_requests = max_requests
        self.prefix_cache = prefix_cache
        self.layout = CacheLayout.probe(model, dtype=dtype,
                                        probe_len=max(8, block_size))
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))  # 0 = trash
        self._tables: Dict[int, List[int]] = {}
        self._slots: Dict[int, int] = {}
        self._free_slots: List[int] = list(range(max_requests - 1, -1, -1))
        # --- prefix registry (all empty / inert when prefix_cache=False) ---
        self._ref: Dict[int, int] = {}          # live block -> refcount (>= 1)
        self._intern: Dict[Tuple[int, Tuple[int, ...]], int] = {}
        self._pid_parent: Dict[int, int] = {}   # prefix id -> parent id
        self._next_pid = 0                      # ids never reused (sweeps)
        self._intern_sweep_at = max(8 * num_blocks, 256)
        self._registry: Dict[int, int] = {}     # prefix id -> block holding it
        self._block_pid: Dict[int, int] = {}    # inverse of _registry
        self._lru: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()           # cached refcount-0 blocks
        self._chain: Dict[int, List[int]] = {}  # req -> prefix ids committed
        self.stats: Dict[str, int] = {"cow_copies": 0, "evictions": 0}
        # typed mirrors of ``stats`` plus live-occupancy callback gauges;
        # ``registry`` is the owning engine's (a private one standalone)
        reg = registry if registry is not None else Registry()
        self.registry = reg
        self._c_cow = reg.counter("pool_cow_copies_total",
                                  "copy-on-write block copies")
        self._c_evict = reg.counter("pool_prefix_evictions_total",
                                    "prefix-cache blocks LRU-evicted")
        reg.gauge("pool_free_blocks", "blocks on the free list",
                  fn=lambda: len(self._free))
        reg.gauge("pool_cached_blocks",
                  "evictable prefix-cache blocks (refcount 0)",
                  fn=lambda: len(self._lru))
        # pooled token pages + per-request state store (last slot = trash)
        self.token_store = [
            jnp.zeros(_token_store_shape(sp, num_blocks, block_size), dt)
            for sp, dt in zip(self.layout.specs, self.layout.dtypes)
            if sp.token_axis is not None]
        self.state_store = [
            jnp.zeros(_state_store_shape(sp, max_requests + 1), dt)
            for sp, dt in zip(self.layout.specs, self.layout.dtypes)
            if sp.token_axis is None]

    # ------------------------------------------------------------ accounting
    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1             # block 0 reserved as trash

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def cached_blocks(self) -> int:
        """Registered blocks no live request references (evictable)."""
        return len(self._lru)

    @property
    def available_blocks(self) -> int:
        """Blocks an allocation may claim: truly free + LRU-evictable."""
        return len(self._free) + len(self._lru)

    @property
    def free_slots(self) -> int:
        return len(self._free_slots)

    @property
    def trash_slot(self) -> int:
        return self.max_requests

    def ref_count(self, block: int) -> int:
        return self._ref.get(block, 0)

    def cached_block_ids(self) -> Tuple[int, ...]:
        return tuple(self._lru)

    def free_block_ids(self) -> Tuple[int, ...]:
        return tuple(self._free)

    def blocks_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 1) // self.block_size)

    def can_alloc(self, n_tokens: int) -> bool:
        return (self.blocks_for(n_tokens) <= self.available_blocks
                and len(self._free_slots) > 0)

    # ------------------------------------------------------- block lifecycle
    def _incref(self, block: int) -> None:
        if self._ref.get(block, 0) == 0:
            self._lru.pop(block, None)       # cached -> live again
        self._ref[block] = self._ref.get(block, 0) + 1

    def _decref(self, block: int) -> None:
        n = self._ref[block] - 1
        assert n >= 0, f"refcount underflow on block {block}"
        if n:
            self._ref[block] = n
            return
        del self._ref[block]
        if block in self._block_pid:         # registered: park in the LRU
            self._lru[block] = None
        else:
            self._free.append(block)

    def _deregister(self, block: int) -> None:
        pid = self._block_pid.pop(block)
        del self._registry[pid]

    def _take_block(self) -> int:
        """Claim a block: the free list first, then LRU-evict a cached one."""
        if self._free:
            return self._free.pop()
        if self._lru:
            block, _ = self._lru.popitem(last=False)     # least recently freed
            self._deregister(block)
            self.stats["evictions"] += 1
            self._c_evict.inc()
            trace.instant("pool.prefix_evict", block=block)
            return block
        raise MemoryError("block pool exhausted")

    # ------------------------------------------------------- prefix registry
    def _lookup(self, tokens) -> Tuple[List[int], List[int]]:
        """Longest chain of registered full blocks matching ``tokens``
        exactly, capped so at least one token is left to prefill."""
        bs = self.block_size
        max_blocks = (len(tokens) - 1) // bs
        parent, blocks, pids = _ROOT, [], []
        for i in range(max_blocks):
            key = (parent, tuple(int(t) for t in tokens[i * bs:(i + 1) * bs]))
            pid = self._intern.get(key)
            if pid is None or pid not in self._registry:
                break
            blocks.append(self._registry[pid])
            pids.append(pid)
            parent = pid
        return blocks, pids

    def _sweep_intern(self) -> None:
        """Bound the intern table: drop prefix ids that are neither in a
        live request's chain, nor registered, nor an ancestor of either
        (ancestors keep evicted-then-recommitted chains revivable under
        their original ids). Without this the table would grow by one entry
        per distinct block ever served."""
        keep = set(self._registry)
        for chain in self._chain.values():
            keep.update(chain)
        for pid in list(keep):
            p = self._pid_parent.get(pid, _ROOT)
            while p != _ROOT and p not in keep:
                keep.add(p)
                p = self._pid_parent.get(p, _ROOT)
        for key, pid in list(self._intern.items()):
            if pid not in keep:
                del self._intern[key]
                self._pid_parent.pop(pid, None)
        # re-arm so a legitimately large working set doesn't sweep per commit
        self._intern_sweep_at = max(2 * len(self._intern),
                                    8 * self.num_blocks, 256)

    def probe_prefix(self, tokens) -> int:
        """Cached-prefix tokens a lookup would hit right now (no acquire)."""
        if not self.prefix_cache or tokens is None:
            return 0
        return len(self._lookup(tokens)[0]) * self.block_size

    def commit(self, req_id: int, tokens) -> None:
        """Register the request's newly completed full blocks of ``tokens``
        (its committed prompt+generated stream) in the prefix registry."""
        if not self.prefix_cache or req_id not in self._tables:
            return
        bs = self.block_size
        table = self._tables[req_id]
        chain = self._chain.setdefault(req_id, [])
        n_full = min(len(tokens) // bs, len(table))
        while len(chain) < n_full:
            i = len(chain)
            parent = chain[-1] if chain else _ROOT
            key = (parent, tuple(int(t) for t in tokens[i * bs:(i + 1) * bs]))
            pid = self._intern.get(key)
            if pid is None:
                pid = self._next_pid
                self._next_pid += 1
                self._intern[key] = pid
                self._pid_parent[pid] = parent
                if len(self._intern) > self._intern_sweep_at:
                    self._sweep_intern()
            chain.append(pid)
            # first committer wins; duplicates stay unregistered and return
            # to the free list when their request ends
            if pid not in self._registry and table[i] not in self._block_pid:
                self._registry[pid] = table[i]
                self._block_pid[table[i]] = pid

    def alloc(self, req_id: int, n_tokens: int, tokens=None) -> int:
        """Reserve blocks covering ``n_tokens`` and a state slot.

        With ``prefix_cache`` and the request's token stream in ``tokens``,
        the longest registered block-aligned prefix is reused (refcounted)
        instead of freshly allocated. Returns the number of cached prefix
        tokens (0 without caching); the caller prefills only the suffix.
        """
        assert req_id not in self._tables, f"request {req_id} already allocated"
        hit_blocks: List[int] = []
        hit_pids: List[int] = []
        if self.prefix_cache and tokens is not None and len(tokens) > 1:
            hit_blocks, hit_pids = self._lookup(tokens)
        need = self.blocks_for(n_tokens) - len(hit_blocks)
        assert need >= 0
        for b in hit_blocks:                 # pin hits before any eviction
            self._incref(b)
        if need > self.available_blocks or not self._free_slots:
            for b in hit_blocks:
                self._decref(b)
            raise MemoryError(
                f"pool exhausted: need {need} blocks / 1 slot, have "
                f"{self.available_blocks} blocks / "
                f"{len(self._free_slots)} slots")
        blks = [self._take_block() for _ in range(need)]
        self._zero(blks)
        for b in blks:
            self._ref[b] = 1
        self._tables[req_id] = hit_blocks + blks
        self._slots[req_id] = self._free_slots.pop()
        self._chain[req_id] = list(hit_pids)
        return len(hit_blocks) * self.block_size

    def extend(self, req_id: int, n_tokens: int, *,
               write_start: Optional[int] = None) -> None:
        """Grow the request's table to cover ``n_tokens`` total tokens and
        guarantee the written span is exclusively owned (copy-on-write if
        shared with another request).

        By default only the block holding token ``n_tokens - 1`` is made
        writable (single-token decode). ``write_start`` widens the COW
        guarantee to every block covering ``[write_start, n_tokens - 1]`` —
        the speculative draft/verify paths write an L-token run that can
        begin mid-block inside a fork-shared page."""
        table = self._tables[req_id]
        need = self.blocks_for(n_tokens) - len(table)
        if need > self.available_blocks:
            raise MemoryError(f"pool exhausted extending request {req_id}")
        if need > 0:
            blks = [self._take_block() for _ in range(need)]
            self._zero(blks)
            for b in blks:
                self._ref[b] = 1
            table.extend(blks)
        lo = n_tokens - 1 if write_start is None else \
            max(0, min(write_start, n_tokens - 1))
        for i in range(lo // self.block_size,
                       (n_tokens - 1) // self.block_size + 1):
            self._ensure_writable(req_id, i * self.block_size
                                  if i * self.block_size > lo else lo)

    def truncate(self, req_id: int, n_tokens: int) -> None:
        """Roll back the request's table to cover only ``n_tokens`` tokens,
        releasing blocks past that point (speculative-decode rejection: the
        uncommitted tail pages a rejected draft run wrote are dropped; a
        registered or fork-shared block is decref'd, not clobbered)."""
        table = self._tables[req_id]
        keep = self.blocks_for(n_tokens)
        while len(table) > keep:
            self._decref(table.pop())
        chain = self._chain.get(req_id)
        if chain is not None and len(chain) > len(table):
            del chain[len(table):]

    def _ensure_writable(self, req_id: int, pos: int) -> None:
        """Copy-on-write: the block containing ``pos`` must have refcount 1.
        Only uncommitted (partial) blocks are ever written, so the registry
        is never invalidated by a write."""
        table = self._tables[req_id]
        i = pos // self.block_size
        blk = table[i]
        if self._ref[blk] <= 1:
            return
        with trace.span("pool.cow_copy", req_id=req_id, block=blk):
            new = self._take_block()
            if self.token_store:
                self.token_store = _copy_block(
                    tuple(self.layout.specs), self.token_store,
                    jnp.int32(blk), jnp.int32(new))
            self._ref[new] = 1
            self._decref(blk)
            table[i] = new
            self.stats["cow_copies"] += 1
            self._c_cow.inc()

    def fork(self, parent_id: int, child_id: int) -> None:
        """Share the parent's whole table with ``child_id`` (copy-on-write:
        the first divergent write mid-block copies that block) and duplicate
        its recurrent-state slot."""
        assert child_id not in self._tables
        if not self._free_slots:
            raise MemoryError("no free state slot to fork into")
        table = list(self._tables[parent_id])
        for b in table:
            self._incref(b)
        self._tables[child_id] = table
        self._slots[child_id] = self._free_slots.pop()
        self._chain[child_id] = list(self._chain.get(parent_id, []))
        if self.state_store:
            self.state_store = _copy_state_slot(
                tuple(self.layout.specs), self.state_store,
                jnp.int32(self._slots[parent_id]),
                jnp.int32(self._slots[child_id]))

    def _zero(self, blks: List[int]) -> None:
        # reused blocks must read as zeros, not stale KV from a freed request.
        # The id count pads to a power of two (trash page absorbs the extra
        # writes) so the zeroing jit keeps a closed signature set that
        # ``warm()`` can pre-compile instead of recompiling per alloc size.
        if blks and self.token_store:
            n = 1 << max(len(blks) - 1, 0).bit_length()
            ids = list(blks) + [0] * (n - len(blks))
            self.token_store = _zero_blocks(tuple(self.layout.specs),
                                            self.token_store,
                                            jnp.asarray(ids, jnp.int32))

    def warm(self, max_blocks: int) -> None:
        """Pre-compile the pool's own jitted maintenance ops — block zeroing
        at every padded id-count signature up to ``max_blocks`` and the
        copy-on-write block copy — against the trash page, so none of them
        compiles on a request's critical path after ``ContinuousEngine.
        warmup()``."""
        if not self.token_store:
            return
        n = 1
        while True:
            self.token_store = _zero_blocks(tuple(self.layout.specs),
                                            self.token_store,
                                            jnp.zeros((n,), jnp.int32))
            if n >= max(max_blocks, 1):
                break
            n *= 2
        # trash copied onto itself: same signature as a real COW copy
        self.token_store = _copy_block(tuple(self.layout.specs),
                                       self.token_store,
                                       jnp.int32(0), jnp.int32(0))

    def free(self, req_id: int) -> None:
        for b in self._tables.pop(req_id):
            self._decref(b)
        self._free_slots.append(self._slots.pop(req_id))
        self._chain.pop(req_id, None)

    def table(self, req_id: int) -> List[int]:
        return list(self._tables[req_id])

    def slot(self, req_id: int) -> int:
        return self._slots[req_id]

    def max_table_blocks(self, req_ids) -> int:
        return max((len(self._tables[r]) for r in req_ids), default=0)

    def padded_tables(self, req_ids, *, rows: Optional[int] = None,
                      blocks: Optional[int] = None) -> jnp.ndarray:
        """(rows, blocks) int32 block tables. Ragged rows are padded with
        the trash block; extra rows (batch-bucket padding) are all-trash."""
        nb = self.max_table_blocks(req_ids)
        nb = max(blocks or nb, nb)
        b = max(rows or len(req_ids), len(req_ids))
        rows_ = [self._tables[r] + [0] * (nb - len(self._tables[r]))
                 for r in req_ids]
        rows_ += [[0] * nb] * (b - len(req_ids))
        return jnp.asarray(rows_, jnp.int32)

    def slots(self, req_ids, *, rows: Optional[int] = None) -> jnp.ndarray:
        s = [self._slots[r] for r in req_ids]
        b = max(rows or len(req_ids), len(req_ids))
        s += [self.trash_slot] * (b - len(req_ids))
        return jnp.asarray(s, jnp.int32)

    # ------------------------------------------------------ paged (hot path)
    def paged_cache(self, req_ids, *, rows: Optional[int] = None):
        """Cache pytree for the paged decode path: token leaves are the page
        stores themselves (original axis order — zero copy), state leaves
        are gathered per-slot for the (padded) batch."""
        state = _gather_state(tuple(self.layout.specs), self.state_store,
                              self.slots(req_ids, rows=rows))
        leaves, ti, si = [], 0, 0
        for sp in self.layout.specs:
            if sp.token_axis is None:
                leaves.append(state[si])
                si += 1
            else:
                leaves.append(self.token_store[ti])
                ti += 1
        return jax.tree.unflatten(self.layout.treedef, leaves)

    def absorb_paged(self, req_ids, cache, *, rows: Optional[int] = None) -> None:
        """Take back the cache returned by a paged decode step: token leaves
        ARE the updated page stores (swap references); state leaves are
        scattered back into their slots (padding rows hit the trash slot)."""
        token, state = [], []
        for sp, leaf in zip(self.layout.specs, jax.tree.leaves(cache)):
            (state if sp.token_axis is None else token).append(leaf)
        self.token_store = token
        if state:
            self.state_store = _scatter_state(
                tuple(self.layout.specs), self.state_store, tuple(state),
                self.slots(req_ids, rows=rows))

    # --------------------------------------------------- gather (oracle path)
    def gather_batch(self, req_ids, *, rows: Optional[int] = None,
                     blocks: Optional[int] = None):
        """Assemble the contiguous batched cache pytree for ``req_ids``.

        Returns a pytree identical in structure to
        ``model.init_cache(B, nb * block_size)`` — directly consumable by the
        jitted prefill/decode functions. ``rows``/``blocks`` pad the batch
        and page envelope to bucket sizes (padding rows read trash).
        """
        tables = self.padded_tables(req_ids, rows=rows, blocks=blocks)
        slots = self.slots(req_ids, rows=rows)
        leaves = _gather(tuple(self.layout.specs), self.block_size,
                         self.token_store, self.state_store, tables, slots)
        return jax.tree.unflatten(self.layout.treedef, leaves)

    def scatter_prefill(self, req_ids, cache, n_tokens: int) -> None:
        """Write the first ``n_tokens`` positions of a freshly prefilled
        contiguous cache (plus all state leaves) back into the pool."""
        tables = self.padded_tables(req_ids)
        nb_used = self.blocks_for(n_tokens)
        self.token_store, self.state_store = _scatter_prefill(
            tuple(self.layout.specs), self.block_size, nb_used,
            self.token_store, self.state_store,
            tuple(jax.tree.leaves(cache)), tables, self.slots(req_ids))

    def scatter_suffix(self, req_ids, cache, starts, lens, *,
                       rows: Optional[int] = None,
                       blocks: Optional[int] = None) -> None:
        """Write back only the blocks each request's suffix prefill touched:
        row ``i`` scatters blocks covering token range
        ``[starts[i], starts[i] + lens[i])`` (plus all state leaves).

        Blocks outside that range — shared prefix blocks below it, envelope
        padding above it — are redirected to the trash page, so a cached
        prefix another request references is never rewritten. ``rows`` and
        ``blocks`` pad to the same bucketed (B, nb) envelope the cache was
        gathered with, keeping the jit signature closed."""
        tables = np.asarray(self.padded_tables(req_ids, rows=rows,
                                               blocks=blocks))
        b, nb = tables.shape
        lo = np.zeros((b,), np.int64)
        hi = np.zeros((b,), np.int64)
        lo[:len(req_ids)] = np.asarray(starts) // self.block_size
        hi[:len(req_ids)] = [self.blocks_for(s + l) if l else 0
                             for s, l in zip(starts, lens)]
        j = np.arange(nb)
        masked = np.where((j[None, :] >= lo[:, None])
                          & (j[None, :] < hi[:, None]), tables, 0)
        self.token_store, self.state_store = _scatter_prefill(
            tuple(self.layout.specs), self.block_size, nb,
            self.token_store, self.state_store,
            tuple(jax.tree.leaves(cache)),
            jnp.asarray(masked, jnp.int32), self.slots(req_ids, rows=rows))

    def scatter_token(self, req_ids, cache, positions, *,
                      rows: Optional[int] = None,
                      blocks: Optional[int] = None) -> None:
        """Write back the single page each request decoded into (the block
        containing ``positions[i]``) plus updated state leaves. ``positions``
        must already be padded to ``rows`` (padding rows write trash);
        ``blocks`` pads the table width to the same bucket the cache was
        gathered with, keeping this op's jit signature bucketed too."""
        tables = self.padded_tables(req_ids, rows=rows, blocks=blocks)
        self.token_store, self.state_store = _scatter_token(
            tuple(self.layout.specs), self.block_size,
            self.token_store, self.state_store,
            tuple(jax.tree.leaves(cache)), tables,
            self.slots(req_ids, rows=rows),
            jnp.asarray(positions, jnp.int32))


# ---------------------------------------------------------------------------
# jitted pool <-> batch converters
#
# The store arguments of the in-place update ops are donated so XLA reuses
# the pool buffers instead of copying the whole pool every step; the pool
# immediately replaces its references with the returned arrays.
#
# Token stores keep the leaf's axis order except for the in-page token axis
# (``spec.in_page_axis``), so indexing happens at ``spec.blocks_axis``
# (resp. ``spec.slot_axis``) rather than axis 0; the only data ever
# transposed is the gathered batch-sized slice, never a pool.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _zero_blocks(specs, token_store, ids):
    token_specs = [sp for sp in specs if sp.token_axis is not None]
    return [s.at[_ix(sp.blocks_axis, ids)].set(0)
            for sp, s in zip(token_specs, token_store)]


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _copy_block(specs, token_store, src, dst):
    """Copy-on-write: duplicate page ``src`` into ``dst`` on every leaf."""
    token_specs = [sp for sp in specs if sp.token_axis is not None]
    return [s.at[_ix(sp.blocks_axis, dst)].set(s[_ix(sp.blocks_axis, src)])
            for sp, s in zip(token_specs, token_store)]


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _copy_state_slot(specs, state_store, src, dst):
    """Fork: duplicate the per-request state slot ``src`` into ``dst``."""
    state_specs = [sp for sp in specs if sp.token_axis is None]
    return [s.at[_ix(sp.slot_axis, dst)].set(s[_ix(sp.slot_axis, src)])
            for sp, s in zip(state_specs, state_store)]


@functools.partial(jax.jit, static_argnums=(0,))
def _gather_state(specs, state_store, slots):
    """slots: (B,). Returns state leaves in original axis order."""
    out, si = [], 0
    for sp in specs:
        if sp.token_axis is not None:
            continue
        out.append(jnp.take(state_store[si], slots, axis=sp.slot_axis))
        si += 1
    return out


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _scatter_state(specs, state_store, state_leaves, slots):
    new_state, si = list(state_store), 0
    for sp in specs:
        if sp.token_axis is not None:
            continue
        leaf = state_leaves[si]
        new_state[si] = new_state[si].at[_ix(sp.slot_axis, slots)].set(
            leaf.astype(new_state[si].dtype))
        si += 1
    return new_state


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gather(specs, block_size, token_store, state_store, tables, slots):
    """tables: (B, nb); slots: (B,). Returns leaves in treedef order."""
    b, nb = tables.shape
    out, ti, si = [], 0, 0
    for sp in specs:
        if sp.token_axis is None:
            out.append(jnp.take(state_store[si], slots, axis=sp.slot_axis))
            si += 1
            continue
        ax = sp.blocks_axis
        g = jnp.take(token_store[ti], tables, axis=ax)
        g = jnp.moveaxis(g, sp.in_page_axis + 1, ax + 2)  # pre+(B,nb,bs)+post
        g = g.reshape(g.shape[:ax] + (b, nb * block_size) + g.shape[ax + 3:])
        # batch now sits where the page axis was; restore the original order
        out.append(jnp.moveaxis(g, ax, sp.batch_axis))
        ti += 1
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3, 4))
def _scatter_prefill(specs, block_size, nb_used, token_store, state_store,
                     cache_leaves, tables, slots):
    b = tables.shape[0]
    new_token, new_state = list(token_store), list(state_store)
    ti, si = 0, 0
    for sp, leaf in zip(specs, cache_leaves):
        if sp.token_axis is None:
            new_state[si] = new_state[si].at[_ix(sp.slot_axis, slots)].set(
                leaf.astype(new_state[si].dtype))
            si += 1
            continue
        ax = sp.blocks_axis
        t_used = nb_used * block_size
        blk = jnp.take(leaf, jnp.arange(t_used), axis=sp.token_axis)
        blk = blk.reshape(blk.shape[:sp.token_axis] + (nb_used, block_size)
                          + blk.shape[sp.token_axis + 1:])
        # move batch to just before the page axis (splitting the token axis
        # shifted it by one when it followed the token axis)
        b_src = sp.batch_axis + (1 if sp.batch_axis > sp.token_axis else 0)
        blk = jnp.moveaxis(blk, b_src, ax)               # pre+(B,nb,bs)+post
        blk = jnp.moveaxis(blk, ax + 2, sp.in_page_axis + 1)   # store order
        ids = tables[:, :nb_used]                        # (B, nb_used)
        new_token[ti] = new_token[ti].at[_ix(ax, ids)].set(
            blk.astype(new_token[ti].dtype))
        ti += 1
    return new_token, new_state


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
def _scatter_token(specs, block_size, token_store, state_store,
                   cache_leaves, tables, slots, positions):
    """Write back only the page containing ``positions[i]`` per request."""
    blk_idx = positions // block_size                    # (B,)
    blk_ids = jnp.take_along_axis(tables, blk_idx[:, None], axis=1)[:, 0]
    new_token, new_state = list(token_store), list(state_store)
    ti, si = 0, 0
    for sp, leaf in zip(specs, cache_leaves):
        if sp.token_axis is None:
            new_state[si] = new_state[si].at[_ix(sp.slot_axis, slots)].set(
                leaf.astype(new_state[si].dtype))
            si += 1
            continue
        arr = jnp.moveaxis(leaf, (sp.batch_axis, sp.token_axis), (0, 1))
        slab = jax.vmap(
            lambda a, i: jax.lax.dynamic_slice_in_dim(
                a, i * block_size, block_size, axis=0)
        )(arr, blk_idx)                                  # (B, bs, *tail)
        ax = sp.blocks_axis
        slab = jnp.moveaxis(slab, (0, 1), (ax, ax + 1))  # pre+(B,bs)+post
        slab = jnp.moveaxis(slab, ax + 1, sp.in_page_axis)     # store order
        new_token[ti] = new_token[ti].at[_ix(ax, blk_ids)].set(
            slab.astype(new_token[ti].dtype))
        ti += 1
    return new_token, new_state
