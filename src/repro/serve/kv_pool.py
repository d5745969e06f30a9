"""Slot-based KV-cache pool for continuous batching.

One fixed cache tree of shape ``[n_layers, n_slots, max_len, ...]`` is
allocated once (per-layer K/V slabs for GQA, compressed latents for MLA)
and shared by every request the engine ever serves: a request checks a
*slot* (one batch row) out of the pool for its lifetime and the slot is
returned on retirement.  Because the tree's shapes never change, the jitted
prefill-chunk and decode steps compile exactly once — admission, retirement
and slot reuse are pure host-side bookkeeping plus in-place
``dynamic_update_slice`` / scatter writes (DESIGN.md §7).

This is paging at slot granularity: the unit of allocation is a whole
``max_len`` row rather than a fixed-size token block.  That forgoes
vLLM-style fine-grained page sharing but needs no gather indirection inside
the kernels.  ``PagedKVPool`` below is the block-paged backend behind the
same interface: ``admit`` / ``ensure`` / ``ensure_decode`` /
``register_prefix`` / ``free`` / ``lengths`` for the scheduler, and
``step_operands`` — the trailing arguments of the engine's step programs
(none for a slab, the page table for pages) — for the engine.  Which pool
a deployment runs is ``ServeConfig.paged``; nothing above the pool asks.

Slot hygiene: freed slots are NOT zeroed.  Every read is masked by the
explicit per-row valid length the scheduler passes to the model
(``kv_valid_len``), so stale bytes from a previous tenant are never
attended; the next tenant's prefill overwrites positions [0, P) before any
read of them.  ``lengths[slot]`` is the single source of truth for how many
positions of a slot are committed.

**Capacity is a function of KV bytes per token** (DESIGN.md §9): the pool
dtype knob (``kv_dtype`` = 'bf16' | 'int8' | 'fp8') sets how many bytes one
cached position costs, and ``slots_for_budget`` turns a cache-memory budget
into a slot count — quantizing the cache is how the same budget serves
roughly twice the concurrent requests.
"""
from __future__ import annotations

import heapq
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.quant.kv_cache import kv_dtype_name

# Families whose cache tree is stacked per-layer KV slabs with a batch
# (= slot) axis at position 1.  SSM/hybrid state pools would be a different
# (cheaper) layout; audio additionally caches the encoder output.
POOLABLE_FAMILIES = ("dense", "moe", "vlm")


def _spec_bytes(tree) -> int:
    """Total bytes of a cache tree (arrays or ShapeDtypeStructs)."""
    return sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


def bytes_per_slot(cfg: T.ModelConfig, max_len: int, *, kv_dtype="bf16",
                   align: int = 1) -> int:
    """Allocated cache bytes one pool slot costs (all layers, K+V, scales
    included for quantized dtypes; computed from the abstract cache spec so
    it can never drift from what ``init_cache`` actually allocates)."""
    capacity = -(-max_len // align) * align
    spec = T.init_cache(cfg, 1, capacity, abstract=True, kv_dtype=kv_dtype)
    return _spec_bytes(spec)


def slots_for_budget(cfg: T.ModelConfig, max_len: int, budget_bytes: int, *,
                     kv_dtype="bf16", align: int = 1) -> int:
    """How many ``max_len`` slots fit a cache-memory budget at ``kv_dtype``."""
    per = bytes_per_slot(cfg, max_len, kv_dtype=kv_dtype, align=align)
    n = int(budget_bytes) // per
    if n < 1:
        raise ValueError(
            f"cache budget {budget_bytes} B < one {max_len}-position slot "
            f"({per} B at kv_dtype={kv_dtype_name(kv_dtype)!r})")
    return n


class KVCachePool:
    paged = False       # fixed-slab layout: no page indirection on the slot

    def __init__(self, cfg: T.ModelConfig, n_slots: int, max_len: int, *,
                 kv_dtype=jnp.bfloat16, align: int = 1):
        """``align``: allocation granularity of the sequence axis.  The
        engine passes its prefill chunk size so every chunk's write window
        [k*C, (k+1)*C) fits the slab even when ``max_len`` is not
        chunk-aligned — ``dynamic_update_slice`` clamps out-of-range
        starts, which would silently shift the write otherwise.  Reads are
        bounded by per-row valid lengths, so the pad tail is never
        attended."""
        if cfg.family not in POOLABLE_FAMILIES:
            raise ValueError(
                f"KVCachePool supports {POOLABLE_FAMILIES} families, "
                f"not {cfg.family!r} (recurrent/enc-dec state pooling is a "
                f"separate layout)")
        assert n_slots >= 1 and max_len >= 1 and align >= 1
        self.cfg = cfg
        self.n_slots = n_slots
        self.n_rows = n_slots            # rows of the cache's slot axis
        self.max_len = max_len                            # logical capacity
        self.capacity = -(-max_len // align) * align      # allocated positions
        self.kv_dtype = kv_dtype_name(kv_dtype)
        # what the engine's compiled steps are keyed on: shapes and tier
        self.geometry = (n_slots, self.capacity, self.kv_dtype)
        self.cache = T.init_cache(cfg, n_slots, self.capacity,
                                  kv_dtype=kv_dtype)
        self.shardings = None           # set by place() under a device mesh
        self.lengths = np.zeros((n_slots,), np.int32)   # committed positions
        self._free: List[int] = list(range(n_slots))    # min-heap of slot ids
        heapq.heapify(self._free)

    def place(self, shardings) -> "KVCachePool":
        """Commit the cache tree to a device mesh: one NamedSharding per
        slab (``partitioning.serve_pool_pspec``: slots on the data axis,
        heads on 'model').  The engine's mesh-aware jits pin the same
        shardings on their cache in/outputs, so the slabs never migrate
        after this one placement and buffer donation stays in-place
        (DESIGN.md §10).  Host-side bookkeeping (lengths / free heap) is
        untouched — the scheduler cannot tell a sharded pool from a local
        one."""
        self.shardings = shardings
        self.cache = jax.device_put(self.cache, shardings)
        return self

    # -- memory accounting -------------------------------------------------
    @property
    def cache_bytes(self) -> int:
        """Allocated bytes of the whole cache tree (codes + scales)."""
        return _spec_bytes(self.cache)

    @property
    def bytes_per_token(self) -> int:
        """Cache bytes one committed position costs across all layers."""
        return self.cache_bytes // (self.n_slots * self.capacity)

    # -- allocation --------------------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.n_slots - len(self._free)

    @property
    def occupancy(self) -> float:
        return self.n_used / self.n_slots

    def alloc(self) -> Optional[int]:
        """Check out the lowest free slot id (deterministic placement), or
        None when the pool is full."""
        if not self._free:
            return None
        slot = heapq.heappop(self._free)
        self.lengths[slot] = 0
        return slot

    def free(self, slot: int) -> None:
        assert 0 <= slot < self.n_slots
        assert slot not in self._free, f"double free of slot {slot}"
        self.lengths[slot] = 0
        heapq.heappush(self._free, slot)

    def room(self, slot: int) -> int:
        """Cache positions still writable in ``slot``."""
        return self.max_len - int(self.lengths[slot])

    # -- the write path (no-ops: every slab position is already private) --
    def admit(self, tokens: Sequence[int], max_new: int
              ) -> Optional[Tuple[int, int, int]]:
        """``(slot, prefill_pos, hit_tokens)`` = (lowest free slot, 0, 0),
        or None when the pool is full.  A slot already holds its worst
        case and shares nothing, so the request itself does not matter."""
        slot = self.alloc()
        return None if slot is None else (slot, 0, 0)

    def ensure(self, slot: int, upto: int) -> None:
        """Nothing to pin: the slot owns every position it may write."""

    def ensure_decode(self, slots: Sequence[int], k: int = 1,
                      rems: Optional[Sequence[int]] = None) -> None:
        """Nothing to pin: the slot owns every position it may write."""

    def register_prefix(self, slot: int, tokens: Sequence[int]) -> int:
        """A slab shares no prefix; returns the 0 pages registered."""
        return 0

    def step_operands(self, slot: Optional[int] = None) -> tuple:
        """Trailing host arguments of the engine's step programs: none —
        the slab's slot axis is its layout."""
        return ()


# ===========================================================================
# Paged pool: shared page arena + per-slot page tables (DESIGN.md §15)
# ===========================================================================
#
# The slab pool above reserves worst-case ``capacity`` positions per slot.
# The paged pool instead stores every layer's cache as a page *arena*
# ``[L, n_pages, page_size, ...]`` and gives each slot a page table
# ``page_table[slot] -> [pages_per_slot] int32``; pages are allocated as a
# request's committed length grows, refcounted, shared copy-on-write across
# requests whose token prefixes match page-by-page, and evicted LRU when the
# arena runs dry.  Page 0 is a reserved garbage page: unmapped table entries
# point at it, so the jitted gather/scatter (quant/kv_cache.gather_pages /
# scatter_pages) needs no masking — page 0's bytes are only ever gathered
# into positions >= kv_valid_len, which the attention mask zeroes exactly.
#
# The load-bearing invariant (documented and enforced here, relied on by
# scatter_pages): **no shared page ever sits at any slot's write position.**
# Decode/burst steps write a KV row at ``lengths[slot]`` for *every* slot —
# including inactive and mid-prefill ones (the write is unconditional inside
# the jitted step; slab semantics made that harmless because each slot owned
# its row).  ``ensure()`` keeps it harmless here: before any step may write
# positions [lengths, upto) of a slot, every page covering that range is made
# privately owned — entry 0 gets a fresh page, a shared (refcount > 1) entry
# is copy-on-write duplicated.  Everything else a write can touch is either
# already private or the garbage page.

_ROOT_KEY = ("kv-prefix-root",)


def _copy_page_fn(cache, src, dst):
    """arena[:, dst] <- arena[:, src] on every leaf (COW page duplication).
    Donated + jitted once per cache structure; src/dst are traced scalars so
    repeated COWs reuse one executable."""
    return jax.tree_util.tree_map(lambda a: a.at[:, dst].set(a[:, src]),
                                  cache)


_copy_page = jax.jit(_copy_page_fn, donate_argnums=(0,))


def bytes_per_page(cfg: T.ModelConfig, page_size: int, *,
                   kv_dtype="bf16") -> int:
    """Allocated cache bytes one arena page costs (all layers, K+V,
    scales included for quantized dtypes)."""
    spec = T.init_cache(cfg, 1, page_size, abstract=True, kv_dtype=kv_dtype)
    return _spec_bytes(spec)


def pages_for_budget(cfg: T.ModelConfig, max_len: int, budget_bytes: int, *,
                     kv_dtype="bf16", page_size: int, align: int = 1) -> int:
    """How many arena pages fit a cache-memory budget at ``kv_dtype``.

    The page-granular replacement for ``slots_for_budget``: the budget buys
    ``budget // bytes_per_page`` pages outright — no worst-case ``max_len``
    rounding per request.  Requires room for the reserved garbage page plus
    one worst-case request (so admission can always make progress)."""
    assert page_size >= 1 and page_size % align == 0
    per = bytes_per_page(cfg, page_size, kv_dtype=kv_dtype)
    n = int(budget_bytes) // per
    capacity = -(-max_len // page_size) * page_size
    floor = 1 + capacity // page_size           # garbage page + one full slot
    if n < floor:
        raise ValueError(
            f"cache budget {budget_bytes} B < {floor} pages of {page_size} "
            f"positions ({per} B/page at kv_dtype={kv_dtype_name(kv_dtype)!r})"
            f" — too small for one {max_len}-position request")
    return n


class PageAllocator:
    """Host-side bookkeeping of the page arena: free list, refcounts, page
    tables, content-keyed prefix cache and LRU eviction.  Pure python over
    numpy tables — no device arrays — so the whole state machine is
    property-testable (tests/test_paged_properties.py).  The only device
    effect it ever *requests* is a page copy: mutating calls return a list
    of ``(src, dst)`` page copies for the owner to execute on the arena.

    Refcount accounting: ``refcounts[p]`` = number of slot table entries
    equal to ``p``, plus 1 if the prefix cache holds ``p`` (a *cache ref*).
    A page at refcount 0 is free; a registered page at refcount 1 is held
    only by the cache and sits in the LRU ``evictable`` queue — eviction
    unregisters it and hands it out as a fresh page.

    Prefix keys are nested content tuples: ``key_i = (key_{i-1},
    tuple(tokens[i*ps:(i+1)*ps]))``.  Exact token-chain equality — a "hash
    match" with no collisions — so adopting a cached page is always sound.
    """

    def __init__(self, n_pages: int, page_size: int, n_slots: int,
                 pages_per_slot: int, *, align: int = 1):
        assert n_pages >= 1 + pages_per_slot, \
            f"arena of {n_pages} pages cannot hold garbage page + one slot " \
            f"({pages_per_slot} pages)"
        assert page_size % align == 0, \
            f"page_size {page_size} must be a multiple of the prefill " \
            f"chunk {align} (pages are chunk-aligned by construction)"
        self.n_pages = n_pages
        self.page_size = page_size
        self.n_slots = n_slots
        self.pages_per_slot = pages_per_slot
        self.align = align
        self.capacity = pages_per_slot * page_size
        # page 0 reserved as the garbage page — never allocated.
        self._free_pages: List[int] = list(range(1, n_pages))
        heapq.heapify(self._free_pages)
        self.refcounts = np.zeros((n_pages,), np.int32)
        self.table = np.zeros((n_slots, pages_per_slot), np.int32)
        self._free_slots: List[int] = list(range(n_slots))
        heapq.heapify(self._free_slots)
        self.prefix_map: Dict[tuple, int] = {}   # chain key -> page id
        self.page_key: Dict[int, tuple] = {}     # page id -> chain key
        self.evictable: "OrderedDict[int, None]" = OrderedDict()  # LRU
        # pages promised to admitted-but-not-yet-allocated growth, so a
        # later admission can't strand an in-flight request mid-decode.
        self._slot_reserve = np.zeros((n_slots,), np.int32)
        self._reserved = 0
        # counters (read by pool/scheduler metrics)
        self.n_evictions = 0
        self.n_cow_copies = 0

    # -- internal page lifecycle -------------------------------------------
    def _evict_lru(self) -> int:
        page, _ = self.evictable.popitem(last=False)     # least recently used
        key = self.page_key.pop(page)
        del self.prefix_map[key]
        self.refcounts[page] -= 1                        # drop the cache ref
        assert self.refcounts[page] == 0
        self.n_evictions += 1
        return page

    def _alloc_page(self, slot: int) -> int:
        if self._free_pages:
            page = heapq.heappop(self._free_pages)
        elif self.evictable:
            page = self._evict_lru()
        else:
            raise RuntimeError(
                "page arena exhausted: admission reservations should make "
                "this unreachable — allocator invariant violated")
        self.refcounts[page] = 1
        if self._slot_reserve[slot] > 0:
            self._slot_reserve[slot] -= 1
            self._reserved -= 1
        return page

    def _deref(self, page: int) -> None:
        self.refcounts[page] -= 1
        rc = int(self.refcounts[page])
        assert rc >= 0, f"refcount underflow on page {page}"
        if page in self.page_key:
            if rc == 1:      # cache-only now: eligible for eviction (MRU end)
                self.evictable[page] = None
                self.evictable.move_to_end(page)
            assert rc >= 1, f"registered page {page} lost its cache ref"
        elif rc == 0:
            heapq.heappush(self._free_pages, page)

    def _ref(self, page: int) -> None:
        self.refcounts[page] += 1
        if page in self.evictable:       # back in active use: not evictable
            del self.evictable[page]

    # -- prefix cache ------------------------------------------------------
    def match(self, tokens: Sequence[int]) -> List[int]:
        """Longest page-aligned cached prefix of ``tokens``: the list of
        cached page ids covering tokens[0 : len(pages)*page_size]."""
        key = _ROOT_KEY
        pages: List[int] = []
        limit = min(len(tokens) // self.page_size, self.pages_per_slot)
        for i in range(limit):
            key = (key, tuple(
                int(t) for t in
                tokens[i * self.page_size:(i + 1) * self.page_size]))
            page = self.prefix_map.get(key)
            if page is None:
                break
            pages.append(page)
        return pages

    def _admission_plan(self, tokens: Sequence[int], max_new: int):
        P = len(tokens)
        ps = self.page_size
        need_total = min(-(-(P + max_new) // ps), self.pages_per_slot)
        pages = self.match(tokens)
        hit_tokens = len(pages) * ps
        full_cover = hit_tokens >= P
        if full_cover:
            # Re-prefill only the final chunk so the engine still produces
            # the first-token logits; its page is COW'd by ensure().
            prefill_pos = ((P - 1) // self.align) * self.align
        else:
            prefill_pos = hit_tokens
        need_new = need_total - len(pages) + (1 if full_cover else 0)
        return pages, hit_tokens, prefill_pos, need_new

    def can_admit(self, tokens: Sequence[int], max_new: int) -> bool:
        if not self._free_slots:
            return False
        pages, _, _, need_new = self._admission_plan(tokens, max_new)
        adopted_evictable = sum(1 for p in pages if p in self.evictable)
        avail = (len(self._free_pages) + len(self.evictable)
                 - adopted_evictable - self._reserved)
        return avail >= need_new

    def admit(self, tokens: Sequence[int], max_new: int
              ) -> Optional[Tuple[int, int, int, List[Tuple[int, int]]]]:
        """Admit a request: adopt its cached prefix pages and reserve arena
        room for its worst-case growth.  Returns ``(slot, prefill_pos,
        hit_tokens, copies)`` — prefill resumes at ``prefill_pos`` (0 on a
        full miss; the prompt tail past the cached pages otherwise) — or
        None when no slot or not enough pages are available.  ``copies``
        are ``(src, dst)`` arena page copies the caller must execute."""
        if not self.can_admit(tokens, max_new):
            return None
        pages, hit_tokens, prefill_pos, need_new = \
            self._admission_plan(tokens, max_new)
        slot = heapq.heappop(self._free_slots)
        for i, page in enumerate(pages):
            self.table[slot, i] = page
            self._ref(page)
        self._slot_reserve[slot] = need_new
        self._reserved += need_new
        # The write-position invariant: the page under prefill_pos (where
        # the next dispatch writes) must be privately owned NOW — in the
        # full-cover case it is an adopted shared page and gets COW'd here.
        copies = self.ensure(slot, prefill_pos, prefill_pos + 1)
        return slot, prefill_pos, hit_tokens, copies

    def ensure(self, slot: int, committed: int, upto: int
               ) -> List[Tuple[int, int]]:
        """Make every page covering positions [committed, upto) of ``slot``
        privately writable: entry 0 -> fresh page; shared (refcount > 1)
        entry -> copy-on-write duplicate.  Returns the ``(src, dst)`` page
        copies to execute.  Idempotent; must run before any jitted step may
        write those positions."""
        upto = min(upto, self.capacity)
        copies: List[Tuple[int, int]] = []
        for idx in range(committed // self.page_size,
                         -(-upto // self.page_size)):
            entry = int(self.table[slot, idx])
            if entry == 0:
                self.table[slot, idx] = self._alloc_page(slot)
            elif int(self.refcounts[entry]) > 1:
                fresh = self._alloc_page(slot)
                copies.append((entry, fresh))
                self.table[slot, idx] = fresh
                self._deref(entry)
                self.n_cow_copies += 1
        return copies

    def register_prefix(self, slot: int, tokens: Sequence[int]) -> int:
        """Publish ``slot``'s fully-prefilled prompt pages into the prefix
        cache (called once, when prefill completes).  Only whole pages are
        cacheable; already-cached chains are deduped (the slot keeps its
        private copy unregistered).  Returns pages newly registered."""
        key = _ROOT_KEY
        registered = 0
        ps = self.page_size
        for i in range(min(len(tokens) // ps, self.pages_per_slot)):
            key = (key, tuple(int(t) for t in tokens[i * ps:(i + 1) * ps]))
            if key in self.prefix_map:
                continue
            page = int(self.table[slot, i])
            assert page != 0, "registering an unmapped prompt page"
            self.prefix_map[key] = page
            self.page_key[page] = key
            self.refcounts[page] += 1        # the cache ref
            registered += 1
        return registered

    def free_slot(self, slot: int) -> None:
        assert 0 <= slot < self.n_slots
        assert slot not in self._free_slots, f"double free of slot {slot}"
        for idx in range(self.pages_per_slot):
            entry = int(self.table[slot, idx])
            if entry != 0:
                self._deref(entry)
        self.table[slot, :] = 0
        self._reserved -= int(self._slot_reserve[slot])
        self._slot_reserve[slot] = 0
        heapq.heappush(self._free_slots, slot)

    # -- accounting --------------------------------------------------------
    @property
    def n_free_slots(self) -> int:
        return len(self._free_slots)

    @property
    def pages_free(self) -> int:
        return len(self._free_pages)

    @property
    def pages_cached(self) -> int:
        """Cache-only (refcount-1 registered) pages, evictable LRU."""
        return len(self.evictable)

    @property
    def pages_in_use(self) -> int:
        """Pages held by at least one slot table (excludes cache-only)."""
        return self.n_pages - 1 - self.pages_free - self.pages_cached

    def check(self) -> None:
        """Assert every allocator invariant (the property-test oracle)."""
        table_refs = np.bincount(self.table.reshape(-1),
                                 minlength=self.n_pages)
        table_refs[0] = 0
        cache_refs = np.zeros((self.n_pages,), np.int64)
        for page in self.page_key:
            cache_refs[page] += 1
        expect = table_refs + cache_refs
        assert (self.refcounts == expect).all(), \
            f"refcount drift: {self.refcounts.tolist()} != {expect.tolist()}"
        free = set(self._free_pages)
        assert len(free) == len(self._free_pages), "duplicate free pages"
        assert 0 not in free and 0 not in self.page_key \
            and 0 not in self.evictable, "garbage page 0 leaked into lists"
        assert all(self.refcounts[p] == 0 for p in free), \
            "free page with live refs"
        assert free.isdisjoint(self.evictable), "page both free and evictable"
        assert all(p in self.page_key and self.refcounts[p] == 1
                   for p in self.evictable), "evictable page not cache-only"
        assert all(self.refcounts[p] >= 1 for p in self.page_key), \
            "registered page with no refs"
        assert {self.prefix_map[k] for k in self.prefix_map} \
            == set(self.page_key), "prefix_map / page_key out of sync"
        assert self._reserved == int(self._slot_reserve.sum())
        for slot in self._free_slots:
            assert (self.table[slot] == 0).all(), "freed slot keeps pages"
        # no leaks: every non-garbage page is free, cached-only or in a table
        accounted = len(free) + int((table_refs > 0).sum()) \
            + sum(1 for p in self.page_key if table_refs[p] == 0)
        assert accounted == self.n_pages - 1, \
            f"page leak: {accounted} accounted of {self.n_pages - 1}"


class PagedKVPool:
    """Paged drop-in for ``KVCachePool``: the same scheduler- and
    engine-facing surface, where page-aware admission (``admit``),
    write-window pinning (``ensure`` / ``ensure_decode``), prefix
    publication (``register_prefix``) and the page-table step operand do
    real work.  Device state is the per-layer page arena
    ``[L, n_pages, page_size, ...]`` and the host-side ``page_table`` that
    the engine ships to its jitted steps; all paging policy lives in the
    ``PageAllocator``."""

    paged = True

    def __init__(self, cfg: T.ModelConfig, n_slots: int, max_len: int, *,
                 kv_dtype=jnp.bfloat16, align: int = 1,
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None):
        if cfg.family not in POOLABLE_FAMILIES:
            raise ValueError(
                f"PagedKVPool supports {POOLABLE_FAMILIES} families, "
                f"not {cfg.family!r}")
        assert n_slots >= 1 and max_len >= 1 and align >= 1
        page_size = align if page_size is None else page_size
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.page_size = page_size
        self.capacity = -(-max_len // page_size) * page_size
        self.pages_per_slot = self.capacity // page_size
        if n_pages is None:       # full provisioning: slab parity + garbage
            n_pages = 1 + n_slots * self.pages_per_slot
        self.n_pages = self.n_rows = n_pages
        self.kv_dtype = kv_dtype_name(kv_dtype)
        self.geometry = (n_slots, self.capacity, self.kv_dtype, n_pages,
                         page_size)
        self.allocator = PageAllocator(n_pages, page_size, n_slots,
                                       self.pages_per_slot, align=align)
        self.cache = T.init_cache(cfg, n_pages, page_size, kv_dtype=kv_dtype)
        self.shardings = None
        self.lengths = np.zeros((n_slots,), np.int32)
        # prefix-cache effectiveness counters (metrics / bench)
        self.n_prefix_hits = 0
        self.n_prefix_misses = 0
        self.prefix_hit_tokens_total = 0

    def place(self, shardings) -> "PagedKVPool":
        """Commit the arena to a device mesh (pages ride the slot axis of
        ``serve_pool_pspec``, heads on 'model' — see engine.pool_shardings).
        Page-table/bookkeeping stays host-side, exactly like slab lengths."""
        self.shardings = shardings
        self.cache = jax.device_put(self.cache, shardings)
        return self

    @property
    def page_table(self) -> np.ndarray:
        return self.allocator.table

    def step_operands(self, slot: Optional[int] = None) -> tuple:
        """Trailing host arguments of the engine's step programs: the page
        table [n_slots, pages_per_slot], or ``slot``'s row
        [1, pages_per_slot] for a prefill chunk."""
        table = self.page_table
        return (table if slot is None else table[slot:slot + 1],)

    # -- memory accounting -------------------------------------------------
    @property
    def cache_bytes(self) -> int:
        return _spec_bytes(self.cache)

    @property
    def bytes_per_token(self) -> int:
        return self.cache_bytes // (self.n_pages * self.page_size)

    # -- slot / page availability ------------------------------------------
    @property
    def n_free(self) -> int:
        return self.allocator.n_free_slots

    @property
    def n_used(self) -> int:
        return self.n_slots - self.n_free

    @property
    def occupancy(self) -> float:
        return self.n_used / self.n_slots

    @property
    def pages_in_use(self) -> int:
        return self.allocator.pages_in_use

    @property
    def pages_cached(self) -> int:
        return self.allocator.pages_cached

    @property
    def pages_free(self) -> int:
        return self.allocator.pages_free

    def room(self, slot: int) -> int:
        return self.max_len - int(self.lengths[slot])

    # -- request lifecycle -------------------------------------------------
    def can_admit(self, tokens: Sequence[int], max_new: int) -> bool:
        return self.allocator.can_admit(tokens, max_new)

    def admit(self, tokens: Sequence[int], max_new: int
              ) -> Optional[Tuple[int, int, int]]:
        """Admit on pages available: returns ``(slot, prefill_pos,
        hit_tokens)`` or None.  ``prefill_pos > 0`` means the prompt's
        first ``hit_tokens`` positions were adopted from the prefix cache
        and prefill resumes mid-prompt (or, on a full-cover hit, re-runs
        only the final chunk for its logits)."""
        out = self.allocator.admit(tokens, max_new)
        if out is None:
            return None
        slot, prefill_pos, hit_tokens, copies = out
        self.lengths[slot] = prefill_pos
        self._run_copies(copies)
        if hit_tokens > 0:
            self.n_prefix_hits += 1
            self.prefix_hit_tokens_total += hit_tokens
        else:
            self.n_prefix_misses += 1
        return slot, prefill_pos, hit_tokens

    def ensure(self, slot: int, upto: int) -> None:
        """Pin the write window [lengths[slot], upto): allocate/COW pages so
        the jitted steps may write there without touching shared state."""
        self._run_copies(self.allocator.ensure(
            slot, int(self.lengths[slot]), upto))

    def ensure_decode(self, slots: Sequence[int], k: int = 1,
                      rems: Optional[Sequence[int]] = None) -> None:
        """Pin every decoding slot's write window for a ``k``-step
        decode/burst dispatch (the scheduler calls this each step).

        ``rems`` (remaining new tokens per slot) caps the pinned window:
        a row that finishes mid-burst keeps issuing writes at its frozen
        length, but those are garbage rows that flow through unmapped
        (entry-0) table slots into the reserved garbage page — only
        positions that will actually be *committed* (at most
        ``min(k, rem)`` of them) need privately mapped pages.  This keeps
        page allocation within the admission-time reservation."""
        for i, slot in enumerate(slots):
            kk = k if rems is None else min(k, int(rems[i]))
            self.ensure(slot, int(self.lengths[slot]) + kk)

    def register_prefix(self, slot: int, tokens: Sequence[int]) -> int:
        """Publish the prompt's whole pages to the prefix cache once
        prefill completes (content-keyed; deduped against existing chains)."""
        return self.allocator.register_prefix(slot, tokens)

    def free(self, slot: int) -> None:
        """Retire a request: drop its page refs (shared pages survive for
        other holders; cache-only pages become evictable; private pages
        return to the free list) and release the slot."""
        self.allocator.free_slot(slot)
        self.lengths[slot] = 0

    def _run_copies(self, copies: List[Tuple[int, int]]) -> None:
        for src, dst in copies:
            self.cache = _copy_page(self.cache, jnp.int32(src),
                                    jnp.int32(dst))
