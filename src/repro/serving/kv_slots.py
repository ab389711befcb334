"""Slot-based KV / recurrent-state pools for continuous batching.

One padded decode batch of ``n_slots`` rows serves requests of different
ages: slot ``b`` owns row ``b`` of every cache leaf plus a per-slot length.
Admission writes a batch-1 prefill cache into a free slot; decode steps the
whole pool with a (B,) length vector; retirement just marks the slot free
(stale KV beyond a slot's length is never attended to, so no zeroing).

Cache pytrees differ per family (attention K/V with a capacity axis, SSM /
RWKV recurrent state without one) and per layout (unstacked ``prefix``
layers carry batch at axis 0, scanned ``stack`` layers at axis 1). Rather
than hard-coding that, the batch axis of every leaf is discovered once by
shape-probing ``init_cache`` — the pool works for any model whose prefill
cache matches its ``init_cache`` tree structure.

Two pools share that probing trick:

* :class:`SlotPool` — capacity-dense: every slot owns ``capacity`` cache
  rows whether it uses them or not.
* :class:`PagedSlotPool` — block-paged: attention K/V leaves become a
  shared page pool ``(n_pages, Hkv, page_size, D)`` plus per-slot block
  tables (physical page ids); pages are reserved at admission, allocated
  lazily as a slot's length crosses page boundaries, and returned on
  retirement. Slot count decouples from context capacity: provisioned HBM
  is ``n_pages`` pages, not ``n_slots × capacity`` rows, and decode reads
  scale with live lengths (see kernels/paged_decode_attention.py).
  Physical page 0 is reserved as the null sink for pad/inactive writes.

The paged pool is also a cross-request *prefix cache*: pages are
ref-counted and full prompt pages can be published into a content-
addressed prefix index (a hash chain keyed by ``(parent_chain_hash,
page_token_ids)``, radix-style). Admission matches a prompt against the
chain, adopts the shared pages (refcount bump), and only the uncached
suffix needs prefill. Registered pages whose refcount drops to zero move
to an LRU list instead of the free list — a hot prefix survives across
requests and is only evicted lazily when an allocation cannot be served
from truly free pages. Registered pages are immutable: a slot whose
final (partial) page is shared copies it into a private page before its
own K/V writes land (copy-on-write).
"""

from __future__ import annotations

import hashlib
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

_CHAIN_ROOT = b"\x00" * 20


def _chain_hash(parent: bytes, chunk: np.ndarray) -> bytes:
    """Content address of a token prefix: digest over (parent digest,
    this page's token ids). Collision-safe (sha1), unlike ``hash()``."""
    return hashlib.sha1(
        parent + np.asarray(chunk, "<i4").tobytes()).digest()


def _first_diff_axis(a, b) -> int:
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i
    return -1


def cache_batch_axes(init_cache: Callable) -> PyTree:
    """Per-leaf batch-axis index, discovered by probing ``init_cache`` with
    two batch sizes (int leaves, same treedef as the cache)."""
    s1 = jax.eval_shape(lambda: init_cache(1, 8))
    s2 = jax.eval_shape(lambda: init_cache(2, 8))
    axes = jax.tree_util.tree_map(
        lambda a, b: _first_diff_axis(a.shape, b.shape), s1, s2)
    for ax in jax.tree_util.tree_leaves(axes):
        assert ax >= 0, "cache leaf without a batch axis"
    return axes


def write_slot(pool: PyTree, new: PyTree, batch_axes: PyTree,
               slot: jax.Array) -> PyTree:
    """Write a (batch=k, seq≤capacity) cache into pool rows [slot, slot+k).

    jit-able with a traced ``slot``; seq-shorter updates land at position 0
    of the capacity axis (prefill KV for a length-P prompt fills [0, P)).
    """
    def w(p, n, bax):
        starts = [0] * p.ndim
        starts[bax] = slot
        return jax.lax.dynamic_update_slice(p, n.astype(p.dtype),
                                            tuple(starts))
    return jax.tree_util.tree_map(w, pool, new, batch_axes)


def seat_prefill(init_cache: Callable, prefill_cache: PyTree, batch: int,
                 capacity: int) -> PyTree:
    """Expand a whole-batch prefill cache (seq axis = prompt length) into a
    capacity-sized decode cache — the uniform-batch ``generate`` path."""
    pool = init_cache(batch, capacity)
    axes = cache_batch_axes(init_cache)
    return write_slot(pool, prefill_cache, axes, jnp.asarray(0, jnp.int32))


class SlotPool:
    """Device-side cache pool + host-side per-slot lengths.

    The pool owns the decode cache pytree; ``insert`` seats a batch-1
    prefill cache into one slot (donating the old pool buffers), ``lens``
    is the (n_slots,) vector handed to ``decode_step`` each step.
    """

    def __init__(self, init_cache: Callable, n_slots: int, capacity: int):
        self.n_slots = n_slots
        self.capacity = capacity
        self.cache = init_cache(n_slots, capacity)
        self._axes = cache_batch_axes(init_cache)
        self.lens = np.zeros((n_slots,), np.int32)
        self._insert = jax.jit(
            lambda pool, new, slot: write_slot(pool, new, self._axes, slot),
            donate_argnums=(0,))
        self._insert_rows = jax.jit(self._insert_rows_fn, donate_argnums=(0,))

    def _insert_rows_fn(self, pool: PyTree, new: PyTree,
                        slots: jax.Array) -> PyTree:
        """Seat each batch row of ``new`` into slot ``slots[i]``. Rows are
        written in REVERSE order so grouped-admission padding works: pad
        rows (i ≥ real count) alias ``slots[0]`` and get overwritten by the
        real row 0, which lands last."""
        def row(n, bax, i):
            return jax.lax.slice_in_dim(n, i, i + 1, axis=bax)
        k = {leaf.shape[bax] for leaf, bax in zip(
            jax.tree_util.tree_leaves(new),
            jax.tree_util.tree_leaves(self._axes))}
        assert len(k) == 1, k
        for i in reversed(range(k.pop())):
            pool = jax.tree_util.tree_map(
                lambda p, n, bax: jax.lax.dynamic_update_slice(
                    p, row(n, bax, i).astype(p.dtype),
                    tuple(slots[i] if d == bax else 0
                          for d in range(p.ndim))),
                pool, new, self._axes)
        return pool

    def insert(self, prefill_cache: PyTree, slot: int, length: int) -> None:
        assert length <= self.capacity, (length, self.capacity)
        self.cache = self._insert(self.cache, prefill_cache,
                                  jnp.asarray(slot, jnp.int32))
        self.lens[slot] = length

    def insert_rows(self, prefill_cache: PyTree, slots: np.ndarray,
                    lengths: np.ndarray) -> None:
        """Grouped admission: batch rows of ``prefill_cache`` → slots.
        ``slots``/``lengths`` cover only the real rows; pad rows of the
        cache (if any) must already alias ``slots[0]`` in the full slots
        vector handed to the device (see engine._admit_group)."""
        assert max(lengths, default=0) <= self.capacity
        self.cache = self._insert_rows(self.cache, prefill_cache,
                                       jnp.asarray(slots, jnp.int32))
        for s, l in zip(slots[:len(lengths)], lengths):
            self.lens[s] = l

    def advance(self, slot: int) -> None:
        self.lens[slot] += 1

    def truncate(self, slot: int, length: int) -> None:
        """Rewind a slot to ``length`` valid positions (speculative-decode
        rollback). Stale K/V past the new frontier is never attended to —
        the per-slot length mask covers it — so only the length moves."""
        assert 0 <= length <= self.capacity, (length, self.capacity)
        self.lens[slot] = length

    def release(self, slot: int) -> None:
        self.lens[slot] = 0


class PagedSlotPool:
    """Block-paged decode cache: a shared page pool per attention leaf +
    per-slot block tables, with recurrent-state leaves kept slot-major.

    Leaf classes are discovered by shape-probing ``init_cache`` twice:
    leaves that change with the batch size are slot leaves (recurrent
    state), leaves that change with ``kv_pages`` are page leaves. The jit'd
    writer scatters prefill KV rows into table-mapped pages and seats slot
    leaves exactly like :class:`SlotPool`.

    Allocator lifecycle: ``reserve`` claims a slot's worst-case page budget
    at admission (so decode can never strand a running request without a
    page — oversubscription is resolved by admission control, not
    preemption); ``ensure`` allocates lazily from that budget as the
    length crosses page boundaries; ``release`` returns every allocated
    page to the free list and drops the remaining reservation.
    """

    def __init__(self, init_cache: Callable, n_slots: int, capacity: int, *,
                 page_size: int, n_pages: Optional[int] = None):
        assert page_size > 0
        self.n_slots = n_slots
        self.capacity = capacity
        self.page_size = page_size
        self.max_pages = -(-capacity // page_size)
        if n_pages is None:               # full provisioning (+ null page)
            n_pages = n_slots * self.max_pages + 1
        assert n_pages > 1, "need at least one page beyond the null page"
        self.n_pages = n_pages
        self.cache = init_cache(n_slots, capacity, kv_pages=n_pages,
                                page_size=page_size)

        probe = lambda b, p: jax.eval_shape(
            lambda: init_cache(b, capacity, kv_pages=p,
                               page_size=page_size))
        diff = lambda a, b: jax.tree_util.tree_map(
            lambda x, y: _first_diff_axis(x.shape, y.shape), a, b)
        self._batch_axes = diff(probe(1, n_pages), probe(2, n_pages))
        self._page_axes = diff(probe(1, n_pages), probe(1, n_pages + 1))
        for bax, pax in zip(jax.tree_util.tree_leaves(self._batch_axes),
                            jax.tree_util.tree_leaves(self._page_axes)):
            assert (bax >= 0) != (pax >= 0), \
                "cache leaf is neither slot-major nor paged"
        assert any(p >= 0
                   for p in jax.tree_util.tree_leaves(self._page_axes)), \
            "no attention K/V leaf to page — use SlotPool for this family"

        self.lens = np.zeros((n_slots,), np.int32)
        self.table = np.zeros((n_slots, self.max_pages), np.int32)
        self._free: deque[int] = deque(range(1, n_pages))   # 0 = null
        self._n_alloc = np.zeros((n_slots,), np.int32)
        self._reserved = np.zeros((n_slots,), np.int32)     # unallocated
        # scalar mirror of _reserved.sum(): free_pages() runs per-alloc and
        # per-admission, so it must not rescan the per-slot vector
        self._reserved_total = 0
        # -- prefix cache state -------------------------------------------
        self._refcount = np.zeros((n_pages,), np.int32)
        # chain hash -> first token -> {page_token_ids: page_id}: the
        # radix-style children map, bucketed by first token so the
        # partial-tail scan touches only same-first-token siblings
        # instead of every child registered under a hot node
        self._children: Dict[bytes, Dict[int, Dict[Tuple[int, ...], int]]] \
            = {}
        self._page_key: Dict[int, Tuple[bytes, Tuple[int, ...]]] = {}
        # registered refcount-0 pages, insertion order = LRU (dict keeps
        # insertion order; O(1) membership + removal)
        self._lru: Dict[int, None] = {}
        self.stats: Dict[str, int] = {}
        self.reset_stats()
        self._write = jax.jit(self._write_fn, donate_argnums=(0,))
        self._copy = jax.jit(self._copy_fn, donate_argnums=(0,))

    def reset_stats(self) -> None:
        self.stats.update(pages_allocated=0, evictions=0, cow_copies=0)

    # -- allocator ---------------------------------------------------------

    def free_pages(self) -> int:
        """Pages allocatable right now: truly free plus lazily evictable
        (registered, refcount-0 LRU) pages, minus outstanding
        reservations. Maintained as O(1) counters — no per-slot rescans."""
        return len(self._free) + len(self._lru) - self._reserved_total

    def page_gauges(self) -> Dict[str, int]:
        """The pool's state in three O(1) gauges that sum to
        ``n_pages - 1`` (the null page aside): pages some slot holds (each
        counted once however many slots share it), pages reserved at
        admission but not yet allocated, and pages allocatable now."""
        return {"kv_pages_in_use": self.n_pages - 1 - self.idle_pages(),
                "kv_pages_reserved": self._reserved_total,
                "kv_pages_free": self.free_pages()}

    def pages_needed(self, total_len: int) -> int:
        return -(-total_len // self.page_size)

    def _set_reserved(self, slot: int, n: int) -> None:
        self._reserved_total += n - int(self._reserved[slot])
        self._reserved[slot] = n

    def reserve(self, slot: int, total_len: int) -> bool:
        """Admission control: claim the slot's worst-case page budget
        (prompt + max_new_tokens). False → the caller must requeue."""
        need = self.pages_needed(total_len) - int(self._n_alloc[slot])
        if need > self.free_pages():
            return False
        self._set_reserved(slot, max(need, 0))
        return True

    def _take_free_page(self) -> int:
        """Pop a writable page: the free list first, else lazily evict the
        least-recently-retired registered page (dropping its index entry —
        descendants become unreachable and age out of the LRU the same
        way)."""
        if self._free:
            pid = self._free.popleft()
        else:
            assert self._lru, "page pool exhausted past its reservations"
            pid = next(iter(self._lru))
            del self._lru[pid]
            self._unregister(pid)
            self.stats["evictions"] += 1
        self._refcount[pid] = 1
        self.stats["pages_allocated"] += 1
        return pid

    def _alloc_page(self, slot: int) -> None:
        assert self._n_alloc[slot] < self.max_pages, \
            f"slot {slot} exceeds capacity {self.capacity}"
        pid = self._take_free_page()
        self.table[slot, self._n_alloc[slot]] = pid
        self._n_alloc[slot] += 1
        self._set_reserved(slot, max(0, int(self._reserved[slot]) - 1))

    def ensure(self, slot: int, length: int) -> None:
        """Alloc-on-advance: guarantee pages cover positions [0, length)."""
        while int(self._n_alloc[slot]) * self.page_size < length:
            self._alloc_page(slot)

    # -- prefix cache ------------------------------------------------------

    def _unregister(self, pid: int) -> None:
        h, chunk = self._page_key.pop(pid)
        bucket = self._children[h][chunk[0]]
        del bucket[chunk]
        if not bucket:
            del self._children[h][chunk[0]]
            if not self._children[h]:
                del self._children[h]

    def _drop_page_ref(self, pid: int) -> None:
        """Decrement a page's refcount; at zero, registered pages park on
        the LRU list (content kept — a hot prefix survives retirement),
        private pages return to the free list."""
        self._refcount[pid] -= 1
        assert self._refcount[pid] >= 0, f"refcount underflow on page {pid}"
        if self._refcount[pid] == 0:
            if pid in self._page_key:
                self._lru[pid] = None
            else:
                self._free.append(pid)

    def match_prefix(self, tokens: np.ndarray) -> Tuple[int, List[int]]:
        """Longest cached prefix of ``tokens``: exact-match full pages down
        the hash chain, then (if every full page matched) one partial tail
        page — a cached FULL page whose token ids start with the remaining
        sub-page tail. Read-only; returns (hit_tokens, page_ids)."""
        toks = np.asarray(tokens, np.int32)
        ps = self.page_size
        h, hit, pages = _CHAIN_ROOT, 0, []
        for i in range(len(toks) // ps):
            chunk = tuple(int(t) for t in toks[i * ps:(i + 1) * ps])
            pid = self._children.get(h, {}).get(chunk[0], {}).get(chunk)
            if pid is None:
                return hit, pages
            pages.append(pid)
            hit += ps
            h = _chain_hash(h, chunk)
        tail = tuple(int(t) for t in toks[hit:])
        if tail:
            # scan only siblings sharing the tail's first token (tuple
            # compares short-circuit at the first divergence)
            for ctoks, pid in self._children.get(h, {}).get(
                    tail[0], {}).items():
                if ctoks[:len(tail)] == tail:
                    pages.append(pid)
                    hit += len(tail)
                    break
        return hit, pages

    def admit_prefix(self, slot: int, tokens: np.ndarray,
                     total_len: int) -> Optional[int]:
        """Prefix-sharing admission: match ``tokens`` (the prompt; the
        final token is always left for the suffix so prefill produces the
        first-sample logits), adopt the shared pages into this slot's
        table, and reserve only the uncached-suffix page budget. Returns
        the hit length (0 → cold) or None when the pool cannot cover the
        request — in which case nothing was adopted or reserved."""
        assert self._n_alloc[slot] == 0 and self._reserved[slot] == 0, \
            f"slot {slot} admitted while still holding pages"
        toks = np.asarray(tokens, np.int32)
        hit, pages = self.match_prefix(toks[:-1])
        n_keep = hit // self.page_size          # full pages kept as-is
        # budget: every page past the kept full ones is a fresh allocation
        # (boundary-crossing allocs + the CoW copy of a partial tail page)
        need = self.pages_needed(total_len) - n_keep
        # adopted LRU pages leave the evictable set, so they cannot also
        # back the reservation — count them against availability
        n_from_lru = sum(1 for p in pages if p in self._lru)
        if need + n_from_lru > self.free_pages():
            return None
        for j, pid in enumerate(pages):
            self._refcount[pid] += 1
            self._lru.pop(pid, None)
            self.table[slot, j] = pid
        self._n_alloc[slot] = len(pages)
        # reserve the FULL fresh-page demand: boundary-crossing allocs
        # (pages_needed - len(pages)) plus, when a partial tail page was
        # adopted, the CoW copy that will replace it — i.e. exactly
        # ``need``. Reserving less lets free_pages() overstate and a
        # later reservation over-commit the pool.
        self._set_reserved(slot, need)
        return hit

    def ensure_writable(self, slot: int, pos: int
                        ) -> Optional[Tuple[int, int]]:
        """Copy-on-write: the page covering ``pos`` must be privately owned
        before this slot's K/V write at ``pos`` lands. Shared or registered
        pages are immutable — materialize a private copy (drawn from the
        slot's reservation), swap the table entry, drop the shared ref.
        Returns (src, dst) page ids for the caller to copy on device, or
        None when the page is already private."""
        col = pos // self.page_size
        assert col < self._n_alloc[slot], \
            f"slot {slot} position {pos} has no page (call ensure first)"
        pid = int(self.table[slot, col])
        if self._refcount[pid] == 1 and pid not in self._page_key:
            return None
        dst = self._take_free_page()
        self.table[slot, col] = dst
        self._drop_page_ref(pid)
        self._set_reserved(slot, max(0, int(self._reserved[slot]) - 1))
        self.stats["cow_copies"] += 1
        return pid, dst

    def register_prefix(self, slot: int, tokens: np.ndarray) -> None:
        """Publish this slot's FULL prompt pages into the prefix index so
        later admissions can adopt them. First writer wins: chunks already
        present (including pages this slot itself adopted) are skipped, as
        are pages already registered under another key. Partial final
        pages are never registered — they are the slot's private write
        frontier (decode K/V lands there)."""
        toks = np.asarray(tokens, np.int32)
        ps = self.page_size
        h = _CHAIN_ROOT
        for i in range(len(toks) // ps):
            chunk = tuple(int(t) for t in toks[i * ps:(i + 1) * ps])
            kids = self._children.setdefault(h, {}).setdefault(chunk[0], {})
            pid = int(self.table[slot, i])
            if chunk not in kids and pid not in self._page_key and pid != 0:
                kids[chunk] = pid
                self._page_key[pid] = (h, chunk)
            h = _chain_hash(h, chunk)

    def reset_prefix(self) -> None:
        """Drop the whole prefix index (e.g. after warmup): refcount-0
        registered pages return to the free list; pages still adopted by
        live slots just lose their index entry and free on release."""
        for pid in list(self._page_key):
            self._unregister(pid)
        self._free.extend(self._lru)
        self._lru.clear()
        self.reset_stats()

    def copy_pages(self, src: np.ndarray, dst: np.ndarray) -> None:
        """Device-side CoW materialization: copy page rows ``src[i]`` →
        ``dst[i]`` in every paged leaf (one jit'd gather+scatter for the
        whole batch of copies)."""
        self.cache = self._copy(self.cache, jnp.asarray(src, jnp.int32),
                                jnp.asarray(dst, jnp.int32))

    def _copy_fn(self, pool: PyTree, src: jax.Array,
                 dst: jax.Array) -> PyTree:
        def c(p, pax):
            if pax < 0:
                return p
            vals = jnp.take(p, src, axis=pax)
            idx = (slice(None),) * pax + (dst,)
            return p.at[idx].set(vals)
        return jax.tree_util.tree_map(
            lambda p, pax: c(p, pax), pool, self._page_axes)

    # -- cache writes ------------------------------------------------------

    def _write_fn(self, pool: PyTree, new: PyTree, dest: jax.Array,
                  slots: jax.Array) -> PyTree:
        """Paged leaves: one scatter of every (row, position) prefill entry
        into its pool row ``dest = table[row, pos // ps] * ps + pos % ps``,
        i.e. page ``dest // ps`` at in-page offset ``dest % ps`` (pad rows /
        positions past a slot's pages carry table id 0 and land in the null
        page). Slot leaves: reverse-order row writes as in
        :meth:`SlotPool._insert_rows_fn`."""
        ps = self.page_size
        pid, off = dest // ps, dest % ps

        def w(p, n, bax, pax):
            if pax >= 0:
                # the pool leaf is (..., n_pages, Hkv, page_size[, D]) and
                # the prefill leaf (..., batch, seq, Hkv[, D]) with the same
                # leading stacking dims: merge (batch, seq) into one row
                # axis and move it first, where numpy-style indexing puts
                # the (page, offset) index pair split by the Hkv slice
                src = n.reshape(n.shape[:pax] + (-1,) + n.shape[pax + 2:])
                src = jnp.moveaxis(src, pax, 0)
                idx = (slice(None),) * pax + (pid, slice(None), off)
                return p.at[idx].set(src.astype(p.dtype))
            return p

        pool = jax.tree_util.tree_map(w, pool, new, self._batch_axes,
                                      self._page_axes)

        def row(n, bax, i):
            return jax.lax.slice_in_dim(n, i, i + 1, axis=bax)

        def w_slot(p, n, bax, pax, i):
            if pax >= 0:
                return p
            return jax.lax.dynamic_update_slice(
                p, row(n, bax, i).astype(p.dtype),
                tuple(slots[i] if d == bax else 0 for d in range(p.ndim)))

        k_rows = slots.shape[0]
        for i in reversed(range(k_rows)):
            pool = jax.tree_util.tree_map(
                lambda p, n, bax, pax: w_slot(p, n, bax, pax, i),
                pool, new, self._batch_axes, self._page_axes)
        return pool

    def insert_rows(self, prefill_cache: PyTree, slots: np.ndarray,
                    lengths: np.ndarray) -> None:
        """Seat a batched prefill cache: allocate each real slot's prompt
        pages, then scatter the (right-padded) KV rows into them. ``slots``
        may carry pad rows past ``len(lengths)``; their table rows are
        zeroed so pad writes land in the null page."""
        assert max(lengths, default=0) <= self.capacity
        k = len(lengths)
        for s, l in zip(slots[:k], lengths):
            self.ensure(int(s), int(l))
        # prefill seq length from any paged leaf: the axis after the page
        # axis in the pool is (batch, seq) in the prefill cache
        seq = None
        for leaf, pax in zip(jax.tree_util.tree_leaves(prefill_cache),
                             jax.tree_util.tree_leaves(self._page_axes)):
            if pax >= 0:
                seq = leaf.shape[pax + 1]
        assert seq is not None
        k_pad = len(slots)
        nbp = -(-seq // self.page_size)
        bt = np.zeros((k_pad, nbp), np.int32)
        bt[:k, :] = self.table[np.asarray(slots[:k]), :nbp]
        # clamp columns past each slot's allocated pages to the null page
        # (bucket padding may cover more pages than the prompt needs)
        cols = np.arange(nbp)[None, :]
        bt[:k] = np.where(cols < self._n_alloc[np.asarray(slots[:k]), None],
                          bt[:k], 0)
        pos = np.arange(seq)
        dest = (bt[:, pos // self.page_size] * self.page_size
                + (pos % self.page_size)[None, :])       # (k_pad, seq)
        self.cache = self._write(self.cache, prefill_cache,
                                 jnp.asarray(dest.reshape(-1), jnp.int32),
                                 jnp.asarray(slots, jnp.int32))
        for s, l in zip(slots[:k], lengths):
            self.lens[s] = l

    def insert(self, prefill_cache: PyTree, slot: int, length: int) -> None:
        assert length <= self.capacity, (length, self.capacity)
        self.insert_rows(prefill_cache, np.asarray([slot]),
                         np.asarray([length]))

    # -- decode-step views -------------------------------------------------

    def table_width(self, extra: int = 1) -> int:
        """Block-table columns the next decode step needs: pages covering
        ``len + extra`` for the longest live slot (``extra`` = tokens the
        step writes: 1 for decode, the suffix bucket for a speculative
        verify dispatch), bucketed to a power of two so jit retraces stay
        O(log max_pages)."""
        live = self.lens[self.lens > 0]
        need = self.pages_needed(int(live.max()) + extra) if live.size else 1
        w = 1
        while w < need:
            w *= 2
        return min(w, self.max_pages)

    def device_tables(self, width: Optional[int] = None) -> jax.Array:
        width = self.table_width() if width is None else width
        return jnp.asarray(self.table[:, :width])

    def live_page_rows(self) -> int:
        """Cache rows the length-aware kernel reads this step (sum of live
        pages × page_size over occupied slots)."""
        live = self.lens[self.lens > 0] + 1
        pages = -(-live // self.page_size)
        return int(pages.sum()) * self.page_size

    # -- lifecycle ---------------------------------------------------------

    def advance(self, slot: int) -> None:
        self.lens[slot] += 1

    def truncate(self, slot: int, length: int) -> None:
        """Rewind a slot to ``length`` valid positions and return pages
        wholly past the new frontier to the slot's reservation
        (speculative-decode rollback: rejected draft K/V sits in pages the
        verify dispatch just allocated).

        Safe by construction: pages past the prompt are allocated fresh
        during decode/verify and are never registered in the prefix index
        nor adopted by another slot (``register_prefix`` only publishes
        full PROMPT pages at admission), so every freed page has refcount
        1 and goes straight back to the free list. The page containing
        position ``length - 1`` stays — its leading K/V is still live —
        and stale rows past the frontier inside it are masked by the
        length, then overwritten when the slot advances again."""
        assert 0 <= length <= self.capacity, (length, self.capacity)
        keep = self.pages_needed(length)
        n = int(self._n_alloc[slot])
        assert keep <= n, (slot, length, keep, n)
        for col in range(keep, n):
            pid = int(self.table[slot, col])
            assert pid not in self._page_key and self._refcount[pid] == 1, \
                f"truncate hit a shared/registered page {pid} past the " \
                f"write frontier of slot {slot}"
            self._drop_page_ref(pid)
            self.table[slot, col] = 0
        self._n_alloc[slot] = keep
        # freed pages go back into the slot's worst-case budget so a later
        # ensure() can re-draw them without over-committing the pool
        self._set_reserved(slot, int(self._reserved[slot]) + (n - keep))
        self.lens[slot] = length

    def release(self, slot: int) -> None:
        """Retire: DECREMENT every table page's refcount instead of
        freeing — shared prefix pages stay live for their other owners,
        and registered refcount-0 pages park on the LRU list."""
        n = int(self._n_alloc[slot])
        for p in self.table[slot, :n]:
            self._drop_page_ref(int(p))
        self.table[slot, :] = 0
        self._n_alloc[slot] = 0
        self._set_reserved(slot, 0)
        self.lens[slot] = 0

    def idle_pages(self) -> int:
        """Non-null pages held by no slot: free list + LRU-parked prefix
        pages. On a fully drained pool this equals ``n_pages - 1``; the
        chaos harness checks exactly that to prove nothing leaked."""
        return len(self._free) + len(self._lru)

    def check_consistency(self) -> None:
        """Audit the allocator's bookkeeping against the tables themselves.

        Rebuilds every page's reference count from the slot tables and
        asserts the conservation invariants the chaos tests rely on: each
        non-null page is in exactly one of {free list, LRU, live}; stored
        refcounts match the rebuilt ones; reservation totals agree; and
        free + LRU + live + null covers the pool exactly. Cheap (host-side
        ints only), so callable mid-run too."""
        ref = np.zeros((self.n_pages,), np.int64)
        for slot in range(self.n_slots):
            n = int(self._n_alloc[slot])
            for pid in self.table[slot, :n]:
                assert int(pid) != 0, f"null page in live table of slot {slot}"
                ref[int(pid)] += 1
            assert not self.table[slot, n:].any(), \
                f"slot {slot} table non-zero past its {n} allocated pages"
            assert self.pages_needed(int(self.lens[slot])) <= n, \
                f"slot {slot} length {int(self.lens[slot])} overruns its " \
                f"{n} allocated pages"
        free = list(self._free)
        free_set = set(free)
        assert len(free) == len(free_set), "duplicate pages on the free list"
        assert 0 not in free_set and 0 not in self._lru, \
            "null page entered the free/LRU lists"
        for pid in range(1, self.n_pages):
            states = ((pid in free_set) + (pid in self._lru)
                      + (ref[pid] > 0))
            assert states == 1, \
                f"page {pid} in {states} of free/LRU/live (refs={ref[pid]})"
            assert int(self._refcount[pid]) == int(ref[pid]), \
                f"page {pid} refcount {int(self._refcount[pid])} != " \
                f"{int(ref[pid])} table references"
            if pid in self._lru:
                assert pid in self._page_key, \
                    f"LRU page {pid} missing from the prefix index"
            if pid in free_set:
                assert pid not in self._page_key, \
                    f"free page {pid} still registered in the prefix index"
        assert self._reserved_total == int(self._reserved.sum()), \
            "reservation total out of sync with per-slot reservations"
        live = int((ref > 0).sum())
        assert len(free) + len(self._lru) + live + 1 == self.n_pages, \
            "free + LRU + live + null does not cover the pool"
