"""Paged KV-cache subsystem: block-table page pool for continuous batching.

Dense continuous batching (PR 2) gives every slot a worst-case
``ctx_len + max_new_tokens`` KV row, so GPU KV memory — the scarcest
resource in RAGDoll's joint placement problem — is provisioned for the
longest possible request.  This module replaces those rows with
vLLM-style paging:

``PagePool``
    Pure host-side bookkeeping (no JAX): a free-list of fixed-size KV
    *pages* plus per-slot *block tables*.  Page id 0 is a reserved
    **trash page** that is never allocated — freed slots' block tables
    are reset to it, so a recycled slot's parked decode writes can never
    corrupt a page that has been re-issued to another slot.  ``admit``
    reserves a request's worst-case page count up front (so a request
    can never hit mid-decode exhaustion), while ``ensure`` allocates
    pages lazily as the sequence actually grows.  Invariants are
    property-tested in ``tests/test_paged.py``: pages never leak, no
    page is ever leased twice, ``len(block_table) ==
    ceil(written_len / page_size)`` exactly, and reservations are always
    backed by free pages.

    Pages carry **refcounts** so one physical page can back the same
    logical prefix in many block tables (prefix-sharing KV, see
    ``serving/prefixcache.py``): ``admit(..., shared=pages)`` maps an
    already-referenced prefix into a joining slot's table, ``incref``/
    ``decref`` adjust standalone holds (the radix prefix cache holds one
    reference per cached page), and a page only returns to the free
    list when its count hits zero.  Shared pages are **read-only**:
    a holder that must write one first detaches it with ``cow`` —
    allocate a fresh page, repoint the block-table entry, drop one
    reference on the original (copy-on-write; the device-side data copy
    is the caller's job, see ``PagedKVCache.cow_block``).  The
    conservation law — every page's refcount equals its block-table
    occurrences plus its standalone holds, and ``free ∩ referenced =
    ∅`` — is property-tested in ``tests/test_prefix.py``.

``PagedKVCache``
    The device-facing half: builds pooled KV arrays where every dense
    cache leaf ``(B, S, kv_heads, head_dim)`` becomes
    ``(num_pages + 1, page_size, kv_heads, head_dim)`` (row 0 = trash
    page), owns the shared ``(num_slots, max_blocks)`` int32 block
    table, and scatters batch=1 prefill rows into pages.  **Block-table
    layout:** logical position ``p`` of slot ``s`` lives at
    ``(block_tab[s, p // page_size], p % page_size)`` in every layer's
    pool; the table is shared across layers because all layers advance
    in lockstep.  Attention gathers pages back through the table
    (``ops.paged_decode_attention``), so per-row compute stays
    bit-identical to the dense layout on the gather backend.

``HostPagePool``
    The host tier of the paper's KV placement (the ``c_cpu`` fraction of
    Eq. 3): preallocated host-side page arrays mirroring the device
    pool's leaves, plus a free-list of host page ids.  ``PagedKVCache``
    swaps a preempted slot's pages here in whole-page units
    (``swap_out`` = D2H DMA + device free, ``swap_in`` = H2D DMA onto
    *fresh* device pages + block-table remap).  On swap-in the slot
    generally lands on different physical pages than it left — logical
    order is preserved by the remapped block table, never by page
    identity, so the trash-page isolation invariant survives arbitrary
    preempt/resume/resize interleavings (``tests/test_swap.py`` /
    ``tests/test_swap_pool.py``).  On a real accelerator these arrays
    would live in pinned host memory (``jax.device_put`` onto a
    ``pinned_host`` memory kind) so the DMA can run async; on the CPU
    backend numpy arrays *are* the host tier.

**Page-budget ↔ placement coupling:** the engine's policy boundary
retargets ``PagePool.resize`` from the live placement via
``PlacementOptimizer.kv_page_budget`` — the KV bytes the placement puts
on the accelerator, divided by ``CostModel.kv_page_bytes`` — and
``HostPagePool.resize`` via ``PlacementOptimizer.kv_host_page_budget``
(the ``c_cpu`` term), so both tiers of the KV placement track the live
solve.  Because a request only reserves
``ceil((ctx + its_budget) / page_size)`` pages, the same GPU KV byte
budget admits a strictly larger concurrent batch than dense worst-case
rows whenever budgets/contexts are heterogeneous; with swap-to-host the
pool can additionally *reclaim* pages from live slots, so admission is
bounded by device + host pages rather than device pages alone.
"""
from __future__ import annotations

import functools
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.obs.metrics import NULL_REGISTRY
from repro.obs.trace import NULL_TRACER

TRASH_PAGE = 0

# bytes per KV element for each pool format ("int8" additionally carries
# fp32 per-page-per-head scale leaves; see ``kernels/quant.py``)
KV_FORMAT_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}
KV_FORMAT_DTYPE = {"fp32": jnp.float32, "bf16": jnp.bfloat16,
                   "int8": jnp.int8}


class PageExhausted(RuntimeError):
    """The pool cannot supply the pages a live sequence needs."""


class PagePool:
    """Free-list of fixed-size KV pages with per-slot block tables.

    ``capacity`` counts *usable* pages (ids ``1..capacity``); id 0 is
    the reserved trash page.  ``admit`` books a worst-case reservation,
    ``ensure`` draws pages lazily (first from the slot's reservation,
    then from unreserved spares), ``release`` returns everything.
    """

    def __init__(self, capacity: int, page_size: int):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.page_size = page_size
        self._capacity = capacity
        self._free: List[int] = list(range(capacity, 0, -1))  # pop() -> 1
        self._tables: Dict[Any, List[int]] = {}
        self._reserved: Dict[Any, int] = {}
        # page id -> reference count.  An allocated page starts at 1
        # (its table entry / standalone hold); free pages have no entry.
        self._refs: Dict[int, int] = {}
        # pages freed into an outstanding async D2H DMA: unreferenced
        # but NOT allocatable until ``complete_inflight`` lands them
        # (free / leased / shared / parked / in-flight / trash states)
        self._inflight: set = set()

    # ------------------------------------------------------------ queries
    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return sum(len(t) for t in self._tables.values())

    @property
    def reserved_pages(self) -> int:
        return sum(self._reserved.values())

    @property
    def available_pages(self) -> int:
        """Free pages not backing any slot's reservation."""
        return self.free_pages - self.reserved_pages

    @property
    def referenced_pages(self) -> int:
        """Distinct pages with refcount >= 1 (free + referenced +
        in-flight = capacity)."""
        return len(self._refs)

    @property
    def inflight_pages(self) -> int:
        """Pages pinned by an outstanding async swap DMA."""
        return len(self._inflight)

    def is_inflight(self, page: int) -> bool:
        return page in self._inflight

    def refcount(self, page: int) -> int:
        """Live references to ``page`` (0 = free / never allocated)."""
        return self._refs.get(page, 0)

    def blocks_for(self, length: int) -> int:
        return -(-max(length, 0) // self.page_size)

    def table(self, key: Any) -> List[int]:
        return list(self._tables[key])

    def reservation(self, key: Any) -> int:
        """Unspent worst-case reservation still booked for ``key``."""
        return self._reserved.get(key, 0)

    def holders(self) -> List[Any]:
        return list(self._tables)

    def can_admit(self, length: int) -> bool:
        return self.blocks_for(length) <= self.available_pages

    def admit_capacity(self, length: int) -> int:
        """How many worst-case-``length`` requests fit right now."""
        need = self.blocks_for(length)
        if need == 0:
            return self._capacity
        return self.available_pages // need

    # ---------------------------------------------------------- lifecycle
    def admit(self, key: Any, length: int,
              shared: Sequence[int] = ()) -> bool:
        """Reserve ``blocks_for(length)`` pages for a joining request.

        ``shared`` maps an already-referenced page run (a cached prefix)
        into the head of the new block table: the caller must hold one
        reference per page (a pin from ``PrefixCache.match``), and that
        reference transfers to the table entry — no incref here, and
        ``release`` later decrefs it like any other entry.  Only the
        blocks *beyond* the shared prefix are reserved, so a prefix-hit
        join costs ``blocks_for(length) - len(shared)`` pages of
        worst-case headroom instead of the full run.
        """
        if key in self._tables:
            raise ValueError(f"slot {key!r} already holds pages")
        for p in shared:
            if self._refs.get(p, 0) < 1:
                raise ValueError(f"shared page {p} is not referenced")
        need = max(0, self.blocks_for(length) - len(shared))
        if need > self.available_pages:
            return False
        self._tables[key] = list(shared)
        self._reserved[key] = need
        return True

    def ensure(self, key: Any, length: int) -> List[int]:
        """Grow ``key``'s block table to cover ``length`` positions.

        Returns the newly allocated page ids (possibly empty).  Draws
        from the slot's reservation first, then from unreserved spares;
        raises :class:`PageExhausted` if the pool cannot cover it.
        """
        tab = self._tables[key]
        need = self.blocks_for(length) - len(tab)
        if need <= 0:
            return []
        res = self._reserved.get(key, 0)
        extra = max(0, need - res)
        if extra > self.available_pages:
            raise PageExhausted(
                f"need {need} pages for slot {key!r}, "
                f"reservation {res} + available {self.available_pages}")
        new = [self._free.pop() for _ in range(need)]
        for p in new:
            self._refs[p] = 1
        tab.extend(new)
        self._reserved[key] = max(0, res - need)
        return new

    def release(self, key: Any) -> int:
        """End ``key``'s lease: drop one reference per table entry (and
        the unspent reservation).  Pages shared with other tables or the
        prefix cache survive — only refcount-zero pages return to the
        free list, so a page is never freed while shared."""
        tab = self._tables.pop(key)       # KeyError = double free
        self._reserved.pop(key, None)
        for p in reversed(tab):           # low ids pop first again
            self.decref(p)
        return len(tab)

    # ----------------------------------------------- sharing (prefix cache)
    def incref(self, page: int) -> None:
        """Add a standalone reference to an allocated page (the prefix
        cache's hold, or a match-time pin)."""
        if page not in self._refs:
            raise ValueError(f"page {page} is not allocated")
        self._refs[page] += 1

    def decref(self, page: int, inflight: bool = False) -> None:
        """Drop one reference; the page frees when the count hits zero.

        With ``inflight=True`` a count-zero page enters the in-flight
        set instead of the free list: it cannot be re-leased until the
        async D2H reading it completes (:meth:`complete_inflight`).
        """
        rc = self._refs[page] - 1         # KeyError = double free
        if rc <= 0:
            del self._refs[page]
            if inflight:
                self._inflight.add(page)
            else:
                self._free.append(page)
        else:
            self._refs[page] = rc

    def complete_inflight(self, pages: Sequence[int]) -> None:
        """Land an async D2H: the pinned pages return to the free list."""
        for p in pages:
            if p not in self._inflight:
                raise ValueError(f"page {p} is not in flight")
            self._inflight.remove(p)
            self._free.append(p)

    def grab(self, n: int = 1) -> Optional[List[int]]:
        """Allocate ``n`` standalone pages (refcount 1, no table) from
        the unreserved spares — the prefix cache's own allocations
        (cached tail copies, host-tier revivals).  ``None`` when the
        spares cannot cover it; never touches slot reservations."""
        if n < 0:
            raise ValueError("n must be >= 0")
        if n > self.available_pages:
            return None
        got = [self._free.pop() for _ in range(n)]
        for p in got:
            self._refs[p] = 1
        return got

    def cow(self, key: Any, block: int) -> Optional[Tuple[int, int]]:
        """Copy-on-write detach of ``key``'s ``block`` before a write.

        A shared page (refcount > 1) is read-only for every holder; the
        writer swaps in a fresh page and drops its reference on the
        original.  Returns ``(src, dst)`` so the caller can copy the
        page *data* device-side (``PagedKVCache.cow_block``), or
        ``None`` when the page is already private (refcount 1 — no copy
        needed).  Draws from unreserved spares only: the slot's own
        reservation covers its private blocks, never a detach, so a
        CoW can raise :class:`PageExhausted` — callers fall back to
        un-caching the page instead (see
        ``ContinuousGenerator._cow_barrier``).
        """
        tab = self._tables[key]
        src = tab[block]
        if self._refs.get(src, 0) <= 1:
            return None
        if self.available_pages < 1:
            raise PageExhausted(
                f"no spare page to detach shared page {src} for {key!r}")
        dst = self._free.pop()
        self._refs[dst] = 1
        tab[block] = dst
        self.decref(src)
        return src, dst

    # --------------------------------------------------------------- swap
    def park(self, key: Any, handle: Any, blocks: Optional[int] = None,
             inflight: bool = False) -> Tuple[List[int], int]:
        """End ``key``'s device residency for a (possibly partial) swap.

        The first ``blocks`` table entries — the sequence's *coldest*,
        oldest-position pages (FlexGen-style) — lose this slot's
        reference and are returned as ``(cold_pages, reservation)`` in
        logical order so the caller can DMA them out before re-issue.
        Any hotter tail pages stay device-resident, re-keyed under
        ``handle`` (still refcounted, still counted in ``used_pages``)
        until :meth:`unpark` splices them back behind the reloaded
        prefix.  ``blocks=None`` sheds the whole table (a full swap).
        With ``inflight=True`` count-zero freed pages enter the
        in-flight set instead of the free list — unallocatable until
        the async D2H completes (:meth:`complete_inflight`).
        """
        tab = self._tables.pop(key)       # KeyError = not a holder
        res = self._reserved.pop(key, 0)
        k = len(tab) if blocks is None else blocks
        if not 0 <= k <= len(tab):
            self._tables[key] = tab       # restore before raising
            self._reserved[key] = res
            raise ValueError(f"cannot shed {k} of {len(tab)} pages "
                             f"for {key!r}")
        cold, tail = tab[:k], tab[k:]
        for p in reversed(cold):
            self.decref(p, inflight=inflight)
        if tail:
            self._tables[handle] = tail
        return list(cold), res

    def unpark(self, handle: Any, key: Any, blocks: int,
               reserve: int = 0) -> Optional[List[int]]:
        """Re-lease ``blocks`` fresh pages (+ re-book ``reserve``) for a
        resuming slot, splicing any device-resident tail retained under
        ``handle`` behind them.  Returns the fresh prefix page ids, or
        ``None`` when the pool cannot cover ``blocks + reserve`` right
        now (the slot stays parked, its retained tail untouched)."""
        if blocks < 0 or reserve < 0:
            raise ValueError("blocks/reserve must be >= 0")
        tail = self._tables.pop(handle, [])
        if key in self._tables:
            if tail:
                self._tables[handle] = tail
            raise ValueError(f"slot {key!r} already holds pages")
        if blocks + reserve > self.available_pages:
            if tail:
                self._tables[handle] = tail
            return None
        new = [self._free.pop() for _ in range(blocks)]
        for p in new:
            self._refs[p] = 1
        self._tables[key] = new + tail
        self._reserved[key] = reserve
        return new

    def swap_out(self, key: Any) -> Tuple[List[int], int]:
        """End ``key``'s device residency for a full host swap.

        Returns ``(pages, reservation)``: the page ids in logical order
        (so the caller can DMA them out before they are re-issued) and
        the unspent worst-case reservation the slot must re-book on
        swap-in.  The freed pages are re-issuable *immediately* — the
        swapped-out data's integrity lives host-side from here on.
        Shared pages (a mapped cached prefix) merely lose this slot's
        reference; the cache and other holders keep reading them.
        ``park`` is the partial/async-aware generalization.
        """
        return self.park(key, key)

    def swap_in(self, key: Any, blocks: int,
                reserve: int = 0) -> Optional[List[int]]:
        """Re-lease ``blocks`` pages (+ re-book ``reserve``) for a
        swapped-in slot.

        The physical ids generally differ from the ones ``swap_out``
        returned — correctness must come from the caller's remapped
        block table, never from page identity.  Returns ``None`` when
        the pool cannot cover ``blocks + reserve`` right now (the slot
        stays parked host-side).  ``unpark`` is the partial-residency
        generalization.
        """
        # a full swap_out leaves no retained tail under ``key``, so any
        # table held under it is a live one
        if key in self._tables:
            raise ValueError(f"slot {key!r} already holds pages")
        return self.unpark(key, key, blocks, reserve)

    # ------------------------------------------------------------- resize
    def resize(self, target: int) -> int:
        """Retarget the usable-page capacity; returns the actual size.

        Growth mints fresh ids; shrink removes a contiguous run of free
        pages from the top, clamped so no in-use page and no backed
        reservation is ever dropped.
        """
        target = max(int(target), 1)
        if target > self._capacity:
            self._free.extend(range(self._capacity + 1, target + 1))
            self._capacity = target
            return self._capacity
        in_use_max = max(max(self._refs, default=0),   # tables + holds
                         max(self._inflight, default=0))  # pending DMA
        floor = max(target, in_use_max)
        budget = self.free_pages - self.reserved_pages
        free_set = set(self._free)
        new_cap = self._capacity
        while new_cap > floor and budget > 0 and new_cap in free_set:
            free_set.remove(new_cap)
            new_cap -= 1
            budget -= 1
        self._free = sorted(free_set, reverse=True)
        self._capacity = new_cap
        return self._capacity


# ---------------------------------------------------------------------------
# host page pool (swap-to-host tier)
# ---------------------------------------------------------------------------

def _pool_leaves(pools):
    """Yield ``(leaf, page_axis)`` for every pooled-cache array.

    Handles both cache layouts — the stacked ``Model`` dict (page axis 1
    under ``"blocks"``, 0 under ``"prefix"``) and the streamed per-layer
    list (page axis 0) — in a stable order shared with the host mirror,
    the same dispatch as :func:`resize_cache_rows`.
    """
    if isinstance(pools, dict):
        for leaf in jax.tree.leaves(pools["blocks"]):
            yield leaf, 1
        for leaf in jax.tree.leaves(pools.get("prefix", [])):
            yield leaf, 0
    else:
        for c in pools:
            for leaf in jax.tree.leaves(c):
                yield leaf, 0


def _rebuild_pools(pools, new_leaves: List[Any]):
    """Reassemble a pools pytree from leaves in ``_pool_leaves`` order."""
    it = iter(new_leaves)
    if isinstance(pools, dict):
        bl, bdef = jax.tree.flatten(pools["blocks"])
        out = dict(pools)
        out["blocks"] = jax.tree.unflatten(bdef, [next(it) for _ in bl])
        if "prefix" in pools:
            pl, pdef = jax.tree.flatten(pools["prefix"])
            out["prefix"] = jax.tree.unflatten(pdef, [next(it) for _ in pl])
        return out
    rebuilt = []
    for c in pools:
        cl, cdef = jax.tree.flatten(c)
        rebuilt.append(jax.tree.unflatten(cdef, [next(it) for _ in cl]))
    return rebuilt


class HostPagePool:
    """Host-side KV page store for swapped-out slots (Eq. 3's ``c_cpu``).

    Bookkeeping mirrors :class:`PagePool` — a free-list of fixed-size
    pages — with 0-based ids and no trash page (host pages are never
    decoded against, only DMA'd).  Each holder additionally remembers
    the device-side worst-case reservation it must re-book on swap-in,
    so a resumed slot keeps its no-mid-decode-exhaustion guarantee.

    The page *data* lives in preallocated host arrays mirroring the
    device pool's leaves with the page axis sized to this capacity
    (built lazily on the first ``store``).  ``capacity`` may be 0 — a
    placement with no ``c_cpu`` KV share simply cannot swap.
    """

    def __init__(self, capacity: int, page_size: int):
        if capacity < 0:
            raise ValueError("capacity must be >= 0")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.page_size = page_size
        self._capacity = capacity
        self._free: List[int] = list(range(capacity - 1, -1, -1))
        self._held: Dict[Any, List[int]] = {}
        self._reserve: Dict[Any, int] = {}
        self._mirror: Optional[List[Any]] = None   # [(np array, axis)]

    # ------------------------------------------------------------ queries
    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return sum(len(p) for p in self._held.values())

    def holders(self) -> List[Any]:
        return list(self._held)

    def pages(self, key: Any) -> List[int]:
        return list(self._held[key])

    def reservation(self, key: Any) -> int:
        return self._reserve[key]

    def can_hold(self, blocks: int) -> bool:
        return blocks <= len(self._free)

    # ---------------------------------------------------------- lifecycle
    def acquire(self, key: Any, blocks: int,
                reserve: int = 0) -> Optional[List[int]]:
        """Lease ``blocks`` host pages for a swapped-out slot, recording
        the device reservation to restore on swap-in.  ``None`` when the
        host pool cannot hold the slot."""
        if key in self._held:
            raise ValueError(f"handle {key!r} already holds host pages")
        if blocks < 0 or reserve < 0:
            raise ValueError("blocks/reserve must be >= 0")
        if blocks > len(self._free):
            return None
        got = [self._free.pop() for _ in range(blocks)]
        self._held[key] = got
        self._reserve[key] = reserve
        return got

    def release(self, key: Any) -> List[int]:
        """Return ``key``'s host pages to the free list (swap-in done,
        or the parked request was cancelled)."""
        got = self._held.pop(key)          # KeyError = double free
        self._reserve.pop(key, None)
        self._free.extend(reversed(got))
        return got

    # ------------------------------------------------------------- resize
    def resize(self, target: int) -> int:
        """Retarget host capacity; returns the actual size.

        Growth appends fresh ids (and pads the data arrays when built);
        shrink drops only *free* pages from the top, clamped to one past
        the highest held page so no parked slot's KV is ever dropped.
        """
        target = max(int(target), 0)
        if target > self._capacity:
            self._free = sorted(
                self._free + list(range(self._capacity, target)),
                reverse=True)
            self._capacity = target
        else:
            floor = max(target,
                        max((p for ps in self._held.values() for p in ps),
                            default=-1) + 1)
            self._free = sorted((p for p in self._free if p < floor),
                                reverse=True)
            self._capacity = floor
        self._fit_mirror()
        return self._capacity

    # --------------------------------------------------------- page data
    def _fit_mirror(self) -> None:
        if self._mirror is None:
            return
        fitted = []
        for arr, axis in self._mirror:
            if self._capacity > arr.shape[axis]:
                pad = [(0, 0)] * arr.ndim
                pad[axis] = (0, self._capacity - arr.shape[axis])
                arr = np.pad(arr, pad)
            elif self._capacity < arr.shape[axis]:
                sl = [slice(None)] * arr.ndim
                sl[axis] = slice(0, self._capacity)
                arr = np.ascontiguousarray(arr[tuple(sl)])
            fitted.append((arr, axis))
        self._mirror = fitted

    def _ensure_mirror(self, pools) -> None:
        if self._mirror is not None:
            return
        mirror = []
        for leaf, axis in _pool_leaves(pools):
            shape = list(leaf.shape)
            shape[axis] = self._capacity
            mirror.append((np.zeros(shape, leaf.dtype), axis))
        self._mirror = mirror

    def store(self, pools, key: Any, dev_pages: Sequence[int]) -> None:
        """D2H DMA: copy ``dev_pages`` (logical order) of every pool
        leaf into ``key``'s host pages."""
        self._ensure_mirror(pools)
        hp = np.asarray(self._held[key], np.int64)
        dp = np.asarray(list(dev_pages), np.int64)
        for (host, axis), (dev, _) in zip(self._mirror,
                                          _pool_leaves(pools)):
            if axis == 1:
                host[:, hp] = np.asarray(dev[:, dp])
            else:
                host[hp] = np.asarray(dev[dp])

    def write_pages(self, hp: np.ndarray, rows: Sequence[Any]) -> None:
        """Commit already-gathered device page rows into host pages
        ``hp`` — the async transfer worker's half of :meth:`store` (the
        submit thread snapshots the gathers and the host page ids, so
        the worker never reads mutable bookkeeping)."""
        for (arr, axis), row in zip(self._mirror, rows):
            if axis == 1:
                arr[:, hp] = np.asarray(row)
            else:
                arr[hp] = np.asarray(row)

    def read_pages(self, hp: np.ndarray) -> List[np.ndarray]:
        """Gather host pages ``hp`` from every mirror leaf — the async
        worker's half of :meth:`load` (the device scatter happens on
        the submitting thread at apply time)."""
        return [np.ascontiguousarray(arr[:, hp] if axis == 1 else arr[hp])
                for arr, axis in self._mirror]

    def load(self, pools, key: Any, dev_pages: Sequence[int]):
        """H2D DMA: copy ``key``'s host pages into ``dev_pages``
        (logical order); returns the updated pools pytree."""
        self._ensure_mirror(pools)
        hp = np.asarray(self._held[key], np.int64)
        dp = jnp.asarray(np.asarray(list(dev_pages), np.int32))
        new_leaves = []
        for (host, axis), (dev, _) in zip(self._mirror,
                                          _pool_leaves(pools)):
            rows = jnp.asarray(host[:, hp] if axis == 1 else host[hp])
            if axis == 1:
                new_leaves.append(dev.at[:, dp].set(rows.astype(dev.dtype)))
            else:
                new_leaves.append(dev.at[dp].set(rows.astype(dev.dtype)))
        return _rebuild_pools(pools, new_leaves)


# ---------------------------------------------------------------------------
# device-facing paged cache
# ---------------------------------------------------------------------------

def _attn_only_kinds(cfg: ModelConfig) -> None:
    bad = {k for k, _ in cfg.layer_kinds()} - {"attn", "local"}
    if bad or cfg.encdec:
        raise NotImplementedError(
            f"paged KV cache supports attn/local mixers only, got "
            f"{sorted(bad)}{' + encdec' if cfg.encdec else ''}")


def resize_cache_rows(pools, rows: int):
    """Pad (zeros) or slice a cache pytree's leading row axis to ``rows``.

    Handles both cache layouts: the stacked ``Model`` dict (row axis 1
    under ``"blocks"``, 0 under ``"prefix"``) and the streamed per-layer
    list (row axis 0).  "Rows" are pool pages here and dense slot rows
    in ``ContinuousGenerator.resize`` — the dispatch is identical.
    """
    def fit(t, axis):
        if rows > t.shape[axis]:
            pad = [(0, 0)] * t.ndim
            pad[axis] = (0, rows - t.shape[axis])
            return jnp.pad(t, pad)
        return jax.lax.slice_in_dim(t, 0, rows, axis=axis)

    if isinstance(pools, dict):               # stacked Model layout
        new = dict(pools)
        new["blocks"] = jax.tree.map(lambda t: fit(t, 1), pools["blocks"])
        if "prefix" in pools:
            new["prefix"] = jax.tree.map(lambda t: fit(t, 0),
                                         pools["prefix"])
        return new
    return [jax.tree.map(lambda t: fit(t, 0), c) for c in pools]


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_rows(caches, row_caches, pages, offs):
    """Write each layer's batch-1 row ``[0, len(pages))`` into its pool
    at ``(pages, offs)``; the pools are donated."""
    n = pages.shape[0]
    return [jax.tree.map(
        lambda t, r: t.at[pages, offs].set(r[0, :n].astype(t.dtype)), tc, rc)
        for tc, rc in zip(caches, row_caches)]


@dataclass
class _SwapJob:
    """One asynchronous swap DMA tracked by the transfer worker.

    ``kind="out"`` (D2H): ``rows`` holds lazy device gathers of the cold
    pages snapshotted at submit time (JAX's data dependencies keep the
    gathered values alive across jit donation), ``flight`` the pool
    pages pinned in-flight until the copy lands.  ``kind="in"`` (H2D):
    the worker fills ``rows`` from the host mirror; the submitting
    thread scatters them device-side at apply time (``poll``).
    """
    kind: str                 # "out" (D2H) | "in" (H2D)
    handle: Any               # host-pool holder key
    slot: int                 # generator slot index
    pages: List[int]          # device page ids (in-flight / fresh lease)
    hp: np.ndarray            # host page ids, snapshotted at submit
    rows: Optional[List[Any]] = None
    flight: List[int] = field(default_factory=list)
    done: threading.Event = field(default_factory=threading.Event)
    error: Optional[BaseException] = None


class PagedKVCache:
    """Pooled KV arrays + shared block table for one generator.

    The pool *arrays* live in the caller's cache pytree (so jit donation
    keeps working); this object owns the bookkeeping (:class:`PagePool`),
    the host block table, and its lazily refreshed device mirror.

    With ``overlap=True`` swap DMA runs on a dedicated transfer worker
    (an async FIFO queue) instead of inline: ``swap_out``/``swap_in``
    submit jobs and return immediately, decode for unaffected slots
    proceeds while the copies are outstanding, and ``poll``/``fence``
    apply completed jobs on the submitting thread.  ``swap_stall_s``
    accumulates the wall-clock the caller actually *blocked* on swap
    DMA — the whole copy in inline mode, only genuine waits in overlap
    mode — the fig8 ``swap_overlap`` row's headline number.
    """

    def __init__(self, cfg: ModelConfig, num_slots: int, total_len: int,
                 page_size: int, num_pages: Optional[int] = None,
                 dtype=jnp.float32, host_pages: Optional[int] = None,
                 kv_format: Optional[str] = None, overlap: bool = False,
                 tracer=None, registry=None):
        _attn_only_kinds(cfg)
        self.cfg = cfg
        self.num_slots = num_slots
        self.total_len = total_len
        self.page_size = page_size
        self.nmax = -(-total_len // page_size)
        worst = num_slots * self.nmax
        self.pool = PagePool(worst if num_pages is None else num_pages,
                             page_size)
        # host swap tier: default sizes it to park every slot worst-case
        self.host = HostPagePool(worst if host_pages is None else host_pages,
                                 page_size)
        if kv_format is None:
            kv_format = ("bf16" if jnp.dtype(dtype) == jnp.bfloat16
                         else "fp32")
        if kv_format not in KV_FORMAT_BYTES:
            raise ValueError(f"unknown kv_format {kv_format!r} "
                             f"(expected one of {sorted(KV_FORMAT_BYTES)})")
        self.kv_format = kv_format
        # pool leaves follow the format; int8 leaves are built by the
        # cache-spec path (int8 payload + fp32 scale leaves)
        self.dtype = (KV_FORMAT_DTYPE[kv_format] if kv_format != "int8"
                      else dtype)
        self.tracer = tracer or NULL_TRACER
        self.registry = registry or NULL_REGISTRY
        self._page_nbytes: Optional[int] = None
        self._tab = np.zeros((num_slots, self.nmax), np.int32)  # TRASH_PAGE
        self._tab_dev: Optional[jnp.ndarray] = None
        # format-dependent DMA accounting (plain ints: deterministic for
        # benchmarks even with a NULL registry)
        self.swap_out_bytes = 0
        self.swap_in_bytes = 0
        # async swap/decode overlap: a dedicated transfer worker drains
        # a FIFO job queue (FIFO guarantees a handle's D2H lands before
        # any H2D reads its host pages); jobs apply on the submitting
        # thread via ``poll``/``fence``
        self.overlap = overlap
        self._jobs: List[_SwapJob] = []
        self._job_q: "queue.Queue[Optional[_SwapJob]]" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self.swap_stall_s = 0.0   # wall-clock actually blocked on swap DMA

    def page_nbytes(self, pools) -> int:
        """Physical bytes one page occupies across every pool leaf
        (lazy: derived from the live arrays on first use, so it tracks
        whatever dtype/format the caller actually allocated — int8 pools
        count their int8 payload plus the fp32 scale rows, never a
        modeled 2-byte figure)."""
        if self._page_nbytes is None:
            total = 0
            for leaf, axis in _pool_leaves(pools):
                total += leaf.dtype.itemsize * (
                    int(np.prod(leaf.shape)) // leaf.shape[axis])
            self._page_nbytes = total
        return self._page_nbytes

    def pool_nbytes(self, pools) -> int:
        """Total physical bytes of every pool leaf (the regression tests
        pin ``pool_nbytes == page_nbytes * array_pages`` per format)."""
        return sum(int(leaf.nbytes) for leaf, _ in _pool_leaves(pools))

    # ------------------------------------------------------ array builders
    @property
    def array_pages(self) -> int:
        """Leading pool-array dim: usable pages + the trash page row 0."""
        return self.pool.capacity + 1

    @property
    def _spec_format(self) -> Optional[str]:
        return "int8" if self.kv_format == "int8" else None

    def init_stacked(self):
        """Pooled cache pytree for the scan-based ``Model`` path."""
        from repro.models import model as M
        specs = M.make_cache_specs(self.cfg, self.array_pages,
                                   self.page_size, self.dtype,
                                   kv_format=self._spec_format)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)

    def init_layered(self, kinds: Sequence) -> List[dict]:
        """Per-layer pooled caches for the ``StreamedExecutor`` path."""
        from repro.models import model as M
        out = []
        for kind in kinds:
            spec = M._layer_cache_spec(self.cfg, kind[0], self.array_pages,
                                       self.page_size, self.dtype, None,
                                       kv_format=self._spec_format)
            out.append(jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                    spec))
        return out

    # -------------------------------------------------------- block table
    def device_tab(self) -> jnp.ndarray:
        if self._tab_dev is None:
            self._tab_dev = jnp.asarray(self._tab)
        return self._tab_dev

    def slot_tab(self, slot: int) -> jnp.ndarray:
        """(1, nmax) block-table row for a batch=1 chunk prefill."""
        return self.device_tab()[slot:slot + 1]

    def _sync(self, slot: int, pages: List[int]) -> None:
        if pages:
            tab = self.pool.table(slot)
            self._tab[slot, :len(tab)] = tab
            self._tab_dev = None

    # ----------------------------------------------------------- lifecycle
    def admit(self, slot: int, length: int,
              shared: Sequence[int] = ()) -> bool:
        """Book ``slot``'s worst-case reservation; with ``shared`` the
        caller's pinned prefix pages become the head of the block table
        (refs transfer, see ``PagePool.admit``)."""
        if not self.pool.admit(slot, length, shared=shared):
            return False
        if shared:
            self._tab[slot, :len(shared)] = list(shared)
            self._tab_dev = None
        return True

    def ensure(self, slot: int, length: int) -> None:
        self._sync(slot, self.pool.ensure(slot, length))

    def release(self, slot: int) -> None:
        self.pool.release(slot)
        self._tab[slot, :] = TRASH_PAGE
        self._tab_dev = None

    def admit_capacity(self, length: int) -> int:
        return self.pool.admit_capacity(length)

    # ------------------------------------------------- sharing (CoW pages)
    def copy_page(self, pools, src: int, dst: int):
        """Device-side whole-page copy ``src -> dst`` in every pool leaf
        (the data half of copy-on-write); returns the updated pools."""
        new_leaves = []
        for leaf, axis in _pool_leaves(pools):
            if axis == 1:
                new_leaves.append(leaf.at[:, dst].set(leaf[:, src]))
            else:
                new_leaves.append(leaf.at[dst].set(leaf[src]))
        return _rebuild_pools(pools, new_leaves)

    def cow_block(self, pools, slot: int, block: int):
        """Detach ``slot``'s ``block`` if shared: fresh physical page,
        data copied, block-table entry repointed.  Returns
        ``(pools, copied)`` — ``copied`` False when the page was already
        private.  May raise :class:`PageExhausted` (spares-only draw,
        see ``PagePool.cow``)."""
        res = self.pool.cow(slot, block)
        if res is None:
            return pools, False
        src, dst = res
        with self.tracer.span("kv.cow_copy", slot=slot, block=block):
            pools = self.copy_page(pools, src, dst)
        self.registry.counter("kv.cow_copies").inc()
        self._tab[slot, block] = dst
        self._tab_dev = None
        return pools, True

    # ------------------------------------------------------ swap-to-host
    @staticmethod
    def _tail_key(handle: Any) -> Tuple[str, Any]:
        """Device-pool key for a partial park's retained hot tail.

        Namespaced so a hashable request key (often a small int) can
        never collide with a live slot index in ``PagePool._tables``.
        """
        return ("kv.tail", handle)

    def can_swap_out(self, slot: int, pages: Optional[int] = None) -> bool:
        """The host pool can hold ``slot``'s pages (or the first
        ``pages`` of them) right now."""
        need = len(self.pool.table(slot)) if pages is None else pages
        return self.host.can_hold(need)

    def swap_out(self, pools, slot: int, handle: Any,
                 pages: Optional[int] = None) -> bool:
        """Preempt ``slot``: DMA its pages D2H under ``handle``, free its
        device pages + reservation, point its block-table row at the
        trash page (parked decode writes can never corrupt re-issued
        pages).  ``False`` when the host pool lacks room — the slot
        stays live and untouched.

        ``pages=k`` sheds only the slot's ``k`` coldest (oldest-
        position) pages: the hot tail stays device-resident under
        ``handle`` and is spliced back behind the reloaded prefix on
        ``swap_in`` — both DMA directions move only ``k`` pages.  In
        overlap mode the D2H is submitted to the async transfer worker
        (the freed pages sit in-flight until it lands); inline mode
        blocks as before.
        """
        dev = self.pool.table(slot)
        k = len(dev) if pages is None else pages
        if not 0 <= k <= len(dev):
            raise ValueError(f"cannot swap {k} of {len(dev)} pages "
                             f"for slot {slot}")
        cold = dev[:k]
        hp = self.host.acquire(handle, k,
                               reserve=self.pool.reservation(slot))
        if hp is None:
            return False
        if self.overlap:
            self._submit_swap_out(pools, slot, handle, cold, hp)
        else:
            t0 = time.perf_counter()
            with self.tracer.span("swap.out", slot=slot, pages=k):
                # D2H before the pages recycle
                self.host.store(pools, handle, cold)
                self.pool.park(slot, self._tail_key(handle), blocks=k)
                self._tab[slot, :] = TRASH_PAGE
                self._tab_dev = None
            self.swap_stall_s += time.perf_counter() - t0
        nbytes = k * self.page_nbytes(pools)
        self.swap_out_bytes += nbytes
        self.registry.counter("kv.swap_out_pages").inc(k)
        self.registry.counter("kv.swap_out_bytes").inc(nbytes)
        return True

    def swap_in(self, pools, slot: int, handle: Any):
        """Resume ``handle`` into ``slot``: fresh physical pages (ids
        generally differ from the swapped-out ones), H2D DMA in logical
        order, block-table row remapped (any device-retained tail from
        a partial swap splices in behind the reloaded prefix).  Returns
        the updated pools, or ``None`` when the device pool cannot cover
        the slot's pages plus its re-booked reservation (the request
        stays parked host-side).

        In overlap mode the H2D is submitted async: the slot's
        block-table row stays all-trash (so interim decode writes park
        harmlessly) until ``poll`` applies the landed copy and reports
        the slot resumed.
        """
        blocks = len(self.host.pages(handle))
        new = self.pool.unpark(self._tail_key(handle), slot, blocks,
                               self.host.reservation(handle))
        if new is None:
            return None
        if self.overlap:
            self._submit_swap_in(pools, slot, handle, new)
        else:
            t0 = time.perf_counter()
            with self.tracer.span("swap.in", slot=slot, pages=blocks):
                pools = self.host.load(pools, handle, new)
                self.host.release(handle)
                tab = self.pool.table(slot)
                self._tab[slot, :] = TRASH_PAGE
                self._tab[slot, :len(tab)] = tab
                self._tab_dev = None
            self.swap_stall_s += time.perf_counter() - t0
        nbytes = blocks * self.page_nbytes(pools)
        self.swap_in_bytes += nbytes
        self.registry.counter("kv.swap_in_pages").inc(blocks)
        self.registry.counter("kv.swap_in_bytes").inc(nbytes)
        return pools

    # ------------------------------------------ async swap/decode overlap
    def _ensure_worker(self) -> None:
        if self._worker is None:
            self._worker = threading.Thread(
                target=self._worker_loop, name="kv-swap-dma", daemon=True)
            self._worker.start()

    def _worker_loop(self) -> None:
        while True:
            job = self._job_q.get()
            if job is None:
                return
            try:
                if job.kind == "out":
                    # force the device gathers (snapshotted at submit,
                    # so later writes to recycled pages can't corrupt
                    # them) and commit them into the host mirror
                    self.host.write_pages(
                        job.hp, [np.asarray(r) for r in job.rows])
                else:
                    job.rows = self.host.read_pages(job.hp)
            except BaseException as e:       # surfaced by poll()
                job.error = e
            finally:
                job.done.set()

    def _submit_swap_out(self, pools, slot: int, handle: Any,
                         cold: List[int], hp: List[int]) -> None:
        self._ensure_worker()
        self.host._ensure_mirror(pools)
        dp = np.asarray(cold, np.int64)
        # lazy device gathers: data deps keep the gathered values valid
        # even though the decode jit donates the pool arrays
        rows = [leaf[:, dp] if axis == 1 else leaf[dp]
                for leaf, axis in _pool_leaves(pools)]
        self.pool.park(slot, self._tail_key(handle), blocks=len(cold),
                       inflight=True)
        flight = [p for p in cold if self.pool.is_inflight(p)]
        self._tab[slot, :] = TRASH_PAGE
        self._tab_dev = None
        job = _SwapJob(kind="out", handle=handle, slot=slot,
                       pages=list(cold), hp=np.asarray(hp, np.int64),
                       rows=rows, flight=flight)
        self.tracer.instant("swap.async", kind="out", slot=slot,
                            pages=len(cold))
        self._jobs.append(job)
        self._job_q.put(job)

    def _submit_swap_in(self, pools, slot: int, handle: Any,
                        new: List[int]) -> None:
        self._ensure_worker()
        self.host._ensure_mirror(pools)
        job = _SwapJob(kind="in", handle=handle, slot=slot,
                       pages=list(new),
                       hp=np.asarray(self.host.pages(handle), np.int64))
        self.tracer.instant("swap.async", kind="in", slot=slot,
                            pages=len(job.hp))
        self._jobs.append(job)
        self._job_q.put(job)

    def _apply_swap_in(self, pools, job: _SwapJob):
        dp = jnp.asarray(np.asarray(job.pages, np.int32))
        new_leaves = []
        for (leaf, axis), rows in zip(_pool_leaves(pools), job.rows):
            r = jnp.asarray(rows)
            if axis == 1:
                new_leaves.append(leaf.at[:, dp].set(r.astype(leaf.dtype)))
            else:
                new_leaves.append(leaf.at[dp].set(r.astype(leaf.dtype)))
        pools = _rebuild_pools(pools, new_leaves)
        self.host.release(job.handle)
        tab = self.pool.table(job.slot)
        self._tab[job.slot, :] = TRASH_PAGE
        self._tab[job.slot, :len(tab)] = tab
        self._tab_dev = None
        return pools

    @property
    def outstanding(self) -> int:
        """Async swap jobs submitted but not yet applied."""
        return len(self._jobs)

    def poll(self, pools):
        """Apply completed async jobs FIFO from the head; returns
        ``(pools, resumed_slots, applied_count)``.  Never blocks."""
        resumed: List[int] = []
        applied = 0
        while self._jobs and self._jobs[0].done.is_set():
            job = self._jobs.pop(0)
            if job.error is not None:
                raise job.error
            if job.kind == "out":
                self.pool.complete_inflight(job.flight)
            else:
                pools = self._apply_swap_in(pools, job)
                resumed.append(job.slot)
            applied += 1
        return pools, resumed, applied

    def wait_any(self, timeout: Optional[float] = None) -> bool:
        """Block (stall-counted) until the head job completes."""
        if not self._jobs:
            return False
        job = self._jobs[0]
        if not job.done.is_set():
            t0 = time.perf_counter()
            job.done.wait(timeout)
            self.swap_stall_s += time.perf_counter() - t0
        return job.done.is_set()

    def fence(self, pools):
        """Barrier: wait for every outstanding swap DMA and apply it —
        the policy boundary's token-identity guarantee.  Returns
        ``(pools, resumed_slots, applied_count)`` like ``poll``."""
        for job in self._jobs:
            if not job.done.is_set():
                t0 = time.perf_counter()
                job.done.wait()
                self.swap_stall_s += time.perf_counter() - t0
        return self.poll(pools)

    def close(self) -> None:
        """Stop the transfer worker (tests; daemon thread otherwise)."""
        if self._worker is not None:
            self._job_q.put(None)
            self._worker.join(timeout=5.0)
            self._worker = None

    def set_host_budget(self, pages: int) -> int:
        """Retarget the host pool (the placement's ``c_cpu`` KV share).

        Callers must ``fence`` first in overlap mode: the resize
        replaces the host mirror arrays the transfer worker reads."""
        if self._jobs:
            raise RuntimeError("fence outstanding swap DMA before "
                               "resizing the host pool")
        return self.host.resize(pages)

    # ------------------------------------------------------------ scatter
    def _quant_block(self, block, row, pages, offs, length: int,
                     stacked: bool):
        """Quantize a dense fp32 prefill row dict into an int8 block
        dict (the row cache carries no scale leaves, so the tree
        structures differ — handled key-wise, not by ``tree.map``)."""
        from repro.kernels import quant
        out = dict(block)
        for base in ("k", "v"):
            r = (row[base][:, :, :length] if stacked
                 else row[base][:, :length])
            pool, scale = quant.quantize_rows(
                block[base], block[base + "_scale"], r, pages, offs)
            out[base] = pool
            out[base + "_scale"] = scale
        return out

    def _count_quant(self, length: int) -> None:
        self.registry.counter("kv.quant_bytes").inc(
            length * self.cfg.kv_cache_bytes_per_token(1))
        self.registry.counter("kv.quant_tokens").inc(length)

    def scatter_row_stacked(self, cache, row_cache, slot: int,
                            length: int):
        """Scatter a batch=1 dense prefill row's ``[0:length]`` prefix
        into the slot's pages (stacked ``{"blocks","prefix"}`` layout).

        Int8 pools quantize on append: every touched page is written
        from offset 0 (a fresh lease), so per-page scales are
        reset-then-set (see ``kernels/quant.py``)."""
        self.ensure(slot, length)
        pages, offs = self._page_index(slot, length)

        new = dict(cache)
        if self.kv_format == "int8":
            with self.tracer.span("kv.quant_append", slot=slot,
                                  tokens=length):
                new["blocks"] = [
                    self._quant_block(bc, rc, pages, offs, length,
                                      stacked=True)
                    for bc, rc in zip(cache["blocks"],
                                      row_cache["blocks"])]
                if "prefix" in cache:
                    new["prefix"] = [
                        self._quant_block(bc, rc, pages, offs, length,
                                          stacked=False)
                        for bc, rc in zip(cache["prefix"],
                                          row_cache["prefix"])]
            self._count_quant(length)
            return new
        new["blocks"] = jax.tree.map(
            lambda t, r: t.at[:, pages, offs].set(
                r[:, 0, :length].astype(t.dtype)),
            cache["blocks"], row_cache["blocks"])
        if "prefix" in cache:
            new["prefix"] = jax.tree.map(
                lambda t, r: t.at[pages, offs].set(
                    r[0, :length].astype(t.dtype)),
                cache["prefix"], row_cache["prefix"])
        return new

    def scatter_row_layered(self, caches, row_caches, slot: int,
                            length: int):
        """Same, for the per-layer list layout of ``StreamedExecutor``:
        one compiled program over every layer, the pools donated (int8
        pools quantize eagerly)."""
        self.ensure(slot, length)
        pages, offs = self._page_index(slot, length)
        if self.kv_format == "int8":
            with self.tracer.span("kv.quant_append", slot=slot,
                                  tokens=length):
                out = [self._quant_block(tc, rc, pages, offs, length,
                                         stacked=False)
                       for tc, rc in zip(caches, row_caches)]
            self._count_quant(length)
            return out
        return _write_rows(caches, row_caches, pages, offs)

    def _page_index(self, slot: int, length: int):
        idx = np.arange(length)
        pages = jnp.asarray(self._tab[slot, idx // self.page_size])
        offs = jnp.asarray((idx % self.page_size).astype(np.int32))
        return pages, offs

    # -------------------------------------------------------------- resize
    def resize_slots(self, num_slots: int) -> None:
        if num_slots == self.num_slots:
            return
        tab = np.zeros((num_slots, self.nmax), np.int32)
        keep = min(num_slots, self.num_slots)
        tab[:keep] = self._tab[:keep]
        self._tab = tab
        self._tab_dev = None
        self.num_slots = num_slots

    def resize_pages(self, pools, target: int):
        """Retarget the page budget; returns (new_pools, actual_pages).

        Growth zero-pads the pooled arrays, shrink slices — the pool
        guarantees dropped page ids are free.
        """
        old = self.pool.capacity
        actual = self.pool.resize(target)
        if actual == old:
            return pools, actual
        return resize_cache_rows(pools, actual + 1), actual
