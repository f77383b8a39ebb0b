"""Generation workers for the real (mini) engine: whole-batch + continuous.

A deterministic hash tokenizer keeps the substrate self-contained; prompts
are padded/truncated to a fixed context length.  The model path is either
the scan-based ``Model`` or the offloading ``StreamedExecutor`` (the
paper's prefetch-queue engine).

Two execution disciplines share that substrate:

``Generator``
    The classic whole-batch loop (prefill the batch together, decode it
    together, return when every row is done).  Kept for the serial
    baselines so Fig. 9 / benchmark comparisons stay like-for-like.

``ContinuousGenerator``
    Orca/vLLM-style iteration-level scheduling over a fixed-capacity
    **slot table**.  Each slot owns one row of the batched KV caches plus
    per-slot position / last-token / budget state.  Requests ``join`` at
    any decode step (a batch=1 prefill is scattered into a free slot's
    cache row), every ``step`` advances all live slots one token, and
    ``harvest`` returns rows the moment they emit EOS or exhaust their
    token budget — the freed slot is immediately reusable.  Slot rows are
    fully overwritten on join, so a recycled slot can never serve a stale
    KV cache; per-row decode is batch-size invariant on this backend, so
    outputs are token-identical to the whole-batch path (see
    ``tests/test_continuous.py``).

Slot lifecycle::

    free --acquire--> active --step*--> finished --harvest--> free
                      |    ^   (epoch bumped on release; stale SlotRefs
                 preempt   |    raise — including across preempt/resume)
                      v    resume (any free slot, fresh pages, remapped
                    parked         block table)
                 (KV pages in the host pool, scalars in _Parked)
"""
from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.prefetch import PrefetchPolicy, StreamedExecutor
from repro.models.model import Model, init_cache
from repro.obs.metrics import NULL_REGISTRY
from repro.obs.trace import NULL_SPAN, NULL_TRACER
from repro.serving import kvpool
from repro.serving.kvpool import PagedKVCache
from repro.serving.prefixcache import PrefixCache


class HashTokenizer:
    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size

    def encode(self, text: str, length: int) -> np.ndarray:
        ids = []
        for w in text.lower().split()[:length]:
            h = int.from_bytes(
                hashlib.blake2b(w.encode(), digest_size=4).digest(), "little")
            ids.append(h % (self.vocab_size - 2) + 2)   # 0=pad, 1=bos
        ids = [1] + ids
        ids = ids[:length]
        ids = ids + [0] * (length - len(ids))
        return np.asarray(ids, np.int32)

    def decode(self, ids: Sequence[int]) -> str:
        return " ".join(f"tok{int(i)}" for i in ids)


@dataclass
class GeneratorConfig:
    ctx_len: int = 64
    max_new_tokens: int = 16
    dtype: object = jnp.float32
    eos_id: Optional[int] = None   # None: always decode max_new_tokens


def _trim_at_eos(tokens: List[int], eos_id: Optional[int]) -> List[int]:
    if eos_id is None:
        return tokens
    for j, t in enumerate(tokens):
        if t == eos_id:
            return tokens[:j + 1]
    return tokens


class _GeneratorBase:
    """Shared model/tokenizer substrate for both batching disciplines."""

    def __init__(self, cfg: ModelConfig, params, gen_cfg: GeneratorConfig,
                 streamed: bool = False,
                 policy: Optional[PrefetchPolicy] = None,
                 resident_layers: int = 0,
                 free_bytes: float = float("inf")):
        """``resident_layers`` and ``free_bytes`` configure the streamed
        executor: how many leading layers stay in device memory, and the
        device bytes its prefetch queue may fill."""
        self.cfg = cfg
        self.gen_cfg = gen_cfg
        self.tok = HashTokenizer(cfg.vocab_size)
        self.streamed = streamed
        if streamed:
            self.exec = StreamedExecutor(cfg, params,
                                         policy or PrefetchPolicy(),
                                         resident_layers=resident_layers,
                                         free_bytes=free_bytes)
            self.model = None
            self.params = None
        else:
            self.exec = None
            self.model = Model(cfg, remat=False)
            self.params = params
            self._prefill = jax.jit(self.model.prefill)
            self._decode = jax.jit(self.model.decode, donate_argnums=(2,))


class Generator(_GeneratorBase):
    """Whole-batch prefill + greedy decode over a fixed-context batch."""

    def generate(self, prompts: List[str]) -> List[str]:
        g = self.gen_cfg
        b = len(prompts)
        toks = np.stack([self.tok.encode(p, g.ctx_len) for p in prompts])
        toks = jnp.asarray(toks)
        total = g.ctx_len + g.max_new_tokens
        outs = []
        if self.streamed:
            caches = self.exec.init_caches(b, total, g.dtype)
            logits, caches = self.exec.prefill(toks, caches)
        else:
            cache = init_cache(self.cfg, b, total, g.dtype)
            logits, cache = self._prefill(self.params, toks, cache)
        cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        outs.append(np.asarray(cur)[:, 0])
        for t in range(g.max_new_tokens - 1):
            pos = jnp.full((b,), g.ctx_len + t, jnp.int32)
            if self.streamed:
                logits, caches = self.exec.decode(cur, caches, pos)
            else:
                logits, cache = self._decode(self.params, cur, cache, pos)
            cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            outs.append(np.asarray(cur)[:, 0])
        mat = np.stack(outs, axis=1)     # (B, new)
        return [self.tok.decode(_trim_at_eos([int(t) for t in row],
                                             g.eos_id))
                for row in mat]


# ---------------------------------------------------------------------------
# slot table (pure bookkeeping — no JAX; property-tested in test_slots.py)
# ---------------------------------------------------------------------------

class StaleSlotError(RuntimeError):
    """A SlotRef outlived its slot's lease (the slot was recycled)."""


@dataclass
class SlotState:
    key: Any                      # caller's request handle
    pos: int                      # absolute position: ctx_len + emitted
    remaining: int                # decode steps left in the token budget
    tokens: List[int] = field(default_factory=list)
    t_first_token: Optional[float] = None   # perf_counter at token one


@dataclass(frozen=True)
class SlotRef:
    """Capability to one lease of one slot: (index, epoch) pair."""
    index: int
    epoch: int


class SlotTable:
    """Fixed-capacity slot allocator with per-slot lease epochs.

    ``acquire`` leases the lowest free slot; ``release`` bumps the slot's
    epoch so any retained :class:`SlotRef` from the previous lease raises
    :class:`StaleSlotError` instead of silently touching a recycled
    slot's KV row.  Invariants (property-tested): free + active partition
    the capacity; a key's position is strictly monotone while leased.
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._free: List[int] = list(range(capacity - 1, -1, -1))
        self._epochs: List[int] = [0] * capacity
        self._active: Dict[int, SlotState] = {}

    # ------------------------------------------------------------ queries
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def active_slots(self) -> int:
        return len(self._active)

    def active_refs(self) -> List[SlotRef]:
        return [SlotRef(i, self._epochs[i]) for i in sorted(self._active)]

    def mask(self) -> np.ndarray:
        m = np.zeros(self.capacity, bool)
        for i in self._active:
            m[i] = True
        return m

    def state(self, ref: SlotRef) -> SlotState:
        self._check(ref)
        return self._active[ref.index]

    def _check(self, ref: SlotRef) -> None:
        if (ref.index not in self._active
                or self._epochs[ref.index] != ref.epoch):
            raise StaleSlotError(f"slot {ref.index} epoch {ref.epoch} "
                                 f"is not the live lease")

    # ---------------------------------------------------------- lifecycle
    def acquire(self, key: Any, pos: int, remaining: int
                ) -> Optional[SlotRef]:
        """Lease a free slot, or None when the table is full."""
        if not self._free:
            return None
        idx = self._free.pop()
        self._active[idx] = SlotState(key=key, pos=pos, remaining=remaining)
        return SlotRef(idx, self._epochs[idx])

    def advance(self, ref: SlotRef, token: int) -> SlotState:
        """Record one decode step for a live slot (position +1)."""
        self._check(ref)
        st = self._active[ref.index]
        st.tokens.append(int(token))
        st.pos += 1
        st.remaining -= 1
        return st

    def release(self, ref: SlotRef) -> SlotState:
        """End the lease: bump the epoch, return the slot to the free list."""
        self._check(ref)
        st = self._active.pop(ref.index)
        self._epochs[ref.index] += 1
        self._free.append(ref.index)
        return st

    # -------------------------------------------------------------- resize
    def resize(self, target: int) -> int:
        """Retarget capacity; returns the actual new capacity.

        Growth appends fresh free slots; shrink drops only *free* slots
        from the top, so the result is clamped to one past the highest
        active lease (capacity never dips below live work).  Dropped
        slots keep their epoch counters, so a SlotRef retained across a
        shrink/grow cycle still raises :class:`StaleSlotError` instead
        of validating against a fresh lease of the re-grown slot.
        """
        target = max(int(target), 1)
        if target > self.capacity:
            grown = list(range(self.capacity, target))
            if target > len(self._epochs):      # epochs survive shrink
                self._epochs.extend([0] * (target - len(self._epochs)))
            self._free = sorted(self._free + grown, reverse=True)
            self.capacity = target
            return self.capacity
        floor = max(target, max(self._active, default=-1) + 1)
        self._free = sorted((i for i in self._free if i < floor),
                            reverse=True)
        self.capacity = floor
        return self.capacity


# ---------------------------------------------------------------------------
# continuous (iteration-level) generator
# ---------------------------------------------------------------------------

@dataclass
class _ChunkJob:
    """A join whose prompt is still being prefilled chunk by chunk."""
    ref: SlotRef
    toks: np.ndarray          # (ctx_len,) full padded prompt
    offset: int = 0           # next unwritten position


class _ParkHandle:
    """Opaque resume handle for unhashable request keys.

    The parked dict and the host page pool index by the handle; plain
    object identity hashing keeps mutable keys (e.g. ``Request``
    dataclasses) usable without touching their equality semantics.
    """
    __slots__ = ("key",)

    def __init__(self, key: Any):
        self.key = key


def _park_handle(key: Any) -> Any:
    try:
        hash(key)
    except TypeError:
        return _ParkHandle(key)
    return key


@dataclass
class _Parked:
    """Host-side state of a preempted (swapped-out) request.

    Everything a resume needs that does not live in the host page pool:
    the decode scalars and the emitted-token history.  The KV pages
    themselves sit in :class:`~repro.serving.kvpool.HostPagePool` under
    the request key.
    """
    key: Any
    tokens: List[int]         # emitted so far (harvest continuity)
    pos: int                  # SlotState.pos at preemption
    remaining: int            # decode budget left
    cur: int                  # pending token awaiting its KV write
    dec_pos: int              # _pos value: the next decode position
    trace_ids: Tuple = ()     # request trace scope, restored on resume
    t_first_token: Optional[float] = None


class ContinuousGenerator(_GeneratorBase):
    """Decode-step batching: requests join/leave a persistent slot table.

    Two KV layouts share the discipline:

    * **dense** (default): caches are allocated once for ``num_slots``
      rows of worst-case ``ctx_len + max_new_tokens``; ``join`` prefills
      at batch=1 and scatters the cache row into a free slot.  Dead
      slots keep riding the batched decode (their rows are
      row-independent garbage, fully overwritten on the next join).
    * **paged** (``paged=True``): KV lives in a shared
      :class:`~repro.serving.kvpool.PagedKVCache` pool; ``join``
      reserves only ``ceil((ctx + budget) / page_size)`` pages, so the
      same KV byte budget admits more concurrent requests than dense
      worst-case rows.  ``join`` returns ``None`` on page exhaustion as
      well as slot exhaustion (join backpressure).  Freed slots' block
      tables are reset to the trash page, so a recycled slot can never
      read or clobber pages reissued to another request.  With
      ``prefill_chunk=N`` a joiner's prompt is prefilled ``N`` tokens
      per ``step`` interleaved with live decode (chunked prefill), so
      long contexts no longer stall the batch.

    Paged mode additionally supports **prefix sharing**
    (``prefix_cache=True``): a radix tree over prompt tokens
    (:class:`~repro.serving.prefixcache.PrefixCache`) remembers the KV
    pages of completed prefills, and a joining prompt that matches a
    cached prefix maps those pages straight into its block table at
    refcount+1 and prefills only the novel suffix — TTFT work drops
    from ``ctx_len`` to ``ctx_len - matched`` tokens.  Shared pages are
    read-only: the partially-matched boundary page is copied at join
    time, and a decode write landing in a still-shared page (a donor's
    cached tail) is detached copy-on-write by ``_cow_barrier`` before
    the step runs.  Cold cached prefixes demote to the host swap tier
    and revive on the next hit; the engine arbitrates device pages
    between live KV and the cache via ``retarget(prefix_page_budget=)``.

    Paged mode additionally supports **page-granular preemption**
    (swap-to-host): ``preempt(ref)`` parks a live slot by DMA-ing its
    pages into the :class:`~repro.serving.kvpool.HostPagePool` and
    releasing the lease (epoch bump — stale SlotRefs raise), freeing
    both the slot and its device pages for joiners; ``resume(key)``
    re-admits the parked request into any free slot on fresh physical
    pages with the block table remapped.  Preempt→resume cycles are
    token-identical to uninterrupted generation (``tests/test_swap.py``)
    because whole-page host round-trips are bitwise exact and the
    gather backend reads through the table, never page identity.
    ``preempt(ref, pages=k)`` is the *partial* variant — only the
    slot's ``k`` coldest pages move host-side, the hot tail stays
    device-resident, and resume reloads just the shed prefix — and
    ``overlap_swap=True`` moves the swap DMA onto an async transfer
    worker so decode for unaffected slots proceeds while copies are
    outstanding (``fence`` is the policy-boundary barrier; slots with
    an in-flight swap-in are excluded from decode until their copy
    lands, which preserves token identity).

    Both layouts are token-identical to the whole-batch ``Generator``
    (see ``tests/test_continuous.py`` / ``tests/test_paged.py``).
    """

    def __init__(self, cfg: ModelConfig, params, gen_cfg: GeneratorConfig,
                 num_slots: int = 4, streamed: bool = False,
                 policy: Optional[PrefetchPolicy] = None,
                 paged: bool = False, page_size: int = 8,
                 page_budget: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 host_page_budget: Optional[int] = None,
                 prefix_cache: bool = False,
                 prefix_page_budget: Optional[int] = None,
                 kv_format: Optional[str] = None,
                 overlap_swap: bool = False,
                 resident_layers: int = 0,
                 free_bytes: float = float("inf"),
                 tracer=None, registry=None):
        super().__init__(cfg, params, gen_cfg, streamed=streamed,
                         policy=policy, resident_layers=resident_layers,
                         free_bytes=free_bytes)
        self.tracer = tracer or NULL_TRACER
        self.registry = registry or NULL_REGISTRY
        # slot -> the joining request's trace-id scope, so decode/swap
        # spans (which run outside the engine's per-request scope) can
        # still tag the requests they advance
        self._slot_scope: Dict[int, Tuple] = {}
        self.num_slots = num_slots
        self.table = SlotTable(num_slots)
        total = gen_cfg.ctx_len + gen_cfg.max_new_tokens
        self._total = total
        self.paged = paged
        self.page_size = page_size
        if prefill_chunk is not None and not paged:
            raise ValueError("prefill_chunk requires paged=True")
        if prefix_cache and not paged:
            raise ValueError("prefix_cache requires paged=True")
        self.prefill_chunk = prefill_chunk
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(page_size, prefix_page_budget) if prefix_cache
            else None)
        # prefill/sharing accounting (deterministic; fig8 asserts on these)
        self.joins = 0
        self.prefill_tokens = 0       # prompt tokens actually prefilled
        self.prefix_hit_tokens = 0    # prompt tokens served from the cache
        self.cow_copies = 0
        # logits batches (prefill or decode) holding a NaN or inf
        self.nonfinite_batches = 0
        self._prefilling: Dict[int, _ChunkJob] = {}
        self._parked: Dict[Any, _Parked] = {}
        # slots whose async H2D swap-in is outstanding: leased, but
        # excluded from decode until ``poll`` applies the landed copy
        self._pending_resume: set = set()
        self.swap_outs = 0
        self.swap_ins = 0
        self.peak_in_flight = 0
        if kv_format is not None and not paged:
            raise ValueError("kv_format requires paged=True")
        if overlap_swap and not paged:
            raise ValueError("overlap_swap requires paged=True")
        if overlap_swap and prefix_cache:
            # the prefix cache touches the host mirror inline
            # (demote/revive) — racy against the transfer worker
            raise ValueError("overlap_swap is incompatible with "
                             "prefix_cache")
        if paged:
            self.kv: Optional[PagedKVCache] = PagedKVCache(
                cfg, num_slots, total, page_size, num_pages=page_budget,
                dtype=gen_cfg.dtype, host_pages=host_page_budget,
                kv_format=kv_format, overlap=overlap_swap,
                tracer=self.tracer, registry=self.registry)
            if streamed:
                # committed to the device like every program's output, so
                # the first join and the next share one compiled program
                self.caches = jax.device_put(
                    self.kv.init_layered(self.exec.layer_kinds()),
                    self.exec.device)
            else:
                self.cache = self.kv.init_stacked()
                span, ctx_span = total, gen_cfg.ctx_len
                self._decode_paged = jax.jit(
                    lambda p, x, c, pos, bt: self.model.decode(
                        p, x, c, pos, block_tab=bt, kv_span=span),
                    donate_argnums=(2,))
                self._chunk_paged = jax.jit(
                    lambda p, x, c, off, bt: self.model.chunk_prefill(
                        p, x, c, off, block_tab=bt, kv_span=ctx_span),
                    donate_argnums=(2,))
        else:
            self.kv = None
            if streamed:
                self.caches = self.exec.init_caches(num_slots, total,
                                                    gen_cfg.dtype)
            else:
                self.cache = init_cache(cfg, num_slots, total, gen_cfg.dtype)
        # host-side per-slot scalars (tiny; converted per step)
        self._cur = np.zeros(num_slots, np.int32)
        self._pos = np.zeros(num_slots, np.int32)
        # (key, text, tokens, t_first_token) of rows that left
        self._finished: List[Tuple[Any, str, List[int], float]] = []
        self.steps = 0

    # ------------------------------------------------------------ helpers
    def bind_obs(self, tracer=None, registry=None) -> None:
        """Late-bind observability: the engine owns the tracer/registry
        but receives an already-constructed generator, so it hands them
        down here (and into the paged KV cache) at startup."""
        if tracer is not None:
            self.tracer = tracer
            if self.kv is not None:
                self.kv.tracer = tracer
            if self.exec is not None:
                self.exec.tracer = tracer
        if registry is not None:
            self.registry = registry
            if self.kv is not None:
                self.kv.registry = registry

    def _scope_ids(self, slots) -> List:
        """Union of the given slots' request trace ids (sorted, so span
        attrs are deterministic)."""
        ids = set()
        for s in slots:
            ids.update(self._slot_scope.get(s, ()))
        return sorted(ids, key=str)

    @property
    def kv_format(self) -> str:
        """The live KV byte format ("fp32"/"bf16"/"int8"): derived from
        the paged pool, else from the dense cache dtype — the source of
        truth the cost model's bits-per-token pricing must track."""
        if self.kv is not None:
            return self.kv.kv_format
        return ("bf16" if jnp.dtype(self.gen_cfg.dtype) == jnp.bfloat16
                else "fp32")

    @property
    def free_slots(self) -> int:
        return self.table.free_slots

    @property
    def active_slots(self) -> int:
        return self.table.active_slots

    @property
    def admit_capacity(self) -> int:
        """Joins guaranteed to succeed right now (slots AND pages).

        With a prefix cache, pages the cache could surrender (refcount
        1, evictable by ``PrefixCache.reclaim``) count as available —
        ``join`` reclaims them on demand, so they never block admission.
        """
        if not self.paged:
            return self.table.free_slots
        worst = self.gen_cfg.ctx_len + self.gen_cfg.max_new_tokens
        cap = self.kv.admit_capacity(worst)
        if self.prefix is not None and cap == 0:
            spare = (self.kv.pool.available_pages
                     + self.prefix.evictable_pages(self.kv))
            cap = spare // max(1, self.kv.pool.blocks_for(worst))
        return min(self.table.free_slots, cap)

    def _pools(self):
        """The pooled cache pytree (layout depends on the executor)."""
        return self.caches if self.streamed else self.cache

    def _set_pools(self, pools) -> None:
        if self.streamed:
            self.caches = pools
        else:
            self.cache = pools

    def _scatter_row(self, row_cache, slot: int) -> None:
        """Overwrite slot ``slot``'s KV row with a batch=1 cache."""
        if self.streamed:
            # per-layer list of dicts, leaves (1, ...) -> (S, ...)
            self.caches = [
                jax.tree.map(lambda t, r: t.at[slot].set(r[0]), tc, rc)
                for tc, rc in zip(self.caches, row_cache)]
        else:
            # stacked layout: "blocks" leaves are (reps, B, ...),
            # "prefix" leaves are (B, ...)
            new = dict(self.cache)
            new["blocks"] = jax.tree.map(
                lambda t, r: t.at[:, slot].set(r[:, 0]),
                self.cache["blocks"], row_cache["blocks"])
            if "prefix" in self.cache:
                new["prefix"] = jax.tree.map(
                    lambda t, r: t.at[slot].set(r[0]),
                    self.cache["prefix"], row_cache["prefix"])
            self.cache = new

    def _greedy(self, logits) -> np.ndarray:
        """Greedy tokens of a logits batch, read back with one transfer
        that also counts a batch holding a non-finite logit."""
        nxt, finite = jax.device_get(
            (jnp.argmax(logits, axis=-1).astype(jnp.int32),
             jnp.isfinite(logits).all()))
        self.nonfinite_batches += int(not finite)
        return nxt

    def _emit(self, ref: SlotRef, token: int) -> None:
        """Append one token; finish + free the slot on EOS / budget end."""
        st = self.table.advance(ref, token)
        if st.t_first_token is None:
            st.t_first_token = time.perf_counter()
        self._cur[ref.index] = token
        # st.pos counts ctx_len + emitted tokens; the emitted token is
        # *pending* its KV write, so the next decode call runs at pos-1
        self._pos[ref.index] = st.pos - 1
        eos = self.gen_cfg.eos_id
        if st.remaining <= 0 or (eos is not None and token == eos):
            st = self.table.release(ref)
            self._cur[ref.index] = 0
            # park the dead slot's writes on its last position: dense rows
            # are fully overwritten by the next join's scatter; paged slots
            # free their pages and point the block table at the trash
            # page, so the parked writes can never hit a reissued page
            if self.paged:
                self.kv.release(ref.index)
            self._slot_scope.pop(ref.index, None)
            self._finished.append(
                (st.key, self.tok.decode(st.tokens), list(st.tokens),
                 st.t_first_token))

    # ------------------------------------------------------------- public
    def join(self, key: Any, prompt: str,
             max_new_tokens: Optional[int] = None) -> Optional[SlotRef]:
        """Prefill ``prompt`` into a free slot; None when the table is full
        or (paged) the page pool cannot cover the request's worst case.

        The first token is emitted by the prefill itself (same as the
        whole-batch loop), so a budget of 1 finishes without any step.
        With chunked prefill the slot is leased immediately but the
        first token only appears after the last chunk lands (the chunks
        ride subsequent ``step`` calls, interleaved with live decode).

        With ``prefix_cache=True`` the prompt's tokens are first walked
        against the radix cache: matched full pages map into the block
        table shared (refcount+1, read-only), a partially-matched
        boundary page is copied into a private page, and only the
        ``ctx_len - matched`` suffix tokens are prefilled — capped at
        ``ctx_len - 1`` matched so the suffix prefill always emits the
        first token's logits.  Tokens are identical to an uncached join
        (``tests/test_prefix.py``).
        """
        g = self.gen_cfg
        req = g.max_new_tokens if max_new_tokens is None else max_new_tokens
        # prefill always emits the first token, so the budget floor is 1
        budget = max(1, min(req, g.max_new_tokens))
        ref = self.table.acquire(key, pos=g.ctx_len, remaining=budget)
        if ref is None:
            return None
        ptoks = self.tok.encode(prompt, g.ctx_len)
        matched = 0
        if self.paged:
            if self.prefix is not None:
                m = self._admit_shared(ref, ptoks, g.ctx_len + budget)
                if m is None:
                    self.table.release(ref)     # page backpressure
                    return None
                matched = m
            elif not self.kv.admit(ref.index, g.ctx_len + budget):
                self.table.release(ref)         # page backpressure
                return None
        self.joins += 1
        self.prefill_tokens += g.ctx_len - matched
        self.peak_in_flight = max(self.peak_in_flight, self.in_flight)
        if self.tracer.enabled:
            self._slot_scope[ref.index] = self.tracer.current_scope()
        if self.prefill_chunk is not None:
            # park decode writes on the last position: its page is either
            # unallocated (-> trash) or self-overwritten by the final
            # decode step before it is ever read.  A prefix hit starts
            # the job at the matched offset — only the suffix chunks run.
            self._prefilling[ref.index] = _ChunkJob(
                ref=ref, toks=ptoks, offset=matched)
            self._cur[ref.index] = 0
            self._pos[ref.index] = self._total - 1
            return ref
        if matched > 0:
            # suffix-only prefill through the block table (the shared
            # prefix pages supply positions [0, matched) to attention)
            with self.tracer.span("prefill", slot=ref.index,
                                  tokens=g.ctx_len - matched,
                                  matched=matched):
                self.kv.ensure(ref.index, g.ctx_len)
                chunk = jnp.asarray(ptoks[None, matched:])
                off = jnp.full((1,), matched, jnp.int32)
                bt = self.kv.slot_tab(ref.index)
                if self.streamed:
                    logits, self.caches = self.exec.prefill_chunk(
                        chunk, self.caches, off, block_tab=bt,
                        kv_span=g.ctx_len)
                else:
                    logits, self.cache = self._chunk_paged(
                        self.params, chunk, self.cache, off, bt)
            self._prefix_insert(ref.index, ptoks)
            self._emit(ref, int(self._greedy(logits)[0]))
            return ref
        # a streamed paged join runs as compiled programs
        # (``StreamedExecutor.prefill_rows``), then writes the slot's
        # pages (int8 pools quantize there)
        fused = self.streamed and self.paged
        with self.tracer.span("prefill", slot=ref.index, tokens=g.ctx_len,
                              fused=fused):
            toks = jnp.asarray(ptoks[None])
            if fused:
                out, row = self.exec.prefill_rows(toks, g.dtype)
                self.caches = self.kv.scatter_row_layered(
                    self.caches, row, ref.index, g.ctx_len)
                nxt, finite = jax.device_get(out)
                self.nonfinite_batches += int(not finite)
                self.registry.counter("prefill.fused_joins").inc()
            elif self.streamed:
                row = self.exec.init_caches(1, self._total, g.dtype)
                logits, row = self.exec.prefill(toks, row)
                self._scatter_row(row, ref.index)
            else:
                row = init_cache(self.cfg, 1, self._total, g.dtype)
                logits, row = self._prefill(self.params, toks, row)
                if self.paged:
                    self.cache = self.kv.scatter_row_stacked(
                        self.cache, row, ref.index, g.ctx_len)
                else:
                    self._scatter_row(row, ref.index)
            if not fused:
                nxt = self._greedy(logits)
        if self.paged:
            self._prefix_insert(ref.index, ptoks)
        self._emit(ref, int(nxt[0]))
        return ref

    # --------------------------------------------------- prefix sharing
    def _admit_shared(self, ref: SlotRef, toks: np.ndarray,
                      length: int) -> Optional[int]:
        """Prefix-aware admission: match, map shared pages, copy the
        boundary page.  Returns matched token count (0 = miss), or
        ``None`` on page backpressure (nothing retained).

        The match pins every returned node (refcount+1), so an eviction
        pass triggered between here and the admit below can never free
        a matched page.  Full-page pins transfer to the joiner's block
        table; the boundary pin is dropped after its page is copied.
        """
        g = self.gen_cfg
        pools = self._pools()
        nodes, m, pools = self.prefix.match(toks, self.kv, pools)
        # cap: the suffix prefill must cover >= 1 token, because it is
        # what emits the request's first output token
        m = min(m, g.ctx_len - 1)
        f, t = divmod(m, self.page_size)
        shared = [n.page for n in nodes[:f]]
        ok = self.kv.admit(ref.index, length, shared=shared)
        if not ok:
            # evict cold cached pages to fund the reservation, retry once
            short = (self.kv.pool.blocks_for(length) - f
                     - self.kv.pool.available_pages)
            if short > 0:
                _, pools = self.prefix.reclaim(short, self.kv, pools)
                ok = self.kv.admit(ref.index, length, shared=shared)
        if not ok:
            self.prefix.unpin(nodes, self.kv)
            self._set_pools(pools)
            return None
        if t > 0:
            # the partially-matched boundary page becomes a private copy
            # (the suffix prefill will overwrite its tail in place)
            self.kv.ensure(ref.index, m)
            dst = self.kv.pool.table(ref.index)[f]
            pools = self.kv.copy_page(pools, nodes[f].page, dst)
        self.prefix.unpin(nodes[f:], self.kv)
        self._set_pools(pools)
        if m > 0:
            self.prefix.stats.hits += 1
            self.prefix.stats.hit_tokens += m
            self.prefix_hit_tokens += m
        else:
            self.prefix.stats.misses += 1
        return m

    def _prefix_insert(self, slot: int, toks: np.ndarray) -> None:
        """Cache a freshly prefilled prompt's pages (refcount+1 each).

        Called once per completed prefill, *before* the first ``_emit``
        — so a budget-1 request that finishes immediately still donates
        its prefix (the cache's references keep the pages alive past the
        slot's release).
        """
        if self.prefix is None:
            return
        blocks = self.kv.pool.blocks_for(self.gen_cfg.ctx_len)
        pages = self.kv.pool.table(slot)[:blocks]
        self._set_pools(
            self.prefix.insert(toks, pages, self.kv, self._pools()))

    def _cow_barrier(self, refs: List[SlotRef]) -> None:
        """Detach shared pages that this step's decode will write.

        A slot's pending write lands at ``_pos`` — if that block is
        still shared (a donor's cached tail page), copy it out first
        (copy-on-write).  When no spare page can fund the copy, the
        fallback un-caches the page instead: the prefix cache is the
        only other holder, so dropping its reference makes the page
        private and the write may proceed in place.
        """
        pools = self._pools()
        changed = False
        for ref in refs:
            blk = int(self._pos[ref.index]) // self.page_size
            tab = self.kv.pool.table(ref.index)
            if blk >= len(tab) or self.kv.pool.refcount(tab[blk]) <= 1:
                continue
            try:
                pools, copied = self.kv.cow_block(pools, ref.index, blk)
                if copied:
                    self.cow_copies += 1
                    changed = True
            except kvpool.PageExhausted:
                if not self.prefix.drop_page(tab[blk], self.kv):
                    raise
        if changed:
            self._set_pools(pools)

    def _advance_prefills(self) -> int:
        """Prefill one chunk for every joining slot (paged mode only).

        On the **streamed** path, slots whose next chunk has the same
        width ride ONE batched call (per-row ``q_offset`` handles their
        differing offsets, the batch is padded to a power of two with
        all-trash block-table rows to bound retraces), so the offloaded
        layers stream host->device once per width group — not once per
        joiner.  On the resident-weight Model path there is no transfer
        to amortize, so per-slot batch=1 calls keep the jit at exactly
        one compiled shape per chunk width.  Per-row compute is
        batch-size invariant, so neither choice changes tokens.
        """
        g = self.gen_cfg
        groups: Dict[int, List[Tuple[int, _ChunkJob]]] = {}
        for slot in sorted(self._prefilling):
            job = self._prefilling[slot]
            c = min(self.prefill_chunk, g.ctx_len - job.offset)
            groups.setdefault(c, []).append((slot, job))
        finished: List[Tuple[int, int]] = []
        span = (self.tracer.span(
                    "prefill.chunk", slots=len(self._prefilling),
                    trace_ids=self._scope_ids(self._prefilling))
                if self.tracer.enabled else NULL_SPAN)
        with span:
            for c, members in sorted(groups.items()):
                for slot, job in members:
                    self.kv.ensure(slot, job.offset + c)
                tab = self.kv.device_tab()
                if not self.streamed:
                    for slot, job in members:
                        chunk = jnp.asarray(
                            job.toks[None, job.offset:job.offset + c])
                        off = jnp.full((1,), job.offset, jnp.int32)
                        logits, self.cache = self._chunk_paged(
                            self.params, chunk, self.cache, off,
                            tab[slot:slot + 1])
                        job.offset += c
                        if job.offset >= g.ctx_len:
                            finished.append(
                                (slot,
                                 int(self._greedy(logits)[0])))
                    continue
                n = len(members)
                padn = 1 << (n - 1).bit_length()
                rows = np.stack([job.toks[job.offset:job.offset + c]
                                 for _, job in members])
                offs = [job.offset for _, job in members]
                bt = tab[jnp.asarray([slot for slot, _ in members])]
                if padn > n:    # pad rows write to trash, logits ignored
                    rows = np.concatenate(
                        [rows, np.zeros((padn - n, c), rows.dtype)])
                    offs = offs + [0] * (padn - n)
                    bt = jnp.concatenate(
                        [bt, jnp.zeros((padn - n, self.kv.nmax),
                                       jnp.int32)])
                logits, self.caches = self.exec.prefill_chunk(
                    jnp.asarray(rows), self.caches,
                    jnp.asarray(offs, jnp.int32), block_tab=bt,
                    kv_span=g.ctx_len)
                nxt = self._greedy(logits)
                for i, (slot, job) in enumerate(members):
                    job.offset += c
                    if job.offset >= g.ctx_len:
                        finished.append((slot, int(nxt[i])))
        progressed = len(self._prefilling)
        for slot, token in finished:
            job = self._prefilling.pop(slot)
            self._prefix_insert(slot, job.toks)  # donate before any release
            self._emit(job.ref, token)      # first token, as full prefill
        return progressed

    def step(self) -> int:
        """Advance every live slot one greedy decode step (and every
        joining slot one prefill chunk, in paged chunked mode).

        Returns the number of slots that made progress (0 = idle).
        """
        progressed = 0
        if self.paged and self.kv.overlap:
            progressed += self._poll_swaps()
        if self._prefilling:
            progressed += self._advance_prefills()
        refs = [r for r in self.table.active_refs()
                if r.index not in self._prefilling
                and r.index not in self._pending_resume]
        if not refs:
            if (not progressed and self.paged and self.kv.overlap
                    and self.kv.outstanding):
                # nothing can decode until a DMA lands: block briefly
                # on the head job (stall-counted) so the pump keeps
                # pumping instead of idling with work in flight
                self.kv.wait_any(0.05)
                progressed += self._poll_swaps()
            if progressed:
                self.steps += 1
            return progressed
        if self.paged:
            if self.prefix is not None:
                # copy-on-write: detach any still-shared page this
                # step's decode writes would land in (donor tail pages)
                self._cow_barrier(refs)
            # allocate the page each live slot's pending write needs
            for ref in refs:
                self.kv.ensure(ref.index, int(self._pos[ref.index]) + 1)
            bt = self.kv.device_tab()
        span = (self.tracer.span(
                    "decode.step", slots=len(refs),
                    trace_ids=self._scope_ids(r.index for r in refs))
                if self.tracer.enabled else NULL_SPAN)
        with span:
            cur = jnp.asarray(self._cur)[:, None]
            pos = jnp.asarray(self._pos)
            if self.streamed:
                mask = self.table.mask()
                for slot in self._prefilling:   # still prefilling != live
                    mask[slot] = False
                for slot in self._pending_resume:   # awaiting async H2D
                    mask[slot] = False
                mask = jnp.asarray(mask)
                if self.paged:
                    logits, self.caches = self.exec.decode(
                        cur, self.caches, pos, slot_mask=mask,
                        block_tab=bt, kv_span=self._total)
                else:
                    logits, self.caches = self.exec.decode(
                        cur, self.caches, pos, slot_mask=mask)
            else:
                if self.paged:
                    logits, self.cache = self._decode_paged(
                        self.params, cur, self.cache, pos, bt)
                else:
                    logits, self.cache = self._decode(self.params, cur,
                                                      self.cache, pos)
            with (self.tracer.interval("decode.sync")
                  if self.tracer.enabled else NULL_SPAN):
                nxt = self._greedy(logits)
        if (self.paged and self.registry.enabled
                and self.kv.kv_format == "int8"):
            # dequant traffic: this step's fused kernel read every live
            # slot's full quantized context (int8 payload bytes)
            toks = sum(int(self._pos[r.index]) + 1 for r in refs)
            self.registry.counter("kv.dequant_bytes").inc(
                toks * self.cfg.kv_cache_bytes_per_token(1))
            self.registry.counter("kv.dequant_tokens").inc(toks)
        for ref in refs:
            self._emit(ref, int(nxt[ref.index]))
        self.steps += 1
        return len(refs) + progressed

    # ---------------------------------------------- preemption (swap-to-host)
    @property
    def parked_slots(self) -> int:
        return len(self._parked)

    def parked_keys(self) -> List[Any]:
        """Resume handles in preemption order (FIFO resume is fair)."""
        return list(self._parked)

    @property
    def in_flight(self) -> int:
        """Requests admitted and unfinished: live slots + parked."""
        return self.table.active_slots + len(self._parked)

    def swap_victim(self) -> Optional[SlotRef]:
        """Preemption policy: the live slot with the most remaining
        budget — the last to finish, i.e. the lowest-priority work —
        excluding slots still chunk-prefilling or awaiting an async
        swap-in.  Ties break to the lowest slot index (deterministic).

        The priority-aware generalization lives in
        ``RequestScheduler.select_victim`` (lowest priority class
        first, then longest remaining budget); this single-class policy
        is kept as its default-knob equivalent.
        """
        best, best_rem = None, -1
        for ref in self.table.active_refs():
            if (ref.index in self._prefilling
                    or ref.index in self._pending_resume):
                continue
            rem = self.table.state(ref).remaining
            if rem > best_rem:
                best, best_rem = ref, rem
        return best

    def preempt(self, ref: SlotRef,
                pages: Optional[int] = None) -> Optional[Any]:
        """Park a live slot: swap its KV pages to the host pool and end
        its lease.  Returns the resume handle (the request key), or
        ``None`` when the host pool cannot hold the slot's pages (or the
        slot is still chunk-prefilling / mid-swap) — the slot stays
        live.

        ``pages=k`` is a *partial* park: only the slot's ``k`` coldest
        pages move to the host, the hot tail stays device-resident
        under the handle (the lease still ends — a slot missing its
        prefix cannot decode), and ``resume`` reloads just the shed
        prefix.

        The release bumps the slot's epoch, so any SlotRef retained
        from before the preemption raises :class:`StaleSlotError`
        instead of touching whatever lease occupies the slot next —
        including this request's own post-``resume`` lease.
        """
        assert self.paged, "preempt requires paged=True"
        st = self.table.state(ref)              # validates the lease
        if (ref.index in self._prefilling
                or ref.index in self._pending_resume):
            return None
        handle = _park_handle(st.key)
        pools = self.caches if self.streamed else self.cache
        scope = self._slot_scope.get(ref.index, ())
        span = (self.tracer.span("swap.preempt", slot=ref.index,
                                 trace_ids=list(scope))
                if self.tracer.enabled else NULL_SPAN)
        with span:
            if not self.kv.swap_out(pools, ref.index, handle,
                                    pages=pages):
                return None                      # host pool exhausted
            st = self.table.release(ref)
        self._slot_scope.pop(ref.index, None)
        self._parked[handle] = _Parked(
            key=st.key, tokens=list(st.tokens), pos=st.pos,
            remaining=st.remaining, cur=int(self._cur[ref.index]),
            dec_pos=int(self._pos[ref.index]), trace_ids=tuple(scope),
            t_first_token=st.t_first_token)
        # the freed row keeps riding the batched decode like any dead
        # slot; its block-table row now points at the trash page, so the
        # parked writes can never land in a page re-issued to a joiner
        self._cur[ref.index] = 0
        self.swap_outs += 1
        return handle

    def resume(self, key: Any) -> Optional[SlotRef]:
        """Un-park a preempted request into any free slot: fresh lease
        (new epoch), fresh physical pages, block-table row remapped.
        ``None`` when slots or device pages are still exhausted — the
        request stays parked host-side."""
        assert self.paged, "resume requires paged=True"
        parked = self._parked[key]
        ref = self.table.acquire(parked.key, pos=parked.pos,
                                 remaining=parked.remaining)
        if ref is None:
            return None
        pools = self.caches if self.streamed else self.cache
        span = (self.tracer.span("swap.resume", slot=ref.index,
                                 trace_ids=list(parked.trace_ids))
                if self.tracer.enabled else NULL_SPAN)
        with span:
            new_pools = self.kv.swap_in(pools, ref.index, key)
            if new_pools is None:
                self.table.release(ref)          # pages still exhausted
                return None
        if self.streamed:
            self.caches = new_pools
        else:
            self.cache = new_pools
        if self.kv.overlap:
            # the H2D is in flight: the slot is leased but its block-
            # table row stays all-trash (interim decode writes park
            # harmlessly) and decode excludes it until poll applies it
            self._pending_resume.add(ref.index)
        if self.tracer.enabled and parked.trace_ids:
            self._slot_scope[ref.index] = parked.trace_ids
        st = self.table.state(ref)
        st.tokens.extend(parked.tokens)
        st.t_first_token = parked.t_first_token
        self._cur[ref.index] = parked.cur
        self._pos[ref.index] = parked.dec_pos
        del self._parked[key]
        self.swap_ins += 1
        return ref

    # ------------------------------------------- async swap/decode overlap
    def _poll_swaps(self) -> int:
        """Apply landed async swap DMA (overlap mode); returns the
        number of jobs applied (counts as step progress so the pump
        keeps pumping while transfers drain)."""
        pools, resumed, applied = self.kv.poll(self._pools())
        if applied:
            self._set_pools(pools)
            for slot in resumed:
                self._pending_resume.discard(slot)
        return applied

    def fence(self) -> None:
        """Barrier: wait for every outstanding swap DMA and apply it —
        called at the policy boundary (before budgets retarget) so
        token identity is guaranteed across overlap schedules.  No-op
        for inline-DMA generators."""
        if self.kv is None or not self.kv.overlap:
            return
        pools, resumed, applied = self.kv.fence(self._pools())
        if applied:
            self._set_pools(pools)
            for slot in resumed:
                self._pending_resume.discard(slot)

    # -------------------------------------------------- dynamic capacity
    def resize(self, num_slots: int) -> int:
        """Grow/shrink the slot table; returns the actual capacity.

        Shrink only drops free top slots (never live work).  Paged mode
        touches just the block table; dense mode pads/slices the cache
        rows (the decode jit retraces at the new batch, which is why the
        engine only retargets at policy boundaries).
        """
        actual = self.table.resize(num_slots)
        if actual == self.num_slots:
            return actual
        keep = min(actual, self.num_slots)
        for name in ("_cur", "_pos"):
            arr = np.zeros(actual, np.int32)
            arr[:keep] = getattr(self, name)[:keep]
            setattr(self, name, arr)
        if self.paged:
            self.kv.resize_slots(actual)
        elif self.streamed:
            self.caches = kvpool.resize_cache_rows(self.caches, actual)
        else:
            self.cache = kvpool.resize_cache_rows(self.cache, actual)
        self.num_slots = actual
        return actual

    def set_page_budget(self, pages: int) -> int:
        """Retarget the paged pool's usable-page budget (paged only).

        A shrink first evicts cold cached prefix pages (LRU demotion to
        the host tier) so the cache never blocks the pool from meeting
        the placement's smaller device share.
        """
        assert self.paged, "set_page_budget requires paged=True"
        pools = self._pools()
        if self.prefix is not None:
            over = self.kv.pool.referenced_pages - pages
            if over > 0:
                _, pools = self.prefix.reclaim(over, self.kv, pools)
        pools, actual = self.kv.resize_pages(pools, pages)
        self._set_pools(pools)
        return actual

    def set_host_page_budget(self, pages: int) -> int:
        """Retarget the host swap pool's page budget (paged only)."""
        assert self.paged, "set_host_page_budget requires paged=True"
        return self.kv.set_host_budget(pages)

    def retarget(self, num_slots: Optional[int] = None,
                 page_budget: Optional[int] = None,
                 host_page_budget: Optional[int] = None,
                 prefix_page_budget: Optional[int] = None
                 ) -> Dict[str, int]:
        """Policy-boundary hook: apply the live placement's capacity.

        The page budget is clamped to what the block tables can address
        (``num_slots * nmax`` — anything beyond is device memory no slot
        could ever reference) and floored at one worst-case request
        (``nmax`` pages) so the pool can never starve admission.  The
        host budget (the placement's ``c_cpu`` KV share) is capped at
        parking every slot worst-case (``num_slots * nmax``); a zero
        budget legitimately disables preemption.  The prefix-cache
        budget caps how many *device* pages the radix cache may hold —
        the placement's arbitration between live KV and cached prefixes
        — enforced immediately by LRU demotion to the host tier.
        """
        out: Dict[str, int] = {}
        self.fence()   # settle outstanding swap DMA before resizing
        if num_slots is not None:
            out["slots"] = self.resize(num_slots)
        if page_budget is not None and self.paged:
            budget = max(min(page_budget, self.num_slots * self.kv.nmax),
                         self.kv.nmax)
            out["pages"] = self.set_page_budget(budget)
        if host_page_budget is not None and self.paged:
            budget = min(host_page_budget, self.num_slots * self.kv.nmax)
            out["host_pages"] = self.set_host_page_budget(budget)
        if (prefix_page_budget is not None and self.paged
                and self.prefix is not None):
            budget = max(0, min(prefix_page_budget, self.kv.pool.capacity))
            self.prefix.budget = budget
            self._set_pools(self.prefix.enforce(self.kv, self._pools()))
            out["prefix_pages"] = budget
        return out

    def harvest(self) -> List[Tuple[Any, str, List[int]]]:
        """Drain (key, text, tokens) for rows finished since last call."""
        return [f[:3] for f in self.harvest_stamped()]

    def harvest_stamped(self) -> List[Tuple[Any, str, List[int], float]]:
        """Drain (key, text, tokens, t_first_token) for rows finished
        since last call; ``t_first_token`` is the ``perf_counter`` time
        the prefill emitted the row's first token."""
        out, self._finished = self._finished, []
        return out

    def run(self, prompts: List[str],
            schedule: Optional[Sequence[int]] = None) -> List[str]:
        """Convenience driver: join everything (as slots free), pump, drain.

        ``schedule[i]`` caps how many queued prompts may join before step
        ``i`` (joins beyond the schedule are unthrottled) — used by the
        equivalence tests to randomize join/leave interleavings.
        """
        pending = list(enumerate(prompts))[::-1]    # pop() = arrival order
        results: List[Optional[str]] = [None] * len(prompts)
        tick = 0
        while pending or self.active_slots:
            allow = len(pending)
            if schedule is not None and tick < len(schedule):
                allow = min(allow, schedule[tick])
            joined = 0
            while pending and joined < allow and self.admit_capacity > 0:
                key, prompt = pending.pop()
                assert self.join(key, prompt) is not None
                joined += 1
            self.step()
            for key, text, _ in self.harvest():
                results[key] = text
            tick += 1
        for key, text, _ in self.harvest():
            results[key] = text
        assert all(r is not None for r in results)
        return results     # type: ignore[return-value]
