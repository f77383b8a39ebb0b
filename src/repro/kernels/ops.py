"""Public kernel API with backend dispatch.

Every op has three tiers:
  * ``pallas``  — the TPU kernel (``<name>.py``), validated with
    ``interpret=True`` on CPU in tests;
  * a memory-efficient pure-jnp implementation (``kv_scan`` /
    ``block_causal`` / ``blocked``) used on CPU and for multi-pod dry-run
    lowering — same memory *shape* as the TPU kernel (online softmax,
    blocked top-k) so roofline terms derived from the lowered HLO are
    representative;
  * the naive reference in ``ref.py`` (the oracle).

``impl=None`` auto-selects: pallas on TPU, the jnp-blocked tier elsewhere.
On TPU the only non-Pallas choice is explicit: ``flash_attention`` with a
per-row ``q_offset`` array (chunked prefill) routes to ``kv_scan``, which
masks per batch row.  Pallas kernels run in interpret mode only off TPU.

Every dispatch is counted in ``DISPATCH_COUNTS`` under ``(op, impl)``.
Dispatch runs while a caller traces, so under ``jit`` each compiled
program counts once; ``dispatch_counts()`` is how a run reports which
implementation each op took.
"""
from __future__ import annotations

import collections
import functools
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref

NEG_INF = -1e30


DISPATCH_COUNTS: "collections.Counter[Tuple[str, str]]" = \
    collections.Counter()
_DISPATCH_LOCK = threading.Lock()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _dispatch(op: str, impl: Optional[str], tpu: str, other: str) -> str:
    """Resolve ``impl`` (``None``: ``tpu`` on TPU, else ``other``) and
    count the choice."""
    if impl is None:
        impl = tpu if _on_tpu() else other
    with _DISPATCH_LOCK:
        DISPATCH_COUNTS[(op, impl)] += 1
    return impl


def dispatch_counts() -> Dict[str, Dict[str, int]]:
    """``{op: {impl: traces}}`` of every dispatch so far."""
    out: Dict[str, Dict[str, int]] = {}
    with _DISPATCH_LOCK:
        for (op, impl), n in sorted(DISPATCH_COUNTS.items()):
            out.setdefault(op, {})[impl] = n
    return out


def _softcap(x, cap):
    return x if cap is None else cap * jnp.tanh(x / cap)


# ===========================================================================
# Flash attention (training / prefill)
# ===========================================================================

def flash_attention(
    q: jnp.ndarray,                # (B, Sq, H, D)
    k: jnp.ndarray,                # (B, Sk, KV, D)
    v: jnp.ndarray,                # (B, Sk, KV, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    kv_len: Optional[jnp.ndarray] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
    block_q: int = 256,
    block_kv: int = 256,
) -> jnp.ndarray:
    if impl is None and not isinstance(q_offset, int):
        # per-row q_offset arrays (chunked prefill) route here on every
        # backend: the scan path masks per batch row, which the TPU
        # kernel and flash_vjp do not support
        impl = "kv_scan"
    aligned = q.shape[1] % 256 == 0 and k.shape[1] % 256 == 0
    # off TPU, aligned shapes take flash_vjp (memory-efficient fwd AND bwd)
    impl = _dispatch("flash_attention", impl, "pallas",
                     "flash_vjp" if aligned else "kv_scan")
    if impl == "naive":
        return ref.attention_reference(
            q, k, v, causal=causal, window=window, softcap=softcap,
            kv_len=kv_len, q_offset=q_offset, scale=scale)
    if impl == "flash_vjp":
        from repro.kernels import flash_vjp
        return flash_vjp.flash_attention_train(
            q, k, v, causal=causal, window=window, softcap=softcap,
            kv_len=kv_len, q_offset=q_offset, scale=scale)
    if impl == "pallas":
        from repro.kernels import flash_attention as fa
        return fa.flash_attention_pallas(
            q, k, v, causal=causal, window=window, softcap=softcap,
            kv_len=kv_len, q_offset=q_offset, scale=scale,
            block_q=block_q, block_kv=block_kv,
            interpret=not _on_tpu())
    if impl == "kv_scan":
        return _attention_kv_scan(
            q, k, v, causal=causal, window=window, softcap=softcap,
            kv_len=kv_len, q_offset=q_offset, scale=scale, block_kv=block_kv)
    if impl == "block_causal":
        return _attention_block_causal(
            q, k, v, window=window, softcap=softcap, scale=scale,
            block_q=block_q, block_kv=block_kv)
    raise ValueError(f"unknown attention impl {impl!r}")


def _block_causal_ok(q, k, causal, kv_len, q_offset) -> bool:
    return (causal and kv_len is None and isinstance(q_offset, int)
            and q_offset == 0 and q.shape[1] == k.shape[1]
            and q.shape[1] >= 512)


def _grouped(q, k, v):
    """Reshape to grouped-query form to avoid materializing repeated KV."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    q_ = q.reshape(b, sq, kvh, g, d).transpose(0, 2, 3, 1, 4)  # (B,KV,G,Sq,D)
    k_ = k.transpose(0, 2, 1, 3)                                # (B,KV,Sk,D)
    v_ = v.transpose(0, 2, 1, 3)
    return q_, k_, v_, g


def _ungroup(out, b, sq, h, d):
    # (B,KV,G,Sq,D) -> (B,Sq,H,D)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)


def _attention_kv_scan(q, k, v, *, causal, window, softcap, kv_len,
                       q_offset, scale, block_kv):
    """Online-softmax attention scanning KV blocks (memory O(Sq + block))."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    dv = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    block_kv = min(block_kv, sk)
    nblk = -(-sk // block_kv)
    pad = nblk * block_kv - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q_, k_, v_, g = _grouped(q, k, v)
    kvh = k_.shape[1]
    # (nblk, B, KV, bk, D)
    k_b = k_.reshape(b, kvh, nblk, block_kv, d).transpose(2, 0, 1, 3, 4)
    v_b = v_.reshape(b, kvh, nblk, block_kv, dv).transpose(2, 0, 1, 3, 4)

    q32 = q_.astype(jnp.float32) * scale
    # scalar q_offset: shared (Sq,) positions; per-row array: (B, Sq)
    per_row = jnp.ndim(q_offset) > 0
    if per_row:
        q_pos = q_offset[:, None] + jnp.arange(sq)          # (B, Sq)
    else:
        q_pos = jnp.arange(sq) + q_offset                   # (Sq,)
    valid_len = kv_len if kv_len is not None else jnp.full((b,), sk)

    def body(carry, xs):
        m, l, acc = carry
        k_blk, v_blk, start = xs
        s = jnp.einsum("bkgqd,bksd->bkgqs", q32,
                       k_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        s = _softcap(s, softcap)
        k_pos = start + jnp.arange(block_kv)                # (bk,)
        mask = k_pos[None, :] < valid_len[:, None]          # (B, bk)
        mask = mask[:, None, None, None, :]                 # (B,1,1,1,bk)

        def qk_mask(cmp):                                   # -> (B,1,1,Sq,bk)
            if per_row:
                return cmp(k_pos[None, None, :],
                           q_pos[:, :, None])[:, None, None]
            return cmp(k_pos[None, :], q_pos[:, None])[None, None, None]

        if causal:
            mask = mask & qk_mask(lambda k_, q_: k_ <= q_)
        if window is not None:
            mask = mask & qk_mask(lambda k_, q_: k_ > q_ - window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bksd->bkgqd", p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, g, sq, dv), jnp.float32)
    starts = jnp.arange(nblk) * block_kv
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (k_b, v_b, starts))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return _ungroup(out, b, sq, h, dv).astype(q.dtype)


def _attention_block_causal(q, k, v, *, window, softcap, scale,
                            block_q, block_kv):
    """Exact-FLOPs causal attention: scan over lower-triangular block pairs.

    Unlike ``kv_scan`` (which computes and masks the upper triangle), this
    only visits blocks (i, j) with j <= i — the HLO FLOP count matches the
    true causal cost, which keeps the roofline compute term honest.
    """
    b, sq, h, d = q.shape
    dv = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    blk = min(block_q, block_kv, sq)
    assert sq % blk == 0, (sq, blk)
    t = sq // blk
    pairs = [(i, j) for i in range(t) for j in range(i + 1)
             if window is None or (i - j) * blk < window + blk]
    pi = jnp.array([p[0] for p in pairs], jnp.int32)
    pj = jnp.array([p[1] for p in pairs], jnp.int32)

    q_, k_, v_, g = _grouped(q, k, v)
    kvh = k_.shape[1]
    q32 = q_.astype(jnp.float32) * scale

    def body(carry, ij):
        m, l, acc = carry
        i, j = ij
        qi = jax.lax.dynamic_slice_in_dim(q32, i * blk, blk, axis=3)
        kj = jax.lax.dynamic_slice_in_dim(k_, j * blk, blk, axis=2)
        vj = jax.lax.dynamic_slice_in_dim(v_, j * blk, blk, axis=2)
        s = jnp.einsum("bkgqd,bksd->bkgqs", qi, kj.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        s = _softcap(s, softcap)
        q_pos = i * blk + jnp.arange(blk)
        k_pos = j * blk + jnp.arange(blk)
        mask = k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        mi = jax.lax.dynamic_slice_in_dim(m, i * blk, blk, axis=3)
        li = jax.lax.dynamic_slice_in_dim(l, i * blk, blk, axis=3)
        ai = jax.lax.dynamic_slice_in_dim(acc, i * blk, blk, axis=3)
        m_new = jnp.maximum(mi, s.max(axis=-1))
        corr = jnp.exp(mi - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = li * corr + p.sum(axis=-1)
        a_new = ai * corr[..., None] + jnp.einsum(
            "bkgqs,bksd->bkgqd", p, vj.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m = jax.lax.dynamic_update_slice_in_dim(m, m_new, i * blk, axis=3)
        l = jax.lax.dynamic_update_slice_in_dim(l, l_new, i * blk, axis=3)
        acc = jax.lax.dynamic_update_slice_in_dim(acc, a_new, i * blk, axis=3)
        return (m, l, acc), None

    m0 = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, g, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (pi, pj))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return _ungroup(out, b, sq, h, dv).astype(q.dtype)


# ===========================================================================
# Decode attention (one new token vs KV cache)
# ===========================================================================

def decode_attention(
    q: jnp.ndarray,        # (B, H, D)
    k_cache: jnp.ndarray,  # (B, S, KV, D)
    v_cache: jnp.ndarray,  # (B, S, KV, D)
    kv_len: jnp.ndarray,   # (B,)
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
    block_kv: int = 512,
) -> jnp.ndarray:
    impl = _dispatch("decode_attention", impl, "pallas", "einsum")
    if impl == "naive":
        return ref.decode_attention_reference(
            q, k_cache, v_cache, kv_len, window=window, softcap=softcap,
            scale=scale)
    if impl == "pallas":
        from repro.kernels import decode_attention as da
        return da.decode_attention_pallas(
            q, k_cache, v_cache, kv_len, window=window, softcap=softcap,
            scale=scale, block_kv=block_kv, interpret=not _on_tpu())
    if impl == "einsum":
        return _decode_einsum(q, k_cache, v_cache, kv_len,
                              window=window, softcap=softcap, scale=scale)
    raise ValueError(f"unknown decode impl {impl!r}")


def _decode_einsum(q, k_cache, v_cache, kv_len, *, window, softcap, scale):
    b, s, kvh, d = k_cache.shape
    dv = v_cache.shape[-1]
    h = q.shape[1]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    # Decode is HBM-bound: never materialize f32 copies of the KV cache.
    # bf16 caches stay bf16 into the matmul with f32 accumulation (native
    # MXU behaviour); scaling happens on the f32 scores.  Measured on the
    # llama3-8b decode_32k dry-run: removes ~4 cache-sized f32
    # materializations per layer (see EXPERIMENTS.md section Perf).
    lowp = k_cache.dtype == jnp.bfloat16
    q_ = q.reshape(b, kvh, g, d)
    if lowp:
        q_ = q_.astype(k_cache.dtype)
    else:
        q_ = q_.astype(jnp.float32)
        k_cache = k_cache.astype(jnp.float32)
        v_cache = v_cache.astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", q_, k_cache,
                        preferred_element_type=jnp.float32) * scale
    scores = _softcap(scores, softcap)
    k_pos = jnp.arange(s)[None, :]
    mask = k_pos < kv_len[:, None]
    if window is not None:
        mask &= k_pos >= (kv_len[:, None] - window)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    m = scores.max(axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = p.sum(axis=-1, keepdims=True)
    probs = p / jnp.maximum(l, 1e-30)
    if lowp:
        probs = probs.astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, dv).astype(q.dtype)


# ===========================================================================
# Paged decode attention (block-table gather over pooled KV pages)
# ===========================================================================

def paged_decode_attention(
    q: jnp.ndarray,          # (B, H, D)
    k_pool: jnp.ndarray,     # (P, page, KV, D) pooled cache pages
    v_pool: jnp.ndarray,     # (P, page, KV, D)
    block_tab: jnp.ndarray,  # (B, nmax) int32 page ids per slot block
    kv_len: jnp.ndarray,     # (B,)
    *,
    kv_span: Optional[int] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,   # (P, KV) int8 dequant scales
    v_scale: Optional[jnp.ndarray] = None,
    impl: Optional[str] = None,
) -> jnp.ndarray:
    """Decode attention over a paged KV cache.

    ``gather`` (the CPU default) materializes the dense per-slot view via
    the block table, statically truncated to ``kv_span`` (the dense cache
    length), and runs the *exact* dense einsum path — so paged decode is
    bit-identical to the dense cache layout.  ``pallas`` streams pages
    inside the kernel via scalar-prefetch block tables (no dense copy).

    For int8 pools, ``k_scale``/``v_scale`` carry the per-page-per-head
    fp32 dequant scales; every backend applies the identical
    ``int8 * scale`` product (the pallas grid dequantizes in-kernel, the
    gather/naive tiers dequantize at gather time), so the cross-backend
    identity contract survives quantization.
    """
    impl = _dispatch("paged_decode_attention", impl, "pallas", "gather")
    if impl == "naive":
        return ref.paged_decode_attention_reference(
            q, k_pool, v_pool, block_tab, kv_len, kv_span=kv_span,
            window=window, softcap=softcap, scale=scale,
            k_scale=k_scale, v_scale=v_scale)
    if impl == "pallas":
        from repro.kernels import paged_attention as pa
        return pa.paged_decode_attention_pallas(
            q, k_pool, v_pool, block_tab, kv_len, window=window,
            softcap=softcap, scale=scale, k_scale=k_scale,
            v_scale=v_scale, interpret=not _on_tpu())
    if impl == "gather":
        k_dense = ref.gather_paged_kv(k_pool, block_tab, kv_span,
                                      scale=k_scale)
        v_dense = ref.gather_paged_kv(v_pool, block_tab, kv_span,
                                      scale=v_scale)
        return _decode_einsum(q, k_dense, v_dense, kv_len,
                              window=window, softcap=softcap, scale=scale)
    raise ValueError(f"unknown paged decode impl {impl!r}")


# ===========================================================================
# Retrieval top-k (exact inner-product search)
# ===========================================================================

def retrieval_topk(
    queries: jnp.ndarray,   # (Q, D)
    database: jnp.ndarray,  # (N, D)
    k: int,
    *,
    impl: Optional[str] = None,
    block_n: int = 4096,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-k by inner product. Returns (scores (Q,k), indices (Q,k))."""
    impl = _dispatch("retrieval_topk", impl, "pallas", "blocked")
    if impl == "naive":
        return ref.topk_reference(queries, database, k)
    if impl == "pallas":
        from repro.kernels import topk_retrieval as tk
        return tk.topk_pallas(queries, database, k, block_n=block_n,
                              interpret=not _on_tpu())
    if impl == "blocked":
        return _topk_blocked(queries, database, k, block_n=block_n)
    raise ValueError(f"unknown topk impl {impl!r}")


def _topk_blocked(queries, database, k, *, block_n):
    qn, d = queries.shape
    n = database.shape[0]
    block_n = min(block_n, n)
    nblk = -(-n // block_n)
    pad = nblk * block_n - n
    if pad:
        database = jnp.pad(database, ((0, pad), (0, 0)))
    db = database.reshape(nblk, block_n, d)
    q32 = queries.astype(jnp.float32)

    def body(carry, xs):
        run_s, run_i = carry
        db_blk, start = xs
        s = jnp.einsum("qd,nd->qn", q32, db_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        idx = start + jnp.arange(block_n)
        s = jnp.where(idx[None, :] < n, s, NEG_INF)
        cat_s = jnp.concatenate([run_s, s], axis=1)
        cat_i = jnp.concatenate([run_i, jnp.broadcast_to(idx, (qn, block_n))],
                                axis=1)
        new_s, pos = jax.lax.top_k(cat_s, k)
        new_i = jnp.take_along_axis(cat_i, pos, axis=1)
        return (new_s, new_i), None

    s0 = jnp.full((qn, k), NEG_INF, jnp.float32)
    i0 = jnp.full((qn, k), -1, jnp.int32)
    starts = jnp.arange(nblk) * block_n
    (scores, idx), _ = jax.lax.scan(body, (s0, i0), (db, starts))
    return scores, idx


# ===========================================================================
# Retrieval multi-partition merge (IVF scoreboard fusion)
# ===========================================================================

def retrieval_topk_merge(
    part_scores: jnp.ndarray,   # (Q, P, k) per-partition top-k scores
    part_ids: jnp.ndarray,      # (Q, P, k) matching global chunk ids
    mask: jnp.ndarray,          # (Q, P) bool — per-query IVF probe set
    k: int,
    *,
    impl: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fuse per-partition scoreboards into a global top-k without a host
    round trip.  The mask is per (query, partition): masked-out (pruned)
    entries never contribute — their scores are forced to NEG_INF *and*
    their ids to the ``-1`` sentinel, so when fewer than ``k`` real
    candidates exist the output tail is ``(NEG_INF, -1)``, never a
    phantom id (all three backends + the ref oracle agree)."""
    impl = _dispatch("retrieval_topk_merge", impl, "pallas", "blocked")
    if impl == "naive":
        return ref.topk_merge_reference(part_scores, part_ids, mask, k)
    if impl == "pallas":
        from repro.kernels import topk_retrieval as tk
        return tk.topk_merge_pallas(part_scores, part_ids, mask, k,
                                    interpret=not _on_tpu())
    if impl == "blocked":
        return _topk_merge_blocked(part_scores, part_ids, mask, k)
    raise ValueError(f"unknown merge impl {impl!r}")


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_merge_blocked(part_scores, part_ids, mask, k):
    """Scan partitions with a running (Q, k) scoreboard — same memory shape
    as the Pallas kernel (never materializes the (Q, P*k) concat)."""
    qn = part_scores.shape[0]

    def body(carry, xs):
        run_s, run_i = carry
        s, i, m = xs                              # (Q, k), (Q, k), (Q,)
        s = jnp.where(m[:, None], s.astype(jnp.float32), NEG_INF)
        i = jnp.where(m[:, None], i.astype(jnp.int32), -1)
        cat_s = jnp.concatenate([run_s, s], axis=1)
        cat_i = jnp.concatenate([run_i, i], axis=1)
        new_s, pos = jax.lax.top_k(cat_s, k)
        return (new_s, jnp.take_along_axis(cat_i, pos, axis=1)), None

    s0 = jnp.full((qn, k), NEG_INF, jnp.float32)
    i0 = jnp.full((qn, k), -1, jnp.int32)
    (scores, idx), _ = jax.lax.scan(
        body, (s0, i0),
        (part_scores.transpose(1, 0, 2), part_ids.transpose(1, 0, 2),
         mask.astype(bool).T))
    return scores, idx


# ===========================================================================
# RMSNorm
# ===========================================================================

def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6,
            *, impl: Optional[str] = None) -> jnp.ndarray:
    impl = _dispatch("rmsnorm", impl, "pallas", "jnp")
    if impl == "pallas":
        from repro.kernels import rmsnorm as rk
        return rk.rmsnorm_pallas(x, w, eps, interpret=not _on_tpu())
    return ref.rmsnorm_reference(x, w, eps)
