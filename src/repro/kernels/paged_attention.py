"""Paged decode attention Pallas TPU kernel (vLLM-style block tables).

One new token per sequence attends over a KV cache stored as pooled
fixed-size *pages*: layer KV lives in ``(P, page, KV, D)`` arrays and a
``(B, nmax)`` block table maps each slot's logical block ``i`` to the
page holding positions ``[i*page, (i+1)*page)``.  The kernel gathers by
block table *inside* the grid via scalar prefetch: the table is a
scalar-prefetch operand, so each KV BlockSpec's ``index_map`` picks the
physical page for grid step ``(b, kh, ik)`` and the DMA engine streams
exactly the pages a sequence owns — no host-side gather, no dense copy.

Grid: (batch, kv_head, blocks); blocks innermost ("arbitrary") with VMEM
scratch carrying the online softmax, mirroring ``decode_attention.py``.
Blocks past ``kv_len`` are skipped by ``pl.when``, and their
``index_map`` entries are *clamped to the slot's last real block*: the
Pallas pipeline elides the DMA when consecutive grid steps resolve to
the same block index, so padded/trash entries of short block tables
re-reference the already-resident page instead of streaming the trash
page once per padded block.  (Measured in
``tests/test_quant_kv.py::test_index_map_clamps_padded_blocks``: a slot
using 2 of 8 table entries issues 2 distinct page fetches per head, not
8 — without the clamp every padded entry DMAs the trash page before
``pl.when`` gates its compute.)

Quantized pools (``k_scale``/``v_scale`` given): KV pages are int8 and a
``(P, KV)`` fp32 per-page-per-head scale array is viewed as ``(P, 1, KV)``
and streamed into VMEM one ``(1, 1, KV)`` block per grid step, routed by
the same clamped block-table lookup as the page itself.  (Scalar prefetch
would put the whole array in SMEM, which is 1 MiB and pads each row to
128 words, so pools beyond about 2k pages could not compile.)  The kernel
picks head ``kh``'s lane and dequantizes each gathered page inside the
grid (``int8 page * scale[tab[b, ik], kh]``) before the fp32
online-softmax accumulation, so quantization never touches the
accumulation precision.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tab_ref, kvlen_ref, *refs,
            scale: float, window: Optional[int], softcap: Optional[float],
            page: int, nk: int, quant: bool):
    if quant:
        (ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        ks_ref = vs_ref = None
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    kh = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = kvlen_ref[b]
    k_start = ik * page
    needed = k_start < kv_len
    if window is not None:
        needed = jnp.logical_and(needed, k_start + page > kv_len - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)
        if quant:
            # per-page-per-head dequant: the (1, KV) scale block was
            # routed by the same block-table entry as this page's DMA;
            # head kh's lane is selected by a masked lane reduction
            def head_scale(ref):
                row = ref[0]                                 # (1, KV)
                lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
                return jnp.sum(jnp.where(lane == kh, row, 0.0), axis=1,
                               keepdims=True)                # (1, 1)
            k = k * head_scale(ks_ref)
            v = v * head_scale(vs_ref)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < kv_len
        if window is not None:
            mask &= k_pos >= kv_len - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _kv_index_map(page: int):
    """Block-table page lookup with padded entries clamped to the slot's
    last real block.

    For grid step ``(b, kh, ik)`` with ``ik`` beyond the slot's live
    blocks, returning ``tab[b, ik]`` (the trash page) would DMA a page
    whose compute ``pl.when`` then discards — the docstring's old "cost
    nothing" claim was wrong about the memory system.  Clamping ``ik``
    to the last block covered by ``kv_len`` makes every padded step
    resolve to the same (already resident) page, which the Pallas
    pipeline recognizes and skips re-fetching.
    """
    def index_map(b, kh, ik, tab, kl):
        last = jnp.maximum((kl[b] + page - 1) // page - 1, 0)
        return (tab[b, jnp.minimum(ik, last)], kh, 0, 0)
    return index_map


def _scale_index_map(page: int):
    """The page lookup of :func:`_kv_index_map` for ``(P, 1, KV)`` scale
    blocks: every head of a page shares one block."""
    kv_map = _kv_index_map(page)

    def index_map(b, kh, ik, tab, kl):
        return (kv_map(b, kh, ik, tab, kl)[0], 0, 0)
    return index_map


def paged_decode_attention_pallas(
    q: jnp.ndarray,          # (B, H, D)
    k_pool: jnp.ndarray,     # (P, page, KV, D)
    v_pool: jnp.ndarray,     # (P, page, KV, D)
    block_tab: jnp.ndarray,  # (B, nmax) int32 page ids
    kv_len: jnp.ndarray,     # (B,)
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,   # (P, KV) fp32, int8 pools
    v_scale: Optional[jnp.ndarray] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, d = q.shape
    p_pages, page, kvh, _ = k_pool.shape
    nmax = block_tab.shape[1]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    quant = k_scale is not None
    assert (v_scale is not None) == quant, "k_scale/v_scale come as a pair"

    qg = q.reshape(b, kvh, g, d)                 # (B, KV, G, D)
    kt = k_pool.transpose(0, 2, 1, 3)            # (P, KV, page, D)
    vt = v_pool.transpose(0, 2, 1, 3)
    block_tab = block_tab.astype(jnp.int32)
    kv_len = kv_len.astype(jnp.int32)

    kernel = functools.partial(
        _kernel, scale=scale, window=window, softcap=softcap,
        page=page, nk=nmax, quant=quant)

    kv_map = _kv_index_map(page)
    in_specs = [
        pl.BlockSpec((1, 1, g, d), lambda b, kh, ik, tab, kl: (b, kh, 0, 0)),
        pl.BlockSpec((1, 1, page, d), kv_map),
        pl.BlockSpec((1, 1, page, d), kv_map),
    ]
    operands = [block_tab, kv_len, qg, kt, vt]
    if quant:
        scale_spec = pl.BlockSpec((1, 1, kvh), _scale_index_map(page))
        in_specs = [scale_spec, scale_spec] + in_specs
        operands[2:2] = [k_scale.astype(jnp.float32).reshape(p_pages, 1, kvh),
                         v_scale.astype(jnp.float32).reshape(p_pages, 1, kvh)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # block table, kv_len
        grid=(b, kvh, nmax),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b, kh, ik, tab, kl: (b, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret,
    )(*operands)
    return out.reshape(b, h, d)
