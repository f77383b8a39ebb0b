"""Decode attention Pallas TPU kernel: one new token vs a long KV cache.

The decode hot loop is *memory-bound*: each step streams the whole KV cache
(HBM -> VMEM) to produce one token. The kernel therefore:
  * tiles the cache sequence dimension (``block_kv``) and keeps the query
    group resident in VMEM across the whole sweep;
  * maps GQA groups to the kv-head grid axis so each KV tile is read exactly
    once for all ``H/KV`` query heads sharing it (the bandwidth optimum);
  * masks by per-sequence ``kv_len`` and optional sliding window.

Grid: (batch, kv_head, kv_blocks), kv innermost ("arbitrary") with VMEM
scratch accumulators carrying the online softmax.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *,
            scale: float, window: Optional[int], softcap: Optional[float],
            block_kv: int, nk: int):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = kvlen_ref[b]
    k_start = ik * block_kv
    needed = k_start < kv_len
    if window is not None:
        needed = jnp.logical_and(needed,
                                 k_start + block_kv > kv_len - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, bk)
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


def decode_attention_pallas(
    q: jnp.ndarray,        # (B, H, D)
    k_cache: jnp.ndarray,  # (B, S, KV, D)
    v_cache: jnp.ndarray,  # (B, S, KV, D)
    kv_len: jnp.ndarray,   # (B,)
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_kv: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, d = q.shape
    _, s, kvh, _ = k_cache.shape
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    block_kv = min(block_kv, s)
    assert s % block_kv == 0, (s, block_kv)
    nk = s // block_kv

    qg = q.reshape(b, kvh, g, d)                 # (B, KV, G, D)
    kt = k_cache.transpose(0, 2, 1, 3)           # (B, KV, S, D)
    vt = v_cache.transpose(0, 2, 1, 3)
    kv_len = kv_len.astype(jnp.int32)

    kernel = functools.partial(
        _kernel, scale=scale, window=window, softcap=softcap,
        block_kv=block_kv, nk=nk)

    # per-row kv_len rides scalar prefetch (SMEM), as in the paged kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kvh, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b, kh, ik, kl: (b, kh, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, kh, ik, kl: (b, kh, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, kh, ik, kl: (b, kh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b, kh, ik, kl: (b, kh, 0, 0)),
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
        name="decode_attention",
    )(kv_len, qg, kt, vt)
    return out.reshape(b, h, d)
