"""Flash attention Pallas TPU kernel (prefill / training).

TPU-native design notes (HBM -> VMEM -> MXU):
  * grid = (batch, q_head, q_blocks, kv_blocks); the kv dimension is
    innermost/"arbitrary" so the f32 accumulators live in VMEM scratch and
    persist across kv steps (the online-softmax recurrence).
  * BlockSpecs stage (block_q x head_dim) / (block_kv x head_dim) tiles into
    VMEM; head_dim (64..256) and the default 256-wide blocks are multiples of
    the 128-lane MXU tiling.
  * GQA is expressed in the k/v index_map (q head -> kv head = h // group):
    repeated KV heads are never materialized.
  * causal / sliding-window blocks that are fully masked are skipped with
    ``pl.when`` — predicated out on TPU, so wasted MXU work is not issued.

Validated on CPU with ``interpret=True`` against ``ref.attention_reference``.
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
            scale: float, causal: bool, window: Optional[int],
            softcap: Optional[float], block_q: int, block_kv: int,
            nk: int, q_offset: int):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q + q_offset
    k_start = ik * block_kv
    # block-level reachability: skip fully-masked tiles
    needed = True
    if causal:
        needed = k_start <= q_start + block_q - 1
    if window is not None:
        needed = jnp.logical_and(
            needed, k_start + block_kv - 1 > q_start - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < kvlen_ref[b]
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
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


def flash_attention_pallas(
    q: jnp.ndarray,                # (B, Sq, H, D)
    k: jnp.ndarray,                # (B, Sk, KV, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    kv_len: Optional[jnp.ndarray] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 256,
    block_kv: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    dv = v.shape[-1]
    group = h // kvh
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk)
    assert sq % block_q == 0 and sk % block_kv == 0, (sq, sk, block_q, block_kv)
    nq, nk = sq // block_q, sk // block_kv

    qt = q.transpose(0, 2, 1, 3)       # (B, H, Sq, D)
    kt = k.transpose(0, 2, 1, 3)       # (B, KV, Sk, D)
    vt = v.transpose(0, 2, 1, 3)
    if kv_len is None:
        kv_len = jnp.full((b,), sk, jnp.int32)
    kv_len = kv_len.astype(jnp.int32)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_kv=block_kv, nk=nk,
        q_offset=q_offset)

    # per-row kv_len rides scalar prefetch (SMEM): a rank-1 (1,) VMEM
    # block over (B,) is neither the full dim nor lane-aligned for B > 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, iq, ik, kl: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, iq, ik, kl, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, dv),
                         lambda b, h, iq, ik, kl, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dv),
                               lambda b, h, iq, ik, kl: (b, h, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
        interpret=interpret,
    )(kv_len, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)   # (B, Sq, H, D)
