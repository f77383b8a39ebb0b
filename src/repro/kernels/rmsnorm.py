"""Fused RMSNorm Pallas TPU kernel.

Single HBM pass per row tile: load (block_r x D) into VMEM, reduce in f32,
scale, write back — avoids the separate mean/rsqrt/mul HLO round-trips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# fp32 bytes of one row tile: the input and output tiles are
# double-buffered and the fp32 temporaries share the 16 MiB scoped VMEM
_TILE_BYTES = 2 ** 20


def _kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6,
                   *, block_r: int = 256, interpret: bool = False):
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    r = x2.shape[0]
    block_r = min(block_r, max(8, _TILE_BYTES // (4 * d) // 8 * 8), r)
    pad = -r % block_r
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(x2.shape[0] // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_r, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, w)
    return out[:r].reshape(shape)
