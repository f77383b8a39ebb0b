"""Retrieval top-k Pallas TPU kernels: fused query x database matmul + merge.

This is the retrieval hot spot of RAGDoll adapted to TPU: exact
inner-product search within a resident database partition. Instead of
materializing the full (Q, N) score matrix in HBM (what the naive reference
does), the kernel:
  * tiles the database rows (``block_n``) through VMEM and feeds the MXU
    with (block_q x D) @ (D x block_n) tiles;
  * keeps a running top-k scoreboard in VMEM scratch, one 128-lane row
    per query (lanes ``[0, k)`` hold the current top k);
  * emits global indices so partition-local results merge trivially across
    shards (see retrieval.distributed).

Selection is ``k`` rounds of max-and-mask, which Mosaic lowers (it has no
lowering for ``lax.top_k`` or ``take_along_axis``): each round takes the
row maximum over the scoreboard and the new tile, breaks score ties toward
the lowest id (``lax.top_k``'s order over a full score row), writes the
winner into lane ``r`` and masks it out.  ``k`` is therefore capped at
128, one vreg row of lanes.

Grid: (q_blocks, n_blocks), n innermost ("arbitrary").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128            # scoreboard width: k <= LANES
_ID_MAX = 2 ** 31 - 1
# VMEM for one database tile; the pipeline double-buffers it, and the
# scoped VMEM limit on v5e is 16 MiB
_DB_TILE_BYTES = 4 * 2 ** 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _topk_rounds(k: int, parts, rows: int):
    """Top ``k`` of the union of ``parts`` by max-and-mask.

    ``parts`` is a list of ``(scores f32 (rows, w), ids int32 (rows, w))``.
    Returns ``(rows, LANES)`` score/id boards: lanes ``[0, k)`` hold the
    winners in descending score order (ties to the lower id), the other
    lanes ``-inf``/``-1`` so they never win a later merge.  A round that
    finds every candidate spent writes ``(NEG_INF, -1)``, the fill the
    reference oracles give when fewer than ``k`` real candidates exist.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)

    def body(r, carry):
        out_s, out_i, scores = carry
        m = functools.reduce(jnp.maximum, [
            jnp.max(s, axis=1, keepdims=True) for s in scores])
        c = functools.reduce(jnp.minimum, [
            jnp.min(jnp.where(s == m, i, _ID_MAX), axis=1, keepdims=True)
            for s, (_, i) in zip(scores, parts)])
        live = m > -jnp.inf
        out_s = jnp.where(lane == r, jnp.where(live, m, NEG_INF), out_s)
        out_i = jnp.where(lane == r, jnp.where(live, c, -1), out_i)
        scores = [jnp.where((s == m) & (i == c), -jnp.inf, s)
                  for s, (_, i) in zip(scores, parts)]
        return out_s, out_i, scores

    out_s = jnp.full((rows, LANES), -jnp.inf, jnp.float32)
    out_i = jnp.full((rows, LANES), -1, jnp.int32)
    out_s, out_i, _ = jax.lax.fori_loop(
        0, k, body, (out_s, out_i, [s for s, _ in parts]))
    return out_s, out_i


def _kernel(q_ref, db_ref, os_ref, oi_ref, s_scr, i_scr, *,
            k: int, block_n: int, n_total: int, nn: int):
    jn = pl.program_id(1)

    @pl.when(jn == 0)
    def _init():
        s_scr[...] = jnp.full_like(s_scr, -jnp.inf)
        i_scr[...] = jnp.full_like(i_scr, -1)

    q = q_ref[...].astype(jnp.float32)            # (bq, D)
    db = db_ref[...].astype(jnp.float32)          # (bn, D)
    s = jax.lax.dot_general(q, db, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (bq, bn)
    idx = jn * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(idx < n_total, s, NEG_INF)
    new_s, new_i = _topk_rounds(
        k, [(s_scr[...], i_scr[...]), (s, idx)], s.shape[0])
    s_scr[...] = new_s
    i_scr[...] = new_i

    @pl.when(jn == nn - 1)
    def _finalize():
        os_ref[...] = s_scr[...]
        oi_ref[...] = i_scr[...]


def topk_pallas(
    queries: jnp.ndarray,   # (Q, D)
    database: jnp.ndarray,  # (N, D)
    k: int,
    *,
    block_q: int = 128,
    block_n: int = 1024,
    interpret: bool = False,
):
    if not 0 < k <= LANES:
        raise ValueError(f"topk_pallas needs 0 < k <= {LANES}, got {k}")
    qn, d = queries.shape
    n = database.shape[0]
    # sublane-aligned tiles: rows in multiples of 8, full-width D, and a
    # database tile no larger than _DB_TILE_BYTES
    fit = max(8, _DB_TILE_BYTES // (d * database.dtype.itemsize) // 8 * 8)
    block_q = min(block_q, _round_up(qn, 8))
    block_n = min(block_n, fit, _round_up(n, 8))
    qpad = -qn % block_q
    npad = -n % block_n
    if qpad:
        queries = jnp.pad(queries, ((0, qpad), (0, 0)))
    if npad:
        database = jnp.pad(database, ((0, npad), (0, 0)))
    nq = queries.shape[0] // block_q
    nn = database.shape[0] // block_n

    kernel = functools.partial(_kernel, k=k, block_n=block_n,
                               n_total=n, nn=nn)
    scores, idx = pl.pallas_call(
        kernel,
        grid=(nq, nn),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((queries.shape[0], LANES), jnp.float32),
            jax.ShapeDtypeStruct((queries.shape[0], LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.int32),
        ],
        interpret=interpret,
        name="topk_sweep",
    )(queries, database)
    return scores[:qn, :k], idx[:qn, :k]


# ===========================================================================
# Masked multi-partition merge
# ===========================================================================
#
# After per-partition search, each partition holds a (Q, k) scoreboard of
# candidate scores + global chunk ids.  Fusing them on the host costs a
# device->host round trip per retrieval batch; this kernel keeps the whole
# merge on-device.  The (Q, P, k) boards are flattened to (Q, P*k) rows,
# lane-padded to a multiple of 128, and each grid step selects the top k of
# one block of query rows with the same max-and-mask rounds as
# ``topk_pallas``.  The mask is per (query, partition) — batched IVF probes
# each query's own ``nprobe`` clusters — and arrives expanded to one int32
# per board entry, so pruning masks entries to (NEG_INF, -1) instead of
# changing the input shape, and one compiled kernel serves every probe set.

def _merge_kernel(mask_ref, s_ref, i_ref, os_ref, oi_ref, *, k: int):
    active = mask_ref[...] != 0                                     # (bq, W)
    s = jnp.where(active, s_ref[...].astype(jnp.float32), NEG_INF)
    # masked-out entries also surrender their ids: a pruned partition's
    # chunk id must never surface, even when < k valid candidates exist
    i = jnp.where(active, i_ref[...], -1)
    os_ref[...], oi_ref[...] = _topk_rounds(k, [(s, i)], s.shape[0])


def topk_merge_pallas(
    part_scores: jnp.ndarray,   # (Q, P, k)
    part_ids: jnp.ndarray,      # (Q, P, k) global chunk ids
    mask: jnp.ndarray,          # (Q, P) bool/int — pruned entries are 0
    k: int,
    *,
    block_q: int = 8,
    interpret: bool = False,
):
    qn, num_parts, kk = part_scores.shape
    assert part_ids.shape == part_scores.shape
    assert mask.shape == (qn, num_parts), (mask.shape, qn, num_parts)
    assert kk == k, (kk, k)
    if not 0 < k <= LANES:
        raise ValueError(f"topk_merge_pallas needs 0 < k <= {LANES}, "
                         f"got {k}")
    width = num_parts * k
    wpad = -width % LANES
    qpad = -qn % block_q
    flat_s = part_scores.reshape(qn, width).astype(jnp.float32)
    flat_i = part_ids.reshape(qn, width).astype(jnp.int32)
    flat_m = jnp.repeat(mask.astype(jnp.int32), k, axis=1)
    # padding rows/lanes are masked out: they read as (NEG_INF, -1)
    flat_s = jnp.pad(flat_s, ((0, qpad), (0, wpad)),
                     constant_values=NEG_INF)
    flat_i = jnp.pad(flat_i, ((0, qpad), (0, wpad)), constant_values=-1)
    flat_m = jnp.pad(flat_m, ((0, qpad), (0, wpad)))
    rows, w = flat_s.shape

    row_block = pl.BlockSpec((block_q, w), lambda i: (i, 0))
    out_block = pl.BlockSpec((block_q, LANES), lambda i: (i, 0))
    scores, idx = pl.pallas_call(
        functools.partial(_merge_kernel, k=k),
        grid=(rows // block_q,),
        in_specs=[row_block, row_block, row_block],
        out_specs=[out_block, out_block],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        ],
        interpret=interpret,
        name="topk_merge",
    )(flat_m, flat_s, flat_i)
    return scores[:qn, :k], idx[:qn, :k]
