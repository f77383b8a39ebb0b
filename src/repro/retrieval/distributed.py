"""Sharded IVF retrieval across the mesh data axis.

The shard/probe/merge contract
------------------------------

``ShardedIVFStore`` partitions a k-means-clustered :class:`VectorStore`
across ``num_shards`` retrieval shards (the mesh data axis in the
multi-host deployment).  The contract, stage by stage:

* **Shard** — each shard owns a *disjoint* subset of the IVF partitions,
  assigned centroid-aware (k-means over the partition centroids, then a
  balanced greedy fill), not round-robin: clusters that are close in
  embedding space land on the same shard, so a query's probe set
  concentrates on few shards and each shard's resident set stays
  coherent.  Every shard is non-empty and the union covers all
  partitions exactly once.
* **Probe** — the IVF probe runs once, globally, against the replicated
  centroids (``VectorStore.probe``), producing the same per-query
  ``(Q, P)`` mask the single-host sweep uses.  Each shard then sweeps
  only *its own* probed partitions with its own
  :class:`~repro.retrieval.streamer.PartitionStreamer` — a per-shard
  disk tier with a per-shard residency budget (``set_budget`` splits the
  placement's host headroom across shards).
* **Merge** — each shard fuses its local scoreboards with
  ``ops.retrieval_topk_merge`` into a local ``(Q, k)`` board; a single
  cross-shard ``(Q, k)`` all-gather + merge (``sharded_topk_merge`` on a
  real mesh, the same merge kernel locally) produces the global top-k.
  The all-gather payload is ``S * Q * k`` (score, id) pairs — tiny next
  to the generation collectives (quantified in benchmarks/roofline).

Correctness: the sweep calls the identical per-partition kernels the
single-host path calls, and both merge stages only *select* — so
``ShardedIVFStore.search`` is bit-identical to single-host
``VectorStore.search`` at equal ``nprobe`` for every shard count
(test-enforced for S in {1, 2, 4}; the only caveat is exact score ties
between distinct chunks, where the two merge orders may rank the tied
ids differently).  Under-filled rows carry the ``(NEG_INF, -1)``
sentinel on every path.

``distributed_topk`` remains the exact (non-IVF) kernel path: raw rows
sharded over the data axis.  Uneven corpora are handled by padding the
row shard with sentinel rows that score NEG_INF via a validity column
(a padded row must never evict a real candidate from a shard-local
top-k, even when every real score is negative).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.obs.metrics import NULL_REGISTRY
from repro.obs.trace import NULL_TRACER
from repro.retrieval.cache import HotPartitionSet
from repro.retrieval.streamer import PartitionStreamer
from repro.retrieval.vectorstore import SearchStats, VectorStore
from repro.sharding.specs import MeshContext

NEG_INF = -1e30


# ===========================================================================
# Exact row-sharded search (kernel path)
# ===========================================================================

def pad_for_row_shards(
    queries: jnp.ndarray,    # (Q, D)
    database: jnp.ndarray,   # (N, D)
    shards: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Pad the database to a multiple of ``shards`` rows so uneven corpora
    row-shard cleanly, with padded rows *guaranteed* to lose.

    Zero-padding alone is wrong: a padded row scores ``q @ 0 = 0``, which
    beats every real candidate with a negative score inside its shard's
    local top-k.  Instead both operands gain a validity column — 1.0 per
    query, ``NEG_INF`` per padded row (0 per real row) — so a padded
    row's score is ~NEG_INF while real rows' scores gain exactly 0.0 and
    keep their bits.  Returns ``(q_aug, db_aug, local_n)``.
    """
    n = database.shape[0]
    local_n = -(-n // shards)                     # ceil: uneven corpora ok
    pad = shards * local_n - n
    if pad:
        database = jnp.pad(database, ((0, pad), (0, 0)))
    flag = (jnp.arange(shards * local_n) >= n).astype(database.dtype)
    db_aug = jnp.concatenate([database, flag[:, None] * NEG_INF], axis=1)
    q_aug = jnp.concatenate(
        [queries, jnp.ones((queries.shape[0], 1), queries.dtype)], axis=1)
    return q_aug, db_aug, local_n


def distributed_topk(
    queries: jnp.ndarray,    # (Q, D) replicated
    database: jnp.ndarray,   # (N, D) sharded over data axis (rows)
    k: int,
    ctx: MeshContext,
    impl: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact sharded search. Returns (scores (Q,k), global row indices
    (Q,k)); rows beyond the corpus (k > N) come back as ``(NEG_INF, -1)``
    sentinels, never as a padded row's index."""
    axes = ctx.batch_axes
    n = database.shape[0]
    shards = ctx.dp_size
    q_aug, db_aug, local_n = pad_for_row_shards(queries, database, shards)

    def fn(q, db):
        s, i = ops.retrieval_topk(q, db, k, impl=impl)
        shard_id = jax.lax.axis_index(axes)
        gi = i + shard_id * local_n
        # normalize sentinels exactly: pad rows (gi >= n) AND the local
        # kernel's own -1 tail (k > local rows) — the latter would
        # otherwise alias to a real-looking global id on shards > 0
        valid = (i >= 0) & (gi < n)
        s = jnp.where(valid, s, NEG_INF)
        gi = jnp.where(valid, gi, -1)
        # gather all shards' candidates and merge
        s_all = jax.lax.all_gather(s, axes, axis=0)      # (S, Q, k)
        i_all = jax.lax.all_gather(gi, axes, axis=0)
        s_cat = jnp.moveaxis(s_all, 0, 1).reshape(q.shape[0], -1)
        i_cat = jnp.moveaxis(i_all, 0, 1).reshape(q.shape[0], -1)
        top_s, pos = jax.lax.top_k(s_cat, k)
        top_i = jnp.take_along_axis(i_cat, pos, axis=1)
        return top_s, top_i

    return jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(P(None, None), P(axes, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False)(q_aug, db_aug)


# ===========================================================================
# Centroid-aware partition -> shard assignment
# ===========================================================================

def assign_partitions(centroids: Optional[np.ndarray], num_shards: int,
                      num_partitions: Optional[int] = None,
                      seed: int = 0) -> List[List[int]]:
    """Assign IVF partitions to shards: disjoint, covering, non-empty,
    balanced to within one partition, and centroid-aware.

    Shard anchors come from k-means over the partition centroids; each
    partition then greedily joins its highest-affinity anchor that still
    has capacity (``ceil(P / S)``), most-decisive partitions first, so
    nearby clusters co-locate.  A final pass steals one partition from
    the fullest shard for any shard left empty.  Falls back to a
    contiguous split when the store has no centroids (hashed stores
    always do; only hand-built stores hit this).
    """
    if centroids is None:
        p_total = int(num_partitions or 0)
        num_shards = max(1, min(num_shards, p_total))
        bounds = np.linspace(0, p_total, num_shards + 1).astype(int)
        return [list(range(bounds[s], bounds[s + 1]))
                for s in range(num_shards)]
    from repro.retrieval.vectorstore import kmeans_centroids
    p_total = centroids.shape[0]
    num_shards = max(1, min(num_shards, p_total))
    if num_shards == 1:
        return [list(range(p_total))]
    anchors, _ = kmeans_centroids(centroids, num_shards, iters=8, seed=seed)
    affinity = centroids.astype(np.float32) @ anchors.T       # (P, S)
    cap = -(-p_total // num_shards)
    # place the partitions with the largest best-vs-runner-up margin
    # first: they have the most to lose from spilling to a second choice
    ranked = np.sort(affinity, axis=1)
    margin = ranked[:, -1] - ranked[:, -2]
    shards: List[List[int]] = [[] for _ in range(num_shards)]
    for pid in np.argsort(-margin, kind="stable"):
        for sid in np.argsort(-affinity[pid], kind="stable"):
            if len(shards[sid]) < cap:
                shards[sid].append(int(pid))
                break
    for sid, members in enumerate(shards):    # non-empty guarantee
        if members:
            continue
        donor = max(range(num_shards), key=lambda s: len(shards[s]))
        steal = min(shards[donor], key=lambda p: affinity[p, donor])
        shards[donor].remove(steal)
        members.append(steal)
    return [sorted(s) for s in shards]


# ===========================================================================
# Cross-shard scoreboard fusion
# ===========================================================================

def sharded_topk_merge(
    shard_scores: jnp.ndarray,   # (Q, S, k) per-shard local top-k boards
    shard_ids: jnp.ndarray,      # (Q, S, k) matching global chunk ids
    k: int,
    ctx: MeshContext,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fuse per-shard (Q, k) boards on a real mesh: each device holds its
    shard's board, one (Q, k)-payload all-gather over the data axis +
    a top-k produces the replicated global board.  Flattening is shard-
    major, identical to the local ``retrieval_topk_merge`` fallback."""
    axes = ctx.batch_axes
    s_in = jnp.moveaxis(shard_scores.astype(jnp.float32), 1, 0)  # (S, Q, k)
    i_in = jnp.moveaxis(shard_ids.astype(jnp.int32), 1, 0)

    def fn(s, i):                       # local (S/dp, Q, k)
        s_all = jax.lax.all_gather(s, axes, axis=0, tiled=True)  # (S, Q, k)
        i_all = jax.lax.all_gather(i, axes, axis=0, tiled=True)
        q = s_all.shape[1]
        s_cat = jnp.moveaxis(s_all, 0, 1).reshape(q, -1)         # (Q, S*k)
        i_cat = jnp.moveaxis(i_all, 0, 1).reshape(q, -1)
        top_s, pos = jax.lax.top_k(s_cat, k)
        return top_s, jnp.take_along_axis(i_cat, pos, axis=1)

    return jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(P(axes, None, None), P(axes, None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False)(s_in, i_in)


class IVFShard:
    """One retrieval shard: a disjoint set of IVF partitions plus its own
    partition streamer (per-shard disk tier + residency budget) and its
    own device-hot tier (per-shard byte grant from the market)."""

    def __init__(self, sid: int, pids: Sequence[int],
                 streamer: PartitionStreamer,
                 hot: Optional[HotPartitionSet] = None):
        self.sid = sid
        self.pids = list(pids)
        self.pid_set = frozenset(pids)
        self.streamer = streamer
        self.hot = hot

    def __repr__(self) -> str:
        return f"IVFShard({self.sid}, pids={self.pids})"


class ShardedIVFStore:
    """IVF-pruned search over a ``VectorStore`` sharded across the mesh.

    See the module docstring for the shard/probe/merge contract.  The
    in-process implementation sweeps the shards serially for determinism
    (the cost model prices the parallel multi-host deployment, including
    the per-shard load bandwidth and the cross-shard all-gather); on a
    real mesh (``ctx`` with ``dp_size == num_shards``) the final fuse
    runs as a shard_map all-gather + merge.
    """

    def __init__(self, store: VectorStore, num_shards: int,
                 policy=None, free_bytes: float = float("inf"),
                 ctx: Optional[MeshContext] = None,
                 use_streamers: bool = True, seed: int = 0,
                 tracer=None, registry=None):
        self.store = store
        self.ctx = ctx
        self.tracer = tracer or NULL_TRACER
        self.registry = registry or NULL_REGISTRY
        self.assignment = assign_partitions(
            store.centroids, num_shards,
            num_partitions=store.num_partitions, seed=seed)
        self.num_shards = len(self.assignment)
        self.shards = [
            IVFShard(sid, pids,
                     PartitionStreamer(store, policy,
                                       free_bytes=free_bytes,
                                       tracer=self.tracer)
                     if use_streamers else None,
                     # inert (budget 0) until the market grants bytes;
                     # eligibility scoped to the shard's own partitions
                     hot=HotPartitionSet(store, eligible=pids,
                                         tracer=self.tracer,
                                         registry=self.registry))
            for sid, pids in enumerate(self.assignment)]

    # ------------------------------------------------------------- budget
    def set_budget(self, host_free_bytes: float) -> None:
        """Split the placement's host headroom evenly across the shards'
        streamers (each shard owns its residency budget)."""
        self.set_budgets([host_free_bytes / self.num_shards]
                         * self.num_shards)

    def set_budgets(self, per_shard_bytes: Sequence[float]) -> None:
        assert len(per_shard_bytes) == self.num_shards
        for shard, budget in zip(self.shards, per_shard_bytes):
            if shard.streamer is not None:
                shard.streamer.set_budget(max(float(budget), 0.0))

    def set_hot_budgets(self, per_shard_bytes: Sequence[float],
                        ranking: Sequence[int]) -> None:
        """Retarget every shard's device-hot tier from the market's byte
        grant (``PlacementOptimizer.shard_hot_budgets``) and the global
        heat ranking; each shard's eligibility filter keeps it to its
        own disjoint partitions."""
        assert len(per_shard_bytes) == self.num_shards
        for shard, budget in zip(self.shards, per_shard_bytes):
            if shard.hot is not None:
                shard.hot.retarget(int(budget), ranking)

    def hot_partitions(self) -> List[int]:
        return sorted(pid for shard in self.shards
                      if shard.hot is not None for pid in shard.hot.pids())

    def hot_device_bytes(self) -> int:
        return sum(shard.hot.device_bytes() for shard in self.shards
                   if shard.hot is not None)

    def close(self) -> None:
        for shard in self.shards:
            if shard.streamer is not None:
                shard.streamer.close()

    # ------------------------------------------------------------- search
    def search(self, queries: np.ndarray, top_k: int,
               impl: Optional[str] = None,
               nprobe: Optional[int] = None,
               stats: Optional[SearchStats] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Global top-k: one global probe, shard-local probe-masked
        sweeps, per-shard scoreboard fuse, cross-shard merge.  Returns
        (scores (Q, k), global chunk ids (Q, k)) — bit-identical to
        ``VectorStore.search`` at equal ``nprobe`` (modulo exact score
        ties between distinct chunks)."""
        store = self.store
        nq = queries.shape[0]
        if nprobe is not None:
            pids, qmask = store.probe(queries, nprobe)
        else:
            pids = list(store.partitions)
            qmask = np.zeros((nq, store.num_partitions), bool)
            qmask[:, pids] = True
        if stats:
            stats.add(partitions_pruned=store.num_partitions - len(pids))

        local_s: List[np.ndarray] = []
        local_i: List[np.ndarray] = []
        # each shard sweeps into a full-width (Q, P, k) board even though
        # it owns ~P/S partitions: the fixed shape keeps ONE compiled
        # merge kernel across every shard and probe set (same trade the
        # single-host sweep makes), at the cost of an S-fold transient
        # board allocation — negligible next to the partition data
        for shard in self.shards:
            # preserve the global probe order (most-probed-first,
            # residents ahead) within the shard's own partitions
            own = [pid for pid in pids if pid in shard.pid_set]
            # each shard sweeps into its own stats object, folded into
            # the caller's through the locked merge() — totals are
            # conserved exactly and a future parallel shard sweep cannot
            # drift the shared counters with unlocked +=
            shard_stats = SearchStats() if stats else None
            with self.tracer.span("shard.sweep", sid=shard.sid,
                                  partitions=len(own)):
                board_s, board_i, searched = store.sweep_boards(
                    queries, own, top_k, impl=impl,
                    streamer=shard.streamer, stats=shard_stats,
                    hot=shard.hot, qmask=qmask)
            if stats:
                stats.merge(shard_stats)
            s, i = ops.retrieval_topk_merge(
                board_s, board_i, qmask & searched[None, :], top_k,
                impl=impl)
            local_s.append(np.asarray(s))
            local_i.append(np.asarray(i))

        fused_s = np.stack(local_s, axis=1)          # (Q, S, k)
        fused_i = np.stack(local_i, axis=1)
        if self.ctx is not None and self.ctx.dp_size == self.num_shards:
            scores, gids = sharded_topk_merge(
                jnp.asarray(fused_s), jnp.asarray(fused_i), top_k,
                self.ctx)
        else:
            scores, gids = ops.retrieval_topk_merge(
                fused_s, fused_i, np.ones((nq, self.num_shards), bool),
                top_k, impl=impl)
        return np.asarray(scores), np.asarray(gids)

    def get_chunks(self, ids: np.ndarray) -> List[List[str]]:
        return self.store.get_chunks(ids)
