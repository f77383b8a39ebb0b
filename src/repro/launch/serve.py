"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

:func:`build_served` builds what ``RagdollEngine`` serves, at the
configuration's published widths:

* seeded bf16 weights; the embed / unembed / final norm and the first
  ``resident_layers`` layers live in device memory, every later layer in
  pinned host memory, streamed through the device on every step by the
  ``StreamedExecutor``;
* a ``ContinuousGenerator`` with paged bf16 KV, sized by
  :func:`plan_memory` from the device's memory limit and the compiled
  join and decode programs' own ``memory_analysis()``;
* an IVF knowledge base of ``synthetic.blob_corpus`` vectors built through
  ``ArrayEmbedder``, half its partitions spilled to disk, with queries as
  ``synthetic.perturb_queries`` rows appended to the embedder's matrix;
* the placement optimizer, priced with the ``HardwareProfile`` of the
  device's ``device_kind``.

``main`` replays a Poisson workload against it and prints the latency
table; ``chip_smoke.py`` drives the same builder.
"""
from __future__ import annotations

import argparse
import random
import tempfile
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core.costmodel import (CostModel, HardwareProfile, ModelProfile,
                                  profile_for_device)
from repro.core.placement import PlacementOptimizer
from repro.core.prefetch import (init_layered_params, layer_param_shapes,
                                 layer_program, prefill_programs,
                                 top_param_shapes, tree_bytes)
from repro.core.scheduler import BacklogScheduler
from repro.launch.compile_cache import enable_compile_cache
from repro.models import layers as L
from repro.models import transformer
from repro.models.model import _layer_cache_spec
from repro.retrieval import synthetic
from repro.retrieval.vectorstore import VectorStore
from repro.serving.engine import RagdollEngine
from repro.serving.generator import ContinuousGenerator, GeneratorConfig
from repro.serving.request import Request, latency_table

DTYPE = jnp.bfloat16      # weights and KV pages
# streamed layers the prefetch queue holds ahead of the one computing
QUEUE_DEPTH = 2


@dataclass(frozen=True)
class MemoryPlan:
    """Device-memory split of one served deployment (bytes)."""
    usable_bytes: int       # device limit x the profile's headroom
    top_bytes: int          # embed + unembed + final norm
    layer_bytes: int        # one transformer layer
    resident_layers: int
    streamed_layers: int
    kv_pages: int           # usable pool pages (+1 trash page row)
    kv_pool_bytes: int      # the pool arrays, every layer
    copy_headroom_bytes: int  # a second pool: un-donated whole-pool copies
    workspace_bytes: int    # compiled temps of a join or a decode step
    retrieval_bytes: int    # the whole corpus, the hot tier's ceiling
    free_bytes: int         # device bytes the prefetch queue may fill


def _sds(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compiled_bytes(jitted, *args) -> int:
    """Temp + output bytes of ``jitted`` compiled for the given shapes."""
    ma = jitted.lower(*args).compile().memory_analysis()
    return int(ma.temp_size_in_bytes + ma.output_size_in_bytes)


def plan_memory(cfg: ModelConfig, *, device, limit_bytes: int,
                headroom: float, num_slots: int, ctx_len: int,
                max_new_tokens: int, page_size: int,
                retrieval_bytes: int) -> MemoryPlan:
    """Split device memory between resident layers, the KV pool and the
    workspace.

    The pool holds every page the slot tables can address
    (``num_slots * ceil(total / page_size)``, what a retarget can grow
    it to), and as much again is left free: resizes and every decode
    step write a whole new pool before the old one is released.  The
    workspace is the larger of a join's and a decode step's, read from
    the compiled programs: a join's per-layer prefill program
    (``prefetch.prefill_programs``) with the other layers' row caches,
    which it holds until its page write, and the per-layer decode
    program plus the unembed.  The streamed layers' in-flight copies
    (``QUEUE_DEPTH`` queued + one computing) are reserved, and resident
    layers fill what remains.
    """
    dtype = DTYPE
    on_dev = SingleDeviceSharding(device)
    itemsize = jnp.dtype(dtype).itemsize
    total = ctx_len + max_new_tokens
    nmax = -(-total // page_size)
    pages = num_slots * nmax
    page_bytes = page_size * cfg.kv_cache_bytes_per_token(itemsize)
    pool_bytes = (pages + 1) * page_bytes
    top_bytes = tree_bytes(top_param_shapes(cfg, dtype))
    layer_bytes = tree_bytes(layer_param_shapes(cfg, dtype))

    kind = cfg.layer_pattern[0]
    lp = _sds(layer_param_shapes(cfg, dtype), on_dev)
    x_pre = jax.ShapeDtypeStruct((1, ctx_len, cfg.d_model), dtype,
                                 sharding=on_dev)
    pool = _sds(_layer_cache_spec(cfg, kind[0], pages + 1, page_size,
                                  dtype, None), on_dev)
    x_dec = jax.ShapeDtypeStruct((num_slots, 1, cfg.d_model), dtype,
                                 sharding=on_dev)
    pos = jax.ShapeDtypeStruct((num_slots,), jnp.int32, sharding=on_dev)
    tab = jax.ShapeDtypeStruct((num_slots, nmax), jnp.int32,
                               sharding=on_dev)
    prefill = _compiled_bytes(prefill_programs(cfg, kind, dtype)[1],
                              lp, x_pre)
    decode = _compiled_bytes(layer_program(cfg, kind, "decode", total),
                             lp, x_dec, pool, pos, tab)
    top = _sds(top_param_shapes(cfg, dtype), on_dev)
    head = _compiled_bytes(jax.jit(
        lambda p, x: transformer.unembed(
            p, cfg, L.rms_norm(x, p["final_norm"], cfg.norm_eps), None)),
        top, x_dec)
    # a join holds every other layer's row cache until its page write
    rows = (cfg.num_layers - 1) * tree_bytes(
        _layer_cache_spec(cfg, kind[0], 1, ctx_len, dtype, None))
    workspace = max(prefill + rows, decode + head)

    usable = int(limit_bytes * headroom)
    fixed = top_bytes + 2 * pool_bytes + workspace + retrieval_bytes
    room = usable - fixed
    if room < (QUEUE_DEPTH + 1) * layer_bytes:
        raise ValueError(
            f"{cfg.name}: {fixed} fixed device bytes leave no room to "
            f"stream layers in the {usable} usable")
    if room >= cfg.num_layers * layer_bytes:
        resident = cfg.num_layers
    else:
        resident = (room - (QUEUE_DEPTH + 1) * layer_bytes) // layer_bytes
    free = room - resident * layer_bytes - layer_bytes
    return MemoryPlan(
        usable_bytes=usable, top_bytes=top_bytes, layer_bytes=layer_bytes,
        resident_layers=int(resident),
        streamed_layers=int(cfg.num_layers - resident),
        kv_pages=pages, kv_pool_bytes=pool_bytes,
        copy_headroom_bytes=pool_bytes, workspace_bytes=workspace,
        retrieval_bytes=retrieval_bytes, free_bytes=int(max(free, 0)))


@dataclass
class Served:
    engine: RagdollEngine
    generator: ContinuousGenerator
    store: VectorStore
    plan: MemoryPlan
    hw: HardwareProfile
    queries: List[str]       # embedder keys of the query rows
    corpus_size: int
    seconds: dict            # setup phase -> wall seconds


def build_served(cfg: ModelConfig, *, spill_root: str, seed: int = 0,
                 num_slots: int = 8, ctx_len: int = 1024,
                 max_new_tokens: int = 32, page_size: int = 16,
                 corpus_size: int = 131072, dim: int = 768,
                 partitions: int = 32, num_queries: int = 64,
                 hw: Optional[HardwareProfile] = None,
                 limit_bytes: Optional[int] = None,
                 log: Callable[[str], None] = print) -> Served:
    """Build the served RAG system for ``cfg`` (see the module doc).

    ``hw`` defaults to the profile of the device's ``device_kind`` (an
    unknown kind raises); ``limit_bytes`` to the device's reported
    ``bytes_limit``.  Half the partitions are spilled under
    ``spill_root``.
    """
    device = jax.devices()[0]
    hw = hw or profile_for_device(device.device_kind)
    if limit_bytes is None:
        limit_bytes = (device.memory_stats() or {}).get("bytes_limit")
        if limit_bytes is None:
            raise ValueError(f"{device.device_kind} reports no memory "
                             f"limit; pass limit_bytes")
    seconds = {}

    t0 = time.perf_counter()
    vecs = synthetic.blob_corpus(corpus_size, dim, partitions, seed=seed)
    qvecs = synthetic.perturb_queries(vecs, num_queries, seed=seed + 1)
    embedder = synthetic.ArrayEmbedder(np.concatenate([vecs, qvecs]))
    store = VectorStore.build([str(i) for i in range(corpus_size)],
                              embedder, num_partitions=partitions,
                              root=spill_root, seed=seed)
    for pid in range(partitions // 2, partitions):
        store.spill(pid)
    seconds["corpus"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    plan = plan_memory(
        cfg, device=device, limit_bytes=limit_bytes,
        headroom=hw.mem_headroom, num_slots=num_slots, ctx_len=ctx_len,
        max_new_tokens=max_new_tokens, page_size=page_size,
        retrieval_bytes=int(vecs.nbytes))
    seconds["plan"] = time.perf_counter() - t0
    log(f"plan: {plan}")

    t0 = time.perf_counter()
    params = init_layered_params(cfg, jax.random.PRNGKey(seed), DTYPE,
                                 plan.resident_layers, device)
    gen = ContinuousGenerator(
        cfg, params, GeneratorConfig(ctx_len=ctx_len,
                                     max_new_tokens=max_new_tokens,
                                     dtype=DTYPE),
        num_slots=num_slots, streamed=True, paged=True,
        page_size=page_size, page_budget=plan.kv_pages, kv_format="bf16",
        resident_layers=plan.resident_layers, free_bytes=plan.free_bytes)
    del params
    seconds["weights"] = time.perf_counter() - t0

    mp = ModelProfile.from_config(cfg, dtype_bytes=jnp.dtype(DTYPE).itemsize,
                                  kv_format="bf16")
    cost = CostModel(hw, mp, partition_bytes=store.partition_bytes(),
                     num_partitions=store.num_partitions, db_dim=dim,
                     chunks_per_partition=corpus_size / partitions)
    ex = gen.exec
    w_gpu = ex.resident_bytes / (ex.resident_bytes + ex.streamed_bytes)
    opt = PlacementOptimizer(cost, avg_ctx_len=ctx_len,
                             avg_out_len=max_new_tokens,
                             kv_page_size=page_size,
                             weight_split=(w_gpu, 1.0 - w_gpu))
    engine = RagdollEngine(
        store, embedder, gen, BacklogScheduler(max_batch=num_slots),
        BacklogScheduler(max_batch=num_slots), optimizer=opt,
        initial_partitions=partitions // 2)
    return Served(engine=engine, generator=gen, store=store, plan=plan,
                  hw=hw, queries=[str(corpus_size + i)
                                  for i in range(num_queries)],
                  corpus_size=corpus_size, seconds=seconds)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=120.0,
                    help="requests per minute")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    with tempfile.TemporaryDirectory() as root:
        served = build_served(cfg, spill_root=root, seed=args.seed,
                              num_slots=args.slots,
                              num_queries=args.requests)
        eng = served.engine
        eng.start()
        try:
            rng = random.Random(args.seed)
            for i, q in enumerate(served.queries[:args.requests]):
                time.sleep(rng.expovariate(args.rate / 60.0))
                eng.submit(Request(rid=i, query=q,
                                   arrival=time.perf_counter()))
            reqs = eng.drain(args.requests, timeout=3600)
        finally:
            eng.stop()

    tab = latency_table(reqs)
    print(f"\nmode=ragdoll arch={args.arch} "
          f"device={jax.devices()[0].device_kind}")
    for k, v in tab.items():
        print(f"  {k:16s} {v:10.3f}" if isinstance(v, float)
              else f"  {k:16s} {v}")


if __name__ == "__main__":
    main()
