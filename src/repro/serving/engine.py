"""RAGDoll serving engines (real, thread-driven).

``RagdollEngine`` is the full system: decoupled retrieval/generation
pipelines, backlog-aware batch schedulers per stage, partition cache
driven by the joint placement policy, and policy-trace recording (Fig. 9).

The generation stage has two disciplines, chosen by the generator type:

* a whole-batch :class:`~repro.serving.generator.Generator` runs behind a
  classic ``PipelineWorker`` (pop batch, generate, forward);
* a :class:`~repro.serving.generator.ContinuousGenerator` runs behind a
  ``StepPumpWorker`` — requests are admitted into free KV slots at any
  decode step and leave the moment they finish, and the placement
  optimizer's batch policy is consulted every ``policy_every`` decode
  steps (mid-generation, the paper's Fig. 9 behaviour) instead of only at
  whole-batch boundaries.  The policy boundary also retargets the
  partition cache, the IVF probe width, the partition streamer's
  host-memory budget, and — for paged generators — both tiers of the KV
  page placement (device pool from ``kv_page_budget``, host swap pool
  from ``kv_host_page_budget``) from the live placement.  Admission,
  preemption and resume are owned by a
  :class:`~repro.serving.reqsched.RequestScheduler`: when a join would
  backpressure on pages (or slots) while a lower-priority slot is live,
  the pump preempts the victim (swap-to-host, vLLM-style) instead of
  stalling, and swaps parked requests back in once the join backlog
  clears.  ``Request.priority`` classes order admission, victim
  selection and resume (with aging so batch work cannot starve);
  ``partial_swap=True`` sheds only the pages a blocked join needs; a
  generator built with ``overlap_swap=True`` runs the swap DMA async,
  fenced by the scheduler at every policy boundary.

With ``retrieval_shards > 1`` the retrieval stage runs through a
:class:`~repro.retrieval.distributed.ShardedIVFStore`: the IVF
partitions split centroid-aware across shards, each shard sweeps with
its own partition streamer, and the policy boundary splits the
placement's host headroom across the per-shard residency budgets.

``SerialRAGEngine`` is the baseline shape (vLLMRAG/AccRAG-style): one
worker retrieves then generates per batch, in arrival order.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from repro.core.pipeline import (Pipeline, PipelineWorker, StageQueue,
                                 StepPumpWorker, build_pipeline)
from repro.core.placement import Placement, PlacementOptimizer
from repro.core.prefetch import PrefetchPolicy
from repro.core.scheduler import BacklogScheduler
from repro.obs.metrics import MetricsRegistry, NULL_REGISTRY
from repro.obs.trace import NULL_TRACER
from repro.retrieval.cache import HotPartitionSet, PartitionCache
from repro.retrieval.embedding import HashEmbedder
from repro.retrieval.streamer import PartitionStreamer
from repro.retrieval.vectorstore import SearchStats, VectorStore
from repro.serving.generator import ContinuousGenerator, Generator
from repro.serving.reqsched import RequestScheduler
from repro.serving.request import Request


@dataclass
class PolicyEvent:
    t: float
    gen_batch: int
    resident_partitions: int
    c_gpu: float
    w_gpu: float
    nprobe: Optional[int] = None
    gen_slots: Optional[int] = None    # live slot-table capacity
    kv_pages: Optional[int] = None     # paged pool budget (paged only)
    kv_host_pages: Optional[int] = None  # host swap-pool budget (c_cpu)
    parked: Optional[int] = None       # requests swapped out right now
    prefix_pages: Optional[int] = None   # prefix-cache device-page cap
    prefix_hit_tokens: Optional[int] = None  # cumulative cached tokens
    hot_partitions: Optional[int] = None  # device-hot IVF partitions
    hot_bytes: Optional[int] = None       # device bytes they occupy
    hot_hit_rate: Optional[float] = None  # observed hot-answered probe frac


class RagdollEngine:
    def __init__(self, store: VectorStore, embedder: HashEmbedder,
                 generator: Generator,
                 ret_scheduler: BacklogScheduler,
                 gen_scheduler: BacklogScheduler,
                 optimizer: Optional[PlacementOptimizer] = None,
                 initial_partitions: Optional[int] = None,
                 streamer: Optional[PartitionStreamer] = None,
                 policy_every: int = 8,
                 retrieval_shards: int = 1,
                 aging_s: float = 30.0,
                 partial_swap: bool = False,
                 tracer=None, registry=None):
        self.store = store
        self.embedder = embedder
        self.generator = generator
        self.continuous = isinstance(generator, ContinuousGenerator)
        self.policy_every = policy_every
        self.opt = optimizer
        self.tracer = tracer or NULL_TRACER
        # the engine's registry defaults to a REAL per-engine registry
        # (not the global no-op): policy-boundary decisions journal
        # through it, and ``policy_trace`` reads them back
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        if self.opt is not None:
            # hand the engine's obs plumbing down unless the caller
            # wired the optimizer to its own
            if self.opt.tracer is NULL_TRACER:
                self.opt.tracer = self.tracer
            if self.opt.registry is NULL_REGISTRY:
                self.opt.registry = self.registry
        if hasattr(generator, "bind_obs"):
            generator.bind_obs(self.tracer, self.registry)
        p0 = (initial_partitions if initial_partitions is not None
              else len(store.partitions))
        self.pcache = PartitionCache(store, target=p0)
        self._owns_streamer = streamer is None
        self.streamer = streamer if streamer is not None else \
            PartitionStreamer(store, PrefetchPolicy(max_depth=2),
                              tracer=self.tracer)
        if not self._owns_streamer and self.streamer.tracer is NULL_TRACER:
            self.streamer.tracer = self.tracer
        # sharded IVF retrieval: partition the store across S shards,
        # each with its own streamer/disk tier; the policy boundary
        # splits the host headroom across them (the single streamer
        # above stays for the S=1 path and injected-streamer callers)
        self.sharded: Optional["ShardedIVFStore"] = None
        if retrieval_shards > 1:
            from repro.retrieval.distributed import ShardedIVFStore
            self.sharded = ShardedIVFStore(store, retrieval_shards,
                                           tracer=self.tracer,
                                           registry=self.registry)
        # device-hot partition tier for the S=1 path (each shard of a
        # sharded store owns its own).  Inert (budget 0) until the
        # device-byte market grants it bytes at a policy boundary.
        self.hot = HotPartitionSet(store, tracer=self.tracer,
                                   registry=self.registry)
        self.nprobe: Optional[int] = None   # set by the placement policy
        self.retrieval_stats = SearchStats()   # cumulative, for reporting
        self.completed: List[Request] = []
        self._done_lock = threading.Lock()
        # completion wakeup: ``drain`` waits on this instead of polling
        self._done_cv = threading.Condition(self._done_lock)
        # open async "request" spans (submit -> harvest), keyed by rid
        self._req_spans: Dict[int, object] = {}
        if self.continuous:
            rq, cq, dq = (StageQueue("retrieval"), StageQueue("context"),
                          StageQueue("done"))
            rw = PipelineWorker("retrieval", rq, cq, self._retrieve_batch,
                                ret_scheduler,
                                on_batch_boundary=self._ret_boundary,
                                on_error=self._worker_failed)
            # the request scheduler owns admission / preemption / resume
            # (priority classes, partial-slot swap, swap/decode overlap
            # fencing); the pump wires its capacity + admit hooks
            self.scheduler: Optional[RequestScheduler] = RequestScheduler(
                generator, cq, aging_s=aging_s, partial_swap=partial_swap,
                tracer=self.tracer, registry=self.registry)
            gw = StepPumpWorker(
                "generation", cq, dq,
                capacity_fn=self.scheduler.capacity,
                admit_fn=self.scheduler.admit,
                step_fn=self._generate_step,
                on_policy_boundary=self._gen_boundary,
                policy_every=policy_every,
                on_error=self._worker_failed)
            self.pipeline = Pipeline(retrieval_queue=rq, context_queue=cq,
                                     done_queue=dq, workers=[rw, gw])
        else:
            self.scheduler = None
            self.pipeline = build_pipeline(
                self._retrieve_batch, self._generate_batch,
                ret_scheduler, gen_scheduler,
                on_ret_boundary=self._ret_boundary,
                on_gen_boundary=self._gen_boundary,
                on_error=self._worker_failed)
        self.gen_scheduler = gen_scheduler

    # ------------------------------------------------------------- stages
    def _retrieve_batch(self, reqs: List[Request]) -> List[Request]:
        # the ambient scope tags every span the sweep emits (partition
        # loads on the streamer's IO thread capture it at submit time)
        # with the rids of the requests being answered
        with self.tracer.scope(*(r.rid for r in reqs)), \
                self.tracer.span("retrieve.batch", batch=len(reqs)):
            t0 = time.perf_counter()
            with self.tracer.span("embed", batch=len(reqs)):
                queries = self.embedder.embed([r.query for r in reqs])
            # IVF probe prunes the sweep; resident partitions answer from
            # RAM and the streamer double-buffers the remaining disk loads
            stats = self.retrieval_stats
            with self.tracer.span("search", top_k=reqs[0].top_k):
                if self.sharded is not None:
                    scores, ids = self.sharded.search(
                        queries, reqs[0].top_k, nprobe=self.nprobe,
                        stats=stats)
                else:
                    scores, ids = self.store.search(
                        queries, reqs[0].top_k, nprobe=self.nprobe,
                        streamer=self.streamer, stats=stats, hot=self.hot)
            chunks = self.store.get_chunks(ids)
            t1 = time.perf_counter()
        if self.registry.enabled:
            self.registry.counter("engine.retrieve_batches").inc()
            self.registry.histogram("retrieve.seconds").observe(t1 - t0)
        for r, ch in zip(reqs, chunks):
            r.retrieved = ch
            r.prompt = " ".join(ch) + " " + r.query
            r.t_ret_start, r.t_ret_end = t0, t1
        return reqs

    def _harvest_obs(self, done: List[Request]) -> None:
        """Close each finished request's async span, record latencies."""
        for r in done:
            self.tracer.end(self._req_spans.pop(r.rid, None))
        if not self.registry.enabled:
            return
        self.registry.counter("engine.completed").inc(len(done))
        lat = self.registry.histogram("request.latency_seconds")
        wait = self.registry.histogram("request.waiting_seconds")
        for r in done:
            if not r.complete:      # partially timestamped: EOS before
                continue            # t_gen_start, or harvested mid-stage
            lat.observe(r.latency)
            wait.observe(r.waiting)

    def _generate_batch(self, reqs: List[Request]) -> List[Request]:
        t0 = time.perf_counter()
        with self.tracer.span("generate.batch", batch=len(reqs),
                              trace_ids=[r.rid for r in reqs]):
            outs = self.generator.generate([r.prompt for r in reqs])
        t1 = time.perf_counter()
        for r, o in zip(reqs, outs):
            r.output = o
            r.t_gen_start, r.t_gen_end = t0, t1
        self._harvest_obs(reqs)
        with self._done_cv:
            self.completed.extend(reqs)
            self._done_cv.notify_all()
        return reqs

    # --------------------------------------- continuous generation stage
    # (admission / preemption / resume policy lives in
    #  repro.serving.reqsched.RequestScheduler — the pump's capacity_fn
    #  and admit_fn are wired straight to it in __init__)
    def _generate_step(self) -> Optional[List[Request]]:
        """One decode step over the slot table; returns rows that left."""
        t0 = time.perf_counter()
        if self.scheduler is not None:
            self.scheduler.tick()       # resume parked work if room
        stepped = self.generator.step()
        finished = self.generator.harvest()
        if not stepped and not finished:
            return None            # idle: no live slots
        t = time.perf_counter()
        if stepped:
            # feed the backlog scheduler per-step samples (batch = live
            # slots).  The power-law argmin is timescale-invariant, so
            # per-step durations steer choose_batch exactly like the
            # whole-batch samples PipelineWorker.observe() would
            self.gen_scheduler.observe(stepped, t - t0)
        if stepped and self.registry.enabled:
            self.registry.histogram("decode.step_seconds").observe(t - t0)
        done: List[Request] = []
        for req, text, _tokens in finished:
            req.output = text
            req.t_gen_end = t
            done.append(req)
        if done:
            if self.scheduler is not None:
                self.scheduler.note_done(done)
            self._harvest_obs(done)
            with self._done_cv:
                self.completed.extend(done)
                self._done_cv.notify_all()
        return done

    # ---------------------------------------------- lazy reconfiguration
    def _ret_boundary(self) -> None:
        pass  # partition target applied by _gen_boundary's placement

    def _gen_boundary(self) -> None:
        if self.opt is None:
            return
        backlog = len(self.pipeline.context_queue)
        if self.continuous:
            # requests already decoding in slots are part of the live
            # batch the placement must provision for (mirrors the
            # simulator's step-level policy consult)
            backlog += self.generator.active_slots
        b = max(self.gen_scheduler.choose_batch(max(backlog, 1)), 1)
        placement = self.opt.solve(b)
        self.pcache.set_target(placement.resident_partitions)
        self.nprobe = placement.nprobe
        # ONE device-byte market clears every elastic accelerator-memory
        # consumer — live KV pages, the prefix-cache cap, swap headroom,
        # and device-hot partitions — from the observed per-partition
        # heat, so the budgets can never over-commit in aggregate
        stats = self.retrieval_stats
        ranking = stats.hot_ranking()
        paged = getattr(self.generator, "paged", False)
        # the live pool format is the market's bits-per-token dimension:
        # an int8 generator clears ~4x the pages out of the same byte
        # grant (the policy boundary is where the knob meets pricing)
        split = self.opt.market(
            placement,
            page_size=self.generator.page_size if paged else None,
            partition_heat=stats.heat(),
            kv_format=getattr(self.generator, "kv_format", None)
            if paged else None,
            # priority-weighted clearing: interactive pressure raises
            # the value of decode throughput relative to retrieval
            priority_pressure=(self.scheduler.priority_pressure()
                               if self.scheduler is not None else 0.0))
        if self.scheduler is not None:
            # the scheduler applies the clearing: it fences outstanding
            # swap DMA (token identity), then retargets the slot table
            # and — for paged generators — both KV tiers + the prefix cap
            applied = self.scheduler.apply_split(b, split)
        else:
            applied = {}
        # hot tier retarget under the market's byte grant: promote down
        # the observed heat ranking, demote what no longer fits
        if self.sharded is not None:
            self.sharded.set_hot_budgets(
                self.opt.shard_hot_budgets(split.hot_bytes,
                                           self.sharded.num_shards),
                ranking)
            hot_parts = len(self.sharded.hot_partitions())
            hot_bytes = self.sharded.hot_device_bytes()
        else:
            self.hot.retarget(split.hot_bytes, ranking)
            hot_parts = len(self.hot)
            hot_bytes = self.hot.device_bytes()
        stats.decay()     # age the heat so the ranking tracks live skew
        # couple the partition streamer's lookahead to the host memory the
        # live placement leaves free (ROADMAP: streamer depth feedback)
        hw = self.opt.cost.hw
        host_free = (hw.cpu_mem * hw.mem_headroom
                     - self.opt.memory_use(placement).cpu)
        if self.sharded is not None:
            # per-shard disk tiers: the placement's host headroom splits
            # across the shards' streamers (each owns its own budget)
            self.sharded.set_budgets(self.opt.shard_streamer_budgets(
                host_free, self.sharded.num_shards))
        else:
            self.streamer.set_budget(max(host_free, 0.0))
        # policy decisions journal through the metrics registry as
        # structured events (``policy_trace`` reads them back as
        # ``PolicyEvent`` rows for the Fig. 9 plots and tests)
        ev = PolicyEvent(
            t=time.perf_counter(), gen_batch=b,
            resident_partitions=placement.resident_partitions,
            c_gpu=placement.c_gpu, w_gpu=placement.w_gpu,
            nprobe=placement.nprobe,
            gen_slots=applied.get("slots"),
            kv_pages=applied.get("pages"),
            kv_host_pages=applied.get("host_pages"),
            parked=getattr(self.generator, "parked_slots", None),
            prefix_pages=applied.get("prefix_pages"),
            prefix_hit_tokens=getattr(self.generator, "prefix_hit_tokens",
                                      None),
            hot_partitions=hot_parts, hot_bytes=hot_bytes,
            hot_hit_rate=stats.hot_hit_rate)
        self.registry.event("policy", **dataclasses.asdict(ev))
        self.tracer.instant("policy.boundary", gen_batch=b,
                            nprobe=placement.nprobe)

    @property
    def policy_trace(self) -> List[PolicyEvent]:
        """Policy-boundary decisions, oldest first (from the registry's
        event journal — bounded, so very long runs keep the tail)."""
        return [PolicyEvent(**{k: v for k, v in e.items()
                               if k not in ("seq", "kind")})
                for e in self.registry.events("policy")]

    def metrics_snapshot(self) -> Dict[str, object]:
        """One coherent dict of every subsystem's counters: sync the
        pull-style sources (search stats, prefix cache, pools, slots)
        into registry gauges, then snapshot."""
        reg = self.registry
        if reg.enabled:
            for name, val in self.retrieval_stats.snapshot().items():
                reg.gauge(f"search.{name}").set(float(val))
            gen = self.generator
            for name in ("active_slots", "parked_slots", "peak_in_flight",
                         "prefix_hit_tokens"):
                val = getattr(gen, name, None)
                if val is not None:
                    reg.gauge(f"gen.{name}").set(float(val))
            kv = getattr(gen, "kv", None)
            if kv is not None:
                pool = getattr(kv, "pool", None)
                if pool is not None:
                    reg.gauge("kv.pages_used").set(
                        float(pool.used_pages))
                    reg.gauge("kv.pages_capacity").set(
                        float(pool.capacity))
                host = getattr(kv, "host", None)
                if host is not None:
                    reg.gauge("kv.host_pages_used").set(
                        float(host.used_pages))
                    reg.gauge("kv.host_pages_capacity").set(
                        float(host.capacity))
            prefix = getattr(gen, "prefix", None)
            if prefix is not None:
                for name, val in dataclasses.asdict(
                        prefix.stats).items():
                    reg.gauge(f"prefix.{name}").set(float(val))
            reg.gauge("hot.partitions").set(
                float(len(self.sharded.hot_partitions())
                      if self.sharded is not None else len(self.hot)))
            reg.gauge("engine.completed_total").set(
                float(len(self.completed)))
        return reg.snapshot()

    def _worker_failed(self, err: BaseException) -> None:
        """A stage thread died: wake ``drain`` so it re-raises now."""
        with self._done_cv:
            self._done_cv.notify_all()

    # ------------------------------------------------------------- public
    def pump_once(self) -> int:
        """One synchronous generation-pump iteration: capacity probe →
        admit from the context queue → decode step — the
        ``StepPumpWorker`` loop body minus the thread and minus the
        ``policy_every`` boundary consult (deliberately: mini-traces
        rely on their constructed slot/page budgets staying put, where
        the boundary would retarget them from the live placement).

        The deterministic seam for mini-traces (the fig8 swap column)
        and tests — keeps the scheduling loop in one place instead of
        letting callers re-implement it against private methods.
        Returns the number of requests completed so far.
        """
        assert self.continuous, "pump_once requires a continuous generator"
        free = self.scheduler.capacity()
        items = self.pipeline.context_queue.pop_batch(free) if free > 0 \
            else []
        if items:
            self.scheduler.admit(items)
        self._generate_step()
        with self._done_lock:
            return len(self.completed)

    def start(self) -> None:
        self.pipeline.start()

    def stop(self) -> None:
        self.pipeline.stop()
        if self._owns_streamer:     # an injected streamer outlives us
            self.streamer.close()
        if self.sharded is not None:
            self.sharded.close()

    def submit(self, req: Request) -> None:
        req.arrival = time.perf_counter() if req.arrival is None \
            else req.arrival
        if self.tracer.enabled:
            # async span: spans submit -> harvest across the retrieval
            # and generation threads, keyed by rid in the trace viewer
            self._req_spans[req.rid] = self.tracer.begin(
                "request", rid=req.rid, trace_ids=[req.rid])
        if self.scheduler is not None:
            self.scheduler.note_queued(req)
        self.pipeline.retrieval_queue.put(req)

    def drain(self, n: int, timeout: float = 120.0) -> List[Request]:
        """Block until ``n`` requests have completed (condition-variable
        wakeup, no polling).  Raises :class:`TimeoutError` — naming the
        in-flight rids and the scheduler's state snapshot — instead of
        silently returning fewer than ``n``.  If a stage thread died,
        re-raises its exception as soon as it is recorded."""
        deadline = time.monotonic() + timeout
        with self._done_cv:
            while len(self.completed) < n:
                err = self.pipeline.error()
                if err is not None:
                    raise err
                left = deadline - time.monotonic()
                if left <= 0 or not self._done_cv.wait(timeout=left):
                    if len(self.completed) >= n:
                        break
                    stuck = (self.scheduler.in_flight_rids()
                             if self.scheduler is not None else [])
                    snap = (self.scheduler.snapshot()
                            if self.scheduler is not None else {})
                    raise TimeoutError(
                        f"drain({n}) timed out after {timeout:.1f}s with "
                        f"{len(self.completed)}/{n} completed; in-flight "
                        f"rids={stuck}; scheduler={snap}")
            return list(self.completed)


class SerialRAGEngine:
    """Baseline: serial retrieve-then-generate, arrival order, one thread."""

    def __init__(self, store: VectorStore, embedder: HashEmbedder,
                 generator: Generator, batch_size: int = 4):
        self.store = store
        self.embedder = embedder
        self.generator = generator
        self.batch_size = batch_size
        self.queue: List[Request] = []
        self.completed: List[Request] = []
        self._lock = threading.Lock()
        # one condition doubles as the submit wakeup (worker waits for
        # arrivals) and the completion wakeup (drain waits for results)
        self._cv = threading.Condition(self._lock)
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()       # wake the worker so it can exit
        self._thread.join(timeout=5.0)

    def submit(self, req: Request) -> None:
        with self._cv:
            self.queue.append(req)
            self._cv.notify_all()

    def _run(self) -> None:
        try:
            self._serve()
        except Exception as e:     # thread boundary: record and report
            with self._cv:
                self._error = e
                self._cv.notify_all()

    def _serve(self) -> None:
        while not self._stop.is_set():
            with self._cv:
                while not self.queue and not self._stop.is_set():
                    self._cv.wait()     # stop() notifies under the cv
                batch = self.queue[:self.batch_size]
                self.queue = self.queue[len(batch):]
            if not batch:
                continue
            t0 = time.perf_counter()
            queries = self.embedder.embed([r.query for r in batch])
            scores, ids = self.store.search(queries, batch[0].top_k)
            chunks = self.store.get_chunks(ids)
            t1 = time.perf_counter()
            for r, ch in zip(batch, chunks):
                r.retrieved = ch
                r.prompt = " ".join(ch) + " " + r.query
                r.t_ret_start, r.t_ret_end = t0, t1
            outs = self.generator.generate([r.prompt for r in batch])
            t2 = time.perf_counter()
            for r, o in zip(batch, outs):
                r.output = o
                r.t_gen_start, r.t_gen_end = t1, t2
            with self._cv:
                self.completed.extend(batch)
                self._cv.notify_all()

    def drain(self, n: int, timeout: float = 120.0) -> List[Request]:
        """Block until ``n`` requests have completed.  Raises
        :class:`TimeoutError` naming the still-queued rids instead of
        silently returning fewer than ``n``.  If the worker thread died,
        re-raises its exception as soon as it is recorded."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while len(self.completed) < n:
                if self._error is not None:
                    raise self._error
                left = deadline - time.monotonic()
                if left <= 0 or not self._cv.wait(timeout=left):
                    if len(self.completed) >= n:
                        break
                    queued = [r.rid for r in self.queue]
                    raise TimeoutError(
                        f"drain({n}) timed out after {timeout:.1f}s with "
                        f"{len(self.completed)}/{n} completed; queued "
                        f"rids={queued}")
            return list(self.completed)
