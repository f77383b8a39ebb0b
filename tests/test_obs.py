"""Observability layer: tracer, metrics registry, engine wiring, checker.

Contracts pinned here:

* disabled tracing/metrics are true no-ops (shared singletons, no state);
* the engine produces **token-identical** outputs with tracing on vs off
  (observability must never perturb scheduling or decoding);
* histogram bucket boundaries are a pure function of their parameters
  (cross-run / cross-shard bucket compatibility);
* exported traces are valid Chrome/Perfetto JSON — every ``E`` closes a
  matching ``B``, async ``b``/``e`` pair up across threads, complete
  ``X`` events carry their duration — and ``scripts/check_trace.py``
  accepts them (and rejects corrupted ones);
* JAX traces and compiles reach the engine's registry always and its
  tracer when one is bound, through one listener per process
  (``obs.jitlog``); the first token of every request is stamped between
  its admission and its harvest;
* ``SearchStats`` merge conserves totals; partially-timestamped requests
  never crash the latency report.
"""
import importlib.util
import json
import math
import threading
from pathlib import Path

import pytest

from repro.obs import (MetricsRegistry, NULL_REGISTRY, NULL_SPAN,
                       NULL_TRACER, NullTracer, Tracer, log_buckets)
from repro.retrieval.vectorstore import SearchStats
from repro.serving.request import Request, latency_table

REPO = Path(__file__).resolve().parent.parent


def _load_checker():
    spec = importlib.util.spec_from_file_location(
        "check_trace", REPO / "scripts" / "check_trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ tracer

def test_null_tracer_is_noop(tmp_path):
    tr = NULL_TRACER
    assert tr.enabled is False
    assert tr.span("x", a=1) is NULL_SPAN
    assert tr.scope(1, 2) is NULL_SPAN
    with tr.span("x"):
        with tr.scope(7):
            assert tr.current_scope() == ()
    token = tr.begin("req")
    assert token is None
    tr.end(token)                       # None token: no-op, no raise
    tr.instant("i")
    tr.counter("c", 1.0)
    assert tr.interval("x", a=1) is NULL_SPAN
    tr.complete("x", 0.0, 1.0, a=1)
    assert tr.events() == []
    out = tmp_path / "t.json"
    tr.export(str(out))
    assert not out.exists()             # disabled tracer writes nothing


def test_span_nesting_round_trips(tmp_path):
    tr = Tracer()
    with tr.span("outer", k=1):
        with tr.span("inner"):
            tr.instant("tick")
        tr.counter("depth", 2.0)
    out = tmp_path / "trace.json"
    n = tr.export(str(out))
    assert n == 6                       # 2x(B+E) + i + C
    doc = json.loads(out.read_text())
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert [e["ph"] for e in evs] == ["B", "B", "i", "E", "C", "E"]
    assert all(e["cat"] == "repro" for e in evs)
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "thread_name"
    # every E closes the matching B (checker enforces nesting)
    chk = _load_checker()
    assert chk.check(doc, require=[], any_groups=[]) == []


def test_scope_tags_trace_ids():
    tr = Tracer()
    with tr.scope(3, 5):
        assert tr.current_scope() == (3, 5)
        with tr.span("tagged"):
            pass
        with tr.span("explicit", trace_ids=[9]):
            pass
    with tr.span("outside"):
        pass
    by_name = {name: attrs for ph, name, ts, tid, aid, attrs
               in tr.events() if ph == "B"}
    assert by_name["tagged"]["trace_ids"] == [3, 5]
    assert by_name["explicit"]["trace_ids"] == [9]
    assert by_name["outside"] is None


def test_async_span_crosses_threads(tmp_path):
    tr = Tracer()
    token = tr.begin("request", trace_ids=[1])
    t = threading.Thread(target=lambda: tr.end(token), name="closer")
    t.start()
    t.join()
    tr.end(None)                        # null token tolerated
    out = tmp_path / "t.json"
    tr.export(str(out))
    doc = json.loads(out.read_text())
    evs = [e for e in doc["traceEvents"] if e["ph"] in "be"]
    assert [e["ph"] for e in evs] == ["b", "e"]
    assert evs[0]["id"] == evs[1]["id"]
    assert evs[0]["tid"] != evs[1]["tid"]
    chk = _load_checker()
    assert chk.check(doc, require=["request"], any_groups=[]) == []


def test_ring_buffer_bounds_memory():
    tr = Tracer(capacity=8)
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events()) == 8
    assert tr.dropped == 32             # 40 events through an 8-slot ring
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_complete_events_round_trip(tmp_path):
    """``interval`` records one X event at exit holding start and
    duration; ``complete`` records one for an interval timed elsewhere;
    both tag the ambient scope and export with ``dur``."""
    import time
    tr = Tracer()
    with tr.scope(5):
        with tr.interval("outer", k=1):
            time.sleep(0.002)
        t = time.perf_counter()
        tr.complete("timed", t - 0.5, t, fun="f")
    (ph, name, ts, tid, dur, attrs), (ph2, name2, ts2, _, dur2, attrs2) = \
        tr.events()
    assert (ph, name, ph2, name2) == ("X", "outer", "X", "timed")
    assert dur >= 2e3 and tid == threading.get_ident()
    assert attrs == {"k": 1, "trace_ids": [5]}
    assert attrs2 == {"fun": "f", "trace_ids": [5]}
    assert dur2 == pytest.approx(0.5e6) and ts2 < ts   # backdated start
    path = tmp_path / "t.json"
    tr.export(str(path))
    rows = [e for e in json.loads(path.read_text())["traceEvents"]
            if e["ph"] == "X"]
    assert [e["name"] for e in rows] == ["timed", "outer"]  # ts order
    assert rows[0]["dur"] == pytest.approx(0.5e6, rel=1e-6)
    assert "id" not in rows[0]
    assert _load_checker().check(
        json.loads(path.read_text()), require=[], any_groups=[]) == []


def test_span_balanced_on_exception(tmp_path):
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("body"):
            raise RuntimeError("boom")
    phases = [e[0] for e in tr.events()]
    assert phases == ["B", "E"]         # exception still closes the span


# ----------------------------------------------------------------- metrics

def test_log_buckets_are_a_pure_function():
    a = log_buckets(1e-6, 1e3, per_decade=2)
    b = log_buckets(1e-6, 1e3, per_decade=2)
    assert a == b                       # bucket-compatible across runs
    assert a[0] == pytest.approx(1e-6)
    assert a[-1] == pytest.approx(1e3)
    assert len(a) == 19                 # 9 decades x 2 + fencepost
    assert all(x < y for x, y in zip(a, a[1:]))
    # half-decade ratio everywhere
    for x, y in zip(a, a[1:]):
        assert y / x == pytest.approx(math.sqrt(10.0), rel=1e-9)
    with pytest.raises(ValueError):
        log_buckets(1.0, 0.5)


def test_histogram_boundary_stability():
    reg = MetricsRegistry()
    h = reg.histogram("lat", bounds=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 1.5, 10.0, 99.0, 1000.0):
        h.observe(v)
    # obs <= bounds[i] lands in bucket i; > bounds[-1] overflows
    assert h.counts == [2, 2, 1, 1]
    assert h.count == 6
    assert h.mean == pytest.approx(sum((0.5, 1.0, 1.5, 10.0, 99.0,
                                        1000.0)) / 6)
    d = h.to_dict()
    assert d["min"] == 0.5 and d["max"] == 1000.0
    assert d["bounds"] == [1.0, 10.0, 100.0]
    # same name returns the same instrument; new bounds are rejected
    assert reg.histogram("lat") is h
    with pytest.raises(ValueError):
        reg.histogram("lat", bounds=(2.0, 20.0))


def test_registry_instruments_and_journal(tmp_path):
    reg = MetricsRegistry(max_events=3)
    reg.counter("hits").inc()
    reg.counter("hits").inc(2)
    with pytest.raises(ValueError):
        reg.counter("hits").inc(-1)
    reg.gauge("occ").set(5)
    reg.gauge("occ").add(-2)
    with pytest.raises(ValueError):
        reg.gauge("hits")               # cross-kind name collision
    for i in range(5):
        reg.event("policy", step=i)
    evs = reg.events("policy")
    assert [e["step"] for e in evs] == [2, 3, 4]   # bounded journal
    assert [e["seq"] for e in evs] == [3, 4, 5]    # seq survives drops
    assert reg.events("nope") == []
    snap = reg.snapshot()
    assert snap["counters"]["hits"] == 3.0
    assert snap["gauges"]["occ"] == 3.0
    out = tmp_path / "metrics.json"
    reg.export(str(out))
    assert json.loads(out.read_text())["counters"]["hits"] == 3.0


def test_null_registry_is_noop(tmp_path):
    reg = NULL_REGISTRY
    assert reg.enabled is False
    reg.counter("x").inc()
    reg.gauge("y").set(1)
    reg.histogram("z").observe(2)
    reg.event("policy", a=1)
    assert reg.events() == []
    assert reg.snapshot() == {}
    out = tmp_path / "m.json"
    reg.export(str(out))
    assert not out.exists()


# -------------------------------------------------------------- SearchStats

def test_searchstats_add_rejects_unknown():
    s = SearchStats()
    s.add(partitions_searched=2, load_seconds=0.5)
    assert s.partitions_searched == 2
    with pytest.raises(AttributeError):
        s.add(not_a_counter=1)


def test_searchstats_merge_conserves_totals():
    a, b = SearchStats(), SearchStats()
    a.add(partitions_searched=3, partitions_loaded=1, hot_hits=2,
          load_seconds=0.25)
    a.record_search(0, 2.0)
    a.record_search(1)
    b.add(partitions_searched=5, partitions_loaded=2, cache_hits=4,
          search_seconds=0.5)
    b.record_search(2)
    a.merge(b)
    assert a.partitions_searched == 8
    assert a.partitions_loaded == 3
    assert a.cache_hits == 4 and a.hot_hits == 2
    assert a.load_seconds == pytest.approx(0.25)
    assert a.search_seconds == pytest.approx(0.5)
    assert a.hit_counts[0] == 2 and a.hit_counts[1] == 1 \
        and a.hit_counts[2] == 1
    snap = a.snapshot()
    assert snap["partitions_searched"] == 8
    assert 0.0 <= snap["hot_hit_rate"] <= 1.0
    a.reset()
    assert a.partitions_searched == 0 and a.load_seconds == 0.0
    assert a.hit_counts[0] == 2        # heat is policy state, kept


# ------------------------------------------------- partial-timestamp guards

def test_partial_timestamps_never_crash_reporting():
    full = Request(rid=0, query="q", arrival=0.0)
    full.output = "x"
    full.t_ret_start, full.t_ret_end = 1.0, 2.0
    full.t_gen_start, full.t_gen_end = 3.0, 4.0
    partial = Request(rid=1, query="q", arrival=0.0)
    partial.output = "y"               # harvested before t_gen_start
    partial.t_ret_start, partial.t_ret_end = 1.0, 2.0
    assert full.complete and not partial.complete
    assert math.isnan(partial.latency) and math.isnan(partial.waiting)
    tab = latency_table([full, partial])
    assert tab["n"] == 1 and tab["incomplete"] == 1
    assert tab["avg_latency"] == pytest.approx(4.0)
    empty = latency_table([partial])
    assert empty == {"n": 0, "incomplete": 1}


# ----------------------------------------------------------------- checker

def test_checker_rejects_broken_traces():
    chk = _load_checker()
    pid = 1
    def ev(ph, name, ts, tid=1, **kw):
        return {"name": name, "ph": ph, "ts": ts, "pid": pid,
                "tid": tid, **kw}
    # unbalanced B
    doc = {"traceEvents": [ev("B", "open", 1.0)]}
    assert any("unclosed" in e for e in
               chk.check(doc, require=[], any_groups=[]))
    # E with no B / bad nesting
    doc = {"traceEvents": [ev("E", "ghost", 1.0)]}
    assert any("no open B" in e for e in
               chk.check(doc, require=[], any_groups=[]))
    doc = {"traceEvents": [ev("B", "a", 1.0), ev("B", "b", 2.0),
                           ev("E", "a", 3.0), ev("E", "b", 4.0)]}
    assert any("bad nesting" in e for e in
               chk.check(doc, require=[], any_groups=[]))
    # out-of-order timestamps
    doc = {"traceEvents": [ev("B", "a", 5.0), ev("E", "a", 1.0)]}
    assert any("not sorted" in e for e in
               chk.check(doc, require=[], any_groups=[]))
    # missing keys
    doc = {"traceEvents": [{"ph": "B", "ts": 1.0}]}
    assert any("missing keys" in e for e in
               chk.check(doc, require=[], any_groups=[]))
    # async e with no b
    doc = {"traceEvents": [ev("e", "req", 1.0, id=7)]}
    assert any("no open b" in e for e in
               chk.check(doc, require=[], any_groups=[]))
    # no request timeline
    doc = {"traceEvents": [ev("B", "a", 1.0), ev("E", "a", 2.0)]}
    assert any("trace_ids" in e for e in
               chk.check(doc, require=["a"], any_groups=[]))
    # and a good trace passes
    doc = {"traceEvents": [
        ev("B", "a", 1.0, args={"trace_ids": [0]}),
        ev("E", "a", 2.0)]}
    assert chk.check(doc, require=["a"], any_groups=[]) == []


# ------------------------------------------------------------ engine wiring

@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models.model import Model

    cfg = get_config("llama3-8b").reduced(num_layers=2)
    params = Model(cfg, remat=False).init(jax.random.PRNGKey(0),
                                          jnp.float32)
    return cfg, params


def _mini_engine(tiny_model, root, tracer, registry, **gen_kw):
    """A mini engine over a 40-chunk store (3 of 4 partitions spilled)
    and a paged continuous generator; ``gen_kw`` adds generator
    options (streamed weights, chunked prefill)."""
    from repro.core.scheduler import BacklogScheduler
    from repro.retrieval import HashEmbedder, VectorStore
    from repro.serving.engine import RagdollEngine
    from repro.serving.generator import (ContinuousGenerator,
                                         GeneratorConfig)

    cfg, params = tiny_model
    emb = HashEmbedder(dim=16)
    texts = [f"doc {i} topic{i % 3}" for i in range(40)]
    store = VectorStore.build(texts, emb, num_partitions=4, root=root)
    store.spill(3)
    gen = ContinuousGenerator(
        cfg, params, GeneratorConfig(ctx_len=16, max_new_tokens=4),
        num_slots=2, paged=True, page_size=4, **gen_kw)
    return RagdollEngine(store, emb, gen, BacklogScheduler(max_batch=8),
                         BacklogScheduler(max_batch=2),
                         initial_partitions=2, tracer=tracer,
                         registry=registry)


def _mini_engine_outputs(tiny_model, root, tracer, registry, **gen_kw):
    """Deterministic single-threaded engine drive (fig8's mini-trace
    shape): retrieve a batch, then pump admit/decode to completion."""
    import time

    from repro.obs import jitlog

    eng = _mini_engine(tiny_model, root, tracer, registry, **gen_kw)
    reqs = [Request(rid=i, query=f"query {i}", arrival=time.perf_counter())
            for i in range(4)]
    try:
        for r in reqs:
            eng.submit(r)               # opens the async request span
        batch = eng.pipeline.retrieval_queue.pop_batch(len(reqs))
        assert len(batch) == len(reqs)
        eng._retrieve_batch(batch)
        eng.pipeline.context_queue.put_many(batch)
        guard = 0
        while eng.pump_once() < len(reqs):
            guard += 1
            assert guard < 400, "mini engine stalled"
    finally:
        eng.streamer.close()
        jitlog.detach(eng)
    return {r.rid: r.output for r in eng.completed}, eng


def _assert_first_token_stamped(eng):
    assert len(eng.completed) == 4
    for r in eng.completed:
        assert r.t_gen_start <= r.t_first_token <= r.t_gen_end


def test_engine_tracing_is_token_identical(tiny_model, tmp_path):
    """Tracing on vs off must not change a single output token, the
    trace must pass the schema checker with per-request stage coverage,
    and the metrics snapshot must cover pages/search/prefix counters."""
    chk = _load_checker()
    out_off, _ = _mini_engine_outputs(
        tiny_model, str(tmp_path / "off"), tracer=None, registry=None)
    tr = Tracer()
    reg = MetricsRegistry()
    out_on, eng = _mini_engine_outputs(
        tiny_model, str(tmp_path / "on"), tracer=tr, registry=reg)
    assert out_on == out_off            # observability never perturbs
    assert len(out_on) == 4 and all(out_on.values())

    path = tmp_path / "trace.json"
    n = tr.export(str(path))
    assert n > 0 and tr.dropped == 0
    doc = json.loads(path.read_text())
    assert chk.check(doc) == []         # default per-request coverage
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] != "M"}
    for required in ("request", "retrieve.batch", "embed", "search",
                     "prefill", "decode.step", "decode.sync", "topk",
                     "jit.trace"):
        assert required in names, required
    topk = [e for e in doc["traceEvents"] if e["name"] == "topk"]
    assert all(e["ph"] == "X" and e["args"]["rows"] > 0 for e in topk)
    assert {e["args"]["pid"] for e in topk} <= set(range(4))
    _assert_first_token_stamped(eng)

    snap = eng.metrics_snapshot()
    assert snap["counters"]["engine.retrieve_batches"] >= 1.0
    assert snap["counters"]["engine.completed"] == 4.0
    assert "kv.pages_capacity" in snap["gauges"]
    assert "search.partitions_searched" in snap["gauges"]
    assert snap["gauges"]["search.partitions_searched"] >= 1.0
    assert snap["histograms"]["request.latency_seconds"]["count"] == 4
    # engine-owned registry keeps the policy journal seam alive
    assert eng.policy_trace == []       # pump_once skips the boundary


# ------------------------------------------- idle and compile attribution

_STREAMED = dict(streamed=True, resident_layers=1)


@pytest.mark.parametrize("gen_kw", [
    {"prefill_chunk": 8},
    dict(_STREAMED),
    dict(_STREAMED, prefill_chunk=8),
], ids=["paged-chunked", "streamed", "streamed-chunked"])
def test_engine_new_spans_are_token_identical(tiny_model, tmp_path, gen_kw):
    """The step's host spans (``step.head``/``step.tail``/``stream.wait``
    on the streamed executor, ``decode.sync``, ``topk``) and the
    ``jit.*`` spans perturb no token; every request's first token is
    stamped between admission and harvest, chunked or not."""
    from repro.core.prefetch import PrefetchPolicy

    def kw():   # a queue one layer deep waits on every streamed layer
        return dict(gen_kw, policy=PrefetchPolicy(max_depth=1)) \
            if gen_kw.get("streamed") else dict(gen_kw)
    out_off, eng_off = _mini_engine_outputs(
        tiny_model, str(tmp_path / "off"), None, None, **kw())
    tr = Tracer()
    out_on, eng = _mini_engine_outputs(
        tiny_model, str(tmp_path / "on"), tr, MetricsRegistry(), **kw())
    assert out_on == out_off and len(out_on) == 4
    names = {e[1] for e in tr.events()}
    want = {"decode.sync", "topk", "jit.trace", "jit.lower", "jit.compile"}
    if gen_kw.get("streamed"):
        want |= {"step.head", "step.tail", "stream.wait"}
    if gen_kw.get("prefill_chunk"):
        want.add("prefill.chunk")
    assert want <= names, want - names
    for e in eng, eng_off:
        _assert_first_token_stamped(e)
    # the counters run with tracing off too
    assert eng_off.registry.counter("jit.events").value > 0


def test_jitlog_spans_on_the_calling_thread():
    """A fresh ``jax.jit`` closure and eager dispatch on a new shape
    each report trace, lower and compile on the thread that ran them,
    with ``fun``; inner primitives nest inside the closure's trace.
    Every attached sink's registry counts every event; a sink with no
    tracer bound records no span."""
    import jax
    import jax.numpy as jnp

    from repro.obs import jitlog

    class Sink:
        pass
    on, off = Sink(), Sink()
    on.tracer, on.registry = Tracer(), MetricsRegistry()
    off.tracer, off.registry = NULL_TRACER, MetricsRegistry()
    jitlog.attach(on)
    jitlog.attach(off)

    def work():
        jax.jit(lambda x: jnp.cos(x) * 3.0)(jnp.arange(5.0))
        jnp.sin(jnp.ones((3, 7, 11)))
    th = threading.Thread(target=work)
    try:
        th.start()
        th.join(timeout=60.0)
        assert not th.is_alive()
    finally:
        jitlog.detach(on)
        jitlog.detach(off)
    assert on not in jitlog.sinks() and off not in jitlog.sinks()
    evs = [e for e in on.tracer.events() if e[3] == th.ident]
    assert all(e[0] == "X" for e in evs)
    by_fun = {}
    for ph, name, ts, tid, dur, attrs in evs:
        by_fun.setdefault(attrs["fun"], []).append((name, ts, dur))
    lam = by_fun["<lambda>"] + by_fun["jit(<lambda>)"]
    assert sorted(n for n, _, _ in lam) == ["jit.compile", "jit.lower",
                                            "jit.trace"]
    assert {n for n, _, _ in by_fun["jit(sin)"]} == {"jit.lower",
                                                     "jit.compile"}
    assert "sin" in by_fun and "cos" in by_fun
    (_, o_ts, o_dur), = [x for x in by_fun["<lambda>"]
                         if x[0] == "jit.trace"]
    (_, i_ts, i_dur), = by_fun["cos"]
    slack = 2e3                          # two clocks: 2 ms of room
    assert o_ts - slack <= i_ts and i_ts + i_dur <= o_ts + o_dur + slack
    n = on.registry.counter("jit.events").value
    assert n >= len(evs) > 0
    assert off.registry.counter("jit.events").value == n
    assert on.registry.counter("jit.seconds").value > 0


def test_jitlog_one_listener_for_many_engines(tiny_model, tmp_path):
    from jax._src import monitoring

    from repro.obs import jitlog
    engines = [_mini_engine(tiny_model, str(tmp_path / str(i)), None, None)
               for i in range(2)]
    assert monitoring._event_time_span_listeners.count(
        jitlog._on_event) == 1
    assert all(e in jitlog.sinks() for e in engines)
    engines[0].streamer.close()
    jitlog.detach(engines[0])
    assert engines[0] not in jitlog.sinks() and engines[1] in jitlog.sinks()
    engines[1].streamer.close()
    jitlog.detach(engines[1])


def test_pump_records_wait_and_step_spans():
    """Idle sleeps are ``pump.wait``, iterations that admit or step are
    ``pump.step``; the tracer is read on every iteration, so binding it
    late works, and the two never overlap on the pump's thread."""
    import time

    from repro.core.pipeline import StageQueue, StepPumpWorker

    class Holder:
        tracer = NULL_TRACER
    holder, live = Holder(), []

    def step():
        return [live.pop()] if live else None
    cq, dq = StageQueue("context"), StageQueue("done")
    pump = StepPumpWorker("generation", cq, dq, capacity_fn=lambda: 1,
                          admit_fn=live.extend, step_fn=step,
                          idle_wait=0.002,
                          tracer_fn=lambda: holder.tracer)
    tr = Tracer()
    pump.start()
    try:
        time.sleep(0.02)
        holder.tracer = tr
        time.sleep(0.02)
        cq.put_many(range(3))
        deadline = time.perf_counter() + 5.0
        while len(dq) < 3 and time.perf_counter() < deadline:
            time.sleep(0.002)
        time.sleep(0.02)
    finally:
        pump.stop()
        pump.join(timeout=5.0)
    assert not pump.is_alive() and len(dq) == 3
    evs = sorted((e for e in tr.events() if e[0] == "X"),
                 key=lambda e: e[2])
    assert {e[3] for e in evs} == {pump.ident}
    names = [e[1] for e in evs]
    assert names.count("pump.step") == 3 and "pump.wait" in names
    for a, b in zip(evs, evs[1:]):
        assert a[2] + a[4] <= b[2] + 1e-3        # disjoint, in order


def test_first_token_survives_preempt_and_resume(tiny_model):
    from repro.serving.generator import (ContinuousGenerator,
                                         GeneratorConfig)
    cfg, params = tiny_model
    cont = ContinuousGenerator(cfg, params,
                               GeneratorConfig(ctx_len=16, max_new_tokens=5),
                               num_slots=2, paged=True, page_size=4)
    ref = cont.join("x", "alpha beta")
    t_first = cont.table.state(ref).t_first_token
    assert t_first is not None
    cont.step()
    ref = cont.resume(cont.preempt(ref))
    assert cont.table.state(ref).t_first_token == t_first
    while cont.active_slots:
        cont.step()
    ((key, _, tokens, stamp),) = cont.harvest_stamped()
    assert key == "x" and len(tokens) == 5 and stamp == t_first
