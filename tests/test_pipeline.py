"""Pipeline machinery: queue conservation, worker isolation, boundaries."""
import threading
import time

import pytest

pytest.importorskip("hypothesis")   # pinned in requirements.txt; skip, never collection-error
from hypothesis import given, settings, strategies as st

from repro.core.pipeline import StageQueue, build_pipeline
from repro.core.scheduler import BacklogScheduler


def _sched(cap=8, c=0.3):
    s = BacklogScheduler(max_batch=cap)
    s.seed([(b, 0.001 * b ** c) for b in (1, 2, 4, 8)])
    return s


def test_stage_queue_fifo_and_batch():
    q = StageQueue("q")
    for i in range(10):
        q.put(i)
    assert len(q) == 10
    assert q.pop_batch(4) == [0, 1, 2, 3]
    assert q.pop_batch(100) == [4, 5, 6, 7, 8, 9]
    assert q.pop_batch(1) == []


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 40))
def test_pipeline_conserves_items(n):
    seen = []
    lock = threading.Lock()

    def ret_fn(items):
        time.sleep(0.0005)
        return [i * 2 for i in items]

    def gen_fn(items):
        time.sleep(0.0005)
        with lock:
            seen.extend(items)
        return items

    pipe = build_pipeline(ret_fn, gen_fn, _sched(), _sched())
    pipe.start()
    for i in range(n):
        pipe.retrieval_queue.put(i)
    t0 = time.time()
    while len(pipe.done_queue) < n and time.time() - t0 < 30:
        time.sleep(0.002)
    pipe.stop()
    assert sorted(seen) == sorted(i * 2 for i in range(n))
    assert len(pipe.done_queue) == n


def test_boundary_hook_called_between_batches():
    calls = {"n": 0}

    def boundary():
        calls["n"] += 1

    pipe = build_pipeline(lambda x: x, lambda x: x, _sched(), _sched(),
                          on_gen_boundary=boundary)
    pipe.start()
    for i in range(20):
        pipe.retrieval_queue.put(i)
    t0 = time.time()
    while len(pipe.done_queue) < 20 and time.time() - t0 < 30:
        time.sleep(0.002)
    pipe.stop()
    assert calls["n"] >= 1


def test_workers_observe_timings():
    pipe = build_pipeline(lambda x: x, lambda x: x, _sched(), _sched())
    pipe.start()
    for i in range(16):
        pipe.retrieval_queue.put(i)
    t0 = time.time()
    while len(pipe.done_queue) < 16 and time.time() - t0 < 30:
        time.sleep(0.002)
    pipe.stop()
    for w in pipe.workers:
        assert w.stats.batches >= 1
        assert w.stats.items == 16
        assert len(w.scheduler.samples) > 0


class _Boom(RuntimeError):
    pass


def test_worker_error_is_recorded_not_swallowed():
    """A stage function that raises ends its worker thread with the
    error recorded on the worker and the pipeline (not a silent death)."""
    def gen_fn(items):
        raise _Boom("generation failed")

    seen = []
    pipe = build_pipeline(lambda x: x, gen_fn, _sched(), _sched(),
                          on_error=seen.append)
    pipe.start()
    pipe.retrieval_queue.put(1)
    t0 = time.time()
    while pipe.error() is None and time.time() - t0 < 30:
        time.sleep(0.002)
    pipe.stop()
    assert isinstance(pipe.error(), _Boom)
    assert len(seen) == 1 and seen[0] is pipe.error()


@pytest.mark.parametrize("engine", ["ragdoll", "serial"])
def test_drain_reraises_failed_step_at_once(engine):
    """``drain`` re-raises a worker's exception as soon as the thread
    dies, instead of waiting out its timeout with a bare TimeoutError."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models.model import Model
    from repro.retrieval import HashEmbedder, VectorStore
    from repro.serving.engine import RagdollEngine, SerialRAGEngine
    from repro.serving.generator import (ContinuousGenerator, Generator,
                                         GeneratorConfig)
    from repro.serving.request import Request

    cfg = get_config("llama3-8b").reduced(num_layers=2)
    params = Model(cfg, remat=False).init(jax.random.PRNGKey(0), jnp.float32)
    gcfg = GeneratorConfig(ctx_len=16, max_new_tokens=2)
    emb = HashEmbedder(dim=16)
    texts = [f"doc {i}" for i in range(24)]

    def boom(*_a, **_k):
        raise _Boom("decode step failed")

    with tempfile.TemporaryDirectory() as root:
        store = VectorStore.build(texts, emb, num_partitions=2, root=root)
        if engine == "ragdoll":
            gen = ContinuousGenerator(cfg, params, gcfg, num_slots=2,
                                      paged=True)
            gen.step = boom                     # the injected step_fn fault
            eng = RagdollEngine(store, emb, gen, BacklogScheduler(4),
                                BacklogScheduler(2))
        else:
            gen = Generator(cfg, params, gcfg)
            gen.generate = boom
            eng = SerialRAGEngine(store, emb, gen, batch_size=2)
        eng.start()
        eng.submit(Request(rid=0, query="doc 3", arrival=time.perf_counter()))
        t0 = time.monotonic()
        with pytest.raises(_Boom):
            eng.drain(1, timeout=120)
        waited = time.monotonic() - t0
        eng.stop()
    assert waited < 60, waited
