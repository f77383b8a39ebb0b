"""Continuous decode-step batching: equivalence + slot-table behaviour.

The headline contract: a ``ContinuousGenerator`` driving a randomized
join/leave schedule produces **token-identical** outputs to the
whole-batch ``Generator`` for the same prompts under greedy decode, on
both the scan-based ``Model`` path and the offloading
``StreamedExecutor`` path.  Per-row computation is batch-size invariant
on this backend, and slot rows are fully overwritten on join, so the
equality is exact — not approximate.

Deliberately hypothesis-free (the SlotTable property suite lives in
``test_slots.py``) so this module always runs in the CI fast tier.
"""
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models.model import Model
from repro.serving.generator import (ContinuousGenerator, Generator,
                                     GeneratorConfig)

CTX, MAX_NEW = 16, 5


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama3-8b").reduced(num_layers=2)
    params = Model(cfg, remat=False).init(jax.random.PRNGKey(0),
                                          jnp.float32)
    return cfg, params


def _prompts(n=6):
    return [f"query {i} topic{i % 3} alpha beta" for i in range(n)]


def _random_schedule(seed, ticks=40, max_joins=3):
    rng = np.random.default_rng(seed)
    return [int(rng.integers(0, max_joins)) for _ in range(ticks)]


# ---------------------------------------------------------------- equivalence

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_continuous_token_identical_to_whole_batch(tiny_model, seed):
    """Randomized join/leave schedules never change greedy outputs."""
    cfg, params = tiny_model
    g = GeneratorConfig(ctx_len=CTX, max_new_tokens=MAX_NEW)
    prompts = _prompts()
    ref = Generator(cfg, params, g, streamed=False).generate(prompts)
    cont = ContinuousGenerator(cfg, params, g, num_slots=3, streamed=False)
    out = cont.run(prompts, schedule=_random_schedule(seed))
    assert out == ref
    # slot reuse happened (6 prompts through 3 slots) and left no leases
    assert cont.free_slots == cont.num_slots


@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_continuous_token_identical_streamed(tiny_model, seed):
    """Same contract through the offloading StreamedExecutor path."""
    cfg, params = tiny_model
    g = GeneratorConfig(ctx_len=CTX, max_new_tokens=MAX_NEW)
    prompts = _prompts()
    ref = Generator(cfg, params, g, streamed=True).generate(prompts)
    cont = ContinuousGenerator(cfg, params, g, num_slots=3, streamed=True)
    out = cont.run(prompts, schedule=_random_schedule(seed))
    assert out == ref


def _paged_streamed(cfg, params, resident, kv_format=None, registry=None,
                    tracer=None):
    g = GeneratorConfig(ctx_len=CTX, max_new_tokens=MAX_NEW)
    return ContinuousGenerator(cfg, params, g, num_slots=3, streamed=True,
                               paged=True, page_size=4,
                               resident_layers=resident,
                               kv_format=kv_format, registry=registry,
                               tracer=tracer)


def _eager_join(gen, prompt, slot):
    """The reference join: the executor's per-layer programs on
    full-length row caches with the eager embedding and tail, then the
    pool's scatter of the prompt's rows into ``slot``'s pages.  Returns
    the first token and the pools."""
    g = gen.gen_cfg
    toks = jnp.asarray(gen.tok.encode(prompt, g.ctx_len)[None])
    logits, row = gen.exec.prefill(
        toks, gen.exec.init_caches(1, gen._total, g.dtype))
    assert gen.kv.admit(slot, g.ctx_len + g.max_new_tokens)
    pools = gen.kv.scatter_row_layered(gen.caches, row, slot, g.ctx_len)
    return int(gen._greedy(logits)[0]), pools


@pytest.mark.parametrize("arch,resident,kv_format", [
    ("llama3-8b", 2, None),      # every layer resident: one program
    ("llama3-8b", 1, None),      # resident program, a streamed layer, tail
    ("llama3-8b", 0, None),      # every layer streamed
    ("llama3-8b", 1, "bf16"),    # fp32 rows cast into bf16 pages
    ("llama3-8b", 1, "int8"),    # rows quantized into int8 pages
    ("chatglm3-6b", 2, None),    # QKV bias, rotary on half the head dims
])
def test_compiled_paged_prefill_matches_eager(arch, resident, kv_format):
    """A streamed paged join run as compiled programs writes the same KV
    pages, bit for bit, and the same first token as the eager join
    (per-layer programs on row caches, eager embedding and tail), and
    serves the same tokens as the whole-batch streamed generator."""
    cfg = get_config(arch).reduced(num_layers=2)
    params = Model(cfg, remat=False).init(jax.random.PRNGKey(0),
                                          jnp.float32)
    from repro.obs.metrics import MetricsRegistry

    prompt = "alpha beta gamma delta"
    fused = _paged_streamed(cfg, params, resident, kv_format,
                            registry=MetricsRegistry())
    ref = fused.join("first", prompt)
    first, pools = _eager_join(
        _paged_streamed(cfg, params, resident, kv_format), prompt,
        ref.index)
    assert fused.table.state(ref).tokens == [first]
    pages = fused.kv.pool.table(ref.index)
    for a, b in zip(jax.tree.leaves(fused.caches), jax.tree.leaves(pools)):
        assert np.asarray(a)[pages].any()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    while fused.active_slots:
        fused.step()
    fused.harvest()
    g = GeneratorConfig(ctx_len=CTX, max_new_tokens=MAX_NEW)
    prompts = _prompts()
    out = fused.run(prompts, schedule=_random_schedule(3))
    if kv_format is None:       # narrower pages attend to rounded keys
        assert out == Generator(cfg, params, g,
                                streamed=True).generate(prompts)
    assert fused.registry.counter("prefill.fused_joins").value == 7


def test_compiled_join_compiles_once_and_counts(tiny_model):
    """A second join of the same prompt length traces, lowers and
    compiles nothing, and a join after the pool is resized compiles only
    the page write; ``prefill.fused_joins`` counts the compiled joins,
    an int8-pool join among them."""
    from repro.obs import jitlog
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.trace import Tracer

    cfg, params = tiny_model

    class Sink:
        tracer = Tracer()
        registry = MetricsRegistry()

    sink = Sink()
    reg = sink.registry
    jitlog.attach(sink)
    try:
        gen = _paged_streamed(cfg, params, 1, registry=reg,
                              tracer=sink.tracer)
        gen.join(0, "alpha beta gamma")              # compiles
        events = reg.counter("jit.events").value
        assert events > 0
        n = len(sink.tracer.events())
        gen.join(1, "delta epsilon zeta eta theta")
        assert reg.counter("jit.events").value == events
        assert not [e for e in sink.tracer.events()[n:]
                    if e[1].startswith("jit.")]
        assert reg.counter("prefill.fused_joins").value == 2
        # a policy boundary resizes the pool: only the page write, not
        # the prefill, compiles again for the new pool shape
        gen.set_page_budget(gen.kv.pool.capacity + 4)
        n = len(sink.tracer.events())
        gen.join(2, "iota kappa lambda")
        funs = {e[5]["fun"] for e in sink.tracer.events()[n:]
                if e[1] == "jit.trace"}
        assert "_write_rows" in funs
        assert not funs & {"embed", "layer", "pick"}
        int8 = _paged_streamed(cfg, params, 1, kv_format="int8",
                               registry=reg, tracer=sink.tracer)
        int8.join(3, "alpha beta gamma")
        assert reg.counter("prefill.fused_joins").value == 4
    finally:
        jitlog.detach(sink)
    fused = [e[5]["fused"] for e in sink.tracer.events()
             if e[0] == "B" and e[1] == "prefill"]
    assert fused == [True, True, True, True]


def test_eos_exit_matches_whole_batch_trim(tiny_model):
    """A slot leaves the moment it emits EOS; the whole-batch path trims
    at the same token, so outputs still agree exactly."""
    cfg, params = tiny_model
    base = GeneratorConfig(ctx_len=CTX, max_new_tokens=MAX_NEW)
    prompts = _prompts(4)
    plain = Generator(cfg, params, base, streamed=False).generate(prompts)
    # pick a token the greedy decode actually emits mid-stream as "EOS"
    eos = int(plain[0].split()[2][3:])
    g = GeneratorConfig(ctx_len=CTX, max_new_tokens=MAX_NEW, eos_id=eos)
    ref = Generator(cfg, params, g, streamed=False).generate(prompts)
    cont = ContinuousGenerator(cfg, params, g, num_slots=2, streamed=False)
    out = cont.run(prompts, schedule=_random_schedule(7))
    assert out == ref
    assert len(ref[0].split()) <= 3          # the trim actually bit


def test_join_respects_capacity_and_harvest_frees(tiny_model):
    cfg, params = tiny_model
    g = GeneratorConfig(ctx_len=CTX, max_new_tokens=2)
    cont = ContinuousGenerator(cfg, params, g, num_slots=2, streamed=False)
    assert cont.join("a", "alpha") is not None
    assert cont.join("b", "beta") is not None
    assert cont.join("c", "gamma") is None       # table full
    assert cont.free_slots == 0
    cont.step()                                   # budget 2: both finish
    done = {k for k, _, _ in cont.harvest()}
    assert done == {"a", "b"}
    assert cont.free_slots == 2                   # slots immediately reusable
    assert cont.join("c", "gamma") is not None


def test_per_request_budget_capped_by_cache(tiny_model):
    cfg, params = tiny_model
    g = GeneratorConfig(ctx_len=CTX, max_new_tokens=4)
    cont = ContinuousGenerator(cfg, params, g, num_slots=1, streamed=False)
    cont.join("k", "alpha", max_new_tokens=100)   # beyond the cache room
    steps = 0
    while cont.active_slots and steps < 50:
        cont.step()
        steps += 1
    (_, _, tokens), = cont.harvest()
    assert len(tokens) == 4                       # clamped to gen_cfg budget


# ------------------------------------------------- streamed slot-mask contract

def test_streamed_executor_skips_stream_when_all_slots_dead(tiny_model):
    cfg, params = tiny_model
    g = GeneratorConfig(ctx_len=CTX, max_new_tokens=4)
    cont = ContinuousGenerator(cfg, params, g, num_slots=2, streamed=True)
    caches = cont.caches
    inputs = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.full((2,), CTX, jnp.int32)
    mask = jnp.zeros((2,), bool)
    logits, out_caches = cont.exec.decode(inputs, caches, pos,
                                          slot_mask=mask)
    assert out_caches is caches          # untouched: no layer re-stream
    assert logits.shape == (2, cfg.vocab_size)
    assert not np.asarray(logits).any()


def test_streamed_decode_mask_never_changes_live_rows(tiny_model):
    """The slot mask only skips work — live-row logits are unchanged."""
    cfg, params = tiny_model
    g = GeneratorConfig(ctx_len=CTX, max_new_tokens=4)
    cont = ContinuousGenerator(cfg, params, g, num_slots=2, streamed=True)
    cont.join("live", "alpha beta")
    caches = cont.caches
    inputs = jnp.asarray(cont._cur)[:, None]
    pos = jnp.asarray(cont._pos)
    mask = jnp.asarray(cont.table.mask())         # [True, False]
    l_masked, _ = cont.exec.decode(inputs, caches, pos, slot_mask=mask)
    l_plain, _ = cont.exec.decode(inputs, caches, pos)
    np.testing.assert_array_equal(np.asarray(l_masked[0]),
                                  np.asarray(l_plain[0]))


# ----------------------------------------------------------------- engine e2e

@pytest.mark.slow
def test_ragdoll_engine_continuous_end_to_end():
    import tempfile

    from repro.core.scheduler import BacklogScheduler
    from repro.retrieval import HashEmbedder, VectorStore
    from repro.serving.engine import RagdollEngine
    from repro.serving.request import Request

    cfg = get_config("llama3-8b").reduced(num_layers=2)
    params = Model(cfg, remat=False).init(jax.random.PRNGKey(0),
                                          jnp.float32)
    gen = ContinuousGenerator(
        cfg, params, GeneratorConfig(ctx_len=32, max_new_tokens=4),
        num_slots=3, streamed=False)
    emb = HashEmbedder(dim=32)
    texts = [f"doc {i} topic{i % 5}" for i in range(120)]
    with tempfile.TemporaryDirectory() as root:
        store = VectorStore.build(texts, emb, num_partitions=4, root=root)
        store.spill(3)
        eng = RagdollEngine(store, emb, gen,
                            BacklogScheduler(max_batch=8),
                            BacklogScheduler(max_batch=4),
                            initial_partitions=3, policy_every=2)
        assert eng.continuous
        eng.start()
        n = 10
        for i in range(n):
            eng.submit(Request(rid=i, query=f"query {i}",
                               arrival=time.perf_counter()))
        reqs = eng.drain(n, timeout=120)
        eng.stop()
    assert len(reqs) == n
    assert sorted(r.rid for r in reqs) == list(range(n))
    for r in reqs:
        assert r.done and r.output
        assert r.t_gen_start >= r.t_ret_end - 1e-6
    assert gen.free_slots == gen.num_slots       # every lease returned
