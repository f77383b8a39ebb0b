"""The served-path builder, its memory plan, the device profile table and
the compile-cache rule, on the CPU at a tiny size."""
import os
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.costmodel import (TPU_V5E_HOST, CostModel, ModelProfile,
                                  profile_for_device)
from repro.core.placement import PlacementOptimizer
from repro.kernels import ops
from repro.launch.compile_cache import CHECKOUT_CACHE
from repro.launch.serve import build_served, plan_memory
from repro.serving.request import Request

TINY = dict(num_slots=4, ctx_len=32, max_new_tokens=6, page_size=8,
            corpus_size=2048, dim=64, partitions=8, num_queries=6,
            hw=TPU_V5E_HOST, log=lambda _msg: None)


def _plan(cfg, limit):
    return plan_memory(cfg, device=jax.devices()[0], limit_bytes=limit,
                       headroom=TPU_V5E_HOST.mem_headroom, num_slots=4,
                       ctx_len=32, max_new_tokens=6, page_size=8,
                       retrieval_bytes=2048 * 64 * 4)


def test_plan_fits_and_streams_what_does_not_fit():
    cfg = get_config("llama3-8b").reduced(num_layers=6)
    roomy = _plan(cfg, 10 ** 8)
    assert roomy.resident_layers == 6 and roomy.streamed_layers == 0
    tight = _plan(cfg, 1_800_000)
    assert 0 < tight.streamed_layers < 6
    used = (tight.top_bytes + tight.kv_pool_bytes
            + tight.copy_headroom_bytes + tight.workspace_bytes
            + tight.retrieval_bytes
            + tight.resident_layers * tight.layer_bytes)
    # one layer computing + the prefetch queue fit in what is left
    assert used + tight.layer_bytes + tight.free_bytes <= tight.usable_bytes
    assert tight.free_bytes >= 2 * tight.layer_bytes
    with pytest.raises(ValueError):
        _plan(cfg, 500_000)


def test_build_served_streams_from_host_and_serves():
    cfg = get_config("llama3-8b").reduced(num_layers=6)
    with tempfile.TemporaryDirectory() as root:
        served = build_served(cfg, spill_root=root,
                              limit_bytes=1_800_000, **TINY)
        ex = served.generator.exec
        assert 0 < ex.resident < ex.n_layers
        for i, (_, lp) in enumerate(ex.layers):
            kinds = {leaf.sharding.memory_kind
                     for leaf in jax.tree.leaves(lp)}
            assert kinds == ({"device"} if i < ex.resident
                             else {"pinned_host"})
        assert len(served.store.resident_set()) == 4     # half spilled
        eng = served.engine
        eng.start()
        try:
            for i, q in enumerate(served.queries):
                eng.submit(Request(rid=i, query=q,
                                   arrival=time.perf_counter()))
            reqs = eng.drain(len(served.queries), timeout=300)
        finally:
            eng.stop()
    assert sorted(r.rid for r in reqs) == list(range(len(served.queries)))
    for r in reqs:
        ids = [int(c) for c in r.retrieved]
        assert len(ids) == r.top_k
        assert all(0 <= i < served.corpus_size for i in ids)
        assert len(r.output.split()) == 6
    assert served.generator.nonfinite_batches == 0


def test_profile_table_refuses_unknown_devices():
    assert profile_for_device("TPU v5 lite") is TPU_V5E_HOST
    with pytest.raises(KeyError, match="cpu"):
        profile_for_device("cpu")


def test_optimizer_keeps_the_executors_weight_split():
    cfg = get_config("llama3-8b")
    mp = ModelProfile.from_config(cfg, kv_format="bf16")
    cost = CostModel(TPU_V5E_HOST, mp, partition_bytes=12 * 2 ** 20,
                     num_partitions=32)
    opt = PlacementOptimizer(cost, avg_ctx_len=1024, avg_out_len=32,
                             weight_split=(0.6, 0.4))
    for b in (1, 8):
        p = opt.solve(b)
        assert (p.w_gpu, p.w_cpu) == (0.6, 0.4)
        # with the weights pinned, the KV of b requests fits on device
        split = opt.market(p, page_size=16, kv_format="bf16")
        assert split.kv_page_budget >= b * 66


def test_dispatch_is_counted():
    before = ops.dispatch_counts().get("rmsnorm", {}).get("jnp", 0)
    ops.rmsnorm(jnp.ones((2, 8)), jnp.ones((8,)))
    assert ops.dispatch_counts()["rmsnorm"]["jnp"] == before + 1


_PROBE = """
import os, sys
sys.path.insert(0, {src!r})
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
import jax, jax.numpy as jnp
jax.jit(lambda x: jnp.tanh(x) @ x.T + {salt})(jnp.ones((32, 32))).block_until_ready()
"""


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_goes_where_the_rule_says(tmp_path, env_dir):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    before = (set(os.listdir(CHECKOUT_CACHE)) if CHECKOUT_CACHE.exists()
              else set())
    salt = time.time_ns() % 10 ** 9  # a program no earlier run cached
    out = subprocess.run(
        [sys.executable, "-c", _PROBE.format(src=src, salt=salt)],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    used = out.stdout.strip().splitlines()[0]
    after = (set(os.listdir(CHECKOUT_CACHE)) if CHECKOUT_CACHE.exists()
             else set())
    if env_dir:
        assert used == str(tmp_path / "cache")
        # the salted program's entry is there and not in the checkout
        mine = {e for e in os.listdir(used) if e.startswith("jit__lambda")}
        assert mine and not mine & after
    else:
        assert used == str(CHECKOUT_CACHE)
        assert after - before
