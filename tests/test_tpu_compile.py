"""Compile the main-path Pallas kernels for a TPU v5e at llama3-8b widths.

No chip is needed: the v5e topology is described, not attached, and each
kernel is lowered and compiled for one of its devices from shapes alone.
The compiler refuses what interpret mode accepts (unaligned blocks, SMEM
overflow), so these guard the chip path on every run.  The topology is
described inside a module-scoped fixture, never at import, so every
test worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import paged_attention as pa
from repro.kernels import rmsnorm as rk
from repro.kernels import topk_retrieval as tk

# llama3-8b attention widths, one page of 16 bf16 tokens
H, KV, D, PAGE = 32, 8, 128, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("fmt,pages", [("bf16", 40000), ("int8", 8192),
                                       ("int8", 40000)])
def test_paged_decode_compiles(spec, fmt, pages):
    b, nmax = 8, 66
    dt = jnp.bfloat16 if fmt == "bf16" else jnp.int8
    args = [spec((b, H, D), jnp.bfloat16), spec((pages, PAGE, KV, D), dt),
            spec((pages, PAGE, KV, D), dt), spec((b, nmax), jnp.int32),
            spec((b,), jnp.int32)]
    if fmt == "int8":
        scale = spec((pages, KV), jnp.float32)
        _compile(lambda q, k, v, t, kl, ks, vs:
                 pa.paged_decode_attention_pallas(q, k, v, t, kl,
                                                  k_scale=ks, v_scale=vs),
                 *args, scale, scale)
    else:
        _compile(pa.paged_decode_attention_pallas, *args)


@pytest.mark.parametrize("batch", [1, 4])
def test_flash_prefill_compiles(spec, batch):
    s = 1024
    _compile(lambda q, k, v, kl: fa.flash_attention_pallas(q, k, v,
                                                           kv_len=kl),
             spec((batch, s, H, D), jnp.bfloat16),
             spec((batch, s, KV, D), jnp.bfloat16),
             spec((batch, s, KV, D), jnp.bfloat16),
             spec((batch,), jnp.int32))


def test_decode_attention_compiles(spec):
    b, s = 8, 2048
    _compile(da.decode_attention_pallas, spec((b, H, D), jnp.bfloat16),
             spec((b, s, KV, D), jnp.bfloat16),
             spec((b, s, KV, D), jnp.bfloat16), spec((b,), jnp.int32))


@pytest.mark.parametrize("qn,n", [(16, 4100), (3, 5000)])
def test_topk_compiles(spec, qn, n):
    # block_n as ``ops.retrieval_topk`` passes it
    _compile(lambda q, db: tk.topk_pallas(q, db, 8, block_n=4096),
             spec((qn, 768), jnp.float32), spec((n, 768), jnp.float32))


def test_topk_merge_compiles(spec):
    _compile(lambda s, i, m: tk.topk_merge_pallas(s, i, m, 8),
             spec((16, 32, 8), jnp.float32), spec((16, 32, 8), jnp.int32),
             spec((16, 32), jnp.bool_))


def test_rmsnorm_compiles(spec):
    _compile(rk.rmsnorm_pallas, spec((1024, 4096), jnp.bfloat16),
             spec((4096,), jnp.bfloat16))


def test_served_layer_programs_compile(topo, monkeypatch):
    """The served path's per-layer prefill and decode programs and its
    unembed, at llama3-8b widths with the Pallas kernels, through the
    memory plan that sizes them (``ops`` is steered to its TPU tier
    here because the described chip is not the default backend)."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.serve import plan_memory

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    plan = plan_memory(get_config("llama3-8b"), device=topo.devices[0],
                       limit_bytes=16_909_336_064, headroom=0.92,
                       num_slots=8, ctx_len=1024, max_new_tokens=32,
                       page_size=PAGE, retrieval_bytes=131072 * 768 * 4)
    assert 0 < plan.resident_layers < 32
    assert plan.kv_pages == 8 * 66
