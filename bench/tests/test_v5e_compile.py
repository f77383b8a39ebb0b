"""Compile each cell's per-layer prefill program (the one a served join
runs) and decode program, with its flash prefill and paged decode
kernels, for a described TPU v5e, at the cell's own sizes.

No chip is needed: the topology is described, not attached, and each
program is compiled from shapes.  The program picks its Pallas kernels
when JAX's default backend is a TPU; the test steers that choice to the
TPU path while it lowers.  The topology is described inside a fixture,
never at import.

    PYTHONPATH=src python -m pytest bench/tests/test_v5e_compile.py
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from lib import spec as S

CELLS = ["chatglm3-6b.rag_single", "mistral-7b-v0.3.rag_backlog"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_kernels(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _shapes(name):
    from run import model_config
    cell = S.cell(name)
    conf, mix = S.config(cell["config"]), S.traffic(cell["traffic"])
    return model_config(conf["model"]), mix


def _sds(tree, sharding):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("name", CELLS)
def test_layer_programs_compile(name, one_chip, tpu_kernels):
    from repro.core.prefetch import (layer_param_shapes, layer_program,
                                     prefill_programs)
    from repro.models.model import _layer_cache_spec
    cfg, mix = _shapes(name)
    slots, ctx, page = mix["slots"], mix["ctx_len"], mix["page_size"]
    total = ctx + mix["answer"]["max"]
    nmax = -(-total // page)
    kind = cfg.layer_pattern[0]
    lp = _sds(layer_param_shapes(cfg, jnp.bfloat16), one_chip)
    pool = _sds(_layer_cache_spec(cfg, kind[0], slots * nmax + 1, page,
                                  jnp.bfloat16, None), one_chip)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pre = prefill_programs(cfg, kind, jnp.bfloat16)[1].lower(
        lp, arr((1, ctx, cfg.d_model), jnp.bfloat16)).compile()
    dec = layer_program(cfg, kind, "decode", total).lower(
        lp, arr((slots, 1, cfg.d_model), jnp.bfloat16), pool,
        arr((slots,), jnp.int32), arr((slots, nmax), jnp.int32)).compile()
    for c in (pre, dec):
        assert "tpu_custom_call" in c.as_text()
        ma = c.memory_analysis()
        assert ma.temp_size_in_bytes < 4 * 2 ** 30
