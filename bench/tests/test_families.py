"""A configuration of any family goes through the harness with new files
only:

* ``run.model_config`` builds the program's configs of other families
  (latent attention and experts; state-space, attention and experts)
  from their JSON form, and refuses a section it does not know;
* a family file found by name is what ``check`` and ``step_mfu`` call;
* ``set_scales`` writes a family's seeded leaves at paths it has never
  heard of, and refuses a path the served weights lack.
"""
import dataclasses
import importlib
import json
from types import SimpleNamespace

import numpy as np
import pytest

import tiny
import run as B
from lib import layer
from lib import spec


def _as_json(cfg):
    return json.loads(json.dumps(dataclasses.asdict(cfg)))


@pytest.mark.parametrize("module", ["deepseek_v2_lite_16b",
                                    "jamba_1_5_large_398b"])
def test_model_config_round_trips(module):
    cfg = importlib.import_module(f"repro.configs.{module}").CONFIG
    assert B.model_config(_as_json(cfg)) == cfg


def test_model_config_refuses_an_unknown_section():
    m = dict(spec.config("mistral-7b-v0.3")["model"],
             rotary={"kind": "yarn", "factor": 40})
    with pytest.raises(ValueError, match="rotary"):
        B.model_config(m)


STUB = '''
"""A stand-in family: every layer the identity, logits that put token 7
first by 1.0; each call is logged to a file beside this one."""
from pathlib import Path

LOG = Path(__file__).with_suffix(".calls")


def _log(*what):
    with open(LOG, "a") as f:
        f.write(" ".join(map(str, what)) + "\\n")


def scales(m, seed):
    _log("scales", seed)
    return {"top": {}, "layers": [{} for _ in range(m["num_layers"])]}


def top_weights(m, seed, sc):
    import jax.numpy as jnp
    _log("top_weights", seed)
    return {"embed": jnp.ones((m["vocab_size"], m["d_model"]))}


def layer_weights(m, seed, i, sc):
    _log("layer_weights", i)
    return {}


def layer_fn(m, i, precision):
    _log("layer_fn", i, precision)
    return lambda p, x: x


def head_fn(m, precision):
    import jax.numpy as jnp
    _log("head_fn", precision)
    return lambda top, x: jnp.zeros((x.shape[0], m["vocab_size"])
                                    ).at[:, 7].set(1.0)


def decode_flops(m, kv_len):
    return 10.0 + kv_len


def prefill_flops(m, seq):
    return 100.0 * seq
'''


def test_a_family_file_is_found_and_called(tmp_path, monkeypatch):
    conf = tiny.conf("chatglm3-6b")
    mix = tiny.mix("rag_backlog")
    conf["reference"] = "stub"
    m = conf["model"]
    (tmp_path / "references").mkdir()
    (tmp_path / "references" / "stub.py").write_text(STUB)
    monkeypatch.setattr(spec, "BENCH", tmp_path)
    calls = tmp_path / "references" / "stub.calls"

    retrieved = [(0, [0, 1, 2, 3, 4])]
    sample = [(0, [0, 1, 2, 3, 4], [7, 7, 7]), (1, [5, 6, 7, 8, 9], [7, 3])]
    out = B.check(conf, mix, 11, sample, retrieved)
    # token 3 lies 1.0 below the stub's best: the stub's forward decided
    assert out["token_logit_gap"][0] == 1.0
    log = calls.read_text().split("\n")
    n = m["num_layers"]
    for i in range(n):
        assert f"layer_fn {i} f32" in log and f"layer_weights {i}" in log
    assert "scales 11" in log and "head_fn f32" in log

    fam = spec.reference("stub")
    ctx = {"model": m, "reference": fam, "peaks": {"flops_bf16": 1e3},
           "trace": {"window_s": 2.0, "decode_kv": [30, 31], "joins": 3,
                     "ctx_len": 32}}
    ops = (10 + 30) + (10 + 31) + 3 * 100 * 32
    assert layer.step_mfu(ctx) == pytest.approx(100.0 * ops / 2e3)

    del conf["reference"]
    with pytest.raises(KeyError):
        B.check(conf, mix, 11, sample, retrieved)


def _served_mla_layer():
    """The program's own weights of one tiny latent-attention layer, and
    its top, as the executor holds them."""
    import jax
    import jax.numpy as jnp
    from repro.configs.deepseek_v2_lite_16b import CONFIG
    from repro.models import transformer
    cfg = CONFIG.reduced()
    kind = ("mla", "dense")
    lp = transformer.init_layer(jax.random.PRNGKey(0), cfg, kind,
                                jnp.bfloat16)
    top = {"final_norm": jnp.ones((cfg.d_model,), jnp.bfloat16)}
    ex = SimpleNamespace(top=top, layers=[(kind, lp)])
    return cfg, SimpleNamespace(generator=SimpleNamespace(exec=ex))


def test_set_scales_writes_any_path_and_refuses_a_missing_one():
    import jax.numpy as jnp
    cfg, served = _served_mla_layer()
    ex = served.generator.exec
    before = dict(ex.layers[0][1])
    r = cfg.mla.kv_lora_rank
    norm_kv = jnp.full((r,), 1.25, jnp.bfloat16)
    B.set_scales(served, {"top": {}, "layers": [{"mla": {"norm_kv":
                                                         norm_kv}}]})
    kind, lp = ex.layers[0]
    assert kind == ("mla", "dense")
    np.testing.assert_array_equal(np.asarray(lp["mla"]["norm_kv"]),
                                  np.asarray(norm_kv))
    assert lp["mla"]["w_dkv"] is before["mla"]["w_dkv"]
    assert lp["norm1"] is before["norm1"]

    bias = jnp.zeros((cfg.num_heads, 16), jnp.bfloat16)
    with pytest.raises(KeyError, match="layers/0/attn"):
        B.set_scales(served, {"top": {}, "layers": [{"attn": {"bq": bias}}]})
    with pytest.raises(ValueError, match="layers/0/mla/norm_kv"):
        B.set_scales(served, {"top": {}, "layers": [
            {"mla": {"norm_kv": jnp.ones((r + 1,), jnp.bfloat16)}}]})
    with pytest.raises(ValueError, match="layers"):
        B.set_scales(served, {"top": {}, "layers": [{}, {}]})
