"""The dense attention family: GQA, optional QKV bias, rotary over a
fraction of the head dims, SwiGLU MLP, RMSNorm before each (mistral,
chatglm3, llama).  The interface every family file gives is set out in
``bench/lib/reference.py``.

Nothing here imports the program.  Weight matrices follow its key
schedule: top and per-layer keys split from ``PRNGKey(seed)``, a layer's
key folded with its index and split 4 ways (attention, MLP), truncated
normals scaled by ``1/sqrt(fan_in)``, the embedding by 0.02.

Operations are counted as every weight matmul (2 operations per
multiply-add) and the attention scores and mixing over the positions
each token attends to.  A prefill unembeds only its last position, as
the served path does.  Norms, rotary and softmax are left out.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict

import numpy as np

from lib.reference import _dense, _fp8

# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def scales(m: Dict, seed: int) -> Dict:
    """Norm scales, uniform over [0.5, 1.5], and QKV biases (where the
    model has them), normal with deviation 0.5, made from ``seed`` on the
    device in one call, in bf16: ``top`` holds ``final_norm``, each of
    ``layers`` its ``norm1``, ``norm2`` and ``attn``'s ``bq``, ``bk``,
    ``bv``."""
    import jax
    import jax.numpy as jnp
    d, h, kv = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // h
    n = m["num_layers"]
    key = jax.random.PRNGKey(int(np.random.default_rng([seed, 5]).integers(
        1 << 31)))

    def make(key):
        ks = jax.random.split(key, 6)

        def norm(k, shape):
            return jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5
                                      ).astype(jnp.bfloat16)

        def bias(k, shape):
            return (0.5 * jax.random.normal(k, shape, jnp.float32)
                    ).astype(jnp.bfloat16)
        norm1, norm2 = norm(ks[1], (n, d)), norm(ks[2], (n, d))
        layers = [{"norm1": norm1[i], "norm2": norm2[i]} for i in range(n)]
        if m.get("qkv_bias"):
            bq = bias(ks[3], (n, h, hd))
            bk = bias(ks[4], (n, kv, hd))
            bv = bias(ks[5], (n, kv, hd))
            for i, lp in enumerate(layers):
                lp["attn"] = {"bq": bq[i], "bk": bk[i], "bv": bv[i]}
        return {"top": {"final_norm": norm(ks[0], (d,))}, "layers": layers}
    return jax.jit(make)(key)


def top_weights(m: Dict, seed: int, sc: Dict):
    """Embedding, final norm and unembedding, bf16 as served: matrices
    from ``seed``, the norm scale from ``sc``."""
    import jax
    import jax.numpy as jnp
    d, v = m["d_model"], m["vocab_size"]
    k_top, _ = jax.random.split(jax.random.PRNGKey(seed))
    k_embed, k_head = jax.random.split(k_top)
    embed = (jax.random.truncated_normal(k_embed, -2.0, 2.0, (v, d),
                                         jnp.float32) * 0.02
             ).astype(jnp.bfloat16)
    out = {"embed": embed, "final_norm": sc["top"]["final_norm"]}
    out["lm_head"] = (embed.T if m.get("tie_embeddings", True)
                      else _dense(k_head, (d, v), d))
    return out


def layer_weights(m: Dict, seed: int, i: int, sc: Dict):
    """Layer ``i``'s weights, bf16 as served: matrices from ``seed``,
    norm scales and biases from ``sc``."""
    import jax
    d, h, kv = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // h
    ff = m["d_ff"]
    _, k_layers = jax.random.split(jax.random.PRNGKey(seed))
    ks = jax.random.split(jax.random.fold_in(k_layers, i), 4)
    ka = jax.random.split(ks[0], 6)
    kf = jax.random.split(ks[1], 3)
    s = sc["layers"][i]
    p = {"norm1": s["norm1"], "norm2": s["norm2"],
         "wq": _dense(ka[0], (d, h, hd), d),
         "wk": _dense(ka[1], (d, kv, hd), d),
         "wv": _dense(ka[2], (d, kv, hd), d),
         "wo": _dense(ka[3], (h, hd, d), h * hd),
         "w_gate": _dense(kf[0], (d, ff), d),
         "w_up": _dense(kf[1], (d, ff), d),
         "w_down": _dense(kf[2], (ff, d), ff)}
    if m.get("qkv_bias"):
        p.update(s["attn"])
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def layer_fn(m: Dict, i: int, precision: str):
    """Layer ``i``'s forward; every layer of this family runs one
    compiled function."""
    return _layer_fn(json.dumps(m, sort_keys=True), precision)


@functools.lru_cache(maxsize=None)
def _layer_fn(m_json: str, precision: str):
    import jax
    import jax.numpy as jnp
    m = json.loads(m_json)
    d, h, kv = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // h
    rot = int(hd * m.get("rope_fraction", 1.0))
    eps = m.get("norm_eps", 1e-5)
    theta = m.get("rope_theta", 10000.0)
    g = h // kv

    def w(x):
        x = x.astype(jnp.float32)
        return _fp8(x) if precision == "fp8" else x

    def a(x):
        return _fp8(x, axis=-1) if precision == "fp8" else x

    def norm(x, wt):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return x * wt.astype(jnp.float32)

    def rope(x, pos):
        # rotate-half pairs (i, i + rot/2) over the first ``rot`` dims
        freqs = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                                / rot)
        ang = pos.astype(jnp.float32)[:, None] * freqs       # (S, rot/2)
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                                x[..., rot:]], axis=-1)

    def one(p, x):                                          # x (S, D)
        s = x.shape[0]
        pos = jnp.arange(s)
        hh = a(norm(x, p["norm1"]))
        q = jnp.einsum("sd,dhk->shk", hh, w(p["wq"]))
        k = jnp.einsum("sd,dhk->shk", hh, w(p["wk"]))
        v = jnp.einsum("sd,dhk->shk", hh, w(p["wv"]))
        if "bq" in p:
            q = q + p["bq"].astype(jnp.float32)
            k = k + p["bk"].astype(jnp.float32)
            v = v + p["bv"].astype(jnp.float32)
        q, k = rope(q, pos), rope(k, pos)
        q = q.reshape(s, kv, g, hd)
        sc = jnp.einsum("skgd,tkd->kgst", a(q), a(k)) / math.sqrt(hd)
        mask = pos[:, None] >= pos[None, :]
        sc = jnp.where(mask, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", a(pr), a(v)).reshape(s, h, hd)
        x = x + jnp.einsum("shk,hkd->sd", a(o), w(p["wo"]))
        hh = a(norm(x, p["norm2"]))
        gate = jax.nn.silu(hh @ w(p["w_gate"]))
        up = hh @ w(p["w_up"])
        return x + a(gate * up) @ w(p["w_down"])

    return jax.jit(jax.vmap(one, in_axes=(None, 0)))


def head_fn(m: Dict, precision: str):
    import jax
    import jax.numpy as jnp
    eps = m.get("norm_eps", 1e-5)

    def head(top, x):                                       # x (N, D)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        x = x * top["final_norm"].astype(jnp.float32)
        wt = top["lm_head"].astype(jnp.float32)
        if precision == "fp8":
            x, wt = _fp8(x, axis=-1), _fp8(wt)
        return x @ wt

    return jax.jit(head)


# ---------------------------------------------------------------------------
# operations
# ---------------------------------------------------------------------------


def _dims(m: Dict):
    d, h, kv = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // h
    return d, h, kv, hd


def layer_params(m: Dict) -> int:
    """Matmul weights of one layer."""
    d, h, kv, hd = _dims(m)
    return d * h * hd * 2 + 2 * d * kv * hd + 3 * d * m["d_ff"]


def decode_flops(m: Dict, kv_len: int) -> float:
    """One decoded token attending over ``kv_len`` positions."""
    d, h, _, hd = _dims(m)
    per_layer = 2.0 * layer_params(m) + 4.0 * h * hd * kv_len
    return m["num_layers"] * per_layer + 2.0 * d * m["vocab_size"]


def prefill_flops(m: Dict, seq: int) -> float:
    """One causal prefill of ``seq`` positions."""
    d, h, _, hd = _dims(m)
    per_layer = (2.0 * layer_params(m) * seq
                 + 4.0 * h * hd * seq * (seq + 1) / 2)
    return m["num_layers"] * per_layer + 2.0 * d * m["vocab_size"]
