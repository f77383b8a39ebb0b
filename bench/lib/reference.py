"""The plain reference the benchmark judges the served path against.

Nothing here imports the program.  The reference rebuilds, from the
seeds alone, what the program was given:

* the corpus and the query pool (Gaussian blobs on the unit sphere, the
  same recipe as the program's synthetic generator) and the passages'
  text (``Passages``), from the mix's corpus seed;
* the bf16 weight matrices (the same key schedule: top and per-layer keys
  split from ``PRNGKey(corpus seed)``, truncated normals scaled by
  ``1/sqrt(fan_in)``, embeddings by 0.02);
* the norm scales and QKV biases, from the run's seed (``scales``; the
  benchmark writes the same values into the served weights);
* the prompt tokens (blake2b word hash, bos 1, pad 0, padded to
  ``ctx_len``).

``retrieval_gap`` scores every query against the whole corpus in float64
and measures how far the served top-k lies below the exact top-k.
``token_gap`` runs a float32 forward of the dense attention family
(GQA, QKV bias, partial rotary) over each prompt and its served tokens,
layer by layer under ``default_matmul_precision("highest")``, and
measures by how much a served token's logit lies below the best logit
at its position.  ``precision="fp8"`` computes the same forward with
every matmul input rounded to float8 e4m3 (per-tensor scale for weights,
per-row for activations): that is the control, the step below the
configuration's bf16.
"""
from __future__ import annotations

import hashlib
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# inputs: corpus, queries, prompt tokens
# ---------------------------------------------------------------------------


def blob_corpus(n: int, dim: int, clusters: int, seed: int,
                spread: float = 0.35) -> np.ndarray:
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(clusters, dim))
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    v = centers[rng.integers(0, clusters, size=n)]
    v = v + (spread / np.sqrt(dim)) * rng.normal(size=(n, dim))
    return (v / np.linalg.norm(v, axis=1, keepdims=True)).astype(np.float32)


def perturbed_queries(vecs: np.ndarray, n: int, seed: int,
                      spread: float = 0.2) -> np.ndarray:
    rng = np.random.default_rng(seed)
    dim = vecs.shape[1]
    base = vecs[rng.integers(0, len(vecs), size=n)]
    q = base + (spread / np.sqrt(dim)) * rng.normal(size=base.shape)
    return (q / np.linalg.norm(q, axis=1, keepdims=True)).astype(np.float32)


class Passages:
    """The text of every chunk of the store, made from ``seed`` when it
    is read: chunk ``i`` is its id followed by ``words - 1`` words drawn
    from a vocabulary of 2**20 word types."""

    def __init__(self, n: int, seed: int, words: int):
        self.n, self.seed, self.words = n, seed, words

    def __len__(self) -> int:
        return self.n

    def __getitem__(self, i: int) -> str:
        i = int(i)
        if not 0 <= i < self.n:
            raise IndexError(i)
        rng = np.random.default_rng([self.seed, i])
        body = rng.integers(0, 1 << 20, size=self.words - 1)
        return " ".join([str(i)] + [f"w{w}" for w in body])

    @staticmethod
    def id_of(text: str) -> int:
        return int(text.split(maxsplit=1)[0])


def tokenize(text: str, length: int, vocab: int) -> np.ndarray:
    ids = [1]
    for w in text.lower().split()[:length]:
        h = int.from_bytes(
            hashlib.blake2b(w.encode(), digest_size=4).digest(), "little")
        ids.append(h % (vocab - 2) + 2)
    ids = ids[:length]
    return np.asarray(ids + [0] * (length - len(ids)), np.int32)


# ---------------------------------------------------------------------------
# retrieval
# ---------------------------------------------------------------------------


def retrieval_gap(corpus: np.ndarray, queries: np.ndarray,
                  served_ids: Sequence[Sequence[int]], k: int,
                  lowp: bool = False) -> float:
    """Widest gap, over queries and ranks, between the exact top-k score
    and the served one (served ids re-scored in float64, both sorted).
    A served list shorter than ``k`` or an id out of range reads inf.
    ``lowp`` instead serves the top-k of bfloat16 scores (the control)."""
    c64 = corpus.astype(np.float64)
    if lowp:
        import ml_dtypes
        bf16 = ml_dtypes.bfloat16
        cb = corpus.astype(bf16).astype(np.float32)
    worst = 0.0
    for lo in range(0, len(queries), 256):
        q = queries[lo:lo + 256]
        s = q.astype(np.float64) @ c64.T
        best = -np.sort(np.partition(-s, k - 1, axis=1)[:, :k], axis=1)
        if lowp:
            qb = q.astype(bf16).astype(np.float32)
            sb = (qb @ cb.T).astype(bf16).astype(np.float32)
            ids = np.argpartition(-sb, k - 1, axis=1)[:, :k]
        else:
            ids = served_ids[lo:lo + 256]
        for row, sid, b in zip(s, ids, best):
            sid = [int(i) for i in sid]
            if len(sid) != k or min(sid) < 0 or max(sid) >= len(corpus):
                return math.inf
            got = -np.sort(-row[sid])
            worst = max(worst, float(np.max(b - got)))
    return worst


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def _dense(key, shape, fan_in):
    import jax
    import jax.numpy as jnp
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (w * (1.0 / math.sqrt(max(fan_in, 1)))).astype(jnp.bfloat16)


def scales(m: Dict, seed: int) -> Dict:
    """Norm scales, uniform over [0.5, 1.5], and QKV biases (where the
    model has them), normal with deviation 0.5, made from ``seed`` on the
    device in one call, in bf16: ``final_norm`` and per layer lists
    ``norm1``, ``norm2``, ``bq``, ``bk``, ``bv``."""
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
        out = {"final_norm": norm(ks[0], (d,)),
               "norm1": list(norm(ks[1], (n, d))),
               "norm2": list(norm(ks[2], (n, d)))}
        if m.get("qkv_bias"):
            out["bq"] = list(bias(ks[3], (n, h, hd)))
            out["bk"] = list(bias(ks[4], (n, kv, hd)))
            out["bv"] = list(bias(ks[5], (n, kv, hd)))
        return out
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
    out = {"embed": embed, "final_norm": sc["final_norm"]}
    out["lm_head"] = (embed.T if m.get("tie_embeddings", True)
                      else _dense(k_head, (d, v), d))
    return out


def layer_weights(m: Dict, seed: int, i: int, sc: Dict):
    """Layer ``i``'s weights, bf16 as served: matrices from ``seed``,
    norm scales and biases from ``sc``."""
    import jax
    import jax.numpy as jnp
    d, h, kv = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // h
    ff = m["d_ff"]
    _, k_layers = jax.random.split(jax.random.PRNGKey(seed))
    ks = jax.random.split(jax.random.fold_in(k_layers, i), 4)
    ka = jax.random.split(ks[0], 6)
    kf = jax.random.split(ks[1], 3)
    p = {"norm1": sc["norm1"][i], "norm2": sc["norm2"][i],
         "wq": _dense(ka[0], (d, h, hd), d),
         "wk": _dense(ka[1], (d, kv, hd), d),
         "wv": _dense(ka[2], (d, kv, hd), d),
         "wo": _dense(ka[3], (h, hd, d), h * hd),
         "w_gate": _dense(kf[0], (d, ff), d),
         "w_up": _dense(kf[1], (d, ff), d),
         "w_down": _dense(kf[2], (ff, d), ff)}
    if m.get("qkv_bias"):
        p.update(bq=sc["bq"][i], bk=sc["bk"][i], bv=sc["bv"][i])
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fp8(x, axis=None):
    """Round to float8 e4m3 with an amax scale (per tensor, or per slice
    along ``axis``) and return float32."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _layer_fn(m: Dict, precision: str):
    import jax
    import jax.numpy as jnp
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


def _head_fn(m: Dict, precision: str):
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


def logits_at(m: Dict, seeds: Tuple[int, int], seqs: List[np.ndarray],
              reads: List[Tuple[int, int]], precision: str = "f32",
              length: int = 0, read_len: int = 0, block: int = 4
              ) -> List[np.ndarray]:
    """Float32 logits of each sequence at positions ``[lo, hi)``.

    ``seqs`` are token arrays; ``reads[i] = (lo, hi)``.  The forward runs
    layer by layer over blocks of ``block`` sequences, each padded at the
    end to ``length`` (causal, so padding changes nothing before it),
    with each layer's weights made on the device from ``seeds``: the
    seed of the weight matrices and that of the ``scales``.  Fixed
    ``length`` and ``read_len`` keep one compiled shape per cell.
    """
    import jax
    import jax.numpy as jnp
    seed, scale_seed = seeds
    sc = scales(m, scale_seed)
    top = top_weights(m, seed, sc)
    length = max([length] + [len(s) for s in seqs])
    read_len = max([read_len] + [hi - lo for lo, hi in reads])
    n = -(-len(seqs) // block) * block
    toks = np.zeros((n, length), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        layer = _layer_fn(m, precision)
        head = _head_fn(m, precision)
        xs = [jnp.take(top["embed"], jnp.asarray(toks[lo:lo + block]),
                       axis=0).astype(jnp.float32)
              for lo in range(0, n, block)]
        for i in range(m["num_layers"]):
            p = layer_weights(m, seed, i, sc)
            xs = [layer(p, x) for x in xs]
            del p
        x = jnp.concatenate(xs)
        out = []
        for i, (lo, hi) in enumerate(reads):
            lg = head(top, jax.lax.dynamic_slice_in_dim(x[i], lo, read_len))
            out.append(np.asarray(lg)[:hi - lo])
    return out


def token_gap(m: Dict, seeds: Tuple[int, int], prompts: List[np.ndarray],
              served: List[List[int]], precision: str = "f32",
              max_new: int = 0) -> float:
    """Widest gap by which a served token's logit lies below the best
    float32 reference logit at its position.  With ``precision="fp8"``
    the served tokens are replaced by the tokens an fp8 forward puts
    first at the same positions of the same sequences (the control)."""
    seqs, reads = [], []
    for p, toks in zip(prompts, served):
        seqs.append(np.concatenate([p, np.asarray(toks[:-1], np.int32)]))
        reads.append((len(p) - 1, len(p) - 1 + len(toks)))
    length = max(len(p) for p in prompts) + max_new
    ref = logits_at(m, seeds, seqs, reads, "f32", length, max_new)
    if precision != "f32":
        low = logits_at(m, seeds, seqs, reads, precision, length, max_new)
        served = [list(np.argmax(lg, axis=-1)) for lg in low]
    worst = 0.0
    for lg, toks in zip(ref, served):
        toks = np.asarray(toks)
        if toks.min() < 0 or toks.max() >= lg.shape[-1]:
            return math.inf
        chosen = lg[np.arange(len(toks)), toks]
        worst = max(worst, float(np.max(lg.max(axis=-1) - chosen)))
    return worst
