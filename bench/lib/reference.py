"""The plain reference the benchmark judges the served path against:
what every model family shares.

Nothing here, nor in a family file, imports the program.  The reference
rebuilds, from the seeds alone, what the program was given:

* the corpus and the query pool (Gaussian blobs on the unit sphere, the
  same recipe as the program's synthetic generator) and the passages'
  text (``Passages``), from the mix's corpus seed;
* the prompt tokens (blake2b word hash, bos 1, pad 0, padded to
  ``ctx_len``);
* the model's weights and forward, from its family file.

A configuration names its family (``"reference"``), and
``spec.reference`` loads ``bench/references/<family>.py``.  Every family
file gives, for the configuration's ``model`` section ``m``:

* ``scales(m, seed)``: the norm scales and biases the benchmark seeds
  from the run's seed and writes into the served weights, made on the
  device in one call.  The tree mirrors the served parameters:
  ``{"top": {...}, "layers": [{...}, ...]}``, each leaf at the path of
  the served leaf it replaces; a path the served tree lacks is an error;
* ``top_weights(m, seed, sc)``: a dict with ``embed`` (vocab, d) and
  whatever ``head_fn`` reads; ``layer_weights(m, seed, i, sc)``: layer
  ``i``'s weights, both bf16 as served, matrices from the program's key
  schedule for ``seed`` and scales from ``sc``;
* ``layer_fn(m, i, precision)``: layer ``i``'s float32 forward over a
  batch of sequences, ``(weights, x (B, S, d)) -> x``, causal; it may
  differ by layer (a dense prefix) and should return one compiled
  function for layers alike; ``head_fn(m, precision)``: ``(top, x (N,
  d)) -> logits (N, vocab)``.  ``precision="fp8"`` rounds every matmul
  input to float8 e4m3 (``_fp8``): the control, the step below the
  configuration's bf16;
* ``decode_flops(m, kv_len)`` and ``prefill_flops(m, seq)``: the model
  operations of one decoded token and one prefill, read by ``step_mfu``.

``retrieval_gap`` scores every query against the whole corpus in float64
and measures how far the served top-k lies below the exact top-k.
``token_gap`` runs the family's float32 forward over each prompt and its
served tokens, layer by layer under
``default_matmul_precision("highest")``, and measures by how much a
served token's logit lies below the best logit at its position.
"""
from __future__ import annotations

import hashlib
import math
from types import ModuleType
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
# weights and forward: what the family files share
# ---------------------------------------------------------------------------


def _dense(key, shape, fan_in):
    """A weight matrix as the program makes it: a truncated normal over
    [-2, 2] scaled by ``1/sqrt(fan_in)``, in bf16."""
    import jax
    import jax.numpy as jnp
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (w * (1.0 / math.sqrt(max(fan_in, 1)))).astype(jnp.bfloat16)


def _fp8(x, axis=None):
    """Round to float8 e4m3 with an amax scale (per tensor, or per slice
    along ``axis``) and return float32."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def logits_at(fam: ModuleType, m: Dict, seeds: Tuple[int, int],
              seqs: List[np.ndarray], reads: List[Tuple[int, int]],
              precision: str = "f32", length: int = 0, read_len: int = 0,
              block: int = 4) -> List[np.ndarray]:
    """Float32 logits of each sequence at positions ``[lo, hi)``.

    ``seqs`` are token arrays; ``reads[i] = (lo, hi)``.  The forward runs
    layer by layer over blocks of ``block`` sequences, each padded at the
    end to ``length`` (causal, so padding changes nothing before it),
    with each layer's weights made on the device by the family module
    ``fam`` from ``seeds``: the seed of the weight matrices and that of
    the ``scales``.  Fixed ``length`` and ``read_len`` keep one compiled
    shape per cell.
    """
    import jax
    import jax.numpy as jnp
    seed, scale_seed = seeds
    sc = fam.scales(m, scale_seed)
    top = fam.top_weights(m, seed, sc)
    length = max([length] + [len(s) for s in seqs])
    read_len = max([read_len] + [hi - lo for lo, hi in reads])
    n = -(-len(seqs) // block) * block
    toks = np.zeros((n, length), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        head = fam.head_fn(m, precision)
        xs = [jnp.take(top["embed"], jnp.asarray(toks[lo:lo + block]),
                       axis=0).astype(jnp.float32)
              for lo in range(0, n, block)]
        for i in range(m["num_layers"]):
            layer = fam.layer_fn(m, i, precision)
            p = fam.layer_weights(m, seed, i, sc)
            xs = [layer(p, x) for x in xs]
            del p
        x = jnp.concatenate(xs)
        out = []
        for i, (lo, hi) in enumerate(reads):
            lg = head(top, jax.lax.dynamic_slice_in_dim(x[i], lo, read_len))
            out.append(np.asarray(lg)[:hi - lo])
    return out


def token_gap(fam: ModuleType, m: Dict, seeds: Tuple[int, int],
              prompts: List[np.ndarray], served: List[List[int]],
              precision: str = "f32", max_new: int = 0) -> float:
    """Widest gap by which a served token's logit lies below the best
    float32 reference logit at its position.  With ``precision="fp8"``
    the served tokens are replaced by the tokens an fp8 forward puts
    first at the same positions of the same sequences (the control)."""
    seqs, reads = [], []
    for p, toks in zip(prompts, served):
        seqs.append(np.concatenate([p, np.asarray(toks[:-1], np.int32)]))
        reads.append((len(p) - 1, len(p) - 1 + len(toks)))
    length = max(len(p) for p in prompts) + max_new
    ref = logits_at(fam, m, seeds, seqs, reads, "f32", length, max_new)
    if precision != "f32":
        low = logits_at(fam, m, seeds, seqs, reads, precision, length,
                        max_new)
        served = [list(np.argmax(lg, axis=-1)) for lg in low]
    worst = 0.0
    for lg, toks in zip(ref, served):
        toks = np.asarray(toks)
        if toks.min() < 0 or toks.max() >= lg.shape[-1]:
            return math.inf
        chosen = lg[np.arange(len(toks)), toks]
        worst = max(worst, float(np.max(lg.max(axis=-1) - chosen)))
    return worst
