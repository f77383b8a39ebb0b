"""Mixture-of-Experts FFN: dropless sort-based dispatch + ragged grouped GEMM.

Dispatch is *local by construction*: the whole MoE block runs inside a
``shard_map`` where tokens are sharded over the batch axes and every expert's
hidden dim is tensor-sharded over ``model`` (TP-per-expert). Tokens never
cross the data axis — routing, sort, gather and the grouped GEMMs are all
shard-local, and the only collective is the same psum a dense TP FFN needs.

Rationale (recorded for §Perf): classic EP (experts sharded over ``model``,
tokens all-to-all) is also implemented (``strategy="ep"``) for comparison —
for the fine-grained-expert archs (granite F=512, deepseek F=1408) TP slices
get thin (F/16 = 32..88 columns), so EP trades two all-to-alls for full-width
GEMMs. The dry-run collective analysis quantifies this trade.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.sharding.specs import MeshContext

# TP-MoE psum precision: f32 by default; set to jnp.bfloat16 to halve the
# per-layer all-reduce bytes (hillclimb lever, EXPERIMENTS.md section Perf;
# error feedback is unnecessary because the psum is inside the forward and
# the same rounding applies in backward).
PSUM_DTYPE = None


def init_moe(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 7)
    p = {
        "router": layers.dense_init(ks[0], (d, m.num_experts), jnp.float32),
        "w_gate": layers.dense_init(ks[1], (m.num_experts, d, m.d_ff_expert),
                                    dtype, fan_in=d),
        "w_up": layers.dense_init(ks[2], (m.num_experts, d, m.d_ff_expert),
                                  dtype, fan_in=d),
        "w_down": layers.dense_init(ks[3], (m.num_experts, m.d_ff_expert, d),
                                    dtype, fan_in=m.d_ff_expert),
    }
    if m.num_shared_experts:
        f_sh = (m.d_ff_shared or m.d_ff_expert) * m.num_shared_experts
        p["shared"] = layers.init_mlp(ks[4], d, f_sh, cfg.mlp_kind, dtype)
    return p


def _route(p, x2, m):
    """x2 (T, D) -> weights (T, K), ids (T, K), probs (T, E) [f32]."""
    logits = x2.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, m.top_k)
    w = w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-9)
    return w, ids, probs


def _grouped_ffn(p, xs, gs, mlp_kind):
    """(T*K, D) tokens sorted by expert, group sizes (E,) -> (T*K, D).

    Exact dropless grouped GEMM via ``lax.ragged_dot`` — used for small
    token counts (decode) and as the oracle in tests.  NOTE: XLA's generic
    ragged_dot lowering materializes an (E, T*K, F) dense intermediate, so
    for large T the capacity path below is used instead.
    """
    g = jax.lax.ragged_dot(xs, p["w_gate"], gs,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(xs, p["w_up"], gs,
                           preferred_element_type=jnp.float32)
    act = jax.nn.silu(g) if mlp_kind == "swiglu" else \
        jax.nn.gelu(g, approximate=True)
    h = (act * u).astype(xs.dtype)
    return jax.lax.ragged_dot(h, p["w_down"], gs,
                              preferred_element_type=jnp.float32)


# tokens >= this threshold switch to the capacity path (per shard)
CAPACITY_THRESHOLD = 8192


def _grouped_ffn_capacity(p, xs, gs, mlp_kind,
                          capacity_factor: float = 1.25):
    """Fixed-capacity grouped GEMM: scan over experts, each processing a
    static (cap, D) slice of the expert-sorted token buffer.

    Memory is O(cap * F) per step instead of O(E * T * F); FLOPs are
    capacity_factor x the exact cost.  Tokens routed beyond an expert's
    capacity are dropped (standard GShard/Switch behaviour) — the paper's
    batch scheduler keeps shard token counts near uniform so drops are
    rare in practice.
    """
    tk, d = xs.shape
    e = gs.shape[0]
    cap = -(-int(capacity_factor * tk) // e)
    cap = min(max(8, -(-cap // 8) * 8), tk)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(gs)[:-1].astype(jnp.int32)])

    act_fn = jax.nn.silu if mlp_kind == "swiglu" else \
        functools.partial(jax.nn.gelu, approximate=True)

    def body(y, eidx):
        start = offsets[eidx]
        clamped = jnp.minimum(start, tk - cap)
        blk = jax.lax.dynamic_slice(xs, (clamped, 0), (cap, d))
        g = blk @ p["w_gate"][eidx]
        u = blk @ p["w_up"][eidx]
        h = (act_fn(g.astype(jnp.float32)) * u.astype(jnp.float32)
             ).astype(xs.dtype)
        out = (h @ p["w_down"][eidx]).astype(jnp.float32)
        idx = clamped + jnp.arange(cap)
        valid = (idx >= start) & (idx < start + gs[eidx])
        out = jnp.where(valid[:, None], out, 0.0)
        cur = jax.lax.dynamic_slice(y, (clamped, 0), (cap, d))
        y = jax.lax.dynamic_update_slice(y, cur + out, (clamped, 0))
        return y, None

    y0 = jnp.zeros((tk, d), jnp.float32)
    y, _ = jax.lax.scan(body, y0, jnp.arange(e))
    return y


def grouped_ffn(p, xs, gs, mlp_kind, impl: str = "auto"):
    if impl == "ragged" or (impl == "auto"
                            and xs.shape[0] < CAPACITY_THRESHOLD):
        return _grouped_ffn(p, xs, gs, mlp_kind)
    return _grouped_ffn_capacity(p, xs, gs, mlp_kind)


def _moe_local(p, x2: jnp.ndarray, cfg: ModelConfig,
               gemm_impl: str = "auto") -> Tuple[jnp.ndarray,
                                                 jnp.ndarray,
                                                 jnp.ndarray]:
    """Shard-local dropless MoE. Returns (out (T,D) f32 partial, load (E,),
    importance (E,)) — caller psums out over the TP axis."""
    m = cfg.moe
    t, d = x2.shape
    w, ids, probs = _route(p, x2, m)

    flat_ids = ids.reshape(-1)                            # (T*K,)
    order = jnp.argsort(flat_ids)                         # stable
    tok = order // m.top_k
    xs = x2[tok]                                          # (T*K, D)
    gs = jnp.zeros((m.num_experts,), jnp.int32).at[flat_ids].add(1)
    y = grouped_ffn(p, xs, gs, cfg.mlp_kind, gemm_impl)   # (T*K, D) f32
    wsort = w.reshape(-1)[order].astype(jnp.float32)
    out = jnp.zeros((t, d), jnp.float32).at[tok].add(y * wsort[:, None])

    if "shared" in p:
        out = out + layers.apply_mlp(p["shared"], x2, cfg.mlp_kind
                                     ).astype(jnp.float32)

    # load-balancing stats (summed, normalized by caller)
    load = jnp.zeros((m.num_experts,), jnp.float32).at[flat_ids].add(1.0)
    importance = probs.sum(axis=0)                        # (E,)
    return out, load, importance


def moe_forward(
    p, x: jnp.ndarray, cfg: ModelConfig,
    ctx: Optional[MeshContext] = None,
    gemm_impl: str = "auto",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, D) -> (out (B, S, D), aux_loss scalar)."""
    b, s, d = x.shape
    m = cfg.moe

    if ctx is None:
        out, load, imp = _moe_local(p, x.reshape(-1, d), cfg, gemm_impl)
        t = b * s
        aux = m.num_experts * jnp.sum(
            (load / (t * m.top_k)) * (imp / t)) * m.aux_loss_coef
        return out.reshape(b, s, d).astype(x.dtype), aux

    shard_b = ctx.shard_tokens(b)
    tok_spec = P(ctx.batch_axes, None, None) if shard_b else P(None, None, None)
    mdl = ctx.model_axis
    tp = ctx.tp_size
    wspec = {
        "router": P(None, None),
        "w_gate": P(None, None, mdl if m.d_ff_expert % tp == 0 else None),
        "w_up": P(None, None, mdl if m.d_ff_expert % tp == 0 else None),
        "w_down": P(None, mdl if m.d_ff_expert % tp == 0 else None, None),
    }
    if "shared" in p:
        f_sh = p["shared"]["w_up"].shape[1]
        sh = mdl if f_sh % tp == 0 else None
        wspec["shared"] = {"w_gate": P(None, sh), "w_up": P(None, sh),
                           "w_down": P(sh, None)}
        if "w_gate" not in p["shared"]:
            wspec["shared"].pop("w_gate")

    def fn(p_, x_):
        bl, sl, _ = x_.shape
        out, load, imp = _moe_local(p_, x_.reshape(-1, d), cfg, gemm_impl)
        if PSUM_DTYPE is not None:
            out = out.astype(PSUM_DTYPE)
        out = jax.lax.psum(out, mdl)
        if shard_b:
            load = jax.lax.psum(load, ctx.batch_axes)
            imp = jax.lax.psum(imp, ctx.batch_axes)
            t = bl * sl * ctx.dp_size
        else:
            t = bl * sl
        aux = m.num_experts * jnp.sum(
            (load / (t * m.top_k)) * (imp / t)) * m.aux_loss_coef
        return out.reshape(bl, sl, d).astype(x_.dtype), aux

    return jax.shard_map(
        fn, mesh=ctx.mesh, in_specs=(wspec, tok_spec),
        out_specs=(tok_spec, P()), check_vma=False)(p, x)


# ---------------------------------------------------------------------------
# Classic expert parallelism (all-to-all) — §Perf comparison strategy
# ---------------------------------------------------------------------------

def moe_forward_ep(
    p, x: jnp.ndarray, cfg: ModelConfig, ctx: MeshContext,
    capacity_factor: float = 1.25,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """EP: experts sharded over ``model``; tokens all-to-all to expert owners.

    Fixed per-destination capacity keeps shapes static (tokens over capacity
    are dropped, standard GShard/Switch behaviour).
    """
    b, s, d = x.shape
    m = cfg.moe
    mdl = ctx.model_axis
    tp = ctx.tp_size
    assert m.num_experts % tp == 0, "EP needs num_experts % tp == 0"
    e_local = m.num_experts // tp
    shard_b = ctx.shard_tokens(b)
    tok_spec = P(ctx.batch_axes, None, None) if shard_b else P(None, None, None)
    wspec = {
        "router": P(None, None),
        "w_gate": P(mdl, None, None),
        "w_up": P(mdl, None, None),
        "w_down": P(mdl, None, None),
    }
    if "shared" in p:
        wspec["shared"] = {k: P(None, None) for k in p["shared"]}

    def fn(p_, x_):
        bl, sl, _ = x_.shape
        t = bl * sl
        x2 = x_.reshape(t, d)
        w, ids, probs = _route(p_, x2, m)
        # capacity per (dest shard): even split of local expert traffic
        cap = int(capacity_factor * t * m.top_k / tp) or 1
        dest = ids // e_local                              # (T, K) shard id
        flat_dest = dest.reshape(-1)
        order = jnp.argsort(flat_dest)
        # position of each routed token within its destination bucket
        onehot = jax.nn.one_hot(flat_dest, tp, dtype=jnp.int32)
        pos_in_dest = jnp.cumsum(onehot, axis=0) * onehot
        pos = (pos_in_dest.sum(axis=1) - 1)                # (T*K,)
        keep = pos < cap
        slot = jnp.where(keep, pos, cap)        # cap = trash slot for drops
        # scatter tokens into (tp, cap+1, D) send buffer, slice off trash
        buf = jnp.zeros((tp, cap + 1, d), x_.dtype)
        buf = buf.at[flat_dest, slot].add(
            x2[jnp.arange(t * m.top_k) // m.top_k])[:, :cap]
        eids = jnp.zeros((tp, cap + 1), jnp.int32).at[
            flat_dest, slot].add(ids.reshape(-1) % e_local)[:, :cap]
        recv = jax.lax.all_to_all(buf, mdl, 0, 0, tiled=False)   # (tp,cap,D)
        reids = jax.lax.all_to_all(eids, mdl, 0, 0, tiled=False)
        # local grouped GEMM over owned experts
        rflat = recv.reshape(tp * cap, d)
        rorder = jnp.argsort(reids.reshape(-1))
        gs = jnp.zeros((e_local,), jnp.int32).at[reids.reshape(-1)].add(1)
        y = grouped_ffn(p_, rflat[rorder], gs, cfg.mlp_kind)
        y = jnp.zeros_like(y).at[rorder].set(y).reshape(tp, cap, d)
        back = jax.lax.all_to_all(y.astype(x_.dtype), mdl, 0, 0, tiled=False)
        # gather back to token order, weight, combine
        got = back[flat_dest, jnp.where(keep, pos, cap - 1)]
        got = jnp.where(keep[:, None], got, 0)
        wsort = w.reshape(-1).astype(jnp.float32)
        out = jnp.zeros((t, d), jnp.float32).at[
            jnp.arange(t * m.top_k) // m.top_k].add(
            got.astype(jnp.float32) * wsort[:, None])
        if "shared" in p_:
            out = out + layers.apply_mlp(p_["shared"], x2, cfg.mlp_kind
                                         ).astype(jnp.float32)
        load = jnp.zeros((m.num_experts,), jnp.float32).at[
            ids.reshape(-1)].add(1.0)
        imp = probs.sum(axis=0)
        aux = m.num_experts * jnp.sum(
            (load / (t * m.top_k)) * (imp / t)) * m.aux_loss_coef
        return out.reshape(bl, sl, d).astype(x_.dtype), aux

    return jax.shard_map(
        fn, mesh=ctx.mesh, in_specs=(wspec, tok_spec),
        out_specs=(tok_spec, P()), check_vma=False)(p, x)
