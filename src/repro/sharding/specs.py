"""Sharding rules: logical axes -> mesh axes, per parameter-family.

The production mesh is ``(pod, data, model)`` (multi-pod) or ``(data, model)``
(single pod).  Batch shards over the pod+data axes jointly; tensor-parallel
dims (attention heads, FFN columns, experts' hidden dim, vocab) shard over
``model``.  Rules degrade gracefully: a dim that does not divide by the mesh
axis size is left replicated (e.g. gemma2-2b's 8 heads on a 16-wide model
axis) — recorded per-arch in EXPERIMENTS.md.
"""
from __future__ import annotations

import dataclasses
import re
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MeshContext:
    mesh: Mesh
    batch_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"

    @property
    def dp_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))

    @property
    def tp_size(self) -> int:
        return int(self.mesh.shape[self.model_axis])

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    def shard_tokens(self, global_batch: int) -> bool:
        return global_batch % self.dp_size == 0

    def batch_spec(self, global_batch: int, *rest) -> P:
        """Batch-leading PartitionSpec; replicates if batch doesn't divide."""
        if self.shard_tokens(global_batch):
            return P(self.batch_axes, *rest)
        return P(None, *rest)


def from_mesh(mesh: Mesh) -> MeshContext:
    names = tuple(mesh.axis_names)
    batch_axes = tuple(a for a in names if a in ("pod", "data")) or names[:1]
    model_axis = "model" if "model" in names else names[-1]
    return MeshContext(mesh=mesh, batch_axes=batch_axes, model_axis=model_axis)


def _div(n: int, d: int) -> bool:
    return n % d == 0


# ---------------------------------------------------------------------------
# Parameter partition rules (matched on the flattened tree path)
# ---------------------------------------------------------------------------
# Each rule: (regex on path, fn(shape, ndim_offset, ctx) -> PartitionSpec for
# the *unstacked* layer param).  Stacked (scanned) params get a leading
# repeats dim that is always replicated (None prepended).

def _pspec_for(name: str, shape: Tuple[int, ...], stacked: bool,
               ctx: MeshContext) -> P:
    tp, m = ctx.tp_size, ctx.model_axis
    body = shape[1:] if stacked else shape

    def wrap(*axes) -> P:
        return P(None, *axes) if stacked else P(*axes)

    def expert_axes(e: int):
        """FSDP-style expert-dim sharding over the batch axes (ZeRO for
        expert weights + their optimizer moments); the MoE shard_map
        all-gathers them on use."""
        if e % ctx.dp_size == 0:
            return ctx.batch_axes
        for ax in ctx.batch_axes[::-1]:
            if e % ctx.mesh.shape[ax] == 0:
                return ax
        return None

    # embeddings / heads
    if name.endswith("embed"):
        return wrap(m if _div(body[0], tp) else None, None)
    if name.endswith("lm_head"):
        return wrap(None, m if _div(body[1], tp) else None)
    # attention
    if re.search(r"(wq|wk|wv)$", name):
        return wrap(None, m if _div(body[1], tp) else None, None)
    if re.search(r"(bq|bk|bv)$", name):
        return wrap(m if _div(body[0], tp) else None, None)
    if name.endswith("wo"):
        return wrap(m if _div(body[0], tp) else None, None, None)
    # MLA
    if re.search(r"(w_uk|w_uv)$", name):
        return wrap(None, m if _div(body[1], tp) else None, None)
    if re.search(r"(w_dkv|w_krope)$", name):
        return wrap(None, None)
    # dense FFN
    if re.search(r"(w_gate|w_up)$", name) and len(body) == 2:
        return wrap(None, m if _div(body[1], tp) else None)
    if name.endswith("w_down") and len(body) == 2:
        return wrap(m if _div(body[0], tp) else None, None)
    # MoE expert weights (E, D, F) / (E, F, D)
    if re.search(r"(w_gate|w_up)$", name) and len(body) == 3:
        return wrap(expert_axes(body[0]), None,
                    m if _div(body[2], tp) else None)
    if name.endswith("w_down") and len(body) == 3:
        return wrap(expert_axes(body[0]),
                    m if _div(body[1], tp) else None, None)
    if name.endswith("router"):
        return wrap(None, None)
    # mamba
    if re.search(r"(in_z|in_x|in_dt)$", name):
        return wrap(None, m if _div(body[1], tp) else None)
    if name.endswith("in_bc"):
        return wrap(None, None)
    if re.search(r"(conv_x_w)$", name):
        return wrap(m if _div(body[0], tp) else None, None)
    if re.search(r"(conv_x_b|gate_norm)$", name):
        return wrap(m if _div(body[0], tp) else None)
    if name.endswith("out_proj"):
        return wrap(m if _div(body[0], tp) else None, None)
    # norms, scalars, everything else: replicated
    return wrap(*(None,) * len(body))


def param_pspecs(param_shapes, ctx: Optional[MeshContext]):
    """PartitionSpec tree for a params pytree (of ShapeDtypeStruct/arrays).

    Stacked (scan) params are detected by path: anything under ``blocks``
    has a leading repeats dim.
    """
    if ctx is None:
        return jax.tree.map(lambda _: P(), param_shapes)

    def visit(path, leaf):
        keys = [getattr(p, "key", getattr(p, "idx", "")) for p in path]
        name = "/".join(str(k) for k in keys)
        stacked = any(str(k) == "blocks" for k in keys)
        return _pspec_for(name, leaf.shape, stacked, ctx)

    return jax.tree_util.tree_map_with_path(visit, param_shapes)


def shard_extra_dim(pspecs, param_shapes, ctx: MeshContext):
    """ZeRO/FSDP transform: additionally shard each leaf's first free
    (unsharded, divisible) dim over the batch axes.

    Applied to optimizer state (ZeRO-1: moments + master sharded dp-ways)
    and, for very large models, to the parameters themselves (FSDP —
    GSPMD inserts the per-layer all-gathers/reduce-scatters).
    """
    def visit(spec, shape_leaf):
        shape = shape_leaf.shape
        parts = list(spec) + [None] * (len(shape) - len(spec))
        used = set()
        for entry in parts:
            if entry is None:
                continue
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                used.add(ax)
        free = tuple(a for a in ctx.batch_axes if a not in used)
        if not free:
            return spec
        size = int(np.prod([ctx.mesh.shape[a] for a in free]))
        for i, (ax, n) in enumerate(zip(parts, shape)):
            if ax is None and n % size == 0 and n > 0:
                parts[i] = free
                return P(*parts)
        # fall back to a single free axis if the product doesn't divide
        for a in free:
            sz = ctx.mesh.shape[a]
            for i, (ax, n) in enumerate(zip(parts, shape)):
                if ax is None and n % sz == 0 and n > 0:
                    parts[i] = (a,)
                    return P(*parts)
        return spec

    return jax.tree.map(visit, pspecs, param_shapes,
                        is_leaf=lambda x: isinstance(x, P))


def logical_to_pspec(ctx: Optional[MeshContext], *logical) -> P:
    """Map logical activation axes -> PartitionSpec.

    Logical names: "batch", "model", None.
    """
    if ctx is None:
        return P()
    out = []
    for ax in logical:
        if ax == "batch":
            out.append(ctx.batch_axes)
        elif ax in ("model", "seq"):
            # "seq": Megatron-style sequence parallelism — activations
            # stored seq-sharded over the model axis between blocks
            out.append(ctx.model_axis)
        else:
            out.append(None)
    return P(*out)


def constrain(x, ctx: Optional[MeshContext], *logical):
    """with_sharding_constraint by logical axes (no-op without mesh)."""
    if ctx is None:
        return x
    spec = logical_to_pspec(ctx, *logical)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))
