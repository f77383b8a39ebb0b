"""LLM prefetching pipeline (paper §4.3), TPU-adapted.

The paper replaces FlexGen's fixed next-layer prefetch with a *queue*:
future layers stream host->device continuously, bounded only by free
memory; the queue is shallow during prefill (activations occupy memory)
and deep during decode.

On TPU/JAX the analogue is a layer-streamed executor: per-layer parameter
slices live in the device's pinned host memory and are staged to device
memory ahead of compute.  ``jax.device_put`` is asynchronous, so issuing
the puts for the next ``depth`` layers before computing the current one
overlaps transfer with compute exactly like a background CUDA stream.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer


@dataclass
class PrefetchPolicy:
    """Phase-aware queue depth (conservative prefill, aggressive decode)."""

    max_depth: int = 8
    prefill_depth: int = 1

    def depth(self, phase: str, free_bytes: float,
              layer_bytes: float) -> int:
        if free_bytes == float("inf"):
            cap = self.max_depth
        else:
            cap = int(free_bytes // max(layer_bytes, 1.0))
        if phase == "prefill":
            return max(1, min(self.prefill_depth, cap))
        return max(1, min(self.max_depth, cap))


def tree_bytes(tree) -> int:
    return int(sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
                   for leaf in jax.tree.leaves(tree)))


def _layer_kind(cfg: ModelConfig, i: int):
    """Layer ``i``'s (mixer, ffn) kind in the executor's per-layer order."""
    if cfg.first_k_dense or cfg.encdec:
        raise NotImplementedError(
            "per-layer init covers decoder-only stacks without a dense "
            "prefix")
    return cfg.layer_pattern[i % len(cfg.layer_pattern)]


def _init_top(cfg: ModelConfig, key, dtype):
    k_embed, k_head = jax.random.split(key)
    top = {"embed": L.embed_init(k_embed, (cfg.vocab_size, cfg.d_model),
                                 dtype),
           "final_norm": jnp.ones((cfg.d_model,), dtype)}
    if not cfg.tie_embeddings:
        top["lm_head"] = L.dense_init(k_head, (cfg.d_model, cfg.vocab_size),
                                      dtype)
    return top


def top_param_shapes(cfg: ModelConfig, dtype):
    return jax.eval_shape(lambda: _init_top(cfg, jax.random.PRNGKey(0),
                                            dtype))


def layer_param_shapes(cfg: ModelConfig, dtype):
    return jax.eval_shape(lambda: transformer.init_layer(
        jax.random.PRNGKey(0), cfg, _layer_kind(cfg, 0), dtype))


def init_layered_params(cfg: ModelConfig, key, dtype, resident_layers: int,
                        device=None) -> Dict[str, Any]:
    """Seeded weights in :class:`StreamedExecutor`'s per-layer form.

    Embed, unembed, final norm and layers ``[0, resident_layers)`` are
    created in ``device``'s memory.  Every later layer is created on the
    device, copied into the device's pinned host memory and dropped from
    device memory before the next one is made, so at most one streamed
    layer is ever in device memory while the weights are built.
    """
    device = device or jax.devices()[0]
    on_dev = SingleDeviceSharding(device)
    on_host = SingleDeviceSharding(device, memory_kind="pinned_host")
    k_top, k_layers = jax.random.split(key)
    params = jax.jit(functools.partial(_init_top, cfg, dtype=dtype),
                     out_shardings=on_dev)(k_top)
    makers: Dict[Any, Any] = {}    # one compiled init per layer kind
    layers = []
    for i in range(cfg.num_layers):
        kind = _layer_kind(cfg, i)
        if kind not in makers:
            makers[kind] = jax.jit(functools.partial(
                transformer.init_layer, cfg=cfg, kind=kind, dtype=dtype),
                out_shardings=on_dev)
        lp = makers[kind](jax.random.fold_in(k_layers, i))
        if i >= resident_layers:
            lp = jax.block_until_ready(jax.device_put(lp, on_host))
        layers.append(lp)
    params["layers"] = layers
    return params


def layer_program(cfg: ModelConfig, kind, mode: str,
                  kv_span: Optional[int] = None):
    """The jitted one-layer step the executor runs for ``kind``/``mode``
    (``mode="chunk"`` is chunked prefill).  Its per-layer cache argument
    is not donated, so each call returns a fresh copy of that layer's
    cache while the caller still holds the old one."""
    layer_mode = "prefill" if mode == "chunk" else mode

    def fn(lp, x, cache, pos, block_tab):
        return transformer.apply_layer(
            lp, x, cfg, kind, mode=layer_mode, cache=cache, pos=pos,
            ctx=None, moe_strategy="tp", block_tab=block_tab,
            kv_span=kv_span)

    return jax.jit(fn)


def _unstack(tree, reps: int) -> List[Any]:
    """Split stacked (R, ...) params into R per-layer pytrees (host-side)."""
    leaves, treedef = jax.tree.flatten(tree)
    out = []
    for r in range(reps):
        out.append(jax.tree.unflatten(treedef, [l[r] for l in leaves]))
    return out


class StreamedExecutor:
    """Layer-streamed decode/prefill with a host->device prefetch queue.

    Used by the real serving engine for offloading-mode generation: the
    first ``resident_layers`` layers and the embed / unembed / final norm
    live in device memory; every later layer lives in the device's pinned
    host memory, and each step streams it through device memory with
    lookahead ``policy.depth(phase, free_bytes, layer_bytes)``.

    ``params`` is either the stacked pytree of ``Model.init`` or the
    per-layer form of :func:`init_layered_params` (a ``"layers"`` list);
    either way, streamed layers are moved to pinned host memory here.
    """

    def __init__(self, cfg: ModelConfig, params, policy: PrefetchPolicy,
                 device=None, resident_layers: int = 0,
                 free_bytes: float = float("inf")):
        self.cfg = cfg
        self.policy = policy
        self.device = device or jax.devices()[0]
        self.free_bytes = free_bytes
        self._on_dev = SingleDeviceSharding(self.device)
        self._on_host = SingleDeviceSharding(self.device,
                                             memory_kind="pinned_host")

        # the per-layer (kind, params) list, in execution order
        self.layers: List[Tuple[Any, Any]] = []
        if "layers" in params:
            self.layers = [(_layer_kind(cfg, i), lp)
                           for i, lp in enumerate(params["layers"])]
        else:
            kinds = cfg.layer_kinds()
            reps = transformer.scanned_repeats(cfg)
            for i, lp in enumerate(params.get("prefix", [])):
                self.layers.append(((kinds[i][0], "dense"), lp))
            per_pos = [_unstack(b, reps) for b in params["blocks"]]
            for r in range(reps):
                for j, kind in enumerate(cfg.layer_pattern):
                    self.layers.append((kind, per_pos[j][r]))
        self.n_layers = len(self.layers)
        self.resident = min(resident_layers, self.n_layers)
        # head/tail params stay on device
        self.top = jax.device_put(
            {k: v for k, v in params.items()
             if k not in ("blocks", "prefix", "layers")}, self._on_dev)
        self.layers = [
            (kind, jax.device_put(
                lp, self._on_dev if i < self.resident else self._on_host))
            for i, (kind, lp) in enumerate(self.layers)]
        self._apply_cache: Dict[Any, Any] = {}
        self.layer_bytes = (
            tree_bytes([lp for _, lp in self.layers])
            / max(self.n_layers, 1))

    @property
    def resident_bytes(self) -> int:
        """Device bytes of the resident layers plus the head/tail params."""
        return tree_bytes(self.top) + tree_bytes(
            [lp for _, lp in self.layers[:self.resident]])

    @property
    def streamed_bytes(self) -> int:
        """Pinned-host bytes of the layers streamed every step."""
        return tree_bytes([lp for _, lp in self.layers[self.resident:]])

    # ------------------------------------------------------------ helpers
    def _apply_fn(self, kind, mode, kv_span=None):
        key = (kind, mode, kv_span)
        if key not in self._apply_cache:
            self._apply_cache[key] = layer_program(self.cfg, kind, mode,
                                                   kv_span)
        return self._apply_cache[key]

    def _stream(self, x, caches, pos, mode: str, block_tab=None,
                kv_span=None):
        depth = self.policy.depth(
            "prefill" if mode in ("prefill", "chunk") else "decode",
            self.free_bytes, self.layer_bytes)
        staged: Dict[int, Any] = {}

        def ensure(i):
            if i >= self.n_layers or i in staged:
                return
            kind, lp = self.layers[i]
            if i < self.resident:
                staged[i] = lp
            else:
                # async host->device copy (the prefetch queue entry)
                staged[i] = jax.device_put(lp, self._on_dev)

        # warm the queue
        for i in range(min(depth, self.n_layers)):
            ensure(i)
        new_caches = []
        for i in range(self.n_layers):
            kind, _ = self.layers[i]
            lp = staged.pop(i)
            cache_i = caches[i] if caches is not None else None
            x_prev = x
            x, nc, _ = self._apply_fn(kind, mode, kv_span)(
                lp, x, cache_i, pos, block_tab)
            new_caches.append(nc)
            if self.resident <= i + depth < self.n_layers:
                # dispatch is asynchronous, so without this wait the host
                # would issue every copy at once.  Layer i is queued, so
                # the device stays busy while layer i-1 finishes and frees
                # its streamed weights: at most depth + 1 streamed layers
                # are then in device memory
                jax.block_until_ready(x_prev)
            ensure(i + depth)           # keep the queue full
        return x, (new_caches if caches is not None else None)

    # ------------------------------------------------------------- public
    def prefill(self, inputs, caches: List[dict], enc_embeds=None):
        cfg = self.cfg
        x = transformer._embed_inputs(self.top, cfg, inputs)
        x, new_caches = self._stream(x, caches, None, "prefill")
        x = L.rms_norm(x[:, -1:], self.top["final_norm"], cfg.norm_eps)
        logits = transformer.unembed(self.top, cfg, x, None)[:, 0]
        return logits, new_caches

    def decode(self, inputs, caches: List[dict], pos, slot_mask=None,
               block_tab=None, kv_span=None):
        """One decode step; ``slot_mask`` (B,) marks live slot rows.

        A step where *no* slot is live short-circuits before ``_stream``:
        the offloaded layers are not re-streamed host->device just to
        decode garbage for a drained slot table.  Dead rows in a mixed
        step still ride the batched compute — on the dense layout their
        cache writes are row-independent garbage that the next join's
        full-row scatter overwrites; on the paged layout
        (``block_tab``/``kv_span`` given) their block tables point at
        the trash page, so the writes can never land in a page reused
        by another slot.  Parked rows (preempted slots whose KV pages
        were swapped to the host pool) are just dead rows here: the
        slot mask excludes them and their all-trash table rows absorb
        the garbage writes until ``resume`` remaps them onto fresh
        pages.
        """
        cfg = self.cfg
        if slot_mask is not None \
                and not np.asarray(slot_mask).astype(bool).any():
            return jnp.zeros((inputs.shape[0], cfg.vocab_size)), caches
        x = transformer._embed_inputs(self.top, cfg, inputs)
        x, new_caches = self._stream(x, caches, pos, "decode",
                                     block_tab=block_tab, kv_span=kv_span)
        x = L.rms_norm(x, self.top["final_norm"], cfg.norm_eps)
        logits = transformer.unembed(self.top, cfg, x, None)[:, 0]
        return logits, new_caches

    def prefill_chunk(self, inputs, caches: List[dict], offset,
                      block_tab=None, kv_span=None):
        """Prefill one prompt chunk at per-sequence start ``offset`` (B,).

        Streams the offloaded layers once per chunk (prefill-depth
        queue); the chunk's KV lands at ``[offset, offset + C)`` and its
        attention spans the cache written by earlier chunks.  Returns
        the chunk's last-position logits and the updated caches.
        """
        cfg = self.cfg
        x = transformer._embed_inputs(self.top, cfg, inputs)
        x, new_caches = self._stream(x, caches, offset, "chunk",
                                     block_tab=block_tab, kv_span=kv_span)
        x = L.rms_norm(x[:, -1:], self.top["final_norm"], cfg.norm_eps)
        logits = transformer.unembed(self.top, cfg, x, None)[:, 0]
        return logits, new_caches

    # per-layer cache helpers (unstacked layout)
    def init_caches(self, batch: int, cache_len: int, dtype=jnp.float32):
        from repro.models import model as M
        out = []
        kinds = [k for k, _ in self.layers]
        for kind in kinds:
            spec = M._layer_cache_spec(self.cfg, kind[0], batch, cache_len,
                                       dtype, None)
            out.append(jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                    spec))
        return out

    def layer_kinds(self) -> List[Any]:
        """Mixer kinds per streamed layer (for paged cache construction)."""
        return [k for k, _ in self.layers]
