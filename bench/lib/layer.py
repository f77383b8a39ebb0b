"""Whole-step readings shared by the per-layer metric readers."""
from __future__ import annotations

from lib import flops


def step_mfu(ctx):
    """Model operations of the prefills and decode tokens in the traced
    span over the span's length at the chip's bf16 peak, in percent."""
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    m = ctx["model"]
    ops = sum(flops.decode_flops(m, kv) for kv in tr["decode_kv"])
    ops += tr["joins"] * flops.prefill_flops(m, tr["ctx_len"])
    if ops <= 0:
        return None
    return 100.0 * ops / (tr["window_s"] * ctx["peaks"]["flops_bf16"])


def idle_share(ctx):
    """Share of the traced span in which no operation ran on the chip."""
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
