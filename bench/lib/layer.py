"""Whole-step readings shared by the per-layer metric readers."""
from __future__ import annotations


def step_mfu(ctx):
    """Model operations of the prefills and decode tokens in the traced
    span, counted by the configuration's family file, over the span's
    length at the chip's bf16 peak, in percent."""
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    m, fam = ctx["model"], ctx["reference"]
    ops = sum(fam.decode_flops(m, kv) for kv in tr["decode_kv"])
    ops += tr["joins"] * fam.prefill_flops(m, tr["ctx_len"])
    if ops <= 0:
        return None
    return 100.0 * ops / (tr["window_s"] * ctx["peaks"]["flops_bf16"])


def idle_share(ctx):
    """Share of the traced span in which no operation ran on the chip."""
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
