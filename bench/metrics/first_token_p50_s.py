"""Median time from a window request's admission into a generation slot
to its first answer token (``Request.t_first_token - t_gen_start``):
prefill, and the other prefills of the same admission ahead of it."""
from statistics import median


def read(ctx):
    xs = [r.t_first_token - r.t_gen_start for r in ctx["requests"]
          if getattr(r, "t_first_token", None) is not None
          and r.t_gen_start is not None]
    return median(xs) if xs else None
