"""Median retrieval time of a window request (``t_ret_end -
t_ret_start`` of its retrieval batch): embed, IVF probe, partition
loads and the top-k sweep."""
from statistics import median


def read(ctx):
    xs = [r.retrieval for r in ctx["requests"] if r.complete]
    return median(xs) if xs else None
