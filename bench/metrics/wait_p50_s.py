"""Median time a window request spent queued (before retrieval and
between retrieval and generation), from the program's ``Request``
stamps: the engine and its request scheduler."""
from statistics import median


def read(ctx):
    xs = [r.waiting for r in ctx["requests"] if r.complete]
    return median(xs) if xs else None
