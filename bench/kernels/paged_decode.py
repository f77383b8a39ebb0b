"""Paged decode attention (``kernels/paged_attention.py``): the work one
call needs.

One call serves every slot of a decode step for one layer.  For a live
slot with ``kv_len`` cached positions it takes q.k over ``kv_len`` keys
and p.v over as many values for each of ``heads`` query heads (2
operations per multiply-add), reads the pages that hold those positions
(whole pages of ``page`` tokens, keys and values, ``kv_heads`` heads)
and reads q and writes the output once.  Dead slots need nothing.
"""
from __future__ import annotations

import math
from typing import Iterable, Tuple

# How the kernel's Mosaic call reads in a device trace.  Its pallas_call
# carries no name, so the op is known by its operands: the block table
# (2-D int32) and the per-slot lengths (1-D int32) come first, as scalar
# prefetch.
PATTERN = (r"custom-call\(s32\[\d+,\d+\]\{[^}]*\} %[^,]+, "
           r"s32\[\d+\]\{")


def cost(heads: int, kv_heads: int, head_dim: int, kv_lens: Iterable[int],
         page: int, itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one call over slots at ``kv_lens``."""
    flops = 0.0
    nbytes = 0.0
    for n in kv_lens:
        flops += 4.0 * heads * head_dim * n
        pages = math.ceil(n / page)
        nbytes += 2.0 * pages * page * kv_heads * head_dim * itemsize
        nbytes += 2.0 * heads * head_dim * itemsize
    return flops, nbytes
