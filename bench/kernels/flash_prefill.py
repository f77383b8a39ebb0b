"""Flash prefill attention (``kernels/flash_attention.py``): the work one
call needs.

A causal call over ``seq`` positions scores each query against itself
and every earlier key: ``seq * (seq + 1) / 2`` pairs per head, q.k and
p.v at 2 operations per multiply-add each.  It reads q, k and v once and
writes the output once.
"""
from __future__ import annotations

from typing import Tuple

# How the kernel's Mosaic call reads in a device trace.  Its pallas_call
# carries no name, so the op is known by its operands: the per-row
# lengths (1-D int32, scalar prefetch), then q of rank 4 in bf16.
PATTERN = (r"custom-call\(s32\[\d+\]\{[^}]*\} %[^,]+, "
           r"bf16\[\d+,\d+,\d+,\d+\]")


def cost(batch: int, seq: int, heads: int, kv_heads: int, head_dim: int,
         itemsize: int = 2, causal: bool = True) -> Tuple[float, float]:
    """(operations, bytes) of one call."""
    pairs = seq * (seq + 1) // 2 if causal else seq * seq
    flops = 4.0 * batch * heads * head_dim * pairs
    nbytes = float(batch * itemsize * seq * head_dim
                   * (2 * heads + 2 * kv_heads))
    return flops, nbytes
