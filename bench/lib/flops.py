"""Model operations of the dense attention family, from its sizes.

Counted: every weight matmul (2 operations per multiply-add) and the
attention scores and mixing over the positions each token attends to.
A prefill unembeds only its last position, as the served path does.
Norms, rotary and softmax are left out.
"""
from __future__ import annotations

from typing import Dict


def _dims(m: Dict):
    d, h, kv = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // h
    return d, h, kv, hd


def layer_params(m: Dict) -> int:
    """Matmul weights of one layer."""
    d, h, kv, hd = _dims(m)
    return d * h * hd * 2 + 2 * d * kv * hd + 3 * d * m["d_ff"]


def decode_flops(m: Dict, kv_len: int) -> float:
    """One decoded token attending over ``kv_len`` positions."""
    d, h, _, hd = _dims(m)
    per_layer = 2.0 * layer_params(m) + 4.0 * h * hd * kv_len
    return m["num_layers"] * per_layer + 2.0 * d * m["vocab_size"]


def prefill_flops(m: Dict, seq: int) -> float:
    """One causal prefill of ``seq`` positions."""
    d, h, _, hd = _dims(m)
    per_layer = (2.0 * layer_params(m) * seq
                 + 4.0 * h * hd * seq * (seq + 1) / 2)
    return m["num_layers"] * per_layer + 2.0 * d * m["vocab_size"]
