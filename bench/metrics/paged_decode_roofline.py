"""Paged decode attention's share of its roofline over the traced span:
the least time the chip needs for the operations and bytes of every
decode token in the span (``bench/kernels/paged_decode.py``, one call
per layer per step) over the kernel's device time in the trace."""
from lib import spec, trace


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["decode_kv"]:
        return None
    k = spec.kernel_cost("paged_decode")
    secs, _ = trace.kernel_time(tr["reduced"], k.PATTERN)
    if secs <= 0:
        return None
    m = ctx["model"]
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    fl, by = k.cost(m["num_heads"], m["num_kv_heads"], hd, tr["decode_kv"],
                    int(ctx["mix"]["page_size"]))
    layers = m["num_layers"]
    p = ctx["peaks"]
    least = max(layers * fl / p["flops_bf16"],
                layers * by / p["hbm_bytes_per_s"])
    return 100.0 * least / secs
