"""Flash prefill attention's share of its roofline over the traced
span: the least time for the operations and bytes of every prefill in
the span (``bench/kernels/flash_prefill.py``, one batch-1 call per layer
per join) over the kernel's device time in the trace."""
from lib import spec, trace


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["joins"] <= 0:
        return None
    k = spec.kernel_cost("flash_prefill")
    secs, _ = trace.kernel_time(tr["reduced"], k.PATTERN)
    if secs <= 0:
        return None
    m = ctx["model"]
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    fl, by = k.cost(1, tr["ctx_len"], m["num_heads"], m["num_kv_heads"], hd)
    calls = tr["joins"] * m["num_layers"]
    p = ctx["peaks"]
    least = max(calls * fl / p["flops_bf16"],
                calls * by / p["hbm_bytes_per_s"])
    return 100.0 * least / secs
