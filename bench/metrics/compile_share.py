"""Share of the traced span in which some thread of the program traced,
lowered or compiled a JAX program (the union of the program's ``jit.*``
spans, clipped to the span), in percent."""
from lib import spans


def read(ctx):
    host = spans.host_spans(ctx)
    if host is None:
        return None
    red = ctx["trace"]["reduced"]
    if red.window_s <= 0:
        return None
    jit = [(s, e) for s, e, n, _ in host if n.startswith("jit.")]
    return (100.0 * spans.covered([spans.window_ns(red)], jit) * 1e-9
            / red.window_s)
