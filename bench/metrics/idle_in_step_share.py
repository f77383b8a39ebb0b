"""Device idle time while the generation pump is inside an iteration
that does work (the program's ``pump.step`` spans: capacity probe,
admission, prefill and decode), in percent of the traced span: the
chip kept waiting by the host side of a step."""
from lib import spans


def read(ctx):
    return spans.idle_share_in(ctx, "pump.step")
