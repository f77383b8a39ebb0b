"""Device idle time while the generation pump sleeps with no live slot
and nothing queued (the program's ``pump.wait`` spans), in percent of
the traced span: the chip starved of retrieved requests."""
from lib import spans


def read(ctx):
    return spans.idle_share_in(ctx, "pump.wait")
