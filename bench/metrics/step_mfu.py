"""``step_mfu``, in cells with every layer resident (see ``lib/layer.py``)."""
from lib import layer


def read(ctx):
    if ctx["streamed_bytes"]:
        return None
    return layer.step_mfu(ctx)
