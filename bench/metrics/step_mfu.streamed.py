"""``step_mfu``, in cells with layers streamed from host memory (see ``lib/layer.py``)."""
from lib import layer


def read(ctx):
    if not ctx["streamed_bytes"]:
        return None
    return layer.step_mfu(ctx)
