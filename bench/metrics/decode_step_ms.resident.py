"""Mean host time of a decode step over the window (change in the sum
and count of the engine registry's ``decode.step_seconds``), where every
layer is resident in device memory."""


def read(ctx):
    n, secs = ctx["steps"]
    if ctx["streamed_bytes"] or n <= 0:
        return None
    return 1e3 * secs / n
