"""Mean host time of a decode step over the window (change in the sum
and count of the engine registry's ``decode.step_seconds``), where some
layers stream from host memory on every step."""


def read(ctx):
    n, secs = ctx["steps"]
    if not ctx["streamed_bytes"] or n <= 0:
        return None
    return 1e3 * secs / n
