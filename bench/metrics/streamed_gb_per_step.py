"""Bytes of weights streamed host to device on every step
(``generator.exec.streamed_bytes``), in GB."""


def read(ctx):
    return ctx["streamed_bytes"] / 1e9 if ctx["streamed_bytes"] else None
