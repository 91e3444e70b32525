"""Host milliseconds per batch of the engine's `stage1` span (sparse
retrieval and Stage-I candidates, ending in the device-to-host copy of
the candidates)."""


def read(ctx):
    return ctx.span_ms("stage1")
