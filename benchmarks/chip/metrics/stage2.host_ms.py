"""Host milliseconds per batch of the engine's `stage2_select` span (the
LSTM selection, ending in the copy of the selection to the host)."""


def read(ctx):
    return ctx.span_ms("stage2_select")
