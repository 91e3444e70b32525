"""Host milliseconds per batch of the engine's `cache_fetch` span (cache
lookups and any block reads for the batch's unique selected clusters)."""


def read(ctx):
    return ctx.span_ms("cache_fetch")
