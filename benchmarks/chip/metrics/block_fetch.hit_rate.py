"""Block-cache hits over lookups by the serving path during the window,
in % (prefetch probes are not lookups)."""


def read(ctx):
    if ctx.cache is None or sum(ctx.cache) == 0:
        return None
    hits, misses = ctx.cache
    return 100.0 * hits / (hits + misses)
