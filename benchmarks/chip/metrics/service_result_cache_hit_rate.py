"""Result-cache hits over lookups (``QueryService.stats()["cache"]``) during
the window, in percent."""


def read(ctx):
    hits, misses = ctx.cache_delta
    return 100.0 * hits / (hits + misses) if hits + misses else None
