"""cache_hit_pct: result-cache hits over submit-path lookups in the window."""


def read(ctx):
    hits = sum(c.value for c in ctx.family("ppr_cache_hits_total"))
    misses = sum(c.value for c in ctx.family("ppr_cache_misses_total"))
    if hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
