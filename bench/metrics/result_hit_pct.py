"""Result-cache hits over lookups in the window, both kinds (whole
queries and semi-join materializations X_i), from the service's
``svc.result_cache.{query,x}.{hit,miss}`` counters."""


def read(ctx):
    lookups = ctx.counters_after["result_lookups"] - ctx.counters_before["result_lookups"]
    if lookups <= 0:
        return None
    hits = ctx.counters_after["result_hits"] - ctx.counters_before["result_hits"]
    return 100.0 * hits / lookups
