"""plan_hit_share.interactive: percent of the window's plan-cache lookups
that hit (``QueryServer.plan_cache_info()`` at the window's start and
end)."""


def read(record):
    before, after = record["plan_cache"]
    hits = after["hits"] - before["hits"]
    lookups = hits + after["misses"] - before["misses"]
    return 100.0 * hits / lookups if lookups else None
