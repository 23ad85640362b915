"""Share of the window's block lookups the service's result cache
(``ShardedLRU``) answered, from ``PredictionService.stats()``."""


def read(m):
    c = m["counters"]
    total = c.get("cache_hits", 0) + c.get("cache_misses", 0)
    return 100.0 * c["cache_hits"] / total if total else None
