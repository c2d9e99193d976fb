"""Share of the host shard cache's lookups in the window that hit. Nothing
when the cache is off or was never asked."""


def read(run):
    c = run["counters"]
    n = c.get("host_cache_hits", 0) + c.get("host_cache_misses", 0)
    if "host_cache_hits" not in c or n == 0:
        return None
    return 100.0 * c["host_cache_hits"] / n
