"""Share of burned-state lookups in the window served from the state cache
(``ServiceStats`` diff)."""


def read(run):
    n = run.stats["state_cache_hits"] + run.stats["state_cache_misses"]
    return 100.0 * run.stats["state_cache_hits"] / n if n else None
