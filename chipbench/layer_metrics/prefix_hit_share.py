"""Cache: prompt tokens the prefix cache served, as a share of the
prompt tokens admitted in the window (PrefixCache counters)."""


def read(run):
    seen = run.cache_hits + run.cache_misses
    return 100.0 * run.cache_hits / seen if seen else None
