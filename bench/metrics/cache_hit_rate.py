"""Bucket-cache hit rate over the window, from the change of
``CacheStats`` (hits over demand accesses), in percent."""


def read(run):
    c = run.counters
    if not c["cache_accesses"]:
        return None
    return 100.0 * c["cache_hits"] / c["cache_accesses"]
