"""Executables this process had to compile because the persistent cache
did not hold them (``compile_cache.stats()["disk_misses"]``), with any
compiled inside the window counted once more. 0 in a warm checkout."""


def read(trace, counters, record):
    stats = counters.get("compile_cache")
    if stats is None:
        return None
    return stats["disk_misses"] + max(counters.get("compiled_in_window", 0), 0)
