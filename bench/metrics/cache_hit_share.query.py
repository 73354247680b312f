"""Per-layer metric ``cache_hit_share.query``: the service's verdict-cache hits over its lookups in the window, in %."""
from bench import readers


def read(rec):
    return readers.cache_hit_share(rec)
