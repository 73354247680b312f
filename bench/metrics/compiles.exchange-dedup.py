"""Per-layer metric ``compiles.exchange-dedup``: backend compilations inside the window."""
from bench import readers


def read(rec):
    return readers.compiles(rec)
