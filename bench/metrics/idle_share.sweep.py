"""Per-layer metric ``idle_share.sweep``: the device's idle share of the traced window, in %."""
from bench import readers


def read(rec):
    return readers.idle_share(rec)
