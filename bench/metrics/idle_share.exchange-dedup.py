"""Per-layer metric ``idle_share.exchange-dedup``: the idle share of the busiest of the chips over the traced window, in %."""
from bench import readers


def read(rec):
    return readers.idle_share(rec)
