"""Per-layer metric ``permute_ms.exchange-dedup``: collective-permute device time per exchange on the busiest chip, in ms."""
from bench import readers


def read(rec):
    return readers.kernel_ms(rec, readers.PERMUTE)
