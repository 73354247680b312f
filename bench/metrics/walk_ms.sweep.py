"""Per-layer metric ``walk_ms.sweep``: device time of the jitted receive-queue walk per sweep, in ms."""
from bench import readers


def read(rec):
    return readers.kernel_ms(rec, readers.WALK)
