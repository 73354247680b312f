"""Per-layer metric ``walk_ms.query``: device time of the jitted receive-queue walk per query, in ms."""
from bench import readers


def read(rec):
    return readers.kernel_ms(rec, readers.WALK)
