"""Per-layer metric ``segreduce_ms.sweep``: device time of the Pallas segment-reduce kernel per sweep, in ms."""
from bench import readers


def read(rec):
    return readers.kernel_ms(rec, readers.SEGREDUCE)
