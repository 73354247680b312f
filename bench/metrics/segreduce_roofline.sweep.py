"""Per-layer metric ``segreduce_roofline.sweep``: the segment reduce's least time on its roofline over its measured time, in %."""
from bench import readers


def read(rec):
    return readers.segreduce_roofline(rec)
