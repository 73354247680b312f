"""Per-layer metric ``exchange_roofline.exchange-dedup``: an exchange's least time on its roofline (the messages' HBM and ICI bytes per chip) over the busiest chip's busy time per exchange, in %."""
from bench import readers


def read(rec):
    return readers.exchange_roofline(rec)
