"""Per-layer metric ``combine_ms.exchange-dedup``: the busiest chip's device busy time inside the host spans ``exchange.combine``, per exchange, in ms."""
from bench import span_busy


def read(rec):
    return span_busy.busy_ms(rec, "exchange.combine")
