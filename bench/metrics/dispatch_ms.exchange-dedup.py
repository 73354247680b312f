"""Per-layer metric ``dispatch_ms.exchange-dedup``: the busiest chip's device busy time inside the host spans ``exchange.dispatch``, per exchange, in ms."""
from bench import span_busy


def read(rec):
    return span_busy.busy_ms(rec, "exchange.dispatch")
