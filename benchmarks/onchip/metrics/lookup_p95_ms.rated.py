"""95th percentile latency of all lookups due in the window, due time to answer (ms)."""
from readings import latency_ms


def read(rec):
    return latency_ms(rec, 95)
