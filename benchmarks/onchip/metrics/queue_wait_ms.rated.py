"""99th percentile of due time to dispatch: how late the dispatcher ran (ms)."""
from readings import queue_wait_ms


def read(rec):
    return queue_wait_ms(rec, 99)
