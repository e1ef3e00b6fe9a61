"""Resident-descent dispatch wall per Pallas batch, ServeStats descent_seconds (ms)."""
from readings import per_batch_ms


def read(rec):
    return per_batch_ms(rec, "descent_seconds", "pallas_batches")
