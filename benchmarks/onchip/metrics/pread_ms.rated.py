"""FileBackend.pread wall per batch, ServeStats pread_seconds / batches (ms)."""
from readings import per_batch_ms


def read(rec):
    return per_batch_ms(rec, "pread_seconds")
