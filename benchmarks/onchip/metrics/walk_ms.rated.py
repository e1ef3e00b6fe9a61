"""airindex.walk span (the on-disk walk below the resident layers) per batch, ServeStats walk_seconds (ms)."""
from readings import per_batch_ms


def read(rec):
    if "walk_seconds" not in rec["stats"]:
        return None
    return per_batch_ms(rec, "walk_seconds")
