"""airindex.descent.rebase span (the windows widened to byte offsets, inside collect) per Pallas batch, ServeStats rebase_seconds (ms)."""
from readings import per_batch_ms


def read(rec):
    if "rebase_seconds" not in rec["stats"]:
        return None
    return per_batch_ms(rec, "rebase_seconds", "pallas_batches")
