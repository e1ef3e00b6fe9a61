"""airindex.descent.collect span (wait for the device and float64 copy-back) per Pallas batch, ServeStats descent_collect_seconds (ms)."""
from readings import per_batch_ms


def read(rec):
    if "descent_collect_seconds" not in rec["stats"]:
        return None
    return per_batch_ms(rec, "descent_collect_seconds", "pallas_batches")
