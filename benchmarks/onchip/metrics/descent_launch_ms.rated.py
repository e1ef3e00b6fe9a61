"""airindex.descent.launch span (kernel enqueue and the slices of its output) per Pallas batch, ServeStats descent_launch_seconds (ms)."""
from readings import per_batch_ms


def read(rec):
    if "descent_launch_seconds" not in rec["stats"]:
        return None
    return per_batch_ms(rec, "descent_launch_seconds", "pallas_batches")
