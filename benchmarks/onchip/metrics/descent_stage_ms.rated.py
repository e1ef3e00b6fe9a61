"""airindex.descent.stage span (query cast and pad, upload of the queries and planes) per Pallas batch, ServeStats descent_stage_seconds (ms)."""
from readings import per_batch_ms


def read(rec):
    if "descent_stage_seconds" not in rec["stats"]:
        return None
    return per_batch_ms(rec, "descent_stage_seconds", "pallas_batches")
