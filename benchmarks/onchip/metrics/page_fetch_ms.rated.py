"""airindex.walk.fetch span (cache probes plus coalesced preads) per batch, ServeStats walk_fetch_seconds (ms)."""
from readings import per_batch_ms


def read(rec):
    if "walk_fetch_seconds" not in rec["stats"]:
        return None
    return per_batch_ms(rec, "walk_fetch_seconds")
