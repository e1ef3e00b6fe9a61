"""Self time of the airindex.lookup span per batch: ServeStats (lookup_seconds - descent_seconds - walk_seconds) / batches (ms)."""


def read(rec):
    s = rec["stats"]
    if "lookup_seconds" not in s or not s.get("batches"):
        return None
    return (s["lookup_seconds"] - s["descent_seconds"]
            - s["walk_seconds"]) / s["batches"] * 1e3
