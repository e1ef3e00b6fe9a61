"""Walk time outside the page fetch per distinct window scanned, ServeStats (walk_seconds - walk_fetch_seconds) / walk_windows (us)."""


def read(rec):
    s = rec["stats"]
    if not s.get("walk_windows"):
        return None
    return (s["walk_seconds"] - s["walk_fetch_seconds"]) \
        / s["walk_windows"] * 1e6
