"""Bytes of host arrays handed to the device per Pallas batch, ServeStats h2d_bytes / pallas_batches (B)."""


def read(rec):
    s = rec["stats"]
    if "h2d_bytes" not in s or not s.get("pallas_batches"):
        return None
    return s["h2d_bytes"] / s["pallas_batches"]
