"""Share of the window's queries whose key has a nonzero high 32-bit word, all handed to the device, ServeStats wide_queries / queries (%)."""


def read(rec):
    s = rec["stats"]
    if "wide_queries" not in s or not s.get("queries"):
        return None
    return 100.0 * s["wide_queries"] / s["queries"]
