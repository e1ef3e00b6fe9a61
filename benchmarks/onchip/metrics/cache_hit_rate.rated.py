"""Block-cache hit rate over the window, ServeStats pages_hit / touched (%)."""
from readings import hit_rate_pct as read  # noqa: F401
