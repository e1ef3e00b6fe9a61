"""Share of the traced window in which no operation ran on the chip (%)."""
from readings import idle_pct as read  # noqa: F401
