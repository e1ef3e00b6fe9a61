"""Least time of the calls (work.py, peaks.py) over the kernel's device time (%)."""
from readings import roofline_pct as read  # noqa: F401
