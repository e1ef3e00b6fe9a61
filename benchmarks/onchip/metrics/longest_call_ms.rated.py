"""Wall of the longest IndexService.lookup call in the window (ms): a host stall shows here."""
from readings import longest_call_ms as read  # noqa: F401
