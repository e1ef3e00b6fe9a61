"""Mean wall of one IndexService.lookup call, timed around the call (ms)."""
from readings import call_ms as read  # noqa: F401
