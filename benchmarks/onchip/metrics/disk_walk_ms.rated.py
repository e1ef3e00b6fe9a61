"""Host time per batch outside the resident descent: the on-disk walk (ms)."""
from readings import disk_walk_ms as read  # noqa: F401
