from .backend import (CorruptPageError, DeadlineExceededError,
                      FaultInjectingBackend, FileBackend, ReadError,
                      StorageBackend, StorageError, pread_full)
from .index_service import (IndexService, ServeStats, TieredBlockCache,
                            cacheable_working_set, load_serve_stats,
                            load_stats_history, observed_profile_from_stats,
                            save_stats_snapshot, stats_path)

__all__ = ["IndexService", "ServeStats", "TieredBlockCache",
           "cacheable_working_set", "load_serve_stats", "load_stats_history",
           "observed_profile_from_stats", "save_stats_snapshot", "stats_path",
           "StorageBackend", "FileBackend", "FaultInjectingBackend",
           "StorageError", "ReadError", "CorruptPageError",
           "DeadlineExceededError", "pread_full"]
