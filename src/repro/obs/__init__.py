from repro.obs.history import (
    append_snapshot,
    detect_regressions,
    read_history,
    snapshot_from_bench,
)
from repro.obs.trace import Span, TraceRecorder

__all__ = [
    "Span",
    "TraceRecorder",
    "snapshot_from_bench",
    "append_snapshot",
    "read_history",
    "detect_regressions",
]
