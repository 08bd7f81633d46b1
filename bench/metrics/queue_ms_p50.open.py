"""queue_ms_p50.open: median of the queue wait the server reports for
each answered request (Completed.queue_ms)."""
import statistics


def read(run):
    q = [c.queue_ms for _, c in run.completed]
    return statistics.median(q) if q else None
