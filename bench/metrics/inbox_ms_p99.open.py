"""inbox_ms_p99.open: 99th percentile (nearest rank) of the inbox wait of
every answered request: from ThreadedServer.submit to the worker's pickup
(Completed.inbox_ms), the wait queue_ms does not see."""
import math

from harness import spans


def read(run):
    if spans.records(run) is None:
        return None
    waits = sorted(getattr(c, "inbox_ms", math.nan) for _, c in run.completed)
    if not waits or any(math.isnan(x) for x in waits):
        return None
    return waits[math.ceil(0.99 * len(waits)) - 1]
