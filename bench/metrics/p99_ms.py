"""p99_ms: 99th percentile (nearest rank) of the latency of every request
due in the window, from when it was due to its answer. A request with no
answer counts as waiting until the run stopped waiting for it."""
import math


def read(run):
    w = run.window
    lat = sorted(
        ((r.done if r.response is not None and r.response.ok
          else w.drained_at) - r.due) * 1e3
        for r in w.records)
    if not lat:
        return None
    return lat[math.ceil(0.99 * len(lat)) - 1]
