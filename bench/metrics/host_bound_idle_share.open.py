"""host_bound_idle_share.open: share of the traced window in which chip 0
was idle while the serving worker was not waiting for requests: chip-0
idle time not under the worker's serve.idle spans (the program's ring,
joined to the device trace by harness.spans), over the window."""
from harness import spans


def read(run):
    j = spans.joined(run)
    if j is None or not run.trace.chips:
        return None
    gaps = j.gaps(run.trace)
    idle = sum(b - a for a, b in gaps)
    waiting = spans.overlap(gaps, j.on_trace(spans.IDLE))
    return 100.0 * (idle - waiting) / (j.hi - j.lo)
