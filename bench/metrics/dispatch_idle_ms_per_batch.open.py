"""dispatch_idle_ms_per_batch.open: chip-0 idle time under the worker's
engine.dispatch spans (the host launching a batch's device programs while
the chip waits), per batch (the worker's engine.search spans)."""
from harness import spans


def read(run):
    j = spans.joined(run)
    if j is None or not run.trace.chips:
        return None
    idle = spans.overlap(j.gaps(run.trace), j.on_trace(spans.DISPATCH))
    return idle / j.pairs / 1e6
