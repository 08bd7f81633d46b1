"""gc_pause_ms.open: milliseconds of garbage collection inside the traced
window, on any thread: the program's gc spans (gc.callbacks), clipped to
the window."""
from harness import spans


def read(run):
    return spans.gc_pause_ms(run)
