"""traversal_ms_per_batch: device time of one run of the HELP traversal
program (routing._search_jit), averaged over its runs in the trace."""

PROGRAM = "jit__search_jit"


def read(run):
    runs = run.trace.program(PROGRAM) if run.trace else []
    if not runs:
        return None
    return sum(m.end - m.start for m in runs) / len(runs) / 1e6
