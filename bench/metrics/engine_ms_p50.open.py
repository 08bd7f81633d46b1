"""engine_ms_p50.open: median service time of the batches that answered
the window's requests. Every request of one batch carries that batch's
measured service time (Completed.service_ms), so one value stands for
one batch."""
import statistics


def read(run):
    per_batch = {c.service_ms for _, c in run.completed if not c.cached}
    return statistics.median(per_batch) if per_batch else None
