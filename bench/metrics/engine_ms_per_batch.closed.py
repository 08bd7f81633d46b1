"""engine_ms_per_batch.closed: the server's service seconds per batch in
the window (Engine.search to block_until_ready, host clock; ServerStats)."""


def read(run):
    c = run.window.counters
    return 1e3 * c["service_wall_s"] / c["batches"] if c["batches"] else None
