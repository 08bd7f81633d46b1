"""batch_fill.closed: real rows over padded bucket rows of the batches
served in the window, from the server's own counters (ServerStats)."""


def read(run):
    c = run.window.counters
    return 100.0 * c["real_rows"] / c["bucket_rows"] if c["bucket_rows"] \
        else None
