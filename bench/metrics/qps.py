"""qps: requests answered inside the window, over the window."""


def read(run):
    w = run.window
    done = sum(1 for r, _ in run.completed if r.done <= w.t1)
    return done / w.seconds
