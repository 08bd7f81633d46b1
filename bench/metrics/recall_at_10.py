"""recall_at_10: mean recall@10 of every answered request against the
exact float64 MATCH answer (the comparison's own number)."""


def read(run):
    return run.numbers["recall_at_10"]
