"""fp_evals_per_query: full-precision distance evaluations per query row
served, from the per-row counter every search returns
(SearchResult.n_dist_evals), summed over the window's batches; padded rows
count, as the device computed them."""


def read(run):
    rows = sum(b["rows"] for b in run.batches)
    if not rows:
        return None
    return sum(b["dist_evals"] for b in run.batches) / rows
