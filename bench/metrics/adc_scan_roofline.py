"""adc_scan_roofline: share of the roofline reached by the 4-bit ADC scan
kernel. Each call's least time is reckoned from its own batch (the
kernel's output rows) and the cell's rows, subspaces, attributes and
candidate pool (harness.roofline.adc_scan_work); the share is their sum
over the kernel's measured time."""
from harness import roofline

KERNEL = "adc_scan4_scores"


def read(run):
    calls = run.trace.kernel(KERNEL) if run.trace else []
    if not calls:
        return None
    cfg = run.cell.config
    n, l = cfg["corpus"]["rows"], cfg["corpus"]["attr_dims"]
    s, pool = cfg["index"]["pq_subspaces"], cfg["search"]["pool_size"]
    least, bounds, spent = 0.0, set(), 0
    for o in calls:
        t, bound = roofline.least_seconds(
            *roofline.adc_scan_work(o.dims[0], n, s, l, pool), run.peaks)
        least += t
        bounds.add(bound)
        spent += o.end - o.start
    print(f"adc_scan roofline: {len(calls)} calls, least {least:.6f} s "
          f"(bound by {'/'.join(sorted(bounds))}) in {spent / 1e9:.6f} s")
    return 100.0 * least / (spent / 1e9)
