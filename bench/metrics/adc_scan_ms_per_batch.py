"""adc_scan_ms_per_batch: device time of one call of the 4-bit ADC scan
kernel (Pallas adc_scan4_scores), averaged over its calls in the trace."""

KERNEL = "adc_scan4_scores"


def read(run):
    calls = run.trace.kernel(KERNEL) if run.trace else []
    if not calls:
        return None
    return sum(o.end - o.start for o in calls) / len(calls) / 1e6
