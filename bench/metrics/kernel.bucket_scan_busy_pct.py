"""Share of the device's busy time spent in the bucket-scan kernel."""
KERNEL = "bucket_scan_topk_pallas"


def read(r):
    if r.trace is None:
        return None
    seconds = r.trace.op_seconds(KERNEL)
    return 100.0 * seconds / r.trace.busy_s if seconds else None
