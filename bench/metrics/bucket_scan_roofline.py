"""Share of its roofline that the bucket-scan kernel reached in the window.

Work from ``roofline.scan_work`` over every batch's per-query distance
counts; time from the device trace, the self time of the ops named
``bucket_scan_topk_pallas`` (the jitted wrapper of the Pallas kernel)."""
import roofline

KERNEL = "bucket_scan_topk_pallas"


def read(r):
    if r.trace is None:
        return None
    seconds = r.trace.op_seconds(KERNEL)
    if not seconds:
        return None
    itemsize = 1 if r.config.get("search", {}).get("quantize") else 4
    flops = bytes_ = 0.0
    for b in r.batches:
        f, by = roofline.scan_work(b.distances, r.config["dim"], r.traffic["k"], itemsize)
        flops, bytes_ = flops + f, bytes_ + by
    got = roofline.roofline_share(flops, bytes_, seconds, r.peaks["flops_bf16"],
                                  r.peaks["hbm_bytes_per_s"])
    if got is None:
        return None
    share, bound = got
    return share, {"bound": bound}
