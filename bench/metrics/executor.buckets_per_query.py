"""Mean buckets visited per query (``SearchStats.buckets_visited``): the
paper's node accesses."""


def read(r):
    n = sum(len(b.buckets) for b in r.batches)
    return sum(float(b.buckets.sum()) for b in r.batches) / n if n else None
