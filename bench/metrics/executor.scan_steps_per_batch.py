"""Mean while-loop trips of the bounded scan per search batch
(``SearchStats.steps``)."""


def read(r):
    if not r.batches:
        return None
    return sum(b.steps for b in r.batches) / len(r.batches)
