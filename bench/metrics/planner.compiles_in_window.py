"""Programs traced inside the window: the rise of the plan cache's and the
ingest executor's trace counters (each trace is a compile or a cache load)."""


def read(r):
    return r.compiles
