"""Mean host time of the facade's plan lookup per search call (program span
``search/plan_lookup``), in microseconds."""


def read(r):
    count, seconds = r.spans.get("search/plan_lookup", (0, 0.0))
    return 1e6 * seconds / count if count else None
