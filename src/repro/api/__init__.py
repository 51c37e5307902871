"""Public facade for the overlap-optimized kNN index.

    from repro.api import Config, IndexConfig, OverlapIndex

    ix = OverlapIndex.build(x, Config(index=IndexConfig(method="vbm", eps=2.0)))
    res = ix.search(q, k=10)      # SearchResult(dists, ids, stats)
    ix.ingest(batch); ix.maintain()
    ix.save("index.npz"); ix2 = OverlapIndex.load("index.npz")

Overlap heuristics (the paper's VBM/DBM/OBM and any registered extension)
resolve through ``register_overlap_method`` / ``available_overlap_methods``.
"""
from repro.api.config import (
    Config,
    ConfigError,
    IndexConfig,
    LayoutConfig,
    ObsConfig,
    RoutingConfig,
    SearchConfig,
    StreamConfig,
    as_index_config,
)
from repro.api.executor import make_backend
from repro.api.index import OverlapIndex
from repro.api.plan import PlanCache, PlanKey, SearchPlan, SearchResult
from repro.core.overlap import (
    OverlapMethod,
    available_overlap_methods,
    get_overlap_method,
    register_overlap_method,
    unregister_overlap_method,
)

__all__ = [
    "Config", "ConfigError", "IndexConfig", "LayoutConfig", "ObsConfig",
    "RoutingConfig",
    "SearchConfig", "StreamConfig", "as_index_config", "make_backend",
    "OverlapIndex",
    "PlanCache", "PlanKey", "SearchPlan", "SearchResult",
    "OverlapMethod", "available_overlap_methods", "get_overlap_method",
    "register_overlap_method", "unregister_overlap_method",
]
