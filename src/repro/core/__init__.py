"""The paper's primary contribution: overlap-optimized metric-tree indexing
(DBSCAN preprocessing -> VBM/DBM/OBM overlap estimation -> decision ->
forest of BCCF trees) with a jittable, TPU-native kNN search."""
from repro.core.dbscan import DBSCANResult, dbscan, partitions_from_labels
from repro.core.decision import DecisionStats, Partition, decide
from repro.core.forest import ForestArrays, build_forest, swap_trees
from repro.core.knn import (
    DeltaView,
    DeviceForest,
    SearchStats,
    device_forest,
    knn_exact,
    knn_search_impl,
    route_eligibility,
    route_points,
)
from repro.core.overlap import (
    OverlapMethod,
    available_overlap_methods,
    ball_log_volume,
    cap_log_volume,
    dbm_rate,
    get_overlap_method,
    intersection_log_volume,
    max_neighbor_rate,
    obm_rate,
    overlap_matrix,
    register_overlap_method,
    unregister_overlap_method,
    vbm_rate,
)
from repro.core.pipeline import (
    BuildReport,
    IndexConfig,
    build_baseline_core,
    build_index_core,
    default_c_max,
    default_delta_capacity,
)

__all__ = [
    "DBSCANResult", "dbscan", "partitions_from_labels",
    "DecisionStats", "Partition", "decide",
    "ForestArrays", "build_forest", "swap_trees",
    "DeltaView", "DeviceForest", "SearchStats", "device_forest",
    "knn_exact", "knn_search_impl",
    "route_eligibility", "route_points",
    "OverlapMethod", "available_overlap_methods", "get_overlap_method",
    "register_overlap_method", "unregister_overlap_method",
    "ball_log_volume", "cap_log_volume", "dbm_rate", "intersection_log_volume",
    "max_neighbor_rate", "obm_rate", "overlap_matrix", "vbm_rate",
    "BuildReport", "IndexConfig", "build_baseline_core", "build_index_core",
    "default_c_max", "default_delta_capacity",
]
