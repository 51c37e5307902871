"""Streaming ingestion + overlap-driven online index maintenance.

The write path the paper's Big-IoT-Data premise needs: jittable batched
inserts into device-resident per-index delta buckets (ingest.py), searched
exactly alongside the frozen forest by core.knn's two-phase bucket scan,
with the paper's own VBM/DBM/OBM overlap heuristics re-evaluated online as
the drift trigger for hot index rebuilds (maintenance.py).  See README.md
in this directory for the ingest → monitor → rebuild lifecycle.
"""
from repro.stream.ingest import (
    DeltaBuffer,
    alloc_delta,
    delta_view,
    ingest,
    ingest_host,
    main_index_sums,
    pull_delta_meta,
    route_batch_host,
    updated_geometry,
)
from repro.stream.maintenance import (
    DriftReport,
    MaintenanceConfig,
    OverlapMonitor,
    object_assignment,
    rebuild_indexes,
)

__all__ = [
    "DeltaBuffer", "alloc_delta", "delta_view", "ingest", "ingest_host",
    "main_index_sums", "pull_delta_meta", "route_batch_host",
    "updated_geometry",
    "DriftReport", "MaintenanceConfig", "OverlapMonitor",
    "object_assignment", "rebuild_indexes",
]
