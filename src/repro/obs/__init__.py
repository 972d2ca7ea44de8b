"""Telemetry plane shared by the functional DB (`repro.db`) and the timing
sim (`repro.sim`): metrics registry with deterministic SLO percentiles,
per-txn traces, collector pauses, Prometheus/JSON export, and open-loop
load generation.

Import surface is intentionally flat; see docs/ARCHITECTURE.md#observability.
"""

from .names import (FUNCTIONAL_SPANS, SIM_SPANS, STAT_NAMES, stat_metric,
                    unify_cluster_stats, unify_sim_result)
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       OccupancyMeter, StatsCounter, log_bucket_bounds)
from .trace import GcMeter, Span, Trace, Tracer
from .export import parse_prometheus, to_json, to_prometheus
from .load import bursty_arrivals, find_knee, poisson_arrivals, serve_open_loop

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "OccupancyMeter",
    "StatsCounter", "log_bucket_bounds",
    "GcMeter", "Span", "Trace", "Tracer",
    "parse_prometheus", "to_json", "to_prometheus",
    "poisson_arrivals", "bursty_arrivals", "serve_open_loop", "find_knee",
    "STAT_NAMES", "stat_metric", "unify_cluster_stats", "unify_sim_result",
    "FUNCTIONAL_SPANS", "SIM_SPANS",
]
