"""Observability: metrics registry, error monitor, prometheus exposition.

Rebuilds the reference's stats layer (SURVEY §2.7):
``antidote_stats_collector`` (/root/reference/src/antidote_stats_collector.erl:80-93)
declares prometheus counters/gauges/histograms and periodically observes
staleness; ``antidote_error_monitor`` hooks the error logger; elli serves
``/metrics`` on :3001 (/root/reference/src/antidote_sup.erl:118-128).
"""

from antidote_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NetMetrics,
    NodeMetrics,
    install_error_monitor,
    net_metrics,
)
from antidote_tpu.obs.server import MetricsServer
from antidote_tpu.obs.trace import (
    PhaseAccumulator,
    StageAccumulator,
    device_program,
    span,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NetMetrics",
    "NodeMetrics",
    "MetricsServer",
    "net_metrics",
    "install_error_monitor",
    "PhaseAccumulator",
    "StageAccumulator",
    "device_program",
    "span",
]
