"""Tracing + metrics + export for the reconfigurable-dispatch stack.

``trace``   — span recorder (nesting, JSON export, zero-overhead disabled);
``metrics`` — counters/gauges/histograms and rolling latency percentiles;
``report``  — planned-vs-measured reconciliation (paper Table II mirror);
``prom``    — Prometheus text exposition + stdlib HTTP exporter;
``events``  — structured JSONL event log for the control planes.
"""
from . import events, metrics, prom, report, trace
from .metrics import Counter, Gauge, Histogram, LatencyWindow, MetricsRegistry
from .prom import MetricsExporter
from .report import ReconRow, format_table, reconcile, totals
from .trace import Capture, Span, Tracer, capture, span, tracer

__all__ = [
    "Capture", "Counter", "Gauge", "Histogram", "LatencyWindow",
    "MetricsExporter", "MetricsRegistry", "ReconRow", "Span", "Tracer",
    "capture", "events", "format_table", "metrics", "prom", "reconcile",
    "report", "span", "totals", "trace", "tracer",
]
