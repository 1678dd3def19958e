"""``repro.obs`` — streaming telemetry, run records and profiler hooks.

The observability subsystem: what a run is doing *while it runs* (the
in-jit `MetricTap` + logger sinks), what it did once it finished (the
structured `RunRecord` under ``results/runs/<run_id>/``), and why it was
slow (`profile_trace` / `RetraceCounter` / `roofline_summary`, and the
runners' named phases).  See
``docs/OBSERVABILITY.md`` for the run-record schema and workflows.
"""
from repro.obs.profile import (
    RetraceCounter,
    profile_trace,
    roofline_summary,
)
from repro.obs.record import RunRecord, default_run_id, git_sha, provenance
from repro.obs.sinks import (
    ConsoleSink,
    CsvSink,
    JsonlSink,
    Logger,
    MultiLogger,
    SeedAggregator,
    to_python,
)
from repro.obs.stream import MetricTap

__all__ = [
    "ConsoleSink",
    "CsvSink",
    "JsonlSink",
    "Logger",
    "MetricTap",
    "MultiLogger",
    "RetraceCounter",
    "RunRecord",
    "SeedAggregator",
    "default_run_id",
    "git_sha",
    "profile_trace",
    "provenance",
    "roofline_summary",
    "to_python",
]
