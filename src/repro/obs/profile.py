"""Profiler hooks: trace capture, retrace counting, HLO-cost summaries.

Three ways to see *why* a fused run is slow, all attached to the run
record rather than printed and lost:

  * `profile_trace(dir)` — a context manager around ``jax.profiler.trace``
    writing a TensorBoard/Perfetto trace directory (a profiler that cannot
    start raises: a ``--profile`` run never ends without its trace).
  * `RetraceCounter` — accidental recompiles surface as telemetry, not
    mystery slowness: jax emits `jax.monitoring` duration events per
    jaxpr trace / backend compile, and the counter snapshots them around a
    region.  A steady-state region that re-traces is a bug (shape drift,
    non-hashable static args); the total compile seconds also give the
    run record its compile-vs-steady-state wall split.
  * `roofline_summary(hlo_text)` — the `repro.roofline` trip-count-aware
    cost of a compiled program (FLOPs / bytes / collective traffic), the
    per-program companion to the profiler's timeline.

jax.monitoring offers no per-listener unregister, so one module-level
listener pair is installed on first use and counters are read by
snapshot-delta — cheap enough to leave on for the life of the process.
"""
from __future__ import annotations

import collections
import contextlib
import pathlib
from typing import Any, Dict

import jax

from repro.roofline.hlo_cost import module_cost

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
MLIR_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# the stages summed into compile_seconds: lowering + backend compilation.
# jaxpr tracing is excluded on purpose — trace events nest (an outer jit's
# trace contains its inner jits'), so summing them double-counts wall time.
_COMPILE_STAGE_EVENTS = (MLIR_LOWER_EVENT, BACKEND_COMPILE_EVENT)

_EVENT_COUNTS: collections.Counter = collections.Counter()
_EVENT_SECONDS: Dict[str, float] = collections.defaultdict(float)
_INSTALLED = False


def _install() -> None:
    global _INSTALLED
    if _INSTALLED:
        return

    def on_event(event: str, **kwargs: Any) -> None:
        _EVENT_COUNTS[event] += 1

    def on_duration(event: str, duration_secs: float, **kwargs: Any) -> None:
        _EVENT_COUNTS[event] += 1
        _EVENT_SECONDS[event] += float(duration_secs)

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    _INSTALLED = True


class RetraceCounter:
    """Count traces/compiles (and their seconds) inside a ``with`` region.

        with RetraceCounter() as rc:
            out = program(key)
        rc.jaxpr_traces, rc.backend_compiles, rc.compile_seconds

    Re-enterable: each ``with`` takes fresh snapshots.  ``summary()`` is
    the dict the run record stores under ``"retrace"``.
    """

    def __enter__(self) -> "RetraceCounter":
        _install()
        self._counts0 = dict(_EVENT_COUNTS)
        self._secs0 = dict(_EVENT_SECONDS)
        return self

    def __exit__(self, *exc) -> None:
        self.jaxpr_traces = _EVENT_COUNTS[TRACE_EVENT] - self._counts0.get(
            TRACE_EVENT, 0
        )
        self.backend_compiles = _EVENT_COUNTS[
            BACKEND_COMPILE_EVENT
        ] - self._counts0.get(BACKEND_COMPILE_EVENT, 0)
        self.compile_seconds = sum(
            _EVENT_SECONDS[event] - self._secs0.get(event, 0.0)
            for event in _COMPILE_STAGE_EVENTS
        )

    def summary(self) -> Dict[str, float]:
        """The run-record ``retrace`` block (call after the region exits)."""
        return {
            "jaxpr_traces": int(self.jaxpr_traces),
            "backend_compiles": int(self.backend_compiles),
            "compile_seconds": float(self.compile_seconds),
        }


@contextlib.contextmanager
def profile_trace(out_dir):
    """Capture a ``jax.profiler.trace`` into ``out_dir`` around the body.

    Yields a dict describing the capture (``{"trace_dir": ...}``).  When
    the profiler cannot start, the error propagates and the body never
    runs: a profiled run that carried on untraced would look like success.
    """
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    with jax.profiler.trace(str(out)):
        yield {"trace_dir": str(out)}


def roofline_summary(hlo_text: str) -> Dict[str, Any]:
    """The `repro.roofline` HLO-cost block for a compiled program.

    Trip-count-aware (scan bodies scaled by their trip counts — see
    `repro.roofline.hlo_cost`), so the figures cover the *whole* fused
    training run, not one loop body.
    """
    cost = module_cost(hlo_text)
    return {
        "hlo_flops": float(cost.flops),
        "hlo_bytes": float(cost.bytes),
        "collective_bytes": float(cost.collective_bytes),
        "collectives": {k: float(v) for k, v in cost.collectives.items()},
    }
