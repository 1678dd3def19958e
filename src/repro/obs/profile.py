"""Profiler hooks: trace capture, named phases, set-up stages, HLO-cost summaries.

Ways to see *why* a fused run is slow, all attached to the run record or
read back after a traced window rather than printed and lost:

  * `profile_trace(dir)` — a context manager around ``jax.profiler.trace``
    writing a TensorBoard/Perfetto trace directory (a profiler that cannot
    start raises: a ``--profile`` run never ends without its trace).  It
    snapshots the set-up stages when entered (`last_trace`).
  * Named phases — the runners put every op of a training iteration under
    one of the `PHASES` scopes (``jax.named_scope``), which reach the
    optimized HLO's ``op_name`` metadata.  `op_phases` maps a compiled
    program's instructions to their phase, and `phase_map` does so for the
    programs the runners registered (`register_program`), so a device
    trace's per-op times (named by instruction) sum per phase.
  * Set-up stages — one `jax.monitoring` listener, installed when this
    module is imported, keeps the wall intervals of jaxpr tracing,
    lowering and compilation (or loading from the persistent cache) and
    counts compiles and cache hits/misses (`stages`).  `RetraceCounter`
    reads the same intervals around a region: a steady-state region that
    re-traces is a bug (shape drift, non-hashable static args).
  * `roofline_summary(hlo_text)` — the `repro.roofline` trip-count-aware
    cost of a compiled program (FLOPs / bytes / collective traffic), the
    per-program companion to the profiler's timeline.
"""
from __future__ import annotations

import collections
import contextlib
import pathlib
import re
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from repro.roofline.hlo_cost import module_cost

# The runners' phases of one training iteration, in order: every op of the
# iteration body runs under exactly one of these scopes.
PHASES = ("act", "env_step", "observe", "update")
# Scopes inside the on-policy update (advantages, minibatch shuffle and
# gathers, gradient, optimizer step), and around the interleaved evaluator.
UPDATE_PARTS = ("advantage", "minibatch", "grad", "optimizer")
EVAL = "eval"

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
MLIR_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

# Each set-up stage and the duration events whose wall intervals it unites.
# Trace events nest (an outer jit's trace holds its inner jits') and the
# cache read may run inside the backend-compile event, so a stage counts
# the union of its intervals, never their sum.
STAGES = {
    "trace": (TRACE_EVENT,),
    "lower": (MLIR_LOWER_EVENT,),
    "compile": (BACKEND_COMPILE_EVENT, CACHE_RETRIEVAL_EVENT),
}
_STAGE_OF = {event: stage for stage, events in STAGES.items() for event in events}

_EVENT_COUNTS: collections.Counter = collections.Counter()
_INTERVALS: Dict[str, List[Tuple[float, float]]] = {stage: [] for stage in STAGES}


def _on_event(event: str, **kwargs: Any) -> None:
    _EVENT_COUNTS[event] += 1


def _on_duration(event: str, duration_secs: float, **kwargs: Any) -> None:
    _EVENT_COUNTS[event] += 1
    stage = _STAGE_OF.get(event)
    if stage is not None:
        # events are recorded as they end
        end = time.perf_counter()
        _INTERVALS[stage].append((end - float(duration_secs), end))


# jax.monitoring offers no per-listener unregister: one listener pair for
# the life of the process, read by snapshot.
jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def union_seconds(intervals, since: float = float("-inf")) -> float:
    """Length of the union of ``(start, end)`` intervals, clipped below at ``since``."""
    total, reach = 0.0, since
    for start, end in sorted(intervals):
        start = max(start, reach)
        if end > start:
            total += end - start
            reach = end
    return total


def stages(since: float = float("-inf")) -> Dict[str, float]:
    """Wall seconds of each set-up stage and the compile counts so far.

    ``{"trace_s", "lower_s", "compile_s", "compiles", "cache_hits",
    "cache_misses"}``; with ``since`` (a ``time.perf_counter`` reading) the
    seconds count only time after it.  ``compile_s`` is backend compilation
    or loading from the persistent cache.
    """
    out: Dict[str, float] = {
        f"{stage}_s": union_seconds(_INTERVALS[stage], since) for stage in STAGES
    }
    out["compiles"] = _EVENT_COUNTS[BACKEND_COMPILE_EVENT]
    out["cache_hits"] = _EVENT_COUNTS[CACHE_HIT_EVENT]
    out["cache_misses"] = _EVENT_COUNTS[CACHE_MISS_EVENT]
    return out


class RetraceCounter:
    """Count traces/compiles (and their seconds) inside a ``with`` region.

        with RetraceCounter() as rc:
            out = program(key)
        rc.jaxpr_traces, rc.backend_compiles, rc.compile_seconds

    ``compile_seconds`` is the wall time of lowering plus compiling or
    loading from the persistent cache inside the region.  Re-enterable:
    each ``with`` takes fresh snapshots.  ``summary()`` is the dict the run
    record stores under ``"retrace"``.
    """

    def __enter__(self) -> "RetraceCounter":
        self._counts0 = dict(_EVENT_COUNTS)
        self._t0 = time.perf_counter()
        return self

    def _delta(self, event: str) -> int:
        return _EVENT_COUNTS[event] - self._counts0.get(event, 0)

    def __exit__(self, *exc) -> None:
        self.jaxpr_traces = self._delta(TRACE_EVENT)
        self.backend_compiles = self._delta(BACKEND_COMPILE_EVENT)
        self.cache_hits = self._delta(CACHE_HIT_EVENT)
        self.cache_misses = self._delta(CACHE_MISS_EVENT)
        seconds = stages(since=self._t0)
        self.compile_seconds = seconds["lower_s"] + seconds["compile_s"]

    def summary(self) -> Dict[str, float]:
        """The run-record ``retrace`` block (call after the region exits)."""
        return {
            "jaxpr_traces": int(self.jaxpr_traces),
            "backend_compiles": int(self.backend_compiles),
            "compile_seconds": float(self.compile_seconds),
            "cache_hits": int(self.cache_hits),
            "cache_misses": int(self.cache_misses),
        }


_LAST_TRACE: Optional[Dict[str, Any]] = None


@contextlib.contextmanager
def profile_trace(out_dir):
    """Capture a ``jax.profiler.trace`` into ``out_dir`` around the body.

    Yields a dict describing the capture (``{"trace_dir": ...}``), which
    also holds ``"stages_at_start"``: `stages` as the body starts, i.e.
    what set-up cost before the traced window (`last_trace` returns it
    after the body).  When the profiler cannot start, the error propagates
    and the body never runs: a profiled run that carried on untraced would
    look like success.
    """
    global _LAST_TRACE
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    info = {"trace_dir": str(out), "stages_at_start": stages()}
    with jax.profiler.trace(str(out)):
        _LAST_TRACE = info
        yield info


def last_trace() -> Optional[Dict[str, Any]]:
    """The dict the latest `profile_trace` yielded, or None before any."""
    return _LAST_TRACE


# ------------------------------------------------------------ named phases

# one HLO computation header, and one instruction line: its name and the rest
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s+=\s(.*)$")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,]+)")
_REF = re.compile(r"%([^\s,(){}=]+)")
_PARAMETER = re.compile(r"\sparameter\(\d+\)")
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def scopes_in(op_name: str, names: Sequence[str] = PHASES) -> Tuple[str, ...]:
    """The ``names`` an ``op_name`` path passes through, outermost first.

    A scope shows as a path component (``.../update/...``) or innermost in
    a transform's parentheses (``vmap(act)``, ``transpose(jvp(update))``);
    fused ops join several paths with ``;``.  A function name in the path
    (``make_ppo_system.<locals>.update``) is not a scope.
    """
    found: List[str] = []
    for component in op_name.split("/"):
        for piece in component.split(";"):
            while (wrapped := _WRAPPED.match(piece)) is not None:
                piece = wrapped.group(1)
            if piece in names and piece not in found:
                found.append(piece)
    return tuple(found)


def op_phases(hlo_text: str, names: Sequence[str] = PHASES) -> Dict[str, str]:
    """``{instruction name: scope}`` for the instructions of ``hlo_text`` under one of ``names``.

    Names carry no ``%``.  An instruction whose ``op_name`` passes through
    none of ``names`` (a loop's control) maps to nothing, and one through
    two maps to nothing either (the runners nest no phase in another).  An
    instruction with no ``op_name`` at all, a parameter aside, is one the
    compiler made: a layout or memory-space copy, or a fusion whose root
    it built.  It takes the scope of what it fuses, else of its operands,
    else of its users.
    """
    out: Dict[str, str] = {}
    made: List[str] = []  # instructions with no op_name
    calls: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    users: Dict[str, List[str]] = collections.defaultdict(list)
    members: Dict[str, List[str]] = collections.defaultdict(list)
    computation = None
    for line in hlo_text.splitlines():
        header = _COMPUTATION.match(line)
        if header:
            computation = header.group(1)
            continue
        match = _INSTRUCTION.match(line)
        if not match:
            continue
        name, rest = match.groups()
        members[computation].append(name)
        operands[name] = _REF.findall(rest.split(", metadata=", 1)[0])
        for operand in operands[name]:
            users[operand].append(name)
        called = _CALLS.search(rest)
        if called:
            calls[name] = called.group(1)
        op_name = _OP_NAME.search(rest)
        if op_name is None:
            if not _PARAMETER.search(rest):
                made.append(name)
            continue
        found = scopes_in(op_name.group(1), names)
        if len(found) == 1:
            out[name] = found[0]

    def fused(name, seen):
        """The scopes of what the computation ``name`` calls holds."""
        called = calls.get(name)
        if called is None or called in seen:
            return set()
        seen.add(called)
        found = set()
        for member in members[called]:
            found |= {out[member]} if member in out else fused(member, seen)
        return found

    changed = True
    while changed:
        changed = False
        for name in made:
            if name in out:
                continue
            inner = fused(name, set())
            near = [out[n] for n in operands[name] + users[name] if n in out]
            if len(inner) == 1 or (not inner and near):
                out[name] = inner.pop() if inner else near[0]
                changed = True
    return out


# The programs the runners built, newest last: the jitted training program
# and a thunk for its abstract arguments.  Resolved only when read.
_PROGRAMS: collections.deque = collections.deque(maxlen=4)


def register_program(fused, abstract_args: Callable[[], tuple]) -> None:
    """Remember a built program for `phase_map`; nothing is lowered here."""
    _PROGRAMS.append({"fused": fused, "abstract_args": abstract_args})


def phase_map() -> Dict[str, str]:
    """`op_phases` of every registered program's optimized HLO, united.

    Lowers and compiles each program from its abstract arguments the first
    time (served from the in-memory or persistent cache of the program's
    own run), so call it after the measured window.  The HLO text stays
    with the registration (``"text"``) for other scopes' maps.
    """
    out: Dict[str, str] = {}
    for entry in _PROGRAMS:
        if "phases" not in entry:
            compiled = entry["fused"].lower(*entry["abstract_args"]()).compile()
            entry["text"] = compiled.as_text()
            entry["phases"] = op_phases(entry["text"])
        out.update(entry["phases"])
    return out


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
