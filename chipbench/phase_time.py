"""Per-phase device time and set-up stages, from what the program records.

The program runs every op of a training iteration under one of its named
phases (``repro.obs.profile.PHASES``) and maps each instruction of its
optimized HLO to its phase (``profile.phase_map``); the trace names device
ops by instruction (``devtrace``'s ``op_seconds``), so the two join on the
name.  ``profile_trace`` snapshots the set-up stages as the traced window
starts (``profile.last_trace``).  A program that records neither gives
no reading: the readers return None.
"""
from __future__ import annotations


def _profile(attr):
    try:
        from repro.obs import profile
    except ImportError:
        return None
    return getattr(profile, attr, None)


def env_steps(ctx) -> int:
    """Env-steps in the traced window: updates x rollout x seed lanes x envs per seed."""
    rollout = ctx["config"]["system_overrides"]["rollout_len"]
    return ctx["updates_traced"] * rollout * ctx["lanes"] * ctx["envs_per_seed"]


def ns_per_step(ctx, phase):
    """Device ns per env-step of the ops ``phase_map`` puts in ``phase``, over all chips."""
    phase_map = _profile("phase_map")
    if phase_map is None:
        return None
    phases = phase_map()
    seconds = [v for k, v in ctx["op_seconds"].items() if phases.get(k.lstrip("%")) == phase]
    if not seconds:
        return None
    return 1e9 * sum(seconds) * ctx["chips"] / env_steps(ctx)


def setup_seconds(stage):
    """Wall seconds of set-up ``stage`` (trace, lower, compile) before the traced window."""
    last_trace = _profile("last_trace")
    info = last_trace() if last_trace is not None else None
    if not info or "stages_at_start" not in info:
        return None
    return info["stages_at_start"][f"{stage}_s"]
