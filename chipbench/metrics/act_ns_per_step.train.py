"""Device time of the ``act`` phase per env-step in training (ns).

The phase is acting: the reset-key refresh, the global state and the
policy's action selection.  Source: the profiler trace's per-op self times
of the ops that ``repro.obs.profile.phase_map`` puts in the phase, times the
chips, over the env-steps of the traced window (``phase_time``).  Absent
where the program names no phases.
"""
import phase_time


def read(ctx):
    return phase_time.ns_per_step(ctx, "act")
