"""Device time of the ``update`` phase per env-step in training (ns).

The phase is the gated update: the readiness test and the trainer update(s).
Source: the profiler trace's per-op self times of the ops that
``repro.obs.profile.phase_map`` puts in the phase, times the chips, over the
env-steps of the traced window (``phase_time``).  Absent where the program
names no phases.
"""
import phase_time


def read(ctx):
    return phase_time.ns_per_step(ctx, "update")
