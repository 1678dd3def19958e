"""Device time of the ``env_step`` phase per env-step in training (ns).

The phase is the env step: the vectorised step, the next global state, the
episode metrics and the carry reset at episode starts.  Source: the profiler
trace's per-op self times of the ops that ``repro.obs.profile.phase_map``
puts in the phase, times the chips, over the env-steps of the traced window
(``phase_time``).  Absent where the program names no phases.
"""
import phase_time


def read(ctx):
    return phase_time.ns_per_step(ctx, "env_step")
