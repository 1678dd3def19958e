"""Seconds of set-up spent tracing Python to jaxprs, before the traced window (s).

Host clock: the union of the intervals of
``/jax/core/compile/jaxpr_trace_duration`` from the program's
``jax.monitoring`` listener, so nested events count once, as snapshot when
the traced window starts (``phase_time``).  Absent where the program keeps
no such snapshot.
"""
import phase_time


def read(ctx):
    return phase_time.setup_seconds("trace")
