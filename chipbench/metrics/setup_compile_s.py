"""Seconds of set-up spent compiling, or loading from the persistent cache, before the traced window (s).

Host clock: the union of the intervals of
``/jax/core/compile/backend_compile_duration`` and
``/jax/compilation_cache/cache_retrieval_time_sec`` from the program's
``jax.monitoring`` listener, so nested events count once, as snapshot when
the traced window starts (``phase_time``).  Absent where the program keeps
no such snapshot.
"""
import phase_time


def read(ctx):
    return phase_time.setup_seconds("compile")
