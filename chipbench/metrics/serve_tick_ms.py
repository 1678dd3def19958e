"""Median host wall time of one engine tick in the traced window (ms).

Source: ``DecisionEngine.tick_log`` seconds, host clock: admission, the
jitted decision program and the per-slot bookkeeping of one tick.
"""
import statistics


def read(ctx):
    ticks = ctx["tick_seconds"]
    return 1e3 * statistics.median(ticks) if ticks else None
