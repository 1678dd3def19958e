"""Median time from a request's due time to the start of the tick that admits it (ms).

Host clock, over the requests admitted in the traced window: the wait in
the engine's queue, before any decision work.
"""
import statistics


def read(ctx):
    waits = ctx["queue_seconds"]
    return 1e3 * statistics.median(waits) if waits else None
