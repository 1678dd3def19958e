"""Open-loop arrivals, scheduled in wall seconds: the one generator of request traffic.

``streams`` independent users each send episode requests as a Poisson
process; the merged schedule gives every request's due time in seconds
from the start of the window, with the (stream, index) it came from.  So
that every seed offers the same amount of work, each stream's count over
the window is fixed at ``rate_per_s * seconds / streams`` and its due times
are that many uniform draws, sorted: a Poisson process conditioned on its
count.  Only numpy, seeded by the run's seed.
"""
from __future__ import annotations

import numpy as np


def poisson_schedule(seed: int, streams: int, rate_per_s: float, seconds: float):
    """Due times (sorted) and (stream, index) of every request due in ``[0, seconds)``."""
    if rate_per_s <= 0 or streams < 1 or seconds <= 0:
        raise ValueError(f"need positive rate, streams and seconds: {rate_per_s}, {streams}, {seconds}")
    rng = np.random.default_rng(seed)
    per_stream = max(1, int(round(rate_per_s * seconds / streams)))
    due = np.sort(rng.uniform(0.0, seconds, size=(streams, per_stream)), axis=1)
    stream = np.repeat(np.arange(streams), per_stream)
    index = np.tile(np.arange(per_stream), streams)
    due = due.reshape(-1)
    order = np.lexsort((stream, due))
    return due[order], stream[order], index[order]
