"""Share of the traced serving window in which no op ran on the device (percent).

Source: the profiler trace; busy is the union of device-op intervals
(see ``devtrace``).  Moves the first-decision tail: while the device
idles, the host's admission and tick loop sets the pace.
"""


def read(ctx):
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
