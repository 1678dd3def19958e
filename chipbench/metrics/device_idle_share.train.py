"""Share of the traced training window in which no op ran on the device (percent).

Source: the profiler trace; busy is the union of device-op intervals,
averaged over the chips used (see ``devtrace``).
"""


def read(ctx):
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
