"""The recurrent-scan kernel's share of its roofline in training (percent).

The recurrence is memory-bound, so its least time is the bytes it must
move over the HBM peak.  Bytes: ``counts.scan_bytes_per_update`` (what the
algorithm reads and writes, whatever implements it) times the seed lanes
and the updates in the traced window.  Kernel time: the summed device time
of the ops whose name holds `KERNEL` (the Pallas custom calls are named
after the jitted op that issues them, forward, transpose and vmap alike).
With no such op the metric is absent.
"""
import counts

KERNEL = "linear_recurrent_scan"


def read(ctx):
    seconds = sum(v for k, v in ctx["op_seconds"].items() if KERNEL in k)
    if seconds <= 0:
        return None
    moved = (counts.scan_bytes_per_update(ctx["config"], ctx["envs_per_seed"])
             * ctx["lanes"] * ctx["updates_traced"])
    return 100.0 * moved / ctx["peaks"]["hbm_bytes_per_s"] / seconds
