"""The whole training step's share of the chip's bf16 peak (percent).

Matmul FLOPs per env-step of training, forward and backward, counted from
the configuration's shapes (``counts.train_flops_per_env_step``), times the
env-steps per second of the traced window, over chips times the peak of
``peaks.json``.
"""
import counts


def read(ctx):
    flops = counts.train_flops_per_env_step(ctx["config"]) * ctx["steps_per_s"]
    return 100.0 * flops / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
