"""The whole training step's share of the chip's bf16 peak (percent).

Matmul FLOPs per env-step of training, forward and backward, counted from
the configuration's shapes by its reference module
(``train_flops_per_env_step``; ``counts.py`` for (recurrent) IPPO), times
the env-steps per second of the traced window, over chips times the peak of
``peaks.json``.
"""
import harness


def read(ctx):
    count = harness.reference(ctx["config"]).train_flops_per_env_step(ctx["config"])
    flops = count * ctx["steps_per_s"]
    return 100.0 * flops / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
