"""Operations and bytes the algorithms need, counted from a configuration's shapes.

Matmuls only: each dense layer of ``n_in`` by ``n_out`` costs ``2 n_in n_out``
FLOPs per row forward and twice that backward.  Element-wise work, the
scan, the env step and the optimizer are not counted.
"""
from __future__ import annotations


def _mlp_flops(sizes):
    return sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))


def forward_flops_per_agent(config):
    """(actor, critic) forward FLOPs of one agent's row."""
    n = config["env_kwargs"]["num_agents"]
    obs, acts = 6 * n, 5 + n
    hidden = list(config["system_overrides"]["hidden_sizes"])
    if not config["system"].startswith("rec_"):
        return _mlp_flops([obs, *hidden, acts]), _mlp_flops([obs, *hidden, 1])
    h = hidden[-1]
    trunk = _mlp_flops([obs, *hidden]) + 2 * h * 2 * h  # encoder + core projection
    return trunk + 2 * h * acts, trunk + 2 * h


def train_flops_per_env_step(config):
    """Matmul FLOPs one env-step of training needs, forward and backward.

    Act runs actor and critic forward once per agent.  Each of the PPO
    epochs runs them forward and backward (3x forward) over every row.  The
    bootstrap value runs the critic once per rollout on the last
    observation; the recurrent stack also re-runs the critic over the
    whole window for it.
    """
    n = config["env_kwargs"]["num_agents"]
    so = config["system_overrides"]
    actor, critic = forward_flops_per_agent(config)
    per_row = actor + critic
    flops = n * per_row * (1 + 3 * so["epochs"])
    flops += n * critic / so["rollout_len"]
    if config["system"].startswith("rec_"):
        flops += n * critic
    return flops


def scan_bytes_per_update(config, envs):
    """Bytes the linear recurrence must move in one PPO update of one lane.

    Each call of the recurrence over ``T`` steps of ``B`` sequences of width
    ``H`` reads a and b (``T B H`` each), the per-row reset (``T B``) and
    h0 (``B H``), and writes h (``T B H``), four bytes each.  The adjoint
    scan of the backward pass moves the same.  Per update: for each agent,
    actor and critic, forward and backward, over each minibatch of every
    epoch; plus one forward critic call over all envs for the bootstrap.
    """
    so = config["system_overrides"]
    n = config["env_kwargs"]["num_agents"]
    T, H = so["rollout_len"], so["hidden_sizes"][-1]
    n_mb = max(m for m in range(1, min(so["num_minibatches"], envs) + 1) if envs % m == 0)

    def call(B):
        return 4 * (3 * T * B * H + T * B + B * H)

    per_epoch = n_mb * n * 2 * 2 * call(envs // n_mb)
    return so["epochs"] * per_epoch + n * call(envs)
