"""The key schedule a seed lane of the fused training program follows.

Every random draw of a run is a function of the run's key alone.  This
restates that schedule so the reference can follow a lane: the run key
splits into one key per seed lane; a lane key splits into (train, env,
runner); each iteration splits the runner key into (next, act, update,
reset).  Agent ``i`` samples its action with ``fold_in(act, i)``; env ``e``
that ends an episode resets from ``split(reset, num_envs)[e]``; an update
at iteration ``t`` starts from ``fold_in(update_t, 0)``.
"""
from __future__ import annotations

import jax


def lane_keys(run_key, num_seeds: int):
    """One key per seed lane."""
    return jax.random.split(run_key, num_seeds)


def lane_start(lane_key):
    """(train key, runner key) of a lane: the lane key split three ways."""
    k_train, _k_env, k_runner = jax.random.split(lane_key, 3)
    return k_train, k_runner


def iteration_keys(k_runner, num_iterations: int):
    """Per-iteration (act, update, reset) keys, stacked over iterations."""

    def body(key, _):
        key, k_act, k_upd, k_reset = jax.random.split(key, 4)
        return key, (k_act, k_upd, k_reset)

    _, (k_act, k_upd, k_reset) = jax.lax.scan(body, k_runner, None, length=num_iterations)
    return k_act, k_upd, k_reset
