"""The check of a serving cell: served greedy episodes against the plain reference.

For each sampled request the reference resets the episode from the
request's key, and at every step takes the observation, the reference
logits of its own weights, and the decision the engine served; then it
steps the reference env with that decision.  ``decision_gap`` is the
widest gap by which a served action's reference logit lies below the
reference's best (0 where the reference would have chosen the same);
``episode_gap`` is the largest difference of a served episode's return,
and of its length, from the reference's replay of it.

The control plays the episodes itself in bfloat16 in the engine's place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import ppo, smax

NUMBERS = ("decision_gap", "episode_gap")


@functools.partial(jax.jit, static_argnames=("p_items", "spec", "steps", "dt", "follow"))
def play(p_items, spec, params, keys, actions, steps, dt=jnp.float32, follow=True):
    """Episodes from ``keys`` over ``steps`` steps.

    With ``follow`` the given ``actions`` ``(K, steps, n)`` are taken and
    the widest logit gap below the best is returned per episode; otherwise
    the greedy actions of these weights are taken (and returned).
    Returns (gap, actions, episode length, episode return) per episode.
    """
    p = dict(p_items)
    actor = params["actor"]["shared"]
    state = smax.reset(p, keys, dt)
    K = keys.shape[0]

    def body(carry, a_t):
        state, t, alive, ret, length = carry
        logits = ppo.mlp(actor, smax.observe(p, *state), dt).astype(jnp.float32)
        a = a_t if follow else jnp.argmax(logits, -1).astype(jnp.int32)
        taken = jnp.take_along_axis(logits, a[..., None], -1)[..., 0]
        gap = jnp.where(alive, jnp.max(logits.max(-1) - taken, -1), 0.0)
        (*state, t), reward, done = smax.step(p, *state, t, a)
        ret = ret + jnp.where(alive, reward.astype(jnp.float32), 0.0)
        length = length + alive
        return (tuple(state), t, alive & ~done, ret, length), (gap, a)

    init = (state, jnp.zeros((K,), jnp.int32), jnp.ones((K,), bool),
            jnp.zeros((K,), jnp.float32), jnp.zeros((K,), jnp.int32))
    (_, _, _, ret, length), (gap, acts) = jax.lax.scan(
        body, init, jnp.moveaxis(actions, 1, 0))
    return gap.max(0), jnp.moveaxis(acts, 0, 1), length, ret


def numbers(config, params, keys, actions, lengths, returns, control=False):
    """(decision_gap, episode_gap) of served episodes (or the control's own)."""
    spec = ppo.Spec.from_config(config)
    p_items = tuple(sorted(config["env_kwargs"].items()))
    steps = config["env_kwargs"]["horizon"]
    if control:
        lo = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
        _, actions, lengths, returns = play(p_items, spec, lo, keys, actions, steps,
                                            jnp.bfloat16, follow=False)
    gap, _, ref_len, ref_ret = jax.device_get(play(p_items, spec, params, keys, actions, steps))
    episode = max(float(np.abs(np.asarray(returns, np.float32) - ref_ret).max()),
                  float(np.abs(np.asarray(lengths) - ref_len).max()))
    return {"decision_gap": float(gap.max()), "episode_gap": episode}
