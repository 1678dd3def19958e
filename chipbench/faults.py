"""Faults planted under the timed path, to show that the check catches each one.

Each returns a copy of a ``System`` with one fault in the program's own
functions; a run built on it must come out not correct.  The benchmark's
runs never use them: ``tools/calibrate.py`` reads them on the chip and the
tests under ``chipbench/tests`` on the CPU.

* ``state_unchanged``: the training update returns the state it was given.
* ``half_batch``: the update sees only the first half of the envs' rows, the
  mean taken over those.
* ``altered_action``: the first env's first agent takes another action
  than the one its policy produced (the log-prob still that of the first).
* ``frozen_env``: serving's env step returns the state it was given.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.buffer import rollout_reset


def state_unchanged(system):
    def update(train, buffer, key):
        _, buffer, metrics = system.update(train, buffer, key)
        return train, buffer, metrics

    return dataclasses.replace(system, update=update)


def half_batch(system):
    def update(train, buffer, key):
        envs = buffer.storage.discount.shape[1]
        half = buffer._replace(storage=jax.tree_util.tree_map(
            lambda x: x[:, : envs // 2], buffer.storage))
        train, _, metrics = system.update(train, half, key)
        return train, rollout_reset(buffer), metrics

    return dataclasses.replace(system, update=update)


def altered_action(system):
    first = system.spec.agent_ids[0]
    n = system.spec.actions[first].num_values

    def select_actions(train, obs, state, carry, key, training=True):
        actions, carry, extras = system.select_actions(train, obs, state, carry, key, training)
        a = actions[first]
        actions = dict(actions, **{first: a.at[0].set((a[0] + 1) % n)})
        return actions, carry, extras

    return dataclasses.replace(system, select_actions=select_actions)


@dataclasses.dataclass(frozen=True)
class _FrozenEnv:
    env: object

    def __getattr__(self, name):
        return getattr(self.env, name)

    def step(self, state, actions):
        _, ts = self.env.step(state, actions)
        return state, ts._replace(step_type=jnp.zeros_like(ts.step_type) + 1)


def frozen_env(system):
    return dataclasses.replace(system, env=_FrozenEnv(system.env))


TRAIN = {"state_unchanged": state_unchanged, "half_batch": half_batch,
         "altered_action": altered_action}
SERVE = {"altered_action": altered_action, "frozen_env": frozen_env}
