"""What the check of every training family on ``smax_lite`` under ``make_anakin`` shares.

The fused program is driven from the seed through its first chunks by the
window's own call; each chunk ends in one update.  For a sample of seed
lanes, drawn from the seed, the runner (``runners/anakin_seeds.py``) keeps
what each chunk left: the rollout rows the update consumed, the parameters
after it, and the Adam state after the first.  A training configuration
names its family's reference module (``harness.reference``), which follows
each lane with its own model and update.  What any family shares is here:

* lanes: ``lanes(sweep)`` gives each checked lane's env rows, the
  program's parameters and first moment, and the keys the lane's
  iterations drew (``reference/keys.py``).
* env: from each stored state row and the stored actions, the reference
  env gives the next state, reward, discount, observations and the
  episode-start flags (a reset from the lane's reset key where an episode
  ended).  ``env_gap`` is the largest absolute difference.
* update: ``update_numbers`` compares, leaf by leaf, the norm of Adam's
  first moment after the first update (the gradients as the optimizer got
  them), ``grad1``, and the norm of each leaf's change over the checked
  updates, ``delta3``.  Each is the gap of the two norms over the larger of
  the reference leaf's norm and the median leaf's; the worst leaf counts.
  ``grad1_med`` and ``delta3_med`` are the same gaps of the median leaf.
  Leaves whose reference first moment is under a thousandth of the median
  leaf's move by round-off alone and are left out.
* ``worst_over_lanes``: each number's worst over the checked lanes.

A family's module gives ``NUMBERS`` (these and its own), ``numbers(sweep,
control=False, detail=None)`` and ``train_flops_per_env_step(config)``.
With ``control`` its reference in bfloat16 takes the program's place, and
``env_gap`` compares the reference env run in bfloat16.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from reference import keys as K
from reference import smax

FIRST = 0
NUMBERS = ("env_gap", "grad1", "delta3", "grad1_med", "delta3_med")


def lane_leaf(snaps, lane: int, get):
    """One lane's leaf ``get(rows)`` over the checked chunks, time-major ``(C*T, E, ...)``."""
    return np.concatenate([np.asarray(get(s["rows"]))[lane] for s in snaps], axis=0)


def agent_leaf(snaps, lane: int, ids, get):
    """``lane_leaf`` of a per-agent leaf ``{agent: (L, T, E, ...)}``, the agents on axis 2."""
    return lane_leaf(snaps, lane, lambda r: np.stack([np.asarray(get(r)[a]) for a in ids], axis=3))


def env_rows(snaps, lane: int, ids):
    """The env's stored rows of one lane over the checked chunks."""
    return {
        "obs": agent_leaf(snaps, lane, ids, lambda r: r.obs),
        "next_obs": agent_leaf(snaps, lane, ids, lambda r: r.next_obs),
        "actions": agent_leaf(snaps, lane, ids, lambda r: r.actions),
        "reward": agent_leaf(snaps, lane, ids, lambda r: r.rewards),
        "discount": lane_leaf(snaps, lane, lambda r: r.discount),
        "state": lane_leaf(snaps, lane, lambda r: r.state),
        "next_state": lane_leaf(snaps, lane, lambda r: r.next_state),
        "step_type": lane_leaf(snaps, lane, lambda r: r.step_type),
    }


class Lane(NamedTuple):
    """One checked seed lane: what the program left, and the keys its iterations drew."""

    index: int  # among the checked lanes: the snapshots' lane axis
    seed_lane: int  # the lane in the program
    rows: dict  # env_rows
    params: list  # the program's parameters before and after each checked update
    mu1: Any  # Adam's first moment after the first update
    k_train: Any
    k_act: Any  # (C*T,) per iteration
    k_upd: Any
    k_reset: Any


def lanes(sweep):
    """Each checked lane of ``sweep`` (a ``Sweep`` after set-up) as a ``Lane``."""
    ids = list(sweep.system.spec.agent_ids)
    C = len(sweep.snaps) - 1
    for i, lane in enumerate(sweep.lanes):
        lane_key = K.lane_keys(sweep.key, sweep.S)[int(lane)]
        k_train, k_runner = K.lane_start(lane_key)
        k_act, k_upd, k_reset = K.iteration_keys(k_runner, C * sweep.T)
        yield Lane(
            index=i, seed_lane=int(lane), rows=env_rows(sweep.snaps[1:], i, ids),
            params=[jax.tree_util.tree_map(lambda x: x[i], s["params"]) for s in sweep.snaps],
            mu1=jax.tree_util.tree_map(lambda x: x[i], sweep.snaps[1]["mu1"]),
            k_train=k_train, k_act=k_act, k_upd=k_upd, k_reset=k_reset,
        )


def worst_over_lanes(sweep, lane_numbers, control=False):
    """``env_gap`` and the numbers ``lane_numbers(lane)`` gives, each the worst over the lanes."""
    env_p = dict(sweep.cell.config["env_kwargs"])
    worst = {}
    for lane in lanes(sweep):
        nums = {"env_gap": env_gap(env_p, lane.rows, lane.k_reset, control), **lane_numbers(lane)}
        for k, v in nums.items():
            worst[k] = max(worst.get(k, 0.0), v)
    return worst


# ------------------------------------------------------------------- env


@functools.partial(jax.jit, static_argnames=("p_items", "dt"))
def reference_env(p_items, state, actions, reset_keys, dt=jnp.float32):
    """Reference next state, reward, done and observations of stored state rows.

    ``state`` ``(R, E, 6n)``, ``actions`` ``(R, E, n)``, ``reset_keys``
    ``(R,)``: the lane's per-iteration reset keys.
    """
    p = dict(p_items)
    E = state.shape[1]

    def body(t, inp):
        gs, act, k_reset = inp
        parts = smax.split_state(p, gs, dt)
        (*nxt, t_next), reward, done = smax.step(p, *parts, t, act)
        fresh = smax.reset(p, jax.random.split(k_reset, E), dt)
        nxt = [jnp.where(done.reshape(done.shape + (1,) * (x.ndim - 1)), f, x)
               for x, f in zip(nxt, fresh)]
        next_state = smax.join_state(p, *nxt)
        out = (next_state, reward, done, smax.observe(p, *parts), smax.observe(p, *nxt))
        return jnp.where(done, 0, t_next), out

    _, out = jax.lax.scan(body, jnp.zeros((E,), jnp.int32), (state, actions, reset_keys))
    return out


def env_gap(p, rows, k_reset, control=False):
    """Largest gap of the env rows against the reference: the program's, or the control's."""
    p_items = tuple(sorted(p.items()))
    ref = reference_env(p_items, rows["state"], rows["actions"], k_reset)
    ref = [np.asarray(x, np.float32) for x in ref]
    ref_next, ref_reward, ref_done, ref_obs, ref_next_obs = ref
    if not control:
        got_next, got_reward = rows["next_state"], rows["reward"]
        got_disc, got_obs, got_next_obs = rows["discount"], rows["obs"], rows["next_obs"]
        # continuity: each row starts where the one before it ended
        cont = np.max(np.abs(rows["state"][1:] - rows["next_state"][:-1]), initial=0.0)
        first = rows["step_type"] == FIRST
    else:
        out = [np.asarray(x, np.float32) for x in reference_env(
            p_items, rows["state"], rows["actions"], k_reset, jnp.bfloat16)]
        got_next, got_reward, got_done, got_obs, got_next_obs = out
        got_reward = np.repeat(got_reward[..., None], rows["reward"].shape[-1], -1)
        got_disc = 1.0 - got_done
        cont = 0.0
        first = np.concatenate([np.ones_like(got_done[:1]), got_done[:-1]], 0) > 0
    want_first = np.concatenate([np.ones_like(ref_done[:1]), ref_done[:-1]], 0) > 0
    gaps = [
        np.abs(got_next - ref_next).max(),
        np.abs(got_reward - ref_reward[..., None]).max(),
        np.abs(got_disc - (1.0 - ref_done)).max(),
        np.abs(got_obs - ref_obs).max(),
        np.abs(got_next_obs - ref_next_obs).max(),
        float(np.any(first != want_first)),
        cont,
    ]
    return float(max(gaps))


# ---------------------------------------------------------------- update


def leaf_norms(tree):
    return np.array([float(np.linalg.norm(np.asarray(x, np.float32).ravel()))
                     for x in jax.tree_util.tree_leaves(tree)])


def leaf_gaps(got, want, keep):
    """Each kept leaf's gap of norms over the larger of its reference norm and the median leaf's."""
    scale = np.maximum(want, np.median(want[keep]))
    return np.where(keep, np.abs(got - want) / scale, 0.0)


def update_numbers(lane, ref_hist, ref_mu1, got_hist, got_mu1, detail=None):
    """``grad1``, ``delta3`` and their median-leaf forms, the produced against the reference.

    ``*_hist``: the parameters before and after each checked update (the
    same tree on both sides, host arrays); ``*_mu1``: Adam's first moment
    after the first.  Per-leaf gaps go into ``detail`` when it is a list.
    """
    C = len(ref_hist) - 1
    want_mu = leaf_norms(ref_mu1)
    keep = want_mu >= 1e-3 * np.median(want_mu)
    delta = lambda h: jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float32) - np.asarray(b, np.float32), h[C], h[0])
    grad = leaf_gaps(leaf_norms(got_mu1), want_mu, keep)
    change = leaf_gaps(leaf_norms(delta(got_hist)), leaf_norms(delta(ref_hist)), keep)
    if detail is not None:
        names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(ref_mu1)]
        detail.append({"lane": lane.seed_lane, "leaves": names, "grad1": grad.tolist(),
                       "delta3": change.tolist(), "ref_mu_norm": want_mu.tolist()})
    return {
        "grad1": float(grad.max()),
        "delta3": float(change.max()),
        "grad1_med": float(np.median(grad[keep])),
        "delta3_med": float(np.median(change[keep])),
    }
