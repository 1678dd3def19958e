"""The check of an IPPO or recurrent IPPO configuration (``"reference": "ppo_check"``).

Each checked lane is followed with ``reference/ppo.py``'s networks and PPO
update; the env rows, the update's leaf gaps and the loop over lanes are
every family's (``reference/train_check.py``).

* act: the reference forward pass of the lane's own reference parameters
  gives logits, values (and, for the recurrent stack, the next carries).
  ``act_gap`` is the largest absolute difference of the stored log-prob
  of the taken action, the stored value and the stored next carry.
  ``act_margin`` is the largest gap by which the taken action's
  Gumbel-perturbed reference logit (the same Gumbel draw the lane's act
  key makes) lies below the best one: 0 where the reference would have
  sampled the same action.
* update: the reference PPO update, from its own initial weights, on the
  stored rows of each chunk, compared by ``train_check.update_numbers``.

A cell compares the numbers its limits file names.  The control runs the
same reference in bfloat16 in the program's place: its outputs go through
the same comparison.  ``mfu.train`` counts ``counts.train_flops_per_env_step``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from counts import train_flops_per_env_step  # noqa: F401  (the module's count for mfu.train)
from reference import ppo, train_check
from reference.train_check import FIRST

NUMBERS = train_check.NUMBERS + ("act_gap", "act_margin")


def act_rows(snaps, lane: int, ids):
    """What act stored for one lane: the taken action's log-prob, the value and the carry in."""
    rows = {
        "logp": train_check.agent_leaf(snaps, lane, ids, lambda r: r.extras["logp"]),
        "value": train_check.agent_leaf(snaps, lane, ids, lambda r: r.extras["value"]),
    }
    if "carry_in" in snaps[0]["rows"].extras:
        rows["carry_in"] = {
            net: train_check.agent_leaf(snaps, lane, ids,
                                        lambda r, net=net: r.extras["carry_in"].hidden[net])
            for net in ("actor", "critic")
        }
    return rows


def numbers(sweep, control=False, detail=None):
    """The compared numbers of ``sweep`` after set-up, worst over its checked lanes.

    With ``control`` the reference in bfloat16 takes the program's place;
    per-leaf gaps of the update go into ``detail`` when it is a list.
    """
    spec = ppo.Spec.from_config(sweep.cell.config)
    ids = list(sweep.system.spec.agent_ids)
    T = sweep.T

    def lane_numbers(lane):
        rows = dict(lane.rows, **act_rows(sweep.snaps[1:], lane.index, ids))
        C = rows["obs"].shape[0] // T
        chunks = [update_rows(rows, T, c, spec.recurrent) for c in range(C)]
        ref_hist, ref_mu1 = reference_chain(spec, lane.k_train, chunks, lane.k_upd, T)
        if control:
            got_hist, got_mu1 = reference_chain(spec, lane.k_train, chunks, lane.k_upd, T,
                                                jnp.bfloat16)
        else:
            got_hist, got_mu1 = lane.params, lane.mu1
        act_gap, act_margin = act_numbers(
            spec, ref_hist[:C], rows, lane.k_act, T, got_hist[:C] if control else None)
        return {"act_gap": act_gap, "act_margin": act_margin,
                **train_check.update_numbers(lane, ref_hist, ref_mu1, got_hist, got_mu1, detail)}

    return train_check.worst_over_lanes(sweep, lane_numbers, control)


# ------------------------------------------------------------------- act


@functools.partial(jax.jit, static_argnames=("spec", "dt"))
def _act_block(spec, params, obs, carry_in, k_act, dt=jnp.float32):
    """Reference act over one chunk's rows: logits, log-softmax, values, carries, Gumbels."""
    logits, values, carry = ppo.act_outputs(spec, params, obs, carry_in, dt)
    E, n, A = logits.shape[1:]
    gumbel = jax.vmap(lambda k: jnp.stack(
        [jax.random.gumbel(jax.random.fold_in(k, i), (E, A), jnp.float32) for i in range(n)], 1
    ))(k_act)
    return logits.astype(jnp.float32), values.astype(jnp.float32), carry, gumbel


def _log_softmax(x):
    x = x - x.max(-1, keepdims=True)
    return x - np.log(np.sum(np.exp(x), -1, keepdims=True))


def _act_all(spec, params, rows, k_act, T, dt=jnp.float32):
    """Reference act outputs over every checked chunk, chunk ``c`` under ``params[c]``."""
    outs = []
    for c in range(rows["obs"].shape[0] // T):
        sl = slice(c * T, (c + 1) * T)
        cin = {k: v[sl] for k, v in rows["carry_in"].items()} if spec.recurrent else None
        outs.append(jax.device_get(_act_block(spec, params[c], rows["obs"][sl], cin, k_act[sl], dt)))
    lg, v, carry, g = zip(*outs)
    carry = ({k: np.concatenate([np.asarray(x[k], np.float32) for x in carry]) for k in carry[0]}
             if spec.recurrent else None)
    return np.concatenate(lg), np.concatenate(v), carry, np.concatenate(g)


def act_numbers(spec, ref_params, rows, k_act, T, ctrl_params=None):
    """(act_gap, act_margin); ``ctrl_params`` None compares the program's stored rows."""
    lg, v, carry, g = _act_all(spec, ref_params, rows, k_act, T)
    perturbed = lg + g
    if ctrl_params is None:
        taken = rows["actions"]
        got_lp, got_v = rows["logp"], rows["value"]
        gaps = []
        if spec.recurrent:
            # the carry a row hands on is the next row's carry_in, zeroed where an episode starts
            keep = (rows["step_type"][1:] != FIRST)[:, :, None, None]
            gaps = [np.abs(rows["carry_in"][k][1:] - carry[k][:-1] * keep).max() for k in carry]
    else:
        c_lg, got_v, c_carry, _ = _act_all(spec, ctrl_params, rows, k_act, T, jnp.bfloat16)
        c_lg = c_lg.astype(np.float32)
        taken = np.argmax(c_lg + g, -1)
        got_lp = np.take_along_axis(_log_softmax(c_lg), taken[..., None], -1)[..., 0]
        gaps = [np.abs(c_carry[k] - carry[k]).max() for k in carry] if spec.recurrent else []
    ref_lp = np.take_along_axis(_log_softmax(lg), taken[..., None], -1)[..., 0]
    gaps += [np.abs(got_lp - ref_lp).max(), np.abs(np.asarray(got_v, np.float32) - v).max()]
    at_taken = np.take_along_axis(perturbed, taken[..., None], -1)[..., 0]
    return float(max(gaps)), float((perturbed.max(-1) - at_taken).max())


# ---------------------------------------------------------------- update


def reference_chain(spec, k_train, chunks, k_upd, T, dt=jnp.float32):
    """The reference's own weights before and after each checked update, and Adam after the first."""
    params = ppo.init_params(spec, k_train)
    params = jax.tree_util.tree_map(lambda x: x.astype(dt), params)
    opt = ppo.init_adam(params, dt)
    history, mu1 = [params], None
    for c, rows in enumerate(chunks):
        key = jax.random.fold_in(k_upd[(c + 1) * T - 1], 0)
        params, opt = ppo.ppo_update(spec, params, opt, rows, key, dt)
        history.append(params)
        if c == 0:
            mu1 = opt["mu"]
    return jax.device_get(history), jax.device_get(mu1)


def update_rows(rows, T, c, recurrent):
    """The rows the update of chunk ``c`` consumed."""
    sl = slice(c * T, (c + 1) * T)
    out = {k: rows[k][sl] for k in ("obs", "actions", "logp", "value", "reward", "discount")}
    out["next_obs_last"] = rows["next_obs"][sl][-1]
    if recurrent:
        out["resets"] = rows["step_type"][sl] == FIRST
        out["carry0"] = {k: v[sl][0] for k, v in rows["carry_in"].items()}
    return out
