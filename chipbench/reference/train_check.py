"""The check of a training cell: what the timed program produced, against the plain reference.

The fused program is driven from the seed through its first chunks by the
window's own call; each chunk ends in one PPO update.  For a sample of
seed lanes, drawn from the seed, the runner keeps what each chunk left:
the rollout rows the update consumed, the parameters after it, and the
Adam state after the first.  The reference then follows the lane:

* env: from each stored state row and the stored actions, the reference
  env gives the next state, reward, discount, observations and the
  episode-start flags (a reset from the lane's reset key where an episode
  ended).  ``env_gap`` is the largest absolute difference.
* act: the reference forward pass of the lane's own reference parameters
  gives logits, values (and, for the recurrent stack, the next carries).
  ``act_gap`` is the largest absolute difference of the stored log-prob
  of the taken action, the stored value and the stored next carry.
  ``act_margin`` is the largest gap by which the taken action's
  Gumbel-perturbed reference logit (the same Gumbel draw the lane's act
  key makes) lies below the best one: 0 where the reference would have
  sampled the same action.
* update: the reference PPO update, from its own initial weights, on the
  stored rows of each chunk.  ``grad1`` compares, leaf by leaf, the norm of
  Adam's first moment after the first update (the gradients as the
  optimizer got them); ``delta3`` the norm of each leaf's change over the
  three updates.  Each is the gap of the two norms over the larger of the
  reference leaf's norm and the median leaf's; the worst leaf counts.
  ``grad1_med`` and ``delta3_med`` are the same gaps of the median leaf.
  Leaves whose reference first moment is under a thousandth of the median
  leaf's move by round-off alone and are left out.

A cell compares the numbers its limits file names.

The control runs the same reference in bfloat16 in the program's place:
its outputs go through the same comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import keys as K
from reference import ppo, smax

FIRST = 0
NUMBERS = ("env_gap", "act_gap", "act_margin", "grad1", "delta3", "grad1_med", "delta3_med")


def _stack_agents(d, ids):
    """Per-agent leaves ``(L, T, E, ...)`` stacked to ``(L, T, E, n, ...)``."""
    return np.stack([np.asarray(d[a]) for a in ids], axis=3)


def lane_rows(snaps, lane: int, ids):
    """The stored rows of one lane over the checked chunks, time-major ``(C*T, E, ...)``."""
    def cat(get):
        return np.concatenate([np.asarray(get(s["rows"]))[lane] for s in snaps], axis=0)

    rows = {
        "obs": cat(lambda r: _stack_agents(r.obs, ids)),
        "next_obs": cat(lambda r: _stack_agents(r.next_obs, ids)),
        "actions": cat(lambda r: _stack_agents(r.actions, ids)),
        "reward": cat(lambda r: _stack_agents(r.rewards, ids)),
        "logp": cat(lambda r: _stack_agents(r.extras["logp"], ids)),
        "value": cat(lambda r: _stack_agents(r.extras["value"], ids)),
        "discount": cat(lambda r: r.discount),
        "state": cat(lambda r: r.state),
        "next_state": cat(lambda r: r.next_state),
        "step_type": cat(lambda r: r.step_type),
    }
    if "carry_in" in snaps[0]["rows"].extras:
        hidden = lambda r, net: _stack_agents(r.extras["carry_in"].hidden[net], ids)
        rows["carry_in"] = {net: cat(lambda r, net=net: hidden(r, net)) for net in ("actor", "critic")}
    return rows


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


def leaf_norms(tree):
    return np.array([float(np.linalg.norm(np.asarray(x, np.float32).ravel()))
                     for x in jax.tree_util.tree_leaves(tree)])


def leaf_gaps(got, want, keep):
    """Each kept leaf's gap of norms over the larger of its reference norm and the median leaf's."""
    scale = np.maximum(want, np.median(want[keep]))
    return np.where(keep, np.abs(got - want) / scale, 0.0)


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


def lane_numbers(spec, env_p, run_key, num_seeds, lane, rows, produced, T, control=False,
                 detail=None):
    """The compared numbers of one seed lane.

    ``produced``: ``{"params": [p0, p1, p2, p3], "mu1": mu}`` from the
    program (host arrays, same tree as the reference's).  With ``control``
    the reference in bfloat16 takes the program's place.
    """
    C = rows["obs"].shape[0] // T
    lane_key = K.lane_keys(run_key, num_seeds)[lane]
    k_train, k_runner = K.lane_start(lane_key)
    k_act, k_upd, k_reset = K.iteration_keys(k_runner, C * T)
    chunks = [update_rows(rows, T, c, spec.recurrent) for c in range(C)]
    ref_hist, ref_mu1 = reference_chain(spec, k_train, chunks, k_upd, T)
    if control:
        produced_hist, produced_mu1 = reference_chain(spec, k_train, chunks, k_upd, T, jnp.bfloat16)
    else:
        produced_hist, produced_mu1 = produced["params"], produced["mu1"]
    act_gap, act_margin = act_numbers(
        spec, ref_hist[:C], rows, k_act, T, produced_hist[:C] if control else None)
    want_mu = leaf_norms(ref_mu1)
    keep = want_mu >= 1e-3 * np.median(want_mu)
    delta = lambda h: jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float32) - np.asarray(b, np.float32), h[C], h[0])
    grad = leaf_gaps(leaf_norms(produced_mu1), want_mu, keep)
    change = leaf_gaps(leaf_norms(delta(produced_hist)), leaf_norms(delta(ref_hist)), keep)
    if detail is not None:
        names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(ref_mu1)]
        detail.append({"lane": lane, "leaves": names, "grad1": grad.tolist(),
                       "delta3": change.tolist(), "ref_mu_norm": want_mu.tolist()})
    return {
        "env_gap": env_gap(env_p, rows, k_reset, control),
        "act_gap": act_gap,
        "act_margin": act_margin,
        "grad1": float(grad.max()),
        "delta3": float(change.max()),
        "grad1_med": float(np.median(grad[keep])),
        "delta3_med": float(np.median(change[keep])),
    }
