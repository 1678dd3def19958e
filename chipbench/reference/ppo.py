"""Plain IPPO and recurrent IPPO: networks, initialisation, act and the PPO update.

Written afresh from the algorithm (PPO with per-agent GAE, a clipped
surrogate, a value loss and an entropy bonus; global-norm clipping then
Adam), in straightforward ``jax.numpy`` on one seed lane at a time.  It
imports nothing of the program and takes none of its weights: the
weights are drawn from the run's key with the same initialiser
(orthogonal for the MLP layers, LeCun-normal for the core's projection,
zero biases) and key schedule the configuration's system uses.  The recurrent core is the minGRU-style linear cell,
``h_t = (1 - z_t) (1 - reset_t) h_{t-1} + z_t cand_t`` with
``z = sigmoid(x W_z + c_z)`` and ``cand = tanh(x W_h + c_h)``, unrolled one
step at a time.

``dt`` is the computing dtype.  float32 runs every matmul at
``Precision.HIGHEST``; bfloat16 (the control) keeps weights, activations
and Adam state in bfloat16 at the default precision.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

B1, B2, EPS = 0.9, 0.999, 1e-8


@dataclasses.dataclass(frozen=True)
class Spec:
    """The sizes and hyperparameters of one configuration (hashable, so static)."""

    num_agents: int
    obs_dim: int
    num_actions: int
    hidden_sizes: tuple
    recurrent: bool
    hyper: tuple  # (name, value) pairs of the PPO hyperparameters

    @classmethod
    def from_config(cls, config):
        env, sys_ = config["env_kwargs"], config["system_overrides"]
        n = env["num_agents"]
        keys = ("learning_rate", "gamma", "gae_lambda", "clip_eps", "value_coef",
                "entropy_coef", "epochs", "num_minibatches", "max_grad_norm")
        return cls(
            num_agents=n, obs_dim=6 * n, num_actions=5 + n,
            hidden_sizes=tuple(sys_["hidden_sizes"]),
            recurrent=config["system"].startswith("rec_"),
            hyper=tuple((k, sys_[k]) for k in keys),
        )


def _prec(dt):
    return lax.Precision.HIGHEST if dt == jnp.float32 else lax.Precision.DEFAULT


def dense(p, x, dt):
    return jnp.dot(x.astype(dt), p["w"].astype(dt), precision=_prec(dt)) + p["b"].astype(dt)


def mlp(p, x, dt, activate_final=False):
    n = len(p)
    for i in range(n):
        x = dense(p[f"dense_{i}"], x, dt)
        if i < n - 1 or activate_final:
            x = jax.nn.relu(x)
    return x


# --------------------------------------------------------------------- init


def _orthogonal(key, shape):
    rows, cols = shape
    a = jax.random.normal(key, (max(rows, cols), min(rows, cols)), jnp.float32)
    q, r = jnp.linalg.qr(a)
    q = q * jnp.sign(jnp.diagonal(r))
    return q.T if rows < cols else q


def _lecun_normal(key, shape):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape) / jnp.sqrt(float(shape[0]))


def _init_dense(key, n_in, n_out, init=_orthogonal):
    w_key, _ = jax.random.split(key)
    return {"w": init(w_key, (n_in, n_out)), "b": jnp.zeros((n_out,), jnp.float32)}


def _init_mlp(key, sizes):
    keys = jax.random.split(key, len(sizes) - 1)
    return {f"dense_{i}": _init_dense(k, sizes[i], sizes[i + 1]) for i, k in enumerate(keys)}


def init_params(spec, key):
    """Shared-weight actor and critic parameters from the lane's train key."""
    k_actor, k_critic = jax.random.split(key)
    obs, acts, hidden = spec.obs_dim, spec.num_actions, spec.hidden_sizes
    if not spec.recurrent:
        return {
            "actor": {"shared": _init_mlp(k_actor, (obs, *hidden, acts))},
            "critic": {"shared": _init_mlp(k_critic, (obs, *hidden, 1))},
        }
    h = hidden[-1]

    def stack(key, out):
        k_enc, k_core, k_head = jax.random.split(key, 3)
        return {
            "encoder": _init_mlp(k_enc, (obs, *hidden)),
            "core": {"proj": _init_dense(k_core, h, 2 * h, _lecun_normal)},
            "head": _init_mlp(k_head, (h, out)),
        }

    return {"actor": {"shared": stack(k_actor, acts)}, "critic": {"shared": stack(k_critic, 1)}}


def init_adam(params, dt):
    zeros = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, dt), params)
    return {"count": jnp.zeros((), jnp.int32), "mu": zeros, "nu": zeros}


# --------------------------------------------------------------- networks


def core_gates(p, z, dt):
    g = dense(p["proj"], z, dt)
    h = g.shape[-1] // 2
    gate = jax.nn.sigmoid(g[..., :h])
    return 1.0 - gate, gate * jnp.tanh(g[..., h:])


def rec_step(p, h, x, dt):
    """One act-time step of an encoder -> core -> head stack."""
    a, b = core_gates(p["core"], mlp(p["encoder"], x, dt, activate_final=True), dt)
    h = a * h.astype(dt) + b
    return h, mlp(p["head"], h, dt)


def rec_unroll(p, h0, xs, resets, dt):
    """Sequential unroll over ``(T, B, obs)`` with FIRST-row resets."""
    a, b = core_gates(p["core"], mlp(p["encoder"], xs, dt, activate_final=True), dt)
    a = a * (1.0 - resets[..., None].astype(dt))

    def body(h, ab):
        h = ab[0] * h + ab[1]
        return h, h

    h_last, hs = lax.scan(body, h0.astype(dt), (a, b))
    return h_last, mlp(p["head"], hs, dt)


# ------------------------------------------------------------------ update


def _gae(values, rewards, disc, last, lam):
    def back(carry, inp):
        gae, v_next = carry
        v, r, d = inp
        gae = r + d * v_next - v + d * lam * gae
        return (gae, v), gae

    _, adv = lax.scan(back, (jnp.zeros_like(last), last), (values, rewards, disc), reverse=True)
    return adv, adv + values


def _surrogate(h, lp_all, action, logp_old, adv, v, ret):
    lp = jnp.take_along_axis(lp_all, action[..., None], axis=-1)[..., 0]
    ratio = jnp.exp(lp - logp_old)
    adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    pg = -jnp.minimum(ratio * adv, jnp.clip(ratio, 1 - h["clip_eps"], 1 + h["clip_eps"]) * adv)
    ent = -jnp.sum(jnp.exp(lp_all) * lp_all, axis=-1)
    return jnp.mean(pg + h["value_coef"] * jnp.square(v - ret) - h["entropy_coef"] * ent)


def _adam(h, params, opt, grads, dt):
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves))
    scale = jnp.minimum(1.0, h["max_grad_norm"] / (norm + 1e-9))
    grads = jax.tree_util.tree_map(lambda g: (g * scale).astype(dt), grads)
    count = opt["count"] + 1
    mu = jax.tree_util.tree_map(lambda m, g: (B1 * m + (1 - B1) * g).astype(dt), opt["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: (B2 * v + (1 - B2) * g * g).astype(dt), opt["nu"], grads)
    c = count.astype(jnp.float32)
    bc1, bc2 = 1 - B1**c, 1 - B2**c

    def move(p, m, v):
        step = (m.astype(jnp.float32) / bc1) / (jnp.sqrt(v.astype(jnp.float32) / bc2) + EPS)
        return (p + (-h["learning_rate"] * step).astype(dt)).astype(dt)

    params = jax.tree_util.tree_map(move, params, mu, nu)
    return params, {"count": count, "mu": mu, "nu": nu}


@functools.partial(jax.jit, static_argnames=("spec", "dt"))
def ppo_update(spec, params, opt, rows, key, dt=jnp.float32):
    """One PPO update over a ``(T, B)`` rollout of one lane.

    ``rows``: obs ``(T, B, n, obs)``, next_obs_last ``(B, n, obs)``, actions,
    logp, value, reward ``(T, B, n)``, discount ``(T, B)`` and, for the
    recurrent stack, resets ``(T, B)`` and carry0 ``{actor, critic}:
    (B, n, H)``.  Returns the new params and Adam state.
    """
    h = dict(spec.hyper)
    n = spec.num_agents
    params = jax.tree_util.tree_map(lambda p: p.astype(dt), params)
    actor, critic = params["actor"]["shared"], params["critic"]["shared"]
    obs = rows["obs"].astype(dt)
    T, B = obs.shape[:2]
    disc = rows["discount"].astype(dt) * h["gamma"]

    adv, ret = [], []
    for i in range(n):
        if spec.recurrent:
            h_t, _ = rec_unroll(critic, rows["carry0"]["critic"][:, i], obs[:, :, i],
                                rows["resets"], dt)
            _, v_last = rec_step(critic, h_t, rows["next_obs_last"][:, i].astype(dt), dt)
        else:
            v_last = mlp(critic, rows["next_obs_last"][:, i].astype(dt), dt)
        a_i, r_i = _gae(rows["value"][..., i].astype(dt), rows["reward"][..., i].astype(dt),
                        disc, v_last[..., 0], h["gae_lambda"])
        adv.append(a_i)
        ret.append(r_i)
    data = {
        "obs": obs, "actions": rows["actions"], "logp": rows["logp"].astype(dt),
        "adv": jnp.stack(adv, -1), "ret": jnp.stack(ret, -1),
    }

    def loss(params, mb):
        actor, critic = params["actor"]["shared"], params["critic"]["shared"]
        total = 0.0
        for i in range(n):
            if spec.recurrent:
                _, lg = rec_unroll(actor, mb["carry0"]["actor"][:, i], mb["obs"][..., i, :],
                                   mb["resets"], dt)
                _, v = rec_unroll(critic, mb["carry0"]["critic"][:, i], mb["obs"][..., i, :],
                                  mb["resets"], dt)
            else:
                lg = mlp(actor, mb["obs"][..., i, :], dt)
                v = mlp(critic, mb["obs"][..., i, :], dt)
            total = total + _surrogate(
                h, jax.nn.log_softmax(lg), mb["actions"][..., i], mb["logp"][..., i],
                mb["adv"][..., i], v[..., 0], mb["ret"][..., i],
            )
        return total

    if spec.recurrent:
        n_mb = max(m for m in range(1, min(h["num_minibatches"], B) + 1) if B % m == 0)
        axis_len = B
        data = dict(data, resets=rows["resets"])
    else:
        n_mb = h["num_minibatches"]
        axis_len = T * B
        data = {k: v.reshape((T * B,) + v.shape[2:]) for k, v in data.items()}
    size = axis_len // n_mb

    def epoch(carry, _):
        params, opt, key = carry
        key, k_perm = jax.random.split(key)
        perm = jax.random.permutation(k_perm, axis_len)[: n_mb * size]
        if spec.recurrent:
            # whole sequences: split the env axis, keep time
            mbs = {k: jnp.moveaxis(v[:, perm].reshape((T, n_mb, size) + v.shape[2:]), 1, 0)
                   for k, v in data.items()}
            mbs["carry0"] = {k: v[perm].reshape((n_mb, size) + v.shape[1:]).astype(dt)
                             for k, v in rows["carry0"].items()}
        else:
            mbs = {k: v[perm].reshape((n_mb, size) + v.shape[1:]) for k, v in data.items()}

        def minibatch(carry, mb):
            params, opt = carry
            return _adam(h, params, opt, jax.grad(loss)(params, mb), dt), None

        (params, opt), _ = lax.scan(minibatch, (params, opt), mbs)
        return (params, opt, key), None

    (params, opt, _), _ = lax.scan(epoch, (params, opt, key), None, length=h["epochs"])
    return params, opt


@functools.partial(jax.jit, static_argnames=("spec", "dt"))
def act_outputs(spec, params, obs, carry_in, dt=jnp.float32):
    """Logits, values and new carries of one row of envs ``(B, n, obs)``."""
    actor, critic = params["actor"]["shared"], params["critic"]["shared"]
    obs = obs.astype(dt)
    if not spec.recurrent:
        return mlp(actor, obs, dt), mlp(critic, obs, dt)[..., 0], None
    h_a, lg = rec_step(actor, carry_in["actor"], obs, dt)
    h_c, v = rec_step(critic, carry_in["critic"], obs, dt)
    return lg, v[..., 0], {"actor": h_a, "critic": h_c}
