"""On-policy PPO family: IPPO / MAPPO, feed-forward and recurrent.

The flagship systems of JAX-Mava, expressed as `repro.core.system.System`
instances so they run through the same three runners (python loop, Anakin,
shard_map) and the fused evaluator as every other system. The dataset half
is the rollout accumulator (`repro.core.buffer.RolloutState`): the executor
streams transitions — with behaviour log-probs and values riding along in
`Transition.extras` — into a time-major `rollout_len` buffer, and the
`rollout_len`-gated `update` consumes the whole trajectory (per-agent GAE,
PPO epochs with clipped objective + entropy bonus) and resets it.

Four variants from two axes:

* critic input — IPPO conditions each agent's critic on its own
  observation; MAPPO's centralised critic conditions on the global
  environment state (CTDE);
* memory — the feed-forward variants (``ippo`` / ``mappo``) use plain MLP
  actors; the recurrent variants (``rec_ippo`` / ``rec_mappo``) put a
  memory core between an MLP encoder and each head (a `repro.nn.ScannedRNN`
  GRU by default, or the fused-associative-scan `LinearScannedRNN` via
  ``PPOConfig.recurrent_core="linear"``), threading a typed `Carry`
  through the runners.  The paper's headline
  systems are the recurrent ones: on partially observable tasks
  (switch_game, speaker_listener, rware) a feed-forward policy is the
  wrong model class.

The recurrent trainer follows the shared memory-core protocol
(`repro.nn.recurrent`): the executor stores its incoming carry per step in
``Transition.extras["carry_in"]``, the update re-runs actor and critic
cores over the stored window from the *exact* stored start carry
(`window_start_carry` — on-policy windows never span a parameter update),
resets memory at stored FIRST rows inside the BPTT scan, and minibatches
over the env axis so sequences stay intact (the JaxMARL recurrent-PPO
idiom), instead of the feed-forward path's time-flattened shuffling.

Advantages are computed from *per-agent* rewards, so general-sum scenarios
(e.g. batched matrix games with distinct payoffs) are handled correctly.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro import optim
from repro.core.buffer import (
    rollout_add,
    rollout_init,
    rollout_ready,
    rollout_reset,
    rollout_take,
)
from repro.core.system import System
from repro.core.types import Carry, TrainState, Transition
from repro.envs.api import EnvSpec, StepType
from repro.nn import MLP
from repro.nn.recurrent import make_core, window_start_carry
from repro.obs.profile import UPDATE_PARTS
from repro.systems.vtrace import vtrace_advantages

# Scopes of the update's parts on the device timeline (metadata only).
_ADVANTAGE, _MINIBATCH, _GRAD, _OPTIMIZER = UPDATE_PARTS


@dataclasses.dataclass(frozen=True)
class PPOConfig:
    """Hyperparameters shared by all four PPO variants.

    ``hidden_sizes`` shapes the MLP trunk; the recurrent variants reuse it
    as the encoder widths and put a GRU core of ``hidden_sizes[-1]`` units
    between encoder and head.  ``num_minibatches`` divides the flattened
    ``rollout_len * num_envs`` rows for the feed-forward variants and the
    ``num_envs`` sequence axis for the recurrent ones (clamped to the
    number of envs, so the single-env python loop still trains).

    ``recurrent_core`` selects the memory core behind the recurrent
    variants (ignored by the feed-forward ones): ``"gru"`` is the
    `ScannedRNN` reference path every seed milestone is pinned on;
    ``"linear"`` swaps in the gated-linear `LinearScannedRNN`, whose BPTT
    unrolls run as one fused associative scan
    (`repro.kernels.recurrent_scan` — the throughput path, see
    docs/KERNELS.md).

    ``use_vtrace`` swaps GAE for V-trace off-policy corrected advantages
    (`repro.systems.vtrace`), re-evaluating values and log-probs under the
    *current* params and importance-weighting against the stored behaviour
    log-probs — required for correctness when trajectories are collected
    by stale-snapshot actors (the async runner with
    ``param_sync_every > 1``, see docs/DISTRIBUTED.md); a no-op
    generalisation of GAE when behaviour == current (exact at
    ``gae_lambda = 1``).  ``vtrace_clip_rho`` / ``vtrace_clip_c`` are the
    IMPALA truncation levels for the importance ratios and the trace
    coefficients.
    """

    hidden_sizes: Sequence[int] = (64, 64)
    learning_rate: float = 3e-4
    gamma: float = 0.99
    gae_lambda: float = 0.95
    clip_eps: float = 0.2
    value_coef: float = 0.5
    entropy_coef: float = 0.01
    epochs: int = 4
    num_minibatches: int = 4
    max_grad_norm: float = 0.5
    rollout_len: int = 128
    shared_weights: bool = True
    recurrent_core: str = "gru"
    distributed_axis: str | None = None
    use_vtrace: bool = False
    vtrace_clip_rho: float = 1.0
    vtrace_clip_c: float = 1.0


def _pack_rows(tree):
    """Pack a pytree of ``(N, ...)`` leaves so one gather permutes its rows.

    Each 4-byte leaf is flattened to ``width`` columns and bit-cast to
    int32, and the columns are concatenated into one ``(N, F)`` array: on
    the TPU a gather costs about the same per index whatever the row's
    width, so one gather of packed rows replaces one gather per leaf.
    Bit-casting keeps every bit (no float op ever sees an int's pattern),
    so the unpacked rows equal the leaves' own rows bitwise.  A leaf of
    another item size stays apart and is gathered on its own.

    Returns ``(packed, others, unpack)``: ``unpack(rows, other_rows)``
    rebuilds the tree from rows of ``packed`` and the matching rows of each
    leaf of ``others``, under any common leading shape.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    n = leaves[0].shape[0]
    cols, others, slots, offset = [], [], [], 0
    for x in leaves:
        if x.dtype.itemsize != 4:
            slots.append(len(others))
            others.append(x)
            continue
        width = math.prod(x.shape[1:])
        cols.append(jax.lax.bitcast_convert_type(x.reshape(n, width).T, jnp.int32))
        slots.append((offset, width, x.shape[1:], x.dtype))
        offset += width
    features = jnp.concatenate(cols) if cols else jnp.zeros((0, n), jnp.int32)
    # Build the columns feature-major and transpose once.  Left free, XLA
    # gives the concatenation the gather's row-major layout, which makes
    # each scalar leaf an (N, 1) column padded to a whole tile on the TPU.
    features = with_layout_constraint(features, Layout(major_to_minor=(0, 1)))
    packed = features.T

    def unpack(rows, other_rows):
        """The tree from packed rows ``(..., F)`` and the others' rows."""
        lead = rows.shape[:-1]
        # slice leaves out of the feature-major copy: whole rows, no padding
        features = jnp.moveaxis(rows, -1, 0)
        out = []
        for slot in slots:
            if isinstance(slot, int):
                out.append(other_rows[slot])
                continue
            start, width, shape, dtype = slot
            col = jnp.moveaxis(features[start:start + width], 0, -1)
            out.append(jax.lax.bitcast_convert_type(col.reshape(lead + shape), dtype))
        return treedef.unflatten(out)

    return packed, others, unpack


def _shuffled_minibatches(packed, others, unpack, key, num_minibatches):
    """One epoch's minibatches: rows permuted by ``key``, cut to whole minibatches.

    ``packed``, ``others`` and ``unpack`` come from `_pack_rows`.  The
    leaves come out shaped ``(num_minibatches, N // num_minibatches, ...)``,
    bitwise equal to gathering each leaf by the same permutation.
    """
    n = packed.shape[0]
    mb_size = n // num_minibatches
    perm = jax.random.permutation(key, n)[: mb_size * num_minibatches]
    lead = (num_minibatches, mb_size)
    rows = packed[perm].reshape(lead + packed.shape[1:])
    other_rows = [x[perm].reshape(lead + x.shape[1:]) for x in others]
    return unpack(rows, other_rows)


def _update_rows(traj: Transition, adv, ret, centralised: bool):
    """The rollout's ``(T*B, ...)`` rows that the feed-forward PPO epochs shuffle.

    ``state`` is kept only for a centralised critic (MAPPO); IPPO's loss
    never reads it, so it is None there and packs no columns.
    """
    T, B = traj.discount.shape
    data = dict(
        obs=traj.obs,
        state=traj.state if centralised else None,
        actions=traj.actions,
        logp=traj.extras["logp"],
        advantage=adv,
        returns=ret,
    )
    return jax.tree_util.tree_map(lambda x: x.reshape((T * B,) + x.shape[2:]), data)


def _make_gae(cfg: PPOConfig, ids):
    """Per-agent GAE over a time-major (T, B) trajectory (shared by all variants)."""

    def gae(traj: Transition, last_values):
        """Per-agent advantages and returns for one stored trajectory."""
        adv, ret = {}, {}
        values = traj.extras["value"]
        disc = traj.discount * cfg.gamma
        for a in ids:
            v = values[a]          # (T, B) behaviour values
            r = traj.rewards[a]    # (T, B) this agent's reward

            def back(carry, inp):
                gae_t, v_next = carry
                v_t, r_t, d_t = inp
                delta = r_t + d_t * v_next - v_t
                gae_t = delta + d_t * cfg.gae_lambda * gae_t
                return (gae_t, v_t), gae_t

            (_, _), advs = jax.lax.scan(
                back,
                (jnp.zeros_like(last_values[a]), last_values[a]),
                (v, r, disc),
                reverse=True,
            )
            adv[a] = advs
            ret[a] = advs + v
        return adv, ret

    return gae


def _ppo_surrogate(cfg: PPOConfig, lp, lp_all, logp_old, adv, v, returns):
    """The clipped PPO objective for one agent's batch of rows (any shape)."""
    ratio = jnp.exp(lp - logp_old)
    adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    pg = -jnp.minimum(
        ratio * adv,
        jnp.clip(ratio, 1 - cfg.clip_eps, 1 + cfg.clip_eps) * adv,
    )
    v_loss = jnp.square(v - returns)
    ent = -jnp.sum(jnp.exp(lp_all) * lp_all, axis=-1)
    return jnp.mean(pg + cfg.value_coef * v_loss - cfg.entropy_coef * ent)


# ------------------------------------------------------------- feed-forward


def make_ppo_networks(env, cfg: PPOConfig, centralised: bool):
    """Build the feed-forward per-agent actor/critic MLPs (shared if homogeneous)."""
    spec: EnvSpec = env.spec()
    ids = list(spec.agent_ids)
    num_actions = {a: spec.actions[a].num_values for a in ids}
    obs_dims = {a: spec.observations[a].shape[0] for a in ids}
    state_dim = spec.state.shape[0]

    homogeneous = len(set((obs_dims[a], num_actions[a]) for a in ids)) == 1
    share = cfg.shared_weights and homogeneous

    actors = {a: MLP((obs_dims[a], *cfg.hidden_sizes, num_actions[a])) for a in ids}
    critic_in = {a: (state_dim if centralised else obs_dims[a]) for a in ids}
    critics = {a: MLP((critic_in[a], *cfg.hidden_sizes, 1)) for a in ids}

    def init(key):
        """Initialise actor/critic params (shared across agents if homogeneous)."""
        ka, kc = jax.random.split(key)
        if share:
            return {
                "actor": {"shared": actors[ids[0]].init(ka)},
                "critic": {"shared": critics[ids[0]].init(kc)},
            }
        kas = jax.random.split(ka, len(ids))
        kcs = jax.random.split(kc, len(ids))
        return {
            "actor": {a: actors[a].init(k) for a, k in zip(ids, kas)},
            "critic": {a: critics[a].init(k) for a, k in zip(ids, kcs)},
        }

    def logits(params, agent, obs):
        """Actor logits for one agent's observation batch."""
        p = params["actor"]["shared"] if share else params["actor"][agent]
        return actors[agent].apply(p, obs)

    def value(params, agent, critic_obs):
        """Critic value for one agent's (obs or state) batch."""
        p = params["critic"]["shared"] if share else params["critic"][agent]
        return critics[agent].apply(p, critic_obs)[..., 0]

    return ids, num_actions, init, logits, value


def make_ppo_system(env, cfg: PPOConfig, centralised: bool, name: str) -> System:
    """Build a feed-forward PPO `System` (IPPO or MAPPO by critic input)."""
    spec: EnvSpec = env.spec()
    ids, num_actions, init_params, logits_fn, value_fn = make_ppo_networks(
        env, cfg, centralised
    )
    opt = optim.chain(
        optim.clip_by_global_norm(cfg.max_grad_norm),
        optim.adamw(cfg.learning_rate),
    )

    def critic_obs(obs, state, agent):
        """The critic input: global state (MAPPO) or own obs (IPPO)."""
        return state if centralised else obs[agent]

    def init_train(key):
        """Initialise the `TrainState` (params, targets, optimizer, steps)."""
        params = init_params(key)
        return TrainState(params, params, opt.init(params), jnp.zeros((), jnp.int32))

    # ------------------------------------------------------------ executor

    def select_actions(train: TrainState, obs, state, carry, key, training=True):
        """Sample actions; log-probs/values ride along in extras."""
        params = train.params
        if not training:
            # greedy execution (fused evaluator): no log-probs/values needed
            actions = {
                a: jnp.argmax(logits_fn(params, a, obs[a]), axis=-1).astype(
                    jnp.int32
                )
                for a in ids
            }
            return actions, carry, {}
        actions, logps, values = {}, {}, {}
        for i, a in enumerate(ids):
            lg = logits_fn(params, a, obs[a])
            act_ = jax.random.categorical(jax.random.fold_in(key, i), lg)
            lp = jax.nn.log_softmax(lg)
            logps[a] = jnp.take_along_axis(lp, act_[..., None], axis=-1)[..., 0]
            actions[a] = act_.astype(jnp.int32)
            values[a] = value_fn(params, a, critic_obs(obs, state, a))
        return actions, carry, {"logp": logps, "value": values}

    def initial_carry(batch_shape):
        """The executor's initial memory for a ``batch_shape`` of envs."""
        del batch_shape
        return ()

    # ------------------------------------------------------------- trainer

    gae = _make_gae(cfg, ids)

    def loss_fn(params, minibatch):
        """Summed per-agent clipped PPO surrogate over one minibatch."""
        total = 0.0
        metrics = {}
        for a in ids:
            lg = logits_fn(params, a, minibatch["obs"][a])
            lp_all = jax.nn.log_softmax(lg)
            lp = jnp.take_along_axis(
                lp_all, minibatch["actions"][a][..., None], axis=-1
            )[..., 0]
            v = value_fn(
                params, a, critic_obs(minibatch["obs"], minibatch["state"], a)
            )
            total = total + _ppo_surrogate(
                cfg, lp, lp_all, minibatch["logp"][a],
                minibatch["advantage"][a], v, minibatch["returns"][a],
            )
        metrics["loss"] = total
        return total, metrics

    def advantages(train: TrainState, traj: Transition):
        """Per-agent advantages and returns of a stored trajectory.

        Bootstrap from the final next-observation with the learner's
        current params.  Under the synchronous runners these equal the
        behaviour params (no update fired mid-rollout), so GAE sees
        behaviour values exactly as if recorded at act time; under the
        async runner with staleness they differ, and the V-trace branch
        re-evaluates the whole trajectory under current params and
        importance-corrects against the stored behaviour log-probs.
        """
        last_obs = jax.tree_util.tree_map(lambda x: x[-1], traj.next_obs)
        last_state = traj.next_state[-1]
        last_values = {
            a: value_fn(train.params, a, critic_obs(last_obs, last_state, a))
            for a in ids
        }
        if not cfg.use_vtrace:
            return gae(traj, last_values)
        adv, ret = {}, {}
        disc = traj.discount * cfg.gamma
        for a in ids:
            lp_all = jax.nn.log_softmax(logits_fn(train.params, a, traj.obs[a]))
            curr_lp = jnp.take_along_axis(
                lp_all, traj.actions[a][..., None], axis=-1
            )[..., 0]
            curr_v = value_fn(
                train.params, a, critic_obs(traj.obs, traj.state, a)
            )
            adv[a], ret[a] = vtrace_advantages(
                curr_lp, traj.extras["logp"][a], curr_v, last_values[a],
                traj.rewards[a], disc,
                clip_rho=cfg.vtrace_clip_rho, clip_c=cfg.vtrace_clip_c,
                lam=cfg.gae_lambda,
            )
        return adv, ret

    def update(train: TrainState, buffer, key):
        """Consume the rollout: GAE or V-trace, then epochs of minibatches."""
        traj: Transition = rollout_take(buffer)  # leaves (T, B, ...)
        with jax.named_scope(_ADVANTAGE):
            adv, ret = advantages(train, traj)
        with jax.named_scope(_MINIBATCH):
            rows = _update_rows(traj, adv, ret, centralised)
            packed, others, unpack = _pack_rows(rows)

        def epoch(carry, _):
            """One PPO epoch: shuffle, split into minibatches, scan `mb_step`."""
            params, opt_state, key = carry
            with jax.named_scope(_MINIBATCH):
                key, kp = jax.random.split(key)
                mbs = _shuffled_minibatches(
                    packed, others, unpack, kp, cfg.num_minibatches
                )

            def mb_step(carry, mb):
                """One minibatch gradient step (optionally pmean over the mesh)."""
                params, opt_state = carry
                with jax.named_scope(_GRAD):
                    (loss, m), grads = jax.value_and_grad(
                        loss_fn, has_aux=True
                    )(params, mb)
                    if cfg.distributed_axis:
                        grads = jax.lax.pmean(grads, cfg.distributed_axis)
                with jax.named_scope(_OPTIMIZER):
                    updates, opt_state = opt.update(grads, opt_state, params)
                    params = optim.apply_updates(params, updates)
                return (params, opt_state), loss

            (params, opt_state), losses = jax.lax.scan(
                mb_step, (params, opt_state), mbs
            )
            return (params, opt_state, key), jnp.mean(losses)

        (params, opt_state, _), losses = jax.lax.scan(
            epoch, (train.params, train.opt_state, key), None, length=cfg.epochs
        )
        new_train = TrainState(params, params, opt_state, train.steps + 1)
        return new_train, rollout_reset(buffer), {"loss": jnp.mean(losses)}

    # ------------------------------------------------------------- dataset

    def example_transition():
        """A zero `Transition` fixing the buffer's shapes and dtypes."""
        obs = {a: jnp.zeros(spec.observations[a].shape) for a in ids}
        scalars = {a: jnp.zeros(()) for a in ids}
        return Transition(
            obs=obs,
            actions={a: jnp.zeros((), jnp.int32) for a in ids},
            rewards=dict(scalars),
            discount=jnp.zeros(()),
            next_obs=obs,
            state=jnp.zeros(spec.state.shape),
            next_state=jnp.zeros(spec.state.shape),
            extras={"logp": dict(scalars), "value": dict(scalars)},
            step_type=jnp.zeros((), jnp.int32),
        )

    def init_buffer(num_envs: int):
        """A fresh experience buffer for ``num_envs`` parallel envs."""
        return rollout_init(example_transition(), cfg.rollout_len, num_envs)

    return System(
        env=env,
        spec=spec,
        init_train=init_train,
        update=update,
        select_actions=select_actions,
        initial_carry=initial_carry,
        init_buffer=init_buffer,
        observe=rollout_add,
        can_sample=lambda buf: rollout_ready(buf, cfg.rollout_len),
        name=name,
    )


# --------------------------------------------------------------- recurrent


def make_recurrent_ppo_networks(env, cfg: PPOConfig, centralised: bool):
    """Build per-agent recurrent actor/critic stacks (encoder -> core -> head).

    Each network is an MLP encoder over ``cfg.hidden_sizes`` (final layer
    activated), a memory core of ``cfg.hidden_sizes[-1]`` units selected
    by ``cfg.recurrent_core`` (`ScannedRNN` GRU reference or the fused
    `LinearScannedRNN`), and a linear head.  Weights are shared across agents when the env is
    homogeneous and ``cfg.shared_weights`` is set (hidden *state* is always
    per-agent).  Returns ``(ids, num_actions, init, actor, critic)`` where
    ``actor`` / ``critic`` each expose ``step`` (one env step) and
    ``unroll`` (BPTT over a stored window with FIRST-row resets).
    """
    spec: EnvSpec = env.spec()
    ids = list(spec.agent_ids)
    num_actions = {a: spec.actions[a].num_values for a in ids}
    obs_dims = {a: spec.observations[a].shape[0] for a in ids}
    state_dim = spec.state.shape[0]
    hidden = cfg.hidden_sizes[-1]

    homogeneous = len(set((obs_dims[a], num_actions[a]) for a in ids)) == 1
    share = cfg.shared_weights and homogeneous
    critic_in = {a: (state_dim if centralised else obs_dims[a]) for a in ids}

    def stack(in_dim, out_dim):
        """One encoder -> memory core -> linear head network stack."""
        return {
            "encoder": MLP((in_dim, *cfg.hidden_sizes), activate_final=True),
            "core": make_core(cfg.recurrent_core, hidden, hidden),
            "head": MLP((hidden, out_dim)),
        }

    actors = {a: stack(obs_dims[a], num_actions[a]) for a in ids}
    critics = {a: stack(critic_in[a], 1) for a in ids}

    def init_stack(net, key):
        """Initialise one encoder/core/head stack."""
        ke, kc, kh = jax.random.split(key, 3)
        return {
            "encoder": net["encoder"].init(ke),
            "core": net["core"].init(kc),
            "head": net["head"].init(kh),
        }

    def init(key):
        """Initialise actor/critic stacks (shared across agents if homogeneous)."""
        ka, kc = jax.random.split(key)
        if share:
            return {
                "actor": {"shared": init_stack(actors[ids[0]], ka)},
                "critic": {"shared": init_stack(critics[ids[0]], kc)},
            }
        kas = jax.random.split(ka, len(ids))
        kcs = jax.random.split(kc, len(ids))
        return {
            "actor": {a: init_stack(actors[a], k) for a, k in zip(ids, kas)},
            "critic": {a: init_stack(critics[a], k) for a, k in zip(ids, kcs)},
        }

    class _Net:
        """step/unroll faces of one recurrent network family (actor or critic)."""

        def __init__(self, nets, group):
            self.nets, self.group = nets, group

        def _p(self, params, agent):
            sub = params[self.group]
            return sub["shared"] if share else sub[agent]

        def step(self, params, agent, h, x, reset=None):
            """One act-time step: ``(h, x) -> (h, head_output)``."""
            net, p = self.nets[agent], self._p(params, agent)
            z = net["encoder"].apply(p["encoder"], x)
            h, y = net["core"].step(p["core"], h, z, reset)
            return h, net["head"].apply(p["head"], y)

        def unroll(self, params, agent, h, xs, resets):
            # encoder/head are pointwise: apply outside the scan, scan the core
            """BPTT over ``(T, B, ...)`` inputs with FIRST-row resets."""
            net, p = self.nets[agent], self._p(params, agent)
            z = net["encoder"].apply(p["encoder"], xs)
            h, ys = net["core"].unroll(p["core"], h, z, resets)
            return h, net["head"].apply(p["head"], ys)

    return ids, num_actions, init, _Net(actors, "actor"), _Net(critics, "critic")


def make_recurrent_ppo_system(env, cfg: PPOConfig, centralised: bool, name: str) -> System:
    """Build a recurrent PPO `System` (rec-IPPO or rec-MAPPO by critic input)."""
    spec: EnvSpec = env.spec()
    ids, num_actions, init_params, actor, critic = make_recurrent_ppo_networks(
        env, cfg, centralised
    )
    hidden = cfg.hidden_sizes[-1]
    opt = optim.chain(
        optim.clip_by_global_norm(cfg.max_grad_norm),
        optim.adamw(cfg.learning_rate),
    )

    def critic_obs(obs, state, agent):
        """The critic input: global state (rec-MAPPO) or own obs (rec-IPPO)."""
        return state if centralised else obs[agent]

    def init_train(key):
        """Initialise the `TrainState` (params, targets, optimizer, steps)."""
        params = init_params(key)
        return TrainState(params, params, opt.init(params), jnp.zeros((), jnp.int32))

    def initial_carry(batch_shape):
        """The executor's initial memory for a ``batch_shape`` of envs."""
        zeros = lambda: {a: jnp.zeros((*batch_shape, hidden)) for a in ids}
        return Carry(hidden={"actor": zeros(), "critic": zeros()})

    # ------------------------------------------------------------ executor

    def select_actions(train: TrainState, obs, state, carry, key, training=True):
        """One recurrent act step; threads the typed `Carry` through.

        In training mode the *incoming* carry rides along in
        ``extras["carry_in"]`` so BPTT windows can re-run from the exact
        executor memory (the runner has already zeroed it at auto-reset
        FIRST boundaries, so stored FIRST rows carry zeros).  Greedy
        execution (``training=False``) threads only the actor cores.
        """
        params = train.params
        h_actor, h_critic = dict(carry.hidden["actor"]), dict(carry.hidden["critic"])
        if not training:
            actions = {}
            for a in ids:
                h_actor[a], lg = actor.step(params, a, h_actor[a], obs[a])
                actions[a] = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            return actions, Carry(hidden={"actor": h_actor, "critic": h_critic}), {}
        actions, logps, values = {}, {}, {}
        for i, a in enumerate(ids):
            h_actor[a], lg = actor.step(params, a, h_actor[a], obs[a])
            act_ = jax.random.categorical(jax.random.fold_in(key, i), lg)
            lp = jax.nn.log_softmax(lg)
            logps[a] = jnp.take_along_axis(lp, act_[..., None], axis=-1)[..., 0]
            actions[a] = act_.astype(jnp.int32)
            h_critic[a], v = critic.step(
                params, a, h_critic[a], critic_obs(obs, state, a)
            )
            values[a] = v[..., 0]
        new_carry = Carry(hidden={"actor": h_actor, "critic": h_critic})
        extras = {"logp": logps, "value": values, "carry_in": carry}
        return actions, new_carry, extras

    # ------------------------------------------------------------- trainer

    gae = _make_gae(cfg, ids)

    def loss_fn(params, mb):
        """PPO loss over full-length sequences (one BPTT re-run per net)."""
        total = 0.0
        resets = mb["resets"]
        for a in ids:
            h0 = mb["carry0"].hidden["actor"][a]
            _, lg = actor.unroll(params, a, h0, mb["obs"][a], resets)
            lp_all = jax.nn.log_softmax(lg)
            lp = jnp.take_along_axis(
                lp_all, mb["actions"][a][..., None], axis=-1
            )[..., 0]
            hc0 = mb["carry0"].hidden["critic"][a]
            _, v = critic.unroll(
                params, a, hc0, critic_obs(mb["obs"], mb["state"], a), resets
            )
            total = total + _ppo_surrogate(
                cfg, lp, lp_all, mb["logp"][a],
                mb["advantage"][a], v[..., 0], mb["returns"][a],
            )
        return total, {"loss": total}

    def update(train: TrainState, buffer, key):
        """Consume the rollout: GAE, then epochs of sequence minibatches."""
        traj: Transition = rollout_take(buffer)  # leaves (T, B, ...)
        T, B = traj.discount.shape
        with jax.named_scope(_ADVANTAGE):
            resets = traj.step_type == StepType.FIRST  # (T, B)
            carry0 = window_start_carry(traj.extras, initial_carry, (B,))

            # Bootstrap value at T: replay the critic cores over the window
            # from the stored start carry (same params as act time —
            # on-policy), then one step on the final next-observation.  When
            # the last row ended an episode its discount is 0, so the
            # (stale-memory) bootstrap for the just-started episode is gated
            # out of GAE entirely.
            last_obs = jax.tree_util.tree_map(lambda x: x[-1], traj.next_obs)
            last_state = traj.next_state[-1]
            last_values, curr_values = {}, {}
            for a in ids:
                h_t, v_seq = critic.unroll(
                    train.params, a, carry0.hidden["critic"][a],
                    critic_obs(traj.obs, traj.state, a), resets,
                )
                _, v = critic.step(
                    train.params, a, h_t, critic_obs(last_obs, last_state, a)
                )
                last_values[a] = v[..., 0]
                curr_values[a] = v_seq[..., 0]
            if cfg.use_vtrace:
                # off-policy correction for stale-snapshot actors: current
                # log-probs from an actor BPTT re-run over the stored window,
                # current values from the critic unroll above
                adv, ret = {}, {}
                disc = traj.discount * cfg.gamma
                for a in ids:
                    _, lg = actor.unroll(
                        train.params, a, carry0.hidden["actor"][a],
                        traj.obs[a], resets,
                    )
                    curr_lp = jnp.take_along_axis(
                        jax.nn.log_softmax(lg), traj.actions[a][..., None],
                        axis=-1,
                    )[..., 0]
                    adv[a], ret[a] = vtrace_advantages(
                        curr_lp, traj.extras["logp"][a], curr_values[a],
                        last_values[a], traj.rewards[a], disc,
                        clip_rho=cfg.vtrace_clip_rho, clip_c=cfg.vtrace_clip_c,
                        lam=cfg.gae_lambda,
                    )
            else:
                adv, ret = gae(traj, last_values)

        data = dict(
            obs=traj.obs,
            state=traj.state,
            actions=traj.actions,
            logp=traj.extras["logp"],
            advantage=adv,
            returns=ret,
            resets=resets,
        )
        # sequence minibatching: shuffle and split the env axis, keep time
        # intact. n_mb is the largest divisor of B up to cfg.num_minibatches
        # so every collected sequence trains each epoch (no silent drops)
        # and the B=1 python loop still gets one minibatch.
        n_mb = max(
            m for m in range(1, min(cfg.num_minibatches, B) + 1) if B % m == 0
        )
        mb_size = B // n_mb

        def epoch(carry, _):
            """One PPO epoch: shuffle, split into minibatches, scan `mb_step`."""
            params, opt_state, key = carry
            with jax.named_scope(_MINIBATCH):
                key, kp = jax.random.split(key)
                perm = jax.random.permutation(kp, B)[: n_mb * mb_size]
                # (T, B, ...) -> (n_mb, T, mb_size, ...)
                mbs = jax.tree_util.tree_map(
                    lambda x: jnp.moveaxis(
                        x[:, perm].reshape((T, n_mb, mb_size) + x.shape[2:]),
                        1, 0,
                    ),
                    data,
                )
                # window-start carries ride the same env shuffle:
                # (n_mb, mb_size, H)
                mbs["carry0"] = jax.tree_util.tree_map(
                    lambda x: x[perm].reshape((n_mb, mb_size) + x.shape[1:]),
                    carry0,
                )

            def mb_step(carry, mb):
                """One minibatch gradient step (optionally pmean over the mesh)."""
                params, opt_state = carry
                with jax.named_scope(_GRAD):
                    (loss, m), grads = jax.value_and_grad(
                        loss_fn, has_aux=True
                    )(params, mb)
                    if cfg.distributed_axis:
                        grads = jax.lax.pmean(grads, cfg.distributed_axis)
                with jax.named_scope(_OPTIMIZER):
                    updates, opt_state = opt.update(grads, opt_state, params)
                    params = optim.apply_updates(params, updates)
                return (params, opt_state), loss

            (params, opt_state), losses = jax.lax.scan(
                mb_step, (params, opt_state), mbs
            )
            return (params, opt_state, key), jnp.mean(losses)

        (params, opt_state, _), losses = jax.lax.scan(
            epoch, (train.params, train.opt_state, key), None, length=cfg.epochs
        )
        new_train = TrainState(params, params, opt_state, train.steps + 1)
        return new_train, rollout_reset(buffer), {"loss": jnp.mean(losses)}

    # ------------------------------------------------------------- dataset

    def example_transition():
        """A zero `Transition` fixing the buffer's shapes and dtypes."""
        obs = {a: jnp.zeros(spec.observations[a].shape) for a in ids}
        scalars = {a: jnp.zeros(()) for a in ids}
        return Transition(
            obs=obs,
            actions={a: jnp.zeros((), jnp.int32) for a in ids},
            rewards=dict(scalars),
            discount=jnp.zeros(()),
            next_obs=obs,
            state=jnp.zeros(spec.state.shape),
            next_state=jnp.zeros(spec.state.shape),
            # carry_in stores the full incoming Carry per step. Only row 0
            # is read back (window_start_carry); the per-step rows buy the
            # simple protocol invariant "memory rides Transition.extras"
            # at ~2*hidden floats per agent per step — revisit with a
            # window-start-only slot if rollout memory ever dominates.
            extras={
                "logp": dict(scalars),
                "value": dict(scalars),
                "carry_in": initial_carry(()),
            },
            step_type=jnp.zeros((), jnp.int32),
        )

    def init_buffer(num_envs: int):
        """A fresh experience buffer for ``num_envs`` parallel envs."""
        return rollout_init(example_transition(), cfg.rollout_len, num_envs)

    return System(
        env=env,
        spec=spec,
        init_train=init_train,
        update=update,
        select_actions=select_actions,
        initial_carry=initial_carry,
        init_buffer=init_buffer,
        observe=rollout_add,
        can_sample=lambda buf: rollout_ready(buf, cfg.rollout_len),
        name=name,
    )


# ------------------------------------------------------------ constructors


def make_ippo(env, cfg: PPOConfig = PPOConfig()) -> System:
    """Feed-forward IPPO: decentralised MLP critics on each agent's obs."""
    return make_ppo_system(env, cfg, centralised=False, name="ippo")


def make_mappo(env, cfg: PPOConfig = PPOConfig()) -> System:
    """Feed-forward MAPPO: centralised MLP critics on the global state."""
    return make_ppo_system(env, cfg, centralised=True, name="mappo")


def make_rec_ippo(env, cfg: PPOConfig = PPOConfig()) -> System:
    """Recurrent IPPO: GRU-core actors/critics on each agent's obs stream."""
    return make_recurrent_ppo_system(env, cfg, centralised=False, name="rec_ippo")


def make_rec_mappo(env, cfg: PPOConfig = PPOConfig()) -> System:
    """Recurrent MAPPO: GRU-core actors, centralised GRU critics on state."""
    return make_recurrent_ppo_system(env, cfg, centralised=True, name="rec_mappo")
