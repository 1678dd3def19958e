"""IPPO/MAPPO behaviour tests (System-API ports of the flagship systems)."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.system import train_anakin
from repro.envs import MatrixGame, SpeakerListener
from repro.systems.onpolicy import (
    PPOConfig,
    _pack_rows,
    _shuffled_minibatches,
    _update_rows,
    make_ippo,
    make_mappo,
)

# Learning-curve milestones recorded from the seed (pre-System) IPPO
# implementation on matrix_game: PPOConfig(rollout_len=32, epochs=4,
# num_minibatches=2, entropy_coef=0.02, learning_rate=1e-3), seed 0,
# 150 updates x 16 envs -> per-update mean reward 2.281 (first 15) and
# 4.994 (last 15); the policy converges to the climbing game's safe
# equilibrium (payoff 5).
SEED_IPPO_FIRST15 = 2.281
SEED_IPPO_LAST15 = 4.994


def _per_update_rewards(system, key, num_updates, rollout_len, num_envs):
    """Train fused and fold per-iteration rewards into per-update means."""
    _, metrics = train_anakin(
        system, key, num_updates * rollout_len, num_envs=num_envs
    )
    r = np.asarray(metrics["reward"])
    return r.reshape(num_updates, rollout_len).mean(axis=-1)


def _milestone_system():
    return make_ippo(
        MatrixGame(horizon=10),
        PPOConfig(rollout_len=32, epochs=4, num_minibatches=2,
                  entropy_coef=0.02, learning_rate=1e-3),
    )


@functools.lru_cache(maxsize=1)
def _seed0_curve():
    """The milestone run (seed 0, 150 updates), shared by the tests below."""
    return _per_update_rewards(_milestone_system(), jax.random.key(0), 150, 32, 16)


def _assert_seed_milestones(r):
    late = r[-15:].mean()
    improvement = late - r[:15].mean()
    seed_improvement = SEED_IPPO_LAST15 - SEED_IPPO_FIRST15
    # converged within 10% of the seed's final level...
    assert abs(late - SEED_IPPO_LAST15) < 0.1 * abs(SEED_IPPO_LAST15), late
    # ...with at least half the seed's early->late improvement
    assert improvement > 0.5 * seed_improvement, (improvement, seed_improvement)


def test_ippo_learns_matrix_game():
    r = _seed0_curve()
    assert r[-15:].mean() > r[:15].mean() + 1.0, (r[:15].mean(), r[-15:].mean())


def test_ippo_parity_with_seed_curve():
    """The System-API port reproduces the seed implementation's curve.

    Same hyperparameters, seed and env-step budget as the recorded seed
    run: the port must hit the same milestones — clear early->late
    improvement and convergence to the safe equilibrium (payoff ~5).
    """
    _assert_seed_milestones(_seed0_curve())


def test_vmapped_seed_training_hits_seed_milestones():
    """Seed-vectorized training preserves the recorded IPPO milestones.

    Training seeds (0, 123) as one vmapped jit program, the seed-0 lane
    must be bitwise-identical to the serial seed-0 milestone run — the
    sweep's multi-seed vectorization is a pure execution change, not a
    semantic one.
    """
    keys = jnp.stack([jax.random.key(0), jax.random.key(123)])
    _, metrics = train_anakin(
        _milestone_system(), keys, 150 * 32, num_envs=16, num_seeds=2
    )
    lane0 = np.asarray(metrics["reward"])[0].reshape(150, 32).mean(axis=-1)
    np.testing.assert_array_equal(lane0, _seed0_curve())
    _assert_seed_milestones(lane0)


def test_mappo_improves_speaker_listener():
    env = SpeakerListener()
    system = make_mappo(
        env, PPOConfig(rollout_len=64, shared_weights=False, learning_rate=7e-4)
    )
    r = _per_update_rewards(system, jax.random.key(0), 120, 64, 16)
    assert r[-12:].mean() > r[:12].mean(), (r[:12].mean(), r[-12:].mean())


def test_ppo_per_agent_rewards_drive_gae():
    """General-sum rewards must not be collapsed to their mean.

    On a general-sum variant of the matrix game (agent_1's payoff is the
    negation of agent_0's), a mean-collapsing implementation sees the same
    (zero) reward stream for both variants below, so its updates would be
    bitwise identical; the per-agent GAE fix must produce different ones.
    (A plain nonzero-delta check would not do: AdamW weight decay moves
    params even at zero gradient.)
    """
    from repro.core.types import Transition

    env = MatrixGame(horizon=10)
    cfg = PPOConfig(rollout_len=8, epochs=1, num_minibatches=1, entropy_coef=0.0)
    system = make_ippo(env, cfg)
    train = system.init_train(jax.random.key(0))

    # hand-roll one rollout, storing antisymmetric per-agent rewards in one
    # buffer and their (identically zero) mean in the other
    buf_pa, buf_mean = system.init_buffer(4), system.init_buffer(4)
    key = jax.random.key(1)
    env_state, ts = jax.vmap(env.reset)(jax.random.split(key, 4))
    for _ in range(cfg.rollout_len):
        key, k_act = jax.random.split(key)
        gs = jax.vmap(env.global_state)(env_state)
        actions, _, extras = system.select_actions(
            train, ts.observation, gs, (), k_act
        )
        env_state, new_ts = jax.vmap(env.step)(env_state, actions)
        r0 = new_ts.reward["agent_0"]
        per_agent = {"agent_0": r0, "agent_1": -r0}      # general-sum
        collapsed = {a: (r0 - r0) / 2 for a in per_agent}  # their mean: 0

        def tr(rewards):
            return Transition(
                obs=ts.observation, actions=actions, rewards=rewards,
                discount=new_ts.discount, next_obs=new_ts.observation,
                state=gs, next_state=jax.vmap(env.global_state)(env_state),
                extras=extras, step_type=ts.step_type,
            )

        buf_pa = system.observe(buf_pa, tr(per_agent))
        buf_mean = system.observe(buf_mean, tr(collapsed))
        ts = new_ts
    assert bool(system.can_sample(buf_pa))
    train_pa, new_buf, _ = system.update(train, buf_pa, jax.random.key(2))
    train_mean, _, _ = system.update(train, buf_mean, jax.random.key(2))
    # the update consumed-and-reset the rollout...
    assert int(new_buf.t) == 0
    # ...and per-agent rewards produced a different update than their mean
    pa = jax.tree_util.tree_leaves(train_pa.params["actor"])
    mean = jax.tree_util.tree_leaves(train_mean.params["actor"])
    assert any(
        float(np.abs(np.asarray(p) - np.asarray(m)).max()) > 1e-6
        for p, m in zip(pa, mean)
    )


def test_centralised_critic_sees_state():
    """MAPPO's critic input dim == global state dim (CTDE wiring)."""
    env = MatrixGame()
    ippo = make_ippo(env, PPOConfig())
    mappo = make_mappo(env, PPOConfig())
    k = jax.random.key(0)
    ti = ippo.init_train(k)
    tm = mappo.init_train(k)
    spec = env.spec()
    # ippo critic first layer: obs dim; mappo: state dim
    wi = jax.tree_util.tree_leaves(ti.params["critic"])[1]
    wm = jax.tree_util.tree_leaves(tm.params["critic"])[1]
    assert wi.shape[0] == spec.observations["agent_0"].shape[0]
    assert wm.shape[0] == spec.state.shape[0]


# ----------------------------------------------- feed-forward minibatch shuffle

_SHUFFLE_CFG = PPOConfig(rollout_len=6, epochs=3, num_minibatches=4)


def _random_rollout(system, num_envs, key):
    """A full rollout buffer of random rows; odd floats test bit-exactness."""
    buf = system.init_buffer(num_envs)
    leaves, treedef = jax.tree_util.tree_flatten(buf.storage)
    keys = jax.random.split(key, len(leaves))
    filled = []
    for k, x in zip(keys, leaves):
        if jnp.issubdtype(x.dtype, jnp.integer):
            filled.append(jax.random.randint(k, x.shape, 0, 5, x.dtype))
            continue
        v = jax.random.normal(k, x.shape, x.dtype).ravel()
        v = v.at[:4].set(jnp.array([-0.0, jnp.nan, jnp.inf, 1e-45], x.dtype))
        filled.append(v.reshape(x.shape))
    return buf._replace(storage=treedef.unflatten(filled), t=buf.t + _SHUFFLE_CFG.rollout_len)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("make", [make_ippo, make_mappo], ids=["ippo", "mappo"])
def test_packed_shuffle_matches_per_leaf_gather(make):
    """One gather of the packed rows gives each leaf's own ``x[perm]`` minibatches, bit for bit."""
    env = SpeakerListener()
    system = make(env, _SHUFFLE_CFG)
    num_envs = 5
    traj = _random_rollout(system, num_envs, jax.random.key(3)).storage
    ids = list(env.spec().agent_ids)
    ka, kr = jax.random.split(jax.random.key(4))
    adv = {a: jax.random.normal(jax.random.fold_in(ka, i), traj.discount.shape)
           for i, a in enumerate(ids)}
    ret = {a: jax.random.normal(jax.random.fold_in(kr, i), traj.discount.shape)
           for i, a in enumerate(ids)}
    centralised = make is make_mappo
    rows = _update_rows(traj, adv, ret, centralised)

    # the state rides along only for the centralised critic
    n = _SHUFFLE_CFG.rollout_len * num_envs
    if centralised:
        np.testing.assert_array_equal(
            _bits(rows["state"]), _bits(traj.state.reshape((n, -1)))
        )
    else:
        assert rows["state"] is None
    packed, others, unpack = _pack_rows(rows)
    widths = sum(math.prod(x.shape[1:]) for x in jax.tree_util.tree_leaves(rows))
    assert packed.shape == (n, widths) and packed.dtype == jnp.int32
    assert others == []

    key = jax.random.key(7)
    got = _shuffled_minibatches(packed, others, unpack, key, _SHUFFLE_CFG.num_minibatches)
    perm = jax.random.permutation(key, n)
    mb = n // _SHUFFLE_CFG.num_minibatches
    want = jax.tree_util.tree_map(
        lambda x: x[perm][: mb * _SHUFFLE_CFG.num_minibatches].reshape(
            (_SHUFFLE_CFG.num_minibatches, mb) + x.shape[1:]
        ),
        rows,
    )
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            if hasattr(v, "jaxpr") and hasattr(v, "consts"):
                yield v.jaxpr
            elif hasattr(v, "eqns"):
                yield v


def _gathers_outside(jaxpr, skip_scan_length):
    """Gather eqns of ``jaxpr`` and its sub-jaxprs, not entering scans of that length."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == skip_scan_length:
            continue
        if eqn.primitive.name == "gather":
            found.append(eqn)
        for sub in _sub_jaxprs(eqn):
            found += _gathers_outside(sub, skip_scan_length)
    return found


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _scans(sub)


@pytest.mark.parametrize("make", [make_ippo, make_mappo], ids=["ippo", "mappo"])
def test_ppo_epoch_gathers_rollout_rows_once(make):
    """Each epoch permutes the rollout with one gather of the packed rows, not one per leaf."""
    env = SpeakerListener()
    cfg = _SHUFFLE_CFG
    system = make(env, cfg)
    num_envs = 5
    train = system.init_train(jax.random.key(0))
    buf = _random_rollout(system, num_envs, jax.random.key(1))
    jaxpr = jax.make_jaxpr(system.update)(train, buf, jax.random.key(2)).jaxpr
    epochs = [e for e in _scans(jaxpr) if e.params["length"] == cfg.epochs]
    assert len(epochs) == 1
    body = epochs[0].params["jaxpr"].jaxpr
    assert any(e.params["length"] == cfg.num_minibatches for e in _scans(body))
    n = cfg.rollout_len * num_envs
    row_gathers = [
        e for e in _gathers_outside(body, cfg.num_minibatches)
        if e.invars[0].aval.shape[:1] == (n,)
    ]
    assert len(row_gathers) == 1, [e.invars[0].aval for e in row_gathers]

    spec = env.spec()
    ids = list(spec.agent_ids)
    # obs per agent, then actions, logp, advantage and returns per agent
    width = sum(spec.observations[a].shape[0] for a in ids) + 4 * len(ids)
    if make is make_mappo:
        width += spec.state.shape[0]
    assert row_gathers[0].invars[0].aval.shape == (n, width)
