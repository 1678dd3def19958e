"""The Mava *system* abstraction and its runners.

A System bundles the executor (select_actions + carry), the trainer (update)
and the dataset (buffer) exactly as in the paper's Fig. 2, but as a pytree of
pure functions, so one system definition runs at every scale:

  run_environment_loop — the paper's Block-1 python loop (one env, one
      process): the *faithful* Acme-style baseline used in benchmarks as the
      pre-JAX reference point.
  train_anakin — the whole loop (env steps, replay, updates) fused into a
      single lax.scan under jit, vmapped over num_envs parallel environments.
      This is the JAX rewrite's core move and the source of the 10-100x
      speedup claim.
  train_distributed — shard_map over the mesh "data" axis: each device runs
      its own envs + replay shard (the paper's num_executors), updates are
      synchronised by gradient pmean inside the update (the Launchpad
      CourierNode graph collapsed into one SPMD program).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.types import EvalMetrics, SystemState, TrainState, Transition
from repro.envs.api import StepType
from repro.envs.wrappers import AutoReset, EpisodeStats, replace_reset_keys
from repro.nn.recurrent import reset_carry
from repro.obs.profile import EVAL, PHASES, register_program

# Every op of a training iteration runs under one of these scopes
# (`jax.named_scope`), so the optimized HLO names each op's phase
# (`repro.obs.profile.op_phases`); they change metadata only.
_ACT, _ENV_STEP, _OBSERVE, _UPDATE = PHASES


@dataclasses.dataclass(frozen=True)
class System:
    """A full MARL algorithm specification (executor + trainer + dataset).

    The dataset half is an *experience-collection protocol* with three
    regimes:

      * replay (MADQN/VDN/QMIX/MADDPG): ``observe`` writes per-step rows
        into a circular table, ``can_sample`` gates on fill, ``update``
        samples i.i.d. minibatches and returns the buffer unchanged;
      * rollout (IPPO/MAPPO/DIAL): ``observe`` appends to a time-major
        ``rollout_len`` accumulator, ``can_sample`` fires exactly when the
        rollout is complete, and ``update`` consumes the whole trajectory
        and returns the buffer *reset* (consume-and-reset);
      * sequence replay (rec-MADQN): ``observe`` streams steps through a
        rolling ring that flushes fixed-length overlapping windows into a
        FIFO window table (`repro.core.buffer.SeqBufferState`),
        ``can_sample`` gates on the stored-window count (a pure function
        of the step counter), and ``update`` samples whole windows for
        burn-in + BPTT and returns the buffer unchanged.

    Executors may thread act-time side outputs (log-probs, values, outgoing
    messages, incoming recurrent carries) to the trainer by returning them
    as the third element of ``select_actions``; the runners store them in
    ``Transition.extras``.
    """

    env: Any
    spec: Any
    # trainer
    init_train: Callable[[Any], TrainState]
    update: Callable  # (train, buffer, key) -> (train, buffer, metrics)
    # executor
    select_actions: Callable  # (train, obs, state, carry, key, training) -> (actions, carry, extras)
    initial_carry: Callable   # (batch_shape) -> carry
    # dataset
    init_buffer: Callable[[int], Any]  # (num_envs) -> buffer_state
    observe: Callable         # (buffer, transition_batch) -> buffer
    can_sample: Callable      # (buffer,) -> bool scalar (ready to update)
    # schedule
    updates_per_step: int = 1
    name: str = "system"
    # action-space support declared by the algorithm ("discrete"/"continuous")
    action_space: str = "discrete"


def _training_env(env):
    """The runner-side wrapper stack: episode stats over fused auto-reset.

    The runners used to hand-roll reset/global-state plumbing (select-where
    auto-resets, python-side return accumulators); it now composes from the
    `repro.envs.wrappers` stack, shared by every env and runner.
    """
    return EpisodeStats(AutoReset(env))


def _team_return(last_returns):
    """Mean-over-agents of the per-agent completed-episode returns."""
    return jnp.mean(jnp.stack(list(last_returns.values())), axis=0)


# ------------------------------------------------------ faithful python loop


def run_environment_loop(
    system: System,
    key,
    num_episodes: int = 10,
    training: bool = True,
    train_state: Optional[TrainState] = None,
    buffer_state=None,
):
    """The paper's Block-1 executor-environment loop, one env, python-paced.

    Returns (train_state, buffer_state, EvalMetrics over the episodes) —
    per-agent and team (mean-over-agents) undiscounted returns, accumulated
    by the `EpisodeStats` wrapper rather than python-side bookkeeping.
    """
    env = EpisodeStats(system.env)
    ids = list(system.spec.agent_ids)
    key, k_init = jax.random.split(key)
    if train_state is None:
        train_state = system.init_train(k_init)
    if buffer_state is None:
        buffer_state = system.init_buffer(1)

    select = jax.jit(functools.partial(system.select_actions, training=training))
    observe = jax.jit(system.observe)
    update = jax.jit(system.update)
    reset = jax.jit(env.reset)
    step_env = jax.jit(env.step)
    gstate = jax.jit(env.global_state)

    team_returns, lengths = [], []
    agent_returns = {a: [] for a in ids}
    for _ in range(num_episodes):
        key, k_reset = jax.random.split(key)
        # make initial observation for each agent
        env_state, ts = reset(k_reset)
        carry = system.initial_carry(())
        while int(ts.step_type) != StepType.LAST:
            key, k_act, k_upd = jax.random.split(key, 3)
            obs = ts.observation
            gs = gstate(env_state)
            actions, carry, extras = select(train_state, obs, gs, carry, k_act)
            new_env_state, new_ts = step_env(env_state, actions)
            if training:
                # make an observation for each agent (adder -> dataset)
                tr = Transition(
                    obs=obs,
                    actions=actions,
                    rewards=new_ts.reward,
                    discount=new_ts.discount,
                    next_obs=new_ts.observation,
                    state=gs,
                    next_state=gstate(new_env_state),
                    extras=extras,
                    step_type=ts.step_type,
                )
                tr_b = jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], tr)
                buffer_state = observe(buffer_state, tr_b)
                # update the trainer (and the executor's policy networks)
                if bool(system.can_sample(buffer_state)):
                    train_state, buffer_state, _ = update(
                        train_state, buffer_state, k_upd
                    )
            env_state, ts = new_env_state, new_ts
        for a in ids:
            agent_returns[a].append(float(env_state.last_returns[a]))
        team_returns.append(float(_team_return(env_state.last_returns)))
        lengths.append(int(env_state.last_length))
    metrics = EvalMetrics(
        episode_return=np.asarray(team_returns),
        agent_returns={a: np.asarray(agent_returns[a]) for a in ids},
        episode_length=np.asarray(lengths, np.int32),
    )
    return train_state, buffer_state, metrics


# ------------------------------------------------------------ Anakin runner


def _act_phase(system: System, tenv, train, env_state, timestep, carry, key):
    """One vectorised acting step under ``train``'s policy — no dataset write.

    The executor half of an iteration: refresh auto-reset randomness from
    the runner key, select actions, step every env, assemble the resulting
    `Transition` batch and zero executor carries at auto-reset FIRST
    boundaries (the memory-core protocol's one reset-masking rule).

    This is the exact acting computation `_step_phase` wraps; the async
    actor/learner runner (`repro.distributed.impala`) replays it verbatim
    with a *snapshot* train state, which is what makes the staleness-0
    async run bitwise-reproduce anakin's update sequence.

    Returns ``(env_state, timestep, carry, next_key, transition, k_upd,
    metrics)`` — ``k_upd`` is the update key this step would use if its
    transition completes a batch (the callers own the update gate).
    """
    with jax.named_scope(_ACT):
        key, k_act, k_upd, k_reset = jax.random.split(key, 4)
        num_envs = jax.tree_util.tree_leaves(env_state)[0].shape[0]
        env_state = replace_reset_keys(
            env_state, jax.random.split(k_reset, num_envs)
        )

        obs = timestep.observation
        gs = jax.vmap(tenv.global_state)(env_state)
        actions, new_carry, extras = system.select_actions(
            train, obs, gs, carry, k_act, training=True
        )
    with jax.named_scope(_ENV_STEP):
        new_env_state, new_ts = jax.vmap(tenv.step)(env_state, actions)
        tr = Transition(
            obs=obs,
            actions=actions,
            rewards=new_ts.reward,
            discount=new_ts.discount,
            next_obs=new_ts.observation,
            state=gs,
            next_state=jax.vmap(tenv.global_state)(new_env_state),
            extras=extras,
            step_type=timestep.step_type,
        )

        # a FIRST out of step marks an auto-reset boundary: executor carries
        # (recurrent cores, comm messages) restart with the new episode
        done = new_ts.step_type == StepType.FIRST
        new_carry = reset_carry(
            new_carry, done, initial=system.initial_carry((num_envs,))
        )

        ep_reward = jnp.mean(jnp.stack(list(new_ts.reward.values())))
        done_f = done.astype(jnp.float32)
        # mean return of the episodes that completed this iteration (0 if none)
        ep_return = jnp.sum(
            _team_return(new_env_state.last_returns) * done_f
        ) / jnp.maximum(jnp.sum(done_f), 1.0)
        metrics = {
            "reward": ep_reward,
            "done_frac": jnp.mean(done_f),
            "episode_return": ep_return,
        }
    return new_env_state, new_ts, new_carry, key, tr, k_upd, metrics


def _step_phase(system: System, tenv, st: SystemState, key):
    """Everything in one iteration *except* the trainer update.

    ``tenv`` is the wrapper stack from `_training_env`: `AutoReset` fuses
    episode boundaries into the step (a terminated env returns the FIRST
    timestep of its next episode, carrying the terminal reward/discount)
    and `EpisodeStats` accumulates completed-episode returns — so the
    runner has no reset plumbing of its own.  Auto-reset randomness is
    refreshed from the runner key every iteration, keeping training a
    reproducible function of the runner key alone.

    Acting is `_act_phase`; this wrapper adds the dataset write
    (``system.observe``).  Returns (SystemState with the *old* train
    state, update key, metrics); the callers own the update gate so the
    seed-vectorized runner can hoist it out of the lane axis (see
    `_one_iteration_seeds`).
    """
    env_state, ts, carry, key, tr, k_upd, metrics = _act_phase(
        system, tenv, st.train, st.env_state, st.timestep, st.carry, key
    )
    buffer = _observe(system, st.buffer, tr)
    st = SystemState(st.train, buffer, env_state, ts, carry, key)
    return st, k_upd, metrics


def _observe(system: System, buffer, tr):
    """The dataset write of one iteration's transition batch."""
    with jax.named_scope(_OBSERVE):
        return system.observe(buffer, tr)


def _do_updates(system: System, train, buffer, k_upd):
    """``updates_per_step`` trainer updates (the gated branch body)."""
    for i in range(system.updates_per_step):
        train, buffer, _ = system.update(
            train, buffer, jax.random.fold_in(k_upd, i)
        )
    return train, buffer


def _gated_update(system: System, train, buffer, k_upd):
    """The trainer update(s), gated on buffer readiness (replay fill, or a
    complete rollout — in which case update consumes and resets it)."""
    with jax.named_scope(_UPDATE):
        return jax.lax.cond(
            system.can_sample(buffer),
            lambda tb: _do_updates(system, tb[0], tb[1], k_upd),
            lambda tb: tb,
            (train, buffer),
        )


def _one_iteration(system: System, tenv, carry, key):
    """One vectorised step of every env + gated updates. carry = SystemState."""
    st, k_upd, metrics = _step_phase(system, tenv, carry, key)
    train, buffer = _gated_update(system, st.train, st.buffer, k_upd)
    return st._replace(train=train, buffer=buffer), metrics


def _one_iteration_seeds(system: System, tenv, carry, keys):
    """Seed-batched `_one_iteration`: every SystemState leaf and ``keys``
    carry a leading ``(num_seeds,)`` lane axis.

    Stepping is vmapped per lane, but the update gate is hoisted *out* of
    the lane axis: under a plain vmap the per-lane `lax.cond` lowers to
    `select`, executing both branches every iteration — for rollout systems
    that means the full consume-and-reset update every step instead of every
    ``rollout_len`` steps, destroying the fused program's speed.  All three
    experience regimes advance their schedules data-independently (replay
    fill, rollout cursors and sequence-window counts move identically in
    every lane — `seq_expected_size` is the closed form tests pin), so all
    lanes agree and one scalar cond preserves the serial runner's exact
    update cadence.
    """
    st, k_upd, metrics = jax.vmap(
        functools.partial(_step_phase, system, tenv)
    )(carry, keys)
    with jax.named_scope(_UPDATE):
        ready = jax.vmap(system.can_sample)(st.buffer)
        train, buffer = jax.lax.cond(
            jnp.all(ready),
            lambda tb: jax.vmap(
                functools.partial(_do_updates, system)
            )(tb[0], tb[1], k_upd),
            lambda tb: tb,
            (st.train, st.buffer),
        )
    return st._replace(train=train, buffer=buffer), metrics


def seed_keys(key, num_seeds: int):
    """A ``(num_seeds,)`` batch of per-seed PRNG keys.

    Accepts either a single key (split into ``num_seeds`` independent
    streams) or an already-stacked batch, returned as-is — the sweep stacks
    ``jax.random.key(s)`` per seed so each vmapped lane sees exactly the key
    the serial path would have.
    """
    key = jnp.asarray(key)
    batch_ndim = 1 if jnp.issubdtype(key.dtype, jax.dtypes.prng_key) else 2
    if key.ndim == batch_ndim:
        if key.shape[0] != num_seeds:
            raise ValueError(
                f"got a batch of {key.shape[0]} keys for num_seeds={num_seeds}"
            )
        return key
    return jax.random.split(key, num_seeds)


def init_system_state(
    system: System, key, num_envs: int, train_env=None, num_seeds: Optional[int] = None
) -> SystemState:
    """Fresh SystemState; with ``num_seeds`` every leaf gains a leading seed
    axis (one independent run per key from `seed_keys`)."""
    tenv = train_env if train_env is not None else _training_env(system.env)
    if num_seeds is not None:
        return jax.vmap(
            lambda k: init_system_state(system, k, num_envs, train_env=tenv)
        )(seed_keys(key, num_seeds))
    k_train, k_env, k_sys = jax.random.split(key, 3)
    env_state, ts = jax.vmap(tenv.reset)(jax.random.split(k_env, num_envs))
    return SystemState(
        train=system.init_train(k_train),
        buffer=system.init_buffer(num_envs),
        env_state=env_state,
        timestep=ts,
        carry=system.initial_carry((num_envs,)),
        key=k_sys,
    )


def _tap_body(iterate_fn, log_every: int, log_callback):
    """Wrap a scan body with the in-jit telemetry tap (a pure observer).

    The wrapped body is scanned over the iteration index; every
    ``log_every`` iterations a `jax.debug.callback` ships the iteration
    index, the trainer's update counter and the per-iteration metrics to
    the host (``log_callback``, typically a `repro.obs.MetricTap`).  The
    callback has no outputs, so nothing can flow back into the program —
    taps-on and taps-off runs stay bitwise-identical (pinned in
    tests/test_bench.py) — and the `lax.cond` keeps non-logging iterations
    free of host traffic.
    """

    def body(carry, it):
        st, metrics = iterate_fn(carry)
        jax.lax.cond(
            (it + 1) % log_every == 0,
            lambda: jax.debug.callback(
                log_callback, it, st.train.steps, metrics
            ),
            lambda: None,
        )
        return st, metrics

    return body


def make_anakin(
    system: System,
    num_iterations: int,
    num_envs: int,
    eval_every: int = 0,
    eval_episodes: int = 32,
    eval_num_envs: Optional[int] = None,
    num_seeds: Optional[int] = None,
    log_every: int = 0,
    log_callback=None,
):
    """Build the fused Anakin program as a reusable function of ``key``.

    The returned ``program(key)`` is what `train_anakin` calls once; holding
    on to it amortises compilation across calls (the benchmark's serial-seed
    baseline) because the jit cache is keyed on the closure object.  The
    scanned carry is donated, so each call's SystemState buffers are reused
    in place rather than copied.  ``program.fused`` / ``program.init_fn``
    expose the underlying jits for AOT inspection (the ``--profile``
    roofline path lowers ``fused`` without running it).

    With ``num_seeds`` the whole program — init, training scan and any
    interleaved eval — is vmapped over a leading seed axis: N independent
    runs execute as one fused jit program (the JaxMARL vmap-over-seeds
    idiom), and every output leaf gains a leading ``(num_seeds,)`` axis.
    ``key`` may then be a single key (split per seed) or a stacked
    ``(num_seeds,)`` key batch for exact parity with serial runs.

    With ``log_every > 0`` and a ``log_callback``, the scan streams
    in-flight telemetry to the host every ``log_every`` iterations via
    `jax.debug.callback` — live progress out of an otherwise silent jit,
    without perturbing it (see `_tap_body`).  When off (the default) the
    scan body is byte-for-byte the untapped program.
    """
    tenv = _training_env(system.env)
    iterate = _one_iteration if num_seeds is None else _one_iteration_seeds
    tapping = log_every > 0 and log_callback is not None

    def _iterate(st):
        return iterate(system, tenv, st, st.key)

    if tapping:
        tapped = _tap_body(_iterate, log_every, log_callback)

        def train_body(carry, it):
            return tapped(carry, it)
    else:
        def train_body(carry, _):
            return _iterate(carry)

    # a seed-batched scan stacks metrics time-major (T, S, ...); promised
    # axis order is seed-major, matching N stacked serial runs
    def seed_major(x):
        return x if num_seeds is None else jnp.moveaxis(x, 0, 1)

    if eval_every <= 0:
        def run(st):
            xs = jnp.arange(num_iterations) if tapping else None
            st, metrics = jax.lax.scan(train_body, st, xs, length=num_iterations)
            return st, jax.tree_util.tree_map(seed_major, metrics)
    else:
        if num_iterations % eval_every:
            raise ValueError(
                f"num_iterations ({num_iterations}) must be a multiple of "
                f"eval_every ({eval_every})"
            )
        num_blocks = num_iterations // eval_every
        # local import: repro.eval's sweep harness imports this module back
        from repro.eval.evaluator import make_evaluator

        eval_fn = make_evaluator(system, eval_episodes, eval_num_envs or num_envs)

        def run(st):
            def block(st, b):
                # global iteration indices for the tap; None leaves the
                # untapped block scan untouched
                xs = b * eval_every + jnp.arange(eval_every) if tapping else None
                st, metrics = jax.lax.scan(train_body, st, xs, length=eval_every)
                with jax.named_scope(EVAL):
                    if num_seeds is None:
                        k_eval, k_next = jax.random.split(st.key)
                        ev = eval_fn(st.train, k_eval)
                    else:
                        split = jax.vmap(jax.random.split)(st.key)
                        k_eval, k_next = split[:, 0], split[:, 1]
                        ev = jax.vmap(eval_fn)(st.train, k_eval)
                return st._replace(key=k_next), (metrics, ev)

            bxs = jnp.arange(num_blocks) if tapping else None
            st, (metrics, evals) = jax.lax.scan(block, st, bxs, length=num_blocks)
            # (num_blocks, eval_every, [S,] ...) -> ([S,] num_iterations, ...)
            metrics = jax.tree_util.tree_map(
                lambda x: seed_major(
                    x.reshape((num_iterations,) + x.shape[2:])
                ),
                metrics,
            )
            # eval points: (num_blocks, [S,] E) -> ([S,] num_blocks, E)
            evals = jax.tree_util.tree_map(seed_major, evals)
            return st, metrics, evals

    init_fn = jax.jit(
        lambda key: _unalias(
            init_system_state(
                system, key, num_envs, train_env=tenv, num_seeds=num_seeds
            )
        )
    )
    fused = jax.jit(run, donate_argnums=0)
    _register(fused, init_fn)

    def program(key):
        return fused(init_fn(key))

    # AOT handles for observability tooling (HLO-cost summaries, traces)
    program.fused = fused
    program.init_fn = init_fn
    return program


def _register(fused, init_fn):
    """Register ``fused`` for the op -> phase map, its argument being what
    ``init_fn`` builds from a typed PRNG key (`jax.random.key`)."""
    register_program(
        fused, lambda: (jax.eval_shape(init_fn, jax.random.key(0)),)
    )


def _unalias(tree):
    """Copy leaves that appear more than once so the tree can be donated.

    `init_train` aliases ``target_params`` to ``params`` at step 0; donating
    a pytree containing one buffer twice is an XLA error.  Applied *inside*
    the jitted init, where duplicated leaves are literally the same tracer
    (so the ``id`` check fires and inserts a copy), guaranteeing the
    returned state has distinct output buffers on every backend.
    """
    seen: set = set()

    def uniq(x):
        if id(x) in seen:
            return jnp.array(x)
        seen.add(id(x))
        return x

    return jax.tree_util.tree_map(uniq, tree)


def train_anakin(
    system: System,
    key,
    num_iterations: int,
    num_envs: int,
    eval_every: int = 0,
    eval_episodes: int = 32,
    eval_num_envs: Optional[int] = None,
    num_seeds: Optional[int] = None,
    log_every: int = 0,
    log_callback=None,
):
    """Fused jit training: scan(num_iterations) x vmap(num_envs).

    Returns (final SystemState, metrics stacked over iterations).

    With ``eval_every > 0`` the greedy evaluator (`repro.eval`) runs inside
    the same jit every `eval_every` iterations — no host round trip — and
    the return becomes (state, metrics, EvalMetrics stacked over the
    num_iterations // eval_every eval points).  Each eval uses the first
    half of a split of the post-block scan key, so its returns are
    reproducible by the standalone `repro.eval.evaluate` given the same
    train state and key.

    With ``num_seeds`` set, N independent seeds train simultaneously in one
    compiled program (vmap over per-seed SystemState); every return leaf
    gains a leading ``(num_seeds,)`` axis and per-seed lanes are the runs
    the serial path would produce from the same per-seed keys.  ``key`` may
    be a single key or a stacked ``(num_seeds,)`` batch (see `seed_keys`).

    ``log_every``/``log_callback`` install the in-flight telemetry tap
    (see `make_anakin`): metrics stream to the host mid-scan without
    changing a single bit of the run's results.  Unlike the raw
    `make_anakin` program, this wrapper drains the callback queue before
    returning (``jax.debug.callback`` is async), so every due emission
    has landed by the time the caller reads its tap.
    """
    out = make_anakin(
        system,
        num_iterations,
        num_envs,
        eval_every=eval_every,
        eval_episodes=eval_episodes,
        eval_num_envs=eval_num_envs,
        num_seeds=num_seeds,
        log_every=log_every,
        log_callback=log_callback,
    )(key)
    if log_every > 0 and log_callback is not None:
        jax.block_until_ready(out)
        jax.effects_barrier()
    return out


# -------------------------------------------------------- distributed runner


def make_distributed(
    system: System,
    num_iterations: int,
    num_envs_per_device: int,
    mesh,
    axis: str = "data",
    eval_episodes: int = 0,
    eval_num_envs: Optional[int] = None,
    log_every: int = 0,
    log_callback=None,
):
    """Build the shard_map training program as a reusable function of ``key``.

    `train_distributed` calls it once; the benchmark holds on to it so timed
    calls hit the jit cache instead of re-tracing the SPMD program.

    ``log_every``/``log_callback`` stream in-flight metrics exactly as in
    `make_anakin`; under shard_map the callback fires per device shard, so
    the host tap sees each executor's local metrics (callers that want one
    line per emission should aggregate in their logger).

    Like `make_anakin`, the program is split into an init jit and a
    training jit (``program.init_fn`` / ``program.fused``), so repeat
    calls — the benchmark's timed calls in particular — re-run only the
    training scan.  The earlier one-jit form re-built every device's
    SystemState inside each call, which is why committed BENCH_speed
    tables showed shard_map trailing anakin on some cells (see
    docs/DISTRIBUTED.md).  Unlike anakin's fused jit the training jit is
    *not* donated: its outputs are reductions (replicated params + mean
    metrics), so there are no output buffers the state could alias —
    donation would only produce "unusable donation" warnings.
    """
    n_dev = mesh.shape[axis]

    eval_fn = None
    if eval_episodes > 0:
        # local import: repro.eval's sweep harness imports this module back
        from repro.eval.evaluator import make_evaluator

        eval_fn = make_evaluator(
            system, eval_episodes, eval_num_envs or num_envs_per_device
        )

    tenv = _training_env(system.env)

    tapping = log_every > 0 and log_callback is not None

    def per_device_init(dev_keys, train_key):
        st = init_system_state(
            system, dev_keys[0], num_envs_per_device, train_env=tenv
        )
        # envs, buffer and executor keys are the device's own; the trainer
        # state is the same on every device, so the pmean'd updates keep
        # the parameters replicated (out_specs P() does not check it)
        st = st._replace(train=system.init_train(train_key))
        # every leaf gains a leading per-device axis of 1 so the state can
        # cross the shard_map boundary sharded on the data axis (scalars
        # included — P(axis) cannot shard a rank-0 leaf)
        return jax.tree_util.tree_map(lambda x: x[None], _unalias(st))

    def per_device_run(st_batched):
        st = jax.tree_util.tree_map(lambda x: x[0], st_batched)

        def _iterate(st):
            return _one_iteration(system, tenv, st, st.key)

        if tapping:
            tapped = _tap_body(_iterate, log_every, log_callback)

            def body(carry, it):
                return tapped(carry, it)
        else:
            def body(carry, _):
                return _iterate(carry)

        xs = jnp.arange(num_iterations) if tapping else None
        st, metrics = jax.lax.scan(body, st, xs, length=num_iterations)
        # return replicated params + per-device mean reward (rank-1 so the
        # data axis can concatenate device results)
        out = st.train.params, jax.tree_util.tree_map(
            lambda x: jnp.mean(x)[None], metrics
        )
        if eval_fn is not None:
            with jax.named_scope(EVAL):
                k_eval, _ = jax.random.split(st.key)
                ev = eval_fn(st.train, k_eval)
            out = out + (jnp.mean(ev.episode_return)[None],)
        return out

    sharded_init = jax.shard_map(
        per_device_init,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
        check_vma=False,
    )

    @jax.jit
    def init_fn(key):
        k_train, k_devices = jax.random.split(key)
        return sharded_init(jax.random.split(k_devices, n_dev), k_train)

    out_specs = (P(), P(axis)) if eval_fn is None else (P(), P(axis), P(axis))
    fused = jax.jit(
        jax.shard_map(
            per_device_run,
            mesh=mesh,
            in_specs=(P(axis),),
            out_specs=out_specs,
            check_vma=False,
        )
    )
    _register(fused, init_fn)

    def program(key):
        return fused(init_fn(key))

    program.fused = fused
    program.init_fn = init_fn
    return program


def train_distributed(
    system: System,
    key,
    num_iterations: int,
    num_envs_per_device: int,
    mesh,
    axis: str = "data",
    eval_episodes: int = 0,
    eval_num_envs: Optional[int] = None,
    log_every: int = 0,
    log_callback=None,
):
    """shard_map over the mesh data axis: paper's num_executors scaling.

    Each device runs its own envs + buffer shard; the system's update must
    pmean gradients over `axis` (systems built with distributed=True do).
    Params start replicated and stay replicated.

    With ``eval_episodes > 0`` every device additionally runs the fused
    greedy evaluator on the final (replicated) params inside the same SPMD
    program, and the return becomes (params, metrics, per-device mean eval
    return of shape (num_devices,)).

    When the telemetry tap is installed this wrapper drains the callback
    queue before returning (``jax.debug.callback`` is async), so every due
    emission has landed by the time the caller reads its tap.
    """
    out = make_distributed(
        system,
        num_iterations,
        num_envs_per_device,
        mesh,
        axis=axis,
        eval_episodes=eval_episodes,
        eval_num_envs=eval_num_envs,
        log_every=log_every,
        log_callback=log_callback,
    )(key)
    if log_every > 0 and log_callback is not None:
        jax.block_until_ready(out)
        jax.effects_barrier()
    return out
