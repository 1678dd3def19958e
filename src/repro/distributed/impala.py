"""IMPALA-style async actor/learner training as one fused jit program.

Everything before this module is lockstep: anakin interleaves acting and
learning in one scan, so the learner waits for every env step and the
actors wait for every update. `make_async` splits the two roles the way
the paper's Launchpad graphs (and marl-jax) do — N *actor replicas* roll
out trajectory chunks with a (possibly stale) **snapshot** of the learner
params and push them into a shared device-resident trajectory queue
(`repro.core.buffer.QueueState`); the *learner* pops chunks, feeds them
through the system's ordinary dataset protocol (`observe` + the
``can_sample``-gated update) and refreshes the actors' snapshot every
``param_sync_every`` ticks.  The whole graph still compiles to a single
``lax.scan`` under one jit — deterministic, reproducible, and the actor
axis is vmapped so throughput scales with actor count instead of being
bound by the lockstep scan (the `async_actors` rung of BENCH_speed).

The bounded-staleness contract (pinned by ``tests/test_async.py``):

* staleness 0 — with ``num_actors=1`` and ``param_sync_every=1`` the
  program replays anakin's exact acting stream (`_act_phase` with the
  same key threading) and update sequence (the shipped per-row update
  keys), **bitwise**, for all three experience regimes;
* staleness bounded — a chunk collected under snapshot ``s`` is consumed
  after at most ``param_sync_every * num_actors * U`` learner updates
  (``U`` rows per chunk, one potential update per row), and every
  consumed chunk's actual staleness (learner updates since its snapshot)
  is surfaced in the per-tick telemetry;
* off-policy correction — on-policy families consume stale chunks with
  V-trace importance weighting (``PPOConfig.use_vtrace``, math in
  `repro.systems.vtrace`); replay-regime systems consume directly (their
  update is already off-policy).

Device placement rides the `repro.distributed.sharding` seam: actor-state
leaves are annotated with the ``"actors"`` logical axis, so running the
program under ``enter_mesh`` spreads actor replicas across the mesh data
axis while the learner/queue stay replicated (no-op without a mesh — see
docs/DISTRIBUTED.md).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.buffer import (
    QueueState,
    RolloutState,
    SeqBufferState,
    queue_init,
    queue_pop,
    queue_push,
)
from repro.core.system import (
    System,
    _act_phase,
    _gated_update,
    _observe,
    _tap_body,
    _training_env,
    _unalias,
    init_system_state,
)
from repro.core.types import TrainState
from repro.distributed.sharding import with_logical_constraint


class ActorState(NamedTuple):
    """One actor replica's private state (leaves carry a ``(num_actors,)``
    lane axis inside `AsyncState`)."""

    env_state: Any
    timestep: Any
    carry: Any
    key: Any


class AsyncState(NamedTuple):
    """The async program's scan carry: learner + snapshot + queue + actors."""

    train: TrainState      # the learner's live train state
    snapshot: TrainState   # the actors' (possibly stale) param snapshot
    buffer: Any            # the learner-owned dataset (replay table / rollout)
    queue: QueueState      # the shared device-resident trajectory queue
    actors: ActorState     # per-actor env/carry/key, lane axis (num_actors,)
    tick: jnp.ndarray      # () int32 — completed learner ticks
    dropped: jnp.ndarray   # () int32 — chunks dropped by a full queue


def default_unroll_len(system: System) -> int:
    """The natural trajectory-chunk length for a system's dataset regime.

    Rollout-regime systems (PPO family, DIAL) unroll exactly one rollout
    per chunk, so chunk boundaries coincide with update boundaries and the
    staleness-0 run replays anakin's cadence exactly.  Replay and
    sequence-replay systems have no natural window — chunks of 8 steps
    amortise queue traffic while keeping within-chunk staleness small
    (the sequence buffer's own window striding is independent of the
    chunk length: `observe` consumes the chunk row by row).
    """
    buffer = system.init_buffer(1)
    if isinstance(buffer, RolloutState):
        return int(jax.tree_util.tree_leaves(buffer.storage)[0].shape[0])
    return 8


def _chunk_example(buffer, unroll_len: int, num_envs: int):
    """A zero trajectory chunk (time-major ``(U, num_envs, ...)`` leaves)
    matching the system's per-step `Transition` structure, recovered from
    its dataset storage.  The rollout accumulator and the sequence
    buffer's step ring both hold ``(T, num_envs, ...)`` per-step rows; the
    flat replay table holds ``(capacity, ...)`` rows."""
    if isinstance(buffer, RolloutState):
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros((unroll_len, num_envs) + x.shape[2:], x.dtype),
            buffer.storage,
        )
    if isinstance(buffer, SeqBufferState):
        # storage leaves are whole windows (capacity, window_len, ...);
        # the per-step transition structure lives in the step ring
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros((unroll_len, num_envs) + x.shape[2:], x.dtype),
            buffer.acc,
        )
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros((unroll_len, num_envs) + x.shape[1:], x.dtype),
        buffer.storage,
    )


def _actor_keys(key, num_actors: int):
    """Per-actor runner keys.  A single actor gets ``key`` itself (not a
    split of it), so the ``num_actors=1`` program consumes exactly the key
    stream anakin would — the staleness-0 bitwise pin depends on this."""
    key = jnp.asarray(key)
    if num_actors == 1:
        return key[None]
    return jax.random.split(key, num_actors)


def _shard_actors(actors: ActorState) -> ActorState:
    """Annotate actor-state leaves with the ``"actors"`` logical axis.

    Under `repro.distributed.sharding.enter_mesh` this spreads the actor
    lane axis across the mesh data axis (one replica group per device);
    outside any mesh context it is a no-op, so the unsharded smoke path
    runs the same code.  PRNG-key leaves are left unconstrained — their
    extended dtypes predate sharding-constraint support on older jax.
    """

    def _constrain(x):
        if jnp.issubdtype(jnp.asarray(x).dtype, jax.dtypes.prng_key):
            return x
        return with_logical_constraint(x, ("actors",))

    return jax.tree_util.tree_map(_constrain, actors)


def make_async(
    system: System,
    num_iterations: int,
    num_envs: int,
    num_actors: int,
    param_sync_every: int = 1,
    unroll_len: Optional[int] = None,
    queue_capacity: Optional[int] = None,
    learner_pops_per_tick: Optional[int] = None,
    log_every: int = 0,
    log_callback=None,
):
    """Build the fused async actor/learner program as a function of ``key``.

    ``num_iterations`` counts env steps per env *per actor* (anakin's
    iteration unit), so ``make_async(system, N, E, 1)`` does exactly the
    env-step work of ``make_anakin(system, N, E)``; total environment
    steps are ``num_iterations * num_envs * num_actors``.  It must divide
    into ``unroll_len``-step ticks (default: the system's rollout length,
    or 8 for replay systems — see `default_unroll_len`).

    Each tick: (1) every ``param_sync_every`` ticks the actors' snapshot
    refreshes from the learner params; (2) the vmapped actors unroll
    ``unroll_len`` acting steps each (`_act_phase` with snapshot params)
    and push their chunks into the queue; (3) the learner pops up to
    ``learner_pops_per_tick`` chunks (default ``num_actors`` — keeps up
    exactly) and runs each row through ``observe`` + the gated update,
    using the update keys shipped with the chunk.  Push to a full queue
    (default capacity ``2 * num_actors``) drops the chunk and counts it.

    The returned ``program(key)`` yields ``(AsyncState, metrics)`` with
    per-tick metrics: the actors' reward/episode-return stream plus
    ``queue_depth``, ``staleness`` (mean learner-updates-behind of the
    chunks consumed that tick), ``updates`` and cumulative ``dropped``.
    ``program.fused`` / ``program.init_fn`` expose the jits for AOT
    tooling, and ``program.unroll_len`` / ``program.num_ticks`` the
    resolved schedule.  ``log_every``/``log_callback`` install the
    `repro.obs` telemetry tap per tick, exactly as in ``make_anakin``.
    """
    if num_actors < 1:
        raise ValueError(f"num_actors must be >= 1, got {num_actors}")
    if param_sync_every < 1:
        raise ValueError(
            f"param_sync_every must be >= 1, got {param_sync_every}"
        )
    unroll = unroll_len or default_unroll_len(system)
    if num_iterations % unroll:
        raise ValueError(
            f"num_iterations ({num_iterations}) must be a multiple of the "
            f"unroll length ({unroll})"
        )
    ticks = num_iterations // unroll
    capacity = queue_capacity or 2 * num_actors
    pops = learner_pops_per_tick or num_actors

    tenv = _training_env(system.env)
    tapping = log_every > 0 and log_callback is not None
    key_data_shape = jax.random.key_data(jax.random.key(0)).shape

    def example_item(buffer):
        """A zero queue slot: chunk + per-row update keys + snapshot age."""
        return {
            "chunk": _chunk_example(buffer, unroll, num_envs),
            "k_upd": jnp.zeros((unroll,) + key_data_shape, jnp.uint32),
            "snapshot_steps": jnp.zeros((), jnp.int32),
        }

    def init_state(key) -> AsyncState:
        """Fresh AsyncState; actor lane 0 reproduces anakin's init exactly."""
        sts = jax.vmap(
            lambda k: init_system_state(system, k, num_envs, train_env=tenv)
        )(_actor_keys(key, num_actors))
        lane0 = jax.tree_util.tree_map(lambda x: x[0], sts)
        return AsyncState(
            train=lane0.train,
            snapshot=lane0.train,
            buffer=lane0.buffer,
            queue=queue_init(example_item(lane0.buffer), capacity),
            actors=ActorState(
                sts.env_state, sts.timestep, sts.carry, sts.key
            ),
            tick=jnp.zeros((), jnp.int32),
            dropped=jnp.zeros((), jnp.int32),
        )

    def one_actor(snapshot, act: ActorState):
        """Unroll one actor replica for ``unroll`` steps under the snapshot."""

        def _step(carry, _):
            env_state, ts, rnn_carry, key = carry
            env_state, ts, rnn_carry, key, tr, k_upd, m = _act_phase(
                system, tenv, snapshot, env_state, ts, rnn_carry, key
            )
            return (env_state, ts, rnn_carry, key), (
                tr, jax.random.key_data(k_upd), m
            )

        (env_state, ts, rnn_carry, key), (chunk, k_upds, ms) = jax.lax.scan(
            _step,
            (act.env_state, act.timestep, act.carry, act.key),
            None,
            length=unroll,
        )
        return ActorState(env_state, ts, rnn_carry, key), chunk, k_upds, ms

    def consume_chunk(train, buffer, item):
        """Feed one chunk row-by-row through observe + the gated update —
        the exact per-iteration cadence anakin's `_one_iteration` has, so
        the data-to-update ratio is regime-faithful at any actor count."""

        def _row(carry, x):
            train, buffer = carry
            tr, k_data = x
            buffer = _observe(system, buffer, tr)
            train, buffer = _gated_update(
                system, train, buffer, jax.random.wrap_key_data(k_data)
            )
            return (train, buffer), ()

        (train, buffer), _ = jax.lax.scan(
            _row, (train, buffer), (item["chunk"], item["k_upd"])
        )
        return train, buffer

    def learner_phase(train, buffer, queue):
        """Pop up to ``pops`` chunks and consume each (empty-queue gated)."""

        def _pop_one(carry, _):
            train, buffer, queue, stale_sum, consumed = carry

            def _do_pop(operand):
                train, buffer, queue, stale_sum, consumed = operand
                queue, item = queue_pop(queue)
                staleness = (
                    train.steps - item["snapshot_steps"]
                ).astype(jnp.float32)
                train, buffer = consume_chunk(train, buffer, item)
                return train, buffer, queue, stale_sum + staleness, consumed + 1

            return (
                jax.lax.cond(
                    queue.size > 0,
                    _do_pop,
                    lambda op: op,
                    (train, buffer, queue, stale_sum, consumed),
                ),
                (),
            )

        (train, buffer, queue, stale_sum, consumed), _ = jax.lax.scan(
            _pop_one,
            (
                train, buffer, queue,
                jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
            ),
            None,
            length=pops,
        )
        staleness = stale_sum / jnp.maximum(consumed, 1).astype(jnp.float32)
        return train, buffer, queue, staleness, consumed

    def tick_fn(state: AsyncState):
        """One learner tick: sync -> actor unrolls -> pushes -> learner pops."""
        snapshot = jax.lax.cond(
            state.tick % param_sync_every == 0,
            lambda _: state.train,
            lambda s: s,
            state.snapshot,
        )
        actors, chunks, k_upds, ms = jax.vmap(
            lambda a: one_actor(snapshot, a)
        )(state.actors)
        actors = _shard_actors(actors)

        queue, dropped = state.queue, state.dropped
        for a in range(num_actors):
            item = {
                "chunk": jax.tree_util.tree_map(lambda x: x[a], chunks),
                "k_upd": k_upds[a],
                "snapshot_steps": snapshot.steps,
            }
            queue, ok = queue_push(queue, item)
            dropped = dropped + (1 - ok.astype(jnp.int32))
        depth = queue.size

        train, buffer, queue, staleness, consumed = learner_phase(
            state.train, state.buffer, queue
        )
        metrics = {
            **jax.tree_util.tree_map(jnp.mean, ms),  # (A, U) -> scalar
            "queue_depth": depth.astype(jnp.float32),
            "staleness": staleness,
            "consumed": consumed.astype(jnp.float32),
            "dropped": dropped.astype(jnp.float32),
        }
        state = AsyncState(
            train=train,
            snapshot=snapshot,
            buffer=buffer,
            queue=queue,
            actors=actors,
            tick=state.tick + 1,
            dropped=dropped,
        )
        return state, metrics

    if tapping:
        tapped = _tap_body(tick_fn, log_every, log_callback)

        def _body(carry, it):
            return tapped(carry, it)
    else:
        def _body(carry, _):
            return tick_fn(carry)

    def run(state):
        """The fused scan over ticks."""
        xs = jnp.arange(ticks) if tapping else None
        return jax.lax.scan(_body, state, xs, length=ticks)

    init_fn = jax.jit(lambda key: _unalias(init_state(key)))
    fused = jax.jit(run, donate_argnums=0)

    def program(key):
        """Run the async program from ``key``; returns (state, metrics)."""
        return fused(init_fn(key))

    program.fused = fused
    program.init_fn = init_fn
    program.unroll_len = unroll
    program.num_ticks = ticks
    return program


def train_async(
    system: System,
    key,
    num_iterations: int,
    num_envs: int,
    num_actors: int,
    param_sync_every: int = 1,
    unroll_len: Optional[int] = None,
    queue_capacity: Optional[int] = None,
    learner_pops_per_tick: Optional[int] = None,
    log_every: int = 0,
    log_callback=None,
):
    """One-shot `make_async` run: IMPALA-style actor/learner training.

    Returns ``(AsyncState, metrics)`` — ``state.train`` is the learner's
    final train state, metrics the per-tick stream (see `make_async`).
    When the telemetry tap is installed this wrapper drains the async
    callback queue before returning, exactly like ``train_anakin``.
    """
    out = make_async(
        system,
        num_iterations,
        num_envs,
        num_actors,
        param_sync_every=param_sync_every,
        unroll_len=unroll_len,
        queue_capacity=queue_capacity,
        learner_pops_per_tick=learner_pops_per_tick,
        log_every=log_every,
        log_callback=log_callback,
    )(key)
    if log_every > 0 and log_callback is not None:
        jax.block_until_ready(out)
        jax.effects_barrier()
    return out
