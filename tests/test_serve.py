"""The decision-serving engine: admission order, carry hygiene, parity.

The load-bearing pin is greedy parity: decisions served out of the slot
pool must be bitwise what `repro.eval`'s fused evaluator computes for the
same episodes — same reset keys in, same actions and returns out,
regardless of pool size.  That is what makes BENCH_serve a measurement of
the *trained policy*, not of a serving-only code path.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bench.throughput import smoke_overrides
from repro.core.system import train_anakin
from repro.eval import evaluate
from repro.serve import (
    DecisionEngine,
    ServeRequest,
    load_policy,
    poisson_requests,
    read_policy_meta,
    save_policy,
    serve_workload,
    workload_stats,
)
from repro.systems.registry import make_pair

HORIZON = 10  # matrix_game episode length


def _tiny(name):
    """A registry (env, system) pair at smoke-test size."""
    return make_pair(name, "matrix_game", **smoke_overrides(name))


def _eval_reset_keys(key, num_envs):
    """The env-reset keys `evaluate(system, train, key, B, B)` uses.

    Mirrors the evaluator's split chain (one_round then _episode_batch),
    so requests carrying these keys serve the *same episodes* eval rolls.
    """
    _, kr = jax.random.split(key)
    k_reset, _ = jax.random.split(kr)
    return jax.random.split(k_reset, num_envs)


# ------------------------------------------------------------- admission


def test_admission_and_recycle_order_is_deterministic():
    _, system = _tiny("vdn")
    train = system.init_train(jax.random.key(0))
    engine = DecisionEngine(system, train, max_slots=2, warmup=False)
    for i in range(5):
        engine.submit(ServeRequest(uid=i, key=jax.random.key(100 + i)))

    finished = engine.run_until_drained()
    # FIFO queue x lowest-free-slot-first: 0,1 start; 2,3 recycle those
    # slots in order; 4 takes the first slot to free again
    assert [r.uid for r in finished] == [0, 1, 2, 3, 4]
    assert [r.slot for r in finished] == [0, 1, 0, 1, 0]
    assert all(r.done and r.length == HORIZON for r in finished)
    assert engine.idle() and engine.num_live == 0


def test_queue_overflow_waits_for_free_slots():
    _, system = _tiny("vdn")
    train = system.init_train(jax.random.key(0))
    engine = DecisionEngine(system, train, max_slots=1, warmup=False)
    for i in range(3):
        engine.submit(ServeRequest(uid=i, key=jax.random.key(i)))
    engine.tick()
    assert engine.num_live == 1 and len(engine.queue) == 2
    finished = engine.run_until_drained()
    assert [r.uid for r in finished] == [0, 1, 2]


def test_tick_log_splits_each_tick_into_its_parts():
    _, system = _tiny("vdn")
    train = system.init_train(jax.random.key(0))
    engine = DecisionEngine(system, train, max_slots=2, warmup=False)
    for i in range(3):
        engine.submit(ServeRequest(uid=i, key=jax.random.key(i)))
    engine.run_until_drained()
    assert engine.tick_log
    for tick in engine.tick_log:
        parts = (tick["admit_s"], tick["decide_s"], tick["bookkeep_s"])
        assert all(p >= 0 for p in parts)
        assert sum(parts) == pytest.approx(tick["seconds"], rel=1e-6, abs=1e-9)


def test_engine_rejects_bad_config():
    _, system = _tiny("vdn")
    train = system.init_train(jax.random.key(0))
    with pytest.raises(ValueError):
        DecisionEngine(system, train, max_slots=0, warmup=False)
    with pytest.raises(ValueError):
        DecisionEngine(system, train, mode="argmax", warmup=False)


# ---------------------------------------------------------- carry hygiene


def _hidden_rows(engine):
    """Stack every hidden leaf to (leaves, max_slots, H): rows by slot."""
    leaves = jax.tree_util.tree_leaves(engine.carry.hidden)
    return np.stack([np.asarray(x) for x in leaves])


def test_recurrent_carry_zeroed_on_admission_and_at_boundary():
    _, system = _tiny("rec_ippo")
    train = system.init_train(jax.random.key(0))
    engine = DecisionEngine(system, train, max_slots=2, warmup=False)

    engine.submit(ServeRequest(uid=0, key=jax.random.key(1)))
    for _ in range(3):
        engine.tick()
    hidden = _hidden_rows(engine)
    # every pool row was stepped (free slots burn FLOPs), so both rows
    # hold non-zero GRU state by now
    assert np.abs(hidden[:, 0]).sum() > 0.0
    assert np.abs(hidden[:, 1]).sum() > 0.0

    # admission must zero exactly the admitted slot's memory (slot 1),
    # leaving the live episode's state (slot 0) untouched
    engine.submit(ServeRequest(uid=1, key=jax.random.key(2)))
    engine._admit()
    after = _hidden_rows(engine)
    np.testing.assert_array_equal(after[:, 1], np.zeros_like(after[:, 1]))
    np.testing.assert_array_equal(after[:, 0], hidden[:, 0])

    # at the episode boundary (LAST) the retiring slot's carry is zeroed
    # inside the same tick, so a recycled slot can never leak user state
    for _ in range(HORIZON - 3):
        engine.tick()
    assert engine.slots[0] is None  # uid 0 retired
    boundary = _hidden_rows(engine)
    np.testing.assert_array_equal(
        boundary[:, 0], np.zeros_like(boundary[:, 0])
    )
    assert np.abs(boundary[:, 1]).sum() > 0.0  # uid 1 still running


# ---------------------------------------------------------- greedy parity


@pytest.mark.parametrize("name", ["ippo", "rec_ippo"])
def test_served_greedy_episodes_bitwise_match_eval(name):
    """Served returns == `repro.eval.evaluate` returns, bit for bit."""
    _, system = _tiny(name)
    train = system.init_train(jax.random.key(3))
    key = jax.random.key(7)
    B = 4

    ev = evaluate(system, train, key, num_episodes=B, num_envs=B)
    reset_keys = _eval_reset_keys(key, B)

    for max_slots in (B, 2):
        engine = DecisionEngine(
            system, train, max_slots=max_slots, warmup=False
        )
        for i in range(B):
            engine.submit(ServeRequest(uid=i, key=reset_keys[i]))
        finished = sorted(engine.run_until_drained(), key=lambda r: r.uid)
        served = np.asarray([r.episode_return for r in finished], np.float32)
        np.testing.assert_array_equal(served, np.asarray(ev.episode_return))
        for a in system.spec.agent_ids:
            np.testing.assert_array_equal(
                np.asarray([r.agent_returns[a] for r in finished], np.float32),
                np.asarray(ev.agent_returns[a]),
            )
        np.testing.assert_array_equal(
            np.asarray([r.length for r in finished]),
            np.asarray(ev.episode_length),
        )


@pytest.mark.parametrize("name", ["ippo", "rec_ippo"])
def test_served_greedy_actions_bitwise_match_reference(name):
    """Per-step served actions == an unrolled greedy reference loop."""
    _, system = _tiny(name)
    env = system.env
    train = system.init_train(jax.random.key(3))
    B = 3
    reset_keys = jax.random.split(jax.random.key(11), B)
    ids = list(system.spec.agent_ids)

    # reference: the evaluator's episode roll, unrolled in python
    env_state, ts = jax.vmap(env.reset)(reset_keys)
    carry = system.initial_carry((B,))
    reference = []
    for t in range(HORIZON):
        gs = jax.vmap(env.global_state)(env_state)
        actions, carry, _ = system.select_actions(
            train, ts.observation, gs, carry, jax.random.key(t),
            training=False,
        )
        env_state, ts = jax.vmap(env.step)(env_state, actions)
        reference.append({a: np.asarray(actions[a]) for a in ids})

    engine = DecisionEngine(
        system, train, max_slots=B, record_actions=True, warmup=False
    )
    for i in range(B):
        engine.submit(ServeRequest(uid=i, key=reset_keys[i]))
    finished = sorted(engine.run_until_drained(), key=lambda r: r.uid)
    for i, req in enumerate(finished):
        assert len(req.actions) == HORIZON
        for t, decision in enumerate(req.actions):
            for a in ids:
                np.testing.assert_array_equal(
                    decision[a], reference[t][a][i]
                )


def test_sample_mode_actions_differ_from_greedy():
    _, system = _tiny("ippo")
    train = system.init_train(jax.random.key(0))
    streams = {}
    for mode in ("greedy", "sample"):
        engine = DecisionEngine(
            system, train, max_slots=2, mode=mode, record_actions=True,
            warmup=False,
        )
        for i in range(4):
            engine.submit(ServeRequest(uid=i, key=jax.random.key(50 + i)))
        finished = sorted(engine.run_until_drained(), key=lambda r: r.uid)
        streams[mode] = [
            np.asarray([d[a] for d in r.actions])
            for r in finished for a in system.spec.agent_ids
        ]
    same = all(
        np.array_equal(g, s)
        for g, s in zip(streams["greedy"], streams["sample"])
    )
    assert not same, "sampled traffic should not replay the greedy stream"


# ------------------------------------------------------- traffic + stats


def test_poisson_requests_are_reproducible_and_ordered():
    a = poisson_requests(4, 3, 0.5, seed=9)
    b = poisson_requests(4, 3, 0.5, seed=9)
    assert len(a) == 12
    assert [r.arrival_tick for r in a] == [r.arrival_tick for r in b]
    assert all(
        np.array_equal(
            jax.random.key_data(x.key), jax.random.key_data(y.key)
        )
        for x, y in zip(a, b)
    )
    ticks = [r.arrival_tick for r in a]
    assert ticks == sorted(ticks)
    assert [r.uid for r in a] == list(range(12))
    c = poisson_requests(4, 3, 0.5, seed=10)
    assert [r.arrival_tick for r in c] != ticks or not all(
        np.array_equal(
            jax.random.key_data(x.key), jax.random.key_data(y.key)
        )
        for x, y in zip(a, c)
    )


def test_poisson_requests_reject_bad_rate():
    with pytest.raises(ValueError):
        poisson_requests(2, 2, 0.0)


def test_serve_workload_serves_every_request():
    _, system = _tiny("vdn")
    train = system.init_train(jax.random.key(0))
    engine = DecisionEngine(system, train, max_slots=2, warmup=False)
    requests = poisson_requests(3, 2, 0.3, seed=1)
    stats = serve_workload(engine, requests)
    assert stats["episodes"] == len(requests)
    assert stats["decisions"] == len(requests) * HORIZON
    assert stats["decisions_per_sec"] > 0
    assert stats["latency"]["p99_ms"] >= stats["latency"]["p50_ms"] > 0


def test_workload_stats_weights_latency_by_live_slots():
    log = [{"seconds": 0.001, "live": 1}, {"seconds": 0.003, "live": 3}]
    stats = workload_stats(log, [])
    # 4 decisions: one at 1ms, three at 3ms -> p50 is 3ms, mean 2.5ms
    assert stats["decisions"] == 4
    assert stats["latency"]["p50_ms"] == pytest.approx(3.0)
    assert stats["latency"]["mean_ms"] == pytest.approx(2.5)
    with pytest.raises(ValueError):
        workload_stats([], [])


# ----------------------------------------------------- policy round trip


def test_policy_checkpoint_round_trip_serves_identically(tmp_path):
    """save_policy -> load_policy -> served returns match the original."""
    _, system = _tiny("rec_ippo")
    key = jax.random.key(0)
    st, _ = train_anakin(system, key, 8, 4)

    d = str(tmp_path / "pol")
    save_policy(
        d, "rec_ippo", "matrix_game",
        st.train, config_overrides=smoke_overrides("rec_ippo"), step=8,
    )
    meta = read_policy_meta(d)
    assert meta["system"] == "rec_ippo" and meta["env"] == "matrix_game"
    assert meta["tree"] == "train_state"

    _, system2, train2 = load_policy(d)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        ),
        st.train.params, train2.params,
    )
    ev_key = jax.random.key(5)
    before = evaluate(system, st.train, ev_key, num_episodes=4, num_envs=4)
    after = evaluate(system2, train2, ev_key, num_episodes=4, num_envs=4)
    np.testing.assert_array_equal(
        np.asarray(before.episode_return), np.asarray(after.episode_return)
    )


def test_policy_checkpoint_per_seed_lanes(tmp_path):
    _, system = _tiny("ippo")
    st, _ = train_anakin(system, jax.random.key(0), 8, 4, num_seeds=2)
    d = str(tmp_path / "pol")
    save_policy(
        d, "ippo", "matrix_game", st.train,
        config_overrides=smoke_overrides("ippo"), num_seeds=2, step=8,
    )
    for s in range(2):
        _, _, train_s = load_policy(d, seed=s)
        lane = jax.tree_util.tree_map(lambda x: jnp.asarray(x)[s], st.train)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32)
            ),
            lane.params, train_s.params,
        )
    with pytest.raises(ValueError):
        load_policy(d, seed=2)


def test_policy_meta_rejects_foreign_directories(tmp_path):
    d = tmp_path / "not_a_policy"
    d.mkdir()
    (d / "policy.json").write_text(json.dumps({"format": "something-else"}))
    with pytest.raises(ValueError):
        read_policy_meta(str(d))
