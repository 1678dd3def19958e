"""Multi-device tests (subprocess: jax locks device count at first init)."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n: int = 8, timeout: int = 480):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )


def test_distributed_executor_training_runs_and_syncs():
    """shard_map runner: params identical across executors (pmean sync)."""
    r = run_with_devices(
        """
        import jax, numpy as np
        from repro.envs import MatrixGame
        from repro.systems import make_madqn
        from repro.systems.offpolicy import OffPolicyConfig
        from repro.core.system import train_distributed

        from repro.launch.mesh import make_auto_mesh

        mesh = make_auto_mesh((4,), ("data",))
        env = MatrixGame(horizon=10)
        cfg = OffPolicyConfig(buffer_capacity=2000, min_replay=50, batch_size=16,
                              eps_decay_steps=500, distributed_axis="data")
        params, metrics, ev = train_distributed(make_madqn(env, cfg), jax.random.key(0),
                                                400, 4, mesh, eval_episodes=8)
        # out_specs P() does not check replication: compare every device's
        # copy of every parameter, bitwise
        for leaf in jax.tree_util.tree_leaves(params):
            copies = [np.asarray(s.data) for s in leaf.addressable_shards]
            assert len(copies) == 4
            assert all(np.array_equal(c, copies[0]) for c in copies)
        r = np.asarray(metrics["reward"])
        assert np.isfinite(r).all()
        # fused per-device greedy eval: one mean return per executor
        ev = np.asarray(ev).ravel()
        assert ev.shape == (4,) and np.isfinite(ev).all()
        print("OK", r.ravel(), ev)
        """
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_async_runner_under_cpu_mesh():
    """2 actors on 2 forced CPU devices under an ambient mesh: the actors
    logical axis constraint engages (no-op correctness: results stay
    finite, chunks flow, nothing drops)."""
    r = run_with_devices(
        """
        import jax, numpy as np
        from repro.distributed import enter_mesh, make_async
        from repro.envs import make_env
        from repro.launch.mesh import make_auto_mesh
        from repro.systems.registry import make_system

        assert jax.local_device_count() == 2
        env = make_env("matrix_game")
        system = make_system("ippo", env, hidden_sizes=(32, 32), rollout_len=8,
                             epochs=1, num_minibatches=2)
        mesh = make_auto_mesh((2,), ("data",))
        with enter_mesh(mesh):
            st, m = make_async(system, 16, 4, 2)(jax.random.key(0))
        assert int(st.train.steps) > 0
        assert all(np.isfinite(np.asarray(x)).all()
                   for x in jax.tree_util.tree_leaves(st.train.params))
        assert float(np.asarray(m["dropped"])[-1]) == 0.0
        print("OK", int(st.train.steps))
        """,
        n=2,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_sharded_train_step_matches_single_device():
    """pjit'd LM train step on a 1x4 mesh == unsharded single-device step."""
    r = run_with_devices(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.launch.steps import make_train_step
        from repro.models import model as M
        import dataclasses

        cfg = get_smoke_config("internlm2-1.8b")
        params = M.init_model(jax.random.key(0), cfg)
        opt, train_step = make_train_step(cfg, 1e-3)
        opt_state = opt.init(params)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)), jnp.int32)}

        # single-device reference
        p1, o1, m1 = jax.jit(train_step)(params, opt_state, batch)

        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with jax.set_mesh(mesh):
            p2, o2, m2 = jax.jit(train_step)(params, opt_state, batch)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
        a = jax.tree_util.tree_leaves(p1)[0]
        b = jax.tree_util.tree_leaves(p2)[0]
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   atol=1e-5)
        print("OK", float(m1["loss"]))
        """
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
