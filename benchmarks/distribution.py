"""Paper Fig. 6 (bottom right): time-to-reward vs number of executors.

The paper scales Launchpad executor processes; here the executors are
devices on the mesh data axis (shard_map), in this process.  On the CPU
backend `benchmarks.run` splits the host platform into 4 devices before
JAX starts, so wall-clock does not improve — the claim probed is *system*
scaling: reward-per-env-step parity while total throughput (env-steps/sec
summed over executors) rises with executor count.  On a TPU host the
executors are the chips, and counts above the chips present are left out.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core.system import train_anakin, train_distributed
from repro.envs import Spread
from repro.launch.mesh import make_auto_mesh
from repro.systems.madqn import make_madqn
from repro.systems.offpolicy import OffPolicyConfig


def bench(fast: bool = False):
    iters = 400 if fast else 4_000
    rows = []
    for n_exec in (1, 2, 4):
        if n_exec > len(jax.devices()):
            continue
        env = Spread(num_agents=3, horizon=25)
        cfg = OffPolicyConfig(buffer_capacity=20000, min_replay=500, batch_size=64,
                              eps_decay_steps=10000,
                              distributed_axis="data" if n_exec > 1 else None)
        system = make_madqn(env, cfg)
        key = jax.random.key(0)
        t0 = time.time()
        if n_exec == 1:
            st, metrics = train_anakin(system, key, iters, 8)
            jax.block_until_ready(st.train.params)
            r = float(np.asarray(metrics["reward"])[-iters // 10:].mean())
        else:
            mesh = make_auto_mesh((n_exec,), ("data",))
            params, metrics = train_distributed(system, key, iters, 8, mesh)
            r = float(np.asarray(metrics["reward"]).mean())
        dt = time.time() - t0
        steps = iters * 8 * n_exec
        rows.append((
            f"distribution/num_executors_{n_exec}",
            dt / iters * 1e6,
            f"reward={r:.3f} total_env_steps/s={steps / dt:.0f} wall={dt:.1f}s",
        ))
    return rows
