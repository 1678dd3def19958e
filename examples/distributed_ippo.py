"""Distributed on-policy training: IPPO on spread through the unified
System runners — fused Anakin first, then the sharded executor scale-out
(the paper's num_executors experiment, now available to the on-policy
family too) over up to 4 devices.

Everything runs in this one process.  On the CPU backend the host
platform is split into 4 devices; the flag touches only the host
platform, so on a TPU host the executors are the chips JAX finds.

  PYTHONPATH=src python examples/distributed_ippo.py
"""
import os

# JAX fixes the host device count at first use, so this precedes its import
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.system import train_anakin, train_distributed  # noqa: E402
from repro.envs import make_env  # noqa: E402
from repro.launch.mesh import make_auto_mesh  # noqa: E402
from repro.systems import make_system  # noqa: E402

print("== IPPO (fused rollout+update, 16 envs) ==")
env = make_env("spread", num_agents=3, horizon=25)
system = make_system("ippo", env, rollout_len=64, epochs=2, num_minibatches=2)
st, metrics = train_anakin(system, jax.random.key(0), 120 * 64, num_envs=16)
r = np.asarray(metrics["reward"])
k = max(len(r) // 10, 1)
print(f"reward/step: first10%={r[:k].mean():.3f} last10%={r[-k:].mean():.3f}")

num_executors = min(4, len(jax.devices()))
print(f"== sharded IPPO executors ({num_executors} devices via shard_map) ==")
mesh = make_auto_mesh((num_executors,), ("data",))
system = make_system("ippo", make_env("spread", num_agents=3),
                     distributed_axis="data",
                     rollout_len=64, epochs=2, num_minibatches=2)
params, metrics = train_distributed(system, jax.random.key(0), 1500, 8, mesh)
print("per-executor mean reward:", np.round(np.asarray(metrics["reward"]).ravel(), 3))
