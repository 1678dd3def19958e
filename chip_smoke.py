"""Bring-up run on the TPU: train -> greedy eval -> serve, end to end.

Drives the library's main path once, through the entry points a user
calls, at the registry-default widths, with weights made from seed 0:

  train_ippo      `repro.launch.train_marl.run` (the train_marl CLI body):
                  ippo on smax_lite, anakin runner, 64 envs x 512
                  iterations (4 PPO updates), greedy eval inside the jit
                  every 256 iterations, policy checkpoint saved
  train_vdn       the same call for vdn, the replay family
  train_rec_ippo  `make_system("rec_ippo", env, recurrent_core="linear")`
                  + `make_anakin`; the compiled program must hold the
                  Pallas recurrent-scan kernel (a ``tpu_custom_call``)
  kernel_parity   `linear_recurrent_scan` against the sequential
                  reference at rec_ippo's shapes: forward, and the
                  gradients of a, b and h0
  serve           the ippo checkpoint restored through `repro.serve` and
                  served to 16 Poisson streams on 8 slots

With ``--chips 4`` it runs only the sharded runner (`train_distributed`,
ippo on smax_lite) on a 4-device mesh and the same per-device program on
a 1-device mesh, and checks that the parameters stayed replicated, that
every device holds state, and that every executor ran its own keys.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # one host with four chips

On a backend that is not a TPU it exits non-zero before any work.  Every
check raises, so any failed phase also exits non-zero.  The last line of
stdout is the JSON result, printed only when every phase passed.  The
JAX compile cache is `repro.launch.compile_cache`'s: a second run of the
same checkout shows cache hits.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import pathlib
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / "results" / "chip_smoke"

NUM_ENVS = 64
ITERATIONS = 512
EVAL_EVERY = 256
SERVE_STREAMS = 16
SERVE_SLOTS = 8
# docs/KERNELS.md parity table: the Pallas kernel and the gradients
KERNEL_TOL = 1e-4

def require_tpu():
    """The first device, or exit non-zero when the backend is not a TPU."""
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU backend, found {device.platform!r}"
        )
    return device


def assert_kernel_compiled(hlo_text: str, what: str) -> None:
    """The compiled program must call the Pallas kernel, not the XLA path."""
    if "tpu_custom_call" not in hlo_text:
        raise AssertionError(f"{what}: no tpu_custom_call in the compiled program")


def _finite(tree, what: str) -> None:
    for leaf in jax.tree_util.tree_leaves(tree):
        x = np.asarray(leaf)
        if np.issubdtype(x.dtype, np.floating) and not np.isfinite(x).all():
            raise AssertionError(f"{what}: non-finite values")


def _phase(name: str, fn) -> None:
    """Run one phase; print what it did, its wall and compile seconds."""
    from repro.obs import RetraceCounter

    with RetraceCounter() as rc:
        t0 = time.perf_counter()
        info = fn()
        wall = time.perf_counter() - t0
    print(
        f"[{name}] ok  wall_s={wall!r}  compile_s={rc.compile_seconds!r}  "
        f"backend_compiles={rc.backend_compiles}  "
        f"cache_hits={rc.cache_hits}  cache_misses={rc.cache_misses}  {info}",
        flush=True,
    )


def _check_trained(train, metrics: dict, what: str) -> dict:
    """The checks every training phase shares; returns what it printed."""
    _finite(train.params, f"{what} params")
    steps = int(np.asarray(train.steps))
    if steps <= 0:
        raise AssertionError(f"{what}: no update ran (steps={steps})")
    rewards = [metrics["reward_first10pct"], metrics["reward_last10pct"]]
    evals = metrics["eval_returns"]
    if not np.isfinite(rewards).all() or not np.isfinite(evals).all():
        raise AssertionError(f"{what}: non-finite rewards or eval returns")
    if len(evals) != ITERATIONS // EVAL_EVERY:
        raise AssertionError(f"{what}: {len(evals)} eval points")
    return {"updates": steps, "reward_first_last": rewards, "eval_returns": evals}


def train_cli(system: str, checkpoint=None) -> dict:
    """Train through the train_marl CLI body, as `python -m` would."""
    from repro.launch.train_marl import parse_args, run

    argv = [
        "--system", system, "--env", "smax_lite", "--runner", "anakin",
        "--num-envs", str(NUM_ENVS), "--iterations", str(ITERATIONS),
        "--eval-every", str(EVAL_EVERY),
    ]
    if checkpoint is not None:
        argv += ["--save-checkpoint", str(checkpoint)]
    train, metrics = run(parse_args(argv))
    return _check_trained(train, metrics, system)


def train_rec_ippo_linear() -> dict:
    """rec_ippo with the linear core through the documented Python API."""
    from repro.core.system import make_anakin
    from repro.envs import make_env
    from repro.systems import make_system

    system = make_system("rec_ippo", make_env("smax_lite"), recurrent_core="linear")
    program = make_anakin(
        system, ITERATIONS, NUM_ENVS, eval_every=EVAL_EVERY, eval_episodes=32
    )
    st = program.init_fn(jax.random.key(0))
    compiled = program.fused.lower(st).compile()
    assert_kernel_compiled(compiled.as_text(), "rec_ippo fused program")
    st, metrics, evals = jax.block_until_ready(compiled(st))
    r = np.asarray(metrics["reward"])
    k = max(r.shape[-1] // 10, 1)
    summary = {
        "reward_first10pct": float(r[..., :k].mean()),
        "reward_last10pct": float(r[..., -k:].mean()),
        "eval_returns": np.asarray(evals.episode_return).mean(axis=-1).tolist(),
    }
    return {"kernel": "tpu_custom_call", **_check_trained(st.train, summary, "rec_ippo")}


def kernel_parity() -> dict:
    """The compiled kernel against the sequential oracle, forward and grads."""
    from repro.kernels.recurrent_scan.ops import linear_recurrent_scan
    from repro.kernels.recurrent_scan.ref import linear_recurrence_ref

    # rec_ippo on smax_lite: rollout 128, 64 envs x 3 agents, hidden 64
    T, B, H = 128, NUM_ENVS * 3, 64
    ks = jax.random.split(jax.random.key(0), 5)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (T, B, H)))
    b = 0.1 * jax.random.normal(ks[1], (T, B, H))
    h0 = jax.random.normal(ks[2], (B, H))
    reset = jax.random.bernoulli(ks[3], 0.3, (T, B))
    g = jax.random.normal(ks[4], (T, B, H))

    assert_kernel_compiled(
        linear_recurrent_scan.lower(a, b, h0, reset).compile().as_text(),
        "linear_recurrent_scan",
    )

    def loss(scan):
        return lambda a, b, h0: jnp.sum(scan(a, b, h0, reset) * g)

    got = linear_recurrent_scan(a, b, h0, reset)
    got_grads = jax.grad(loss(linear_recurrent_scan), argnums=(0, 1, 2))(a, b, h0)
    with jax.default_matmul_precision("highest"):
        want = linear_recurrence_ref(a, b, h0, reset)
        want_grads = jax.grad(loss(linear_recurrence_ref), argnums=(0, 1, 2))(a, b, h0)
    errors = {}
    for name, x, y in zip(
        ("h", "da", "db", "dh0"), (got, *got_grads), (want, *want_grads)
    ):
        x, y = np.asarray(x), np.asarray(y)
        np.testing.assert_allclose(x, y, atol=KERNEL_TOL, rtol=KERNEL_TOL, err_msg=name)
        errors[name] = float(np.max(np.abs(x - y)))
    return {"shape": [T, B, H], "tol": KERNEL_TOL, "max_abs_err": errors}


def serve(checkpoint) -> dict:
    """Restore the checkpoint and serve Poisson traffic until it drains."""
    from repro.serve import DecisionEngine, load_policy, poisson_requests, serve_workload

    _, system, train = load_policy(str(checkpoint))
    engine = DecisionEngine(system, train, max_slots=SERVE_SLOTS, mode="greedy", seed=0)
    requests = poisson_requests(SERVE_STREAMS, 2, 0.2, seed=0)
    stats = serve_workload(engine, requests)
    served = sorted(r.uid for r in engine.finished)
    if served != [r.uid for r in requests] or not engine.idle():
        raise AssertionError(f"serve: {len(served)} of {len(requests)} requests drained")
    latency = [stats["latency"][k] for k in ("p50_ms", "p99_ms", "mean_ms")]
    returns = [r.episode_return for r in engine.finished]
    if not np.isfinite(latency).all() or not np.isfinite(returns).all():
        raise AssertionError("serve: non-finite latencies or returns")
    return {
        "requests": len(requests),
        "decisions": stats["decisions"],
        "decisions_per_sec": stats["decisions_per_sec"],
        "latency_ms_p50_p99": latency[:2],
        "episode_return_mean": stats["episode_return_mean"],
    }


def run_one_chip() -> int:
    """The train -> eval -> serve phases on the first device."""
    shutil.rmtree(OUT, ignore_errors=True)
    checkpoint = OUT / "ckpt" / "ippo"
    _phase("train_ippo", lambda: train_cli("ippo", checkpoint))
    _phase("train_vdn", lambda: train_cli("vdn"))
    _phase("train_rec_ippo", train_rec_ippo_linear)
    _phase("kernel_parity", kernel_parity)
    _phase("serve", lambda: serve(checkpoint))
    return len(jax.devices())


def bytes_in_use(device) -> int:
    """Bytes the device's allocator holds right now."""
    return device.memory_stats()["bytes_in_use"]


def _sharded_run(system, n: int) -> dict:
    """The sharded program on the first ``n`` devices: checks and steps/s."""
    from repro.core.system import make_distributed
    from repro.launch.mesh import make_auto_mesh

    mesh = make_auto_mesh((n,), ("data",))
    program = make_distributed(system, ITERATIONS, NUM_ENVS, mesh)
    key = jax.random.key(0)
    jax.block_until_ready(program(key))  # compile
    st = jax.block_until_ready(program.init_fn(key))
    in_use = [bytes_in_use(d) for d in mesh.devices.flat]
    t0 = time.perf_counter()
    params, metrics = jax.block_until_ready(program.fused(st))
    seconds = time.perf_counter() - t0

    if min(in_use) <= 0:
        raise AssertionError(f"{n} devices: a device holds no state: {in_use}")
    for leaf in jax.tree_util.tree_leaves(params):
        copies = [np.asarray(s.data).tobytes() for s in leaf.addressable_shards]
        if len(copies) != n or any(c != copies[0] for c in copies):
            raise AssertionError(f"{n} devices: parameter copies differ")
    _finite(params, f"{n} devices params")
    rewards = np.asarray(metrics["reward"]).ravel()
    if rewards.shape != (n,) or not np.isfinite(rewards).all():
        raise AssertionError(f"{n} devices: per-executor rewards {rewards}")
    if n > 1 and np.unique(rewards).size == 1:
        raise AssertionError(f"{n} devices: every executor saw the same rewards")
    steps_per_sec = ITERATIONS * NUM_ENVS * n / seconds
    return {
        "devices": n,
        "steps_per_sec": steps_per_sec,
        "bytes_in_use": in_use,
        "per_executor_reward": rewards.tolist(),
    }


def run_four_chips() -> int:
    """The sharded runner on 4 devices against the same program on 1."""
    from repro.systems.registry import make_pair

    if len(jax.devices()) < 4:
        raise SystemExit(f"chip_smoke --chips 4: found {len(jax.devices())} devices")
    _, system = make_pair("ippo", "smax_lite", distributed_axis="data")
    _phase("sharded_4", lambda: _sharded_run(system, 4))
    _phase("sharded_1", lambda: _sharded_run(system, 1))
    return 4


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: train -> eval -> serve on one chip; 4: only the sharded "
        "runner on a 4-device mesh against a 1-device mesh",
    )
    args = p.parse_args(argv)
    device = require_tpu()

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compilation_cache

    from repro.obs.profile import stages

    cache_dir = use_compilation_cache()
    versions = {}
    for dist in ("jax", "jaxlib", "libtpu"):
        try:
            versions[dist] = importlib.metadata.version(dist)
        except importlib.metadata.PackageNotFoundError:
            versions[dist] = "not installed"
    print(f"device_kind={device.device_kind!r} devices={len(jax.devices())} "
          f"versions={versions} compile_cache={cache_dir}", flush=True)

    t0 = time.perf_counter()
    count = run_four_chips() if args.chips == 4 else run_one_chip()
    counts = {k: v for k, v in stages().items() if k.startswith("cache_")}
    print(f"all phases passed in {time.perf_counter() - t0!r}s  "
          f"cache events: {counts}", flush=True)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": device.platform, "kind": device.device_kind, "count": count,
        },
    }))


if __name__ == "__main__":
    main()
