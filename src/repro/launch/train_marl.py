"""MARL system launcher — the JAX analogue of the paper's Block 2.

Where Acme-Mava built a Launchpad program graph
(madqn.MADQN(...).build(); launchpad.launch(program, LOCAL_MULTI_PROCESSING)),
here any system in ``repro.systems.REGISTRY`` is launched at three scales
by picking a runner:

  --runner loop     the paper's Block-1 python environment loop (faithful)
  --runner anakin   fused jit: scan(steps) x vmap(num_envs)
  --runner sharded  shard_map over the mesh data axis (num_executors devices)
  --runner async    IMPALA-style async actor/learner: --num-actors vmapped
                    actor replicas feed a device-resident trajectory queue,
                    the learner consumes with --param-sync-every bounded
                    staleness (see docs/DISTRIBUTED.md)

Action-space compatibility is spec-driven: each registry entry declares
discrete/continuous support and the env's spec is checked against it (a
continuous-control system automatically builds the env in continuous mode
when it has one).

Observability (``repro.obs``): ``--log-every N`` streams in-flight
metrics (iteration, update count, live SPS, episode return) out of the
fused jit every N iterations; ``--log-dir`` writes a structured run
record — config, provenance, compile-vs-steady timing, per-phase timing,
the metric stream as JSONL+CSV — under ``<log-dir>/<run-id>/``; and
``--profile`` captures a ``jax.profiler`` trace plus a `repro.roofline`
HLO-cost summary into the same record.  All human-facing output goes
through the `ConsoleSink`, so streamed telemetry and launcher reporting
share one formatting path.

  PYTHONPATH=src python -m repro.launch.train_marl --system ippo \
      --env smax_lite --runner anakin --iterations 5000 --num-envs 16 \
      --log-every 500 --log-dir results/runs --profile
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import numpy as np

from repro.core.system import (
    make_anakin,
    run_environment_loop,
    train_distributed,
)
from repro.distributed.impala import default_unroll_len, train_async
from repro.envs import REGISTRY as ENVS
from repro.launch.compile_cache import use_compilation_cache
from repro.obs import (
    ConsoleSink,
    CsvSink,
    JsonlSink,
    MetricTap,
    MultiLogger,
    RetraceCounter,
    RunRecord,
    SeedAggregator,
    profile_trace,
    roofline_summary,
)
from repro.systems.registry import REGISTRY as SYSTEMS
from repro.systems.registry import make_pair


def parse_args(argv=None):
    """The launcher CLI (exposed for the telemetry smoke tests)."""
    p = argparse.ArgumentParser()
    p.add_argument("--system", choices=sorted(SYSTEMS), default="madqn")
    p.add_argument("--env", choices=sorted(ENVS), default="smax_lite")
    p.add_argument(
        "--runner", choices=("loop", "anakin", "sharded", "async"),
        default="anakin",
    )
    p.add_argument("--iterations", type=int, default=2000)
    p.add_argument("--num-envs", type=int, default=16)
    p.add_argument("--num-executors", type=int, default=2, help="devices (sharded)")
    p.add_argument(
        "--num-actors", type=int, default=2,
        help="async: actor replicas feeding the trajectory queue "
        "(--iterations counts env steps per env per actor and must divide "
        "into the system's unroll length)",
    )
    p.add_argument(
        "--param-sync-every", type=int, default=1,
        help="async: refresh the actors' param snapshot every N learner "
        "ticks (1 = every tick; staleness stays < N)",
    )
    p.add_argument(
        "--num-seeds", type=int, default=0,
        help="anakin: train N independent seeds as one vmapped jit "
        "(0 = a single run); streamed metrics aggregate over lanes",
    )
    p.add_argument(
        "--continuous", action="store_true",
        help="force the env's continuous-action mode (spec-checked; "
        "continuous systems enable it automatically)",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--eval-every", type=int, default=0,
        help="anakin: run the fused greedy evaluator inside the training jit "
        "every N iterations (0 = off); sharded: any value > 0 evaluates the "
        "final params on every device",
    )
    p.add_argument("--eval-episodes", type=int, default=32)
    p.add_argument(
        "--log-every", type=int, default=0,
        help="stream in-flight metrics from inside the fused jit every N "
        "iterations (0 = off); a pure observer — results are bitwise "
        "identical with it on or off",
    )
    p.add_argument(
        "--log-dir", default=None,
        help="write a structured run record (run.json + metrics.jsonl/csv) "
        "under <log-dir>/<run-id>/ — see docs/OBSERVABILITY.md",
    )
    p.add_argument(
        "--run-id", default=None,
        help="run-record directory name (default: a generated sortable id)",
    )
    p.add_argument(
        "--profile", action="store_true",
        help="capture a jax.profiler trace directory and attach a "
        "repro.roofline HLO-cost summary to the run record",
    )
    p.add_argument(
        "--save-checkpoint", default=None, metavar="DIR",
        help="write the final trained policy as a self-describing "
        "checkpoint directory (system/env/config + params via "
        "repro.checkpoint; per-seed lanes when --num-seeds > 1) that "
        "repro.serve can restore — see docs/SERVING.md",
    )
    return p.parse_args(argv)


def run(args):
    """Launch one training run as configured (the CLI body).

    Returns ``(final_train, final_metrics)``: the trained policy state (as
    ``--save-checkpoint`` persists it) and the summary metrics printed.
    """
    console = ConsoleSink()
    record = None
    logger = console
    if args.log_dir:
        record = RunRecord(
            args.log_dir, run_id=args.run_id, config=vars(args),
            tag=f"{args.system}-{args.env}",
        )
        logger = MultiLogger(
            console,
            JsonlSink(record.metrics_path("jsonl")),
            CsvSink(record.metrics_path("csv")),
        )
        console.line(f"run record: {record.dir}")

    env_kwargs = {"continuous": True} if args.continuous else None
    axis = "data" if args.runner == "sharded" else None
    env, system = make_pair(
        args.system, args.env, distributed_axis=axis, env_kwargs=env_kwargs
    )
    key = jax.random.key(args.seed)
    num_seeds = args.num_seeds if args.num_seeds > 0 else None

    tap = None
    if args.log_every > 0 and args.runner != "loop":
        stream_logger = SeedAggregator(logger) if num_seeds else logger
        # the async runner's scan unit is one learner tick (= unroll_len
        # acting steps on each of num_actors replicas), not one env step
        steps_per_iteration = (
            default_unroll_len(system) * args.num_envs * args.num_actors
            if args.runner == "async"
            else args.num_envs * (num_seeds or 1)
        )
        tap = MetricTap(
            stream_logger, args.log_every,
            steps_per_iteration=steps_per_iteration,
        )

    trace_ctx = contextlib.nullcontext({})
    if args.profile:
        trace_root = record.dir if record is not None else "results"
        trace_ctx = profile_trace(f"{trace_root}/trace")

    program = None
    final_metrics = {}
    with RetraceCounter() as rc:
        t0 = time.perf_counter()
        with trace_ctx as trace_info:
            final_train = None  # the trained policy --save-checkpoint persists
            if args.runner == "loop":
                final_train, _, ev = run_environment_loop(
                    system, key, num_episodes=args.iterations
                )
                returns = ev.episode_return
                final_metrics = {
                    "first_returns": float(np.mean(returns[:3])),
                    "last_returns": float(np.mean(returns[-3:])),
                }
                console.write(
                    {"episode_return_first": final_metrics["first_returns"],
                     "episode_return_last": final_metrics["last_returns"]}
                )
            elif args.runner == "anakin":
                program = make_anakin(
                    system, args.iterations, args.num_envs,
                    eval_every=args.eval_every,
                    eval_episodes=args.eval_episodes,
                    num_seeds=num_seeds,
                    log_every=args.log_every,
                    log_callback=tap,
                )
                if tap is not None:
                    tap.reset_clock()
                out = jax.block_until_ready(program(key))
                if tap is not None:
                    # debug.callback is async: drain the queue so the tap's
                    # emit count (and the sinks) reflect the whole run
                    jax.effects_barrier()
                if args.eval_every > 0:
                    st, metrics, evals = out
                    ev_returns = np.asarray(evals.episode_return).mean(axis=-1)
                    console.line(
                        "greedy eval return (team), per eval point: "
                        + np.array2string(ev_returns, precision=3)
                    )
                    final_metrics["eval_returns"] = ev_returns.tolist()
                else:
                    st, metrics = out
                final_train = st.train
                r = np.asarray(metrics["reward"])
                k = max(r.shape[-1] // 10, 1)
                final_metrics["reward_first10pct"] = float(r[..., :k].mean())
                final_metrics["reward_last10pct"] = float(r[..., -k:].mean())
                console.write(
                    {"reward_first10pct": final_metrics["reward_first10pct"],
                     "reward_last10pct": final_metrics["reward_last10pct"]}
                )
            elif args.runner == "async":
                if tap is not None:
                    tap.reset_clock()
                # inside the runner log_every counts learner ticks (the async
                # scan unit), but the CLI flag is denominated in iterations
                # like every other runner: convert, emitting at least as
                # often as one tap per run
                log_every_ticks = (
                    max(1, args.log_every // default_unroll_len(system))
                    if args.log_every > 0
                    else 0
                )
                st, metrics = train_async(
                    system, key, args.iterations, args.num_envs,
                    args.num_actors,
                    param_sync_every=args.param_sync_every,
                    log_every=log_every_ticks,
                    log_callback=tap,
                )
                final_train = st.train
                r = np.asarray(metrics["reward"])
                k = max(r.shape[-1] // 10, 1)
                final_metrics["reward_first10pct"] = float(r[..., :k].mean())
                final_metrics["reward_last10pct"] = float(r[..., -k:].mean())
                # the async runner's own telemetry: queue pressure and the
                # actual staleness of what the learner consumed
                final_metrics["num_actors"] = args.num_actors
                final_metrics["param_sync_every"] = args.param_sync_every
                final_metrics["queue_depth_mean"] = float(
                    np.mean(metrics["queue_depth"])
                )
                final_metrics["staleness_mean"] = float(
                    np.mean(metrics["staleness"])
                )
                final_metrics["dropped_chunks"] = float(metrics["dropped"][-1])
                console.write(
                    {"reward_first10pct": final_metrics["reward_first10pct"],
                     "reward_last10pct": final_metrics["reward_last10pct"],
                     "queue_depth_mean": final_metrics["queue_depth_mean"],
                     "staleness_mean": final_metrics["staleness_mean"],
                     "dropped_chunks": final_metrics["dropped_chunks"]}
                )
            else:
                from repro.launch.mesh import make_auto_mesh

                mesh = make_auto_mesh((args.num_executors,), ("data",))
                out = train_distributed(
                    system, key, args.iterations, args.num_envs, mesh,
                    eval_episodes=(
                        args.eval_episodes if args.eval_every > 0 else 0
                    ),
                    log_every=args.log_every,
                    log_callback=tap,
                )
                params, metrics = out[0], out[1]
                # the sharded runner returns bare replicated params; they
                # save as a params-only checkpoint (servable, not resumable)
                final_train = params
                rewards = np.asarray(metrics["reward"]).ravel()
                console.write(
                    {"per_executor_reward": rewards.tolist()}
                )
                final_metrics["per_executor_reward"] = rewards.tolist()
                if args.eval_every > 0:
                    ev = np.asarray(out[2]).ravel()
                    console.write({"per_executor_eval_return": ev.tolist()})
                    final_metrics["per_executor_eval_return"] = ev.tolist()
        wall = time.perf_counter() - t0

    if args.runner == "async":
        # wall-clock throughput split per actor replica (compile included;
        # the BENCH_speed async_actors rung reports the steady-state number)
        total_steps = args.iterations * args.num_envs * args.num_actors
        final_metrics["steps_per_sec"] = total_steps / wall
        final_metrics["per_actor_steps_per_sec"] = (
            total_steps / wall / args.num_actors
        )
    console.line(
        f"wall time: {wall:.1f}s  "
        f"({args.system} on {args.env}, runner={args.runner})"
    )
    if args.save_checkpoint:
        from repro.serve.checkpoint import save_policy

        meta_path = save_policy(
            args.save_checkpoint,
            args.system,
            args.env,
            final_train,
            env_kwargs=env_kwargs,
            num_seeds=num_seeds,
            step=args.iterations,
        )
        console.line(f"wrote policy checkpoint: {meta_path}")
    if args.log_every > 0 and tap is not None:
        console.line(f"streamed {tap.emits} in-flight telemetry rows")

    if record is not None:
        retrace = rc.summary()
        record.update("retrace", **retrace)
        record.update(
            "timing",
            total_seconds=wall,
            compile_seconds=retrace["compile_seconds"],
            steady_seconds=max(wall - retrace["compile_seconds"], 0.0),
        )
        record.update("metrics", **final_metrics)
        if args.profile:
            record.update("profile", **trace_info)
            if program is not None:
                # AOT-lower the fused program for the trip-count-aware
                # HLO-cost block (an extra backend compile, --profile only)
                compiled = program.fused.lower(program.init_fn(key)).compile()
                record.update(
                    "profile", roofline=roofline_summary(compiled.as_text())
                )
        path = record.save()
        console.line(f"wrote run record: {path}")
    logger.close()
    return final_train, final_metrics


def main():
    use_compilation_cache()
    run(parse_args())


if __name__ == "__main__":
    main()
