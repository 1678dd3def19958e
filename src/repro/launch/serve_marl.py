"""MARL decision-serving launcher — the traffic half of the north star.

Serves restored policy checkpoints (any REGISTRY system, feed-forward or
recurrent) behind the `repro.serve.DecisionEngine` slot pool against
reproducible synthetic traffic — Poisson episode arrivals over N
concurrent user streams — and writes the ``BENCH_serve.json`` +
``BENCH_serve.md`` latency/throughput artifact (schema in docs/BENCH.md,
validated by ``scripts/check_bench_schema.py``): p50/p99 per-decision
latency and decisions/sec at every requested slot count.

Two ways in:

  # serve checkpoints you already trained (e.g. train_marl --save-checkpoint)
  PYTHONPATH=src python -m repro.launch.serve_marl \
      --checkpoints results/ckpts/ippo-matrix_game --slots 2 8

  # or train-then-serve: tiny anakin runs, each saved + *restored* before
  # serving, so the artifact always measures the checkpoint round trip
  PYTHONPATH=src python -m repro.launch.serve_marl \
      --systems ippo rec_ippo --env matrix_game --train-iterations 512 \
      --slots 2 8 --streams 8
"""
from __future__ import annotations

import argparse
import json
import pathlib

from repro.envs import REGISTRY as ENVS
from repro.launch.compile_cache import use_compilation_cache
from repro.obs import ConsoleSink, provenance
from repro.serve import (
    DecisionEngine,
    load_policy,
    poisson_requests,
    read_policy_meta,
    save_policy,
    serve_workload,
)
from repro.systems.registry import REGISTRY as SYSTEMS


def parse_args(argv=None):
    """The serving CLI (exposed for the smoke tests)."""
    p = argparse.ArgumentParser()
    p.add_argument(
        "--checkpoints", nargs="+", default=None, metavar="DIR",
        help="policy checkpoint directories to serve (default: train tiny "
        "checkpoints for --systems on --env first)",
    )
    p.add_argument(
        "--systems", nargs="+", choices=sorted(SYSTEMS),
        default=["ippo", "rec_ippo"],
        help="systems to train-then-serve when no --checkpoints are given "
        "(default: the ff + recurrent on-policy pair)",
    )
    p.add_argument("--env", choices=sorted(ENVS), default="matrix_game")
    p.add_argument(
        "--train-iterations", type=int, default=512,
        help="anakin iterations for the train-then-serve checkpoints",
    )
    p.add_argument("--train-num-envs", type=int, default=8)
    p.add_argument(
        "--ckpt-dir", default="results/ckpts",
        help="where train-then-serve writes its checkpoints",
    )
    p.add_argument(
        "--slots", type=int, nargs="+", default=[2, 8],
        help="slot-pool sizes to serve at (one BENCH_serve cell each)",
    )
    p.add_argument(
        "--streams", type=int, default=8,
        help="concurrent user streams generating Poisson episode arrivals",
    )
    p.add_argument("--episodes-per-stream", type=int, default=4)
    p.add_argument(
        "--arrival-rate", type=float, default=0.2,
        help="episode requests per tick per stream (exponential gaps)",
    )
    p.add_argument("--mode", choices=("greedy", "sample"), default="greedy")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default="BENCH_serve.json")
    return p.parse_args(argv)


def _train_checkpoints(args, console) -> list:
    """Train tiny anakin runs and persist them as policy checkpoints."""
    import jax

    from repro.bench.throughput import smoke_overrides
    from repro.core.system import train_anakin
    from repro.systems.registry import make_pair

    dirs = []
    for name in args.systems:
        overrides = smoke_overrides(name)
        _, system = make_pair(name, args.env, **overrides)
        st, _ = train_anakin(
            system, jax.random.key(args.seed),
            args.train_iterations, args.train_num_envs,
        )
        directory = str(pathlib.Path(args.ckpt_dir) / f"{name}-{args.env}")
        save_policy(
            directory, name, args.env, st.train,
            config_overrides=overrides, step=args.train_iterations,
        )
        console.line(f"trained + saved checkpoint: {directory}")
        dirs.append(directory)
    return dirs


def serve_cell(directory: str, max_slots: int, args) -> dict:
    """One BENCH_serve cell: a restored checkpoint under one slot count."""
    env, system, train = load_policy(directory)
    del env  # the engine serves system.env
    engine = DecisionEngine(
        system, train, max_slots=max_slots, mode=args.mode, seed=args.seed
    )
    requests = poisson_requests(
        args.streams, args.episodes_per_stream, args.arrival_rate,
        seed=args.seed,
    )
    stats = serve_workload(engine, requests)
    return {"checkpoint": directory, "max_slots": max_slots, **stats}


def run(args) -> dict:
    """Serve every checkpoint at every slot count; write the artifact."""
    console = ConsoleSink()
    if args.checkpoints is None:
        dirs = _train_checkpoints(args, console)
    else:
        dirs = list(args.checkpoints)

    results = {
        "workload": "serve",
        "provenance": provenance(),
        "config": {
            "streams": args.streams,
            "episodes_per_stream": args.episodes_per_stream,
            "arrival_rate": args.arrival_rate,
            "mode": args.mode,
            "seed": args.seed,
            "train_iterations": (
                args.train_iterations if args.checkpoints is None else 0
            ),
        },
        "cells": [],
    }
    for directory in dirs:
        meta = read_policy_meta(directory)
        for max_slots in args.slots:
            cell = serve_cell(directory, max_slots, args)
            cell["system"] = meta["system"]
            cell["env"] = meta["env"]
            results["cells"].append(cell)
            lat = cell["latency"]
            console.line(
                f"{cell['system']:>10s} x {cell['env']:<14s} "
                f"slots={max_slots:<3d}: "
                f"p50={lat['p50_ms']:.2f}ms p99={lat['p99_ms']:.2f}ms  "
                f"{cell['decisions_per_sec']:,.0f} decisions/s  "
                f"({cell['episodes']} episodes, "
                f"mean return {cell['episode_return_mean']:.3f})"
            )

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    md_path = str(pathlib.Path(args.out).with_suffix(".md"))
    with open(md_path, "w") as f:
        f.write(to_markdown(results))
    console.line(f"wrote {args.out} and {md_path}")
    return results


def to_markdown(results: dict) -> str:
    """Render the serving sweep as one row per (checkpoint, slot count)."""
    cfg = results["config"]
    lines = [
        "# Decision-serving latency/throughput — slot pool x checkpoint",
        "",
        f"{cfg['streams']} concurrent streams x "
        f"{cfg['episodes_per_stream']} episodes each, Poisson arrivals at "
        f"{cfg['arrival_rate']} req/tick/stream, mode={cfg['mode']}. "
        "Latency is per decision (one jitted tick advances every live "
        "slot); decisions/sec counts joint actions served.",
        "",
        "| system | env | slots | p50 (ms) | p99 (ms) | decisions/s | "
        "episodes | mean return |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for cell in results["cells"]:
        lat = cell["latency"]
        lines.append(
            f"| {cell['system']} | {cell['env']} | {cell['max_slots']} "
            f"| {lat['p50_ms']:.2f} | {lat['p99_ms']:.2f} "
            f"| {cell['decisions_per_sec']:,.0f} "
            f"| {cell['episodes']} | {cell['episode_return_mean']:.3f} |"
        )
    return "\n".join(lines) + "\n"


def main():
    use_compilation_cache()
    run(parse_args())


if __name__ == "__main__":
    main()
