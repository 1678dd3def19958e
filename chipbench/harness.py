"""What every cell shares: finding its files by name, the chip, the cache, the result line.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``.  Everything that
belongs to one configuration, traffic mix or per-layer metric sits in a
file of its own under ``chipbench/``, found by the name the entry gives:

  configs/<config>.json     sizes, precision and the source it was cut from
  traffic/<traffic>.json    the runner kind and its parameters
  runners/<runner>.py       one module per runner kind, ``run(cell) -> dict``
  metrics/<metric>.py       one reader per per-layer metric, ``read(ctx)``
  limits/<cell>.json        the limit of each number the check compares
  reference/<module>.py     a training family's check and FLOP count, named by the
                            configuration's "reference" key (``reference(config)``)

Nothing here imports JAX at module level, so the tests can load cells
without a backend.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import sys
import time
from typing import Any, Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# fixed path inside the checkout: the path is part of the cache key
CACHE_DIR = ROOT / ".jax_compilation_cache"


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``), 0.0 elsewhere."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def _load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


PENDING = BENCH_DIR / "pending.json"


def load_benchmark() -> Dict[str, Any]:
    """``BENCHMARK.json`` at the root of the checkout, with ``chipbench/pending.json`` added.

    The pending cells are built and checked but not yet measured on the
    chip as the benchmark's bounds need; the driver never names them, the
    tools and tests do.
    """
    bench = _load_json(ROOT / "BENCHMARK.json")
    for key, entries in _load_json(PENDING).items():
        bench[key] = bench[key] + entries
    return bench


@dataclasses.dataclass
class Cell:
    """One workload entry with its configuration, traffic and metric lists."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    limits: Dict[str, float]


def _reported_in(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` (or a pending one) with every file it names loaded."""
    bench = load_benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(entries)}")
    entry = entries[name]
    config_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = _load_json(ROOT / config_entry["file"])
    traffic = _load_json(BENCH_DIR / "traffic" / f"{entry['traffic']}.json")
    limits = _load_json(BENCH_DIR / "limits" / f"{name}.json")
    end_to_end = [m for m in bench["end_to_end"] if _reported_in(m, name)]
    per_layer = [m for m in bench["per_layer"] if _reported_in(m, name)]
    # a per-layer metric without a "workloads" key is reported wherever the
    # end-to-end metric it moves is
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [m for m in per_layer if "workloads" in m or m["moves"] in e2e_names]
    return Cell(name, entry["chips"], config, traffic, end_to_end, per_layer, limits)


def load_module(path: pathlib.Path):
    """Import a module from a file path (metric names carry dots)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path
    )
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def runner(kind: str):
    """The runner module of this kind (``runners/<kind>.py``)."""
    return load_module(BENCH_DIR / "runners" / f"{kind}.py")


def reader(metric: str):
    """The reader module of a per-layer metric (``metrics/<metric>.py``)."""
    return load_module(BENCH_DIR / "metrics" / f"{metric}.py")


def reference(config):
    """The reference module a training configuration names: ``reference/<config["reference"]>.py``.

    It gives ``NUMBERS``, the names its cells' limits files may use;
    ``numbers(sweep, control=False, detail=None)``, the compared numbers of
    a runner's ``Sweep`` after set-up, worst over its checked lanes; and
    ``train_flops_per_env_step(config)``, the matmul FLOPs ``mfu.train``
    divides by.  A configuration without the key, or naming no such file,
    is an error.
    """
    name = config.get("reference")
    if name is None:
        raise KeyError(f"configuration {config.get('name')!r} names no reference module: "
                       f"give it \"reference\": \"<module>\" for chipbench/reference/<module>.py")
    path = BENCH_DIR / "reference" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"configuration {config.get('name')!r} names the reference "
                                f"module {name!r}, but {path} does not exist")
    return load_module(path)


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    table = _load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def use_checkout_cache() -> None:
    """Keep JAX's persistent cache in the checkout, for every program.

    The one place the cache is set: JAX reads these variables when it is
    imported, so this runs before (and the program's own
    ``use_compilation_cache`` leaves a directory set there as it is).  The
    engine's admit and tick programs compile in well under JAX's one-second
    default, so the minimum compile time for caching is 0.
    """
    CACHE_DIR.mkdir(exist_ok=True)  # JAX writes no entry into a directory that is not there
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def require_chips(chips: int):
    """The first ``chips`` TPU devices, or exit non-zero with no result."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"chipbench: no accelerator backend: {e}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, found {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def program_key(seed: int):
    """The PRNG key of a run: every bit of a seed of up to 64 bits counts."""
    import jax

    if not 0 <= seed < 2**64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def build_system(config):
    """The configuration's system, built through the program's own registry."""
    from repro.envs import make_env
    from repro.systems import make_system

    env = make_env(config["env"], **config["env_kwargs"])
    overrides = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in config["system_overrides"].items()}
    return make_system(config["system"], env, **overrides)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int(d.memory_stats().get("peak_bytes_in_use", 0)) for d in devices)


class Clock:
    """Host-clock stopwatch whose zero is the start of the process."""

    def __init__(self):
        self.zero = time.perf_counter() - process_age_s()

    def now(self) -> float:
        return time.perf_counter() - self.zero


def emit(result: Dict[str, Any]) -> None:
    """Print the compared numbers to stderr, then the result as the last stdout line."""
    checks = result["checks"]
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
