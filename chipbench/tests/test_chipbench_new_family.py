"""A new training family takes a cell by new files and list entries alone.

The test copies ``BENCHMARK.json`` and ``chipbench/`` into a scratch tree
and adds a family there as a configuration of a new model would: a
configuration that names its own reference module and has none of the
(recurrent) IPPO width keys, that module, a limits file, the entries in
``BENCHMARK.json`` and the new cell's name in the metrics' ``workloads``
lists.  The copied harness then loads the cell, builds its system, hands
the runner's check to the family's ``numbers`` and counts ``mfu.train``
with the family's FLOPs.  The family is a tiny IPPO system under a name of
its own, and its module's numbers are stubs.  No file of the original tree
changes.
"""
import hashlib
import importlib.util
import json
import shutil
import sys

import pytest

from chipbench_testing import BENCH, assert_cell_loads

ROOT = BENCH.parent
CONFIG = "stub_family"
CELL = "stub_family.sweep10x128"
# the cell whose metric entries the new cell joins
LIKE = "ippo_smax.sweep10x128"
FLOPS = 123456.0
CONFIG_FILE = {
    "name": CONFIG,
    "source": "https://example.org/stub-family",
    "system": "ippo",
    "reference": "stub_check",
    "env": "smax_lite",
    "env_kwargs": {"num_agents": 2, "horizon": 10},
    "system_overrides": {"rollout_len": 16, "epochs": 1, "num_minibatches": 1},
    "reduced": [],
}
MODULE = f'''"""A stub family's check: numbers that show what the runner handed over."""
NUMBERS = ("stub_lanes", "stub_control")


def numbers(sweep, control=False, detail=None):
    if detail is not None:
        detail.append(sweep.seed)
    return {{"stub_lanes": float(len(sweep.lanes)), "stub_control": float(control)}}


def train_flops_per_env_step(config):
    return {FLOPS!r}
'''


def _digest(root):
    files = [root / "BENCHMARK.json"] + sorted(
        p for p in (root / "chipbench").rglob("*")
        if p.is_file() and "__pycache__" not in p.parts)
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest() for p in files}


def _add_family(tree):
    """Add the stub family to a copied tree, as new files and list entries only."""
    bench_dir = tree / "chipbench"
    (bench_dir / "configs" / f"{CONFIG}.json").write_text(json.dumps(CONFIG_FILE, indent=2))
    (bench_dir / "reference" / "stub_check.py").write_text(MODULE)
    (bench_dir / "limits" / f"{CELL}.json").write_text(
        json.dumps({"stub_lanes": 10.0, "stub_control": 0.5}))
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": CONFIG, "source": CONFIG_FILE["source"],
                             "file": f"chipbench/configs/{CONFIG}.json", "reduced": [],
                             "why": "a stub family"})
    bench["workloads"].append({"name": CELL, "config": CONFIG, "traffic": "sweep10x128",
                               "chips": 1, "why": "a stub cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    (tree / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))


def test_a_new_family_needs_only_new_files(tmp_path, monkeypatch):
    before = _digest(ROOT)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench", ignore=shutil.ignore_patterns("__pycache__"))
    copied = _digest(tmp_path)
    _add_family(tmp_path)
    after = _digest(tmp_path)
    assert {k for k in copied if copied[k] != after[k]} == {"BENCHMARK.json"}

    # the copied harness, and every module it loads, sees the copied tree
    spec = importlib.util.spec_from_file_location("harness", tmp_path / "chipbench" / "harness.py")
    tree = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "harness", tree)
    spec.loader.exec_module(tree)
    assert_cell_loads(tree, CELL)
    cell = tree.load_cell(CELL)
    assert "mfu.train" in {m["name"] for m in cell.per_layer}

    system = tree.build_system(cell.config)
    assert len(system.spec.agent_ids) == 2
    sweep = tree.runner(cell.traffic["runner"]).Sweep(cell, 2**33 + 7, system=system)
    detail = []
    assert sweep.check(detail=detail) == {"stub_lanes": 4.0, "stub_control": 0.0}
    assert sweep.check(control=True) == {"stub_lanes": 4.0, "stub_control": 1.0}
    assert detail == [2**33 + 7]

    peaks = tree.peaks("TPU v5 lite")
    ctx = {"config": cell.config, "steps_per_s": 2e6, "chips": 1, "peaks": peaks}
    want = 100.0 * FLOPS * 2e6 / peaks["bf16_flops_per_s"]
    assert tree.reader("mfu.train").read(ctx) == pytest.approx(want)

    assert _digest(ROOT) == before
