"""BENCHMARK.json, the files it names, and the entry point's refusal off the chip."""
import json
import os
import re
import subprocess
import sys

import pytest

from chipbench_testing import BENCH, assert_cell_loads, harness

ROOT = BENCH.parent
BENCH_JSON = json.loads((ROOT / "BENCHMARK.json").read_text())
WITH_PENDING = harness.load_benchmark()
# admitted cells and the pending ones (chipbench/pending.json) alike
CELLS = [w["name"] for w in WITH_PENDING["workloads"]]
TRAINING_CONFIGS = sorted({
    w["config"] for w in WITH_PENDING["workloads"]
    if json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())["runner"]
    == "anakin_seeds"
})
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys_and_names():
    assert set(BENCH_JSON) == {"command", "paths", "run_seconds", "configs", "workloads",
                               "end_to_end", "per_layer"}
    assert BENCH_JSON["paths"] == ["chipbench"]
    assert 1 <= BENCH_JSON["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH_JSON[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in BENCH_JSON["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert sum(w["chips"] == 4 for w in BENCH_JSON["workloads"]) <= 1


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_by_name(name):
    assert_cell_loads(harness, name)


@pytest.mark.parametrize("name", TRAINING_CONFIGS)
def test_every_training_config_names_its_reference(name):
    entry = next(c for c in WITH_PENDING["configs"] if c["name"] == name)
    module = harness.reference(json.loads((ROOT / entry["file"]).read_text()))
    assert module.NUMBERS and all(NAME.match(n) for n in module.NUMBERS)
    assert callable(module.numbers) and callable(module.train_flops_per_env_step)


def test_a_missing_reference_is_a_clear_error():
    config = json.loads((BENCH / "configs" / "ippo_smax.json").read_text())
    with pytest.raises(FileNotFoundError, match="no_such_check"):
        harness.reference(dict(config, reference="no_such_check"))
    del config["reference"]
    with pytest.raises(KeyError, match="names no reference module"):
        harness.reference(config)


def test_every_config_is_used_and_stands_alone():
    used = {w["config"] for w in BENCH_JSON["workloads"]}
    assert {c["name"] for c in BENCH_JSON["configs"]} == used
    for c in WITH_PENDING["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        config = json.loads((ROOT / c["file"]).read_text())
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_program_key_uses_every_bit_of_the_seed():
    import jax

    a, b = harness.program_key(5), harness.program_key(5 + 2**32)
    assert not (jax.random.key_data(a) == jax.random.key_data(b)).all()


def test_run_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0], "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
