"""repro.obs: sinks, streaming tap, run records, profiler hooks."""
import csv
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bench.schema import check_provenance, check_run_record
from repro.core.system import train_anakin
from repro.envs import MatrixGame
from repro.obs import profile
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
    provenance,
    roofline_summary,
)
from repro.systems.offpolicy import OffPolicyConfig
from repro.systems.vdn import make_vdn

CFG = OffPolicyConfig(buffer_capacity=500, min_replay=50, batch_size=16)


def _vdn():
    return make_vdn(MatrixGame(horizon=10), CFG)


class CaptureSink:
    """A test double recording every (metrics, step) write."""

    def __init__(self):
        self.rows = []
        self.closed = False

    def write(self, metrics, step=None):
        self.rows.append((dict(metrics), step))

    def close(self):
        self.closed = True


# ------------------------------------------------------------------- sinks


def test_jsonl_sink_round_trips(tmp_path):
    path = tmp_path / "metrics.jsonl"
    sink = JsonlSink(path)
    rows = [
        {"reward": 1.5, "sps": 1000.0, "updates": 3},
        {"reward": np.float32(-2.25), "sps": jnp.asarray(2000.0), "updates": 4},
    ]
    for i, row in enumerate(rows):
        sink.write(row, step=i)
    sink.close()
    back = [json.loads(line) for line in path.read_text().splitlines()]
    assert back == [
        {"step": 0, "reward": 1.5, "sps": 1000.0, "updates": 3},
        {"step": 1, "reward": -2.25, "sps": 2000.0, "updates": 4},
    ]


def test_csv_sink_round_trips(tmp_path):
    path = tmp_path / "metrics.csv"
    sink = CsvSink(path)
    sink.write({"reward": 1.5, "updates": 3}, step=10)
    sink.write({"reward": -0.5, "updates": 4}, step=20)
    sink.close()
    with open(path) as f:
        back = list(csv.DictReader(f))
    assert [r["step"] for r in back] == ["10", "20"]
    assert [float(r["reward"]) for r in back] == [1.5, -0.5]
    assert [int(r["updates"]) for r in back] == [3, 4]


def test_csv_sink_rejects_schema_drift(tmp_path):
    sink = CsvSink(tmp_path / "m.csv")
    sink.write({"a": 1.0}, step=0)
    sink.write({}, step=1)  # missing columns are fine (logged empty)
    with pytest.raises(ValueError, match="not in the header"):
        sink.write({"a": 1.0, "surprise": 2.0}, step=2)
    sink.close()


def test_console_sink_single_formatting_path(capsys):
    console = ConsoleSink()
    console.write({"reward": 1.23456, "updates": 7}, step=5)
    console.line("free-form report")
    out = capsys.readouterr().out.splitlines()
    assert out[0] == "step=5  reward=1.235  updates=7"
    assert out[1] == "free-form report"


def test_multi_logger_fans_out_and_closes():
    a, b = CaptureSink(), CaptureSink()
    logger = MultiLogger(a, b)
    logger.write({"x": 1}, step=0)
    logger.close()
    assert a.rows == b.rows == [({"x": 1}, 0)]
    assert a.closed and b.closed


def test_seed_aggregator_reduces_lane_axes():
    inner = CaptureSink()
    logger = SeedAggregator(inner)
    logger.write(
        {"reward": np.array([1.0, 3.0, 5.0]), "iteration": 7, "tag": "x"},
        step=7,
    )
    (row, step), = inner.rows
    assert step == 7
    assert row["reward"] == pytest.approx(3.0)       # mean over lanes
    assert row["reward/min"] == pytest.approx(1.0)
    assert row["reward/max"] == pytest.approx(5.0)
    assert row["iteration"] == 7 and row["tag"] == "x"  # scalars untouched


def test_seed_aggregator_means_trailing_dims_within_lane():
    inner = CaptureSink()
    SeedAggregator(inner).write({"m": np.arange(6.0).reshape(2, 3)})
    (row, _), = inner.rows
    assert row["m"] == pytest.approx(2.5)
    assert row["m/min"] == pytest.approx(1.0)  # lane 0 mean
    assert row["m/max"] == pytest.approx(4.0)  # lane 1 mean


# ----------------------------------------------------------- streaming tap


def test_metric_tap_counts_and_reports_sps():
    sink = CaptureSink()
    tap = MetricTap(sink, log_every=8, steps_per_iteration=4)
    tap(7, 2, {"reward": 0.5})
    tap(np.int32(15), 4, {"reward": 1.5})
    assert tap.emits == 2
    (r0, s0), (r1, s1) = sink.rows
    assert (s0, s1) == (8, 16)
    assert r0["iteration"] == 8 and r1["iteration"] == 16
    assert r0["sps"] > 0 and r1["sps"] > 0
    assert r1["updates"] == 4 and r1["reward"] == 1.5


def test_metric_tap_rejects_nonpositive_period():
    with pytest.raises(ValueError, match="log_every"):
        MetricTap(CaptureSink(), log_every=0, steps_per_iteration=1)


def test_train_anakin_streams_inflight_metrics():
    """A fused run with log_every set emits rows *during* the scan."""
    sink = CaptureSink()
    tap = MetricTap(sink, log_every=16, steps_per_iteration=4)
    train_anakin(
        _vdn(), jax.random.key(0), 64, num_envs=4,
        log_every=16, log_callback=tap,
    )
    assert tap.emits == 4  # >= 2 in-flight lines is the acceptance bar
    steps = [s for _, s in sink.rows]
    assert steps == [16, 32, 48, 64]
    for row, _ in sink.rows:
        assert {"iteration", "updates", "sps", "reward"} <= set(row)


def test_train_anakin_tap_covers_seed_vmap_lanes():
    sink = CaptureSink()
    tap = MetricTap(SeedAggregator(sink), log_every=10, steps_per_iteration=8)
    keys = jnp.stack([jax.random.key(s) for s in (0, 1)])
    train_anakin(
        _vdn(), keys, 20, num_envs=4, num_seeds=2,
        log_every=10, log_callback=tap,
    )
    assert tap.emits == 2
    for row, _ in sink.rows:
        assert "reward/min" in row and "reward/max" in row


# ------------------------------------------------------------- run records


def test_provenance_block_conforms():
    assert check_provenance({"provenance": provenance()}) == []


def test_run_record_schema_round_trip(tmp_path):
    record = RunRecord(tmp_path, config={"system": "vdn"}, tag="vdn-test")
    record.update(
        "timing", total_seconds=1.5, compile_seconds=1.0, steady_seconds=0.5
    )
    record.update("timing", phases={"rollout_seconds": 0.1})
    record.update("retrace", jaxpr_traces=3, backend_compiles=1,
                  compile_seconds=1.0)
    record.update("metrics", reward_last10pct=0.25)
    path = record.save()
    with open(path) as f:
        doc = json.load(f)
    assert check_run_record(doc) == []
    assert doc["config"] == {"system": "vdn"}
    assert doc["run_id"].startswith("vdn-test-")
    assert record.metrics_path("jsonl").parent == record.dir


def test_run_record_schema_catches_drift(tmp_path):
    record = RunRecord(tmp_path, tag="t")
    record.update(
        "timing", total_seconds=1.0, compile_seconds=0.5, steady_seconds=0.5
    )
    with open(record.save()) as f:
        doc = json.load(f)
    doc["timing"].pop("compile_seconds")
    doc["provenance"].pop("git_sha")
    doc["profile"] = {"trace_dir": 3}
    errs = check_run_record(doc)
    assert any("compile_seconds" in e for e in errs)
    assert any("git_sha" in e for e in errs)
    assert any("trace_dir" in e for e in errs)
    assert check_run_record({"run_id": ""})  # everything missing


def test_check_bench_schema_script_validates_run_records(tmp_path):
    """scripts/check_bench_schema.py dispatches run.json by its run_id key."""
    import importlib.util
    import pathlib

    script = (
        pathlib.Path(__file__).resolve().parent.parent
        / "scripts" / "check_bench_schema.py"
    )
    spec = importlib.util.spec_from_file_location("cbs", script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    record = RunRecord(tmp_path, tag="ok")
    record.update(
        "timing", total_seconds=1.0, compile_seconds=0.5, steady_seconds=0.5
    )
    path = record.save()
    assert mod.main([str(path)]) == 0
    record.doc["timing"].pop("total_seconds")
    record.save()
    assert mod.main([str(path)]) == 1


# ---------------------------------------------------------- profiler hooks


def test_retrace_counter_sees_fresh_compiles():
    with RetraceCounter() as rc:
        jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones((3,)))
    assert rc.jaxpr_traces >= 1
    assert rc.backend_compiles >= 1
    assert rc.compile_seconds > 0
    summary = rc.summary()
    assert set(summary) == {"jaxpr_traces", "backend_compiles", "compile_seconds",
                            "cache_hits", "cache_misses"}
    # cached second call: no new compiles inside a fresh region
    fn = jax.jit(lambda x: x - 1.0)
    fn(jnp.ones((2,)))
    with RetraceCounter() as rc2:
        fn(jnp.ones((2,)))
    assert rc2.backend_compiles == 0


def test_profile_trace_writes_directory(tmp_path):
    jax.jit(lambda x: x + 3.0)(jnp.ones((5,)))  # set-up before the window
    with profile_trace(tmp_path / "trace") as info:
        jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    assert (tmp_path / "trace").is_dir()
    assert info["trace_dir"] == str(tmp_path / "trace")
    # the set-up stages as the window started, kept for a reader afterwards
    assert profile.last_trace() is info
    at_start = info["stages_at_start"]
    assert set(at_start) == {"trace_s", "lower_s", "compile_s", "compiles",
                             "cache_hits", "cache_misses"}
    assert at_start["compiles"] >= 1 and at_start["compile_s"] > 0


def test_profile_trace_raises_when_profiler_cannot_start(tmp_path, monkeypatch):
    def refuse(*args, **kwargs):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax.profiler, "trace", refuse)
    ran = []
    with pytest.raises(RuntimeError, match="no profiler here"):
        with profile_trace(tmp_path / "trace"):
            ran.append(True)
    assert not ran  # the body never runs untraced


def test_roofline_summary_counts_scanned_flops():
    def body(c, _):
        return c @ jnp.ones((8, 8)), None

    def fn(x):
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    text = jax.jit(fn).lower(jnp.ones((8, 8))).compile().as_text()
    summary = roofline_summary(text)
    # 10 trips x (2 * 8^3) flops — trip-count awareness is the point
    assert summary["hlo_flops"] == pytest.approx(10 * 2 * 8**3)
    assert summary["hlo_bytes"] > 0


def test_union_seconds_counts_nested_events_once():
    assert profile.union_seconds([(0.0, 10.0), (2.0, 5.0), (9.0, 12.0), (20.0, 21.0)]) == 13.0
    assert profile.union_seconds([(0.0, 10.0), (20.0, 21.0)], since=9.5) == 1.5
    # an outer trace event ends after the inner one it holds: counted once
    t0 = time.perf_counter()
    time.sleep(0.3)
    jax.monitoring.record_event_duration_secs(profile.TRACE_EVENT, 0.1)
    jax.monitoring.record_event_duration_secs(profile.TRACE_EVENT, 0.2)
    assert profile.stages(since=t0)["trace_s"] == pytest.approx(0.2, abs=0.02)


def test_retrace_counter_counts_cache_retrieval():
    with RetraceCounter() as rc:
        time.sleep(0.05)
        jax.monitoring.record_event(profile.CACHE_HIT_EVENT)
        jax.monitoring.record_event_duration_secs(profile.CACHE_RETRIEVAL_EVENT, 0.04)
    assert rc.compile_seconds == pytest.approx(0.04)
    assert rc.summary()["cache_hits"] == 1
    assert rc.summary()["cache_misses"] == 0


HLO = """\
%fused_computation.9 (param_0: s32[8]) -> f32[8] {
  %param_0 = s32[8]{0} parameter(0)
  %convert.10 = f32[8]{0} convert(%param_0), metadata={op_name="jit(run)/while/body/\
closed_call/vmap(env_step)/vmap()/convert"}
  ROOT %scatter.11 = f32[8]{0} scatter(%convert.10, %param_0, %convert.10), to_apply=%r.1
}

ENTRY %main.12 (a: f32[8], b: f32[8]) -> (f32[4]) {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[4,8]{1,0} fusion(%p.0), kind=kLoop, calls=%fused_computation.1, \
metadata={op_name="jit(run)/while/body/closed_call/vmap(act)/dot_general" source_file="s.py" \
source_line=3}, backend_config={"flag":{"a":"1"}}
  %dot.2 = f32[4,8]{1,0} dot(%a, %b), metadata={op_name="jit(run)/while/body/update/cond/\
branch_1_fun/vmap(update)/transpose(jvp(update))/dot_general"}
  ROOT %tuple.3 = (f32[4]) tuple(%x), metadata={op_name="jit(run)/while/body/env_step/add"}
  %dynamic-update-slice.4 = f32[8] dynamic-update-slice(%a, %b, %c), metadata={op_name=\
"jit(run)/while/body/dynamic_update_slice"}
  %add.5 = s32[] add(%i, %one), metadata={op_name="jit(run)/while/body/add"}
  %copy.6 = f32[8] copy(%a)
  %wrapped_add.7 = f32[] fusion(%a), kind=kLoop, calls=%c.7, metadata={op_name=\
"jit(run)/while/body/observe/act/add"}
  %mul.8 = f32[] multiply(%a, %b), metadata={op_name="jit(run)/while/body/vmap(observe)/mul"}
  %fusion.13 = f32[8]{0:S(1)} fusion(%i), kind=kCustom, calls=%fused_computation.9
  %copy-start.14 = (f32[4,8]{1,0}, f32[4,8]{1,0:S(1)}, u32[]) copy-start(%dot.2)
  %copy-done.15 = f32[4,8]{1,0:S(1)} copy-done(%copy-start.14)
  %copy.16 = f32[8]{0:S(1)} copy(%add.5)
  %fusion.17 = f32[8]{0} fusion(%copy.16), kind=kLoop, calls=%fused_computation.1, \
metadata={op_name="jit(run)/while/body/closed_call/vmap(act)/add"}
}
"""


def test_op_phases_parses_scopes_from_hlo_text():
    text = HLO.replace("\\\n", "")
    assert profile.op_phases(text) == {
        "convert.10": "env_step",
        "fusion.1": "act",          # vmap(act), fusion line with backend_config
        "dot.2": "update",          # update, transpose(jvp(update))
        "tuple.3": "env_step",      # ROOT line
        "mul.8": "observe",
        "fusion.17": "act",
        # no op_name: made by the compiler, so it takes the scope of what it fuses,
        "scatter.11": "env_step",
        "fusion.13": "env_step",
        # else of its operand (an async copy of the update's dot)
        "copy-start.14": "update",
        "copy-done.15": "update",
        # else of its user
        "copy.16": "act",
    }  # no phase (dynamic_update_slice, loop add, a copy of a parameter) or two: nothing
    assert profile.scopes_in("a/vmap(observe)/act/transpose(jvp(update))") == (
        "observe", "act", "update")
    assert profile.scopes_in("x/mul;update/add") == ("update",)
    assert profile.scopes_in("x/make_ppo_system.<locals>.update/jit(act_fn)/add") == ()
    assert profile.op_phases(text, profile.UPDATE_PARTS) == {}


SMALL_SYSTEMS = [("ippo", {}), ("rec_ippo", {"recurrent_core": "linear"}), ("vdn", {})]


@pytest.mark.parametrize("name, overrides", SMALL_SYSTEMS, ids=[n for n, _ in SMALL_SYSTEMS])
def test_every_phase_names_ops_of_the_compiled_program(name, overrides):
    from repro.core.system import make_anakin
    from repro.systems import make_system

    kw = dict(rollout_len=4, hidden_sizes=(8,)) if name != "vdn" else {}
    system = make_system(name, MatrixGame(horizon=10), **kw, **overrides)
    program = make_anakin(system, 4, 2, num_seeds=2)
    abstract = jax.eval_shape(program.init_fn, jax.random.key(0))
    text = program.fused.lower(abstract).compile().as_text()
    phases = profile.op_phases(text)
    assert set(phases.values()) == set(profile.PHASES)
    # no instruction sits under two phases
    op_names = re.findall(r'op_name="([^"]*)"', text)
    assert op_names and all(len(profile.scopes_in(n)) <= 1 for n in op_names)
    if name != "vdn":
        parts = profile.op_phases(text, profile.UPDATE_PARTS)
        assert set(parts.values()) == set(profile.UPDATE_PARTS)
        assert all(phases[op] == "update" for op in parts if op in phases)
    # the runner registered the program: phase_map resolves the same map
    assert profile.phase_map().items() >= phases.items()
