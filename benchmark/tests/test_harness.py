"""CPU rehearsal tests of the benchmark's harness.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are not part of the repository's tier-1 run (``pytest tests/``).  Every
test runs the harness at a configuration's ``rehearse`` sizes on whatever
platform JAX has; none of their numbers is a chip's.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import metric_reader  # noqa: E402
import run as bench_run  # noqa: E402
import trace_reduce  # noqa: E402
import work_bytes  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
ONE_CHIP_CELLS = [w["name"] for w in BENCHMARK["workloads"] if w["chips"] == 1]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def args_for(cell: str, seed: int = 2_147_483_777, trace: int = 0,
             control: str = "", seconds: float = 2.0) -> argparse.Namespace:
    return argparse.Namespace(workload=cell, seed=seed, seconds=seconds, trace=trace,
                              rehearse=True, control=control, keep_trace="")


def rehearse(cell: str, before_window=None, **kw):
    code, line = bench_run.run_cell(args_for(cell, **kw), before_window=before_window)
    assert code == 0 and line is not None
    return line


# ------------------------------------------------- the harness, end to end
@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_end_to_end(cell, trace):
    """The command as the driver gives it (plus --rehearse), in a process
    of its own: the last line of standard output is the result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if cell not in ONE_CHIP_CELLS:
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "4294967311", "--seconds", "2", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(line) and list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["rehearsal"] is True and line["device"]["platform"] != "tpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    group = BENCHMARK["per_layer"] if trace else BENCHMARK["end_to_end"]
    wanted = {m["name"] for m in group if bench_run.applies(m, cell)}
    # off the chip a share of a peak has nothing to read and is left out
    wanted = {n for n in wanted if not n.startswith("step_roofline")}
    assert set(line["metrics"]) == wanted
    # the CPU keeps no memory peak, and a tiny rehearsal may never queue
    may_be_zero = ("hbm_peak_bytes", "backlog_rows_max")
    assert all(m["value"] > 0 or n.startswith(may_be_zero) for n, m in line["metrics"].items())
    if trace:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    # each number compared stands beside its limit at the end of stderr
    assert "COMPARED correct=True" in out.stderr.splitlines()[-1]


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2 and out.stdout.strip() == ""


def test_refuses_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files: no result, exit code not 0."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""),
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""


# --------------------------------------- the control: correct must be false
CONTROLS = {
    "pageviews": ["lost_event", "lost_tick", "stale_count"],
}


def _deployment_of(cell: str) -> str:
    _, _, config, _ = bench_run.load_cell(cell, True)
    return config["deployment"]


@pytest.mark.parametrize("cell,control", [
    (c, k) for c in ONE_CHIP_CELLS if c.endswith(".saturated")
    for k in CONTROLS[_deployment_of(c)]
])
def test_control_is_not_correct(cell, control, capfd):
    """The reference with one guarantee broken, in the program's place,
    fails the comparison that the program's own answers pass."""
    line = rehearse(cell, control=control)
    assert line["correct"] is False and line["control"] == control
    err = capfd.readouterr().err
    program = next(l for l in err.splitlines() if l.startswith("BENCH program_numbers"))
    assert json.loads(program.split(" ", 2)[2])["correct"] is True


# ------------------------------- faults planted under the timed path
def _alter_an_answer(run):
    """One result record altered where it is produced."""
    real, state = run.sink.produce, {"n": 0}

    def produce(record):
        state["n"] += 1
        if state["n"] == 50:
            row = json.loads(record.value)
            row["CNT"] += 1000
            record = dataclasses.replace(record, value=json.dumps(row, separators=(",", ":")))
        return real(record)

    run.sink.produce = produce


def _leave_out_half_a_batch(run):
    """One tick polls its records and hands on only every second one."""
    consumer = run.handle.consumer
    real, state = consumer.poll, {"done": False}

    def poll(max_records=4096):
        records = real(max_records)
        if not state["done"] and len(records) > 8:
            state["done"] = True
            return records[::2]
        return records

    consumer.poll = poll


def _return_state_unchanged(run):
    """One device step computes its emits and hands back the state it was
    given."""
    import jax
    import jax.numpy as jnp

    device = run.ex.device
    real, state = device._step, {"n": 0}

    def step(store, arrays):
        state["n"] += 1
        if state["n"] == 3:
            kept = jax.tree_util.tree_map(jnp.copy, store)
            _new, emits = real(store, arrays)
            return kept, emits
        return real(store, arrays)

    device._step = step


FAULTS = [
    ("pv_count.saturated", _alter_an_answer),
    ("pv_count.saturated", _leave_out_half_a_batch),
    ("pv_count.saturated", _return_state_unchanged),
    ("pv_count.paced", _alter_an_answer),
]


@pytest.mark.parametrize("cell,fault", [f for f in FAULTS if f[0] in CELLS],
                         ids=lambda v: v if isinstance(v, str) else v.__name__)
def test_fault_under_the_timed_path_is_not_correct(cell, fault):
    """The harness's look for a chip skipped, the rest of a run driven with
    the timed path broken underneath: ``correct`` comes out false."""
    line = rehearse(cell, before_window=fault)
    assert line["correct"] is False, line["compared"]
    assert any(n["value"] > n["limit"] for n in line["compared"].values())


# ------------------------------------------------------ the small pieces
def test_trace_reduce_on_a_recorded_trace():
    """A trace recorded on one v5e chip: three calls of a jitted
    ``small_step`` between the harness's two anchors."""
    red = trace_reduce.reduce_file(
        os.path.join(HERE, "data", "small.xplane.pb"),
        host_spans=[("process", 0.0, 1e9, 0)])
    assert red["on_device"] and red["devices"] == 1
    assert red["top_module"] == "jit_small_step"
    # the device's clock runs some tens of microseconds ahead of the
    # host's anchors, so the first call may fall just outside the window
    assert red["obs"]["trace.step.calls"] in (2, 3)
    assert 0 < red["busy_s"] < red["window_s"] < 1.0
    assert 0 < red["obs"]["trace.idle_pct"] < 100
    per_call = red["obs"]["trace.step.total_ms"] / red["obs"]["trace.step.calls"]
    assert 0.001 < per_call < 5.0
    assert red["breakdown"]["device_ops"] and red["breakdown"]["idle_gaps"]
    assert red["aligned"] is True


def test_interval_arithmetic():
    cover = trace_reduce.union([(5, 7), (0, 2), (1, 3), (6, 6.5)])
    assert cover == [(0, 3), (5, 7)]
    assert trace_reduce.gaps(cover, 0, 10) == [(3, 5), (7, 10)]
    assert trace_reduce.clipped([(0, 4), (8, 12), (20, 30)], 2, 10) == [(2, 4), (8, 10)]
    assert trace_reduce._module_name("jit__trace_step(12345)") == "jit__trace_step"
    spans = [("poll", 0, 10, 0), ("process", 10, 50, 0), ("deserialize", 12, 30, 1)]
    assert trace_reduce._covering(spans, 13, 29) == {"deserialize": 16}
    assert trace_reduce._covering(spans, 5, 35) == {"deserialize": 18, "poll": 5, "process": 7}
    assert trace_reduce._covering(spans, 45, 70) == {"process": 5, "between_ticks": 20}
    assert trace_reduce._op_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") == "fusion.3"


def test_work_bytes_on_known_shapes():
    widths = {"per_input_row": {"key": 8, "ts": 8, "slot": 16, "agg_r": 8, "agg_w": 8},
              "per_emitted_row": {"key": 8, "window": 8, "count": 8}}
    assert work_bytes.step_min_bytes(widths, 4096, 3000) == 4096 * 48 + 3000 * 24
    assert work_bytes.step_min_bytes(widths, 0, 0) == 0
    with pytest.raises(ValueError):
        work_bytes.step_min_bytes(widths, -1, 0)


def test_peaks_table():
    assert work_bytes.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work_bytes.peaks_for("TPU v9 imaginary")
    assert work_bytes.peaks_for("cpu", rehearse=True) == {}


def test_metric_reader_returns_nothing_when_there_is_nothing_to_read():
    spec = {"unit": "%", "num": ["a"], "den": ["b"], "scale": 100.0}
    assert metric_reader.read(spec, {"a": 1.0, "b": 4.0}) == 25.0
    assert metric_reader.read(spec, {"a": 1.0}) is None          # nothing observed
    assert metric_reader.read(spec, {"a": 1.0, "b": 0.0}) is None
    assert metric_reader.read(spec, {"a": 0.0, "b": 4.0}) is None  # never 0 for a share
    assert metric_reader.read({"unit": "ms", "num": ["a"]}, {"a": 0.0}) == 0.0


def test_every_named_file_exists():
    bench = BENCHMARK
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert os.path.exists(os.path.join(BENCH, "deployments", cfg["deployment"] + ".py"))
        assert all(k in cfg for k in c["reduced"])
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    for m in bench["per_layer"]:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics", m["name"] + ".json")))
        assert (spec["layer"], spec["unit"], spec["moves"], spec["source"]) == (
            m["layer"], m["unit"], m["moves"], m["source"])
