"""The per-layer metrics that read the tick's span tree (PR 25), and
``scope_times.py`` on a trace recorded on the chip.  Run by hand with the
other harness tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import run as bench_run  # noqa: E402
import scope_times  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCHMARK = json.load(_f)
#: the metrics PR 25 appended: they read the spans and counters it added
NEW = ("tick_busy_ms_mean", "tick_unattributed_pct", "process_us_per_event",
       "batch_assemble_us_per_event", "step_dispatch_ms_mean",
       "step_block_ms_mean", "emit_decode_us_per_record",
       "emit_dispatch_us_per_record", "probe_rounds_per_step",
       "step_h2d_bytes_per_event", "emit_d2h_bytes_per_record")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_traced_rehearsal_reads_every_tick_span_metric(cell):
    wanted = {m["name"] for m in BENCHMARK["per_layer"]
              if m["name"].startswith(NEW) and bench_run.applies(m, cell)}
    assert wanted, cell
    code, line = bench_run.run_cell(argparse.Namespace(
        workload=cell, seed=2_147_483_777, seconds=2.0, trace=1,
        rehearse=True, control="", keep_trace=""))
    assert code == 0 and line["correct"] is True
    metrics = {n: m["value"] for n, m in line["metrics"].items()}
    assert wanted <= set(metrics)
    assert all(metrics[n] > 0 for n in wanted)
    suffix = ".paced" if cell.endswith(".paced") else ""
    # a tick's own duration cannot exceed the window's seconds per tick
    assert metrics["tick_busy_ms_mean" + suffix] <= metrics["tick_ms_mean" + suffix]
    if not suffix:
        assert metrics["tick_unattributed_pct"] < 10
        # the children of the device step's span account for it
        assert (metrics["step_dispatch_ms_mean"] + metrics["step_block_ms_mean"]
                <= metrics["step_wait_ms_mean"])
    # the device's idle gaps carry the new spans' names
    gaps = {name for name, _s in line["breakdown"]["idle_gaps"]}
    assert gaps & {"emit.dispatch", "emit.decode", "batch.assemble", "step.dispatch"}


def test_scope_times_on_a_recorded_trace():
    """Recorded on one v5e chip: three calls of a jitted step whose
    operators carry named scopes, ``probe_insert`` around a while loop."""
    per_scope, carried = scope_times.scope_seconds(
        os.path.join(HERE, "data", "small_scopes.xplane.pb"))
    assert {"probe_insert", "scatter_combine", "emit_compact"} <= set(per_scope)
    assert carried.get("tf_op", 0) >= 3
    busy = sum(per_scope.values())
    assert 0 < busy < 1.0 and all(s >= 0 for s in per_scope.values())
    # a while's body is counted once: self times add up to the busy time
    planes = scope_times.device_planes(
        os.path.join(HERE, "data", "small_scopes.xplane.pb"))
    events = scope_times.op_events(planes[0])
    assert sum(scope_times.self_times(events).values()) / 1e12 == pytest.approx(busy)
    assert busy <= sum(d for _m, _s, d in events) / 1e12


def test_scope_of_an_op_name():
    assert scope_times.scope_of(
        "jit(_trace_step)/jit(main)/probe_insert/while/body/gather") == "probe_insert"
    assert scope_times.scope_of(
        "jit(_trace_step)/source_decode/probe_find/while/cond/lt") == "source_decode/probe_find"
    assert scope_times.scope_of("jit(small_step)/dot_general") == scope_times.NO_SCOPE
    nested = [(1, 0, 100), (2, 10, 30), (3, 20, 10), (2, 50, 20), (4, 100, 5)]
    assert scope_times.self_times(nested) == {1: 50, 2: 40, 3: 10, 4: 5}
