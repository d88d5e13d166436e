"""The cell ``pv_count.mesh4`` (``pageviews_count_mesh4``: four lanes, the
GROUP BY repartition as an all-to-all, the store sharded over four devices)
at the configuration's ``rehearse`` sizes on four virtual CPU devices.  Run
by hand with the other harness tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Every run is a process of its own: the four devices are asked for in
``XLA_FLAGS`` before JAX is imported, which a test that runs a cell inside
pytest's process (``test_tick_spans.py``) cannot do for itself.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

CELL = "pv_count.mesh4"
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCHMARK = json.load(_f)
#: the metrics this cell brought: the exchange's counters, and the step's
#: share of four chips' bandwidth (read on the chip only)
EXCHANGE = ["exchange_rows_per_event.mesh4", "exchange_fullest_shard_pct.mesh4",
            "exchange_wire_bytes_per_event.mesh4", "exchange_bucket_fill_pct.mesh4"]


def rehearse(trace: int, control: str = ""):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
           "--seed", "4294967311", "--seconds", "2", "--trace", str(trace), "--rehearse"]
    out = subprocess.run(cmd + (["--control", control] if control else []),
                         cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    said = {}
    for row in out.stderr.splitlines():
        if row.startswith("BENCH "):
            _, step, facts = row.split(" ", 2)
            said[step] = json.loads(facts)
    return json.loads(out.stdout.strip().splitlines()[-1]), said


def wanted(group: str):
    return {m["name"] for m in BENCHMARK[group]
            if "workloads" not in m or CELL in m["workloads"]}


def test_the_configuration_is_the_one_chip_cells_on_four_shards():
    by_name = {c["name"]: c for c in BENCHMARK["configs"]}
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "pageviews_count_mesh4", "saturated", 4)
    mesh, one = (json.load(open(os.path.join(ROOT, by_name[n]["file"])))
                 for n in ("pageviews_count_mesh4", "pageviews_count"))
    same = ("statements", "sizes", "work_bytes", "rehearse", "deployment",
            "native_ingest", "reduced", "state_entries_at_window_start")
    assert all(mesh[k] == one[k] for k in same)
    assert mesh["guarantees"][:len(one["guarantees"])] == one["guarantees"]
    assert mesh["source"] == by_name["pageviews_count_mesh4"]["source"] != one["source"]
    props = mesh["engine_props"]
    assert props["ksql.runtime.backend"] == "distributed" and props["ksql.device.shards"] == 4
    assert props["ksql.batch.capacity"] == one["engine_props"]["ksql.batch.capacity"]
    assert mesh["chips"] == 4 and list(by_name["pageviews_count_mesh4"]["reduced"]) == list(mesh["reduced"])


def test_untraced_rehearsal_reports_the_end_to_end_metrics():
    line, said = rehearse(trace=0)
    assert line["correct"] is True and line["rehearsal"] is True
    assert all(n["value"] == 0 and n["limit"] == 0 for n in line["compared"].values())
    assert line["device"]["count"] == 4 and said["start"]["config"] == "pageviews_count_mesh4"
    assert set(line["metrics"]) == wanted("end_to_end") == {"events_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0


def test_traced_rehearsal_reads_every_per_layer_metric():
    line, said = rehearse(trace=1)
    assert line["correct"] is True and line["device"]["count"] == 4
    # off the chip a share of a peak has nothing to read and is left out
    names = {n for n in wanted("per_layer") if not n.startswith("step_roofline")}
    assert set(line["metrics"]) == names and set(EXCHANGE) <= names
    assert "step_roofline.mesh4" in wanted("per_layer")
    assert "step_roofline" not in wanted("per_layer")  # one chip's bandwidth
    metrics = {n: m["value"] for n, m in line["metrics"].items()}
    assert all(v > 0 or n == "hbm_peak_bytes" for n, v in metrics.items())
    # COUNT(*) filters nothing: every polled row crosses once
    assert metrics["exchange_rows_per_event.mesh4"] == 1.0
    assert 25.0 <= metrics["exchange_fullest_shard_pct.mesh4"] <= 100.0
    assert 0.0 < metrics["exchange_bucket_fill_pct.mesh4"] <= 100.0
    # what is shipped, over the share of it that is rows: a row's width
    row_bytes = (metrics["exchange_wire_bytes_per_event.mesh4"]
                 * metrics["exchange_bucket_fill_pct.mesh4"] / 100.0)
    assert row_bytes == pytest.approx(round(row_bytes)) and 24 <= row_bytes <= 256
    # the lane split runs under the host batch's span
    assert metrics["batch_assemble_us_per_event"] > 0
    # the CPU backend's operations stand in as one device; on the chip the
    # trace holds a plane a chip (trace.devices 4)
    assert said["trace_reduced"]["devices"] >= 1
    ops = [name for name, _s in line["breakdown"]["device_ops"]]
    assert any(name.startswith("all-to-all") for name in ops), ops


def test_control_is_not_correct():
    line, said = rehearse(trace=0, control="lost_event")
    assert line["correct"] is False and line["control"] == "lost_event"
    assert said["program_numbers"]["correct"] is True
