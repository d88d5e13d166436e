"""The cell ``pv_hopstats.backlog`` (``pageviews_hopping_stats``: HOPPING 1 h
/ 15 min, COUNT/SUM/AVG/MIN/MAX over a DOUBLE, GRACE PERIOD 15 MINUTES, on
the sliced store) at the configuration's ``rehearse`` sizes.  Run by hand
with the other harness tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The cell's controls live here: ``test_harness.py``'s table knows the
``pageviews`` deployment only and builds its cases for ``.saturated`` names
(hence this cell's name).
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

CELL = "pv_hopstats.backlog"
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCHMARK = json.load(_f)
#: the metrics this cell brought
HOP = ["emits_per_event.hop", "emit_lane_fill_pct.hop", "store_grave_pct.hop"]
COMPARED = {"compiles_in_window", "results_unplaced", "sink_rows_wrong", "sink_rows_extra",
            "sink_events_missing", "sink_counts_backwards", "sink_avg_rel_err_max",
            "store_keys_missing", "store_keys_lingering", "pulls_wrong"}


def rehearse(trace: int, control: str = ""):
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
           "--seed", "4294967311", "--seconds", "2", "--trace", str(trace), "--rehearse"]
    out = subprocess.run(cmd + (["--control", control] if control else []), cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
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


def test_the_configuration_is_pageviews_counts_view_with_a_latency():
    by_name = {c["name"]: c for c in BENCHMARK["configs"]}
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "pageviews_hopping_stats", "saturated", 1)
    hop, one = (json.load(open(os.path.join(ROOT, by_name[n]["file"])))
                for n in ("pageviews_hopping_stats", "pageviews_count"))
    # the record, key universe, skew, rates and fill are pageviews_count's
    # own; what is added is the latency draw and what the reference needs to
    # know of the set-up's batches
    differs = {k for k in hop["sizes"] if hop["sizes"][k] != one["sizes"].get(k)}
    assert differs == {"latency_quarters", "fill_batch_events", "evict_cadence_batches"}
    assert differs <= set(hop["assumed"]) | {"latency_quarters"}
    assert "grace_period" in hop["assumed"] and "fill_events" not in hop["assumed"]
    assert hop["engine_props"] == one["engine_props"]
    assert hop["source"] == by_name["pageviews_hopping_stats"]["source"] != one["source"]
    assert hop["deployment"] == "pageviews_hopping" and hop["chips"] == 1
    assert hop["native_ingest"] is True
    assert list(hop["reduced"]) == by_name["pageviews_hopping_stats"]["reduced"] == [
        "state_entries_at_window_start"]
    assert "GRACE PERIOD 15 MINUTES" in hop["statements"][1]
    assert "HOPPING (SIZE 1 HOUR, ADVANCE BY 15 MINUTES" in hop["statements"][1]
    # the fill's batches are the engine's, at full size and in the rehearsal
    assert hop["sizes"]["evict_cadence_batches"] == 64
    for part in (hop, hop["rehearse"]):
        assert part["sizes"]["fill_batch_events"] == part["engine_props"]["ksql.batch.capacity"]
    assert hop["state_entries_at_window_start"] > 0


def test_untraced_rehearsal_reports_the_end_to_end_metrics():
    line, said = rehearse(trace=0)
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["compared"]) == COMPARED
    assert all(n["value"] == 0 for n in line["compared"].values())
    assert line["compared"]["sink_avg_rel_err_max"]["limit"] == 1e-12
    assert said["start"]["config"] == "pageviews_hopping_stats"
    assert set(line["metrics"]) == wanted("end_to_end") == {"events_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    # keys expire in the rehearsal too: the store holds fewer URLs than it saw
    assert 0 < said["answers_read"]["store"]["live_keys"] < 8000


def test_traced_rehearsal_reads_every_per_layer_metric():
    line, _said = rehearse(trace=1)
    assert line["correct"] is True
    # off the chip a share of a peak has nothing to read and is left out
    names = {n for n in wanted("per_layer") if not n.startswith("step_roofline")}
    assert set(line["metrics"]) == names and set(HOP) <= names
    metrics = {n: m["value"] for n, m in line["metrics"].items()}
    # one event answers up to four windows; a batch coalesces some
    assert 1.0 < metrics["emits_per_event.hop"] <= 4.0
    assert 0.0 < metrics["emit_lane_fill_pct.hop"] <= 100.0
    assert 0.0 < metrics["store_grave_pct.hop"] < 100.0


@pytest.mark.parametrize("control", ["lost_event", "lost_tick", "lost_window", "stale_stat"])
def test_control_is_not_correct(control):
    line, said = rehearse(trace=0, control=control)
    assert line["correct"] is False and line["control"] == control
    assert said["program_numbers"]["correct"] is True
    assert line["compared"]["sink_rows_wrong"]["value"] >= 1
