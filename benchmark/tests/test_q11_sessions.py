"""The cell ``q11_sessions.backlog`` (``nexmark_q11_sessions``: NEXmark q11,
SESSION 10 s per bidder over the bid stream, event time from ``DATETIME``)
at the configuration's ``rehearse`` sizes, and the files it brought.  Run
by hand with the other harness tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The cell's controls live here: ``test_harness.py``'s table knows the
``pageviews`` deployment only.
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

CELL = "q11_sessions.backlog"
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCHMARK = json.load(_f)
#: the metrics this cell brought
SESS = ["session_item_fill_pct.sess", "tombstones_per_event.sess"]
COMPARED = {"compiles_in_window", "results_unplaced", "sink_sessions_wrong",
            "sink_sessions_extra", "sink_sessions_missing", "sink_events_missing",
            "store_sessions_diff", "pulls_wrong"}
#: what the benchmark had before this cell: its configurations and cells
OLDER_CONFIGS = ["pageviews_count", "clicks_users_join", "pageviews_count_mesh4",
                 "pageviews_hopping_stats"]
OLDER_CELLS = ["pv_count.saturated", "pv_count.paced", "clicks_join.backlog",
               "pv_count.mesh4", "pv_hopstats.backlog", "pv_count.hotset"]


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


def test_the_configuration_and_the_cell():
    by_name = {c["name"]: c for c in BENCHMARK["configs"]}
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nexmark_q11_sessions", "saturated", 1)
    config = json.load(open(os.path.join(ROOT, by_name["nexmark_q11_sessions"]["file"])))
    assert config["source"] == by_name["nexmark_q11_sessions"]["source"]
    assert config["deployment"] == "nexmark_sessions" and config["native_ingest"] is True
    assert list(config["reduced"]) == by_name["nexmark_q11_sessions"]["reduced"] == [
        "state_entries_at_window_start"]
    assert "TIMESTAMP='DATETIME'" in config["statements"][0]
    assert "WINDOW SESSION (10 SECONDS)" in config["statements"][1]
    assert "GRACE" not in config["statements"][1]  # ksqlDB's default of 24 h
    sizes = config["sizes"]
    assert (sizes["hot_bidders_ratio"], sizes["hot_bidder_batch"], sizes["num_active_people"],
            sizes["first_event_rate"], sizes["prob_delayed_event"],
            sizes["occasional_delay_sec"]) == (4, 100, 1000, 10000, 0.1, 3)
    assert (sizes["person_proportion"], sizes["auction_proportion"],
            sizes["bid_proportion"]) == (1, 3, 46)
    assert sizes["fill_events"] >= 2_000_000
    assert config["engine_props"] == {"ksql.batch.capacity": 32768, "ksql.state.slots": 2097152}


def test_the_older_configurations_and_cells_are_as_they_were():
    """What this cell added is appended: the first configurations and cells
    keep their places and their files."""
    assert [c["name"] for c in BENCHMARK["configs"]][:4] == OLDER_CONFIGS
    assert [w["name"] for w in BENCHMARK["workloads"]][:6] == OLDER_CELLS
    assert [w["name"] for w in BENCHMARK["workloads"]][6:] == [CELL]
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        cells = m.get("workloads", [])
        assert CELL not in cells[:-1], m["name"]


def test_untraced_rehearsal_reports_the_end_to_end_metrics():
    line, said = rehearse(trace=0)
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["compared"]) == COMPARED
    assert all(n["value"] == 0 and n["limit"] == 0 for n in line["compared"].values())
    assert said["start"]["config"] == "nexmark_q11_sessions"
    assert set(line["metrics"]) == wanted("end_to_end") == {"events_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    assert said["answers_read"]["store"]["live_sessions"] > 0


def test_traced_rehearsal_reads_every_per_layer_metric():
    line, _said = rehearse(trace=1)
    assert line["correct"] is True
    # off the chip a share of a peak has nothing to read and is left out
    names = {n for n in wanted("per_layer") if not n.startswith("step_roofline")}
    assert set(line["metrics"]) == names and set(SESS) <= names
    metrics = {n: m["value"] for n, m in line["metrics"].items()}
    assert 0.0 < metrics["session_item_fill_pct.sess"] < 100.0
    assert 0.0 < metrics["tombstones_per_event.sess"] < 1.0


@pytest.mark.parametrize("control", ["lost_event", "lost_tick", "unbridged", "lost_tombstone"])
def test_control_is_not_correct(control):
    line, said = rehearse(trace=0, control=control)
    assert line["correct"] is False and line["control"] == control
    assert said["program_numbers"]["correct"] is True
