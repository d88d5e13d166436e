"""The cell ``q19_topk.backlog`` (``nexmark_q19_topk``: NEXmark q19, the ten
best bids of each auction, ``TOPK(PRICE, BIDDER, DATETIME, 10)`` over q11's
bid stream) at the configuration's ``rehearse`` sizes, and the files it
brought.  Run by hand with the other harness tests:

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

CELL = "q19_topk.backlog"
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCHMARK = json.load(_f)
#: the metrics this cell brought
TOP = ["topk_admit_pct.top", "topk_slots_per_step.top"]
COMPARED = {"compiles_in_window", "results_unplaced", "sink_rows_wrong", "sink_rows_extra",
            "sink_events_missing", "sink_counts_backwards", "store_entries_diff",
            "pulls_wrong"}
#: what the benchmark had before this cell: its configurations and cells
OLDER_CONFIGS = ["pageviews_count", "clicks_users_join", "pageviews_count_mesh4",
                 "pageviews_hopping_stats", "nexmark_q11_sessions"]
OLDER_CELLS = ["pv_count.saturated", "pv_count.paced", "clicks_join.backlog",
               "pv_count.mesh4", "pv_hopstats.backlog", "pv_count.hotset",
               "q11_sessions.backlog"]
#: the per-layer metrics that read nothing in this cell: the sink's block
#: encode declines an ARRAY column, so every emit takes the per-emit path;
#: and no collection fell inside that path's dispatch in a rehearsal
NOT_READ = {"emit_block_pct", "sink_encode_us_per_record", "sink_encode_bulk_pct",
            "emit_callbacks_us_per_record", "emit_dispatch_gc_us_per_record"}


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
        "nexmark_q19_topk", "backlog_bids", 1)
    config = json.load(open(os.path.join(ROOT, by_name["nexmark_q19_topk"]["file"])))
    assert config["source"] == by_name["nexmark_q19_topk"]["source"]
    assert config["deployment"] == "nexmark_topk" and config["native_ingest"] is True
    assert list(config["reduced"]) == by_name["nexmark_q19_topk"]["reduced"] == [
        "state_entries_at_window_start"]
    assert "TOPK(PRICE, BIDDER, DATETIME, 10)" in config["statements"][1]
    assert "WINDOW" not in config["statements"][1]
    # q11's bid stream: the same statements for the stream, the same sizes
    q11 = json.load(open(os.path.join(ROOT, by_name["nexmark_q11_sessions"]["file"])))
    assert config["statements"][0] == q11["statements"][0]
    assert {k: v for k, v in config["sizes"].items() if k != "k"} == q11["sizes"]
    assert config["sizes"]["k"] == 10
    assert config["engine_props"] == {"ksql.batch.capacity": 32768, "ksql.state.slots": 2097152}


def test_the_traffic_is_saturated_at_a_higher_rate():
    with open(os.path.join(BENCH, "traffic", "saturated.json")) as f:
        saturated = json.load(f)
    with open(os.path.join(BENCH, "traffic", "backlog_bids.json")) as f:
        bids = json.load(f)
    assert bids.pop("window_events_per_s") == 60_000
    assert saturated.pop("window_events_per_s") == 45_000
    assert bids == saturated


def test_the_older_configurations_and_cells_are_as_they_were():
    """What this cell added is appended: the first configurations and cells
    keep their places and their files."""
    assert [c["name"] for c in BENCHMARK["configs"]][:5] == OLDER_CONFIGS
    assert [w["name"] for w in BENCHMARK["workloads"]][:7] == OLDER_CELLS
    assert [w["name"] for w in BENCHMARK["workloads"]][7:] == [CELL]
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        cells = m.get("workloads", [])
        assert CELL not in cells[:-1], m["name"]


def test_untraced_rehearsal_reports_the_end_to_end_metrics():
    line, said = rehearse(trace=0)
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["compared"]) == COMPARED
    assert all(n["value"] == 0 and n["limit"] == 0 for n in line["compared"].values())
    assert said["start"]["config"] == "nexmark_q19_topk"
    assert set(line["metrics"]) == wanted("end_to_end") == {"events_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    assert said["answers_read"]["store"]["live_auctions"] > 0


def test_traced_rehearsal_reads_every_per_layer_metric():
    line, _said = rehearse(trace=1)
    assert line["correct"] is True
    # off the chip a share of a peak has nothing to read and is left out
    names = {n for n in wanted("per_layer") if not n.startswith("step_roofline")}
    assert set(line["metrics"]) == names and set(TOP) <= names
    assert not names & NOT_READ
    metrics = {n: m["value"] for n, m in line["metrics"].items()}
    assert 0.0 < metrics["topk_admit_pct.top"] < 100.0
    assert metrics["topk_slots_per_step.top"] > 0.0


@pytest.mark.parametrize("control", ["lost_event", "lost_tick", "stale_topk", "tie_order"])
def test_control_is_not_correct(control):
    line, said = rehearse(trace=0, control=control)
    assert line["correct"] is False and line["control"] == control
    assert said["program_numbers"]["correct"] is True
