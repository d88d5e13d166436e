"""The cell ``clicks_join.backlog``: its controls and the faults planted
under its timed path, at the configuration's ``rehearse`` sizes on whatever
platform JAX has.  Run by hand with the other harness tests:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

``test_harness.py`` builds its control and fault cases from tables that
know the ``pageviews`` deployment only; this cell's live here.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import run as bench_run  # noqa: E402

CELL = "clicks_join.backlog"
CONTROLS = ["lost_event", "lost_tick", "stale_table"]


def rehearse(before_window=None, control: str = ""):
    code, line = bench_run.run_cell(
        argparse.Namespace(workload=CELL, seed=2_147_483_777, seconds=2.0, trace=0,
                           rehearse=True, control=control, keep_trace=""),
        before_window=before_window)
    assert code == 0 and line is not None
    return line


def test_the_configuration_states_its_source_and_its_cut():
    bench, cell, config, traffic = bench_run.load_cell(CELL, False)
    assert cell["chips"] == 1 and config["native_ingest"] is False
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == config["source"] and "_schema.avro" in config["source"]
    assert set(config["reduced"]) == set(entry["reduced"]) == {"table_changes_in_window"}
    assert config["table_changes_in_window"] == 0
    assert config["sizes"]["users_changelog_records"] == 4 * config["engine_props"]["ksql.batch.capacity"]
    assert traffic["mode"] == "backlog" and traffic["window_events_per_s"] == 120_000
    assert bench_run.load_cell(CELL, True)[2]["sizes"]["users_changelog_records"] == 4_096


def test_the_corpus_is_the_generators():
    """Ten users, every pageview's user among them, viewtime an iteration
    from 1 in steps of 10, and a seed that only orders the pageviews."""
    _bench, _cell, config, _traffic = bench_run.load_cell(CELL, True)
    dep = bench_run.load_deployment(config["deployment"])
    a, b = (dep.make_corpus(seed, config["sizes"], 5_000) for seed in (1, 2_147_483_777))
    assert a.payloads[:2] != b.payloads[:2] and a.preload == b.preload
    block = config["sizes"]["seed_block_events"]
    assert sorted(p.split(",", 1)[1] for p in a.payloads[:block]) == sorted(
        p.split(",", 1)[1] for p in b.payloads[:block])
    assert [int(p.split(",")[0]) for p in a.payloads[:3]] == [1, 11, 21]
    table = dep.reference_table(a)
    assert sorted(table) == sorted(dep.USERS) and len(dep.USERS) == 10
    assert set(a.view_user) <= set(table) and set(a.view_page) <= set(dep.PAGES)
    assert {g for _r, g in table.values()} <= set(dep.GENDERS)
    key, value, _ts = a.preload[0][1][0]
    assert json.loads(value)["userid"] == key and list(json.loads(value)) == [
        "registertime", "userid", "regionid", "gender"]


@pytest.mark.parametrize("control", CONTROLS)
def test_control_is_not_correct(control, capfd):
    """The reference with one guarantee broken, in the program's place,
    fails the comparison that the program's own answers pass."""
    line = rehearse(control=control)
    assert line["correct"] is False and line["control"] == control
    err = capfd.readouterr().err
    program = next(l for l in err.splitlines() if l.startswith("BENCH program_numbers"))
    assert json.loads(program.split(" ", 2)[2])["correct"] is True


# ------------------------------- faults planted under the timed path
def _alter_a_region(run):
    """One result record's REGIONID altered where it is produced."""
    real, state = run.sink.produce, {"n": 0}

    def produce(record):
        state["n"] += 1
        if state["n"] == 50:
            viewtime, page, _region, gender = record.value.split(",")
            record = dataclasses.replace(
                record, value=",".join((viewtime, page, "Region_0", gender)))
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


@pytest.mark.parametrize("fault,number", [
    (_alter_a_region, "sink_rows_wrong"),
    (_leave_out_half_a_batch, "sink_events_missing"),
], ids=lambda v: v if isinstance(v, str) else v.__name__)
def test_fault_under_the_timed_path_is_not_correct(fault, number):
    line = rehearse(before_window=fault)
    assert line["correct"] is False, line["compared"]
    assert line["compared"][number]["value"] > 0
