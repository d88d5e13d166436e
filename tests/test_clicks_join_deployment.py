"""The served stream-table join against the benchmark deployment's plain
reference (``benchmark/deployments/clicks_join.py``), at a small size on the
CPU: ksql-datagen's users table loaded from a changelog of upserts, with a
tombstone and a re-insert after a tombstone added, then pageviews that hit,
miss and are filtered, through ``KsqlServer``'s engine and ``poll_once``.
Also the join path's spans and counters (``table.upsert``, ``table.grow``,
``device.step`` ``find_rounds`` / ``join_rows`` / ``join_matched``).
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import urllib.request

import numpy as np
import pytest

from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.runtime import checkpoint
from ksql_tpu.runtime.topics import Record
from ksql_tpu.server.rest import KsqlServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CAPACITY = 256
N_CLICKS = 1500
SIZES = {"users_changelog_records": 600, "key_draw": 20240921, "seed_block_events": 64}
#: what ksql-datagen never writes, added to its changelog: a tombstone that
#: stays (User_3's pageviews then miss), one of a key that is not live, and
#: a re-insert after a tombstone
EXTRA = [("User_3", None), ("User_3", None), ("User_7", None),
         ("User_7", ("Region_9", "FEMALE"))]


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


dep = _load("benchmark/deployments/clicks_join.py", "bench_deployment_clicks_join")
with open(os.path.join(ROOT, "benchmark/configs/clicks_users_join.json")) as _f:
    CONFIG = json.load(_f)


def make_corpus(seed: int):
    """The deployment's corpus at test size, with ``EXTRA`` after it."""
    corpus = dep.make_corpus(seed, SIZES, N_CLICKS)
    changelog = [(u, row, dep.REGISTERTIME[0]) for u, row in corpus.changelog + EXTRA]
    return dep.build_corpus(changelog, corpus.view_user, corpus.view_page)


def many_users_corpus(users: int):
    """A table of ``users`` keys (the generator's has ten and never grows
    its store): inserts, then updates and tombstones of every seventh and
    eleventh user, and pageviews of users in and beyond the table."""
    rng = np.random.default_rng(users)
    row = lambda: (dep.REGIONS[rng.integers(10)], dep.GENDERS[rng.integers(3)])  # noqa: E731
    changelog = [(f"User_{k}", row(), dep.REGISTERTIME[0] + k) for k in range(users)]
    changelog += [(f"User_{k}", row(), dep.REGISTERTIME[1]) for k in range(0, users, 7)]
    changelog += [(f"User_{k}", None, dep.REGISTERTIME[1]) for k in range(0, users, 11)]
    view_user = [f"User_{k}" for k in rng.integers(0, users + users // 4, N_CLICKS).tolist()]
    return dep.build_corpus(changelog, view_user,
                            [dep.PAGES[p] for p in rng.integers(0, 99, N_CLICKS).tolist()])


class Served:
    """One served run of the configuration's statements over ``corpus``."""

    def __init__(self, corpus, table_slots: int = 0):
        self.corpus = corpus
        self.engine = KsqlEngine(KsqlConfig({"ksql.batch.capacity": CAPACITY}))
        self.srv = KsqlServer(engine=self.engine, port=0)
        self.srv.start()
        try:
            self._run(table_slots)
        finally:
            self.srv.stop()

    def _run(self, table_slots: int) -> None:
        req = urllib.request.Request(
            self.srv.url + "/ksql",
            data=json.dumps({"ksql": " ".join(CONFIG["statements"])}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            out = json.loads(resp.read().decode())
        qid = [e["commandStatus"]["queryId"] for e in out
               if e.get("commandStatus", {}).get("queryId")][-1]
        handle = self.engine.queries[qid]
        assert handle.backend == "device", dict(self.engine.fallback_reasons)
        self.ex = handle.executor
        self.device = self.ex.device
        if table_slots:
            # the join table's starting capacity has no key of its own: it
            # is set the way a checkpoint's restore sets it
            with self.srv.engine_lock:
                caps = checkpoint._device_caps(self.device)
                caps.update(table_store_capacity=table_slots,
                            join_capacities=[table_slots])
                checkpoint._apply_caps(self.device, caps)
                self.device._compile_steps()
                self.device.state = self.device.init_state()
        broker = self.engine.broker
        with self.srv.engine_lock:
            for topic, rows in self.corpus.preload:
                self._feed(broker.topic(topic), rows)
            self.stages_after_load = self.engine.trace_recorder(qid).stage_stats()
            self._feed(broker.topic(self.corpus.source_topic), [
                (None, p, ts) for p, ts in zip(self.corpus.payloads, self.corpus.ts)])
        assert handle.state == "RUNNING", list(self.engine.processing_log)
        self.stages = self.engine.trace_recorder(qid).stage_stats()
        self.ticks = self.engine.trace_recorder(qid).recent()
        sink = broker.topic(handle.plan.physical_plan.topic)
        self.records = [(r.key, r.window, r.value) for r in sink.all_records()]
        self.store = dep.read_store(self.ex)

    def _feed(self, topic, rows) -> None:
        for lo in range(0, len(rows), CAPACITY):
            for key, value, ts in rows[lo:lo + CAPACITY]:
                topic.produce(Record(key=key, value=value, timestamp=ts))
            while self.engine.poll_once(max_records=CAPACITY) or self.ex.pending_records():
                pass

    def compared(self):
        return dep.compare(self.corpus, N_CLICKS, self.records, self.store, [])


@pytest.fixture(scope="module")
def served():
    return Served(make_corpus(0))


def _all_zero(numbers) -> bool:
    return all(n["value"] == 0 and n["limit"] == 0 for n in numbers.values())


@pytest.mark.parametrize("seed", [0, 1, 2_147_483_777])
def test_served_join_equals_the_reference(seed, served):
    run = served if seed == 0 else Served(make_corpus(seed))
    corpus = run.corpus
    table, want = dep.reference(corpus, N_CLICKS)
    # the corpus holds what the test is about
    first = {}
    for user, row in corpus.changelog:
        first.setdefault(user, row)
    assert any(table[k] != first[k] for k in table)                    # updates
    assert "User_3" not in table and len(table) == len(first) - 1      # a tombstone
    assert table["User_7"] == ("Region_9", "FEMALE")                   # a re-insert
    users = corpus.view_user
    assert "User_3" in users                                           # misses
    assert any(table.get(u, ("", dep.KEPT))[1] != dep.KEPT for u in users)  # filtered hits
    assert 0 < len(want) < N_CLICKS
    # the device table read back equals the reference's dict
    got_table = dict(zip(run.store["users"], zip(run.store["regions"], run.store["genders"])))
    assert got_table == table
    # exactly one sink record per click that has a result, none for the others
    got = [dep.parse_record(r) for r in run.records]
    assert sorted(got) == sorted(want.items())
    numbers = run.compared()
    assert set(numbers) == {"sink_rows_wrong", "sink_rows_extra", "sink_events_missing",
                            "table_entries_diff", "table_values_wrong"}
    assert _all_zero(numbers), numbers


@pytest.mark.parametrize("control", ["lost_event", "lost_tick", "stale_table"])
def test_control_reference_is_not_equal(control, served):
    records = dep.control_reference(served.corpus, N_CLICKS, control, seed=5)
    assert sorted(records) != sorted(served.records)
    numbers = dep.compare(served.corpus, N_CLICKS, records, None, None)
    assert not _all_zero(numbers), numbers
    # the program's own records in the same comparison are sound
    assert _all_zero(dep.compare(served.corpus, N_CLICKS, served.records, None, None))


def test_a_wrong_table_is_counted(served):
    store = dict(served.store, regions=list(served.store["regions"]))
    store["regions"][3] = "Region_999"
    numbers = dep.compare(served.corpus, N_CLICKS, served.records, store, [])
    assert numbers["table_values_wrong"]["value"] == 1
    short = {k: v - 1 if k == "live_entries" else v[1:] for k, v in served.store.items()}
    numbers = dep.compare(served.corpus, N_CLICKS, served.records, short, [])
    assert numbers["table_entries_diff"]["value"] == 1
    assert numbers["table_values_wrong"]["value"] == 1
    twice = list(served.records) + [served.records[0]]
    assert dep.compare(served.corpus, N_CLICKS, twice, None, None)[
        "sink_rows_extra"]["value"] == 1


def test_join_table_grows_during_the_load_and_answers_stay_equal():
    # a step must find room for its whole batch: 512 slots for 256 rows,
    # then 1,500 users double the table three times
    run = Served(many_users_corpus(1500), table_slots=512)
    assert run.device.table_store_capacity == 4096
    upsert = run.stages["table.upsert"]
    assert upsert["grows"] == 3
    assert run.stages["table.grow"]["n"] == upsert["grows"]
    assert run.stages["table.grow"]["total_ms"] > 0
    assert _all_zero(run.compared()), run.compared()


def test_join_path_spans_and_counters(served):
    # every changelog record reaches the device but a tombstone of a key
    # that is not live, which the table source drops at decode
    rows, live = 0, set()
    for k, row in served.corpus.changelog:
        rows += row is not None or k in live
        (live.discard if row is None else live.add)(k)
    upsert = served.stages_after_load["table.upsert"]
    assert upsert["rows"] == rows and upsert["steps"] == -(-rows // CAPACITY)
    assert upsert["probe_rounds"] >= upsert["steps"]
    assert upsert["probe_lane_rounds"] >= upsert["probe_rounds"]
    assert upsert.get("grows", 0) == 0 and "total_ms" not in upsert  # counters only
    # the table load's ticks hold no stream step yet
    assert "find_rounds" not in served.stages_after_load.get("device.step", {})
    step = served.stages["device.step"]
    steps = -(-N_CLICKS // CAPACITY)
    assert step["sampled"] == steps and step["find_rounds"] >= steps
    assert step["join_rows"] == N_CLICKS
    table, _want = dep.reference(served.corpus, N_CLICKS)
    assert step["join_matched"] == sum(u in table for u in served.corpus.view_user)
    # process_table's device.execute has the step's children, and they
    # account for it: what is left is a few scalar conversions
    load_tick = next(t for t in served.ticks if "table.upsert" in t["stages"])
    spans = load_tick["spans"]
    execute = next(s for s in spans if s["name"] in ("device.execute", "device.compile"))
    inside = [s["name"] for s in spans if s["depth"] == execute["depth"] + 1
              and execute["t0Ms"] <= s["t0Ms"] <= execute["t0Ms"] + execute["durMs"]]
    assert inside[:3] == ["batch.assemble", "step.dispatch", "step.wait"]
    hit = served.stages["device.execute"]
    assert hit["self_ms"] < 0.2 * hit["total_ms"]
    # the Python ingest tier is attributed: decode per record, assembly per batch
    assert served.stages["deserialize"]["n"] >= rows + N_CLICKS
    assert served.stages["batch.assemble"]["total_ms"] > 0
    assert served.stages["step.dispatch"]["h2d_bytes"] > 0
