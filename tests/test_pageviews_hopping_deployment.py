"""``pageviews_hopping_stats`` (the rolling-hour statistics view: HOPPING 1 h
/ 15 min, COUNT/SUM/AVG/MIN/MAX over a DOUBLE, GRACE PERIOD 15 MINUTES)
against the benchmark deployment's plain reference
(``benchmark/deployments/pageviews_hopping.py``), on the CPU at the
configuration's ``rehearse`` engine properties and sizes, through
``KsqlServer``'s engine and ``poll_once``.  Also that the sliced path, the
k-fold expansion path and the oracle leave the same table, that keys expire
and come back from identity, that the ring is 7 from the first batch, and
the spans and counters the cell's metrics read.
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
from ksql_tpu.runtime.lowering import CompiledDeviceQuery
from ksql_tpu.runtime.topics import Record
from ksql_tpu.server.rest import KsqlServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def _json(path: str):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


dep = _load("benchmark/deployments/pageviews_hopping.py", "bench_deployment_pageviews_hopping")
CONFIG = _json("benchmark/configs/pageviews_hopping_stats.json")
SIZES = {**CONFIG["sizes"], **CONFIG["rehearse"]["sizes"]}
PROPS = {**CONFIG["engine_props"], **CONFIG["rehearse"]["engine_props"]}
CAPACITY = int(PROPS["ksql.batch.capacity"])
#: 80 batches: past the store's 64-batch retention cadence, 5.1 hours of
#: event time at the rehearsal's 8,000 events an hour (retention: 75 min)
N_EVENTS = 80 * CAPACITY
#: the run below is fed as one fill of 80 batches: the reference then knows
#: that the store's last certain retention pass ended batch 64
RUN_SIZES = {**SIZES, "fill_events": N_EVENTS, "warm_ticks": 0}
PULLS = 9
MIN15 = dep.ADVANCE_MS


def _post(url: str, path: str, body):
    req = urllib.request.Request(url + path, data=json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read().decode())


def _view(url_index: int, latency: float, ts: int):
    return ('{"URL":"%s","USER_ID":7,"LATENCY":%r}' % (dep.url_of(url_index), latency), ts)


class Served:
    """The configuration's statements behind a ``KsqlServer``, fed as the
    harness's fill feeds them: a batch produced, then ``poll_once`` until
    quiet under the server's engine lock."""

    def __init__(self, props=None):
        self.engine = KsqlEngine(KsqlConfig({**PROPS, **(props or {})}))
        self.srv = KsqlServer(engine=self.engine, port=0)
        self.srv.start()
        try:
            out = _post(self.srv.url, "/ksql", {"ksql": " ".join(CONFIG["statements"])})
        except Exception:
            self.srv.stop()
            raise
        self.qid = [e["commandStatus"]["queryId"] for e in out
                    if e.get("commandStatus", {}).get("queryId")][-1]
        self.handle = self.engine.queries[self.qid]
        self.ex = self.handle.executor
        self.device = getattr(self.ex, "device", None)
        self.capacity = int(self.device.capacity) if self.device is not None else CAPACITY
        self.topic = self.engine.broker.topic(dep.SOURCE_TOPIC)
        self.sink = self.engine.broker.topic(self.handle.plan.physical_plan.topic)

    def feed(self, rows) -> None:
        with self.srv.engine_lock:
            for lo in range(0, len(rows), self.capacity):
                for value, ts in rows[lo:lo + self.capacity]:
                    self.topic.produce(Record(key=None, value=value, timestamp=ts))
                while (self.engine.poll_once(max_records=self.capacity)
                       or getattr(self.ex, "pending_records", int)()):
                    pass
        assert self.handle.state == "RUNNING", list(self.engine.processing_log)

    def records(self):
        return [(r.key, r.window, r.value) for r in self.sink.all_records()]

    def stages(self):
        return self.engine.trace_recorder(self.qid).stage_stats()

    def pull(self, url_index: int):
        sql = ("SELECT URL, WINDOWSTART, CNT, S, A, MN, MX FROM PV_STATS "
               f"WHERE URL = '{dep.url_of(url_index)}';")
        return dep.read_pull(_post(self.srv.url, "/query", {"ksql": sql}))

    def stop(self) -> None:
        self.srv.stop()


class CorpusRun:
    """One served run over the seed's corpus, kept as readings."""

    def __init__(self, seed: int):
        self.seed = seed
        self.corpus = dep.make_corpus(seed, RUN_SIZES, N_EVENTS)
        run = Served()
        try:
            assert run.handle.backend == "device", dict(run.engine.fallback_reasons)
            assert (run.ex._native_fields is not None) == CONFIG["native_ingest"]
            dev = run.device
            self.built = (dev.sliced, dev.slice_ring, dev.hop_k, dev.retention_ms,
                          dev.store_capacity)
            resized = []
            real = dev._resize_ring
            dev._resize_ring = lambda *a: (resized.append(a), real(*a))[1]
            rows = list(zip(self.corpus.payloads, self.corpus.ts))
            half = 70 * CAPACITY  # past the first retention pass (batch 64)
            run.feed(rows[:half])
            self.stages_half = run.stages()
            run.feed(rows[half:])
            self.stages = run.stages()
            self.ring_after, self.resized = dev.slice_ring, resized
            self.records = run.records()
            self.store = dep.read_store(run.ex)
            self.pulls = [
                (key, dep.read_pull(_post(run.srv.url, "/query", {"ksql": sql})))
                for key, sql in dep.pull_queries(self.corpus, N_EVENTS, seed, PULLS)]
        finally:
            run.stop()

    def compared(self):
        return dep.compare(self.corpus, N_EVENTS, self.records, self.store, self.pulls)


@pytest.fixture(scope="module")
def served():
    return CorpusRun(0)


def _within(numbers) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())


# ---------------------------------------------- (a) the plain reference
@pytest.mark.parametrize("seed", [0, 4_294_967_311])
def test_served_view_equals_the_plain_reference(seed, served):
    run = served if seed == 0 else CorpusRun(seed)
    assert run.built == (True, 7, 4, dep.RETENTION_MS, int(PROPS["ksql.state.slots"]))
    numbers = run.compared()
    assert set(numbers) == {
        "sink_rows_wrong", "sink_rows_extra", "sink_events_missing", "sink_counts_backwards",
        "sink_avg_rel_err_max", "store_keys_missing", "store_keys_lingering", "pulls_wrong"}
    assert all(n["value"] == 0 for n in numbers.values()), numbers
    assert numbers["sink_avg_rel_err_max"]["limit"] == 1e-12
    assert all(n["limit"] == 0 for k, n in numbers.items() if k != "sink_avg_rel_err_max")
    want = dep.reference(run.corpus, N_EVENTS)
    # every event is in four windows, the first of them before the first event
    assert sum(c[0] for c in want.values()) == 4 * N_EVENTS
    assert min(ws for _u, ws in want) == dep.TS0 - 3 * MIN15
    # the store holds the URLs of the last 75 minutes, not the run's, and
    # a store that kept them all reads as lingering: the bound is the pass
    # that ended batch 64, not the run's first event
    ever = len(np.unique(run.corpus.url_idx[:N_EVENTS]))
    assert 0 < run.store["live_keys"] < 0.9 * ever
    assert run.corpus.setup_pass_event == 64 * CAPACITY - 1
    kept_all = dep.compare(run.corpus, N_EVENTS, run.records, {"live_keys": ever}, None)
    assert kept_all["store_keys_lingering"]["value"] > 0
    assert kept_all["store_keys_missing"]["value"] == 0
    # the pulls returned retained windows (some keys have none left)
    assert any(rows for _u, rows in run.pulls) and not all(rows for _u, rows in run.pulls)


def test_the_url_sequence_is_pageviews_own():
    pv = _load("benchmark/deployments/pageviews.py", "bench_deployment_pageviews_for_hopping")
    ours, theirs = (m.make_corpus(11, SIZES, 5000) for m in (dep, pv))
    assert (ours.url_idx == theirs.url_idx).all() and ours.ts == theirs.ts
    # one fixed draw of views, latencies carried with them: another seed
    # is the same multiset a block, in another order
    other = dep.make_corpus(12, SIZES, 5000)
    block = int(SIZES["seed_block_events"])
    pairs = lambda c, lo: sorted(zip(c.url_idx[lo:lo + block].tolist(),  # noqa: E731
                                     c.latency[lo:lo + block].tolist()))
    assert pairs(ours, 0) == pairs(other, 0) and pairs(ours, block) == pairs(other, block)
    assert (ours.url_idx != other.url_idx).any()
    assert set(np.unique(ours.latency * 4 % 1)) == {0.0} and 0 <= ours.latency.min()
    assert ours.latency.max() < 1000 and json.loads(ours.payloads[0])["LATENCY"] == ours.latency[0]


# ------------------------- (b) sliced, k-fold expansion and oracle agree
def test_sliced_expansion_and_oracle_leave_the_same_table():
    sizes = {**SIZES, "urls": 300}
    n = 6 * CAPACITY
    corpus = dep.make_corpus(3, sizes, n)
    rows = list(zip(corpus.payloads, corpus.ts))
    tables = {}
    for name, props in (("sliced", {}), ("expansion", {"ksql.slicing.enable": "false"}),
                        ("oracle", {"ksql.runtime.backend": "oracle"})):
        run = Served(props)
        try:
            if name != "oracle":
                assert run.device.sliced == (name == "sliced")
                assert run.device.hop_k == 4
            else:
                assert run.handle.backend == "oracle"
            run.feed(rows)
            records = run.records()
        finally:
            run.stop()
        tables[name], backwards = dep.fold_sink(records)
        assert backwards == 0
        assert _within(dep.compare(corpus, n, records, None, None)), name
    sliced = tables["sliced"]
    for other in ("expansion", "oracle"):
        assert set(tables[other]) == set(sliced)
        for key, row in sliced.items():
            theirs = tables[other][key]
            assert [theirs[c] for c in ("CNT", "S", "MN", "MX")] == [
                row[c] for c in ("CNT", "S", "MN", "MX")]
            assert theirs["A"] == pytest.approx(row["A"], rel=1e-12, abs=0)


# ----------------------------------------------------- (c) the controls
@pytest.mark.parametrize("kind", ["lost_event", "lost_tick", "lost_window", "stale_stat"])
def test_a_broken_guarantee_reads_not_correct(kind, served):
    broken = dep.control_reference(served.corpus, N_EVENTS, kind, served.seed)
    numbers = dep.compare(served.corpus, N_EVENTS, broken, None, None)
    assert not _within(numbers)
    missing = {"lost_event": 1, "lost_tick": 4096, "lost_window": 1, "stale_stat": 0}[kind]
    assert numbers["sink_events_missing"]["value"] == missing
    assert numbers["sink_rows_wrong"]["value"] >= 1
    if kind in ("lost_window", "stale_stat"):
        assert numbers["sink_rows_wrong"]["value"] == 1
    assert _within(served.compared())


# ------------------------------------ (d) expiry, graves, return, lateness
def test_keys_expire_leave_graves_and_return_from_identity():
    """A store of 4,096 slots at 256 lanes: 1,500 URLs in the first quarter
    hour, two hours of 200 others, then 800 new ones.  The first 1,500
    expire and are evicted by the cadence pass (batch 64); the new URLs
    then take the load to 0.75 less the headroom, where the off-cadence
    pass and the in-place compaction run (and, the live keys being few,
    no doubling).  A URL that returns starts from identity, and a row past
    every window's grace leaves nothing."""
    run = Served({"ksql.batch.capacity": 256, "ksql.state.slots": 4096})
    try:
        ts0 = dep.TS0
        first = [_view(u, 1.25 + u % 4, ts0 + u) for u in range(1500)]
        run.feed(first)
        assert dep.read_store(run.ex) == {"live_keys": 1500}
        # 32 events a batch, 4.5 s of event time apart
        later = [_view(2000 + i % 200 if i < 1600 else 3000 + i % 800, 2.0, ts0 + MIN15 + i * 4500)
                 for i in range(2400)]
        for lo in range(0, len(later), 32):
            run.feed(later[lo:lo + 32])
        stages = run.stages()
        assert stages["store.evict"]["n"] >= 2 and stages["store.evict"]["total_ms"] > 0
        assert stages["store.evict"].get("off_cadence", 0) >= 1
        assert stages["store.compact"]["n"] == stages["store.evict"]["off_cadence"]
        assert stages["device.step"]["graves"] > 0
        live = dep.read_store(run.ex)["live_keys"]
        assert live <= 1000 and run.device.store_capacity == 4096
        assert run.pull(5) == {}
        # URL 5 comes back, three hours after its only event
        back = ts0 + MIN15 + 3 * 3_600_000 + 1000
        n_before = len(run.records())
        run.feed([_view(5, 7.5, back)])
        mine = [(w, json.loads(v)) for k, w, v in run.records()[n_before:]]
        assert len(mine) == 4 and all(
            row == {"CNT": 1, "S": 7.5, "A": 7.5, "MN": 7.5, "MX": 7.5} for _w, row in mine)
        assert sorted(w[0] for w, _row in mine) == [
            back - back % MIN15 - j * MIN15 for j in (3, 2, 1, 0)]
        assert set(run.pull(5)) == {w[0] for w, _row in mine}
        # a row two hours late: every window it belongs to is past its grace
        n_before = len(run.records())
        run.feed([_view(5, 100.0, back - 2 * 3_600_000)])
        assert len(run.records()) == n_before
        assert all(row["CNT"] == 1 and row["MX"] == 7.5 for row in run.pull(5).values())
        # a row 40 minutes behind the newest slice: of its four windows the
        # two that end inside the grace take it, the two past it do not
        newest = back - back % MIN15
        run.feed([_view(5, 0.25, newest - 40 * 60_000)])
        late = {w[0]: json.loads(v) for k, w, v in run.records()[n_before:]}
        assert late == {
            newest - 4 * MIN15: {"CNT": 1, "S": 0.25, "A": 0.25, "MN": 0.25, "MX": 0.25},
            newest - 3 * MIN15: {"CNT": 2, "S": 7.75, "A": 3.875, "MN": 0.25, "MX": 7.5}}
    finally:
        run.stop()


# --------------------------------------- (e) the ring, and no recompile
def test_the_ring_is_seven_at_first_eight_from_minute_ninety_and_then_nothing_recompiles(served):
    """``retention // slice + 2`` = 7 cells hold a batch that stays inside
    one slice; the first batch that straddles a slice boundary once
    retention has filled (event-time minute 90, inside any fill) needs 8:
    one ``_resize_ring`` -- a host rebuild of the store and a recompile --
    and none after it (``ROADMAP.md``, known gaps: found, not fixed)."""
    assert served.built[:3] == (True, 7, 4)
    assert served.ring_after == 8 and served.resized == [(MIN15, 8)]
    half, end = served.stages_half["device.compile"], served.stages["device.compile"]
    # the step's first call and the retention pass's (batch 64) are the
    # misses of the first 70 batches (the resize's recompile is not one:
    # the cache is counted around the call that swaps the jit objects);
    # the ten batches after them, slice boundaries among them, add none
    assert half["jit_miss"] == end["jit_miss"] == 2
    assert served.stages["store.evict"]["n"] == N_EVENTS // CAPACITY // 64


def _plan_of(engine, statement: str):
    results = engine.execute_sql(statement)
    return engine.queries[next(r.query_id for r in results if r.query_id)].plan


#: (query, aggregate state bytes a slot): the configuration's own at ring 7;
#: a hopping query without GRACE PERIOD, whose 24 h default grace makes the
#: ring 102 cells (ROADMAP, known gaps); a COLLECT_LIST at its cap of 4,096
WIDE_STORES = {
    "stated_grace": (CONFIG["statements"][1], 7 * 64),
    "default_grace": (
        "CREATE TABLE NO_GRACE AS SELECT URL, SUM(LATENCY) AS S, MIN(LATENCY) AS MN FROM "
        "PAGE_VIEWS WINDOW HOPPING (SIZE 1 HOUR, ADVANCE BY 15 MINUTES) GROUP BY URL;",
        None),
    "collect_cap": (
        "CREATE TABLE COLLECTED AS SELECT URL, COLLECT_LIST(LATENCY) AS L FROM PAGE_VIEWS "
        "GROUP BY URL;", None),
}


@pytest.mark.parametrize("device_gb, slots", [(0, 1 << 17), (0, 1 << 21), (16, 1 << 17),
                                             (16, 1 << 21)])
@pytest.mark.parametrize("query", sorted(WIDE_STORES))
def test_a_store_is_cut_to_bytes_not_to_the_default_slot_count(query, device_gb, slots,
                                                               monkeypatch):
    """``ksql.state.slots`` is kept while the store's aggregate state fits
    the construction budget -- an eighth of the device's memory where the
    device reports it, 256 MiB where it does not (the CPU) -- and halved
    until it does otherwise, whatever the slot count stated: on a 16 GB
    chip the configuration's 2^21 slots of a 7-cell ring stay (0.94 GB),
    and neither a 102-cell ring nor a COLLECT cap takes more than 2 GB at
    2^21 slots."""
    from ksql_tpu.runtime import lowering

    monkeypatch.setattr(lowering, "_device_memory_bytes", lambda: device_gb << 30)
    budget = max(256 << 20, (device_gb << 30) // 8)
    assert lowering._vec_state_budget_bytes() == budget
    engine = KsqlEngine(KsqlConfig({"ksql.functions.collect_list.limit": 4096}))
    try:
        engine.execute_sql(CONFIG["statements"][0])
        statement, row_bytes = WIDE_STORES[query]
        dev = CompiledDeviceQuery(_plan_of(engine, statement), engine.registry, capacity=64,
                                  store_capacity=slots, analyze_only=True)
    finally:
        engine.shutdown()
    comps = dev.store_layout.components
    measured = sum(np.dtype(c.dtype).itemsize * c.width for c in comps)
    assert row_bytes in (None, measured) and measured >= 448
    assert dev.store_capacity * measured <= budget
    # no more is cut than the bytes ask: the next doubling would not fit
    assert dev.store_capacity == slots or 2 * dev.store_capacity * measured > budget
    if query == "stated_grace":
        assert dev.slice_ring == 7
        assert dev.store_capacity == (slots if device_gb or slots == 1 << 17 else 1 << 19)
    if query == "default_grace":
        assert dev.slice_ring == 102
        assert (dev.store_capacity < slots) == (slots * measured > budget)


# ------------------------------------------------- (f) the new counters
def test_store_and_emit_counters_are_booked_and_consistent(served):
    stages = served.stages
    steps = N_EVENTS // CAPACITY
    step = stages["device.step"]
    # a tick of one batch flushes it: the load is checked every step
    assert step["sampled"] == steps and "slice_ring" not in step and "hop_k" not in step
    assert 0 < step["graves"] < step["occupancy"] <= step["sampled"] * int(PROPS["ksql.state.slots"])
    assert step["occupancy"] >= step["sampled"] * served.store["live_keys"] * 0.5
    decode = stages["emit.decode"]
    # a sliced step's emits are k lanes a batch lane; every emit is a record
    assert decode["lanes"] == steps * 4 * CAPACITY
    assert decode["rows"] == len(served.records) == stages["emit.dispatch"]["rows"]
    assert 2.0 < decode["rows"] / N_EVENTS <= 4.0
    evict = stages["store.evict"]
    assert evict["n"] == steps // 64 and evict["total_ms"] > 0 and "off_cadence" not in evict
    assert "store.compact" not in stages
    # what the cell's metric files divide
    for name in ("emit_lane_fill_pct.hop", "store_grave_pct.hop", "emits_per_event.hop"):
        spec = _json(f"benchmark/layer_metrics/{name}.json")
        for obs in spec["num"] + spec["den"]:
            if obs.startswith("span."):
                stage, field = obs[len("span."):].rsplit(".", 1)
                assert field in stages[stage], obs
