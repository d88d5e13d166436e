"""``nexmark_q11_sessions`` (NEXmark q11: SESSION 10 s per bidder over the bid
stream, event time from the record's ``DATETIME``) against the benchmark
deployment's plain reference (``benchmark/deployments/nexmark_sessions.py``),
on the CPU at the configuration's ``rehearse`` engine properties, through
``KsqlServer``'s engine and ``poll_once``: the device and the oracle leave
the reference's sessions, the controls read not correct, a held-back bid
bridges two stored sessions, the hot bidder moves between batches, a key
with more sessions than the step gathers grows them and re-runs the batch
to the same sink, the step's counters equal what the reference counts, the
merge over the live prefix of its items equals the merge over all of them,
and the row sort in runs equals one sort.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.ops import session_merge
from ksql_tpu.runtime.topics import Record
from ksql_tpu.server.rest import KsqlServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


with open(os.path.join(ROOT, "benchmark/configs/nexmark_q11_sessions.json")) as _f:
    CONFIG = json.load(_f)
dep = _load("benchmark/deployments/nexmark_sessions.py", "bench_deployment_nexmark_sessions")
SIZES = {**CONFIG["sizes"], **CONFIG["rehearse"]["sizes"]}
PROPS = {**CONFIG["engine_props"], **CONFIG["rehearse"]["engine_props"]}
CAPACITY = int(PROPS["ksql.batch.capacity"])
N_EVENTS = 12 * CAPACITY
PULLS = 9
GAP, T0 = dep.GAP_MS, dep.TS0
ORACLE = {"ksql.runtime.backend": "oracle"}


def _post(url: str, path: str, body):
    req = urllib.request.Request(url + path, data=json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read().decode())


def _bid(bidder: int, t: int) -> str:
    return ('{"AUCTION":1000,"BIDDER":%d,"PRICE":100,"CHANNEL":"Google","URL":"u",'
            '"DATETIME":%d,"EXTRA":"x"}' % (bidder, t))


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
        self.topic = self.engine.broker.topic(dep.SOURCE_TOPIC)
        self.sink = self.engine.broker.topic(self.handle.plan.physical_plan.topic)

    def feed(self, payloads, ts=None) -> None:
        """Each ``CAPACITY`` payloads one batch; a record's own timestamp
        is 0 unless given (the query reads DATETIME)."""
        ts = ts if ts is not None else [0] * len(payloads)
        with self.srv.engine_lock:
            for lo in range(0, len(payloads), CAPACITY):
                for value, t in zip(payloads[lo:lo + CAPACITY], ts[lo:lo + CAPACITY]):
                    self.topic.produce(Record(key=None, value=value, timestamp=t))
                while (self.engine.poll_once(max_records=CAPACITY)
                       or getattr(self.ex, "pending_records", int)()):
                    pass
        assert self.handle.state == "RUNNING", list(self.engine.processing_log)

    def records(self):
        return [(r.key, r.window, r.value) for r in self.sink.all_records()]

    def stages(self):
        return self.engine.trace_recorder(self.qid).stage_stats()

    def stop(self) -> None:
        self.srv.stop()


def _serve(payloads, props=None, ts=None):
    """(sink records, stage stats, session slots) of one served run."""
    run = Served(props)
    try:
        run.feed(payloads, ts)
        slots = getattr(getattr(run.ex, "device", None), "session_slots", None)
        return run.records(), run.stages(), slots
    finally:
        run.stop()


class CorpusRun:
    """The seed's corpus served on the device and on the oracle."""

    def __init__(self, seed: int):
        self.corpus = dep.make_corpus(seed, SIZES, N_EVENTS)
        run = Served()
        try:
            assert run.handle.backend == "device", dict(run.engine.fallback_reasons)
            assert (run.ex._native_fields is not None) == CONFIG["native_ingest"] is True
            assert run.ex._native_fields["ts_field"] == "DATETIME"
            run.feed(self.corpus.payloads, self.corpus.ts)
            self.records, self.stages = run.records(), run.stages()
            self.store = dep.read_store(run.ex)
            self.pulls = [
                (key, dep.read_pull(_post(run.srv.url, "/query", {"ksql": sql})))
                for key, sql in dep.pull_queries(self.corpus, N_EVENTS, seed, PULLS)]
            self.log = [(w, m) for w, m in run.engine.processing_log
                        if not w.startswith(("overload.", "telemetry.", "deadline.hint"))]
        finally:
            run.stop()
        self.oracle_records, _, _ = _serve(self.corpus.payloads, ORACLE, self.corpus.ts)


@pytest.fixture(scope="module")
def served():
    return CorpusRun(4_294_967_311)


def _within(numbers) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())


# ---------------------------------------------------- the plain reference
@pytest.mark.parametrize("seed", [4_294_967_311, 3_800_000_001])
def test_served_sessions_equal_the_plain_reference_and_the_oracle(seed, served):
    run = served if seed == 4_294_967_311 else CorpusRun(seed)
    numbers = dep.compare(run.corpus, N_EVENTS, run.records, run.store, run.pulls)
    assert set(numbers) == {
        "sink_sessions_wrong", "sink_sessions_extra", "sink_sessions_missing",
        "sink_events_missing", "store_sessions_diff", "pulls_wrong"}
    assert all(n["value"] == 0 and n["limit"] == 0 for n in numbers.values()), numbers
    assert dep.fold_sink(run.oracle_records) == dep.fold_sink(run.records)
    assert run.log == []
    want = dep.reference(run.corpus, N_EVENTS)
    assert sum(want.values()) == N_EVENTS
    assert run.store["live_sessions"] == len(want) == len(run.store["sessions"])
    # the bidders with most bids answered with their sessions
    assert any(rows for _b, rows in run.pulls) and not run.pulls[-1][1]


@pytest.mark.parametrize("kind", ["lost_event", "lost_tick", "unbridged", "lost_tombstone"])
def test_a_broken_guarantee_reads_not_correct(kind, served):
    records = dep.control_reference(served.corpus, N_EVENTS, kind, 4_294_967_311)
    numbers = dep.compare(served.corpus, N_EVENTS, records, None, None)
    assert not _within(numbers)
    assert dep.compare(served.corpus, N_EVENTS, dep._records(
        dep.reference(served.corpus, N_EVENTS)), None, None) == {
        k: {"value": 0, "limit": 0} for k in numbers}


def test_the_corpus_is_nexmarks_bid_stream():
    corpus = dep.make_corpus(7, SIZES, 120_000)
    other = dep.make_corpus(8, SIZES, 120_000)
    block = int(SIZES["seed_block_events"])
    # one fixed draw: another seed orders each block otherwise
    for lo in (0, 5 * block):
        assert sorted(corpus.payloads[lo:lo + block]) == sorted(other.payloads[lo:lo + block])
    assert corpus.payloads[:block] != other.payloads[:block]
    rows = [json.loads(p) for p in corpus.payloads]
    assert [r["BIDDER"] for r in rows] == corpus.bidder.tolist()
    assert [r["DATETIME"] for r in rows] == corpus.datetime.tolist()
    # a tenth held back (fewer in the stream's first 3 s, which nothing
    # before it is held into), each arriving within 3 s of its event time
    assert 0.08 < corpus.held_back.mean() < 0.11
    arrival = np.asarray(corpus.ts)
    assert ((arrival - corpus.datetime >= 0) & (arrival - corpus.datetime < 3000)).all()
    assert (arrival[~corpus.held_back] == corpus.datetime[~corpus.held_back]).all()
    # 100 us of event time an event, 46 bids in 50 events: 120,000 bids
    # span ~13.0 s, and a little more where the bids held back past the
    # last arrival leave room for later ones
    assert 12_900 < corpus.datetime.max() - corpus.datetime.min() < 13_400
    # extra pads Beam's size model: 54-81 characters
    assert all(54 <= len(r["EXTRA"]) <= 81 for r in rows)
    # a longer corpus begins with a shorter one (the same seed)
    assert dep.make_corpus(7, SIZES, 40_000).payloads[:20_000] == corpus.payloads[:20_000]


def test_the_hot_bidder_holds_three_bids_in_four_and_moves_every_5000_events():
    corpus = dep.make_corpus(7, SIZES, 30_000)
    by_time = np.argsort(corpus.datetime, kind="stable")
    bidder, t = corpus.bidder[by_time], corpus.datetime[by_time]
    block = (t - dep.TS0) // 500  # 5,000 events of 100 us
    hot = []
    for b in np.unique(block)[1:-1]:
        ids, counts = np.unique(bidder[block == b], return_counts=True)
        share = counts.max() / counts.sum()
        assert 0.7 < share < 0.8
        hot.append(int(ids[counts.argmax()]))
    assert len(set(hot)) == len(hot) and np.all(np.diff(hot) == 100)


def test_the_hot_bidder_moves_between_batches_on_the_device():
    """Two batches whose hot bidders differ, with held-back bids of the
    first in the second: one session each, the first extended by its late
    bids (a tombstone and a row), as the oracle and the reference leave
    them."""
    first = [_bid(5001, T0 + i) for i in range(CAPACITY - 6)] + [
        _bid(6000 + i, T0 + 3 * i) for i in range(6)]
    second = [_bid(5101, T0 + 500 + i) for i in range(CAPACITY - 4)] + [
        _bid(5001, T0 - 2000 - i) for i in range(4)]
    records, stages, _ = _serve(first + second)
    oracle, _, _ = _serve(first + second, ORACLE)
    assert dep.fold_sink(records) == dep.fold_sink(oracle)
    table = dep.fold_sink(records)
    assert table[(5001, T0 - 2003, T0 + CAPACITY - 7)]["BID_COUNT"] == CAPACITY - 2
    assert table[(5101, T0 + 500, T0 + 500 + CAPACITY - 5)]["BID_COUNT"] == CAPACITY - 4
    retracted = [(k, w) for k, w, v in records if v is None]
    assert retracted == [(5001, (T0, T0 + CAPACITY - 7))]
    assert stages["emit.decode"]["tombstones"] == 1


def test_a_held_back_bid_bridges_two_stored_sessions():
    """Bids 15 s apart are two sessions; a held-back bid between them
    merges them: each stored session is retracted (one tombstone each) and
    one merged row follows, in the oracle's order."""
    first = [_bid(42, T0), _bid(42, T0 + 15_000)]
    records, stages, _ = _serve(first + [_bid(42, T0 + 7_500)], {"ksql.batch.capacity": 2})
    oracle, _, _ = _serve(first + [_bid(42, T0 + 7_500)],
                          {**ORACLE, "ksql.batch.capacity": 2})
    bridged = records[-3:]
    assert [(k, w, v is None) for k, w, v in bridged] == [
        (42, (T0, T0), True), (42, (T0 + 15_000, T0 + 15_000), True),
        (42, (T0, T0 + 15_000), False)]
    assert json.loads(bridged[-1][2]) == {
        "BID_COUNT": 3, "STARTTIME": T0, "ENDTIME": T0 + 15_000}
    assert dep.fold_sink(records) == dep.fold_sink(oracle) == {
        (42, T0, T0 + 15_000): {"BID_COUNT": 3, "STARTTIME": T0, "ENDTIME": T0 + 15_000}}
    assert stages["emit.decode"]["tombstones"] == 2


def test_more_sessions_than_slots_grow_and_rerun_the_batch_to_the_same_sink():
    """Six sessions of one bidder in one batch, then a bid that extends
    the last: the step keeps 4 sessions a key, so the first batch finds a
    key with more, grows to 8 and runs the batch again under a
    ``session.grow`` span that books the recompile; the second needs no
    more."""
    first = [_bid(9, T0 + 11_000 * i) for i in range(6)] + [
        _bid(100 + i, T0) for i in range(CAPACITY - 6)]
    second = [_bid(9, T0 + 55_001)] + [_bid(100 + i, T0 + 1) for i in range(CAPACITY - 1)]
    records, stages, slots = _serve(first + second)
    oracle, _, _ = _serve(first + second, ORACLE)
    assert slots == 8
    assert stages["session.grow"]["n"] == 1
    assert stages["device.compile"]["jit_miss"] >= 1
    assert dep.fold_sink(records) == dep.fold_sink(oracle)
    want = {(9, T0 + 11_000 * i, T0 + 11_000 * i): 1 for i in range(5)}
    want[(9, T0 + 55_000, T0 + 55_001)] = 2
    got = {k: v["BID_COUNT"] for k, v in dep.fold_sink(records).items() if k[0] == 9}
    assert got == want


def _items_per_step(corpus) -> list:
    """Each step's live items as the reference counts them: its rows plus
    the sessions of each bidder of the batch before it."""
    seen: dict = {}
    per_step = []
    for lo in range(0, N_EVENTS, CAPACITY):
        bidders = set(corpus.bidder[lo:lo + CAPACITY].tolist())
        per_step.append(
            min(CAPACITY, N_EVENTS - lo) + sum(seen.get(b, 0) for b in bidders))
        batch_sessions = dep.reference(corpus, min(lo + CAPACITY, N_EVENTS))
        seen = {}
        for b, _s, _e in batch_sessions:
            seen[b] = seen.get(b, 0) + 1
    return per_step


def _check_step_counters(stages, records, per_step, work_of) -> None:
    step = stages["device.step"]
    steps = N_EVENTS // CAPACITY
    assert step["sampled"] == steps
    assert step["session_items"] == sum(per_step)
    assert step["session_lanes"] == steps * CAPACITY * 5
    assert step["session_work_lanes"] == sum(work_of(items) for items in per_step)
    assert step["probe_rounds"] > 0 and step["probe_lane_rounds"] > 0
    assert stages["emit.decode"]["tombstones"] == sum(
        1 for _k, _w, v in records if v is None) > 0
    assert "session.grow" not in stages


def test_step_counters_equal_what_the_reference_counts(served):
    """``device.step`` ``session_items`` = a step's rows plus the stored
    sessions it gathered (the reference's sessions of each bidder of the
    batch, before it), ``session_lanes`` its static width,
    ``session_work_lanes`` the width its merge's per-item work ran at (the
    static width here: ``WORK_LANES`` is wider than these steps), and
    ``emit.decode`` ``tombstones`` the retractions on the sink."""
    _check_step_counters(served.stages, served.records,
                         _items_per_step(served.corpus), lambda items: CAPACITY * 5)


def test_step_counters_book_the_narrow_width_where_the_items_fit(served, monkeypatch):
    """At a ``WORK_LANES`` some of the corpus's steps fit and some do not:
    ``session_work_lanes`` books ``WORK_LANES`` for each step whose live
    items (as the reference counts them) fit it and the static width for
    the others, and the sink is the one the whole width leaves."""
    monkeypatch.setattr(session_merge, "WORK_LANES", 284)
    run = Served()
    try:
        run.feed(served.corpus.payloads, served.corpus.ts)
        stages, records = run.stages(), run.records()
    finally:
        run.stop()
    assert records == served.records
    _check_step_counters(stages, records, _items_per_step(served.corpus),
                         lambda items: 284 if items <= 284 else CAPACITY * 5)


def _feeds_of(case: str):
    """The feeds of a case of ``test_the_narrow_merge_equals_the_whole_one``,
    each produced and drained before the next."""
    if case == "fits":  # 40 bids, then 35 that extend, join or trail them
        return [[_bid(7000 + i % 20, T0 + 100 * i) for i in range(40)],
                [_bid(7000 + i % 20, T0 + 4_000 + 100 * i) for i in range(30)]
                + [_bid(7000 + i, T0 - 1_000 - i) for i in range(5)]]
    if case == "overflows":  # full batches of distinct bidders
        return [[_bid(8000 + i, T0 + i) for i in range(CAPACITY)],
                [_bid(8000 + i, T0 + 500 + i) for i in range(CAPACITY)]]
    if case == "bridge":  # a held-back bid between two stored sessions
        return [[_bid(42, T0), _bid(42, T0 + 15_000)], [_bid(42, T0 + 7_500)]]
    if case == "expired":  # the stored sessions left the 24 h grace behind
        later = T0 + 25 * 3_600_000
        return [[_bid(9000 + i, T0 + 10 * i) for i in range(30)],
                [_bid(9000 + i, later + 10 * i) for i in range(30)]
                + [_bid(9000, T0 + 5)]]
    if case == "grow":  # six sessions of one bidder: the step grows to 8
        first = [_bid(9, T0 + 11_000 * i) for i in range(6)] + [
            _bid(100 + i, T0) for i in range(CAPACITY - 6)]
        return [first, [_bid(9, T0 + 55_001)] + [
            _bid(100 + i, T0 + 1) for i in range(CAPACITY - 1)]]
    corpus = dep.make_corpus(4_294_967_311, SIZES, N_EVENTS)
    return [corpus.payloads]


def _store_of(executor) -> dict:
    """The device store's arrays without the dump slot (the last: each
    scatter sends a lane that writes nothing there), and its scalars."""
    dev = executor.device
    cap = getattr(dev, "c", dev).store_capacity
    return {name: (a[..., :-1] if a.ndim and a.shape[-1] == cap + 1 else a)
            for name, a in ((k, np.asarray(v)) for k, v in dev.state.items())}


def _serve_at(work: int, feeds, props, monkeypatch):
    """(sink records, store, ``device.step`` stage) of one served run whose
    session step is traced with ``WORK_LANES`` = ``work``."""
    monkeypatch.setattr(session_merge, "WORK_LANES", work)
    run = Served(props)
    try:
        for payloads in feeds:
            run.feed(payloads)
        return run.records(), _store_of(run.ex), run.stages()["device.step"]
    finally:
        run.stop()


MESH = {"ksql.runtime.backend": "distributed", "ksql.device.shards": 4}


@pytest.mark.parametrize("case, work, backend, narrow", [
    ("fits", 64, "device", "all"),
    ("overflows", 32, "device", "none"),
    ("bridge", 4, "device", "all"),
    ("expired", 64, "device", "all"),
    ("grow", CAPACITY, "device", "some"),
    ("corpus", 284, "device", "some"),
    ("fits", 64, "mesh", "all"),
    ("overflows", 32, "mesh", "none"),
    ("bridge", 4, "mesh", "all"),
    ("expired", 64, "mesh", "all"),
    ("grow", CAPACITY, "mesh", "all"),
    ("corpus", 72, "mesh", "some"),
])
def test_the_narrow_merge_equals_the_whole_one(case, work, backend, narrow, monkeypatch):
    """The session step's merge, store rewrite and emission over the first
    ``WORK_LANES`` merged positions when the live items fit them, and over
    all ``n x (S + 1)`` when they do not, leave the sink records (in order,
    tombstones too) and the store that the whole width alone leaves; on
    one device and on the four-shard mesh (each shard takes its own
    branch).  ``session_work_lanes`` books ``WORK_LANES`` for a step that
    fit and the whole width for one that did not (the mesh: summed over
    its shards)."""
    feeds = _feeds_of(case)
    props = MESH if backend == "mesh" else {}
    whole = _serve_at(1 << 30, feeds, props, monkeypatch)
    records, store, step = _serve_at(work, feeds, props, monkeypatch)
    assert records == whole[0] and records
    assert store.keys() == whole[1].keys()
    for name, a in store.items():
        assert np.array_equal(a, whole[1][name]), name
    assert whole[2]["session_work_lanes"] == whole[2]["session_lanes"]
    assert step["session_items"] == whole[2]["session_items"]
    assert step["session_lanes"] == whole[2]["session_lanes"]
    units = step["sampled"] * (4 if backend == "mesh" else 1)
    booked = {
        "all": step["session_work_lanes"] == work * units,
        "none": step["session_work_lanes"] == step["session_lanes"],
        "some": work * units < step["session_work_lanes"] < step["session_lanes"],
    }
    assert booked[narrow], (step, work * units)


@pytest.mark.parametrize("n, run", [(64, 8), (100, 8), (256, 16), (37, 4), (8, 8)])
def test_the_row_sort_in_runs_equals_one_sort(n, run, monkeypatch):
    """Past ``SORT_RUN`` rows the step sorts runs and merges them: the
    same order as one lexicographic sort, dead rows last, ties by row."""
    monkeypatch.setattr(session_merge, "SORT_RUN", run)
    rng = np.random.default_rng(n)
    for _ in range(10):
        k = rng.integers(0, 5, n).astype(np.int64) << 40
        t = rng.integers(0, 6, n).astype(np.int64)
        a = rng.random(n) < 0.8
        got = session_merge.sort_rows(jnp.asarray(k), jnp.asarray(t), jnp.asarray(a))
        big = np.iinfo(np.int64).max
        want = np.lexsort((np.arange(n), np.where(a, t, big), np.where(a, k, big)))
        assert np.array_equal(np.asarray(got.rows), want)
