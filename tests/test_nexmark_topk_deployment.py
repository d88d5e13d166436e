"""``nexmark_q19_topk`` (NEXmark q19: the ten best bids of each auction,
``TOPK(PRICE, BIDDER, DATETIME, 10) ... GROUP BY AUCTION``, unwindowed)
against the benchmark deployment's plain reference
(``benchmark/deployments/nexmark_topk.py``), on the CPU at the
configuration's ``rehearse`` engine properties, through ``KsqlServer``'s
engine and ``poll_once``: the device and the oracle leave the reference's
top lists, the variadic TOPK's ties, nulls and payloads are the oracle's,
the plain TOPK / TOPKDISTINCT keep their results, a grown store keeps its
vector state, the controls read not correct, the step's counters equal what
the reference counts, and the four-shard mesh leaves what one device does.
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
from ksql_tpu.runtime.topics import Record
from ksql_tpu.server.rest import KsqlServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


with open(os.path.join(ROOT, "benchmark/configs/nexmark_q19_topk.json")) as _f:
    CONFIG = json.load(_f)
dep = _load("benchmark/deployments/nexmark_topk.py", "bench_deployment_nexmark_topk")
SIZES = {**CONFIG["sizes"], **CONFIG["rehearse"]["sizes"]}
PROPS = {**CONFIG["engine_props"], **CONFIG["rehearse"]["engine_props"]}
CAPACITY = int(PROPS["ksql.batch.capacity"])
N_EVENTS = 12 * CAPACITY
PULLS = 9
ORACLE = {"ksql.runtime.backend": "oracle"}
MESH = {"ksql.runtime.backend": "distributed", "ksql.device.shards": 4}
SEED = 4_294_967_311
BIDS_DDL = CONFIG["statements"][0]


def _post(url: str, path: str, body):
    req = urllib.request.Request(url + path, data=json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read().decode())


def _bid(auction, bidder, price, t) -> str:
    return json.dumps({"AUCTION": auction, "BIDDER": bidder, "PRICE": price,
                       "CHANNEL": "Google", "URL": "u", "DATETIME": t, "EXTRA": "x"})


class Served:
    """Statements behind a ``KsqlServer``, fed as the harness's fill feeds
    them: a batch produced, then ``poll_once`` until quiet under the
    server's engine lock."""

    def __init__(self, props=None, statements=None):
        self.engine = KsqlEngine(KsqlConfig({**PROPS, **(props or {})}))
        self.capacity = int(self.engine.config.get("ksql.batch.capacity"))
        self.srv = KsqlServer(engine=self.engine, port=0)
        self.srv.start()
        try:
            out = _post(self.srv.url, "/ksql",
                        {"ksql": " ".join(statements or CONFIG["statements"])})
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
        """Each ``capacity`` payloads one batch; a record's own timestamp is
        0 unless given (the query reads DATETIME)."""
        ts = ts if ts is not None else [0] * len(payloads)
        cap = self.capacity
        with self.srv.engine_lock:
            for lo in range(0, len(payloads), cap):
                for value, t in zip(payloads[lo:lo + cap], ts[lo:lo + cap]):
                    self.topic.produce(Record(key=None, value=value, timestamp=t))
                while (self.engine.poll_once(max_records=cap)
                       or getattr(self.ex, "pending_records", int)()):
                    pass
        assert self.handle.state == "RUNNING", list(self.engine.processing_log)

    def records(self):
        return [(r.key, r.window, r.value) for r in self.sink.all_records()]

    def stages(self):
        return self.engine.trace_recorder(self.qid).stage_stats()

    def stop(self) -> None:
        self.srv.stop()


def _serve(feeds, props=None, statements=None):
    """(sink records, stage stats, backend) of one served run; ``feeds``
    is a list of payload lists, each fed (and drained) in turn."""
    run = Served(props, statements)
    try:
        for payloads in feeds:
            run.feed(payloads)
        return run.records(), run.stages(), run.handle.backend
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
            explain = run.engine.execute_sql(f"EXPLAIN {run.qid};")[0].message
            assert "Runtime: device" in explain, explain
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
        run = Served(ORACLE)
        try:
            run.feed(self.corpus.payloads, self.corpus.ts)
            self.oracle_records = run.records()
        finally:
            run.stop()


@pytest.fixture(scope="module")
def served():
    return CorpusRun(SEED)


def _within(numbers) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())


# ---------------------------------------------------- the plain reference
@pytest.mark.parametrize("seed", [SEED, 3_800_000_001])
def test_served_top_lists_equal_the_plain_reference_and_the_oracle(seed, served):
    run = served if seed == SEED else CorpusRun(seed)
    numbers = dep.compare(run.corpus, N_EVENTS, run.records, run.store, run.pulls)
    assert set(numbers) == {
        "sink_rows_wrong", "sink_rows_extra", "sink_events_missing",
        "sink_counts_backwards", "store_entries_diff", "pulls_wrong"}
    assert all(n["value"] == 0 and n["limit"] == 0 for n in numbers.values()), numbers
    assert dep.fold_sink(run.oracle_records) == dep.fold_sink(run.records)
    assert run.log == []
    want = dep.reference(run.corpus, N_EVENTS)
    assert sum(c for c, _top in want.values()) == N_EVENTS
    assert run.store["live_auctions"] == len(want) == len(run.store["entries"])
    # the hot auctions hold full lists; the absent one answers nothing
    assert max(len(top) for _c, top in want.values()) == 10
    assert any(row and len(row[1]) == 10 for _a, row in run.pulls)
    assert run.pulls[-1][1] is None


@pytest.fixture(scope="module")
def longer():
    """60,000 bids: long enough that some auction's list holds two bids at
    one price (about one auction in 1,000 does), which ``tie_order`` swaps."""
    return dep.make_corpus(SEED, SIZES, 60_000)


@pytest.mark.parametrize("kind", ["lost_event", "lost_tick", "stale_topk", "tie_order"])
def test_a_broken_guarantee_reads_not_correct(kind, longer):
    n = len(longer.payloads)
    records = dep.control_reference(longer, n, kind, SEED)
    numbers = dep.compare(longer, n, records, None, None)
    assert not _within(numbers)
    assert dep.compare(longer, n, dep._records(dep.reference(longer, n)), None, None) == {
        k: {"value": 0, "limit": 0} for k in numbers}


def test_the_corpus_is_q11s_bid_stream_with_its_auctions():
    corpus = dep.make_corpus(7, SIZES, 60_000)
    sessions = sys.modules["bench_deployment_nexmark_sessions"].make_corpus(7, SIZES, 60_000)
    assert corpus.payloads == sessions.payloads and corpus.ts == sessions.ts
    rows = [json.loads(p) for p in corpus.payloads]
    for name, col in (("AUCTION", corpus.auction), ("BIDDER", corpus.bidder),
                      ("PRICE", corpus.price), ("DATETIME", corpus.datetime)):
        assert [r[name] for r in rows] == col.tolist(), name
    # the hot auction takes half the bids; prices run 100 .. 10^8
    ids, counts = np.unique(corpus.auction, return_counts=True)
    assert 0.3 < np.sort(counts)[::-1][:40].sum() / len(rows) < 0.7
    assert corpus.price.min() >= 100 and corpus.price.max() <= 10 ** 8
    # ties at a list's price exist (what tie_order swaps)
    assert any(len({p for p, _b, _t in top}) < len(top)
               for _c, top in dep.reference(corpus, 60_000).values())


# ------------------------------------------------- the fold, case by case
def _topk_statements(k: int, form: str = "TOPK(PRICE, BIDDER, DATETIME, %d)"):
    return [BIDS_DDL,
            "CREATE TABLE AUCTION_TOP_BIDS AS SELECT AUCTION, " + form % k
            + " AS TOP_BIDS, COUNT(*) AS BID_COUNT FROM BIDS GROUP BY AUCTION EMIT CHANGES;"]


def _corpus_of(bids, k: int):
    """A reference corpus of ``bids`` ((auction, bidder, price, datetime),
    any of bidder / price / datetime None) in arrival order."""
    col = [np.array([b[i] for b in bids], dtype=object) for i in range(4)]
    return dep.Corpus(dep.SOURCE_TOPIC, [], [], [], *col, k)


def _cases():
    """(name, k, feeds of (auction, bidder, price, datetime)); a feed is
    one batch of 8 rows or fewer."""
    t = dep.TS0
    return [
        ("stored_ties_batch", 3, [
            [(1, 10, 50, t), (1, 11, 40, t + 1), (1, 12, 30, t + 2)],
            [(1, 13, 40, t + 3), (1, 14, 50, t + 4), (1, 15, 30, t + 5)]]),
        ("ties_inside_a_batch", 3, [
            [(2, 20, 7, t), (2, 21, 9, t + 1), (2, 22, 7, t + 2), (2, 23, 7, t + 3),
             (2, 24, 9, t + 4)]]),
        ("tie_at_the_kth_place", 3, [
            [(3, 30, 9, t), (3, 31, 8, t + 1), (3, 32, 5, t + 2)],
            [(3, 33, 5, t + 3), (3, 34, 5, t + 4), (3, 35, 6, t + 5)],
            [(3, 36, 6, t + 6), (3, 37, 1, t + 7)]]),
        ("more_than_k_in_one_batch", 3, [
            [(4, 40 + i, (i * 37) % 11, t + i) for i in range(8)],
            [(4, 50 + i, (i * 53) % 13, t + 10 + i) for i in range(8)]]),
        ("fewer_than_k", 5, [
            [(5, 51, 3, t), (6, 61, 4, t)], [(5, 52, 1, t + 1)]]),
        ("k_is_one", 1, [
            [(7, 70, 3, t), (7, 71, 3, t + 1), (8, 80, 2, t)],
            [(7, 72, 4, t + 2), (7, 73, 4, t + 3), (8, 81, 2, t + 1)]]),
        ("null_sort_value", 3, [
            [(9, 90, None, t), (9, 91, 5, t + 1), (9, 92, None, t + 2)],
            [(9, 93, None, t + 3), (10, 100, None, t)]]),
        ("null_payload", 3, [
            [(11, None, 5, t), (11, 110, 6, t + 1), (11, 111, 4, t + 2)],
            [(11, None, 7, t + 3), (11, None, 5, t + 4)]]),
    ]


#: the struct's columns in the other order: BIDDER, the one that can be
#: null (a null DATETIME skips the record), is then the second payload
SWAPPED = "TOPK(PRICE, DATETIME, BIDDER, %d)"


@pytest.mark.parametrize("name, k, feeds", _cases(), ids=[c[0] for c in _cases()])
@pytest.mark.parametrize("form", [None, SWAPPED])
def test_variadic_topk_device_equals_oracle_equals_reference(name, k, feeds, form):
    """Ties across the stored / batch boundary (the stored entry first) and
    inside a batch (arrival order), a tie at the k-th place (the new entry
    stays out), more than k bids for one auction in a batch, fewer than k,
    k = 1, a null sort value (out of the list, in COUNT(*)) and a null
    payload, first or second: the device, the oracle and the plain
    reference agree."""
    payloads = [[_bid(*b) for b in feed] for feed in feeds]
    props = {"ksql.batch.capacity": 8}
    statements = _topk_statements(k, *([form] if form else []))
    records, _stages, backend = _serve(payloads, props, statements)
    assert backend == "device"
    oracle, _stages, _backend = _serve(payloads, {**ORACLE, **props}, statements)
    bids = [b for feed in feeds for b in feed]
    if form == SWAPPED:  # the reference ranks (price, col0, col1) alike
        bids = [(a, t, p, b) for a, b, p, t in bids]
    want = dep.reference(_corpus_of(bids, k), len(bids))
    assert dep.fold_sink(records) == dep.fold_sink(oracle) == want
    assert dep._counts_backwards(records) == 0


@pytest.mark.parametrize("form, k", [("TOPK(PRICE, %d)", 3), ("TOPK(PRICE, %d)", 1),
                                     ("TOPKDISTINCT(PRICE, %d)", 3),
                                     ("TOPKDISTINCT(PRICE, %d)", 2)])
def test_plain_topk_and_topkdistinct_keep_their_results(form, k):
    """The one-column forms over a feed with repeated prices across and
    inside batches: the device's rows are the oracle's."""
    rng = np.random.default_rng(k)
    bids = [(int(a), 1, int(p), dep.TS0 + i) for i, (a, p) in enumerate(
        zip(rng.integers(0, 5, 96), rng.integers(0, 9, 96)))]
    payloads = [[_bid(*b) for b in bids[lo:lo + 16]] for lo in range(0, 96, 16)]
    props = {"ksql.batch.capacity": 16}
    statements = _topk_statements(k, form)
    records, _stages, backend = _serve(payloads, props, statements)
    assert backend == "device"
    oracle, _stages, _backend = _serve(payloads, {**ORACLE, **props}, statements)

    def last(recs):
        return {key: json.loads(v) for key, _w, v in recs}

    assert last(records) == last(oracle) and len(last(records)) == 5


def test_string_or_session_topk_is_refused_by_name():
    """A string column beside the sort value, and the variadic form over
    SESSION windows, keep the query off the device, named."""
    e = KsqlEngine(KsqlConfig(dict(PROPS)))
    e.execute_sql(BIDS_DDL)
    e.execute_sql("CREATE TABLE S AS SELECT AUCTION, TOPK(PRICE, CHANNEL, 3) AS T "
                  "FROM BIDS GROUP BY AUCTION EMIT CHANGES;")
    e.execute_sql("CREATE TABLE W AS SELECT AUCTION, TOPK(PRICE, BIDDER, 3) AS T "
                  "FROM BIDS WINDOW SESSION (10 SECONDS) GROUP BY AUCTION EMIT CHANGES;")
    assert [h.backend for h in e.queries.values()] == ["oracle", "oracle"]
    reasons = list(e.fallback_reasons)
    assert any("TOPK with a STRING column" in r for r in reasons), reasons
    assert any("TOPK over SESSION windows" in r for r in reasons), reasons
    e.shutdown()


# ----------------------------------------------------------- the store
def test_a_grown_store_keeps_its_vector_state(served):
    """The store doubled half-way (a host rebuild of every live slot, the
    K-wide columns with them) and fed on: the same top lists as the
    reference, on the sink and in the store."""
    corpus = served.corpus
    half = N_EVENTS // 2
    run = Served()
    try:
        run.feed(corpus.payloads[:half], corpus.ts[:half])
        before = set(dep.read_store(run.ex)["entries"])
        capacity = run.ex.device.store_capacity
        run.ex.device._grow()
        assert run.ex.device.store_capacity == 2 * capacity
        assert set(dep.read_store(run.ex)["entries"]) == before
        run.feed(corpus.payloads[half:], corpus.ts[half:])
        numbers = dep.compare(corpus, N_EVENTS, run.records(), dep.read_store(run.ex), None)
    finally:
        run.stop()
    assert all(n["value"] == 0 for n in numbers.values()), numbers


# ----------------------------------------------------------- the counters
def _counted(corpus, capacity: int):
    """(rows folded, entries admitted, slots rewritten) over every batch,
    as the reference counts them."""
    k = corpus.k
    tops: dict = {}
    rows = admitted = slots = 0
    n = len(corpus.auction)
    for lo in range(0, n, capacity):
        touched = set()
        for i in range(lo, min(lo + capacity, n)):
            a, p = int(corpus.auction[i]), int(corpus.price[i])
            rows += 1
            touched.add(a)
            top = tops.setdefault(a, [])
            top.append((p, i))
            top.sort(key=lambda x: x[0], reverse=True)
            del top[k:]
        slots += len(touched)
        admitted += sum(1 for a in touched for _p, i in tops[a] if i >= lo)
    return rows, admitted, slots


def _check_counters(stages, corpus) -> None:
    step = stages["device.step"]
    assert step["sampled"] == N_EVENTS // CAPACITY
    rows, admitted, slots = _counted(corpus, CAPACITY)
    assert (step["topk_rows"], step["topk_admitted"], step["topk_slots"]) == (
        rows, admitted, slots)
    assert 0 < admitted < rows


def test_step_counters_equal_what_the_reference_counts(served):
    """``device.step`` ``topk_rows`` = the rows the fold took (every bid:
    none has a null price), ``topk_admitted`` = the batch's entries in
    their auction's top-10 after the batch, ``topk_slots`` = the auctions
    a batch touched."""
    corpus = dep.make_corpus(SEED, SIZES, N_EVENTS)
    _check_counters(served.stages, corpus)


def test_the_four_shard_mesh_equals_one_device(served):
    """On four of the virtual devices the query runs distributed: the sink
    folds to what one device left, and the counters sum over the shards
    to the same totals."""
    run = Served(MESH)
    try:
        assert run.handle.backend == "distributed", dict(run.engine.fallback_reasons)
        run.feed(served.corpus.payloads, served.corpus.ts)
        records, stages = run.records(), run.stages()
    finally:
        run.stop()
    assert dep.fold_sink(records) == dep.fold_sink(served.records)
    assert dep._counts_backwards(records) == 0
    step = stages["device.step"]
    rows, _admitted, _slots = _counted(served.corpus, CAPACITY)
    assert step["topk_rows"] == rows


def test_explain_analyze_and_metrics_show_the_fold():
    """The three ``device.step`` counters reach ``EXPLAIN ANALYZE``'s extra
    column and the Prometheus exposition, as the other step counters do."""
    from ksql_tpu.common.metrics import prometheus_text

    t = dep.TS0
    bids = [_bid(1 + i % 3, 10 + i, 100 + (i * 7) % 13, t + i) for i in range(40)]
    run = Served({"ksql.batch.capacity": 16})
    try:
        run.feed(bids)
        rows = run.engine.execute_sql(f"EXPLAIN ANALYZE {run.qid};")[0].rows
        text = prometheus_text(run.engine.metrics_snapshot(),
                               {run.qid: run.stages()})
    finally:
        run.stop()
    step = json.loads(next(r["extra"] for r in rows if r["stage"] == "device.step"))
    assert step["topk_rows"] == 40 and step["topk_slots"] == 3 * 3
    assert 0 < step["topk_admitted"] <= 40
    for name in ("topk_rows", "topk_admitted", "topk_slots"):
        assert f"ksql_query_stage_{name}_total{{" in text, name
