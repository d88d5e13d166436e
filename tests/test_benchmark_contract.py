"""The program books what the benchmark reads.

``benchmark/run.py`` builds its ``span.<stage>.<field>`` observations from
``FlightRecorder.stage_stats()`` and the files of
``benchmark/layer_metrics/`` divide them; a span or counter that is renamed
or dropped in the program reads ``null`` under ``per_layer`` in the ledger
and nothing else says so.  This file runs each configuration of
``BENCHMARK.json`` at a small size on the CPU and holds every such name to
what the run booked.  It reads ``benchmark/`` and ``BENCHMARK.json`` as
data and imports nothing from there: the harness's own tests
(``benchmark/tests/``) are run by hand, these by tier-1.
"""

from __future__ import annotations

import glob
import json
import os

import pytest

from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.runtime.topics import Record
from ksql_tpu.server.rest import KsqlServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TS0 = 1_700_000_000_000
#: records a run feeds, in this many polls (several kept ticks)
N_EVENTS, N_POLLS = 600, 3


def _json(path: str):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


BENCH = _json("BENCHMARK.json")
CONFIG_OF_CELL = {w["name"]: w["config"] for w in BENCH["workloads"]}


def _span_names(metric, *parts):
    return [n for part in parts for n in metric.get(part, ()) if n.startswith("span.")]


#: every layer metric that reads a span: file stem -> its definition
LAYER_METRICS = {
    os.path.basename(path)[:-len(".json")]: metric
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark/layer_metrics/*.json")))
    for metric in [_json(path)]
    if _span_names(metric, "num", "den")
}


def _configs_of(metric_name: str):
    """The configurations whose cells report ``metric_name`` (all of them
    where ``BENCHMARK.json`` names no cells for it)."""
    cells = next((m["workloads"] for m in BENCH["per_layer"]
                  if m["name"] == metric_name and "workloads" in m), list(CONFIG_OF_CELL))
    return sorted({CONFIG_OF_CELL[c] for c in cells})


# ------------------------------------------------- the records of each config
def _pageviews_count_feed(engine, poll):
    topic = engine.broker.topic("page_views")
    for i in range(N_EVENTS):
        topic.produce(Record(
            key=None, timestamp=TS0 + i,
            value='{"URL":"/catalog/products/item-%07d/view.html","USER_ID":%d,"VIEWTIME":%d}'
            % (i * i % 97, 1 + i % 999, TS0 + i)))
        if (i + 1) % (N_EVENTS // N_POLLS) == 0:
            poll()


def _pageviews_hopping_stats_feed(engine, poll):
    """66 polls of a batch each, past the store's first retention pass
    (every 64th batch): ``store_grave_pct.hop`` has graves to read."""
    topic = engine.broker.topic("page_views")
    for i in range(N_EVENTS + 60):
        topic.produce(Record(
            key=None, timestamp=TS0 + 1000 * i,
            value='{"URL":"/catalog/products/item-%07d/view.html","USER_ID":%d,"LATENCY":%d.25}'
            % (i * i % 97, 1 + i % 999, i % 1000)))
        if (i + 1) % 10 == 0:
            poll()


def _clicks_users_join_feed(engine, poll):
    users = engine.broker.topic("users")
    for i in range(40):  # ten keys: inserts, then updates
        user = "User_%d" % (i % 10)
        users.produce(Record(
            key=user, timestamp=TS0,
            value='{"registertime":%d,"userid":"%s","regionid":"Region_%d","gender":"%s"}'
            % (1487715775521 + i, user, 1 + i % 9, ("MALE", "FEMALE", "OTHER")[i % 3])))
    poll()
    views = engine.broker.topic("pageviews")
    for i in range(N_EVENTS):
        views.produce(Record(key=None, timestamp=TS0 + 1 + i,
                             value="%d,User_%d,Page_%d" % (1 + 10 * i, i % 10, 1 + i % 90)))
        if (i + 1) % (N_EVENTS // N_POLLS) == 0:
            poll()


def _nexmark_q11_sessions_feed(engine, poll):
    """Bids of a few bidders, event time from DATETIME, some held back:
    sessions extend across polls, so steps retract what they extend."""
    topic = engine.broker.topic("bids")
    for i in range(N_EVENTS):
        held = 3000 if i % 10 == 0 else 0
        topic.produce(Record(
            key=None, timestamp=TS0 + 100 * i,
            value='{"AUCTION":%d,"BIDDER":%d,"PRICE":%d,"CHANNEL":"Google","URL":"u",'
                  '"DATETIME":%d,"EXTRA":"x"}' % (1000 + i % 7, 1000 + i % 13, 100 + i,
                                                 TS0 + 100 * i - held)))
        if (i + 1) % (N_EVENTS // N_POLLS) == 0:
            poll()


def _nexmark_q19_topk_feed(engine, poll):
    """Bids over a few auctions, prices repeating: each poll's batches
    rewrite stored top lists, some bids enter them and some do not."""
    topic = engine.broker.topic("bids")
    for i in range(N_EVENTS):
        topic.produce(Record(
            key=None, timestamp=TS0 + 100 * i,
            value='{"AUCTION":%d,"BIDDER":%d,"PRICE":%d,"CHANNEL":"Google","URL":"u",'
                  '"DATETIME":%d,"EXTRA":"x"}' % (1000 + i % 7, 1000 + i % 13,
                                                 100 + i * i % 97, TS0 + 100 * i)))
        if (i + 1) % (N_EVENTS // N_POLLS) == 0:
            poll()


class Run:
    """What one configuration's statements booked over a few hundred
    records, driven as the harness's fill drives them: ``poll_once()`` until
    quiet under the server's engine lock, the recorder on, an observer on
    the recorder.  Keeps readings only: the engine is shut down."""

    def __init__(self, config_name: str, feed):
        self.config = _json(next(c["file"] for c in BENCH["configs"]
                                 if c["name"] == config_name))
        engine = KsqlEngine(KsqlConfig({**self.config["engine_props"],
                                        **self.config["rehearse"]["engine_props"]}))
        server = KsqlServer(engine=engine, port=0)  # never started
        for statement in self.config["statements"]:
            engine.execute_sql(statement)
        handle = list(engine.queries.values())[-1]
        executor = handle.executor
        recorder = engine.trace_recorder(handle.query_id)

        def poll() -> None:
            with server.engine_lock:
                while engine.poll_once() or executor.pending_records():
                    pass

        # as the harness's TickLog hangs itself on the recorder
        self.traces, prev = [], recorder.observer

        def on_tick(trace) -> None:
            self.traces.append(trace)
            if prev is not None:
                prev(trace)

        recorder.observer = on_tick
        feed(engine, poll)
        recorder.observer = prev
        self.stage_stats = recorder.stage_stats()
        self.state, self.backend = handle.state, handle.backend
        self.native_ingest = executor._native_fields is not None
        engine.shutdown()
        # benchmark/run.py's stage_totals + stage_deltas, re-stated: every
        # numeric field of a stage but these three is an observation
        self.spans = {
            f"span.{stage}.{field}": float(value)
            for stage, stats in self.stage_stats.items()
            for field, value in stats.items()
            if isinstance(value, (int, float)) and field not in ("ticks", "p50_ms", "p99_ms")
        }


@pytest.fixture(scope="module")
def pageviews_count():
    return Run("pageviews_count", _pageviews_count_feed)


@pytest.fixture(scope="module")
def clicks_users_join():
    return Run("clicks_users_join", _clicks_users_join_feed)


@pytest.fixture(scope="module")
def pageviews_hopping_stats():
    return Run("pageviews_hopping_stats", _pageviews_hopping_stats_feed)


@pytest.fixture(scope="module")
def nexmark_q11_sessions():
    return Run("nexmark_q11_sessions", _nexmark_q11_sessions_feed)


@pytest.fixture(scope="module")
def nexmark_q19_topk():
    return Run("nexmark_q19_topk", _nexmark_q19_topk_feed)


@pytest.fixture(scope="module")
def pageviews_count_mesh4():
    """The same statements and feed on four of ``conftest.py``'s virtual
    devices (``ksql.runtime.backend=distributed``)."""
    return Run("pageviews_count_mesh4", _pageviews_count_feed)


# ------------------------------------------------------------------ the cases
@pytest.mark.parametrize("metric_name", sorted(LAYER_METRICS))
def test_layer_metric_reads_what_the_program_books(metric_name, request):
    metric = LAYER_METRICS[metric_name]
    for config in _configs_of(metric_name):
        # the fixture above of that name: a PR that adds a configuration
        # to BENCHMARK.json adds its run here
        run = request.getfixturevalue(config)
        assert run.state == "RUNNING"
        assert run.backend == run.config["engine_props"].get("ksql.runtime.backend", "device")
        absent = [n for n in _span_names(metric, "num", "den") if n not in run.spans]
        assert not absent, (
            f"{metric_name} ({config}) reads {absent}; the program booked "
            f"{sorted(run.spans)}")
        empty = [n for n in _span_names(metric, "den") if not run.spans[n] > 0]
        assert not empty, f"{metric_name} ({config}) divides by {empty}: read 0"


def test_the_harness_finds_what_it_reaches_for(pageviews_count, clicks_users_join,
                                               pageviews_count_mesh4, pageviews_hopping_stats,
                                               nexmark_q11_sessions, nexmark_q19_topk):
    """What ``benchmark/run.py`` takes hold of besides the stages:
    ``FlightRecorder.observer`` and each trace's ``_t0`` and spans
    (``TickLog``), ``KsqlServer.engine_lock`` (the fill, ``_quiescent``) and
    the executor's ``_native_fields``, held to the configuration's
    ``native_ingest``."""
    for run in (pageviews_count, clicks_users_join, pageviews_count_mesh4,
                pageviews_hopping_stats, nexmark_q11_sessions, nexmark_q19_topk):
        kept = run.stage_stats["tick"]["n"]
        assert kept >= N_POLLS and len(run.traces) == kept
        for trace in run.traces:
            assert isinstance(trace._t0, float) and trace.spans
            for span in trace.spans:
                assert isinstance(span["name"], str)
                assert {"t0Ms", "durMs", "depth"} <= set(span), span
                assert span["t0Ms"] >= 0 and span["durMs"] >= 0 and span["depth"] >= 0
        assert bool(run.config["native_ingest"]) == run.native_ingest
