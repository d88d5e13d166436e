"""Metrics/observability (VERDICT round-3 missing item 6).

MetricCollectors analog: per-query consumption/production rates, error
counts, consumer lag, engine aggregates, surfaced through
KsqlEngine.metrics_snapshot() and the REST /metrics endpoint."""

import json
import os

from ksql_tpu.common import config as cfg
from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.runtime.topics import Record

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _engine_with_data(n=5, bad=0):
    from ksql_tpu.common.config import EMIT_CHANGES_PER_RECORD, KsqlConfig

    # these tests count per-record changelog messages; the batched default
    # would legitimately coalesce them
    e = KsqlEngine(KsqlConfig({EMIT_CHANGES_PER_RECORD: True}))
    e.execute_sql(
        "CREATE STREAM PV (URL STRING, V BIGINT) "
        "WITH (kafka_topic='pv', value_format='JSON');"
    )
    e.execute_sql(
        "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV "
        "GROUP BY URL EMIT CHANGES;"
    )
    t = e.broker.topic("pv")
    for i in range(n):
        t.produce(
            Record(key=None, value=json.dumps({"URL": f"/p{i % 2}", "V": i}),
                   timestamp=i)
        )
    for _ in range(bad):
        t.produce(Record(key=None, value="{not json", timestamp=99))
    e.run_until_quiescent()
    return e


def test_per_query_rates_and_totals():
    e = _engine_with_data(n=7)
    snap = e.metrics_snapshot()
    qid = list(e.queries)[0]
    q = snap["queries"][qid]
    assert q["messages-consumed-total"] == 7
    assert q["messages-consumed-per-sec"] > 0
    assert q["messages-produced-total"] == 7  # per-record EMIT CHANGES
    assert q["processing-errors-total"] == 0
    assert q["consumer-lag"] == 0
    assert q["state"] == "RUNNING"
    eng = snap["engine"]
    assert eng["messages-consumed-total"] == 7
    assert eng["num-persistent-queries"] == 1


def test_error_counter_marks_deserialization_failures():
    e = _engine_with_data(n=2, bad=3)
    qid = list(e.queries)[0]
    q = e.metrics_snapshot()["queries"][qid]
    assert q["processing-errors-total"] == 3
    assert q["messages-produced-total"] == 2


def test_consumer_lag_reflects_unconsumed_records():
    e = _engine_with_data(n=3)
    h = list(e.queries.values())[0]
    h.state = "PAUSED"
    t = e.broker.topic("pv")
    for i in range(4):
        t.produce(Record(key=None, value=json.dumps({"URL": "/x", "V": i}), timestamp=i))
    e.poll_once()
    snap = e.metrics_snapshot()
    assert snap["queries"][list(e.queries)[0]]["consumer-lag"] == 4
    assert snap["engine"]["query-states"] == {"PAUSED": 1}


def test_terminate_removes_query_metrics():
    e = _engine_with_data()
    qid = list(e.queries)[0]
    e.execute_sql(f"TERMINATE {qid};")
    assert qid not in e.metrics_snapshot()["queries"]


def test_rest_metrics_endpoint():
    from ksql_tpu.server.rest import KsqlServer
    from ksql_tpu.client.client import KsqlRestClient

    s = KsqlServer(engine=_engine_with_data(), port=0)
    s.start()
    try:
        import urllib.request

        with urllib.request.urlopen(f"{s.url}/metrics") as r:
            body = json.loads(r.read())
        assert "engine" in body and "queries" in body and "server" in body
        assert body["engine"]["messages-consumed-total"] == 5
    finally:
        s.stop()


def test_query_error_classification_and_self_healing():
    """A crashing executor marks the query ERROR with a classified error,
    and the engine restarts it after the retry backoff (QueryError +
    RegexClassifier + restart path analogs)."""
    import time

    from ksql_tpu.common.config import (
        QUERY_RETRY_BACKOFF_INITIAL_MS,
        KsqlConfig,
    )
    from ksql_tpu.engine.engine import KsqlEngine as _E

    e = _E(KsqlConfig({QUERY_RETRY_BACKOFF_INITIAL_MS: 50}))
    e.execute_sql(
        "CREATE STREAM PV (URL STRING, V BIGINT) "
        "WITH (kafka_topic='pv', value_format='JSON');"
    )
    e.execute_sql("CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV GROUP BY URL;")
    handle = list(e.queries.values())[0]

    class Boom:
        def process(self, topic, rec):
            raise RuntimeError("XLA device wedged")

    handle.executor = Boom()
    t = e.broker.topic("pv")
    t.produce(Record(key=None, value=json.dumps({"URL": "/a", "V": 1}), timestamp=0))
    e.poll_once()
    assert handle.state == "ERROR"
    assert handle.error_queue and handle.error_queue[-1].error_type == "SYSTEM"
    snap = e.metrics_snapshot()
    assert snap["queries"][handle.query_id]["error-queue"]
    # before the backoff elapses: still ERROR
    e.poll_once()
    assert handle.state == "ERROR"
    time.sleep(0.06)
    e.run_until_quiescent()
    assert handle.state == "RUNNING"
    # the record was processed by the rebuilt executor (offset had advanced
    # before the crash, so only subsequent records flow)
    t.produce(Record(key=None, value=json.dumps({"URL": "/a", "V": 2}), timestamp=1))
    e.run_until_quiescent()
    res = e.execute_sql("SELECT * FROM C;")[0]
    assert res.rows and res.rows[0]["CNT"] >= 1


def test_custom_classifier_regex():
    from ksql_tpu.engine.engine import classify_error

    assert classify_error(RuntimeError("weird thing"), "USER:weird") == "USER"
    assert classify_error(RuntimeError("boom"), "") == "UNKNOWN"
    assert classify_error(Exception("SerdeException: bad json")) == "USER"
    assert classify_error(Exception("Topic x does not exist")) == "SYSTEM"


def test_classifier_markers_are_word_bounded():
    """'broadcast' must not trip the 'cast' USER rule (word boundaries),
    while genuine marker words still match in any case."""
    from ksql_tpu.engine.engine import classify_error

    assert classify_error(
        ValueError("cannot broadcast shapes (8,) (3,)")
    ) == "UNKNOWN"
    assert classify_error(ValueError("bad CAST to BIGINT")) == "USER"
    assert classify_error(ValueError("integer overflow in SUM")) == "USER"
    assert classify_error(OSError("disk gone")) == "SYSTEM"
    # multi-word markers stay substring matches
    assert classify_error(Exception("stream FOO does not exist")) == "SYSTEM"
    # only the LEADING edge is bounded: markers still match CamelCase
    # exception-name prefixes and word stems
    assert classify_error(OverflowError("int too large")) == "USER"

    class XlaRuntimeError(Exception):
        pass

    assert classify_error(XlaRuntimeError("device wedged")) == "SYSTEM"
    assert classify_error(Exception("failed to deserialize record")) == "USER"


# --------------------------------------------- metrics exposition registry
def test_metrics_registry_complete():
    """ISSUE satellite: every Prometheus series name a representative
    engine run emits must be documented in metrics_registry.json — new
    series land with their registry entry or this fails."""
    import re

    from ksql_tpu.common.metrics import prometheus_text
    from ksql_tpu.server.rest import PushQuerySession

    registry = json.load(
        open(os.path.join(ROOT, "metrics_registry.json"))
    )["series"]
    e = KsqlEngine(KsqlConfig({
        cfg.RUNTIME_BACKEND: "device",
        cfg.BATCH_CAPACITY: 1024,
    }))
    e.execute_sql(
        "CREATE STREAM PV (URL STRING, V BIGINT) "
        "WITH (kafka_topic='pv', value_format='JSON');"
    )
    e.execute_sql(
        "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV "
        "GROUP BY URL EMIT CHANGES;"
    )
    e.session_properties["auto.offset.reset"] = "latest"
    sess = PushQuerySession(e, "SELECT URL FROM PV WHERE V > 1 EMIT CHANGES;")
    t = e.broker.topic("pv")
    for i in range(200):
        t.produce(Record(
            key=None, value=json.dumps({"URL": f"/p{i % 7}", "V": i}),
            timestamp=i,
        ))
    while e.poll_once():
        pass
    sess.poll()
    snap = e.metrics_snapshot()
    stages = {
        qid: rec.stage_stats() for qid, rec in e.trace_recorders.items()
    }
    txt = prometheus_text(snap, stages, server={
        "requests": 3, "errors": 0, "statements-executed": 2,
        "queries-started": 1,
    })
    emitted = {
        m.group(1)
        for m in re.finditer(
            r"^([a-zA-Z_:][a-zA-Z0-9_:]*)[{ ]", txt, re.M
        )
        if not m.group(0).startswith("#")
    }
    assert emitted, "representative run emitted no series"
    unlisted = sorted(emitted - set(registry))
    assert not unlisted, (
        f"Prometheus series missing from metrics_registry.json: "
        f"{unlisted} — document them there (name -> meaning) to land"
    )
    sess.close()
    e.shutdown()
