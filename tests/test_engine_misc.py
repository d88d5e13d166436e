"""Sandboxed validation, device-store pull queries, and fallback
robustness (VERDICT round-3 items 5, 8/9 + advisor findings).

Reference analogs: SandboxedExecutionContext (every distributed statement
validates on an engine fork before mutating state, ksqldb-engine
KsqlEngine.createSandbox) and KsMaterializedTableIQv2 (pull queries served
from the materialized state store)."""

import json

import pytest

from ksql_tpu.common.config import RUNTIME_BACKEND, KsqlConfig
from ksql_tpu.common.errors import KsqlException
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.runtime.topics import Record

DDL = (
    "CREATE STREAM PV (URL STRING, UID BIGINT, LAT DOUBLE) "
    "WITH (kafka_topic='pv', value_format='JSON');"
)


def _feed(e, rows, ts_step=1000):
    t = e.broker.topic("pv")
    for i, row in enumerate(rows):
        t.produce(
            Record(key=None, value=json.dumps(row), timestamp=i * ts_step, partition=0)
        )
    e.run_until_quiescent()


# ------------------------------------------------------------------ sandbox


def test_failing_ctas_leaves_metastore_untouched():
    e = KsqlEngine()
    e.execute_sql(DDL)
    before = set(e.metastore.all_sources())
    with pytest.raises(Exception):
        # LAT2 doesn't exist -> planning fails; the sink source must NOT be
        # registered and the sink topic must NOT be created
        e.execute_sql("CREATE TABLE BAD AS SELECT URL, COUNT(LAT2) AS C FROM PV GROUP BY URL;")
    assert set(e.metastore.all_sources()) == before
    assert not e.broker.has_topic("BAD")


def test_failing_create_stream_registers_nothing():
    e = KsqlEngine()
    e.execute_sql(DDL)
    with pytest.raises(KsqlException):
        # duplicate topic-less stream with bad format
        e.execute_sql(
            "CREATE STREAM S2 (A INT) WITH (kafka_topic='t2', value_format='NOPE');"
        )
    assert e.metastore.get_source("S2") is None


def test_sandbox_does_not_leak_inserts():
    e = KsqlEngine()
    e.execute_sql(DDL)
    e.execute_sql("INSERT INTO PV (URL, UID, LAT) VALUES ('/a', 1, 2.0);")
    # exactly one record lands on the real topic (the sandbox's produce is
    # dropped with the fork)
    assert len(e.broker.topic("pv").all_records()) == 1


def test_valid_statements_still_execute():
    e = KsqlEngine()
    e.execute_sql(DDL)
    e.execute_sql("CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV GROUP BY URL;")
    assert e.metastore.get_source("C") is not None


# ------------------------------------------------- pull from device store


def _pull_rows(backend):
    e = KsqlEngine(KsqlConfig({RUNTIME_BACKEND: backend}))
    e.execute_sql(DDL)
    e.execute_sql(
        "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT, SUM(LAT) AS S "
        "FROM PV GROUP BY URL EMIT CHANGES;"
    )
    _feed(
        e,
        [
            {"URL": "/a", "UID": 1, "LAT": 10.0},
            {"URL": "/b", "UID": 2, "LAT": 20.0},
            {"URL": "/a", "UID": 3, "LAT": 30.0},
        ],
    )
    res = e.execute_sql("SELECT * FROM C;")[0]
    return e, {r["URL"]: (r["CNT"], r["S"]) for r in res.rows}


def test_pull_query_reads_hbm_store():
    e, rows = _pull_rows("device")
    handle = list(e.queries.values())[0]
    assert handle.backend == "device"
    # the pull result comes from CompiledDeviceQuery.scan_store, not the
    # host shadow dict: clearing the shadow must not change the answer
    handle.materialized.clear()
    res = e.execute_sql("SELECT * FROM C;")[0]
    assert {r["URL"]: (r["CNT"], r["S"]) for r in res.rows} == rows


def test_pull_query_device_matches_oracle():
    _, dev = _pull_rows("device")
    _, ora = _pull_rows("oracle")
    assert dev == ora == {"/a": (2, 40.0), "/b": (1, 20.0)}


def test_windowed_pull_from_device_store():
    e = KsqlEngine(KsqlConfig({RUNTIME_BACKEND: "device"}))
    e.execute_sql(DDL)
    e.execute_sql(
        "CREATE TABLE W AS SELECT URL, COUNT(*) AS CNT FROM PV "
        "WINDOW TUMBLING (SIZE 2 SECONDS) GROUP BY URL EMIT CHANGES;"
    )
    _feed(
        e,
        [{"URL": "/a", "UID": 1, "LAT": 1.0}, {"URL": "/a", "UID": 2, "LAT": 2.0}],
        ts_step=3000,
    )
    handle = list(e.queries.values())[0]
    handle.materialized.clear()
    res = e.execute_sql("SELECT URL, WINDOWSTART, CNT FROM W;")[0]
    got = {(r["URL"], r["WINDOWSTART"]): r["CNT"] for r in res.rows}
    assert got == {("/a", 0): 1, ("/a", 2000): 1}


# ------------------------------------- generic failure on a device-eligible plan


def test_generic_device_failure_fails_the_statement(monkeypatch):
    """A build failure that is not DeviceUnsupported, on a plan the static
    classifier places on the device, is the device's own (compile error,
    allocation): the statement fails instead of quietly running on the
    oracle."""
    import ksql_tpu.runtime.device_executor as dx

    def boom(*a, **k):
        raise RuntimeError("simulated XLA failure")

    monkeypatch.setattr(dx, "CompiledDeviceQuery", boom)
    e = KsqlEngine(KsqlConfig({RUNTIME_BACKEND: "device"}))
    e.execute_sql(DDL)
    with pytest.raises(KsqlException, match="device-lowering.*simulated XLA"):
        e.execute_sql(
            "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV GROUP BY URL;"
        )
    assert not e.queries
    assert e.fallback_reasons == {}


def test_unlowerable_plan_still_takes_the_oracle_rung(monkeypatch):
    """Where the classifier's own probe cannot construct the lowering
    either, the plan never was device-eligible: the oracle rung stays,
    logged and counted."""
    import ksql_tpu.runtime.lowering as lw

    def boom(self, step):
        raise RuntimeError("plan analysis broke")

    monkeypatch.setattr(lw.CompiledDeviceQuery, "_analyze", boom)
    e = KsqlEngine(KsqlConfig({RUNTIME_BACKEND: "device"}))
    e.execute_sql(DDL)
    e.execute_sql("CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV GROUP BY URL;")
    handle = list(e.queries.values())[0]
    assert handle.backend == "oracle"
    assert any("device-lowering" in w for w, _ in e.processing_log)
    assert list(e.fallback_reasons) == ["construction failed: plan analysis broke"]
    _feed(e, [{"URL": "/a", "UID": 1, "LAT": 1.0}])
    res = e.execute_sql("SELECT * FROM C;")[0]
    assert res.rows == [{"URL": "/a", "CNT": 1}]


def test_pull_staleness_gate_and_standby_reads():
    """ksql.query.pull.max.allowed.offset.lag rejects stale pulls unless
    standby reads accept the lag (HARouting freshness semantics)."""
    e = KsqlEngine()
    e.execute_sql(DDL)
    e.execute_sql("CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV GROUP BY URL;")
    _feed(e, [{"URL": "/a", "UID": 1, "LAT": 1.0}])
    handle = list(e.queries.values())[0]
    handle.state = "PAUSED"  # stop consuming: lag accumulates
    t = e.broker.topic("pv")
    for i in range(5):
        t.produce(Record(key=None, value=json.dumps({"URL": "/a", "UID": i, "LAT": 0.0}), timestamp=i))
    e.poll_once()
    e.session_properties["ksql.query.pull.max.allowed.offset.lag"] = 2
    with pytest.raises(KsqlException, match="exceeds"):
        e.execute_sql("SELECT * FROM C;")
    e.session_properties["ksql.query.pull.enable.standby.reads"] = True
    rows = e.execute_sql("SELECT * FROM C;")[0].rows
    assert rows and rows[0]["CNT"] == 1  # stale but served
