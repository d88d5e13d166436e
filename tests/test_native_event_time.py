"""The native C++ ingest tier takes a stream's ``TIMESTAMP=`` BIGINT column as
the batch's event time, and hands the device step the same rows, in the same
order and with the same event times, as the Python per-record tier does: a
record whose event time is null (or whose column is absent) is skipped and
logged, a negative one skipped, as ksqlDB skips them (its extractor hands
Kafka Streams -1 on a null)."""

from __future__ import annotations

import numpy as np
import pytest

from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.runtime.device_executor import native_ingest_fields
from ksql_tpu.runtime.topics import Record

T0 = 1_700_000_000_000
DDL = ("CREATE STREAM BIDS (BIDDER BIGINT, PRICE BIGINT, DATETIME BIGINT) WITH ("
       "KAFKA_TOPIC='bids', VALUE_FORMAT='JSON', TIMESTAMP='DATETIME');")
QUERIES = {
    "projection": "CREATE STREAM OUT AS SELECT BIDDER, PRICE FROM BIDS EMIT CHANGES;",
    "sessions": ("CREATE TABLE OUT AS SELECT BIDDER, COUNT(*) AS N FROM BIDS "
                 "WINDOW SESSION (10 SECONDS) GROUP BY BIDDER EMIT CHANGES;"),
}
#: the odd record (at index 5 of 12) of each case; every other record has
#: an event time of its own, far from its Kafka timestamp
ODD = {
    "bigint": '{"BIDDER":3,"PRICE":5,"DATETIME":%d}' % (T0 + 50_000),
    "null": '{"BIDDER":3,"PRICE":5,"DATETIME":null}',
    "negative": '{"BIDDER":3,"PRICE":5,"DATETIME":-5}',
    "absent": '{"BIDDER":3,"PRICE":5}',
}


def _run(query: str, odd: str, native: bool):
    """(the step's batches as (ts, BIDDER) of their valid rows, sink
    records, processing-log categories) of one run over twelve records."""
    e = KsqlEngine(KsqlConfig({"ksql.batch.capacity": 8}))
    e.execute_sql(DDL)
    qid = [r.query_id for r in e.execute_sql(QUERIES[query]) if r.query_id][-1]
    ex = e.queries[qid].executor
    assert (ex._native_fields is not None) and ex._native_fields["ts_field"] == "DATETIME"
    if not native:
        ex._native_fields = None
    dev = ex.device
    batches = []
    real = dev.process_arrays

    def spy(arrays):
        valid = np.asarray(arrays["row_valid"])
        batches.append((np.asarray(arrays["ts"])[valid].tolist(),
                        np.asarray(arrays["v_BIDDER"])[valid].tolist()))
        return real(arrays)

    dev.process_arrays = spy
    topic = e.broker.topic("bids")
    for i in range(12):
        value = odd if i == 5 else '{"BIDDER":%d,"PRICE":%d,"DATETIME":%d}' % (
            i % 4, 10 * i, T0 + 1000 * i)
        topic.produce(Record(key=None, value=value, timestamp=i))
    while e.poll_once():
        pass
    sink = e.broker.topic(e.queries[qid].plan.physical_plan.topic)
    records = [(r.key, r.window, r.value, r.timestamp) for r in sink.all_records()]
    log = [w.split(":")[0] for w, _m in e.processing_log]
    native_rows = sum(ex.native_ingest_rows.values())
    e.shutdown()
    return batches, records, log, native_rows


def _folded(records):
    """A changelog's last value per (key, window); a tombstone deletes."""
    table = {}
    for key, window, value, _ts in records:
        if value is None:
            table.pop((key, window), None)
        else:
            table[(key, window)] = value
    return table


@pytest.mark.parametrize("query", sorted(QUERIES))
@pytest.mark.parametrize("case", sorted(ODD))
def test_native_tier_batches_equal_the_python_tiers(case, query):
    """The step gets the same rows, with the same event times, in the same
    order, and leaves the same answers; where every record has an event
    time, in the same batches.  (A record either tier skips splits the
    native tier's batch there: its neighbours are stepped as the runs they
    were decoded in.)"""
    native = _run(query, ODD[case], native=True)
    python = _run(query, ODD[case], native=False)
    batches, records, log, native_rows = native
    flat = lambda bs: [(t, b) for ts, bidders in bs for t, b in zip(ts, bidders)]  # noqa: E731
    assert flat(batches) == flat(python[0])
    if case == "bigint":
        assert batches == python[0]
    if query == "projection":
        assert records == python[1]
    else:
        assert _folded(records) == _folded(python[1])
    assert log == python[2]
    assert native_rows == (12 if case == "bigint" else 11)
    times = [t for t, _b in flat(batches)]
    # event time is the column's, never the record's own timestamp
    assert len(times) == (12 if case == "bigint" else 11) and min(times) >= T0
    if case == "bigint":
        assert T0 + 50_000 in times
    assert log == (["timestamp-extract"] if case in ("null", "absent") else [])


def test_a_formatted_or_key_timestamp_stays_per_record():
    e = KsqlEngine(KsqlConfig({"ksql.batch.capacity": 8}))
    e.execute_sql("CREATE STREAM S (ID BIGINT KEY, TS STRING, V BIGINT) WITH (KAFKA_TOPIC='s', "
                  "VALUE_FORMAT='JSON', TIMESTAMP='TS', TIMESTAMP_FORMAT='yyyy-MM-dd');")
    e.execute_sql("CREATE STREAM K (ID BIGINT KEY, V BIGINT) WITH (KAFKA_TOPIC='k', "
                  "VALUE_FORMAT='JSON', TIMESTAMP='ID');")
    for src in ("S", "K"):
        qid = [r.query_id for r in e.execute_sql(
            f"CREATE STREAM O_{src} AS SELECT ID, V FROM {src} EMIT CHANGES;") if r.query_id][-1]
        assert native_ingest_fields(e.queries[qid].executor.device) is None
    e.shutdown()
