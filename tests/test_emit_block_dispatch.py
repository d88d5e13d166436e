"""Block dispatch of an emission block (ISSUE 29): when nothing observable
asks for per-emit treatment, ``DeviceExecutor._dispatch_emits`` sends a
decoded block through the engine's emit callback in one pass and onto the
sink topic in one append.  Held here, on the CPU at small shapes:

1. the block path and the per-emit loop (forced by a no-op push listener)
   leave the same sink records, writer state, materialized shadow and
   meters, for every sink shape;
2. each observable condition takes the per-emit loop, and the
   ``emit.dispatch`` stage's ``block_rows`` says so;
3. a block append that fails enters nothing;
4. ``Topic.produce_block`` numbers its records as ``produce`` does.
"""

from __future__ import annotations

import dataclasses
import json

import pytest

from ksql_tpu.common import config as cfg
from ksql_tpu.common import faults
from ksql_tpu.common import types as sql_types
from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.common.schema import LogicalSchema
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.runtime.oracle import _UNSET
from ksql_tpu.runtime.topics import Record, Topic


@pytest.fixture(autouse=True)
def _disarm():
    faults.clear()
    yield
    faults.clear()


def _engine():
    return KsqlEngine(KsqlConfig({
        cfg.RUNTIME_BACKEND: "device",
        cfg.BATCH_CAPACITY: 64,
        cfg.STATE_SLOTS: 1024,
        cfg.QUERY_RETRY_BACKOFF_INITIAL_MS: 1,
        cfg.QUERY_RETRY_BACKOFF_MAX_MS: 5,
    }))


def _feed(e, topic, records, tick_every=40):
    """Produce ``records`` and poll every ``tick_every`` of them, so that
    a run dispatches several blocks."""
    t = e.broker.topic(topic)
    for i, r in enumerate(records, 1):
        t.produce(r)
        if i % tick_every == 0:
            e.poll_once()
    e.run_until_quiescent()


# ------------------------------------------------------- the sink shapes
VIEWS = (
    "CREATE STREAM PAGE_VIEWS (URL STRING, USER_ID BIGINT, VIEWTIME BIGINT) "
    "WITH (KAFKA_TOPIC='page_views', VALUE_FORMAT='JSON');"
)
SUMS = "CREATE STREAM S (ID BIGINT, V BIGINT) WITH (kafka_topic='s', value_format='JSON');"
LINES = (
    "CREATE STREAM L (K STRING KEY, A BIGINT, X DOUBLE, F BOOLEAN, S STRING) "
    "WITH (kafka_topic='lines', value_format='JSON');"
)


def _views(e):
    _feed(e, "page_views", [
        Record(key=None, timestamp=3_600_000 * (i // 150) + i, value=json.dumps(
            {"URL": f"/u{i * 7 % 23}", "USER_ID": i, "VIEWTIME": 1000 + i}))
        for i in range(300)
    ])


def _sums(e):
    # a key's sum falls to or under 0 and comes back: HAVING writes a
    # tombstone each time it stops holding
    _feed(e, "s", [
        Record(key=None, timestamp=i,
               value=json.dumps({"ID": i % 7, "V": 5 if i % 3 else -11}))
        for i in range(200)
    ], tick_every=25)


def _lines(e):
    _feed(e, "lines", [
        Record(key=f"k{i % 5}" if i % 9 else None, timestamp=2000 + i, value=json.dumps(
            {"A": i, "X": i * 0.25, "F": i % 2 == 0, "S": f'row "{i}", with a comma'}))
        for i in range(120)
    ])


def _join(e):
    users = e.broker.topic("users")
    for i in range(40):
        users.produce(Record(key=f"User_{i % 10}", timestamp=i, value=json.dumps(
            {"REGISTERTIME": 1_500_000_000_000 + i, "GENDER": ("FEMALE", "MALE", "OTHER")[i % 3],
             "REGIONID": f"Region_{i % 9 + 1}"})))
    e.run_until_quiescent()
    _feed(e, "pageviews", [
        Record(key=None, timestamp=10 * i + 1, value=f"{10 * i + 1},User_{i * 3 % 10},Page_{i % 90 + 10}")
        for i in range(200)
    ])


def _with_default(e):
    """The sink schema gains a column that no emit carries and the sink
    step a default for it, as a registered value schema with a defaulted
    trailing field gives them (``engine.py`` ``value_defaults``)."""
    writer = list(e.queries.values())[0].executor.sink_writer
    step = writer.sink_step
    b = LogicalSchema.builder()
    for c in step.schema.key_columns:
        b.key_column(c.name, c.type)
    for c in step.schema.value_columns:
        b.value_column(c.name, c.type)
    b.value_column("ORIGIN", sql_types.STRING)
    writer.sink_step = dataclasses.replace(
        step, schema=b.build(), value_defaults=(("ORIGIN", "unknown"),))


#: name -> (statements, sink topic, feed, tweak of the built query or None)
SHAPES = {
    "tumbling_count_table_json": (
        [VIEWS, "CREATE TABLE PV_COUNTS AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
                "WINDOW TUMBLING (SIZE 1 HOUR) GROUP BY URL EMIT CHANGES;"],
        "PV_COUNTS", _views, None),
    "stream_table_join_stream": (
        ["CREATE STREAM PAGEVIEWS_ORIGINAL (VIEWTIME BIGINT, USERID VARCHAR, PAGEID VARCHAR) "
         "WITH (KAFKA_TOPIC='pageviews', VALUE_FORMAT='DELIMITED');",
         "CREATE TABLE USERS_ORIGINAL (USERID VARCHAR PRIMARY KEY, REGISTERTIME BIGINT, "
         "GENDER VARCHAR, REGIONID VARCHAR) WITH (KAFKA_TOPIC='users', VALUE_FORMAT='JSON');",
         "CREATE STREAM PAGEVIEWS_FEMALE AS SELECT USERS_ORIGINAL.USERID AS USERID, VIEWTIME, "
         "PAGEID, REGIONID, GENDER FROM PAGEVIEWS_ORIGINAL LEFT JOIN USERS_ORIGINAL ON "
         "PAGEVIEWS_ORIGINAL.USERID = USERS_ORIGINAL.USERID WHERE GENDER = 'FEMALE' EMIT CHANGES;"],
        "PAGEVIEWS_FEMALE", _join, None),
    "delimited_stream": (
        [LINES, "CREATE STREAM LO WITH (kafka_topic='lines_out', value_format='DELIMITED') "
                "AS SELECT K, A, X, F, S FROM L;"],
        "lines_out", _lines, None),
    "tombstones": (
        [SUMS, "CREATE TABLE C AS SELECT ID, SUM(V) AS SV FROM S GROUP BY ID "
               "HAVING SUM(V) > 0 EMIT CHANGES;"],
        "C", _sums, None),
    "value_defaults": (
        [LINES, "CREATE STREAM LO WITH (kafka_topic='lines_out') AS SELECT K, A, S FROM L;"],
        "lines_out", _lines, _with_default),
    "multi_partition_keys_and_null_keys": (
        [LINES, "CREATE STREAM LO WITH (kafka_topic='lines_out', partitions=3) "
                "AS SELECT K, A, S FROM L;"],
        "lines_out", _lines, None),
    "multi_partition_table": (
        [SUMS, "CREATE TABLE C WITH (partitions=4) AS SELECT ID, SUM(V) AS SV FROM S "
               "GROUP BY ID HAVING SUM(V) > 0 EMIT CHANGES;"],
        "C", _sums, None),
}


def _run(shape, condition=None):
    """One engine run of a shape (a name in SHAPES, or such a tuple);
    ``condition(engine, handle)`` sets up what should keep the per-emit
    loop.  Everything the two paths must agree on, and the
    ``emit.dispatch`` stage's counters."""
    stmts, out_topic, feed, tweak = SHAPES[shape] if isinstance(shape, str) else shape
    e = _engine()
    try:
        for s in stmts:
            e.execute_sql(s)
        h = list(e.queries.values())[0]
        assert h.backend == "device"
        writer = h.executor.sink_writer
        writer.journal_buf = []  # armed, as under a changelog; nothing drains it here
        if tweak is not None:
            tweak(e)
        if condition is not None:
            condition(e, h)
        feed(e)
        stage = e.trace_recorders[h.query_id].stage_stats()["emit.dispatch"]
        seen = {
            "sink": [dataclasses.astuple(r) for r in e.broker.topic(out_topic).all_records()],
            "partitions": [[r.seq for r in p] for p in e.broker.topic(out_topic).partitions],
            "emit_seq": writer.emit_seq,
            "batch_encoded_rows": writer.batch_encoded_rows,
            "journal_buf": list(writer.journal_buf),
            "fenced_out": writer.fenced_out,
            "materialized": dict(h.materialized),
            "messages_out": e.metrics.for_query(h.query_id).messages_out.total,
            "e2e_samples": (h.progress.e2e_hist.count, len(h.progress.e2e._samples)),
            "materialized_at": h.progress.materialized_at_ms is not None,
        }
        return seen, stage
    finally:
        e.shutdown()


def _push_listener(e, h):
    h.push_listeners.append(lambda emit: None)


@pytest.mark.parametrize("shape", list(SHAPES))
def test_block_path_equals_per_emit_loop(shape):
    block, stage = _run(shape)
    assert stage["rows"] > 0 and stage["block_rows"] == stage["rows"]
    loop, stage = _run(shape, _push_listener)
    assert stage["rows"] > 0 and stage["block_rows"] == 0
    assert len(block["sink"]) == block["emit_seq"] == stage["rows"]
    for name, value in loop.items():
        assert block[name] == value, name
    if shape.startswith("tombstones"):
        assert any(r[1] is None for r in block["sink"])
    if shape == "value_defaults":
        assert all('"ORIGIN":"unknown"' in r[1] for r in block["sink"])
    if shape.startswith("multi_partition"):
        assert sum(1 for p in block["partitions"] if p) > 1


# ------------------------------------------- what keeps the per-emit loop
def _faults_armed(e, h):
    # armed, and never fired: no topic of that name is read
    faults.install([faults.FaultRule(point="topic.read", match="no-such-topic")])


def _wrapped_produce(e, h):
    writer = h.executor.sink_writer
    real = writer._produce
    writer._produce = lambda emit: real(emit)


def _wrapped_writer_produce(e, h):
    writer = h.executor.sink_writer
    real = writer.produce
    writer.produce = lambda emit, **kw: real(emit, **kw)


def _a_row_left_to_the_row_serializer(e, h):
    writer = h.executor.sink_writer
    real = writer.encode_batch

    def encode_batch(emits):
        precoded = real(emits)
        precoded[0] = _UNSET
        return precoded

    writer.encode_batch = encode_batch


def _wrapped_topic_produce(e, h):
    topic = e.broker.topic(h.executor.sink_writer.sink_step.topic)
    real = topic.produce
    topic.produce = lambda record: real(record)


def _callback_without_block_twin(e, h):
    real = h.executor.emit_callback
    h.executor.emit_callback = lambda emit: real(emit)


def _no_batch_encode(e, h):
    h.executor.sink_writer.encode_batch = lambda emits: None


LOOP_CONDITIONS = [_faults_armed, _push_listener, _wrapped_produce, _wrapped_writer_produce,
                   _wrapped_topic_produce, _callback_without_block_twin, _no_batch_encode,
                   _a_row_left_to_the_row_serializer]


@pytest.mark.parametrize("condition", LOOP_CONDITIONS, ids=lambda f: f.__name__.strip("_"))
def test_condition_takes_the_per_emit_loop(condition):
    block, _ = _run("tombstones")
    loop, stage = _run("tombstones", condition)
    assert stage["rows"] > 0 and stage["block_rows"] == 0
    if condition is _no_batch_encode:
        assert loop.pop("batch_encoded_rows") == 0 and block.pop("batch_encoded_rows") > 0
    assert loop == block


def test_fence_replay_takes_the_per_emit_loop_until_it_is_past():
    def fenced(e, h):
        h.executor.sink_writer.fence_seq = 5

    block, _ = _run("tombstones")
    loop, stage = _run("tombstones", fenced)
    # the first block holds the five fenced ordinals: emit by emit; the
    # blocks after it start past the fence and go as blocks
    assert 0 < stage["block_rows"] < stage["rows"]
    assert loop["fenced_out"] == 5 and loop["emit_seq"] == block["emit_seq"]
    assert [r[:3] for r in loop["sink"]] == [r[:3] for r in block["sink"][5:]]
    assert loop["journal_buf"] == block["journal_buf"][5:]
    assert loop["materialized"] == block["materialized"]


def test_standby_writer_produces_nothing():
    def standby(e, h):
        h.executor.sink_writer.enabled = False

    block, _ = _run("tombstones")
    loop, stage = _run("tombstones", standby)
    assert stage["rows"] > 0 and stage["block_rows"] == 0
    assert loop["sink"] == [] and loop["emit_seq"] == 0 and loop["journal_buf"] == []
    assert loop["materialized"] == block["materialized"]


@pytest.mark.parametrize("shape,statement", [
    ("sink_timestamp_column",
     "CREATE STREAM LO WITH (kafka_topic='lines_out', timestamp='A') AS SELECT K, A, S FROM L;"),
    ("serde_without_a_block_encoder",
     "CREATE STREAM LO WITH (kafka_topic='lines_out', value_format='AVRO') AS SELECT K, A, S FROM L;"),
])
def test_sink_shape_takes_the_per_emit_loop(shape, statement):
    seen, stage = _run(([LINES, statement], "lines_out", _lines, None))
    assert stage["rows"] == len(seen["sink"]) == 120 and stage["block_rows"] == 0
    if shape == "sink_timestamp_column":
        assert [r[2] for r in seen["sink"]] == list(range(120))


# ------------------------------------------------------- all or nothing
def test_failed_block_append_enters_nothing_and_the_loop_delivers():
    state = {}

    def failing_append(e, h):
        writer = h.executor.sink_writer
        topic = e.broker.topic(writer.sink_step.topic)
        real = topic.produce_block

        def produce_block(keys, values, timestamps, windows):
            if not state:
                before = (topic.end_offsets(), topic._seq, writer.emit_seq, list(writer.journal_buf))
                with pytest.raises(OverflowError):
                    # the third record's key cannot be hashed to a partition
                    real(keys[:2] + [1 << 200] + keys[2:], values[:2] + ["x"] + values[2:],
                         timestamps[:2] + [0] + timestamps[2:], windows[:2] + [None] + windows[2:])
                state["unchanged"] = before == (
                    topic.end_offsets(), topic._seq, writer.emit_seq, list(writer.journal_buf))
                raise OSError("append refused")
            return real(keys, values, timestamps, windows)

        topic.produce_block = produce_block

    block, _ = _run("multi_partition_table")
    seen, stage = _run("multi_partition_table", failing_append)
    assert state["unchanged"] is True
    # the refused block went through the per-emit produce, the later ones as blocks
    assert 0 < stage["block_rows"] < stage["rows"]
    assert seen == block


# -------------------------------------------- Topic.produce_block alone
@pytest.mark.parametrize("partitions", [1, 4])
def test_topic_block_append_numbers_records_as_produce_does(partitions):
    keys = [None, "a", ("b", 2), None, 7, "a", None, {"K": "c"}, 2.5, None]
    rows = [(k, f"v{i}" if i % 4 else None, 100 + i, (i, i + 10) if i % 3 else None)
            for i, k in enumerate(keys)]
    one, many = Topic("one", partitions), Topic("many", partitions)
    for round_ in range(3):  # the later rounds start at offsets past 0
        for k, v, t, w in rows:
            one.produce(Record(key=k, value=v, timestamp=t, partition=-1, window=w))
        out = many.produce_block(*(list(col) for col in zip(*rows)))
        assert len(out) == len(rows) and out == many.all_records()[-len(rows):]
        assert many.produce_block([], [], [], []) == []
    assert many.partitions == one.partitions
    assert many._seq == one._seq == 3 * len(rows)
    assert many.end_offsets() == one.end_offsets()
    if partitions > 1:
        assert sum(1 for p in many.partitions if p) > 1


def test_block_appends_beside_produce_from_other_threads_lose_nothing():
    """Threads that call ``produce`` and ``produce_block`` on one topic at
    once: every record keeps an offset of its own in its partition and a
    ``seq`` of its own in the topic."""
    import sys
    import threading

    topic = Topic("shared", 3)
    rounds, block = 200, 17

    def one_by_one(name):
        for i in range(rounds):
            topic.produce(Record(key=f"{name}{i}", value="v", timestamp=i, partition=-1))

    def in_blocks(name):
        for i in range(rounds):
            keys = [f"{name}{i}.{j}" if j % 5 else None for j in range(block)]
            topic.produce_block(keys, ["v"] * block, [i] * block, [None] * block)

    workers = [threading.Thread(target=fn, args=(f"t{n}-",))
               for n, fn in enumerate([one_by_one, in_blocks] * 4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    total = 4 * rounds * (1 + block)
    assert topic._seq == total == sum(topic.end_offsets())
    assert sorted(r.seq for r in topic.all_records()) == list(range(total))
    for p, part in enumerate(topic.partitions):
        assert [(r.partition, r.offset) for r in part] == [(p, o) for o in range(len(part))]
