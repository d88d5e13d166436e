"""A step's read-back is one transfer (ISSUE 31): the host copies of a
step's emits are started behind the step at dispatch, ``_finish_step``
collects them in one ``jax.device_get``, and the load check, the join's
counts and the row decoder work on host values.  Held here, on the CPU at
small shapes:

(a) the single read gives the same ``SinkEmit``s — key, row, ``ts``,
    window, order, block by block — and the same sink records as the read
    a leaf at a time that it replaced, over eight query shapes, at batch
    row counts from one row to a full batch, pipelined and per record, and
    nothing on the way is handed a device value;
(b) attached members, an armed raw emit block and an EMIT FINAL step are
    read once a step too, the EMIT FINAL step without its emit columns;
(c) ``d2h_bytes`` is the bytes of the pytree that was read;
(d) a store overflow and a DECIMAL sum past its exact envelope still raise.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import numpy as np
import pytest

from ksql_tpu.common import config as cfg
from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.common.errors import QueryRuntimeException
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.runtime.topics import Record

CAPACITY = 2048


def _engine(props=None, capacity=CAPACITY):
    base = {
        cfg.RUNTIME_BACKEND: "device",
        cfg.BATCH_CAPACITY: capacity,
        cfg.STATE_SLOTS: 8192,
    }
    base.update(props or {})
    return KsqlEngine(KsqlConfig(base))


def _poll(e):
    while e.poll_once(max_records=1 << 16):
        pass


def _feed(e, topic, make, counts):
    """``counts[j]`` records in poll j: a batch of that many rows."""
    t, i = e.broker.topic(topic), 0
    for n in counts:
        for _ in range(n):
            t.produce(make(i))
            i += 1
        _poll(e)


# ------------------------------------------------------------ the shapes
VIEWS = (
    "CREATE STREAM PAGE_VIEWS (URL STRING, USER_ID BIGINT, LATENCY DOUBLE) "
    "WITH (KAFKA_TOPIC='page_views', VALUE_FORMAT='JSON');"
)
LINES = (
    "CREATE STREAM L (K STRING KEY, A BIGINT, X DOUBLE, S STRING) "
    "WITH (kafka_topic='lines', value_format='JSON');"
)
SUMS = "CREATE STREAM S (ID BIGINT, V BIGINT) WITH (kafka_topic='s', value_format='JSON');"
HOPPING = (
    "CREATE TABLE H AS SELECT URL, COUNT(*) AS CNT, SUM(USER_ID) AS SU FROM PAGE_VIEWS "
    "WINDOW HOPPING (SIZE 4 SECONDS, ADVANCE BY 2 SECONDS, GRACE PERIOD 20 SECONDS) "
    "GROUP BY URL EMIT CHANGES;"
)


def _view(i, span_ms=3_600_000, per_window=1500):
    return Record(key=None, timestamp=span_ms * (i // per_window) + i, value=json.dumps(
        {"URL": f"/u{i * 7 % 61}", "USER_ID": i % 13, "LATENCY": (i * 37 % 101) / 4}))


def _hop_view(i):
    return Record(key=None, timestamp=3 * i, value=json.dumps(
        {"URL": f"/u{i * 7 % 11}", "USER_ID": i % 13, "LATENCY": 1.0}))


def _session_view(i):
    # bursts of a key 1 s apart, a 30 s pause every 40 records: sessions merge and close
    return Record(key=None, timestamp=1000 * i + 30_000 * (i // 40), value=json.dumps(
        {"URL": f"/u{i % 5}", "USER_ID": i % 13, "LATENCY": 1.0}))


def _line(i):
    return Record(key=f"k{i % 5}" if i % 9 else None, timestamp=2000 + i, value=json.dumps(
        {"A": i, "X": i * 0.25, "S": f'row "{i}"'}))


def _sum(i):
    # 256 records up, 256 down: from batch to batch a key's sum falls to or
    # under 0 and comes back, and HAVING writes a tombstone each time
    return Record(key=None, timestamp=i, value=json.dumps(
        {"ID": i % 7, "V": -4 if i // 256 % 2 else 3}))


def _pageview(i):
    return Record(key=None, timestamp=10 * i + 1,
                  value=f"{10 * i + 1},User_{i * 3 % 10},Page_{i % 90 + 10}")


def _load_users(e):
    users = e.broker.topic("users")
    for i in range(40):
        users.produce(Record(key=f"User_{i % 10}", timestamp=i, value=json.dumps(
            {"REGISTERTIME": 1_500_000_000_000 + i, "GENDER": ("FEMALE", "MALE", "OTHER")[i % 3],
             "REGIONID": f"Region_{i % 9 + 1}"})))
    _poll(e)


@dataclasses.dataclass
class Shape:
    statements: list
    topic: str
    make: object
    sink: str
    props: dict = dataclasses.field(default_factory=dict)
    before: object = None  # set-up before the first batch
    capacity: int = CAPACITY


SHAPES = {
    "tumbling_count": Shape(
        [VIEWS, "CREATE TABLE PV_COUNTS AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
                "WINDOW TUMBLING (SIZE 1 HOUR) GROUP BY URL EMIT CHANGES;"],
        "page_views", _view, "PV_COUNTS"),
    "hopping_expansion": Shape(
        [VIEWS, HOPPING], "page_views", _hop_view, "H",
        props={cfg.SLICING_ENABLE: False}),
    "hopping_sliced": Shape([VIEWS, HOPPING], "page_views", _hop_view, "H"),
    "filter_projection": Shape(
        [LINES, "CREATE STREAM LO WITH (kafka_topic='lines_out') AS "
                "SELECT K, A * 2 AS A2, S FROM L WHERE X > 3.0;"],
        "lines", _line, "lines_out"),
    "stream_table_join": Shape(
        ["CREATE STREAM PAGEVIEWS_ORIGINAL (VIEWTIME BIGINT, USERID VARCHAR, PAGEID VARCHAR) "
         "WITH (KAFKA_TOPIC='pageviews', VALUE_FORMAT='DELIMITED');",
         "CREATE TABLE USERS_ORIGINAL (USERID VARCHAR PRIMARY KEY, REGISTERTIME BIGINT, "
         "GENDER VARCHAR, REGIONID VARCHAR) WITH (KAFKA_TOPIC='users', VALUE_FORMAT='JSON');",
         "CREATE STREAM PAGEVIEWS_FEMALE AS SELECT USERS_ORIGINAL.USERID AS USERID, VIEWTIME, "
         "PAGEID, REGIONID, GENDER FROM PAGEVIEWS_ORIGINAL LEFT JOIN USERS_ORIGINAL ON "
         "PAGEVIEWS_ORIGINAL.USERID = USERS_ORIGINAL.USERID WHERE GENDER = 'FEMALE' EMIT CHANGES;"],
        "pageviews", _pageview, "PAGEVIEWS_FEMALE", before=_load_users),
    "having_tombstones": Shape(
        [SUMS, "CREATE TABLE C AS SELECT ID, SUM(V) AS SV FROM S GROUP BY ID "
               "HAVING SUM(V) > 0 EMIT CHANGES;"],
        "s", _sum, "C"),
    "array_and_map_aggregate": Shape(  # 2-D emit columns: (lanes, 3) and (lanes, 1000)
        [VIEWS, "CREATE TABLE V AS SELECT URL, TOPK(LATENCY, 3) AS TK, HISTOGRAM(URL) AS HG "
                "FROM PAGE_VIEWS GROUP BY URL EMIT CHANGES;"],
        "page_views", _view, "V", capacity=1024),
    "session_aggregate": Shape(
        [VIEWS, "CREATE TABLE SS AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
                "WINDOW SESSION (10 SECONDS) GROUP BY URL EMIT CHANGES;"],
        "page_views", _session_view, "SS", capacity=512),
}


def _batch_rows(capacity):
    """One row, both sides of a 256-lane chunk's edge, an eighth of the
    lanes (4,096 of 32,768) and a full batch."""
    return [1, 255, 256, 257, capacity // 8, capacity]


def _leaf_by_leaf(dev):
    """``_finish_step`` as it was before PR 31: the load check, the join's
    counts and the decoder each read the device leaves they want."""

    def finish(emits, react):
        jax.block_until_ready(emits)
        if react:
            dev._react_to_load(emits)
        dev._note_join_stats(emits)
        dev._deliver_members(emits)
        return dev._decode_emits(emits)

    return finish


def _is_host(emits):
    return all(isinstance(v, (np.ndarray, np.generic)) for v in emits.values())


def _run(shape: Shape, per_record=False, leaf_by_leaf=False, counts=None):
    """One engine run.  The emission blocks the executor dispatched, the
    sink's records, the ``emit.decode`` stage, and whether every consumer
    of a step's read-back was handed host values."""
    props = dict(shape.props)
    if per_record:
        props[cfg.EMIT_CHANGES_PER_RECORD] = True
    e = _engine(props, shape.capacity)
    try:
        for s in shape.statements:
            e.execute_sql(s)
        h = list(e.queries.values())[-1]
        assert h.backend == "device"
        ex, dev = h.executor, h.executor.device
        handed = []
        if leaf_by_leaf:
            dev._finish_step = _leaf_by_leaf(dev)
        else:
            for name in ("_react_to_load", "_note_join_stats", "_decode_emits"):
                def spy(emits, *a, _real=getattr(dev, name), **kw):
                    handed.append(_is_host(emits))
                    return _real(emits, *a, **kw)
                setattr(dev, name, spy)
        blocks, dispatch = [], ex._dispatch

        def note(emits):
            blocks.append([dataclasses.astuple(x) for x in emits])
            dispatch(emits)

        ex._dispatch = note
        if shape.before is not None:
            shape.before(e)
        if counts is None:
            counts = [1, 40, 3] if per_record else _batch_rows(shape.capacity)
        _feed(e, shape.topic, shape.make, counts)
        assert h.state == "RUNNING", h.state
        stage = e.trace_recorders[h.query_id].stage_stats().get("emit.decode", {})
        sink = [dataclasses.astuple(r) for r in e.broker.topic(shape.sink).all_records()]
        return [b for b in blocks if b], sink, stage, handed
    finally:
        e.shutdown()


# ------------------------------------- (a) one read == a read a leaf
@pytest.mark.parametrize("per_record", [False, True], ids=["pipelined", "per_record"])
@pytest.mark.parametrize("name", list(SHAPES))
def test_single_read_equals_leaf_by_leaf_read(name, per_record):
    shape = SHAPES[name]
    blocks, sink, stage, handed = _run(shape, per_record)
    old_blocks, old_sink, old_stage, _ = _run(shape, per_record, leaf_by_leaf=True)
    assert sum(map(len, blocks)) > 0
    if name == "having_tombstones" and not per_record:
        assert any(row is None for b in blocks for _key, row, _ts, _window in b)
    assert blocks == old_blocks
    assert sink == old_sink
    assert handed and all(handed)
    # the same pytree crossed, whole
    assert stage["d2h_bytes"] == old_stage["d2h_bytes"] > 0


# ---------------------------------------------- (b) one read a step
def _count_reads(monkeypatch):
    """The pytrees ``jax.device_get`` is asked for."""
    reads, real = [], jax.device_get

    def device_get(tree):
        reads.append(tree)
        return real(tree)

    monkeypatch.setattr(jax, "device_get", device_get)
    return reads


def _step_reads(reads):
    return [r for r in reads if isinstance(r, dict) and ("emit_mask" in r or "suppress_emit" in r)]


FAMILY = [("W1", 4, 2), ("W2", 8, 2)]
PREFIX_MEMBERS = [
    "CREATE STREAM P1 AS SELECT URL, USER_ID, LATENCY FROM PAGE_VIEWS WHERE LATENCY > 10 EMIT CHANGES;",
    "CREATE STREAM P2 AS SELECT URL, LATENCY FROM PAGE_VIEWS WHERE LATENCY > 10 AND USER_ID > 3 "
    "EMIT CHANGES;",
]


@pytest.mark.parametrize("kind", ["window_family", "source_prefix"])
def test_attached_members_are_read_with_their_step(kind, monkeypatch):
    e = _engine(capacity=1024)
    try:
        e.execute_sql(VIEWS)
        if kind == "window_family":
            statements = [
                f"CREATE TABLE {n} AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS WINDOW HOPPING "
                f"(SIZE {size} SECONDS, ADVANCE BY {adv} SECONDS, GRACE PERIOD 20 SECONDS) "
                "GROUP BY URL EMIT CHANGES;" for n, size, adv in FAMILY]
        else:
            statements = PREFIX_MEMBERS
        qids = [next(x.query_id for x in e.execute_sql(s) if x.query_id) for s in statements]
        dev = e.queries[qids[0]].executor.device
        assert dev.shared_member_ids() + dev.shared_prefix_member_ids() == qids[1:]
        handed, decode = [], dev._decode_emits

        def spy(emits, *a, **kw):
            handed.append(_is_host(emits))
            return decode(emits, *a, **kw)

        dev._decode_emits = spy
        reads = _count_reads(monkeypatch)
        _feed(e, "page_views", _view if kind == "source_prefix" else _hop_view, [300, 40])
        step_reads = _step_reads(reads)
        assert len(step_reads) == 2
        assert any(k.startswith(("fam:", "pfx:")) for k in step_reads[0])
        # the primary and its member decode a step each, from host values
        assert len(handed) == 4 and all(handed)
        for qid in qids:
            sink = e.queries[qid].plan.physical_plan.topic
            assert e.broker.topic(sink).all_records(), qid
    finally:
        e.shutdown()


def test_raw_emit_block_is_read_once_and_gathers_on_the_device(monkeypatch):
    shape = SHAPES["filter_projection"]
    e = _engine(capacity=1024)
    try:
        for s in shape.statements:
            e.execute_sql(s)
        h = list(e.queries.values())[-1]
        dev = h.executor.device
        dev.collect_raw_emits = True
        blocks = []
        h.executor.batch_emit_callback = lambda emits: blocks.append(
            (len(emits), dev.last_raw_block))
        reads = _count_reads(monkeypatch)
        _feed(e, shape.topic, shape.make, [300, 40])
        assert len(_step_reads(reads)) == 2
        assert [n for n, _ in blocks] == [b["n"] for _, b in blocks]
        for _, block in blocks:  # gathered on the device, row-aligned with the emits
            assert all(isinstance(d, jax.Array) for d, _ in block["cols"].values())
            assert block["ts"].shape == (block["n"],)
    finally:
        e.shutdown()


def test_emit_final_step_reads_its_mask_and_scalars_once(monkeypatch):
    e = _engine(capacity=1024)
    try:
        e.execute_sql(VIEWS)
        e.execute_sql(
            "CREATE TABLE F AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS WINDOW TUMBLING "
            "(SIZE 1 SECONDS, GRACE PERIOD 0 SECONDS) GROUP BY URL EMIT FINAL;")
        h = list(e.queries.values())[-1]
        assert h.backend == "device" and h.executor.device.suppress
        reads = _count_reads(monkeypatch)
        # a window closes when a record's time reaches its end: at rows 250 and 500
        _feed(e, "page_views", lambda i: dataclasses.replace(_hop_view(i), timestamp=4 * i),
              [300, 400])
        step_reads = _step_reads(reads)
        assert len(step_reads) == 2
        for read in step_reads:
            # the rows come from the store: no emit column crosses
            assert {k for k, v in read.items() if v.ndim} == {"suppress_emit"}
            assert {"overflow", "occupancy"} <= set(read)
        assert e.broker.topic("F").all_records()
    finally:
        e.shutdown()


# ----------------------------------------- (c) the bytes that were moved
def test_d2h_bytes_are_the_bytes_read(monkeypatch):
    shape = dataclasses.replace(SHAPES["tumbling_count"], capacity=1024)
    reads, real = [], jax.device_get

    def device_get(tree):
        host = real(tree)
        reads.append(sum(v.nbytes for v in host.values()))
        return host

    monkeypatch.setattr(jax, "device_get", device_get)
    _, _, stage, _ = _run(shape, counts=[100, 300, 1024, 600])
    assert len(reads) == 4 and len(set(reads)) == 1
    assert stage["d2h_bytes"] == sum(reads)
    # 1,024 lanes of five int64 and three bool columns, and the scalars
    assert reads[0] > 1024 * 43


# ------------------------------------------------- (d) the loud failures
def test_store_overflow_still_raises():
    e = _engine({cfg.STATE_SLOTS: 8192})
    try:
        for s in SHAPES["tumbling_count"].statements:
            e.execute_sql(s)
        dev = list(e.queries.values())[-1].executor.device
        dev.pipeline = False
        _feed(e, "page_views", _view, [10])
        state = dict(dev.state)
        state["overflow"] = state["overflow"] + 7  # as a step that lost 7 rows leaves it
        dev.state = state
        arrays = dev.layout.encode(_host_batch(dev, 5))
        with pytest.raises(QueryRuntimeException, match=r"overflowed \(7 rows lost\)"):
            dev.process_arrays(arrays)
    finally:
        e.shutdown()


def _host_batch(dev, n):
    from ksql_tpu.common.batch import HostBatch

    rows = [{"URL": f"/u{i}", "USER_ID": i, "LATENCY": 1.0} for i in range(n)]
    return HostBatch.from_rows(dev.source.schema, rows, timestamps=list(range(n)))


def test_decimal_envelope_still_raises(monkeypatch):
    e = _engine(capacity=1024)
    try:
        e.execute_sql("CREATE STREAM D (ID BIGINT, AMT DECIMAL(12, 2)) "
                      "WITH (kafka_topic='d', value_format='JSON');")
        e.execute_sql("CREATE TABLE DS AS SELECT ID, SUM(AMT) AS SA FROM D GROUP BY ID EMIT CHANGES;")
        h = list(e.queries.values())[-1]
        assert h.backend == "device"
        dev = h.executor.device
        raised, finish, real = [], dev._finish_step, jax.device_get

        def drifted(tree):
            # as a step whose emitted sum passed the exact envelope reports it
            host = real(tree)
            if isinstance(host, dict) and "dec_envelope" in host:
                host["dec_envelope"] = host["dec_envelope"] + 1
            return host

        def spy(emits, react):
            try:
                return finish(emits, react)
            except QueryRuntimeException as exc:
                raised.append(str(exc))
                raise

        monkeypatch.setattr(jax, "device_get", drifted)
        dev._finish_step = spy
        _feed(e, "d", lambda i: Record(key=None, timestamp=i, value=json.dumps(
            {"ID": i % 3, "AMT": "1.25"})), [300])
        assert raised and "2^53-exact envelope" in raised[0]
    finally:
        e.shutdown()
