"""The tick hands the executor a poll's records as a block (ISSUE 33): a
run of one topic's records that ``DeviceExecutor.buffer_block`` says it
only buffers crosses in one call, with one run of commit-cursor entries,
and the per-record loop of ``KsqlEngine._poll_query`` keeps everything
else.  Held here, on the CPU at small shapes:

1. the block path and the loop (the entry hidden from the engine) leave the
   same sink records in the same order, the same commit cursor at every
   device step and after every tick, the same consumer positions, nothing
   pending after the drain, and the same ``replayed_records`` after a
   failure in a flush between two blocks;
2. each condition that wants every record looked at takes the loop, and the
   ``process`` stage's ``block_rows`` says so.
"""

from __future__ import annotations

import dataclasses
import json

import pytest

from ksql_tpu.common import config as cfg
from ksql_tpu.common import faults
from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.runtime.device_executor import DeviceExecutor
from ksql_tpu.runtime.topics import Record


@pytest.fixture(autouse=True)
def _disarm():
    faults.clear()
    yield
    faults.clear()


VIEWS = (
    "CREATE STREAM PAGE_VIEWS (URL STRING, USER_ID BIGINT, VIEWTIME BIGINT) "
    "WITH (KAFKA_TOPIC='page_views', VALUE_FORMAT='JSON');"
)
COUNTS = (
    "CREATE TABLE PV_COUNTS AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
    "WINDOW TUMBLING (SIZE 1 HOUR) GROUP BY URL EMIT CHANGES;"
)
#: the quickstart's join (benchmark/configs/clicks_users_join.json): its
#: stream side decodes per record in Python
JOIN = [
    "CREATE STREAM PAGEVIEWS_ORIGINAL (VIEWTIME BIGINT, USERID VARCHAR, PAGEID VARCHAR) "
    "WITH (KAFKA_TOPIC='pageviews', VALUE_FORMAT='DELIMITED');",
    "CREATE TABLE USERS_ORIGINAL (USERID VARCHAR PRIMARY KEY, REGISTERTIME BIGINT, "
    "GENDER VARCHAR, REGIONID VARCHAR) WITH (KAFKA_TOPIC='users', VALUE_FORMAT='JSON');",
    "CREATE STREAM PAGEVIEWS_FEMALE AS SELECT USERS_ORIGINAL.USERID AS USERID, VIEWTIME, "
    "PAGEID, REGIONID, GENDER FROM PAGEVIEWS_ORIGINAL LEFT JOIN USERS_ORIGINAL ON "
    "PAGEVIEWS_ORIGINAL.USERID = USERS_ORIGINAL.USERID WHERE GENDER = 'FEMALE' EMIT CHANGES;",
]


def _view(i, value=...):
    if value is ...:
        value = json.dumps({"URL": f"/u{i * 7 % 23}", "USER_ID": i, "VIEWTIME": 1000 + i})
    return ("page_views", Record(key=None, timestamp=3_600_000 * (i // 250) + i, value=value))


def _pageview(i, value=...):
    if value is ...:
        value = f"{10 * i + 1},User_{i * 3 % 10},Page_{i % 90 + 10}"
    return ("pageviews", Record(key=None, timestamp=1000 + i, value=value))


def _user(i):
    return ("users", Record(key=f"User_{i % 10}", timestamp=i, value=json.dumps(
        {"REGISTERTIME": 1_500_000_000_000 + i, "GENDER": ("FEMALE", "MALE", "OTHER")[i % 3],
         "REGIONID": f"Region_{i % 9 + 1}"})))


def _views(n, polls=3, special=None):
    """``polls`` polls of ``n`` views; ``special`` maps an index to a payload."""
    special = special or {}
    return [[_view(i, special.get(i, ...)) for i in range(p * n, (p + 1) * n)]
            for p in range(polls)]


@dataclasses.dataclass
class Case:
    statements: list
    sink: str
    polls: list  # of lists of (topic, Record): produced, then one poll_once
    props: dict = dataclasses.field(default_factory=dict)
    setup: object = None  # (engine, handle) -> None, before the first poll
    fail_at_step: int = 0  # the device step that raises, once (1-based)
    #: what the block twin's ``process`` stage must read: "all", "some", "none"
    blocks: str = "all"
    backend: str = "device"


def _run(case: Case, monkeypatch, block: bool):
    """One engine run of a case: every comparable thing it left, and the
    ``process`` stage's counters.  ``block`` false hides the entry from
    the engine (on the class: a restarted query's executor lacks it too)."""
    props = {
        cfg.RUNTIME_BACKEND: "device",
        cfg.BATCH_CAPACITY: 1024,
        cfg.STATE_SLOTS: 4096,
        cfg.QUERY_RETRY_BACKOFF_INITIAL_MS: 0,
        cfg.QUERY_RETRY_BACKOFF_MAX_MS: 0,
        **case.props,
    }
    state = {"steps": 0, "cursor_at_step": []}
    real_step = DeviceExecutor._device_step

    def device_step(self, fn, *args, **kw):
        # where the commit cursor stands when a micro-batch reaches the
        # device, mid-tick or in the drain; and the injected failure
        state["steps"] += 1
        handle = state.get("handle")
        if handle is not None:
            state["cursor_at_step"].append(sorted(handle.commit_positions.items()))
        if state["steps"] == case.fail_at_step:
            raise RuntimeError("injected: the device step failed")
        return real_step(self, fn, *args, **kw)

    with monkeypatch.context() as m:
        m.setattr(DeviceExecutor, "_device_step", device_step)
        if not block:
            m.delattr(DeviceExecutor, "buffer_block")
        e = KsqlEngine(KsqlConfig(props))
        try:
            for s in case.statements:
                e.execute_sql(s)
            h = list(e.queries.values())[-1]
            assert h.backend == case.backend
            if case.setup is not None:
                case.setup(e, h)
            state["handle"] = h
            ticks = []
            for poll in case.polls:
                for topic, record in poll:
                    e.broker.topic(topic).produce(record)
                e.poll_once(max_records=100_000)
                ticks.append((sorted(h.commit_positions.items()),
                              sorted(h.consumer.positions.items()),
                              h.replayed_records, h.state))
            e.run_until_quiescent()
            pending = getattr(h.executor, "pending_records", lambda: 0)()
            stage = e.trace_recorders[h.query_id].stage_stats()["process"]
            seen = {
                "sink": [dataclasses.astuple(r) for r in e.broker.topic(case.sink).all_records()],
                "ticks": ticks,
                "cursor_at_step": state["cursor_at_step"],
                "commit": sorted(h.commit_positions.items()),
                "positions": sorted(h.consumer.positions.items()),
                "replayed_records": h.replayed_records,
                "state": h.state,
                "pending": pending,
                "materialized": dict(h.materialized),
                "rows": stage["rows"],
            }
            return seen, stage
        finally:
            e.shutdown()


def _faults_armed(e, h):
    # armed, and never fired: no topic of that name is read
    faults.install([faults.FaultRule(point="topic.read", match="no-such-topic")])


def _poison_skip(e, h):
    h.poison_skip.add(("page_views", 0, 7))


def _poison_bisect(e, h):
    h.poison_bisect = {"limit": 50}


def _wrapped_process(e, h):
    real = h.executor.process
    h.executor.process = lambda topic, record: real(topic, record)


CASES = {
    # one poll is one block: 200 records against a capacity of 1,024
    "native_one_block": Case([VIEWS, COUNTS], "PV_COUNTS", _views(200)),
    # a block ends before every record that fills the micro-batch: that
    # record flushes through the loop, and the cursor moves mid-tick
    "native_capacity_under_poll": Case(
        [VIEWS, COUNTS], "PV_COUNTS", _views(200), props={cfg.BATCH_CAPACITY: 64},
        blocks="some"),
    # a tombstone and a payload the C++ tier cannot take, mid-poll; then two
    # tombstones in a row: the second offer takes nothing and the run ends
    # in the loop
    "native_non_string_payloads": Case(
        [VIEWS, COUNTS], "PV_COUNTS",
        _views(120, special={50: None, 300: None, 301: None,
                             170: {"URL": "/dict", "USER_ID": 1, "VIEWTIME": 2},
                             200: "{not json"}),
        blocks="some"),
    # the join's two topics in one poll: a run each, the table side through
    # the loop (CHANGES.md, PR 33), the stream side as a block
    "two_topics_in_one_poll": Case(
        JOIN, "PAGEVIEWS_FEMALE",
        [[_user(i) for i in range(30)] + [_pageview(i) for i in range(80)],
         [_pageview(i) for i in range(80, 160)] + [_user(i) for i in range(30, 40)],
         [_pageview(i) for i in range(160, 200)]],
        blocks="some"),
    # the Python tier, its blocks cut by the capacity; a record dropped at
    # decode and a null-value record ride inside a block
    "python_tier_join_stream_side": Case(
        JOIN, "PAGEVIEWS_FEMALE",
        [[_user(i) for i in range(40)]]
        + [[_pageview(i, {p * 150 + 20: "not,a,number,at,all", p * 150 + 90: None}.get(i, ...))
            for i in range(p * 150, (p + 1) * 150)] for p in range(3)],
        props={cfg.BATCH_CAPACITY: 64}, blocks="some"),
    # the mesh executor inherits the entry (four of conftest.py's devices)
    "mesh_executor": Case(
        [VIEWS, COUNTS], "PV_COUNTS", _views(300),
        props={cfg.RUNTIME_BACKEND: "distributed", cfg.DEVICE_SHARDS: 4,
               cfg.BATCH_CAPACITY: 2048},
        backend="distributed"),
    # a flush between two blocks fails: the rewind replays from the cursor
    # the earlier flushes left
    "failure_in_a_mid_block_flush": Case(
        [VIEWS, COUNTS], "PV_COUNTS", _views(200), props={cfg.BATCH_CAPACITY: 64},
        fail_at_step=5, blocks="some"),
    # ---- what keeps the loop
    "declines_faults_armed": Case(
        [VIEWS, COUNTS], "PV_COUNTS", _views(200), setup=_faults_armed, blocks="none"),
    "declines_poison_skip": Case(
        [VIEWS, COUNTS], "PV_COUNTS", _views(200, polls=1), setup=_poison_skip, blocks="none"),
    "declines_poison_bisect": Case(
        [VIEWS, COUNTS], "PV_COUNTS", _views(40, polls=1), setup=_poison_bisect, blocks="none"),
    "declines_wrapped_process": Case(
        [VIEWS, COUNTS], "PV_COUNTS", _views(200), setup=_wrapped_process, blocks="none"),
    "declines_epoch_capable_oracle": Case(
        [VIEWS, COUNTS], "PV_COUNTS", _views(60), props={cfg.RUNTIME_BACKEND: "oracle"},
        backend="oracle", blocks="none"),
    "declines_per_record_device": Case(
        [VIEWS, COUNTS], "PV_COUNTS", _views(40),
        props={cfg.EMIT_CHANGES_PER_RECORD: True}, blocks="none"),
}


@pytest.mark.parametrize("name", list(CASES))
def test_block_hand_over_equals_the_per_record_loop(name, monkeypatch):
    case = CASES[name]
    block, stage = _run(case, monkeypatch, block=True)
    assert stage["rows"] > 0
    if case.blocks == "all":
        assert stage["block_rows"] == stage["rows"]
    elif case.blocks == "some":
        assert 0 < stage["block_rows"] < stage["rows"]
    else:
        assert stage["block_rows"] == 0
    loop, stage = _run(case, monkeypatch, block=False)
    assert stage["block_rows"] == 0
    for key, value in loop.items():
        assert block[key] == value, key
    assert block["sink"] and block["pending"] == 0 and block["state"] == "RUNNING"
    assert block["commit"] == block["positions"]
    if case.fail_at_step:
        assert block["replayed_records"] > 0
        assert any(state == "ERROR" for *_, state in block["ticks"])
    else:
        assert block["replayed_records"] == 0
    if name == "native_capacity_under_poll":
        # the cursor moved inside a tick, between two blocks
        moved = {tuple(c) for c in block["cursor_at_step"]}
        assert len(moved) > len(case.polls) + 1


def test_buffer_block_takes_only_what_process_would_only_buffer():
    """The entry on its own: how many records it takes, and from where the
    caller goes on."""
    e = KsqlEngine(KsqlConfig({cfg.RUNTIME_BACKEND: "device", cfg.BATCH_CAPACITY: 8,
                               cfg.STATE_SLOTS: 1024}))
    try:
        for s in (VIEWS, COUNTS):
            e.execute_sql(s)
        ex = list(e.queries.values())[-1].executor
        records = [_view(i)[1] for i in range(20)]
        assert ex.buffer_block("some_other_topic", records) == 0
        # room for capacity - 1: the eighth record is process()'s, and flushes
        assert ex.buffer_block("page_views", records) == 7
        assert ex.pending_records() == 7 and ex.buffer_block("page_views", records[7:]) == 0
        ex.process("page_views", records[7])
        pending = ex.pending_records()  # the pipeline holds the step's emits
        assert len(ex._raw) == 0
        tombstone = dataclasses.replace(records[10], value=None)
        assert ex.buffer_block("page_views", [records[8], records[9], tombstone, records[11]]) == 2
        assert ex.pending_records() == pending + 2
        assert ex.buffer_block("page_views", [tombstone, records[11]]) == 0
        # rows the Python tier decoded are waiting: the order must hold
        ex._rows.append({"URL": "/x", "USER_ID": 1, "VIEWTIME": 2})
        assert ex.buffer_block("page_views", records[12:]) == 0
    finally:
        e.shutdown()
