"""Flight recorder / tracing tests (ISSUE 3 tentpole): per-backend stage
names and nesting, the device compile-vs-execute split, distributed
exchange accounting, the crash-dump path into the processing log, EXPLAIN
ANALYZE output shape, the Prometheus exposition of /metrics, and the new
observability fault points (schema registry lookups, HTTP peer
forwarding)."""

import gc
import json
import re
import threading
import time
import urllib.request

import pytest

from ksql_tpu.common import config as cfg
from ksql_tpu.common import faults, tracing
from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.runtime.topics import Record


@pytest.fixture(autouse=True)
def _disarm():
    faults.clear()
    yield
    faults.clear()


def _engine(extra=None):
    return KsqlEngine(KsqlConfig(dict(extra or {})))


def _feed(e, topic="pv", n=12):
    t = e.broker.topic(topic)
    for i in range(n):
        t.produce(Record(
            key=None, value=json.dumps({"URL": f"/p{i % 3}", "V": i}),
            timestamp=i,
        ))
    e.run_until_quiescent()


PV_DDL = (
    "CREATE STREAM PV (URL STRING, V BIGINT) "
    "WITH (kafka_topic='pv', value_format='JSON');"
)


# -------------------------------------------------------------- per backend
def test_oracle_stage_names():
    e = _engine({cfg.RUNTIME_BACKEND: "oracle"})
    e.execute_sql(PV_DDL)
    e.execute_sql(
        "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV "
        "GROUP BY URL EMIT CHANGES;"
    )
    _feed(e)
    qid = list(e.queries)[0]
    stats = e.trace_recorder(qid).stage_stats()
    assert {"poll", "deserialize", "sink.produce"} <= set(stats)
    # per-ExecutionStep stages carry the node ctx names
    assert any(name.startswith("stage:") for name in stats)
    assert "stage:Aggregate" in stats
    # oracle queries never touch the device: no compile/execute split
    assert not any(name.startswith("device.") for name in stats)
    assert stats["deserialize"]["n"] == 12
    for st in stats.values():
        assert st["p50_ms"] is not None and st["p99_ms"] >= st["p50_ms"] >= 0


def test_device_compile_execute_split_and_nesting():
    e = _engine({cfg.RUNTIME_BACKEND: "device-only"})
    e.execute_sql(PV_DDL)
    e.execute_sql(
        "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV "
        "GROUP BY URL EMIT CHANGES;"
    )
    _feed(e, n=16)
    qid = list(e.queries)[0]
    assert e.queries[qid].backend == "device"
    rec = e.trace_recorder(qid)
    stats = rec.stage_stats()
    # the first tick jit-compiles, later dispatches hit the cache
    assert stats["device.compile"]["jit_miss"] >= 1
    assert stats["device.execute"]["jit_hit"] >= 1
    # bytes ride the stage of the span that moves them
    assert stats["step.dispatch"]["h2d_bytes"] > 0
    assert stats["emit.decode"]["d2h_bytes"] > 0
    assert "device.transfer" not in stats
    # span nesting: device steps run INSIDE the process/drain spans
    tk = rec.recent(1)[0]
    depths = {s["name"]: s["depth"] for s in tk["spans"]}
    assert depths["poll"] == 0
    dev_spans = [s for s in tk["spans"] if s["name"].startswith("device.")]
    assert dev_spans and all(s["depth"] >= 1 for s in dev_spans)
    assert tk["status"] == "OK" and tk["durMs"] >= 0


def test_distributed_stages_and_exchange_bytes():
    e = _engine({cfg.RUNTIME_BACKEND: "distributed"})
    e.execute_sql(PV_DDL)
    e.execute_sql(
        "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV "
        "GROUP BY URL EMIT CHANGES;"
    )
    qid = list(e.queries)[0]
    assert e.queries[qid].backend == "distributed", e.fallback_reasons
    _feed(e, n=32)
    _feed(e, n=32)  # second tick hits the jit cache -> device.execute
    stats = e.trace_recorder(qid).stage_stats()
    assert stats["device.compile"]["jit_miss"] >= 1
    # rows crossed the all-to-all to their key-owner shard
    assert stats["exchange"]["rows"] > 0
    assert stats["exchange"]["bytes"] > 0
    assert stats["step.dispatch"]["h2d_bytes"] > 0
    # EXPLAIN ANALYZE surfaces the same split + exchange volume (the
    # acceptance-criteria table)
    r = e.execute_sql(f"EXPLAIN ANALYZE {qid};")[0]
    assert r.columns == ["stage", "count", "p50Ms", "p99Ms", "totalMs", "extra"]
    by_stage = {row["stage"]: row for row in r.rows}
    assert "device.compile" in by_stage and "device.execute" in by_stage
    assert "bytes" in by_stage["exchange"]["extra"]
    assert "Runtime: distributed" in r.message and "shards=" in r.message


def test_trace_disable_is_honored():
    e = _engine({cfg.RUNTIME_BACKEND: "oracle", cfg.TRACE_ENABLE: "false"})
    e.execute_sql(PV_DDL)
    e.execute_sql("CREATE STREAM O AS SELECT URL FROM PV;")
    _feed(e)
    qid = list(e.queries)[0]
    assert e.trace_recorders == {}
    r = e.execute_sql(f"EXPLAIN ANALYZE {qid};")[0]
    assert r.rows == [] and "tracing disabled" in r.message


# ----------------------------------------------------------- crash dumping
def test_flight_recorder_dump_on_injected_crash():
    e = _engine({cfg.RUNTIME_BACKEND: "device-only"})
    e.execute_sql(PV_DDL)
    e.execute_sql("CREATE STREAM O AS SELECT URL, V + 1 AS W FROM PV;")
    handle = list(e.queries.values())[0]
    _feed(e, n=4)  # healthy ticks first
    e.broker.topic("pv").produce(
        Record(key=None, value=json.dumps({"URL": "/x", "V": 9}), timestamp=99)
    )
    with faults.inject("device.dispatch", match=handle.query_id, count=1):
        e.poll_once()
    assert handle.state == "ERROR"
    # the triggering tick's trace landed in the processing log as JSON
    dumps = [m for w, m in e.processing_log
             if w == f"trace:{handle.query_id}"]
    assert len(dumps) == 1  # dumped once, not re-dumped by later passes
    trace = json.loads(dumps[0])
    assert trace["status"] == "ERROR" and "FaultInjected" in trace["error"]
    assert any(s["name"] == "poll" for s in trace["spans"])
    # the dump serializes mid-tick: elapsed time is reported and the span
    # the crash happened INSIDE is included, marked still-open
    assert trace["durMs"] > 0
    assert any(
        s["name"] == "process" and s.get("open") for s in trace["spans"]
    )
    # ...and the ring retains it for post-mortem
    last = e.trace_recorder(handle.query_id).recent(1)[0]
    assert last["status"] == "ERROR"
    # the structured KSQL_PROCESSING_LOG stream carries it too
    plog = e.broker.topic("default_ksql_processing_log").all_records()
    assert any(
        f"trace:{handle.query_id}" == json.loads(r.value)["LOGGER"]
        for r in plog
    )


# ---------------------------------------------------------- EXPLAIN ANALYZE
def test_explain_analyze_shape_and_errors():
    e = _engine({cfg.RUNTIME_BACKEND: "oracle"})
    e.execute_sql(PV_DDL)
    e.execute_sql("CREATE STREAM O AS SELECT URL FROM PV;")
    _feed(e)
    qid = list(e.queries)[0]
    r = e.execute_sql(f"EXPLAIN ANALYZE {qid};")[0]
    assert r.kind == "rows"
    assert r.columns == ["stage", "count", "p50Ms", "p99Ms", "totalMs", "extra"]
    assert r.rows and r.rows[0]["stage"] == "poll"  # canonical stage order
    for row in r.rows:
        assert set(row) == set(r.columns)
        assert row["count"] >= 0 and row["totalMs"] >= 0
    assert "flight recorder window" in r.message
    from ksql_tpu.common.errors import KsqlException

    with pytest.raises(KsqlException, match="does not exist"):
        e.execute_sql("EXPLAIN ANALYZE NOPE_1;")
    with pytest.raises(KsqlException, match="running query id"):
        e.execute_sql("EXPLAIN ANALYZE SELECT * FROM PV;")


# --------------------------------------------------------------- Prometheus
_PROM_LINE = re.compile(
    r"^(?:# (?:HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[^{}]*\})? -?[0-9.eE+inf]+)$"
)


def _parse_prom(text):
    samples = {}
    for line in text.strip().splitlines():
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
        if line.startswith("#"):
            continue
        name_labels, value = line.rsplit(" ", 1)
        # the dedupe satellite: one sample per (name, labels) series — a
        # query that restarts and re-registers must not emit duplicates
        assert name_labels not in samples, f"duplicate series: {name_labels}"
        samples[name_labels] = float(value)
    return samples


def test_prometheus_exposition_and_counter_monotonicity():
    import urllib.request

    from ksql_tpu.server.rest import KsqlServer

    e = _engine({cfg.RUNTIME_BACKEND: "oracle"})
    e.execute_sql(PV_DDL)
    e.execute_sql(
        "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV "
        "GROUP BY URL EMIT CHANGES;"
    )
    _feed(e, n=6)
    qid = list(e.queries)[0]
    s = KsqlServer(engine=e, port=0)
    s.start()
    try:
        def scrape(how):
            if how == "accept":
                req = urllib.request.Request(
                    f"{s.url}/metrics", headers={"Accept": "text/plain"}
                )
            else:
                req = urllib.request.Request(
                    f"{s.url}/metrics?format=prometheus"
                )
            with urllib.request.urlopen(req) as r:
                assert r.headers["Content-Type"].startswith("text/plain")
                return r.read().decode()
        text = scrape("accept")
        first = _parse_prom(text)
        assert first["ksql_engine_messages_consumed_total"] == 6
        assert f'ksql_query_messages_consumed_total{{query="{qid}"}}' in first
        assert any(
            k.startswith("ksql_query_stage_latency_ms{")
            and 'stage="deserialize"' in k and 'quantile="0.5"' in k
            for k in first
        )
        assert any(
            k.startswith("ksql_query_stage_invocations_total{") for k in first
        )
        # more data -> every *_total counter is monotone non-decreasing
        _feed(e, n=5)
        second = _parse_prom(scrape("query-param"))
        for k, v in first.items():
            if "_total" in k.split("{")[0] and k in second:
                assert second[k] >= v, f"counter regressed: {k}"
        assert second["ksql_engine_messages_consumed_total"] == 11
        # the default (no Accept / no format) response stays JSON
        with urllib.request.urlopen(f"{s.url}/metrics") as r:
            body = json.loads(r.read())
        assert "engine" in body and "queries" in body
        # the satellite fix: cumulative total and windowed rate are separate
        assert body["engine"]["processing-errors-total"] == 0
        assert body["engine"]["error-rate"] == 0.0
    finally:
        s.stop()


def test_prometheus_label_escaping():
    from ksql_tpu.common.metrics import prometheus_text

    snap = {
        "engine": {"messages-consumed-total": 1},
        "queries": {'q"1\\x\n': {"messages-consumed-total": 1}},
    }
    text = prometheus_text(snap)
    line = next(
        ln for ln in text.splitlines()
        if ln.startswith("ksql_query_messages_consumed_total{")
    )
    assert '\\"' in line and "\\\\" in line and "\\n" in line
    assert "\n" not in line  # the newline itself never leaks into the line


def test_query_trace_endpoint():
    import urllib.error
    import urllib.request

    from ksql_tpu.server.rest import KsqlServer

    e = _engine({cfg.RUNTIME_BACKEND: "oracle"})
    e.execute_sql(PV_DDL)
    e.execute_sql("CREATE STREAM O AS SELECT URL FROM PV;")
    _feed(e)
    qid = list(e.queries)[0]
    s = KsqlServer(engine=e, port=0)
    s.start()
    try:
        with urllib.request.urlopen(f"{s.url}/query-trace/{qid}") as r:
            body = json.loads(r.read())
        assert body["queryId"] == qid and body["traceEnabled"] is True
        assert body["ticks"], "flight recorder should hold recent ticks"
        tick = body["ticks"][-1]
        assert {"spans", "stages", "status", "durMs"} <= set(tick)
        assert "poll" in tick["stages"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{s.url}/query-trace/NOPE_9")
        assert ei.value.code == 404
    finally:
        s.stop()


# ------------------------------------------------------- metrics satellites
def test_error_rate_is_windowed_not_cumulative():
    import time

    from ksql_tpu.common.metrics import MetricCollectors

    mc = MetricCollectors()
    qm = mc.for_query("Q_1")
    # 5 errors well outside the 30s rate window: the total remembers them,
    # the windowed rate has decayed to zero (the pre-fix code reported the
    # total under the "error-rate" name forever)
    qm.errors.mark(5, now=time.monotonic() - 120.0)
    snap = mc.snapshot()
    assert snap["engine"]["processing-errors-total"] == 5
    assert snap["engine"]["error-rate"] == 0.0
    qm.errors.mark(2)  # fresh errors DO show up in the rate
    snap = mc.snapshot()
    assert snap["engine"]["processing-errors-total"] == 7
    assert snap["engine"]["error-rate"] > 0.0
    assert snap["queries"]["Q_1"]["processing-errors-per-sec"] > 0.0


# ------------------------------------------------------ new fault points
def test_schema_registry_lookup_fault_point():
    e = _engine()
    e.schema_registry.register("t-value", "AVRO", {
        "type": "record", "name": "V",
        "fields": [{"name": "A", "type": "long"}],
    })
    with faults.inject("schema.registry.lookup", match="t-value", count=1) as rule:
        with pytest.raises(faults.FaultInjected):
            e.schema_registry.latest("t-value")
        assert e.schema_registry.latest("t-value") is not None
    assert rule.fired == 1
    # the schema-inference DDL path surfaces the outage to the caller
    # instead of silently creating a columnless source
    with faults.inject("schema.registry.lookup", match="t-value"):
        with pytest.raises(faults.FaultInjected):
            e.execute_sql(
                "CREATE STREAM T WITH (kafka_topic='t', value_format='AVRO');"
            )
    with faults.inject("schema.registry.lookup", match="id:", count=1):
        with pytest.raises(faults.FaultInjected):
            e.schema_registry.get_by_id(1)


def test_http_peer_forward_fault_point():
    from ksql_tpu.server.rest import KsqlServer

    e = _engine()
    s = KsqlServer(engine=e, port=0, peers=["http://127.0.0.1:1"])
    # (not started: _forward_query is a pure routing helper)
    with faults.inject("http.peer.forward", count=1) as rule:
        assert s._forward_query("SELECT * FROM NOPE;") is None
    assert rule.fired == 1  # the injected fault consumed the only peer


# ----------------------------------------------------------- chaos variant
@pytest.mark.chaos
def test_chaos_soak_corrupt_mode_no_silent_loss():
    """The ROADMAP 'chaos_soak coverage' satellite: with corrupt-serde
    faults armed, every skipped poison record must be accounted for in the
    processing log (no silent loss)."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(__file__), "..", "scripts", "chaos_soak.py"
    )
    spec = importlib.util.spec_from_file_location("chaos_soak", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    res = mod.soak(seconds=1.5, seed=7, backend="oracle", rate=400,
                   verbose=False, corrupt=True)
    assert res["ok"], res["message"]


# ------------------------------------------- the tick accounts for itself
COUNT_CTAS = (
    "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV "
    "WINDOW TUMBLING (SIZE 1 HOUR) GROUP BY URL EMIT CHANGES;"
)
STEP_CHILDREN = ("step.dispatch", "step.wait", "emit.decode")


def _device_count_engine(extra=None):
    e = _engine({cfg.RUNTIME_BACKEND: "device-only", cfg.BATCH_CAPACITY: 256,
                 **(extra or {})})
    e.execute_sql(PV_DDL)
    e.execute_sql(COUNT_CTAS)
    (handle,) = e.queries.values()
    assert handle.backend == "device"
    return e, handle


def _drive_ticks(e, ticks, rows=200):
    t = e.broker.topic("pv")
    for tick in range(ticks):
        for i in range(rows):
            t.produce(Record(
                key=None, timestamp=tick * rows + i,
                value=json.dumps({"URL": f"/p{(i * 7 + tick) % 61}", "V": i}),
            ))
        e.run_until_quiescent()


def _direct_children(span, spans):
    lo, hi = span["t0Ms"], span["t0Ms"] + span["durMs"]
    return [
        s for s in spans
        if s["depth"] == span["depth"] + 1
        and lo - 0.002 <= s["t0Ms"] and s["t0Ms"] + s["durMs"] <= hi + 0.002
    ]


def test_tick_accounts_for_itself():
    """Every kept tick's spans cover it (the time under no span, the tick
    stage's self time, is a small share) and each stage's recorded self
    time is its spans' duration minus their direct children and the timed
    stages accumulated under them."""
    e, handle = _device_count_engine()
    # a pause of the collector is a child of whichever span it falls in
    # and has no entry among the spans: this test's arithmetic over the
    # spans runs without one (the pauses have their own test below)
    gc.disable()
    try:
        _drive_ticks(e, 21)
    finally:
        gc.enable()
    rec = e.trace_recorder(handle.query_id)
    ticks = rec.recent()[1:]  # without the tick that compiled
    assert len(ticks) == 20
    total = sum(t["stages"]["tick"]["ms"] for t in ticks)
    unattributed = sum(t["stages"]["tick"]["self_ms"] for t in ticks)
    assert 0 <= unattributed <= 0.10 * total
    #: timed accumulators (no span of their own) and the span they run under
    accumulated_under = {"sink.produce": "emit.dispatch",
                         "emit.callbacks": "emit.dispatch"}
    for t in ticks:
        spans, stages = t["spans"], t["stages"]
        assert {"poll", "process", "drain", "commit"} == {
            s["name"] for s in spans if s["depth"] == 0}
        top = sum(s["durMs"] for s in spans if s["depth"] == 0)
        assert stages["tick"]["self_ms"] == pytest.approx(
            t["durMs"] - top, abs=0.002 * len(spans))
        expected = {}
        for s in spans:
            kids = _direct_children(s, spans)
            expected[s["name"]] = expected.get(s["name"], 0.0) + (
                s["durMs"] - sum(k["durMs"] for k in kids))
        for acc, under in accumulated_under.items():
            expected[under] -= stages[acc]["ms"]
        for name, want in expected.items():
            n_spans = sum(1 for s in spans if s["name"] == name)
            assert stages[name]["self_ms"] == pytest.approx(
                want, abs=0.001 * (len(spans) + n_spans)), name
            assert stages[name]["self_ms"] >= -0.001
            # booked at every span exit, 0.0 where no collection fell
            assert stages[name]["gc_ms"] == 0.0
        # the CPU clock is a system call: the tick, the declared waits and
        # the native parse read it, no other span
        assert {n for n, st in stages.items() if "off_cpu_ms" in st} == {
            "tick", "step.wait", "emit.read", "deserialize"}
        for name in ("tick", "step.wait", "emit.read", "deserialize"):
            assert 0.0 <= stages[name]["off_cpu_ms"] <= stages[name]["ms"]
        assert "gc.pause" not in stages and stages["tick"]["gc_ms"] == 0.0
    e.shutdown()


def test_device_step_children_nest_and_the_parent_reads_what_it_read():
    """step.dispatch / step.wait / emit.decode are children of the device
    step's span, whose own name, total, count and jit_hit keep the
    parent commit's semantics: two device steps a pipelined tick (the
    batch's dispatch, then flush_pipeline), the first ever a compile."""
    e, handle = _device_count_engine()
    n_ticks = 6
    _drive_ticks(e, n_ticks)
    rec = e.trace_recorder(handle.query_id)
    stats = rec.stage_stats()
    dev_spans = []
    for t in rec.recent():
        spans = t["spans"]
        steps = [s for s in spans if s["name"].startswith("device.")]
        assert [s["name"] for s in steps][1:] == ["device.execute"]
        assert len(steps) == 2
        dev_spans.extend(steps)
        dispatch, flush = sorted(steps, key=lambda s: s["t0Ms"])
        assert [k["name"] for k in _direct_children(dispatch, spans)] == [
            "step.dispatch"]
        assert sorted(k["name"] for k in _direct_children(flush, spans)) == [
            "emit.decode", "step.wait"]
        for s in spans:
            if s["name"] in STEP_CHILDREN:
                assert s["depth"] == dispatch["depth"] + 1
    compiled = stats["device.compile"]
    executed = stats["device.execute"]
    assert compiled["n"] == compiled["jit_miss"] == 1
    assert executed["n"] == executed["jit_hit"] == 2 * n_ticks - 1
    assert executed["total_ms"] == pytest.approx(
        sum(s["durMs"] for s in dev_spans if s["name"] == "device.execute"),
        abs=0.001 * len(dev_spans))
    # the children account for the parent
    parents = executed["total_ms"] + compiled["total_ms"]
    children = sum(stats[c]["total_ms"] for c in STEP_CHILDREN)
    rest = executed["self_ms"] + compiled["self_ms"]
    assert parents == pytest.approx(children + rest, abs=0.01)
    assert stats["step.dispatch"]["h2d_bytes"] > 0
    assert stats["emit.decode"]["d2h_bytes"] > 0
    # the step's own account: one sample a flush, rounds of a real probe loop
    assert stats["device.step"]["sampled"] == n_ticks
    assert stats["device.step"]["probe_rounds"] >= n_ticks
    e.shutdown()


def _reference_probe_rounds(table, capacity, keys, active):
    """Plain linear probing, a batch at a time as the store resolves one:
    every round each unresolved row looks at its next slot; a slot that
    holds its key resolves it; of the rows that find a slot empty the first
    in the batch takes it and the others look again; any other slot sends
    the row one further.  Returns how many rounds that took."""
    from ksql_tpu.ops import hash_store as hs

    mask = capacity - 1
    base = (hs.np_mix64(keys ^ 0) & mask).tolist()
    keys = keys.tolist()
    todo = [i for i in range(len(keys)) if active[i]]
    offset = dict.fromkeys(todo, 0)
    rounds = 0
    while todo:
        rounds += 1
        claims, left = {}, []
        for i in todo:
            slot = (base[i] + offset[i]) & mask
            held = table.get(slot)
            if held == keys[i]:
                continue
            if held is None:
                claims.setdefault(slot, i)
            else:
                offset[i] += 1
            left.append(i)
        for slot, i in claims.items():
            table[slot] = keys[i]
            left.remove(i)
        todo = left
    return rounds


@pytest.mark.parametrize("load", [0.3, 0.6])
def test_probe_rounds_equal_a_plain_linear_probing_count(load):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ksql_tpu.ops import hash_store as hs

    capacity, batch = 8192, 512
    store = hs.init_store(hs.StoreLayout(capacity=capacity, num_keys=1, components=()))
    insert = jax.jit(lambda st, kh, act: hs.probe_insert(
        st, capacity, kh, jnp.zeros_like(kh), [kh],
        jnp.zeros(kh.shape, jnp.int32), act))
    rng = np.random.default_rng(int(load * 100))
    resident = rng.integers(-2**62, 2**62, int(load * capacity))
    for lo in range(0, len(resident), batch):
        chunk, act = np.zeros(batch, np.int64), np.zeros(batch, bool)
        part = resident[lo:lo + batch]
        chunk[:len(part)], act[:len(part)] = part, True
        store, _, _, _ = insert(store, chunk, act)
    occ = np.asarray(store["occ"])[:capacity]
    held = np.asarray(store["khash"])
    table = {int(s): int(held[s]) for s in np.nonzero(occ)[0]}
    assert len(table) == len(resident)
    # the seeded batch: resident keys, new keys, repeats of both, and lanes
    # that carry no row
    fresh = rng.integers(-2**62, 2**62, batch // 4)
    keys = np.concatenate([
        rng.choice(resident, batch // 4), fresh, rng.choice(fresh, batch // 4),
        rng.integers(-2**62, 2**62, batch // 4)])
    active = np.ones(batch, bool)
    active[-batch // 8:] = False
    # the store resolves a batch a chunk of lanes at a time, in row order
    width = min(batch, hs._PROBE_CHUNK)
    want = sum(
        _reference_probe_rounds(
            table, capacity, keys[lo:lo + width], active[lo:lo + width])
        for lo in range(0, batch, width))
    store, _, rounds, lane_rounds = insert(store, keys, active)
    assert int(rounds) == want and want > 1
    assert int(lane_rounds) == want * width
    assert int(np.asarray(store["occ"]).sum()) == len(table)
    # a batch with no valid row runs no round
    _, _, none, no_lanes = insert(store, keys, np.zeros(batch, bool))
    assert int(none) == 0 and int(no_lanes) == 0
    found, find_rounds = jax.jit(lambda st, kh: hs.probe_find(
        st, capacity, kh, jnp.zeros_like(kh), jnp.ones(kh.shape, bool)))(store, keys)
    assert 1 <= int(find_rounds) <= want
    assert (np.asarray(found)[active] < capacity).all()


def test_trace_disabled_records_no_stage_and_the_step_still_counts():
    import jax

    e, handle = _device_count_engine({cfg.TRACE_ENABLE: "false"})
    _drive_ticks(e, 3)
    assert e.trace_recorders == {}
    dev = handle.executor.device
    state, emits = dev._step(dev.state, dev.layout.example())
    dev.state = state  # the step donates its state
    assert emits["probe_rounds"].shape == () and int(emits["probe_rounds"]) == 0
    assert emits["probe_rounds"].dtype == jax.numpy.int32
    sink = e.broker.topic(handle.plan.physical_plan.topic)
    assert sum(sink.end_offsets()) > 0
    e.shutdown()


def _lowered_device_query(statements, **sizes):
    from ksql_tpu.runtime.lowering import CompiledDeviceQuery

    e = _engine({cfg.RUNTIME_BACKEND: "oracle"})
    results = [r for s in statements for r in e.execute_sql(s)]
    qid = next(r.query_id for r in results if r.query_id)
    dev = CompiledDeviceQuery(e.queries[qid].plan, e.registry, **sizes)
    e.shutdown()
    return dev


_SS_DDL = [
    "CREATE STREAM LEFTS (ID BIGINT KEY, V BIGINT) "
    "WITH (KAFKA_TOPIC='lt', VALUE_FORMAT='JSON');",
    "CREATE STREAM RIGHTS (ID BIGINT KEY, V BIGINT) "
    "WITH (KAFKA_TOPIC='rt', VALUE_FORMAT='JSON');",
    "CREATE STREAM J AS SELECT L.ID, L.V AS LV, R.V AS RV FROM LEFTS L "
    "LEFT JOIN RIGHTS R WITHIN 10 SECONDS GRACE PERIOD 1 SECOND "
    "ON L.ID = R.ID EMIT CHANGES;",
]
_JOIN_DDL = [
    "CREATE TABLE USERS (ID BIGINT PRIMARY KEY, REGION STRING) "
    "WITH (KAFKA_TOPIC='users', VALUE_FORMAT='JSON');",
    "CREATE STREAM CLICKS (USER_ID BIGINT, URL STRING) "
    "WITH (KAFKA_TOPIC='clicks', VALUE_FORMAT='JSON');",
    "CREATE STREAM ENRICHED AS SELECT C.USER_ID, C.URL, U.REGION "
    "FROM CLICKS C LEFT JOIN USERS U ON C.USER_ID = U.ID EMIT CHANGES;",
]


@pytest.mark.parametrize("statements,step,scopes", [
    ([PV_DDL, "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV "
              "WINDOW TUMBLING (SIZE 1 HOUR) WHERE V >= 0 GROUP BY URL "
              "EMIT CHANGES;"], "_step",
     ["source_decode", "window_assign", "probe_insert", "scatter_combine",
      "emit_compact"]),
    ([PV_DDL, COUNT_CTAS], "_evict", ["evict"]),
    (_JOIN_DDL, "_step", ["source_decode", "probe_find"]),
    (_SS_DDL, "_ss_l", ["ss_join_match"]),
    ([PV_DDL, "CREATE TABLE S AS SELECT URL, COUNT(*) AS CNT FROM PV "
              "WINDOW SESSION (30 SECONDS) GROUP BY URL EMIT CHANGES;"],
     "_step", ["session_merge", "probe_find", "probe_insert"]),
], ids=["tumbling-count", "evict", "stream-table-join", "stream-stream-join",
        "session"])
def test_operator_scopes_reach_the_step_program(statements, step, scopes):
    """The step program names its operators (jax.named_scope): each scope
    the plan uses is in the lowered program's debug info, where XLA takes
    an operation's ``op_name`` from."""
    import jax

    dev = _lowered_device_query(statements, capacity=64, store_capacity=1 << 10)
    state = jax.eval_shape(dev.init_state)
    args = (state,) if step == "_evict" else (state, dev.layout.array_structs())
    text = getattr(dev, step).lower(*args).as_text(debug_info=True)
    missing = [s for s in scopes if f"{s}/" not in text and f'{s}"' not in text]
    assert not missing, missing


def test_exchange_scope_reaches_the_sharded_step():
    e = _engine({cfg.RUNTIME_BACKEND: "distributed"})
    e.execute_sql(PV_DDL)
    e.execute_sql(COUNT_CTAS)
    (handle,) = e.queries.values()
    assert handle.backend == "distributed", e.fallback_reasons
    _feed(e, n=32)
    dev = handle.executor.device
    from ksql_tpu.common.batch import HostBatch

    arrays = dev.encode(HostBatch.from_rows(dev.c.layout.schema, []))
    text = dev._step.lower(dev.state, arrays).as_text(debug_info=True)
    assert "exchange/" in text and "probe_insert/" in text
    stats = e.trace_recorder(handle.query_id).stage_stats()
    # the sharded step's children and its slowest shard's probe loop
    assert {*STEP_CHILDREN, "device.step"} <= set(stats)
    assert stats["device.step"]["sampled"] >= 1
    e.shutdown()


def test_counter_only_stage_reports_no_time():
    """device.step is never timed: it reports its counters and no
    total/p50/p99 — a 0 there reads as 'this costs nothing' — in
    stage_stats, EXPLAIN ANALYZE and the Prometheus text."""
    from ksql_tpu.common.metrics import prometheus_text

    e, handle = _device_count_engine()
    _drive_ticks(e, 2)
    qid = handle.query_id
    stats = e.trace_recorder(qid).stage_stats()
    assert stats["device.step"]["n"] == 0
    assert not {"total_ms", "p50_ms", "p99_ms"} & set(stats["device.step"])
    assert stats["device.step"]["probe_rounds"] > 0
    assert {"total_ms", "p50_ms", "p99_ms", "self_ms"} <= set(stats["drain"])
    rows = {r["stage"]: r for r in e.execute_sql(f"EXPLAIN ANALYZE {qid};")[0].rows}
    assert rows["device.step"]["totalMs"] is None
    assert "probe_rounds" in rows["device.step"]["extra"]
    assert rows["drain"]["totalMs"] > 0 and list(rows)[-1] == "tick"
    text = prometheus_text(e.metrics_snapshot(), {qid: stats})
    timed = [ln for ln in text.splitlines()
             if ln.startswith(("ksql_query_stage_ms_total", "ksql_query_stage_latency_ms"))]
    assert any('stage="drain"' in ln for ln in timed)
    assert not any('stage="device.step"' in ln for ln in timed)
    assert f'ksql_query_stage_probe_rounds_total{{query="{qid}",stage="device.step"}}' in text
    assert f'ksql_query_stage_self_ms_total{{query="{qid}",stage="tick"}}' in text
    assert f'ksql_query_stage_h2d_bytes_total{{query="{qid}",stage="step.dispatch"}}' in text
    e.shutdown()


def _shape_of(rec):
    """What a recorder holds, without its times."""
    ticks = [[(s["name"], s["depth"]) for s in t["spans"]] for t in rec.recent()]
    stats = {
        name: {k: v for k, v in st.items()
               if not k.endswith("_ms") and k != "d2h_bytes"}
        for name, st in rec.stage_stats().items()
        if name != "gc.pause"  # the collector keeps its own schedule
    }
    return ticks, stats


def test_trace_annotations_leave_the_recorder_unchanged(tmp_path):
    """Every span is mirrored as a jax.profiler.TraceAnnotation: with a
    profiler session open the trace holds the tick and its spans on the
    profiler's clock, and what the recorder keeps is the same either way."""
    import glob

    from jax import profiler

    shapes = []
    for session in (False, True):
        e, handle = _device_count_engine()
        if session:
            profiler.start_trace(str(tmp_path))
        try:
            _drive_ticks(e, 3)
        finally:
            if session:
                profiler.stop_trace()
        shapes.append(_shape_of(e.trace_recorder(handle.query_id)))
        qid = handle.query_id
        e.shutdown()
    assert shapes[0] == shapes[1]
    (xplane,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    names = set()
    for plane in profiler.ProfileData.from_file(xplane).planes:
        for line in plane.lines:
            names.update(ev.name for ev in line.events)
    assert {f"ksql.tick#{qid}#{seq}" for seq in (1, 2, 3)} <= names
    assert {"poll", "process", "drain", "device.execute", "step.wait",
            "emit.decode", "emit.read", "emit.rows", "emit.dispatch",
            "commit"} <= names


# ------------------------------- the host's serial part, by cause (ISSUE 36)
@pytest.fixture
def gc_hook_from_zero(monkeypatch):
    """The hook's holders counted from none, whatever engines earlier tests
    of this process left alive; their hold is given back afterwards."""
    gc.collect()  # dead engines give their hold back now, not mid-test
    installed = tracing._on_gc in gc.callbacks
    if installed:
        gc.callbacks.remove(tracing._on_gc)
    monkeypatch.setattr(tracing, "_gc_holders", 0)
    yield
    while tracing._on_gc in gc.callbacks:
        gc.callbacks.remove(tracing._on_gc)
    if installed:
        gc.callbacks.append(tracing._on_gc)


def _traced_tick(body):
    """One tick of a recorder of its own around ``body(trace)``; returns
    the tick's stages."""
    rec = tracing.FlightRecorder("q", 4)
    with tracing.tick(rec) as tr:
        body(tr)
    return rec.last().stages


def test_a_collection_is_booked_where_it_falls(gc_hook_from_zero):
    """A collection forced inside a span: one ``gc.pause`` under the
    innermost open span, ``gc_ms`` on every open span and on the tick, no
    ``self_ms`` holds it, and each parent counts its child's time once."""
    tracing.hold_gc_hook()

    def body(tr):
        with tracing.span("outer"):
            with tracing.span("inner"):
                gc.collect()
            with tracing.span("quiet"):
                pass

    try:
        gc.disable()  # only the forced one
        stages = _traced_tick(body)
    finally:
        gc.enable()
        tracing.release_gc_hook()
    pause = stages["gc.pause"]
    assert pause["n"] == 1 and pause["gen2"] == 1
    assert pause["gen2_ms"] == pause["ms"] > 0.0
    for name in ("inner", "outer", "tick"):
        assert stages[name]["gc_ms"] == pytest.approx(pause["ms"])
    assert stages["quiet"]["gc_ms"] == 0.0  # the field is there all the same
    inner, outer, tick = stages["inner"], stages["outer"], stages["tick"]
    assert inner["self_ms"] == pytest.approx(inner["ms"] - pause["ms"])
    assert 0.0 <= inner["self_ms"] < 0.5 * pause["ms"] + 0.05
    assert outer["self_ms"] == pytest.approx(
        outer["ms"] - inner["ms"] - stages["quiet"]["ms"])
    assert tick["self_ms"] == pytest.approx(tick["ms"] - outer["ms"])
    assert tracing._on_gc not in gc.callbacks


def test_a_pause_inside_a_timed_stage_is_its_spans_child_once(gc_hook_from_zero):
    """A timed stage's two clock reads hold a pause that fell between them,
    and the pause is ``gc.pause`` under the span already: the span's self
    time loses it once, not twice."""
    tracing.hold_gc_hook()

    def body(tr):
        with tracing.span("outer"):
            gc.collect()  # before the stage: not the stage's
            t0 = time.perf_counter()
            gc.collect()
            tr.stage("leaf", time.perf_counter() - t0)

    try:
        gc.disable()
        stages = _traced_tick(body)
    finally:
        gc.enable()
        tracing.release_gc_hook()
    outer, leaf, pause = stages["outer"], stages["leaf"], stages["gc.pause"]
    assert pause["n"] == 2 and outer["gc_ms"] == pytest.approx(pause["ms"])
    assert leaf["ms"] > 0.4 * pause["ms"]  # the stage's total keeps its pause
    second = outer["ms"] - outer["self_ms"] - leaf["ms"]  # = the first pause
    assert 0.0 < second < pause["ms"]
    assert outer["self_ms"] >= 0.0


def test_a_thread_with_no_open_tick_books_nothing(gc_hook_from_zero):
    tracing.hold_gc_hook()
    try:
        gc.collect()  # no tick on this thread: the hook is a no-op
        stages = _traced_tick(lambda tr: None)
    finally:
        tracing.release_gc_hook()
    assert "gc.pause" not in stages and stages["tick"]["gc_ms"] == 0.0


def test_one_gc_hook_however_many_engines(gc_hook_from_zero):
    first = _engine({cfg.RUNTIME_BACKEND: "oracle"})
    assert gc.callbacks.count(tracing._on_gc) == 1
    second = _engine({cfg.RUNTIME_BACKEND: "oracle"})
    assert gc.callbacks.count(tracing._on_gc) == 1
    first.shutdown()
    first.shutdown()  # gives its hold back once
    assert gc.callbacks.count(tracing._on_gc) == 1
    second.shutdown()
    assert tracing._on_gc not in gc.callbacks and tracing._gc_holders == 0
    # an engine nobody shuts down gives it back when it is collected
    third = _engine({cfg.RUNTIME_BACKEND: "oracle"})
    assert gc.callbacks.count(tracing._on_gc) == 1
    del third
    gc.collect()
    assert tracing._on_gc not in gc.callbacks


def test_trace_disabled_installs_no_hook_and_reads_no_clock(
        gc_hook_from_zero, monkeypatch):
    def no_clock():
        raise AssertionError("a span read a clock with tracing disabled")

    monkeypatch.setattr(tracing, "_perf", no_clock)
    monkeypatch.setattr(tracing, "_cpu", no_clock)
    e, handle = _device_count_engine({cfg.TRACE_ENABLE: "false"})
    assert tracing._on_gc not in gc.callbacks and tracing._gc_holders == 0
    _drive_ticks(e, 2)
    assert handle.state == "RUNNING" and e.trace_recorders == {}
    sink = e.broker.topic(handle.plan.physical_plan.topic)
    assert sum(sink.end_offsets()) > 0
    e.shutdown()
    assert tracing._on_gc not in gc.callbacks


def _spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _on_a_quiet_turn(check, tries=3):
    """``check()`` measures and asserts; a machine shared with other work
    may take the CPU from a busy loop at any moment, so the property has to
    show on one of ``tries`` measurements, not on the first."""
    for attempt in range(tries):
        try:
            return check()
        except AssertionError:
            if attempt == tries - 1:
                raise


def test_off_cpu_is_the_time_the_thread_did_not_run():
    """A sleep is off the CPU, a busy loop is not; a declared wait keeps
    its time to itself (through a span that reads no CPU clock too), any
    other span hands it up to the tick."""
    _on_a_quiet_turn(_off_cpu_is_the_time_the_thread_did_not_run)


def _off_cpu_is_the_time_the_thread_did_not_run():
    nap = 0.05

    def body(tr):
        with tracing.span("sleeps", cpu=True):
            time.sleep(nap)
        with tracing.span("spins", cpu=True):
            _spin(nap)
        with tracing.span("parent", cpu=True):
            with tracing.span("between"):  # reads no CPU clock, books none
                with tracing.span("waits", wait=True):
                    time.sleep(nap)

    stages = _traced_tick(body)
    assert "off_cpu_ms" not in stages["between"]
    nap_ms = nap * 1e3
    assert stages["sleeps"]["off_cpu_ms"] >= 0.9 * nap_ms
    assert stages["spins"]["off_cpu_ms"] <= 0.5 * nap_ms
    assert stages["waits"]["off_cpu_ms"] >= 0.9 * nap_ms
    # the wait handed nothing up: its parent was running the whole time it
    # was not inside the wait, and the tick holds the one undeclared sleep
    assert stages["parent"]["off_cpu_ms"] <= 0.5 * nap_ms
    tick = stages["tick"]
    assert 0.9 * nap_ms <= tick["off_cpu_ms"] <= tick["ms"] - 1.9 * nap_ms


def test_a_spinning_second_thread_shows_as_time_off_the_cpu():
    """The same CPU-bound work beside a thread that spins in Python: the
    GIL is in the other thread's hands about half the time, and the span
    says so."""
    _on_a_quiet_turn(_a_spinning_second_thread_shows_as_time_off_the_cpu)


def _a_spinning_second_thread_shows_as_time_off_the_cpu():
    def work(tr):
        with tracing.span("work", cpu=True):
            x = 0
            for i in range(1_500_000):
                x += i

    alone = _traced_tick(work)["work"]
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass

    other = threading.Thread(target=spin, daemon=True)
    other.start()
    try:
        beside = _traced_tick(work)["work"]
    finally:
        stop.set()
        other.join(10.0)
    assert not other.is_alive()
    assert beside["off_cpu_ms"] > alone["off_cpu_ms"] + 0.2 * alone["ms"]


def _emit_children_add_up(stats):
    decode, read, rows = (stats[n] for n in ("emit.decode", "emit.read", "emit.rows"))
    assert read["n"] == rows["n"] == decode["n"] > 0
    assert decode["total_ms"] == pytest.approx(
        read["total_ms"] + rows["total_ms"] + decode["self_ms"], abs=0.01)
    assert decode["d2h_bytes"] > 0
    assert "d2h_bytes" not in read and "d2h_bytes" not in rows
    assert decode["rows"] > 0 and "rows" not in rows
    for st in (decode, read, rows):
        assert st["gc_ms"] >= 0.0
    # the read is a declared wait; its parent reads no CPU clock
    assert 0.0 <= read["off_cpu_ms"] <= read["total_ms"]
    assert "off_cpu_ms" not in decode and "off_cpu_ms" not in rows


def test_emit_decode_and_emit_dispatch_have_children():
    e, handle = _device_count_engine()
    _drive_ticks(e, 4)
    stats = e.trace_recorder(handle.query_id).stage_stats()
    _emit_children_add_up(stats)
    dispatch, callbacks, sink = (
        stats[n] for n in ("emit.dispatch", "emit.callbacks", "sink.produce"))
    # the block path: one pass of the callbacks a block, and the sink's
    # two stages a block (the encode, the append)
    assert dispatch["block_rows"] == dispatch["rows"] > 0
    assert callbacks["n"] == dispatch["n"]
    assert 0.0 < sink["encode_ms"] < sink["total_ms"]
    assert callbacks["total_ms"] + sink["total_ms"] <= dispatch["total_ms"] + 0.01
    assert dispatch["total_ms"] == pytest.approx(
        callbacks["total_ms"] + sink["total_ms"] + dispatch["self_ms"], abs=0.01)
    # a subscriber keeps the per-emit loop: its callbacks are self time
    handle.push_listeners.append(lambda emit: None)
    _drive_ticks(e, 2)
    after = e.trace_recorder(handle.query_id).stage_stats()
    assert after["emit.dispatch"]["n"] > dispatch["n"]
    assert after["emit.callbacks"]["n"] == callbacks["n"]
    e.shutdown()


def test_emit_decode_has_children_on_four_virtual_devices():
    e = _engine({cfg.RUNTIME_BACKEND: "distributed", "ksql.device.shards": 4})
    e.execute_sql(PV_DDL)
    e.execute_sql(COUNT_CTAS)
    (handle,) = e.queries.values()
    assert handle.backend == "distributed", e.fallback_reasons
    assert handle.executor.device.n_shards == 4
    _drive_ticks(e, 3, rows=64)
    _emit_children_add_up(e.trace_recorder(handle.query_id).stage_stats())
    e.shutdown()


# ------------------------------------------- tracing: push-registry spans
def test_query_trace_serves_push_pipeline_and_tap_spans():
    """ISSUE acceptance: /query-trace over the shared pipeline's id shows
    the push.pipeline.step pump span and push.tap.deliver delivery span,
    with rows + sampled ring lag counters."""
    from ksql_tpu.server.rest import KsqlServer, PushQuerySession

    e = KsqlEngine(KsqlConfig({
        cfg.RUNTIME_BACKEND: "oracle",
    }))
    e.execute_sql(
        "CREATE STREAM S (ID BIGINT, V BIGINT) "
        "WITH (kafka_topic='s', value_format='JSON');"
    )
    e.session_properties["auto.offset.reset"] = "latest"
    sess = PushQuerySession(e, "SELECT ID FROM S WHERE V > 0 EMIT CHANGES;")
    assert sess.shared
    pipe = sess.tap.pipeline
    t = e.broker.topic("s")
    for i in range(8):
        t.produce(Record(key=None, value=json.dumps({"ID": i, "V": i}),
                         timestamp=i))
    rows = sess.poll()
    assert len(rows) == 7  # V > 0
    s = KsqlServer(engine=e, port=0)
    s.start()
    try:
        # pump ticks on <pipe>, tap-delivery ticks on <pipe>/taps —
        # separate rings so N delivering taps can't evict the pump's
        # ticks (and its gated p99 window) under fan-out
        stages = {}
        spans = set()
        for rec_id in (pipe.id, pipe.id + "/taps"):
            with urllib.request.urlopen(
                f"{s.url}/query-trace/{rec_id}"
            ) as r:
                body = json.loads(r.read())
            assert body["ticks"], f"{rec_id} recorder must retain ticks"
            for tk in body["ticks"]:
                spans.update(sp["name"] for sp in tk["spans"])
                for name, st in tk["stages"].items():
                    for k, v in st.items():
                        stages.setdefault(name, {}).setdefault(k, 0)
                        if isinstance(v, (int, float)):
                            stages[name][k] += v
        assert {"push.pipeline.step", "push.tap.deliver"} <= spans
        # the pump counted its ring appends, the tap its deliveries and
        # a per-poll ring-lag sample
        assert stages["push.pipeline.step"]["rows"] == 8
        assert stages["push.tap.deliver"]["rows"] == 7
        assert "ring_lag" in stages["push.tap.deliver"]
    finally:
        sess.close()
        s.stop()


def test_listener_mode_emits_land_on_upstream_recorder():
    """In listener mode the ring appends ride the UPSTREAM query's tick:
    its flight recorder shows push.pipeline.step rows."""
    from ksql_tpu.server.rest import PushQuerySession

    e = KsqlEngine(KsqlConfig({cfg.RUNTIME_BACKEND: "oracle"}))
    e.execute_sql(
        "CREATE STREAM S (ID BIGINT, V BIGINT) "
        "WITH (kafka_topic='s', value_format='JSON');"
    )
    e.execute_sql(
        "CREATE STREAM MAT AS SELECT ID, V FROM S EMIT CHANGES;"
    )
    qid = list(e.queries)[0]
    e.session_properties["auto.offset.reset"] = "latest"
    # a session over the RUNNING query's sink attaches in listener mode
    sess = PushQuerySession(e, "SELECT ID FROM MAT EMIT CHANGES;")
    assert sess.shared and sess.tap.pipeline.mode == "listener"
    t = e.broker.topic("s")
    for i in range(5):
        t.produce(Record(key=None, value=json.dumps({"ID": i, "V": i}),
                         timestamp=i))
    sess.poll()
    st = e.trace_recorder(qid).stage_stats()
    assert st.get("push.pipeline.step", {}).get("rows", 0) >= 5
    sess.close()
    e.shutdown()


# --------------------------------------------- tracing: cutover phase spans
def test_query_trace_serves_reshard_cutover_phase_spans(tmp_path):
    """A live rescale cutover (2 -> 4 shards through the supervised
    drain/cutover ladder) lands phase spans — drain / checkpoint /
    rebuild / restore plus the reshard's gather / repartition / insert —
    on the query's flight recorder (served by /query-trace), and the
    rescale.done /alerts evidence event carries the per-phase ms."""
    from ksql_tpu.server.rest import KsqlServer

    from tests.test_device_parity import DDL, gen_rows

    e = KsqlEngine(KsqlConfig({
        cfg.RUNTIME_BACKEND: "distributed",
        cfg.BATCH_CAPACITY: 64,
        cfg.STATE_SLOTS: 1024,
        cfg.DEVICE_SHARDS: 2,
        cfg.STATE_CHECKPOINT_DIR: str(tmp_path),
        cfg.QUERY_RETRY_BACKOFF_INITIAL_MS: 1,
    }))
    e.execute_sql(DDL)
    e.execute_sql(
        "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
        "WINDOW TUMBLING (SIZE 1 HOUR) GROUP BY URL EMIT CHANGES;"
    )
    h = list(e.queries.values())[0]
    assert h.backend == "distributed"
    t = e.broker.topic("page_views")
    for row, ts in gen_rows(40, seed=5):
        t.produce(Record(key=None, value=json.dumps(row), timestamp=ts))
    e.run_until_quiescent()
    qid = h.query_id
    e._rescale_query(h, 4, "grow")
    assert h.state == "ERROR" and h.pending_rescale is not None
    for _ in range(50):
        e.poll_once()
        if h.state == "RUNNING" and h.pending_rescale is None:
            break
    assert h.state == "RUNNING"
    assert h.executor.device.n_shards == 4
    s = KsqlServer(engine=e, port=0)
    s.start()
    try:
        with urllib.request.urlopen(f"{s.url}/query-trace/{qid}") as r:
            body = json.loads(r.read())
        spans = {
            sp["name"] for tk in body["ticks"] for sp in tk["spans"]
        }
        assert {
            "cutover.drain", "cutover.checkpoint", "cutover.rebuild",
            "cutover.restore", "cutover.gather", "cutover.repartition",
            "cutover.insert",
        } <= spans, spans
    finally:
        s.stop()
    done = [ev for ev in h.progress.events if ev["kind"] == "rescale.done"]
    assert done, list(h.progress.events)
    phases = done[-1]["phasesMs"]
    assert done[-1]["from"] == 2 and done[-1]["to"] == 4
    # the whole cutover is phase-attributed: initiation phases (stashed
    # by _rescale_query) merged with the rebuild tick's spans
    assert {"cutover.checkpoint", "cutover.rebuild",
            "cutover.restore", "cutover.gather"} <= set(phases)
    assert phases["cutover.rebuild"] > 0
    e.shutdown()


# ----------------------------------------------------- deadline auto-sizing
def test_deadline_hint_fires_when_timeout_below_cold_compile_p99(tmp_path):
    """ISSUE satellite: a configured tick/rebuild deadline below the
    observed cold-compile p99 logs a deadline.hint plog entry + /alerts
    evidence NAMING the observed value on rebuild completion."""
    # the tick deadline (1s) is far above any real oracle tick here — no
    # spurious deadline fires — but BELOW the 5s cold-compile p99 seeded
    # onto the recorder, so the hint must fire for the TICK knob; the
    # rebuild deadline stays disabled (0) and must stay hint-silent
    e = KsqlEngine(KsqlConfig({
        cfg.RUNTIME_BACKEND: "oracle",
        cfg.STATE_CHECKPOINT_DIR: str(tmp_path),
        cfg.QUERY_RETRY_BACKOFF_INITIAL_MS: 0,
        cfg.QUERY_TICK_TIMEOUT_MS: 1000,
        # hint-only is opt-in since the ISSUE-13 posture flip: autosize
        # defaults ON and would RAISE the knob instead of hinting
        cfg.DEADLINE_AUTOSIZE: False,
    }))
    e.execute_sql(
        "CREATE STREAM S (ID BIGINT, V BIGINT) "
        "WITH (kafka_topic='s', value_format='JSON');"
    )
    e.execute_sql(
        "CREATE TABLE C AS SELECT ID, COUNT(*) AS CNT FROM S "
        "GROUP BY ID EMIT CHANGES;"
    )
    qid = list(e.queries)[0]
    h = e.queries[qid]
    t = e.broker.topic("s")
    t.produce(Record(key=None, value='{"ID":1,"V":1}', timestamp=1))
    e.run_until_quiescent()
    # seed an observed cold compile (the oracle never compiles): 5s p99
    rec = e.trace_recorder(qid)
    with tracing.tick(rec):
        tracing.stage("device.compile", 5.0, jit_miss=1)
    with faults.inject("stage.process", count=1):
        t.produce(Record(key=None, value='{"ID":2,"V":2}', timestamp=2))
        e.poll_once()
    assert h.state == "ERROR"
    h.retry_at_ms = 0
    for _ in range(10):
        e.poll_once()
        if h.state == "RUNNING":
            break
    assert h.state == "RUNNING"
    hints = [p for p in e.processing_log
             if str(p[0]).startswith("deadline.hint")]
    assert hints, "hint plog entry must land on rebuild completion"
    assert cfg.QUERY_TICK_TIMEOUT_MS in hints[-1][1]
    assert "5000ms" in hints[-1][1]  # names the observed value
    evs = [ev for ev in h.progress.events if ev["kind"] == "deadline.hint"]
    assert evs and evs[-1]["knob"] == cfg.QUERY_TICK_TIMEOUT_MS
    assert evs[-1]["configuredMs"] == 1000
    assert evs[-1]["observedColdCompileP99Ms"] == 5000.0
    # the DISABLED rebuild deadline must never produce a hint
    assert all(
        ev["knob"] != cfg.QUERY_REBUILD_TIMEOUT_MS for ev in evs
    )
    e.shutdown()


def test_no_deadline_hint_when_deadlines_disabled(tmp_path):
    e = KsqlEngine(KsqlConfig({
        cfg.RUNTIME_BACKEND: "oracle",
        cfg.QUERY_RETRY_BACKOFF_INITIAL_MS: 0,
    }))
    e.execute_sql(
        "CREATE STREAM S (ID BIGINT, V BIGINT) "
        "WITH (kafka_topic='s', value_format='JSON');"
    )
    e.execute_sql("CREATE STREAM P AS SELECT ID FROM S EMIT CHANGES;")
    qid = list(e.queries)[0]
    h = e.queries[qid]
    rec = e.trace_recorder(qid)
    with tracing.tick(rec):
        tracing.stage("device.compile", 0.500, jit_miss=1)
    t = e.broker.topic("s")
    with faults.inject("stage.process", count=1):
        t.produce(Record(key=None, value='{"ID":1,"V":1}', timestamp=1))
        e.poll_once()
    h.retry_at_ms = 0
    e.poll_once()
    assert h.state == "RUNNING"
    assert not [p for p in e.processing_log
                if str(p[0]).startswith("deadline.hint")]
    e.shutdown()
