"""Retained telemetry timeline (ISSUE 18): interval fold/rollover and the
cursor contract, empty-interval coalescing + ring bound (memory stays flat
over a long soak), annotation placement, shard-delta re-basing across
rescale/rebuild counter resets, the skew detector's one-event-per-episode
contract, the e2e latency histogram (non-degenerate p50<p99, Prometheus
exposition, registry pinning), the ``/timeline`` + ``/query-trace`` cursor
endpoints, live-skew + live-rescale + overload durability, the plog
registry hygiene gate, and the obs_report renderer."""

import json
import os
import re
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from ksql_tpu.common import config as cfg
from ksql_tpu.common import faults
from ksql_tpu.common import timeline as tlm
from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.common.timeline import TimelineStore
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.runtime.topics import Record


_REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(autouse=True)
def _disarm():
    faults.clear()
    yield
    faults.clear()


class _FakeTrace:
    """The four attributes TimelineStore.fold reads off a TickTrace."""

    def __init__(self, at_ms, dur_ms=1.0, rows=0, status="OK",
                 stages=None):
        self.started_at_ms = at_ms
        self.dur_ms = dur_ms
        self.status = status
        self.stages = dict(stages or {})
        if rows:
            self.stages.setdefault("poll", {"ms": dur_ms})["rows"] = rows


# ------------------------------------------------------------- unit: fold
def test_the_fold_set_is_the_timelines_own():
    """The stages every tick's fold keeps are named here and decided in
    ``common/timeline.py`` itself: in a fresh interpreter it imports no
    other module of the program (it once took the set from a gate's)."""
    assert tlm.FOLD_STAGES == {
        "device.compile", "device.execute", "deserialize", "exchange",
        "sink.produce", "push.pipeline.step", "push.tap.deliver",
        "push.residual.kernel", "poll",
    }
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, ksql_tpu.common.timeline\n"
         "print(sorted(m for m in sys.modules"
         " if m.startswith('ksql_tpu') or 'gate' in m))"],
        capture_output=True, text=True, timeout=120, cwd=_REPO_ROOT,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == str(
        ["ksql_tpu", "ksql_tpu.common", "ksql_tpu.common.timeline"])


def test_interval_rollover_and_cursor_contract():
    tl = TimelineStore("q1", interval_ms=100, ring=16)
    # interval 0: two ticks; interval 1: one error tick; interval 2 opens
    tl.fold(_FakeTrace(10, dur_ms=2.0, rows=5,
                       stages={"deserialize": {"ms": 0.5, "n": 5}}))
    tl.fold(_FakeTrace(60, dur_ms=1.0, rows=3))
    tl.fold(_FakeTrace(120, dur_ms=4.0, rows=2, status="ERROR"))
    tl.fold(_FakeTrace(210, dur_ms=1.0, rows=1))

    body = tl.since(None)
    frames = body["frames"]
    assert [f["seq"] for f in frames] == [0, 1, 2]
    assert frames[0]["ticks"] == 2 and frames[0]["rows"] == 8
    assert frames[0]["startMs"] == 0 and frames[0]["endMs"] == 100
    assert frames[0]["throughputRps"] == pytest.approx(80.0)
    assert "poll" in frames[0]["stages"]
    assert "deserialize" in frames[0]["stages"]
    assert frames[0]["stages"]["poll"]["ticks"] == 2
    assert frames[1]["errTicks"] == 1
    assert frames[2].get("open") is True

    # cursor: nextSince is the last CLOSED seq — passing it back re-reads
    # only the open frame, and never replays history
    assert body["nextSince"] == 1
    nxt = tl.since(body["nextSince"])
    assert [f["seq"] for f in nxt["frames"]] == [2]
    assert nxt["frames"][0].get("open") is True
    assert nxt["nextSince"] == 1  # still nothing newly closed
    # once seq-2 closes, the same cursor picks it up exactly once
    tl.fold(_FakeTrace(330, rows=1))
    nxt2 = tl.since(1)
    assert [f["seq"] for f in nxt2["frames"]] == [2, 3]
    assert nxt2["nextSince"] == 2


def test_empty_interval_coalescing_and_ring_bound():
    """Durability satellite: a long mostly-idle soak stays bounded — empty
    intervals are coalesced (counted, not stored) and the frame ring caps
    retention regardless of how many busy intervals pass."""
    tl = TimelineStore("q1", interval_ms=10, ring=8)
    # 500 intervals, only every 7th sees a tick
    for i in range(500):
        if i % 7 == 0:
            tl.fold(_FakeTrace(i * 10 + 1, rows=1))
        else:
            # roll the interval forward with an empty gauge sample
            tl.observe(i * 10 + 1)
    st = tl.stats()
    assert st["frames"] <= 8
    assert st["coalesced"] > 300
    frames = tl.since(None)["frames"]
    seqs = [f["seq"] for f in frames]
    assert seqs == sorted(seqs)
    assert all(f["ticks"] or f.get("open") for f in frames)
    # seq is the absolute interval index: stable across coalesced gaps
    closed = [f for f in frames if not f.get("open")]
    assert all(f["seq"] % 7 == 0 for f in closed)


def test_annotation_placement_cap_and_rescue():
    tl = TimelineStore("q1", interval_ms=100, ring=8)
    tl.fold(_FakeTrace(10, rows=1))
    # annotation lands on the interval covering its wall time
    tl.annotate("rescale", "2 -> 4", now_ms=150)
    # an annotation ALONE keeps its otherwise-empty interval from
    # coalescing: cause stays visible even across an idle query
    tl.fold(_FakeTrace(250, rows=1))  # closes seq 1 (annotation only)
    tl.observe(350)                   # closes seq 2 (tick only)
    frames = tl.since(None)["frames"]
    by_seq = {f["seq"]: f for f in frames}
    assert by_seq[1]["ticks"] == 0
    assert by_seq[1]["annotations"][0]["kind"] == "rescale"
    assert by_seq[1]["annotations"][0]["detail"] == "2 -> 4"
    assert tl.annotation_kinds() == ["rescale"]
    # per-interval cap: a storm cannot grow one frame without bound
    for i in range(tlm.FRAME_ANNOTATIONS + 10):
        tl.annotate("overload.engage", f"n{i}", now_ms=360)
    assert tl.stats()["annotationsDropped"] == 10
    open_f = [f for f in tl.since(None)["frames"] if f.get("open")][0]
    assert len(open_f["annotations"]) == tlm.FRAME_ANNOTATIONS


def test_stage_reservoir_stride_doubling_bounded():
    agg = tlm._StageAgg()
    for i in range(10 * tlm.STAGE_SAMPLES):
        agg.add(float(i % 100))
    assert agg.n == 10 * tlm.STAGE_SAMPLES
    assert len(agg.samples) <= tlm.STAGE_SAMPLES
    d = agg.to_dict()
    assert d["ticks"] == agg.n
    assert d["p50Ms"] is not None and d["p99Ms"] is not None
    assert d["p50Ms"] <= d["p99Ms"]


def test_shard_delta_rebase_on_width_change_and_reset():
    """Cumulative executor counters become per-interval deltas; a rescale
    (width change) or a rebuild (counter reset) re-bases instead of
    emitting negative rows."""
    tl = TimelineStore("q1", interval_ms=100, ring=8)
    tl.observe(10, shards={"rows-in": [100, 50]})
    tl.observe(50, shards={"rows-in": [160, 70]})   # same interval: +80
    f0 = tl.since(None)["frames"][0]
    assert f0["shards"]["rows"] == [160, 70]  # first sample IS the delta
    # width change (2 -> 4): re-base, no negative deltas
    tl.observe(150, shards={"rows-in": [10, 5, 3, 2],
                            "store-occupancy": [4, 3, 2, 1]})
    frames = tl.since(None)["frames"]
    f1 = [f for f in frames if f["seq"] == 1][0]
    assert f1["shards"]["rows"] == [10, 5, 3, 2]
    assert f1["shards"]["storeOccupancy"] == [4, 3, 2, 1]
    # counter reset (rebuild): cumulative dropped below base -> re-base
    tl.observe(250, shards={"rows-in": [4, 1, 0, 0]})
    f2 = [f for f in tl.since(None)["frames"] if f["seq"] == 2][0]
    assert f2["shards"]["rows"] == [4, 1, 0, 0]
    assert all(r >= 0 for f in tl.since(None)["frames"]
               if "shards" in f for r in f["shards"]["rows"])


# ---------------------------------------------------- unit: skew detector
def test_skew_detector_one_event_per_episode_and_rearm():
    tl = TimelineStore("q1", interval_ms=100, ring=32,
                       skew_ratio=1.8, skew_intervals=2)
    # 2 shards: threshold = min(1.8 * 0.5, 0.95) = 0.9
    cum = [0, 0]

    def sample(t, d0, d1):
        cum[0] += d0
        cum[1] += d1
        tl.observe(t, shards={"rows-in": list(cum)})

    sample(0, 100, 0)     # f0 open
    sample(100, 100, 0)   # closes f0: streak 1
    assert tl.drain_events() == []
    sample(200, 100, 0)   # closes f1: streak 2 -> event
    ev = tl.drain_events()
    assert len(ev) == 1
    assert ev[0]["kind"] == "telemetry.skew"
    assert ev[0]["hotShard"] == 0
    assert ev[0]["share"] == pytest.approx(1.0)
    assert ev[0]["metric"] == "rows"
    assert ev[0]["intervals"] == 2
    # sustained skew: the episode fires ONCE
    sample(300, 100, 0)
    sample(400, 100, 0)
    assert tl.drain_events() == []
    # a balanced interval re-arms the detector...
    sample(500, 100, 100)
    sample(600, 100, 0)   # closes the balanced frame -> streak reset
    assert tl.drain_events() == []
    # ...and a new sustained episode fires a second event
    sample(700, 100, 0)
    sample(800, 100, 0)
    ev2 = tl.drain_events()
    assert len(ev2) == 1 and ev2[0]["hotShard"] == 0


def test_skew_idle_gap_breaks_episode():
    tl = TimelineStore("q1", interval_ms=100, ring=32,
                       skew_ratio=1.8, skew_intervals=2)
    tl.observe(0, shards={"rows-in": [100, 0]})
    tl.observe(100, shards={"rows-in": [200, 0]})  # closes: streak 1
    # idle interval (no movement): coalesced close resets the streak
    tl.observe(250, shards={"rows-in": [200, 0]})
    tl.observe(350, shards={"rows-in": [300, 0]})  # skewed again: streak 1
    tl.observe(450, shards={"rows-in": [400, 0]})  # streak 2 -> fires now
    assert [e["kind"] for e in tl.drain_events()] == ["telemetry.skew"]


# --------------------------------------------------- e2e latency histogram
def test_e2e_histogram_percentiles_and_snapshot():
    from ksql_tpu.common.metrics import E2E_BUCKETS_S, E2eHistogram

    h = E2eHistogram()
    assert h.percentile(0.5) is None
    for _ in range(90):
        h.record(0.008)       # <= 0.01 bucket
    for _ in range(9):
        h.record(0.4)         # <= 0.5 bucket
    h.record(10_000.0)        # +Inf bucket
    p50, p99 = h.percentile(0.50), h.percentile(0.99)
    assert p50 is not None and p99 is not None
    assert p50 < p99, "histogram must be non-degenerate"
    assert p50 <= 10.0          # inside the 10ms bound
    assert p99 >= 250.0
    # +Inf clamps to the last finite bound — a bound, not an estimate
    assert h.percentile(1.0) == E2E_BUCKETS_S[-1] * 1000.0
    snap = h.snapshot()
    assert snap["count"] == 100
    assert len(snap["counts"]) == len(snap["bucketsS"]) + 1
    assert sum(snap["counts"]) == 100
    assert snap["sum"] == pytest.approx(90 * 0.008 + 9 * 0.4 + 10_000.0)


def test_e2e_histogram_live_prometheus_and_registry(tmp_path):
    """Acceptance: a live engine produces a NON-degenerate e2e histogram
    (p50 < p99), exposed as a real Prometheus histogram whose sample names
    are pinned in metrics_registry.json."""
    from ksql_tpu.common.metrics import prometheus_text

    e = KsqlEngine(KsqlConfig({cfg.RUNTIME_BACKEND: "oracle"}))
    e.execute_sql(
        "CREATE STREAM PV (URL STRING, V BIGINT) "
        "WITH (kafka_topic='pv', value_format='JSON');"
    )
    e.execute_sql("CREATE STREAM O AS SELECT URL, V FROM PV;")
    t = e.broker.topic("pv")
    now = int(time.time() * 1000)
    # event times spread across buckets: ~8ms, ~400ms, ~3s old
    for i, age in enumerate([8] * 12 + [400] * 4 + [3000] * 2):
        t.produce(Record(key=None,
                         value=json.dumps({"URL": "/a", "V": i}),
                         timestamp=now - age))
    e.run_until_quiescent()
    qid = list(e.queries)[0]
    hist = e.queries[qid].progress.e2e_hist
    assert hist.count >= 18
    assert hist.percentile(0.50) < hist.percentile(0.99)

    snap = e.metrics_snapshot()
    hs = snap["queries"][qid]["e2e-latency-histogram"]
    assert hs["count"] == hist.count

    text = prometheus_text(snap)
    assert "# TYPE ksql_query_e2e_latency_seconds histogram" in text
    buckets = re.findall(
        r'ksql_query_e2e_latency_seconds_bucket\{le="([^"]+)",query="%s"\} '
        r"(\d+)" % re.escape(qid), text)
    assert buckets and buckets[-1][0] == "+Inf"
    counts = [int(c) for _, c in buckets]
    assert counts == sorted(counts), "histogram buckets must be cumulative"
    assert counts[-1] == hist.count
    assert f'ksql_query_e2e_latency_seconds_sum{{query="{qid}"}}' in text
    assert f'ksql_query_e2e_latency_seconds_count{{query="{qid}"}}' in text
    # the quantile-gauge exposition is gone: histogram replaces it
    assert "ksql_query_e2e_latency_seconds{" not in text

    with open(os.path.join(_REPO_ROOT, "metrics_registry.json")) as f:
        registry = set(json.load(f)["series"])
    for name in ("ksql_query_e2e_latency_seconds_bucket",
                 "ksql_query_e2e_latency_seconds_sum",
                 "ksql_query_e2e_latency_seconds_count",
                 "ksql_query_shard_rows_total"):
        assert name in registry, f"{name} not pinned in metrics_registry"


# ----------------------------------------------------- engine integration
def _telemetry_engine(extra=None):
    props = {
        cfg.RUNTIME_BACKEND: "oracle",
        cfg.TELEMETRY_INTERVAL_MS: 50,
    }
    props.update(extra or {})
    e = KsqlEngine(KsqlConfig(props))
    e.execute_sql(
        "CREATE STREAM PV (URL STRING, V BIGINT) "
        "WITH (kafka_topic='pv', value_format='JSON');"
    )
    e.execute_sql("CREATE STREAM O AS SELECT URL, V FROM PV;")
    return e


def _feed_now(e, n=8, topic="pv"):
    t = e.broker.topic(topic)
    now = int(time.time() * 1000)
    for i in range(n):
        t.produce(Record(key=None,
                         value=json.dumps({"URL": f"/p{i % 3}", "V": i}),
                         timestamp=now - 5))
    e.run_until_quiescent()


def test_engine_folds_ticks_into_timeline_inline():
    e = _telemetry_engine()
    _feed_now(e)
    qid = list(e.queries)[0]
    assert qid in e.timelines
    tl = e.timelines[qid]
    # the flight recorder's observer is the fold — same recorder object
    assert e.trace_recorder(qid).observer == tl.fold
    body = tl.since(None)
    assert body["frames"], "ticks must fold into the open frame"
    f = body["frames"][-1]
    assert f["ticks"] >= 1 and f["rows"] >= 8
    assert "poll" in f["stages"]
    st = tl.stats()
    assert st["folds"] >= 1
    # fold is cheap: self-measured overhead under the tick time it
    # folded (a generous bound, to stay timing-robust)
    assert st["foldMs"] < max(st["tickMsFolded"], 1.0)


def test_timeline_disabled_is_inert():
    e = KsqlEngine(KsqlConfig({
        cfg.RUNTIME_BACKEND: "oracle",
        cfg.TELEMETRY_ENABLE: False,
    }))
    e.execute_sql(
        "CREATE STREAM PV (URL STRING, V BIGINT) "
        "WITH (kafka_topic='pv', value_format='JSON');"
    )
    e.execute_sql("CREATE STREAM O AS SELECT URL FROM PV;")
    _feed_now(e)
    qid = list(e.queries)[0]
    assert e.timelines == {}
    assert e.trace_recorder(qid).observer is None


# ------------------------------------------------------ REST cursor endpoints
def test_timeline_and_query_trace_endpoints_with_cursors():
    """Satellite: /timeline/<qid>?since= and /query-trace/<id>?since=
    share one cursor contract — closed history replays once, the open
    tail re-reads, bad cursors answer 400, unknown owners 404."""
    from ksql_tpu.server.rest import KsqlServer

    e = _telemetry_engine()
    _feed_now(e)
    time.sleep(0.06)
    _feed_now(e)  # rolls the 50ms interval: at least one closed frame
    qid = list(e.queries)[0]
    s = KsqlServer(engine=e, port=0)
    s.start()
    try:
        with urllib.request.urlopen(f"{s.url}/timeline/{qid}") as r:
            body = json.loads(r.read())
        assert body["ownerId"] == qid
        assert body["telemetryEnabled"] is True
        assert body["intervalMs"] == 50
        assert body["frames"]
        closed = [f for f in body["frames"] if not f.get("open")]
        assert closed, "interval rollover must have closed a frame"
        assert body["nextSince"] == closed[-1]["seq"]
        # replay from the cursor: closed history is not re-sent
        with urllib.request.urlopen(
            f"{s.url}/timeline/{qid}?since={body['nextSince']}"
        ) as r:
            tail = json.loads(r.read())
        assert all(f.get("open") for f in tail["frames"])
        assert tail["nextSince"] == body["nextSince"]

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{s.url}/timeline/{qid}?since=abc")
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{s.url}/timeline/NOPE_9")
        assert ei.value.code == 404

        # /query-trace shares the contract at tick granularity
        with urllib.request.urlopen(f"{s.url}/query-trace/{qid}") as r:
            tr = json.loads(r.read())
        ticks = tr["ticks"]
        assert len(ticks) >= 2 and tr["nextSince"] == ticks[-1]["tick"]
        mid = ticks[len(ticks) // 2]["tick"]
        with urllib.request.urlopen(
            f"{s.url}/query-trace/{qid}?since={mid}"
        ) as r:
            tr2 = json.loads(r.read())
        assert all(t["tick"] > mid for t in tr2["ticks"])
        assert [t["tick"] for t in tr2["ticks"]] == \
            [t["tick"] for t in ticks if t["tick"] > mid]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{s.url}/query-trace/{qid}?since=x")
        assert ei.value.code == 400
    finally:
        s.stop()


def test_timeline_endpoint_disabled_and_unticked():
    from ksql_tpu.server.rest import KsqlServer

    e = KsqlEngine(KsqlConfig({
        cfg.RUNTIME_BACKEND: "oracle",
        cfg.TELEMETRY_ENABLE: False,
    }))
    e.execute_sql(
        "CREATE STREAM PV (URL STRING, V BIGINT) "
        "WITH (kafka_topic='pv', value_format='JSON');"
    )
    e.execute_sql("CREATE STREAM O AS SELECT URL FROM PV;")
    qid = list(e.queries)[0]
    s = KsqlServer(engine=e, port=0)
    s.start()
    try:
        with urllib.request.urlopen(f"{s.url}/timeline/{qid}") as r:
            body = json.loads(r.read())
        assert body["telemetryEnabled"] is False
        assert body["frames"] == []
    finally:
        s.stop()


# ------------------------------------------- live acceptance: skew detector
@pytest.mark.slow
def test_live_skewed_workload_raises_skew_alert():
    """ISSUE 18 acceptance: a hot-key GROUP BY on a 2-shard mesh drives
    one shard past ksql.telemetry.skew.ratio x fair share for the
    configured window -> telemetry.skew plog + /alerts evidence naming the
    hot shard and its share, and /timeline replays the imbalance intervals
    with the per-shard series."""
    from ksql_tpu.server.rest import KsqlServer
    from tests.test_device_parity import DDL

    e = KsqlEngine(KsqlConfig({
        cfg.RUNTIME_BACKEND: "distributed",
        cfg.DEVICE_SHARDS: 2,
        cfg.BATCH_CAPACITY: 64,
        cfg.STATE_SLOTS: 1024,
        cfg.TELEMETRY_INTERVAL_MS: 50,
        cfg.TELEMETRY_SKEW_INTERVALS: 2,
    }))
    e.execute_sql(DDL)
    e.execute_sql(
        "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
        "WINDOW TUMBLING (SIZE 1 HOUR) GROUP BY URL EMIT CHANGES;"
    )
    qid = list(e.queries)[0]
    t = e.broker.topic("page_views")
    # every record carries the SAME key: one shard takes 100% of the rows
    for round_ in range(8):
        now = int(time.time() * 1000)
        for i in range(25):
            t.produce(Record(key=None, value=json.dumps(
                {"URL": "/hot", "USER_ID": 1, "LATENCY": 1.0}
            ), timestamp=now - 5))
        e.run_until_quiescent()
        time.sleep(0.06)   # roll the 50ms interval
        e.poll_once()      # gauge sample + skew drain on the new interval
        if e.telemetry_events:
            break
    assert e.telemetry_events, "skew detector never fired on a hot key"
    ev = e.telemetry_events[-1]
    assert ev["queryId"] == qid
    assert ev["share"] >= 0.9
    assert ev["metric"] in ("rows", "occupancy")
    hot = ev["hotShard"]
    assert hot in (0, 1)
    assert f"hot shard {hot}" in ev["detail"]
    # the verdict is a processing-log event AND a timeline annotation
    assert any(w == f"telemetry.skew:{qid}" for w, _ in e.processing_log)
    assert "telemetry.skew" in e.timelines[qid].annotation_kinds()

    # /timeline replays the imbalance: the per-shard series for the
    # metric the detector judged shows the hot lane.  (Input rows spread
    # round-robin across poll lanes; the hot KEY concentrates as store
    # occupancy on its owner shard after the exchange.)
    body = e.timelines[qid].since(None)
    sharded = [f for f in body["frames"] if "shards" in f]
    assert sharded, "gauge samples must land per-shard series"
    key = {"rows": "rows", "occupancy": "storeOccupancy"}[ev["metric"]]
    skewed = [
        f for f in sharded
        if f["shards"].get(key) and sum(f["shards"][key]) > 0
        and f["shards"][key][hot] / sum(f["shards"][key]) >= 0.9
    ]
    assert skewed, "timeline must replay the imbalance intervals"
    assert any(f["shards"].get("exchangeBytes") is not None
               for f in sharded)

    # the per-shard row counters ride Prometheus too
    from ksql_tpu.common.metrics import prometheus_text

    text = prometheus_text(e.metrics_snapshot())
    assert f'ksql_query_shard_rows_total{{query="{qid}",shard="0"}}' in text
    assert f'ksql_query_shard_rows_total{{query="{qid}",shard="1"}}' in text

    # /alerts carries the telemetry evidence section
    s = KsqlServer(engine=e, port=0)
    s.start()
    try:
        with urllib.request.urlopen(f"{s.url}/alerts") as r:
            alerts = json.loads(r.read())
        tele = alerts.get("telemetry") or []
        assert any(ev2["queryId"] == qid and ev2["hotShard"] == hot
                   for ev2 in tele)
    finally:
        s.stop()


# --------------------------------------- durability: rescale and overload
@pytest.mark.slow
def test_timeline_survives_live_rescale_cutover(tmp_path):
    """Durability satellite: a live 2->4 cutover keeps the SAME timeline
    under the SAME qid — pre-cutover frames stay retained, the cutover
    lands as rescale/rescale.done annotations, and post-cutover gauge
    samples carry the 4-wide shard series without negative deltas."""
    from tests.test_device_parity import DDL

    e = KsqlEngine(KsqlConfig({
        cfg.RUNTIME_BACKEND: "distributed",
        cfg.DEVICE_SHARDS: 2,
        cfg.BATCH_CAPACITY: 64,
        cfg.STATE_SLOTS: 1024,
        cfg.STATE_CHECKPOINT_DIR: str(tmp_path),
        cfg.TELEMETRY_INTERVAL_MS: 50,
        cfg.RESCALE_ENABLE: True,
        cfg.DEVICE_SHARDS_MAX: 4,
    }))
    e.execute_sql(DDL)
    e.execute_sql(
        "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
        "WINDOW TUMBLING (SIZE 1 HOUR) GROUP BY URL EMIT CHANGES;"
    )
    h = list(e.queries.values())[0]
    qid = h.query_id
    t = e.broker.topic("page_views")

    def drive(n):
        now = int(time.time() * 1000)
        for i in range(n):
            t.produce(Record(key=None, value=json.dumps(
                {"URL": f"/p{i % 5}", "USER_ID": i, "LATENCY": 1.0}
            ), timestamp=now - 5))
        e.run_until_quiescent()

    drive(40)
    time.sleep(0.06)
    e.poll_once()  # close the first interval with a 2-wide gauge sample
    tl = e.timelines[qid]
    pre = tl.since(None)
    pre_closed = [f["seq"] for f in pre["frames"] if not f.get("open")]
    assert pre_closed, "pre-cutover frames must exist"
    pre_width = max(
        len(f["shards"]["rows"]) for f in pre["frames"] if "shards" in f
    )
    assert pre_width == 2

    e._rescale_query(h, 4, "grow")
    # the drained cutover hands the query to _maybe_restart on the next
    # poll iteration (ERROR + zero backoff); rebuild at the override
    for _ in range(50):
        e.poll_once()
        if getattr(h.executor.device, "n_shards", 0) == 4:
            break
    assert h.executor.device.n_shards == 4
    assert e.timelines[qid] is tl, "cutover must not replace the store"

    drive(40)
    time.sleep(0.06)
    e.poll_once()
    drive(10)

    body = tl.since(None)
    seqs = [f["seq"] for f in body["frames"]]
    assert set(pre_closed) <= set(seqs), "pre-cutover frames were lost"
    kinds = tl.annotation_kinds()
    assert "rescale" in kinds and "rescale.done" in kinds
    widths = {len(f["shards"]["rows"])
              for f in body["frames"] if "shards" in f}
    assert {2, 4} <= widths, f"expected both mesh widths, saw {widths}"
    assert all(r >= 0 for f in body["frames"] if "shards" in f
               for r in f["shards"]["rows"])


def test_overload_engage_clear_annotations_in_order():
    """Durability satellite: an overload episode lands engage AND clear
    annotations on every live timeline, on intervals in cause order."""
    e = _telemetry_engine({
        cfg.OVERLOAD_INTERVAL_MS: 0,
        cfg.OVERLOAD_HYSTERESIS_TICKS: 1,
        cfg.OVERLOAD_MAX_INFLIGHT: 4,
    })
    try:
        _feed_now(e)
        qid = list(e.queries)[0]
        tl = e.timelines[qid]
        ov = e.overload
        inflight = {"n": 10}  # 10/4 -> CRITICAL
        ov.set_inflight_source(lambda: inflight["n"])
        assert ov.maybe_sample()
        assert "overload.engage" in tl.annotation_kinds()
        time.sleep(0.06)  # the clear lands on a LATER interval
        inflight["n"] = 0
        for _ in range(6):
            ov.maybe_sample()
            if not any(ov.engaged.values()):
                break
        assert not any(ov.engaged.values())
        kinds = tl.annotation_kinds()
        assert "overload.engage" in kinds and "overload.clear" in kinds
        frames = tl.since(None)["frames"]
        engage_seq = min(f["seq"] for f in frames if any(
            a["kind"] == "overload.engage" for a in f["annotations"]))
        clear_seq = max(f["seq"] for f in frames if any(
            a["kind"] == "overload.clear" for a in f["annotations"]))
        assert engage_seq < clear_seq
    finally:
        e.shutdown()


# ------------------------------------------------- plog registry hygiene
def _plog_registry():
    with open(os.path.join(_REPO_ROOT, "plog_registry.json")) as f:
        return json.load(f)["categories"]


_CATEGORY_RE = re.compile(r"^[a-z][a-z0-9._-]*$")
#: literal `where` first-arguments at every emission call site (the
#: overload manager's ``_note`` forwards into ``_plog_append``, the
#: engine's ``_lowering_failed`` into ``_on_error``): a string
#: (or f-string) whose category prefix ends at ':', '{' or the quote
_EMIT_RE = re.compile(
    r"(?:_plog_append|_on_error|on_error|_note|_lowering_failed)\(\s*f?[\"']"
    r"([a-z][a-z0-9._-]*)(?=[:{\"'])"
)


def _emitted_categories():
    import pathlib

    out = {}
    root = pathlib.Path(_REPO_ROOT) / "ksql_tpu"
    for path in sorted(root.rglob("*.py")):
        src = path.read_text()
        for m in _EMIT_RE.finditer(src):
            out.setdefault(m.group(1), str(path))
    return out


def test_plog_registry_complete_static():
    """Hygiene satellite: every category the source can emit into the
    processing log is registered (typo'd categories silently vanish from
    operator greps), and the registry carries no dead entries."""
    registry = _plog_registry()
    emitted = _emitted_categories()
    unregistered = {
        c: where for c, where in emitted.items() if c not in registry
    }
    assert not unregistered, (
        "processing-log categories emitted but missing from "
        f"plog_registry.json: {unregistered}"
    )
    dead = set(registry) - set(emitted)
    assert not dead, (
        f"plog_registry.json lists categories no source emits: {dead}"
    )
    # every timeline annotation category is a registered plog category
    assert tlm.ANNOTATION_CATEGORIES <= set(registry)
    assert tlm.ENGINE_WIDE_CATEGORIES <= tlm.ANNOTATION_CATEGORIES
    # registry entries all carry a non-empty meaning
    assert all(isinstance(v, str) and v for v in registry.values())


def test_plog_registry_complete_runtime():
    """Runtime companion: drive an engine through deserialize failures and
    a skew-ish telemetry path, then check every category-shaped entry in
    the LIVE log against the registry (expression-text `where`s from the
    oracle interpreter are exempt by shape)."""
    registry = _plog_registry()
    e = _telemetry_engine()
    t = e.broker.topic("pv")
    t.produce(Record(key=None, value="{not json", timestamp=1))
    _feed_now(e)
    assert any(w.startswith("deserialize:") for w, _ in e.processing_log)
    for where, _ in e.processing_log:
        cat = tlm.plog_category(where)
        if not _CATEGORY_RE.match(cat):
            continue  # expression-text where: outside the contract
        assert cat in registry, (
            f"live processing-log category {cat!r} (from {where!r}) is "
            "not in plog_registry.json"
        )


# ------------------------------------------------------- obs_report tool
def _load_obs_report():
    import importlib.util
    import os
    import sys

    path = os.path.join(
        os.path.dirname(__file__), "..", "scripts", "obs_report.py"
    )
    spec = importlib.util.spec_from_file_location("obs_report", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["obs_report"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_obs_report_summarize_and_render():
    obs = _load_obs_report()
    body = {
        "ownerId": "CTAS_C_7",
        "intervalMs": 5000,
        "coalesced": 3,
        "nextSince": 101,
        "e2eBucketsS": [0.01, 0.1, 1.0],
        "frames": [
            {
                "seq": 100, "startMs": 500000, "endMs": 505000,
                "ticks": 4, "errTicks": 0, "rows": 40, "tickMs": 8.0,
                "throughputRps": 8.0, "watermarkLagMs": 120,
                "stages": {"poll": {"ticks": 4, "p50Ms": 1.0,
                                    "p99Ms": 2.0, "totalMs": 5.0}},
                "annotations": [],
                "shards": {"rows": [30, 10], "exchangeBytes": [64, 8],
                           "storeOccupancy": [5, 2],
                           "watermarkMs": [1, 1]},
                "e2e": {"counts": [10, 0, 0, 0], "count": 10,
                        "sumS": 0.05},
            },
            {
                "seq": 101, "startMs": 505000, "endMs": 510000,
                "ticks": 2, "errTicks": 1, "rows": 20, "tickMs": 3.0,
                "throughputRps": 4.0,
                "stages": {"poll": {"ticks": 2, "p50Ms": 3.0,
                                    "p99Ms": 4.0, "totalMs": 4.0}},
                "annotations": [{"wallMs": 506000, "kind": "rescale",
                                 "detail": "2 -> 4"}],
                "shards": {"rows": [18, 2], "exchangeBytes": [32, 4],
                           "storeOccupancy": [6, 2],
                           "watermarkMs": [1, 1]},
                "e2e": {"counts": [0, 5, 0, 0], "count": 5,
                        "sumS": 0.2},
                "open": True,
            },
        ],
    }
    s = obs.summarize(body)
    assert s["frames"] == 2 and s["rows"] == 60 and s["ticks"] == 6
    assert s["errTicks"] == 1 and s["coalesced"] == 3
    assert s["shardRows"] == [48, 12]
    assert s["hotShard"]["shard"] == 0
    assert s["hotShard"]["share"] == pytest.approx(0.8)
    assert s["e2eCounts"] == [10, 5, 0, 0]
    assert s["e2eP50Ms"] is not None and s["e2eP99Ms"] is not None
    assert s["e2eP50Ms"] < s["e2eP99Ms"]
    assert s["annotations"] == [
        {"wallMs": 506000, "kind": "rescale", "detail": "2 -> 4",
         "seq": 101},
    ]
    assert [st["stage"] for st in s["stages"]] == ["poll"]
    assert s["stages"][0]["ticks"] == 6
    assert s["stages"][0]["p99Ms"] == 4.0

    import io

    out = io.StringIO()
    obs.render(body, out=out)
    text = out.getvalue()
    assert "timeline CTAS_C_7" in text
    assert "<< hot" in text
    assert "[rescale] 2 -> 4" in text
    assert "(open)" in text
    assert "e2e latency" in text

    # empty body renders the idle message, not a crash
    out2 = io.StringIO()
    obs.render({"ownerId": "X", "frames": [], "intervalMs": 5000,
                "nextSince": -1}, out=out2)
    assert "no retained frames" in out2.getvalue()


def test_obs_report_e2e_percentile_matches_histogram():
    from ksql_tpu.common.metrics import E2E_BUCKETS_S, E2eHistogram

    obs = _load_obs_report()
    h = E2eHistogram()
    for v in [0.004] * 50 + [0.2] * 40 + [4.0] * 10:
        h.record(v)
    snap = h.snapshot()
    for p in (0.5, 0.9, 0.99):
        assert obs.e2e_percentile(
            list(E2E_BUCKETS_S), snap["counts"], p
        ) == pytest.approx(h.percentile(p))
