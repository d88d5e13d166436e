"""Perf-evidence loop (ISSUE 11): the per-stage regression gate, the new
push-registry / cutover tracing spans, the deadline auto-sizing hint, and
the Prometheus exposition registry.

Gate contract pinned here: medians over >= 3 runs, an inflated stage
accumulator fails NAMING that workload + stage, 2x container noise on
every number still passes, baseline write/read round-trips through the
CLI, and a missing baseline is a usage error (exit 2) — never a silent
pass."""

import json
import os
import subprocess
import sys
import urllib.request

import pytest

from ksql_tpu.common import config as cfg
from ksql_tpu.common import faults, tracing
from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.common.perfgate import (
    DEFAULT_THRESHOLDS,
    PerfGateUsageError,
    compare,
    extract_run,
    make_baseline,
    summarize,
)
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.runtime.topics import Record

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
PERFGATE = os.path.join(ROOT, "scripts", "perfgate.py")


# ----------------------------------------------------- synthetic run lines
def _stages(scale=1.0):
    return {
        "device.compile": {"p50Ms": 40.0, "p99Ms": 80.0 * scale,
                           "totalMs": 400.0, "jit_miss": 2},
        "device.execute": {"p50Ms": 5.0, "p99Ms": 10.0 * scale,
                           "totalMs": 50.0, "jit_hit": 9},
        "exchange": {"p50Ms": 2.0, "p99Ms": 4.0 * scale, "totalMs": 20.0,
                     "rows": 1000, "bytes": 33000},
        "sink.produce": {"p50Ms": 1.5, "p99Ms": 3.0 * scale,
                         "totalMs": 15.0},
    }


def _run_line(thr_scale=1.0, stage_scale=1.0, stage_overrides=None):
    """One bench JSON line shaped like bench.py's final emission."""
    stages = _stages(stage_scale)
    for name, p99 in (stage_overrides or {}).items():
        stages.setdefault(name, {})["p99Ms"] = p99
    return {
        "metric": "tumbling_count_group_by_events_per_sec",
        "value": 30_000.0 * thr_scale,
        "unit": "events/s",
        "vs_baseline": 1.0,
        "extra": {
            "platform": "cpu",
            "devices": 8,
            "hopping_sum_group_by_events_s": 36_000.0 * thr_scale,
            "window_family_events_s": 900.0 * thr_scale,
            "window_family_stages": stages,
            "push_fanout_delivered_rows_s": 4_500.0 * thr_scale,
            "push_fanout_stages": {
                "push.pipeline.step": {"p99Ms": 100.0 * stage_scale,
                                       "rows": 4000},
                "push.tap.deliver": {"p99Ms": 20.0 * stage_scale,
                                     "rows": 4000, "ring_lag": 0},
                "push.residual.kernel": {"p99Ms": 5.0 * stage_scale,
                                         "rows": 4000, "taps": 64,
                                         "jit_hit": 3},
            },
            "engine_e2e_dist_events_s": 5_000.0 * thr_scale,
            "engine_e2e_dist_stages": stages,
        },
    }


def _baseline():
    return make_baseline(
        summarize([_run_line(), _run_line(), _run_line()]),
        {"platform": "cpu", "smoke": True},
    )


# ------------------------------------------------------------- gate logic
def test_extract_and_summarize_medians():
    runs = [_run_line(thr_scale=s) for s in (0.9, 1.0, 1.4)]
    one = extract_run(runs[0])
    assert set(one) == {
        "tumbling_count_group_by", "hopping_sum_group_by",
        "window_family", "push_fanout", "engine_e2e_dist",
    }
    assert one["window_family"]["stages"]["device.execute"] == 10.0
    summ = summarize(runs)
    # medians: the 1.0-scale run is the middle observation everywhere
    assert summ["tumbling_count_group_by"]["throughput"] == 30_000.0
    assert summ["engine_e2e_dist"]["runs"] == 3
    assert summ["push_fanout"]["stages"]["push.tap.deliver"] == 20.0


def test_summarize_requires_three_runs():
    with pytest.raises(PerfGateUsageError, match=">= 3 runs"):
        summarize([_run_line(), _run_line()])


def test_bench_error_slots_are_skipped_not_crashed():
    line = _run_line()
    line["extra"]["engine_e2e_dist_events_s"] = (
        "error: TimeoutExpired: ..."
    )
    assert "engine_e2e_dist" not in extract_run(line)


def test_injected_stage_regression_fails_naming_the_stage():
    """ISSUE acceptance: inflate ONE stage's accumulator and the gate must
    fail naming that stage (not just 'perf regressed')."""
    base = _baseline()
    current = summarize([
        _run_line(stage_overrides={"device.execute": 10.0 * 6}),
        _run_line(stage_overrides={"device.execute": 10.0 * 6}),
        _run_line(stage_overrides={"device.execute": 10.0 * 6}),
    ])
    rows, regressions = compare(base, current)
    named = {(r["workload"], r["stage"]) for r in regressions}
    assert ("window_family", "device.execute") in named
    assert ("engine_e2e_dist", "device.execute") in named
    # ONLY the inflated stage regressed — the gate is surgical
    assert all(stage == "device.execute" for _, stage in named)


def test_injected_throughput_regression_names_the_workload():
    base = _baseline()
    line = _run_line()
    line["extra"]["push_fanout_delivered_rows_s"] = 4_500.0 * 0.2
    current = summarize([line, line, line])
    _rows, regressions = compare(base, current)
    assert [(r["workload"], r["stage"]) for r in regressions] == [
        ("push_fanout", "(throughput)")
    ]


def test_fused_kernel_disable_mid_baseline_fails_the_gate():
    """ISSUE 12 satellite (injection test): the baseline is snapshotted
    with the fused residual kernel ON; a current round with the kernel
    force-disabled collapses push_fanout delivery to the host-residual
    rate (measured ~5x slower at 64 taps) and the gate must FAIL naming
    push_fanout — a silent de-fusing can never pass."""
    base = _baseline()
    line = _run_line()
    line["extra"]["push_fanout_delivered_rows_s"] = 4_500.0 / 5
    del line["extra"]["push_fanout_stages"]["push.residual.kernel"]
    current = summarize([line, line, line])
    rows, regressions = compare(base, current)
    named = [(r["workload"], r["stage"]) for r in regressions]
    assert ("push_fanout", "(throughput)") in named
    # the vanished kernel stage is visible (info row), the throughput
    # collapse is what gates
    assert any(
        r["stage"] == "push.residual.kernel"
        and r["verdict"] == "missing-current"
        for r in rows
    )


def test_push_residual_kernel_stage_is_gated():
    """push.residual.kernel joined the gated stage set: inflating its
    p99 alone fails the gate naming exactly that stage."""
    base = _baseline()
    line = _run_line()
    line["extra"]["push_fanout_stages"]["push.residual.kernel"]["p99Ms"] = (
        5.0 * 6
    )
    current = summarize([line, line, line])
    _rows, regressions = compare(base, current)
    assert [(r["workload"], r["stage"]) for r in regressions] == [
        ("push_fanout", "push.residual.kernel")
    ]


def test_workload_vanishing_from_every_run_fails_the_gate():
    """A baselined workload whose bench errored/timed out in EVERY
    current run (zero evidence — the rounds-4/5 failure class) must fail
    the gate naming the workload, never pass as 'missing'."""
    base = _baseline()
    line = _run_line()
    line["extra"]["push_fanout_delivered_rows_s"] = "error: TimeoutExpired"
    current = summarize([line, line, line])
    _rows, regressions = compare(base, current)
    named = [(r["workload"], r["stage"]) for r in regressions]
    assert ("push_fanout", "(throughput)") in named
    assert "no usable runs" in regressions[0]["verdict"]


def test_only_narrowed_workloads_are_exempt_from_zero_evidence():
    """--only narrowing deliberately omits workloads: compare() must not
    fail the unselected ones as zero-evidence regressions."""
    base = _baseline()
    line = _run_line()
    for k in ("hopping_sum_group_by_events_s", "window_family_events_s",
              "push_fanout_delivered_rows_s"):
        del line["extra"][k]
    current = summarize([line, line, line])
    rows, regressions = compare(
        base, current,
        expected={"tumbling_count_group_by", "engine_e2e_dist"},
    )
    assert regressions == []
    assert {r["workload"] for r in rows
            if r["verdict"] == "not-selected"} == {
        "hopping_sum_group_by", "window_family", "push_fanout",
    }


def test_stage_appearing_from_zero_baseline_fails():
    """A gated stage whose baseline median-of-p99 is 0 (counter-only at
    snapshot time) growing real wall time must fail — the ratio guard
    alone would be blind to it."""
    base = make_baseline(
        summarize([_run_line(stage_overrides={"exchange": 0.0})] * 3),
        {"platform": "cpu"},
    )
    current = summarize(
        [_run_line(stage_overrides={"exchange": 500.0})] * 3
    )
    _rows, regressions = compare(base, current)
    named = {(r["workload"], r["stage"]) for r in regressions}
    assert ("window_family", "exchange") in named
    assert any("appeared" in r["verdict"] for r in regressions)


def test_workload_with_too_few_usable_runs_fails_not_gates_on_one():
    """A workload whose bench landed in only 1 of 3 rounds must FAIL
    rather than gate a 'median' of one jittery sample."""
    base = _baseline()
    bad = _run_line()
    bad["extra"]["engine_e2e_dist_events_s"] = "error: TimeoutExpired"
    current = summarize([bad, bad, _run_line()])
    assert current["engine_e2e_dist"]["runs"] == 1
    _rows, regressions = compare(base, current, min_workload_runs=3)
    named = {(r["workload"], r["stage"]) for r in regressions}
    assert ("engine_e2e_dist", "(throughput)") in named
    assert any("usable runs" in r["verdict"] for r in regressions)
    # with the floor at 1 (the default), the same current gates normally
    _rows, regressions = compare(base, current, min_workload_runs=1)
    assert regressions == []


def test_two_x_container_variance_passes():
    """The variance-tolerance fixture: every stage 2x slower AND
    throughput halved — inside this container's observed jitter — must
    NOT trip the default thresholds (stage 2.5x, throughput 0.4x)."""
    base = _baseline()
    current = summarize([
        _run_line(thr_scale=0.5, stage_scale=2.0) for _ in range(3)
    ])
    _rows, regressions = compare(base, current)
    assert regressions == []


def test_sub_ms_stage_noise_is_never_gated():
    """A 0.2ms stage tripling is scheduler noise, not a regression."""
    base = make_baseline(
        summarize([_run_line(stage_overrides={"sink.produce": 0.2})] * 3),
        {"platform": "cpu"},
    )
    current = summarize(
        [_run_line(stage_overrides={"sink.produce": 0.6})] * 3
    )
    _rows, regressions = compare(base, current)
    assert regressions == []


def test_sub_floor_baseline_gates_on_absolute_blowup_only():
    """A gated stage whose BASELINE p99 is sub-floor (fused tap delivery
    lives around 0.3-0.6ms here) has no ratio resolution: a jittery
    0.5ms -> 1.8ms flip must pass, but a genuine blow-up past 10x the
    floor must still fail naming the stage."""
    base = make_baseline(
        summarize([_run_line(stage_overrides={"sink.produce": 0.5})] * 3),
        {"platform": "cpu"},
    )
    noisy = summarize(
        [_run_line(stage_overrides={"sink.produce": 1.8})] * 3
    )
    _rows, regressions = compare(base, noisy)
    assert regressions == []
    blown = summarize(
        [_run_line(stage_overrides={"sink.produce": 12.0})] * 3
    )
    _rows, regressions = compare(base, blown)
    assert [(r["workload"], r["stage"]) for r in regressions] == [
        ("window_family", "sink.produce"),
        ("engine_e2e_dist", "sink.produce"),
    ]
    assert "sub-floor" in regressions[0]["verdict"]


def test_non_gated_stages_are_informational():
    """Oracle stage:* chains / poll report as info rows but
    never fail the gate (corpus-shaped, not regression-shaped)."""
    base = make_baseline(
        summarize([_run_line(stage_overrides={"stage:Project": 5.0})] * 3),
        {"platform": "cpu"},
    )
    current = summarize(
        [_run_line(stage_overrides={"stage:Project": 500.0})] * 3
    )
    rows, regressions = compare(base, current)
    assert regressions == []
    info = [r for r in rows if r["stage"] == "stage:Project"]
    assert info and all(r["verdict"] == "info" for r in info)


# ------------------------------------------------------------ CLI contract
def _stub_bench(tmp_path, scale_env="STUB_SCALE"):
    """A bench stand-in printing one canned JSON line instantly; the
    perfgate CLI drives it exactly like the real bench.py."""
    path = tmp_path / "stub_bench.py"
    path.write_text(
        "import json, os\n"
        f"s = float(os.environ.get({scale_env!r}, '1.0'))\n"
        f"line = {json.dumps(_run_line())!r}\n"
        "line = json.loads(line)\n"
        "line['value'] /= s\n"
        "for st in line['extra']['engine_e2e_dist_stages'].values():\n"
        "    st['p99Ms'] = st.get('p99Ms', 0) * s\n"
        "print('noise line the parser must skip')\n"
        "print(json.dumps(line))\n"
    )
    return str(path)


def _perfgate(args, env=None):
    e = dict(os.environ)
    e.update(env or {})
    return subprocess.run(
        [sys.executable, PERFGATE, *args],
        capture_output=True, text=True, cwd=ROOT, env=e, timeout=120,
    )


def test_cli_baseline_roundtrip_and_pass(tmp_path):
    stub = _stub_bench(tmp_path)
    base = str(tmp_path / "base.json")
    w = _perfgate(["--baseline", base, "--bench-cmd",
                   f"{sys.executable} {stub}", "--runs", "3",
                   "--write-baseline"])
    assert w.returncode == 0, w.stderr
    data = json.load(open(base))
    assert data["workloads"]["tumbling_count_group_by"]["throughput"] > 0
    assert data["thresholds"] == DEFAULT_THRESHOLDS
    assert data["meta"]["platform"] == "cpu"
    g = _perfgate(["--baseline", base, "--bench-cmd",
                   f"{sys.executable} {stub}", "--runs", "3"])
    assert g.returncode == 0, g.stdout + g.stderr
    assert "PERFGATE OK" in g.stdout


def test_cli_injected_regression_exits_1_naming_stage(tmp_path):
    stub = _stub_bench(tmp_path)
    base = str(tmp_path / "base.json")
    assert _perfgate(["--baseline", base, "--bench-cmd",
                      f"{sys.executable} {stub}", "--runs", "3",
                      "--write-baseline"]).returncode == 0
    g = _perfgate(["--baseline", base, "--bench-cmd",
                   f"{sys.executable} {stub}", "--runs", "3"],
                  env={"STUB_SCALE": "6.0"})
    assert g.returncode == 1, g.stdout + g.stderr
    assert "PERFGATE FAIL" in g.stdout
    # the diff names both the throughput workload and the stage
    assert "tumbling_count_group_by / (throughput)" in g.stdout
    assert "engine_e2e_dist / device.execute" in g.stdout


def test_cli_missing_baseline_is_usage_error(tmp_path):
    stub = _stub_bench(tmp_path)
    g = _perfgate(["--baseline", str(tmp_path / "absent.json"),
                   "--bench-cmd", f"{sys.executable} {stub}",
                   "--runs", "3"])
    assert g.returncode == 2
    assert "usage error" in g.stderr and "--write-baseline" in g.stderr


def test_cli_usage_errors_are_decided_before_benching(tmp_path):
    """--runs below --min-runs and a smoke/full mode mismatch are both
    rc-2 usage errors raised BEFORE any bench run burns the budget (the
    bench command here would fail instantly if invoked)."""
    stub = _stub_bench(tmp_path)
    base = str(tmp_path / "base.json")
    assert _perfgate(["--baseline", base, "--bench-cmd",
                      f"{sys.executable} {stub}", "--runs", "3",
                      "--write-baseline"]).returncode == 0  # meta.smoke=False
    few = _perfgate(["--baseline", base, "--runs", "2",
                     "--bench-cmd", "/nonexistent never-runs"])
    assert few.returncode == 2 and "--min-runs" in few.stderr
    mode = _perfgate(["--baseline", base, "--smoke", "--runs", "3",
                      "--bench-cmd", "/nonexistent never-runs"])
    assert mode.returncode == 2 and "full sizes" in mode.stderr


def test_cli_from_runs_regates_without_benches(tmp_path):
    stub = _stub_bench(tmp_path)
    base = str(tmp_path / "base.json")
    saved = str(tmp_path / "runs.json")
    assert _perfgate(["--baseline", base, "--bench-cmd",
                      f"{sys.executable} {stub}", "--runs", "3",
                      "--write-baseline", "--save-runs", saved]
                     ).returncode == 0
    g = _perfgate(["--baseline", base, "--from-runs", saved,
                   "--bench-cmd", "/nonexistent never-runs"])
    assert g.returncode == 0, g.stdout + g.stderr


@pytest.mark.slow
def test_cli_smoke_mode_runs_real_bench_harness(tmp_path):
    """End-to-end smoke (tier-2): perfgate --smoke drives the REAL
    bench.py children under the PR-7 watchdog harness — snapshot a
    baseline from 3 real runs of the cheapest workload, then re-gate the
    saved runs against it."""
    base = str(tmp_path / "base.json")
    saved = str(tmp_path / "runs.json")
    env = {"JAX_PLATFORMS": "cpu"}
    w = subprocess.run(
        [sys.executable, PERFGATE, "--baseline", base, "--smoke",
         "--runs", "3", "--only", "push_fanout", "--write-baseline",
         "--save-runs", saved, "--bench-budget-s", "120"],
        capture_output=True, text=True, cwd=ROOT, timeout=500,
        env={**os.environ, **env},
    )
    assert w.returncode == 0, w.stderr[-2000:]
    data = json.load(open(base))
    assert data["workloads"]["push_fanout"]["throughput"] > 0
    # the real flight-recorder stages came through the harness
    assert "push.tap.deliver" in data["workloads"]["push_fanout"]["stages"]
    g = subprocess.run(
        [sys.executable, PERFGATE, "--baseline", base,
         "--from-runs", saved],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
        env={**os.environ, **env},
    )
    assert g.returncode == 0, g.stdout + g.stderr
    assert "PERFGATE OK" in g.stdout


def test_committed_baseline_gates_head_runs():
    """The COMMITTED baseline must accept this tree's own bench shape:
    re-gate the committed BENCH_r09 line (the round the baseline was
    snapshotted alongside) against PERF_BASELINE.json in-process."""
    from ksql_tpu.common.perfgate import load_baseline

    baseline = load_baseline(os.path.join(ROOT, "PERF_BASELINE.json"))
    line = json.load(open(os.path.join(ROOT, "BENCH_r09.json")))
    current = summarize([line, line, line])
    _rows, regressions = compare(baseline, current)
    assert regressions == [], regressions


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
