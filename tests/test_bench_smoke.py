"""BENCH smoke (tier-2, ``slow``-marked): drive bench.py's child entry on
tiny BENCH_SMOKE=1 sizes (its rehearsal, the only way it runs off the
chip) so the bench import/shape path — including the multi-chip
``engine_e2e_dist`` variant — can't silently rot between hardware runs.  Timing values are asserted only for sanity (> 0), never for
magnitude: CI machines are not the benchmark target."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run_one(name, extra_env=None, timeout=600):
    env = dict(os.environ, BENCH_SMOKE="1", JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--one", name],
        capture_output=True, text=True, timeout=timeout, cwd=ROOT, env=env,
    )
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("BENCH_RESULT"):
            return float(line[len("BENCH_RESULT"):].strip())
    raise AssertionError(
        f"no BENCH_RESULT from {name} (rc={proc.returncode}):\n"
        f"{proc.stderr[-2000:]}"
    )


def test_bench_smoke_tumbling_count():
    assert _run_one("bench_tumbling_count") > 0


def test_bench_smoke_engine_e2e_dist():
    v = _run_one(
        "bench_engine_e2e_dist",
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
    )
    assert v > 0


def test_bench_smoke_hopping_sum_group_by():
    assert _run_one("bench_hopping_sum_group_by") > 0


def test_bench_watchdog_contains_hung_bench(tmp_path):
    """ISSUE 7 acceptance: `python bench.py` must emit valid per-bench JSON
    inside its global budget even when one bench is fault-injected to hang
    — the per-bench watchdog contains the wedge, the incremental emission
    keeps every completed number, and the JSON-file mirror survives."""
    import json

    json_path = str(tmp_path / "bench.json")
    env = dict(
        os.environ,
        BENCH_SMOKE="1",
        JAX_PLATFORMS="cpu",
        BENCH_BUDGET_S="570",
        BENCH_PER_BENCH_MAX_S="40",
        BENCH_ONLY="tumbling_count,window_family",
        BENCH_FAULT_HANG="bench_window_family",
        BENCH_JSON_PATH=json_path,
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, timeout=560, cwd=ROOT, env=env,
    )
    # the hung bench is contained AND counted: the others ran, the process
    # says one failed
    assert proc.returncode == 1, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert lines, proc.stdout
    result = json.loads(lines[-1])
    # every line names what it ran on, and a rehearsal says it is one
    assert result["extra"]["platform"] == "cpu"
    assert result["extra"]["device_kind"] and result["extra"]["devices"] >= 1
    assert result["extra"]["rehearsal"] is True
    # the headline bench completed and its number survived the hang
    assert result["value"] > 0
    wf = result["extra"]["window_family_events_s"]
    assert isinstance(wf, str) and wf.startswith("error:"), wf
    assert "TimeoutExpired" in wf
    # the file mirror carries the same final line
    with open(json_path) as f:
        assert json.load(f) == result


def test_tracing_overhead_under_5pct():
    """Flight-recorder overhead gate (ISSUE 3 tooling satellite): the
    engine e2e path with tracing ENABLED must stay within 5% of the
    ksql.trace.enable=false path (which itself must be near-zero-cost —
    its instrumentation sites reduce to a thread-local None check).
    Best-of-3 rounds each to keep CI noise out of the comparison."""
    import json as _json
    import time

    from ksql_tpu.common import config as cfg
    from ksql_tpu.common.config import KsqlConfig
    from ksql_tpu.engine.engine import KsqlEngine
    from ksql_tpu.runtime.topics import Record

    n_events = 60_000
    payloads = [
        _json.dumps({"URL": f"/p{i % 97}", "V": i}) for i in range(n_events)
    ]

    def run(trace_enabled: bool) -> float:
        e = KsqlEngine(KsqlConfig({
            cfg.RUNTIME_BACKEND: "device",
            cfg.TRACE_ENABLE: trace_enabled,
            cfg.BATCH_CAPACITY: 8192,
        }))
        e.execute_sql(
            "CREATE STREAM PV (URL STRING, V BIGINT) "
            "WITH (kafka_topic='pv', value_format='JSON');"
        )
        e.execute_sql(
            "CREATE TABLE C AS SELECT URL, COUNT(*) AS CNT FROM PV "
            "GROUP BY URL EMIT CHANGES;"
        )
        t = e.broker.topic("pv")
        # warm the compile outside the timed region
        for i in range(64):
            t.produce(Record(key=None, value=payloads[i], timestamp=i))
        while e.poll_once(max_records=1 << 17):
            pass
        best = float("inf")
        chunk = (n_events - 64) // 3
        for r in range(3):
            lo = 64 + r * chunk
            t0 = time.perf_counter()
            for i in range(lo, lo + chunk):
                t.produce(Record(key=None, value=payloads[i], timestamp=i))
            while e.poll_once(max_records=1 << 17):
                pass
            best = min(best, time.perf_counter() - t0)
        return best

    run(False)  # prime jit/persistent caches so neither side pays compile
    t_off = run(False)
    t_on = run(True)
    overhead = (t_on - t_off) / t_off
    assert overhead < 0.05, (
        f"tracing overhead {overhead:.1%} (on={t_on:.3f}s off={t_off:.3f}s)"
    )

