#!/usr/bin/env python
"""Benchmarks for the five BASELINE.md configs + the end-to-end engine path.

Headline metric (the driver-recorded JSON line): BASELINE config #1 —
tumbling-window COUNT(*) GROUP BY url — sustained device-step throughput on
pre-encoded columnar batches.  The `extra` field carries the other configs:

  #2 hopping multi-UDAF (SUM/AVG/MIN/MAX)           device step, events/s
  #3 stream-table LEFT JOIN + WHERE                  device step, events/s
  #4 stream-stream windowed JOIN with GRACE          device step, events/s
  #5 SESSION window aggregation                      device step, events/s
  engine_e2e — config #1 through execute_sql + broker + DeviceExecutor
  with host ingest (JSON decode → HostBatch → encode) included, batched
  EMIT CHANGES with pipelined emission decode.
  engine_e2e_dist — the same end-to-end path on
  ksql.runtime.backend=distributed: micro-batches split round-robin
  across the device mesh, rows exchanged to their key-owner shard over
  one all-to-all, state sharded per device.  Needs at least two devices
  (a rehearsal gives the CPU eight virtual ones); `extra` also carries the
  mesh size (engine_e2e_dist_shards) so per-device throughput can be
  derived and compared against engine_e2e.
  engine_e2e_scaling — the same e2e corpus swept at 1→2→4→8 shards on
  the distributed backend (fresh engine per point; needs eight devices):
  the per-shard-count
  throughput + exchange-bytes + per-stage curve lands in `extra` as
  engine_e2e_scaling_curve, so the sharding story is measured as a
  CURVE, not one mesh-sized sample.
  hopping_sum_group_by — stream slicing vs the k-fold expansion baseline
  on the same hopping SUM corpus at k ∈ {4, 12} (per-variant events/s +
  speedups in `extra`).
  window_family — four same-family hopping queries through the engine,
  shared (one device pipeline, per-query combine fan-out) vs unshared,
  with the primary's per-stage flight-recorder breakdown in `extra`.
  mqo_dashboard — the cost-based multi-query optimizer (ISSUE 15): 32
  correlated hopping queries (different sizes/advances AND aggregate
  sets) over 4 sources, shared (≤8 device pipelines via gcd-width slice
  rings + shared partial sets) vs unshared (32 pipelines), with member
  twin-parity asserted and one primary's stage breakdown in `extra`.
  push_fanout — N filtered push sessions over one stream, swept at
  16/64(/256) taps in three serving modes: fused (ONE batched device
  kernel evaluates every tap's residual over the shared emission
  batch, ISSUE 12), host (registry taps with per-tap host residuals,
  the PR-10 posture), unshared (N private consumer+executor chains).
  Headline is the fused delivery rate at the widest tap count all
  three modes ran; the shared pipeline's stage block (incl.
  push.residual.kernel) lands in `extra` for perfgate.

Deadline-proofing: every bench runs in its own child under a per-bench
watchdog inside a global wall-clock budget (BENCH_BUDGET_S); the full
JSON line re-emits after every config so partial results survive a kill
(BENCH_JSON_PATH mirrors it to a file); and BENCH_FAULT_HANG=<bench fn> is
a built-in fault point proving the watchdog contains a hung bench
(tests/test_bench_smoke.py).

A measurement needs the chip: the probe fails the run (exit code 2, no
metric printed) unless JAX's platform is ``tpu``, and a sub-bench that
fails, or that needs more devices than ``jax.devices()`` has, lets the
others run but makes the process exit 1.  BENCH_SMOKE=1 is the rehearsal:
tiny sizes on whatever platform JAX has, every line marked
``"rehearsal": true`` next to the platform it ran on.  Every emitted line
carries ``platform``, ``device_kind`` and ``devices``.

Baseline derivation (BENCH_BASELINE_EVENTS_S): the reference's capacity
guidance puts aggregation throughput at ~¼ of the 40-50 MB/s project/filter
ceiling on a 4-core server (docs/operate-and-deploy/
capacity-planning.md:274-293) ≈ 11 MB/s; at the ~100-byte JSON events of
the quickstart pageviews workload that is ≈ 115k events/sec.  Joins run at
~½ of project/filter ≈ 230k events/s (capacity-planning.md:282-287).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
"""

import json
import os
import time

BENCH_BASELINE_EVENTS_S = 115_000.0
JOIN_BASELINE_EVENTS_S = 230_000.0

# BENCH_SMOKE=1 is the rehearsal: everything shrunk, any platform allowed;
# a measurement (the default) uses the full sizes and needs the chip
_SMOKE = bool(int(os.environ.get("BENCH_SMOKE", "0")))
CAPACITY = 1 << 12 if _SMOKE else 1 << 16  # rows per micro-batch (kernels)
STORE = 1 << 16 if _SMOKE else 1 << 20  # state-store slots
N_KEYS = 5_000 if _SMOKE else 50_000
N_BATCHES = 4 if _SMOKE else 8  # distinct pre-encoded batches, cycled
WARMUP = 2 if _SMOKE else 4  # even: warms BOTH sides of the ss-join bench
ITERS = 4 if _SMOKE else 30
ROUNDS = 1 if _SMOKE else 5

TS0 = 1_700_000_000_000


def _engine(extra_cfg=None):
    from ksql_tpu.common.config import KsqlConfig
    from ksql_tpu.engine.engine import KsqlEngine

    return KsqlEngine(KsqlConfig(dict(extra_cfg or {})))


def _need_devices(n):
    """A config that exists only across devices is never run narrower
    under its multi-chip name: it fails, and is counted as failed."""
    import jax

    have = len(jax.devices())
    if have < n:
        raise RuntimeError(f"needs {n} devices, jax.devices() has {have}")


def _plan_of(engine, sql_stmts):
    for s in sql_stmts:
        results = engine.execute_sql(s)
    qid = next(r.query_id for r in results if r.query_id)
    return engine.queries[qid].plan


def _timeit(fn, iters=ITERS, rounds=ROUNDS, warmup=WARMUP):
    """Best-round wall time for `iters` calls of fn(i)."""
    import jax

    for i in range(warmup):
        out = fn(i)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        out = None
        for i in range(iters):
            out = fn(i)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _pv_batches(layout, schema, capacity=CAPACITY, ts_mult=1,
                n_keys=None, ts_step=None):
    import numpy as np

    from ksql_tpu.common.batch import HostBatch

    n_keys = n_keys or N_KEYS
    rng = np.random.default_rng(7)
    urls = np.array([f"/page/{i}" for i in range(n_keys)], dtype=object)
    batches = []
    for b in range(N_BATCHES):
        key_idx = rng.zipf(1.3, size=capacity).astype(np.int64) % n_keys
        rows_ts = TS0 + (b * capacity + np.arange(capacity)) * (
            ts_step if ts_step is not None else 17 * ts_mult
        )
        hb = HostBatch(
            schema=schema,
            num_rows=capacity,
            columns={
                "URL": urls[key_idx],
                "USER_ID": rng.integers(1, 1000, capacity).astype(object),
                "VIEWTIME": rows_ts.astype(object),
            },
            valid={k: np.ones(capacity, bool) for k in ("URL", "USER_ID", "VIEWTIME")},
            timestamps=rows_ts,
        )
        batches.append(layout.encode(hb))
    return batches


PV_DDL = (
    "CREATE STREAM PAGE_VIEWS (URL STRING, USER_ID BIGINT, VIEWTIME BIGINT) "
    "WITH (KAFKA_TOPIC='page_views', VALUE_FORMAT='JSON');"
)


def _stage_block(rec):
    """One flight recorder's per-stage aggregate in the canonical bench
    `extra` shape: p50/p99/total ms plus every cumulative counter (jit
    hits/misses, transfer/exchange bytes, rows, ring lag).  The p99 is
    what scripts/perfgate.py gates on (median-of-p99 over >=3 runs), so
    every bench that prints BENCH_STAGES must use this helper — aggregate-
    only extras are not stage-gateable."""
    if rec is None:
        return None
    return {
        name: {
            "p50Ms": st.get("p50_ms"),
            "p99Ms": st.get("p99_ms"),
            "totalMs": st.get("total_ms"),
            **{
                k: v for k, v in st.items()
                if k not in ("n", "ticks", "p50_ms", "p99_ms", "total_ms")
            },
        }
        for name, st in rec.stage_stats().items()
    }


# ---------------------------------------------------------------- config 1
def bench_tumbling_count():
    from ksql_tpu.runtime.lowering import CompiledDeviceQuery

    e = _engine()
    plan = _plan_of(e, [
        PV_DDL,
        "CREATE TABLE PV_COUNTS AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
        "WINDOW TUMBLING (SIZE 1 HOUR) GROUP BY URL EMIT CHANGES;",
    ])
    dev = CompiledDeviceQuery(plan, e.registry, capacity=CAPACITY, store_capacity=STORE)
    schema = e.metastore.get_source(plan.source_names[0]).schema
    batches = _pv_batches(dev.layout, schema)
    state = {"s": dev.init_state()}
    step, evict = dev._step, dev._evict
    n_done = {"n": 0}

    def run(i):
        state["s"], emits = step(state["s"], batches[i % N_BATCHES])
        n_done["n"] += 1
        if n_done["n"] % dev.EVICT_INTERVAL == 0:
            state["s"] = evict(state["s"])
        return emits["occupancy"]

    dt = _timeit(run)
    return CAPACITY * ITERS / dt


# ---------------------------------------------------------------- config 2
def bench_hopping_multi_udaf():
    from ksql_tpu.runtime.lowering import CompiledDeviceQuery

    e = _engine()
    plan = _plan_of(e, [
        PV_DDL,
        "CREATE TABLE PV_STATS AS SELECT URL, SUM(USER_ID) AS S, AVG(USER_ID) AS A, "
        "MIN(USER_ID) AS MN, MAX(USER_ID) AS MX FROM PAGE_VIEWS "
        "WINDOW HOPPING (SIZE 1 HOUR, ADVANCE BY 15 MINUTES) GROUP BY URL EMIT CHANGES;",
    ])
    cap = CAPACITY // 4  # 4x hopping expansion keeps the step size constant
    dev = CompiledDeviceQuery(plan, e.registry, capacity=cap, store_capacity=STORE)
    schema = e.metastore.get_source(plan.source_names[0]).schema
    batches = _pv_batches(dev.layout, schema, capacity=cap)
    state = {"s": dev.init_state()}
    step, evict = dev._step, dev._evict
    n_done = {"n": 0}

    def run(i):
        state["s"], emits = step(state["s"], batches[i % N_BATCHES])
        n_done["n"] += 1
        if n_done["n"] % dev.EVICT_INTERVAL == 0:
            state["s"] = evict(state["s"])
        return emits["occupancy"]

    dt = _timeit(run)
    return cap * ITERS / dt


# ------------------------------------------------- sliced hopping (ISSUE 7)
def bench_hopping_sum_group_by():
    """Stream slicing vs the k-fold expansion baseline on the SAME
    query/corpus, k ∈ {4, 12}: hopping SUM GROUP BY through the device
    step, sliced (per-(key, slice) partials + per-window combine) and with
    slicing disabled (k-fold row expansion before the shuffle).  Returns
    the k=12 sliced number; the speedups land in `extra` via BENCH_EXTRA
    (acceptance bar: sliced ≥ 0.5·k × expansion at k=12)."""
    from ksql_tpu.runtime.lowering import CompiledDeviceQuery

    cap = CAPACITY // 4
    n_keys = 1_000
    variants = [
        ("k4", "SIZE 1 MINUTE, ADVANCE BY 15 SECONDS", 4),
        ("k12", "SIZE 1 MINUTE, ADVANCE BY 5 SECONDS", 12),
    ]
    out = {}
    for label, win, k in variants:
        e = _engine()
        plan = _plan_of(e, [
            PV_DDL,
            "CREATE TABLE PV_SUMS AS SELECT URL, SUM(USER_ID) AS S "
            f"FROM PAGE_VIEWS WINDOW HOPPING ({win}, "
            "GRACE PERIOD 10 MINUTES) GROUP BY URL EMIT CHANGES;",
        ])
        schema = e.metastore.get_source(plan.source_names[0]).schema
        for mode, sliced, store in (
            ("sliced", None, 1 << 13),
            # expansion keys per (key, window): retention/advance live
            # windows per key need the bigger store
            ("expansion", False, 1 << 14 if _SMOKE else 1 << 17),
        ):
            dev = CompiledDeviceQuery(
                plan, e.registry, capacity=cap, store_capacity=store,
                sliced=sliced,
            )
            if mode == "sliced":
                assert dev.sliced, dev.windowing_fallback
                assert dev.hop_k == k
            # 1ms event spacing keeps the whole replayed corpus inside the
            # 10-minute grace, so no path ever admission-drops rows
            batches = _pv_batches(
                dev.layout, schema, capacity=cap, n_keys=n_keys, ts_step=1
            )
            state = {"s": dev.init_state()}
            step, evict = dev._step, dev._evict
            n_done = {"n": 0}

            def run(i):
                state["s"], emits = step(state["s"], batches[i % N_BATCHES])
                n_done["n"] += 1
                if n_done["n"] % dev.EVICT_INTERVAL == 0:
                    state["s"] = evict(state["s"])
                return emits["occupancy"]

            dt = _timeit(run)
            out[f"hopping_sum_{label}_{mode}_events_s"] = round(
                cap * ITERS / dt, 1
            )
    for label, _, k in variants:
        s = out[f"hopping_sum_{label}_sliced_events_s"]
        x = out[f"hopping_sum_{label}_expansion_events_s"]
        out[f"hopping_sum_{label}_speedup"] = round(s / x, 2)
    print("BENCH_EXTRA " + json.dumps(out, sort_keys=True), flush=True)
    return out["hopping_sum_k12_sliced_events_s"]


def bench_window_family():
    """Window-family multi-query sharing, end to end: four dashboard-style
    hopping queries (same source/GROUP BY/aggregates, different
    size/advance) through the full engine — once with family sharing (one
    consumer + one device dispatch per tick, per-query combine fan-out)
    and once unshared (four standalone sliced pipelines).  Returns the
    shared events/s; both numbers + the primary's per-stage flight-recorder
    breakdown land in `extra`."""
    import numpy as np

    from ksql_tpu.common.config import (
        BATCH_CAPACITY,
        EMIT_CHANGES_PER_RECORD,
        RUNTIME_BACKEND,
        SLICING_SHARE_FAMILIES,
        STATE_SLOTS,
    )
    from ksql_tpu.runtime.device_executor import FamilyMemberExecutor
    from ksql_tpu.runtime.topics import Record

    n_events = 10_000 if _SMOKE else 200_000
    windows = [(60, 5), (120, 5), (90, 5), (60, 10)]
    rng = np.random.default_rng(23)
    key_idx = rng.zipf(1.3, size=n_events).astype(np.int64) % N_KEYS
    payloads = [
        '{"URL":"/page/%d","USER_ID":%d,"VIEWTIME":%d}'
        % (kx, 1 + (i % 999), TS0 + i * 17)
        for i, kx in enumerate(key_idx)
    ]
    out = {}
    stages = None
    for mode, share in (("shared", True), ("unshared", False)):
        e = _engine({
            RUNTIME_BACKEND: "device",
            EMIT_CHANGES_PER_RECORD: False,
            BATCH_CAPACITY: 8192 if _SMOKE else 32768,
            STATE_SLOTS: 1 << 16,
            SLICING_SHARE_FAMILIES: share,
        })
        e.execute_sql(PV_DDL)
        for i, (size, adv) in enumerate(windows):
            e.execute_sql(
                f"CREATE TABLE FAM{i} AS SELECT URL, COUNT(*) AS CNT, "
                "SUM(USER_ID) AS S FROM PAGE_VIEWS WINDOW HOPPING "
                f"(SIZE {size} SECONDS, ADVANCE BY {adv} SECONDS, "
                "GRACE PERIOD 10 MINUTES) GROUP BY URL EMIT CHANGES;"
            )
        handles = list(e.queries.values())
        n_members = sum(
            isinstance(h.executor, FamilyMemberExecutor) for h in handles
        )
        assert n_members == (len(windows) - 1 if share else 0), n_members
        t = e.broker.topic("page_views")
        for i in range(64):
            t.produce(Record(key=None, value=payloads[i], timestamp=TS0 + i * 17))
        while e.poll_once(max_records=1 << 17):
            pass
        t0 = time.perf_counter()
        for i in range(64, n_events):
            t.produce(Record(key=None, value=payloads[i], timestamp=TS0 + i * 17))
        while e.poll_once(max_records=1 << 17):
            pass
        dt = time.perf_counter() - t0
        out[f"window_family_{mode}_events_s"] = round((n_events - 64) / dt, 1)
        if share:
            stages = _stage_block(e.trace_recorders.get(handles[0].query_id))
    out["window_family_sharing_speedup"] = round(
        out["window_family_shared_events_s"]
        / out["window_family_unshared_events_s"],
        2,
    )
    out["window_family_n_queries"] = len(windows)
    print("BENCH_EXTRA " + json.dumps(out, sort_keys=True), flush=True)
    if stages is not None:
        print("BENCH_STAGES " + json.dumps(stages, sort_keys=True), flush=True)
    return out["window_family_shared_events_s"]


def bench_mqo_dashboard():
    """Cost-based multi-query optimizer, end to end (ISSUE 15): 32
    dashboard-style correlated hopping queries over 4 sources — per
    source, 8 queries with DIFFERENT sizes/advances AND different
    aggregate sets (the Factor-Windows + shared-partial generalization)
    — once with the MQO (each source's family shares ONE sliced pipeline
    at the gcd width: ≤ 8 device pipelines for all 32 queries) and once
    unshared (32 standalone pipelines).  Asserts pipeline count, member
    twin-parity on final materialized state, and EXPLAIN's shared-DAG +
    cost-decision surface; returns the shared aggregate events/s."""
    import numpy as np

    from ksql_tpu.common.config import (
        BATCH_CAPACITY,
        EMIT_CHANGES_PER_RECORD,
        MQO_ENABLE,
        RUNTIME_BACKEND,
        SLICING_SHARE_FAMILIES,
        STATE_SLOTS,
    )
    from ksql_tpu.runtime.device_executor import FamilyMemberExecutor
    from ksql_tpu.runtime.topics import Record

    n_sources = 4
    per_source = 8
    n_events = 24_000 if _SMOKE else 160_000  # total, split across sources
    #: (size s, advance s) + aggregate set per query slot — correlated:
    #: same source/GROUP BY, heterogeneous windows AND aggregates.
    #: Dashboard-style hops (k = size/advance ≤ 4): the shared pipeline
    #: amortizes the per-record decode+scan+fold (paid once instead of 8
    #: times per source); the per-member window combine is paid either
    #: way, so modest hop fan-outs keep the measurement about the lever
    #: sharing actually moves
    aggs_pool = [
        "COUNT(*) AS CNT",
        "COUNT(*) AS CNT, SUM(USER_ID) AS S",
        "SUM(USER_ID) AS S, MIN(USER_ID) AS MN",
        "MIN(USER_ID) AS MN, MAX(USER_ID) AS MX",
    ]
    #: every width is a multiple of the 30s family gcd, so no attach
    #:  re-slices the ring (a gcd-collapsing window — e.g. (60,15) after
    #: (60,30) — is priced dearer than standalone and the cost model
    #: correctly refuses it; that path is exercised in tests/test_mqo.py)
    windows = [(60, 30), (120, 30), (90, 30), (120, 60),
               (180, 60), (240, 60), (180, 90), (240, 120)]
    rng = np.random.default_rng(29)
    key_idx = rng.zipf(1.3, size=n_events).astype(np.int64) % N_KEYS
    payloads = [
        '{"URL":"/page/%d","USER_ID":%d,"VIEWTIME":%d}'
        % (kx, 1 + (i % 999), TS0 + i * 17)
        for i, kx in enumerate(key_idx)
    ]
    out = {}
    stages = None
    sinks = {}
    for mode, share in (("shared", True), ("unshared", False)):
        e = _engine({
            RUNTIME_BACKEND: "device",
            EMIT_CHANGES_PER_RECORD: False,
            BATCH_CAPACITY: 8192 if _SMOKE else 32768,
            STATE_SLOTS: 1 << 16,
            SLICING_SHARE_FAMILIES: share,
            MQO_ENABLE: share,
        })
        qids = []
        for s in range(n_sources):
            e.execute_sql(
                f"CREATE STREAM PV{s} (URL STRING, USER_ID BIGINT, "
                "VIEWTIME BIGINT) "
                f"WITH (KAFKA_TOPIC='pv{s}', VALUE_FORMAT='JSON');"
            )
            for q in range(per_source):
                size, adv = windows[q]
                r = e.execute_sql(
                    f"CREATE TABLE DASH_{s}_{q} AS SELECT URL, "
                    f"{aggs_pool[q % len(aggs_pool)]} FROM PV{s} "
                    f"WINDOW HOPPING (SIZE {size} SECONDS, ADVANCE BY "
                    f"{adv} SECONDS, GRACE PERIOD 60 SECONDS) "
                    "GROUP BY URL EMIT CHANGES;"
                )
                qids.append(next(x.query_id for x in r if x.query_id))
        handles = [e.queries[q] for q in qids]
        pipelines = sum(
            not isinstance(h.executor, FamilyMemberExecutor)
            for h in handles
        )
        if share:
            assert pipelines <= 8, pipelines  # 32 queries, ≤8 pipelines
            out["mqo_dashboard_pipelines"] = pipelines
            # EXPLAIN on a member: shared DAG + the cost decision
            member = next(
                q for q in qids
                if isinstance(e.queries[q].executor, FamilyMemberExecutor)
            )
            txt = e.execute_sql(f"EXPLAIN {member};")[0].message
            assert "shared DAG" in txt and "decision: share" in txt, (
                "EXPLAIN lost the shared-plan DAG / cost decision"
            )
            out["mqo_dashboard_explain_ok"] = True
        else:
            assert pipelines == len(qids), pipelines
        topics = [e.broker.topic(f"pv{s}") for s in range(n_sources)]
        for i in range(256):  # warmup: pay the compiles off the clock
            topics[i % n_sources].produce(Record(
                key=None, value=payloads[i], timestamp=TS0 + i * 17
            ))
        while e.poll_once(max_records=1 << 17):
            pass
        t0 = time.perf_counter()
        for i in range(256, n_events):
            topics[i % n_sources].produce(Record(
                key=None, value=payloads[i], timestamp=TS0 + i * 17
            ))
        while e.poll_once(max_records=1 << 17):
            pass
        dt = time.perf_counter() - t0
        out[f"mqo_dashboard_{mode}_events_s"] = round(
            (n_events - 256) / dt, 1
        )
        sinks[mode] = {}
        for q in qids:
            sink = e.queries[q].plan.physical_plan.topic
            state = {}
            for r in e.broker.topic(sink).all_records():
                state[(r.key, r.window)] = r.value
            sinks[mode][sink] = {
                k: v for k, v in state.items() if v is not None
            }
        if share:
            prim = next(
                q for q in qids
                if not isinstance(e.queries[q].executor, FamilyMemberExecutor)
            )
            stages = _stage_block(e.trace_recorders.get(prim))
    # member twin-parity: every query's final materialized state is
    # bit-identical between the shared and unshared runs
    parity = all(
        sinks["shared"][k] == sinks["unshared"][k] for k in sinks["shared"]
    )
    assert parity, "shared/unshared sink divergence"
    out["mqo_dashboard_parity_ok"] = parity
    out["mqo_dashboard_n_queries"] = n_sources * per_source
    out["mqo_dashboard_sharing_speedup"] = round(
        out["mqo_dashboard_shared_events_s"]
        / out["mqo_dashboard_unshared_events_s"], 2,
    )
    print("BENCH_EXTRA " + json.dumps(out, sort_keys=True), flush=True)
    if stages is not None:
        print("BENCH_STAGES " + json.dumps(stages, sort_keys=True), flush=True)
    return out["mqo_dashboard_shared_events_s"]


# ---------------------------------------------------------------- config 3
def bench_stream_table_join():
    import numpy as np

    from ksql_tpu.common.batch import HostBatch
    from ksql_tpu.runtime.lowering import CompiledDeviceQuery

    e = _engine()
    for s in [
        "CREATE TABLE USERS (ID BIGINT PRIMARY KEY, NAME STRING, REGION STRING) "
        "WITH (KAFKA_TOPIC='users', VALUE_FORMAT='JSON');",
        "CREATE STREAM CLICKS (USER_ID BIGINT, URL STRING) "
        "WITH (KAFKA_TOPIC='clicks', VALUE_FORMAT='JSON');",
    ]:
        e.execute_sql(s)
    results = e.execute_sql(
        "CREATE STREAM ENRICHED AS SELECT C.USER_ID, C.URL, U.REGION "
        "FROM CLICKS C LEFT JOIN USERS U ON C.USER_ID = U.ID "
        "WHERE U.REGION <> 'excluded' EMIT CHANGES;"
    )
    qid = next(r.query_id for r in results if r.query_id)
    plan = e.queries[qid].plan
    n_users = 8_192 if _SMOKE else 100_000
    dev = CompiledDeviceQuery(
        plan, e.registry, capacity=CAPACITY,
        table_store_capacity=1 << 14 if _SMOKE else 1 << 18,
    )
    import jax

    uschema = e.metastore.get_source("USERS").schema
    regions = [f"r{i}" for i in range(50)]
    chunk = CAPACITY
    state = dev.state
    for start in range(0, n_users, chunk):
        rows = [
            {"ID": k, "NAME": f"user{k}", "REGION": regions[k % 50]}
            for k in range(start, start + chunk)
        ]
        hb = HostBatch.from_rows(uschema, rows, timestamps=[TS0] * chunk)
        arrays = dev.table_layout.encode(hb)
        arrays["delete"] = np.zeros(CAPACITY, bool)
        # raw steps (no occupancy readback per chunk: the load stays async)
        state, _m = dev._table_step(state, arrays)
    jax.block_until_ready(state["jtab"]["occ"])
    dev.state = state
    cschema = e.metastore.get_source("CLICKS").schema
    rng = np.random.default_rng(11)
    batches = []
    for b in range(N_BATCHES):
        uid = rng.integers(0, n_users * 2, CAPACITY)  # ~50% match
        rows_ts = TS0 + (b * CAPACITY + np.arange(CAPACITY)) * 3
        hb = HostBatch(
            schema=cschema,
            num_rows=CAPACITY,
            columns={
                "USER_ID": uid.astype(object),
                "URL": np.array([f"/u/{x % 997}" for x in uid], dtype=object),
            },
            valid={k: np.ones(CAPACITY, bool) for k in ("USER_ID", "URL")},
            timestamps=rows_ts,
        )
        batches.append(dev.layout.encode(hb))
    state = {"s": dev.init_state()}
    state["s"]["jtab"] = dev.state["jtab"]  # keep the loaded table store
    step = dev._step

    def run(i):
        state["s"], emits = step(state["s"], batches[i % N_BATCHES])
        return emits["emit_mask"]

    dt = _timeit(run)
    return CAPACITY * ITERS / dt


# ---------------------------------------------------------------- config 4
def bench_stream_stream_join():
    import numpy as np

    from ksql_tpu.common.batch import HostBatch
    from ksql_tpu.runtime.lowering import CompiledDeviceQuery

    e = _engine()
    for s in [
        "CREATE STREAM LEFTS (ID BIGINT KEY, V BIGINT) "
        "WITH (KAFKA_TOPIC='lt', VALUE_FORMAT='JSON');",
        "CREATE STREAM RIGHTS (ID BIGINT KEY, V BIGINT) "
        "WITH (KAFKA_TOPIC='rt', VALUE_FORMAT='JSON');",
    ]:
        e.execute_sql(s)
    results = e.execute_sql(
        "CREATE STREAM J AS SELECT L.ID, L.V AS LV, R.V AS RV FROM LEFTS L "
        "LEFT JOIN RIGHTS R WITHIN 10 SECONDS GRACE PERIOD 1 SECOND "
        "ON L.ID = R.ID EMIT CHANGES;"
    )
    qid = next(r.query_id for r in results if r.query_id)
    plan = e.queries[qid].plan
    cap = min(2048, CAPACITY)
    buf = 1 << 12 if _SMOKE else 1 << 14
    dev = CompiledDeviceQuery(
        plan, e.registry, capacity=cap,
        ss_buffer_capacity=buf, ss_out_capacity=8 * cap,
    )
    n_keys = 20_000
    rng = np.random.default_rng(13)
    sides = []
    for b in range(2 * N_BATCHES):
        ids = rng.integers(0, n_keys, cap)
        rows_ts = TS0 + (b * cap + np.arange(cap)) * 2  # ~2ms per event
        schema = e.metastore.get_source("LEFTS" if b % 2 == 0 else "RIGHTS").schema
        hb = HostBatch(
            schema=schema,
            num_rows=cap,
            columns={"ID": ids.astype(object), "V": ids.astype(object)},
            valid={k: np.ones(cap, bool) for k in ("ID", "V")},
            timestamps=rows_ts,
        )
        layout = dev.layout if b % 2 == 0 else dev.right_layout
        sides.append(layout.encode(hb))
    state = {"s": dev.state}
    ovf = {"n": 0}

    def run(i):
        fn = dev._ss_l if i % 2 == 0 else dev._ss_r
        state["s"], emits = fn(state["s"], sides[i % (2 * N_BATCHES)])
        ovf["n"] = emits["ss_matchovf"]
        return emits["emit_mask"]

    dt = _timeit(run)
    assert int(ovf["n"]) == 0
    return cap * ITERS / dt


# ---------------------------------------------------------------- config 5
def bench_session():
    from ksql_tpu.runtime.lowering import CompiledDeviceQuery

    e = _engine()
    plan = _plan_of(e, [
        PV_DDL,
        "CREATE TABLE SESSIONS AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
        "WINDOW SESSION (30 SECONDS) GROUP BY URL EMIT CHANGES;",
    ])
    cap = min(8192, CAPACITY)  # session step sorts n*(slots+1) items
    dev = CompiledDeviceQuery(plan, e.registry, capacity=cap, store_capacity=STORE)
    dev.session_slots = 16  # presize for zipf-tail session churn
    schema = e.metastore.get_source(plan.source_names[0]).schema
    batches = _pv_batches(dev.layout, schema, capacity=cap)
    state = {"s": dev.init_state()}
    step = dev._step
    ovf = {"n": 0}

    def run(i):
        state["s"], emits = step(state["s"], batches[i % N_BATCHES])
        ovf["n"] = emits["sess_ovf"]
        return emits["emit_mask"]

    dt = _timeit(run)
    assert int(ovf["n"]) == 0
    return cap * ITERS / dt


# ------------------------------------------------------------- engine e2e
def _pv_payloads(n_events, seed=17):
    """The shared engine-e2e corpus: zipf-keyed JSON pageview payloads.
    One generator for engine_e2e / engine_e2e_dist / engine_e2e_scaling,
    so the scaling curve stays comparable to the e2e numbers."""
    import numpy as np

    rng = np.random.default_rng(seed)
    key_idx = rng.zipf(1.3, size=n_events).astype(np.int64) % N_KEYS
    return [
        '{"URL":"/page/%d","USER_ID":%d,"VIEWTIME":%d}'
        % (k, 1 + (i % 999), TS0 + i * 17)
        for i, k in enumerate(key_idx)
    ]


def _drive_pv_engine(e, payloads):
    """The shared timed drive: 64-record warmup (compile outside the
    timed region), then produce + poll the rest; returns events/s."""
    from ksql_tpu.runtime.topics import Record

    t = e.broker.topic("page_views")
    for i in range(64):
        t.produce(Record(key=None, value=payloads[i], timestamp=TS0 + i * 17))
    while e.poll_once(max_records=1 << 17):
        pass
    t0 = time.perf_counter()
    for i in range(64, len(payloads)):
        t.produce(Record(key=None, value=payloads[i], timestamp=TS0 + i * 17))
    while e.poll_once(max_records=1 << 17):
        pass
    return (len(payloads) - 64) / (time.perf_counter() - t0)


def _bench_engine_e2e_on(backend):
    """Config #1 through the full engine: JSON records on the broker →
    consumer poll → decode → HostBatch → encode → device step(s) → sink
    produce.  Batched EMIT CHANGES (per-record parity off)."""
    from ksql_tpu.common.config import (
        BATCH_CAPACITY,
        EMIT_CHANGES_PER_RECORD,
        RUNTIME_BACKEND,
        STATE_SLOTS,
    )

    n_events = 20_000 if _SMOKE else 400_000
    e = _engine({
        RUNTIME_BACKEND: backend,
        EMIT_CHANGES_PER_RECORD: False,
        # large batches amortize the per-step readback
        BATCH_CAPACITY: 8192 if _SMOKE else 32768,
        STATE_SLOTS: 1 << 18,
    })
    e.execute_sql(PV_DDL)
    e.execute_sql(
        "CREATE TABLE PV_COUNTS AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
        "WINDOW TUMBLING (SIZE 1 HOUR) GROUP BY URL EMIT CHANGES;"
    )
    handle = list(e.queries.values())[0]
    assert handle.backend == backend, (
        handle.backend, e.fallback_reasons, e.processing_log,
    )
    v = _drive_pv_engine(e, _pv_payloads(n_events))
    # per-stage breakdown from the flight recorder (where the time went:
    # decode vs device compile/execute vs sink produce, transfer/exchange
    # volumes) — the parent folds this into the result's `extra`
    stages = _stage_block(e.trace_recorders.get(handle.query_id))
    if stages is not None:
        # e2e latency columns off the bucketed histogram (ISSUE 18).
        # Informational in perfgate — not in GATED_STAGES: CPU-smoke
        # jitter plus the corpus's synthetic TS0-based stamps (decades
        # old ⇒ every sample lands in the +Inf bucket) make the absolute
        # values unfit to gate; the column's presence and plumbing are
        # what the baseline pins
        prog = getattr(handle, "progress", None)
        hist = getattr(prog, "e2e_hist", None) if prog is not None else None
        if hist is not None and hist.count:
            stages["e2e.latency"] = {
                "p50Ms": hist.percentile(0.50),
                "p99Ms": hist.percentile(0.99),
                "totalMs": round(hist.sum_s * 1000.0, 3),
                "count": hist.count,
            }
        # telemetry timeline fold overhead: the retention layer rides the
        # poll loop inline, so its cost is measured and bounded right
        # where the perf evidence lives (< 2% of tick wall time)
        tl = e.timelines.get(handle.query_id)
        if tl is not None:
            ts = tl.stats()
            tick_ms = ts["tickMsFolded"]
            pct = 100.0 * ts["foldMs"] / tick_ms if tick_ms else 0.0
            assert pct < 2.0, (
                f"timeline fold overhead {pct:.3f}% >= 2% of tick wall "
                f"time: {ts}"
            )
            stages["telemetry.fold"] = {
                "p50Ms": ts["foldP50Ms"],
                "p99Ms": ts["foldP99Ms"],
                "totalMs": ts["foldMs"],
                "folds": ts["folds"],
            }
        print("BENCH_STAGES " + json.dumps(stages, sort_keys=True), flush=True)
    return v


def bench_engine_e2e():
    return _bench_engine_e2e_on("device")


def bench_engine_e2e_dist():
    """engine_e2e on the distributed backend: the mesh splits each poll
    tick's micro-batch into per-shard lanes and shards the keyed state.
    Prints the mesh size alongside so throughput-per-device is derivable
    (the BENCH acceptance bar: within 2× of single-device per step)."""
    import jax

    _need_devices(2)
    v = _bench_engine_e2e_on("distributed")
    print(f"BENCH_SHARDS {len(jax.devices())}", flush=True)
    return v


def bench_engine_e2e_scaling():
    """Distributed scaling curve (ISSUE 11): the SAME engine-e2e corpus
    swept at 1 → 2 → 4 → 8 shards (one fresh engine per point,
    ksql.device.shards pinned; a rehearsal's parent gives the CPU 8
    virtual devices).  Per point: throughput, exchange rows/bytes off the flight
    recorder, and the full per-stage breakdown — the sharding story as a
    CURVE instead of one mesh-sized sample.  Returns the widest mesh's
    events/s; the curve lands in `extra` as engine_e2e_scaling_curve."""
    import jax

    from ksql_tpu.common.config import (
        BATCH_CAPACITY,
        DEVICE_SHARDS,
        EMIT_CHANGES_PER_RECORD,
        RUNTIME_BACKEND,
        STATE_SLOTS,
    )

    n_events = 10_000 if _SMOKE else 100_000
    shard_counts = [1, 2, 4, 8]
    _need_devices(max(shard_counts))
    payloads = _pv_payloads(n_events)
    curve = {}
    last = 0.0
    for shards in shard_counts:
        e = _engine({
            RUNTIME_BACKEND: "distributed",
            DEVICE_SHARDS: shards,
            EMIT_CHANGES_PER_RECORD: False,
            BATCH_CAPACITY: 8192 if _SMOKE else 32768,
            STATE_SLOTS: 1 << 16,
        })
        e.execute_sql(PV_DDL)
        e.execute_sql(
            "CREATE TABLE PV_COUNTS AS SELECT URL, COUNT(*) AS CNT "
            "FROM PAGE_VIEWS WINDOW TUMBLING (SIZE 1 HOUR) GROUP BY URL "
            "EMIT CHANGES;"
        )
        handle = list(e.queries.values())[0]
        assert handle.backend == "distributed", (
            handle.backend, e.fallback_reasons,
        )
        mesh_n = getattr(getattr(handle.executor, "device", None),
                         "n_shards", 0)
        assert mesh_n == shards, (mesh_n, shards)
        last = round(_drive_pv_engine(e, payloads), 1)
        stages = _stage_block(e.trace_recorders.get(handle.query_id)) or {}
        exch = stages.get("exchange", {})
        curve[str(shards)] = {
            "events_s": last,
            "exchange_rows": int(exch.get("rows", 0) or 0),
            "exchange_bytes": int(exch.get("bytes", 0) or 0),
            "stages": stages,
        }
        e.shutdown()
    print("BENCH_EXTRA " + json.dumps(
        {"engine_e2e_scaling_curve": curve,
         "engine_e2e_scaling_shard_counts": shard_counts},
        sort_keys=True,
    ), flush=True)
    return last


# ---------------------------------------------------------------- config 8
def _push_fanout_once(n_sessions, n_events, payloads, mode):
    """One push-fanout measurement: N filtered sessions in one of three
    serving modes — ``fused`` (registry taps + the batched residual
    kernel), ``host`` (registry taps, row-at-a-time host residuals — the
    PR-10 posture), ``unshared`` (N private consumer+executor sessions).
    Returns (sessions/s setup, delivered rows/s, delivered, stage block
    for registry modes)."""
    from ksql_tpu.common.config import (
        PUSH_FUSED_ENABLE,
        PUSH_REGISTRY_ENABLE,
        RUNTIME_BACKEND,
    )
    from ksql_tpu.runtime.topics import Record
    from ksql_tpu.server.rest import PushQuerySession

    share = mode != "unshared"
    # oracle pipeline on all sides: dedicated sessions always run the
    # oracle, so the comparison isolates the serving architecture (and,
    # fused vs host, exactly the residual-evaluation lever)
    e = _engine({RUNTIME_BACKEND: "oracle",
                 PUSH_REGISTRY_ENABLE: share,
                 PUSH_FUSED_ENABLE: mode == "fused"})
    e.execute_sql(PV_DDL)
    e.session_properties["auto.offset.reset"] = "latest"
    t0 = time.perf_counter()
    sessions = [
        PushQuerySession(
            e,
            f"SELECT URL, VIEWTIME FROM PAGE_VIEWS "
            f"WHERE USER_ID % {n_sessions} = {i} EMIT CHANGES;",
        )
        for i in range(n_sessions)
    ]
    setup_dt = time.perf_counter() - t0
    if share:
        stats = e.push_registry.stats()
        assert stats["pipelines"] == 1, stats
        assert stats["taps-total"] == n_sessions, stats
        if mode == "fused":
            assert stats["residual"]["fused-taps"] == n_sessions, stats
    t = e.broker.topic("page_views")
    # warm-up round (identical for every mode): the fused kernel pays its
    # one-time trace/compile here — sized to the steady-state chunk so the
    # timed window re-traces nothing — and the compile cost stays visible
    # separately via the pipeline recorder's device.compile stage
    step = 1024
    for p in payloads[:step]:
        t.produce(Record(key=None, value=p, timestamp=TS0))
    while sum(len(s.poll()) for s in sessions):
        pass
    t1 = time.perf_counter()
    delivered = 0
    for lo in range(0, n_events, step):
        for p in payloads[lo:lo + step]:
            t.produce(Record(key=None, value=p, timestamp=TS0))
        for s in sessions:
            delivered += len(s.poll())
    # drain: a session polled early in the last round may still trail
    # rows a later session's poll advanced into the shared ring
    while True:
        more = sum(len(s.poll()) for s in sessions)
        delivered += more
        if not more:
            break
    dt = time.perf_counter() - t1
    stages = None
    if share:
        # the shared pipeline's recorders carry the per-stage fan-out
        # breakdown — pump/oracle chain + the fused residual kernel on
        # <pipe>, residual delivery + ring lag on <pipe>/taps (separate
        # rings so tap ticks can't evict pump ticks) — merged into the
        # same extra shape as engine_e2e_stages so perfgate gates both
        pipes = list(e.push_registry.pipelines.values())
        stages = {}
        for rec_id in ([pipes[0].id, pipes[0].id + "/taps"]
                       if pipes else []):
            stages.update(
                _stage_block(e.trace_recorders.get(rec_id)) or {}
            )
        stages = stages or None
    for s in sessions:
        s.close()
    e.shutdown()
    return (
        round(n_sessions / setup_dt, 1),
        round(delivered / dt, 1),
        delivered,
        stages,
    )


def bench_push_fanout():
    """Push-serving fan-out (ISSUE 10 + 12): N concurrent filtered push
    sessions over one stream, swept over tap counts, in three modes —
    fused (ONE batched device kernel evaluates every tap's residual over
    the shared emission batch), host (registry taps, per-tap host-side
    residuals: the PR-10 posture), unshared (N private consumer+executor
    sessions).  Headline is the fused aggregate delivery rate at the
    widest tap count every mode ran; `extra` carries the whole sweep and
    the fused-vs-host / fused-vs-unshared speedups per tap count."""
    taps_sweep = (16, 64) if _SMOKE else (16, 64, 256)
    #: unshared past this tap count is prohibitively slow (N full
    #: consumer+executor chains re-decoding every event) — the sweep
    #: reports fused/host only there, and says so in the extra
    unshared_cap = 64
    out = {}
    stages = None
    headline = None
    headline_n = None
    for n_sessions in taps_sweep:
        # constant event volume across the smoke sweep (ratios at a tap
        # count compare identical traffic); the full run shrinks the
        # widest sweeps to bound wall time
        n_events = (
            4_000 if _SMOKE
            else max(40_000 * 16 // n_sessions, 10_000)
        )
        payloads = [
            '{"URL":"/page/%d","USER_ID":%d,"VIEWTIME":%d}'
            % (i % N_KEYS, 1 + (i % 999), TS0 + i * 17)
            for i in range(n_events)
        ]
        modes = ["fused", "host"] + (
            ["unshared"] if n_sessions <= unshared_cap else []
        )
        rates = {}
        for mode in modes:
            setup_s, rows_s, delivered, st = _push_fanout_once(
                n_sessions, n_events, payloads, mode
            )
            rates[mode] = rows_s
            out[f"push_fanout_{mode}_{n_sessions}_sessions_per_s"] = setup_s
            out[f"push_fanout_{mode}_{n_sessions}_rows_s"] = rows_s
            out[f"push_fanout_{mode}_{n_sessions}_delivered"] = delivered
            if mode == "fused":
                stages = st or stages  # widest fused sweep wins
        out[f"push_fanout_fused_vs_host_{n_sessions}"] = round(
            rates["fused"] / rates["host"], 2
        )
        if "unshared" in rates:
            out[f"push_fanout_fused_vs_unshared_{n_sessions}"] = round(
                rates["fused"] / rates["unshared"], 2
            )
            headline = rates["fused"]
            headline_n = n_sessions
    out["push_fanout_taps_sweep"] = list(taps_sweep)
    out["push_fanout_unshared_cap"] = unshared_cap
    out["push_fanout_n_sessions"] = headline_n
    # perfgate continuity: the gated throughput metric stays
    # push_fanout_delivered_rows_s = fused delivery at the widest tap
    # count that ran all three modes; sharing_speedup keeps its PR-10
    # meaning (shared-fused vs unshared)
    out["push_fanout_delivered_rows_s"] = headline
    out["push_fanout_sharing_speedup"] = out[
        f"push_fanout_fused_vs_unshared_{headline_n}"
    ]
    out["push_fanout_residual_speedup"] = out[
        f"push_fanout_fused_vs_host_{headline_n}"
    ]
    print("BENCH_EXTRA " + json.dumps(out, sort_keys=True), flush=True)
    if stages is not None:
        print("BENCH_STAGES " + json.dumps(stages, sort_keys=True), flush=True)
    return out["push_fanout_delivered_rows_s"]


# ------------------------------------------------- line-rate serde (ISSUE 17)
def _serde_corpus(n_events):
    """Wide-row corpus for the serde bench, one logical row rendered in
    both source formats (JSON object / commons-csv DELIMITED line) so the
    two sweeps decode identical data.  A slice of the string fields needs
    quoting in DELIMITED form, keeping the quote-stateful splitter on the
    measured path."""
    import numpy as np

    rng = np.random.default_rng(23)
    key_idx = rng.zipf(1.3, size=n_events).astype(np.int64) % N_KEYS
    json_rows, delim_rows = [], []
    for i, k in enumerate(int(x) for x in key_idx):
        s1 = f"/page/{k}"
        s2 = f"agent-{i % 37},v2" if i % 11 == 0 else f"agent-{i % 37}"
        flag = "true" if i % 3 == 0 else "false"
        x = (i % 1000) / 8.0
        s3 = f"region-{k % 13}/zone-{i % 5}"
        s4 = f"sku:{(i * 7) % 4096:04x}"
        json_rows.append(
            '{"ID":%d,"A":%d,"B":%d,"C":%d,"D":%d,"X":%s,"Y":%s,"Z":%s,'
            '"W":%s,"FLAG":%s,"S1":"%s","S2":%s,"S3":"%s","S4":"%s",'
            '"VIEWTIME":%d}'
            % (i, k, i % 97, (i * 31) % 100_000, -(i % 1009),
               repr(x), repr(x * 3.5), repr(x * 0.125 + 2.0),
               repr((i % 17) / 16.0), flag,
               s1, json.dumps(s2), s3, s4, TS0 + i * 17)
        )
        d2 = f'"{s2}"' if "," in s2 else s2
        delim_rows.append(
            "%d,%d,%d,%d,%d,%s,%s,%s,%s,%s,%s,%s,%s,%s,%d"
            % (i, k, i % 97, (i * 31) % 100_000, -(i % 1009),
               repr(x), repr(x * 3.5), repr(x * 0.125 + 2.0),
               repr((i % 17) / 16.0), flag,
               s1, d2, s3, s4, TS0 + i * 17)
        )
    return json_rows, delim_rows


def _serde_once(value_format, payloads, batched):
    """One serde_linerate measurement: wide-row pass-through projection
    through the full engine (poll → decode → device step → sink encode →
    produce) with the batch tiers ON (native C++ columnar ingest +
    block-batched sink encode) or forced OFF (the pre-PR per-record
    Python loops).  Returns (rows/s, stage block)."""
    from ksql_tpu.common.config import (
        BATCH_CAPACITY,
        EMIT_CHANGES_PER_RECORD,
        RUNTIME_BACKEND,
        STATE_SLOTS,
    )
    from ksql_tpu.runtime.topics import Record

    e = _engine({
        RUNTIME_BACKEND: "device",
        EMIT_CHANGES_PER_RECORD: False,
        BATCH_CAPACITY: 8192 if _SMOKE else 32768,
        STATE_SLOTS: 1 << 12,
    })
    e.execute_sql(
        "CREATE STREAM WIDE (ID BIGINT, A BIGINT, B BIGINT, C BIGINT, "
        "D BIGINT, X DOUBLE, Y DOUBLE, Z DOUBLE, W DOUBLE, FLAG BOOLEAN, "
        "S1 STRING, S2 STRING, S3 STRING, S4 STRING, VIEWTIME BIGINT) "
        f"WITH (KAFKA_TOPIC='wide', VALUE_FORMAT='{value_format}');"
    )
    # ingest-bound by construction: the filter passes ~1% of rows, so the
    # per-emit produce overhead (identical in both modes) stays off the
    # critical path while every row still rides decode → device step, and
    # the surviving slice rides the sink encoder
    e.execute_sql(
        "CREATE STREAM WIDE_OUT AS SELECT ID, A, B, C, D, X, Y, Z, W, "
        "FLAG, S1, S2, S3, S4, VIEWTIME FROM WIDE WHERE B = 0;"
    )
    handle = list(e.queries.values())[0]
    assert handle.backend == "device", (handle.backend, e.fallback_reasons)
    ex = handle.executor
    if not batched:
        # force the pre-PR posture: Python per-record decode + per-emit
        # serialize (the native tier and the block encoder stay built so
        # both modes pay identical construction costs)
        ex._native_fields = None
        ex.sink_writer.encode_batch = lambda emits: None
    else:
        assert ex._native_fields is not None, (
            "native ingest ineligible for the serde bench plan"
        )
    t = e.broker.topic("wide")
    for i in range(64):
        t.produce(Record(key=None, value=payloads[i], timestamp=TS0 + i * 17))
    while e.poll_once(max_records=1 << 17):
        pass
    t0 = time.perf_counter()
    for i in range(64, len(payloads)):
        t.produce(Record(key=None, value=payloads[i], timestamp=TS0 + i * 17))
    while e.poll_once(max_records=1 << 17):
        pass
    dt = time.perf_counter() - t0
    if batched:
        assert ex.native_ingest_rows.get(value_format, 0) > 0, (
            "batched mode never engaged native ingest", ex.native_ingest_rows)
        assert ex.sink_writer.batch_encoded_rows > 0, (
            "batched mode never engaged the block sink encoder")
    stages = _stage_block(e.trace_recorders.get(handle.query_id))
    e.shutdown()
    return (len(payloads) - 64) / dt, stages


def bench_serde_linerate():
    """Line-rate serde (ISSUE 17): wide-row (15-column) pass-through
    streams on JSON and DELIMITED sources, batched (native C++ columnar
    decode + block-batched sink encode) vs per-record (the pre-PR Python
    serde loops) on the SAME corpus.  Headline is the batched JSON rows/s;
    per-format rates and batched-vs-per-record speedups land in `extra`,
    and the batched JSON run's stage block (deserialize + sink.produce
    are perfgate-gated) in BENCH_STAGES."""
    n_events = 8_000 if _SMOKE else 120_000
    json_rows, delim_rows = _serde_corpus(n_events)
    out = {}
    stages = None
    for fmt, payloads in (("JSON", json_rows), ("DELIMITED", delim_rows)):
        batched, st = _serde_once(fmt, payloads, batched=True)
        per_record, _ = _serde_once(fmt, payloads, batched=False)
        lf = fmt.lower()
        out[f"serde_linerate_{lf}_batched_rows_s"] = round(batched, 1)
        out[f"serde_linerate_{lf}_per_record_rows_s"] = round(per_record, 1)
        out[f"serde_linerate_{lf}_speedup"] = round(batched / per_record, 2)
        if fmt == "JSON":
            stages = st
    print("BENCH_EXTRA " + json.dumps(out, sort_keys=True), flush=True)
    if stages is not None:
        print("BENCH_STAGES " + json.dumps(stages, sort_keys=True), flush=True)
    return out["serde_linerate_json_batched_rows_s"]


def _child_setup():
    """What every child does before its first compile: the shared
    persistent compilation cache, 64-bit types, and the two refusals — no
    measurement off the chip, and none that decodes per record in Python
    for want of the native ingest library."""
    import jax

    from ksql_tpu import native
    from ksql_tpu.runtime import compile_cache

    compile_cache.place()
    jax.config.update("jax_enable_x64", True)
    platform = jax.devices()[0].platform
    if platform != "tpu" and not _SMOKE:
        raise RuntimeError(
            f"no TPU (platform {platform!r}): a measurement needs the chip"
        )
    if not native.available():
        raise RuntimeError(
            f"native ingest library unavailable: {native.build_error()}"
        )
    return jax


def _run_one(fn_name: str) -> None:
    """Child entry (``python bench.py --one <name>``): run one bench and
    print its value on the last line.  BENCH_FAULT_HANG=<fn_name> is the
    harness's own fault point: it wedges this child before any work so the
    parent's per-bench watchdog (not a driver-level kill) has to contain
    it — tests/test_bench_smoke.py proves the final JSON line stays valid."""
    if os.environ.get("BENCH_FAULT_HANG") == fn_name:
        while True:
            time.sleep(3600)
    _child_setup()
    v = globals()[fn_name]()
    print(f"BENCH_RESULT {v!r}", flush=True)


def _probe() -> None:
    """Child entry (``python bench.py --probe``): say what device the
    children will run on, after one tiny dispatch end to end (device_put +
    add + readback).  In a child, so the parent stays off JAX: a chip
    belongs to one process at a time."""
    jax = _child_setup()
    import jax.numpy as jnp

    devs = jax.devices()
    x = jax.block_until_ready(jnp.arange(8) + 1)
    assert int(x[-1]) == 8
    print("PROBE_OK " + json.dumps({
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "devices": len(devs),
    }), flush=True)


# Global wall-clock budget for the whole bench (seconds).  The driver's own
# timeout killed round 4's bench before it printed anything; everything here
# is sized to finish — and to have already printed a parseable line — well
# inside this budget.
BENCH_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "900"))
PROBE_TIMEOUT_S = float(os.environ.get("BENCH_PROBE_TIMEOUT_S", "60"))
#: per-bench watchdog ceiling (a single bench may never eat the whole
#: budget even when it is the only one left)
PER_BENCH_MAX_S = float(os.environ.get("BENCH_PER_BENCH_MAX_S", "300"))
#: optional mirror of every emitted JSON line (atomic replace), so partial
#: results also survive a kill that races the final stdout flush
JSON_PATH = os.environ.get("BENCH_JSON_PATH", "")

_CONFIGS = [
    ("hopping_multi_udaf_events_s", "bench_hopping_multi_udaf", BENCH_BASELINE_EVENTS_S),
    ("hopping_sum_group_by_events_s", "bench_hopping_sum_group_by", BENCH_BASELINE_EVENTS_S),
    ("window_family_events_s", "bench_window_family", BENCH_BASELINE_EVENTS_S),
    ("mqo_dashboard_events_s", "bench_mqo_dashboard", BENCH_BASELINE_EVENTS_S),
    ("stream_table_join_events_s", "bench_stream_table_join", JOIN_BASELINE_EVENTS_S),
    ("stream_stream_join_grace_events_s", "bench_stream_stream_join", JOIN_BASELINE_EVENTS_S),
    ("session_window_events_s", "bench_session", BENCH_BASELINE_EVENTS_S),
    ("engine_e2e_events_s", "bench_engine_e2e", BENCH_BASELINE_EVENTS_S),
    ("engine_e2e_dist_events_s", "bench_engine_e2e_dist", BENCH_BASELINE_EVENTS_S),
    ("engine_e2e_scaling_events_s", "bench_engine_e2e_scaling", BENCH_BASELINE_EVENTS_S),
    ("push_fanout_delivered_rows_s", "bench_push_fanout", BENCH_BASELINE_EVENTS_S),
    ("serde_linerate_rows_s", "bench_serde_linerate", BENCH_BASELINE_EVENTS_S),
]

#: BENCH_ONLY=name1,name2 narrows the run to matching configs (substring
#: match on the metric name) — the watchdog fault-injection test uses it
#: to keep its wall clock tight
_ONLY = [s for s in os.environ.get("BENCH_ONLY", "").split(",") if s]

#: a rehearsal on the CPU gives the multi-chip configs eight virtual
#: devices; the flag does nothing to an accelerator platform, where a
#: config that needs more devices than there are fails (_need_devices)
_DIST_ENV = {
    "XLA_FLAGS": (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
}


def _emit_line(headline, extra):
    """Print the full result as ONE JSON line on stdout.  Called after every
    config completes, so the *last* stdout line is always the most complete
    parseable result even if the process is killed mid-run.  BENCH_JSON_PATH
    additionally mirrors the line to a file via atomic replace."""
    line = json.dumps(
        {
            "metric": "tumbling_count_group_by_events_per_sec",
            "value": round(headline, 1),
            "unit": "events/s",
            "vs_baseline": round(headline / BENCH_BASELINE_EVENTS_S, 2),
            "extra": extra,
        }
    )
    print(line, flush=True)
    if JSON_PATH:
        try:
            tmp = JSON_PATH + ".tmp"
            with open(tmp, "w") as f:
                f.write(line + "\n")
            os.replace(tmp, JSON_PATH)
        except OSError:
            pass  # the file mirror must never kill the stdout line


def main() -> int:
    # Each config runs in its own fresh interpreter, and this parent stays
    # off JAX: a chip belongs to one process at a time, so a parent that
    # had touched it would leave the children none — and a wedged or
    # crashed child cannot take the whole line with it.  The children
    # share one persistent compilation cache (runtime/compile_cache.py).
    import subprocess
    import sys

    t0 = time.monotonic()

    def remaining():
        return BENCH_BUDGET_S - (time.monotonic() - t0)

    last_stdout = {"text": ""}

    def child(args, timeout_s, want_prefix, extra_env=None):
        env = None
        if extra_env:
            env = dict(os.environ)
            env.update(extra_env)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *args],
            capture_output=True, text=True, timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        )
        last_stdout["text"] = proc.stdout
        for line in reversed(proc.stdout.splitlines()):
            if line.startswith(want_prefix):
                return line[len(want_prefix):].strip()
        raise RuntimeError(
            f"no result (rc={proc.returncode}): "
            f"{proc.stderr.strip().splitlines()[-3:]}"
        )

    # -- the probe: what the children will run on.  A measurement path
    # that finds no chip fails; it does not fall back to another platform.
    try:
        extra = json.loads(child(["--probe"], PROBE_TIMEOUT_S, "PROBE_OK"))
    except Exception as ex:  # noqa: BLE001 — report, then fail the run
        print(f"bench: device probe failed: {type(ex).__name__}: {ex}",
              file=sys.stderr, flush=True)
        return 2
    print(f"probe ok: {extra}", file=sys.stderr, flush=True)
    if _SMOKE:
        extra["rehearsal"] = True
    elif extra["platform"] != "tpu":
        print(f"bench: no TPU (probe found {extra}); a measurement needs "
              "the chip — BENCH_SMOKE=1 rehearses on any platform",
              file=sys.stderr, flush=True)
        return 2
    failed = []

    configs = [
        c for c in _CONFIGS
        if not _ONLY or any(pat in c[0] for pat in _ONLY)
    ]
    run_headline = not _ONLY or any(
        pat in "tumbling_count_group_by_events_per_sec" for pat in _ONLY
    )

    # -- one attempt per config, timeout = fair share of the remaining budget
    def run(fn_name, configs_left):
        budget = remaining() - 10.0  # keep slack to print the final line
        if budget <= 30.0:
            raise TimeoutError(f"global budget exhausted ({BENCH_BUDGET_S:.0f}s)")
        # fair share of what's left, never past the global budget or the
        # per-bench ceiling (which also lowers the 60s floor when set
        # tighter — the watchdog knob must actually tighten containment)
        floor = min(60.0, PER_BENCH_MAX_S)
        timeout_s = min(budget, max(floor, min(PER_BENCH_MAX_S,
                                               budget / max(1, configs_left))))
        print(f"run {fn_name} (timeout {timeout_s:.0f}s, {budget:.0f}s left)",
              file=sys.stderr, flush=True)
        extra_env = None
        if fn_name in ("bench_engine_e2e_dist", "bench_engine_e2e_scaling"):
            extra_env = _DIST_ENV
        v = float(child(["--one", fn_name], timeout_s, "BENCH_RESULT",
                        extra_env=extra_env))
        if fn_name == "bench_engine_e2e_dist":
            for line in last_stdout["text"].splitlines():
                if line.startswith("BENCH_SHARDS"):
                    extra["engine_e2e_dist_shards"] = int(line.split()[1])
        # flight-recorder stage breakdowns / extra sub-metrics any child
        # printed fold into the result line
        for line in last_stdout["text"].splitlines():
            if line.startswith("BENCH_STAGES "):
                key = fn_name.replace("bench_", "") + "_stages"
                try:
                    extra[key] = json.loads(line[len("BENCH_STAGES "):])
                except ValueError:
                    pass
            elif line.startswith("BENCH_EXTRA "):
                try:
                    extra.update(json.loads(line[len("BENCH_EXTRA "):]))
                except ValueError:
                    pass
        return v

    n_total = (1 if run_headline else 0) + len(configs)
    headline = 0.0
    if run_headline:
        try:
            headline = run("bench_tumbling_count", n_total)
        except Exception as ex:  # noqa: BLE001 — the others still run
            extra["error"] = f"headline failed: {type(ex).__name__}: {ex}"
            failed.append("tumbling_count_group_by_events_per_sec")
        _emit_line(headline, dict(extra, status=f"partial 1/{n_total}"))

    for i, (name, fn_name, base) in enumerate(configs):
        try:
            v = run(fn_name, len(configs) - i)
            extra[name] = round(v, 1)
            # a metric name not ending in _events_s (push_fanout's
            # delivered_rows_s) must not have its value CLOBBERED by the
            # no-op replace writing vs_baseline over the same key
            vs_key = name.replace("_events_s", "_vs_baseline")
            if vs_key == name:
                vs_key = name + "_vs_baseline"
            extra[vs_key] = round(v / base, 2)
        except Exception as ex:  # noqa: BLE001 — a failed sub-bench lets
            # the others run, and fails the process at the end
            extra[name] = f"error: {type(ex).__name__}: {ex}"
            failed.append(name)
        done = (1 if run_headline else 0) + 1 + i
        status = dict(extra, status=f"partial {done}/{n_total}") \
            if i < len(configs) - 1 else extra
        _emit_line(headline, status)
    if failed:
        print(f"bench: {len(failed)} of {n_total} failed: {failed}",
              file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    import sys as _sys

    if len(_sys.argv) == 2 and _sys.argv[1] == "--probe":
        _probe()
    elif len(_sys.argv) == 3 and _sys.argv[1] == "--one":
        _run_one(_sys.argv[2])
    else:
        _sys.exit(main())
