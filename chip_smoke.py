#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call, on
one TPU chip, and checks what comes out against references that share no
code with the system.  One process: the process that runs this script is
the one that holds the chip.

Phases (all of them by default, in this order; any failure fails the run):

``served``  BASELINE config #1 on the served path at a deployment's size:
            an in-process ``KsqlServer`` driven over HTTP (``POST /ksql``
            DDL + ``CREATE TABLE ... WINDOW TUMBLING (SIZE 1 HOUR)``),
            3,000,000 ~100-byte JSON page views (half Zipf(1.3), half
            uniform over 10^6 URLs, three windows) produced at the pace
            the server's own poll loop drains them, then ``POST /query``
            pull lookups and one ``/query-stream`` push session.  Every
            pulled count, the whole materialized sink and the live store
            entries are compared with a ``collections.Counter`` over the
            generated events.
``steps``   BASELINE configs #2 (hopping SUM/AVG/MIN/MAX over a DOUBLE),
            #3 (stream-table LEFT JOIN + WHERE against a 10^6-row table),
            #4 (stream-stream JOIN WITHIN ... GRACE) and #5 (SESSION COUNT)
            through ``execute_sql`` + ``poll_once`` at the engine's default
            batch capacity and state slots, each compared with a
            ``ksql.runtime.backend=oracle`` twin fed the same records
            (DOUBLE values to 1e-12: the chip emulates float64).
``taps``    one fused push-residual kernel: 16 filtered push sessions over
            one stream, delivered rows compared with the predicate applied
            to the produced rows.
``cache``   a second in-process build of the config #1 query, with the
            persistent compilation cache's hits and misses printed, so a
            cache key that moves would show.

``--chips 4`` runs one phase and no other: ``mesh``, the served config #1
on ``ksql.runtime.backend=distributed`` with ``ksql.device.shards=4``, with
the state checked to sit on four devices.

The script refuses to run — exit code 2, ``"ok": false`` — unless
``jax.devices()[0].platform`` is ``tpu``.  ``--rehearse`` is the way to run
it without a chip (tests, a CPU sandbox): tiny sizes, whatever platform JAX
has, and a last line that names that platform.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import json
import os
import sys
import time
import urllib.request
from typing import Any, Dict, List, Optional, Sequence, Tuple

HOUR_MS = 3_600_000
#: first event time: aligned to the hour, so three hours are three windows
TS0 = 1_700_000_000_000 - 1_700_000_000_000 % HOUR_MS

PHASES_ONE_CHIP = ("served", "steps", "taps", "cache")


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond: Any, what: str, *detail: Any) -> None:
    if not cond:
        raise SmokeFailure(what + (": " + repr(detail) if detail else ""))


def say(phase: str, **facts: Any) -> None:
    print(f"SMOKE {phase} " + json.dumps(facts, sort_keys=True, default=str),
          flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    events: int          # config #1 corpus
    urls: int            # URL universe of its uniform half
    batch_capacity: int  # ksql.batch.capacity of the served engine
    state_slots: int     # ksql.state.slots of the served engine
    min_live: int        # live (key, window) entries the store must end with
    pull_keys: int
    users: int           # config #3 table rows
    step_events: int     # stream events per config #2-#5 run
    step_keys: int
    taps: int
    tap_events: int
    wait_s: float        # ceiling on any one wait for the server


REAL = Sizes(
    events=3_000_000, urls=1_000_000, batch_capacity=32_768,
    state_slots=1 << 21, min_live=1_000_000, pull_keys=120,
    users=1_000_000, step_events=60_000, step_keys=5_000,
    taps=16, tap_events=4_000, wait_s=600.0,
)
TINY = Sizes(
    events=6_000, urls=2_000, batch_capacity=1_024,
    state_slots=1 << 13, min_live=2_000, pull_keys=30,
    users=3_000, step_events=1_500, step_keys=40,
    taps=16, tap_events=300, wait_s=120.0,
)

PV_DDL = (
    "CREATE STREAM PAGE_VIEWS (URL STRING, USER_ID BIGINT, VIEWTIME BIGINT) "
    "WITH (KAFKA_TOPIC='page_views', VALUE_FORMAT='JSON');"
)
PV_COUNTS = (
    "CREATE TABLE PV_COUNTS AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
    "WINDOW TUMBLING (SIZE 1 HOUR) GROUP BY URL EMIT CHANGES;"
)


# ---------------------------------------------------------------- the device
def device_facts() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def on_platform(tree: Any, platform: str) -> List[str]:
    """Platforms the leaves of a state pytree live on, other than
    ``platform`` (empty = all of it is there)."""
    import jax

    return sorted({
        d.platform
        for leaf in jax.tree_util.tree_leaves(tree)
        for d in leaf.devices()
        if d.platform != platform
    })


class CacheCounter:
    """JAX's own count of persistent-compilation-cache traffic."""

    EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self) -> None:
        import jax

        self.n = {"requests": 0, "hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw: Any) -> None:
        name = self.EVENTS.get(event)
        if name is not None:
            self.n[name] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.n)


# ---------------------------------------------------------------- the corpus
@dataclasses.dataclass
class Corpus:
    payloads: List[str]
    ts: List[int]
    #: the reference: events per (URL, window start), straight from the data
    counts: "collections.Counter[Tuple[str, int]]"
    url_idx: Any  # numpy int64[n]
    user: Any  # numpy int64[n]


def url_of(k: int) -> str:
    return f"/catalog/products/item-{k:07d}/view.html"


def make_corpus(seed: int, sizes: Sizes) -> Corpus:
    """Page views from ``seed``: half from a Zipf(1.3) hot set, half uniform
    over the URL universe, shuffled together; event times rise evenly
    across three one-hour windows."""
    import numpy as np

    n = sizes.events
    rng = np.random.default_rng(seed)
    hot = rng.zipf(1.3, size=n // 2).astype(np.int64) % sizes.urls
    cold = rng.integers(0, sizes.urls, n - n // 2)
    idx = np.concatenate([hot, cold])
    rng.shuffle(idx)
    ts = TS0 + (np.arange(n, dtype=np.int64) * (3 * HOUR_MS)) // n
    user = 1 + (np.arange(n, dtype=np.int64) * 7919) % 999
    urls = [url_of(k) for k in idx.tolist()]
    tsl = ts.tolist()
    payloads = [
        '{"URL":"%s","USER_ID":%d,"VIEWTIME":%d}' % (u, uid, t)
        for u, uid, t in zip(urls, user.tolist(), tsl)
    ]
    counts = collections.Counter(
        zip(urls, (t - t % HOUR_MS for t in tsl))
    )
    return Corpus(payloads, tsl, counts, idx, user)


# ------------------------------------------------------------------- HTTP
def post(url: str, path: str, body: Dict[str, Any],
         headers: Optional[Dict[str, str]] = None, timeout: float = 120.0):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode("utf-8"),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read().decode("utf-8")


def get(url: str, path: str, headers: Optional[Dict[str, str]] = None) -> str:
    req = urllib.request.Request(url + path, headers=headers or {})
    with urllib.request.urlopen(req, timeout=60.0) as resp:
        return resp.read().decode("utf-8")


# ------------------------------------------------------ what a clean run logs
#: processing-log categories that report the system working as designed
#: under load; anything else after a run is an error entry
_PLOG_INFORMATIONAL = ("overload.", "telemetry.", "deadline.hint")


def plog_errors(engine) -> List[Tuple[str, str]]:
    return [
        (where, msg) for where, msg in list(engine.processing_log)
        if not where.startswith(_PLOG_INFORMATIONAL)
    ]


def check_on_device(engine, handle, backend: str, platform: str,
                    when: str) -> None:
    """The rung asserts: the query runs where it was asked to, nothing
    fell back, nothing was logged as an error."""
    check(handle.backend == backend, f"backend {when}",
          handle.backend, dict(engine.fallback_reasons), plog_errors(engine))
    check(engine.fallback_reasons == {}, f"fallback_reasons {when}",
          dict(engine.fallback_reasons))
    check(not plog_errors(engine), f"processing log {when}",
          plog_errors(engine)[:5])
    check(handle.state == "RUNNING", f"query state {when}", handle.state,
          [str(x) for x in handle.error_queue][-3:])
    elsewhere = on_platform(handle.executor.device.state, platform)
    check(not elsewhere, f"state arrays off the {platform} {when}", elsewhere)


# ------------------------------------------------------- phase: served (#1)
def phase_served(sizes: Sizes, seed: int, platform: str,
                 shards: int = 0) -> Dict[str, Any]:
    """Config #1 behind the REST server; ``shards`` > 0 asks for the
    distributed backend at that mesh width (the ``mesh`` phase)."""
    from ksql_tpu import native
    from ksql_tpu.common import config as cfg
    from ksql_tpu.common.config import KsqlConfig
    from ksql_tpu.engine.engine import KsqlEngine
    from ksql_tpu.server.rest import KsqlServer

    phase = "mesh" if shards else "served"
    backend = "distributed" if shards else "device"
    t0 = time.perf_counter()
    corpus = make_corpus(seed, sizes)
    say(phase, step="corpus", events=len(corpus.payloads),
        payload_bytes_mean=round(
            sum(map(len, corpus.payloads[:1000])) / min(1000, sizes.events), 1),
        reference_entries=len(corpus.counts),
        windows=len({w for _, w in corpus.counts}),
        seconds=round(time.perf_counter() - t0, 2))
    check(len({w for _, w in corpus.counts}) >= 3, "corpus spans < 3 windows")
    check(len(corpus.counts) >= sizes.min_live,
          "corpus has too few (key, window) entries", len(corpus.counts))
    check(native.available(), "native ingest library", native.build_error())

    props: Dict[str, Any] = {
        cfg.BATCH_CAPACITY: sizes.batch_capacity,
        cfg.STATE_SLOTS: sizes.state_slots,
    }
    if shards:
        props[cfg.RUNTIME_BACKEND] = "distributed"
        props[cfg.DEVICE_SHARDS] = shards
    engine = KsqlEngine(KsqlConfig(props))
    say(phase, step="config", **{k: v for k, v in props.items()},
        backend_asked=backend)
    srv = KsqlServer(engine=engine, port=0)
    srv.start()
    try:
        return _drive_served(phase, srv, engine, corpus, sizes, platform,
                             backend, shards)
    finally:
        # daemon-thread XLA teardown aborts the process otherwise
        srv.stop()


def _drive_served(phase, srv, engine, corpus, sizes, platform, backend,
                  shards) -> Dict[str, Any]:
    import numpy as np

    from ksql_tpu.runtime.topics import Record

    url = srv.url
    out = json.loads(post(url, "/ksql", {"ksql": PV_DDL + " " + PV_COUNTS}))
    qid = next(
        e["commandStatus"]["queryId"] for e in out
        if e.get("commandStatus", {}).get("queryId")
    )
    handle = engine.queries[qid]
    check_on_device(engine, handle, backend, platform, "after CREATE")
    ex = handle.executor
    check(ex._native_fields is not None, "native ingest not engaged for the plan")
    if shards:
        check(ex.device.n_shards == shards, "mesh width", ex.device.n_shards)
        check(ex.native_ingest_bypassed is False, "native ingest bypassed on the mesh")

    # ---- produce at the pace the server drains: the backlog stays under
    # the overload manager's ELEVATED lag (50,000 rows), as an upstream
    # that is not in trouble keeps it
    topic = engine.broker.topic("page_views")
    high_water, chunk = 40_000, 4_096
    n = len(corpus.payloads)
    produced = 0
    t_start = time.perf_counter()
    deadline = t_start + sizes.wait_s

    def consumed() -> int:
        return sum(handle.consumer.positions.values())

    def fail_fast() -> None:
        check(handle.state == "RUNNING", "query left RUNNING during the drain",
              handle.state, [str(x) for x in handle.error_queue][-3:],
              plog_errors(engine)[:5])
        check(time.perf_counter() < deadline, "drain ran out of time",
              {"produced": produced, "consumed": consumed()})

    t_first = None
    while produced < n:
        if produced - consumed() < high_water:
            hi = min(produced + chunk, n)
            for i in range(produced, hi):
                topic.produce(Record(
                    key=None, value=corpus.payloads[i], timestamp=corpus.ts[i]
                ))
            produced = hi
        else:
            time.sleep(0.002)
        if t_first is None and consumed() >= min(n, 2 * sizes.batch_capacity):
            # the first ticks carry the XLA compile; the rate is read after
            t_first = (time.perf_counter(), consumed())
        fail_fast()
    while consumed() < n or ex.pending_records() > 0:
        time.sleep(0.005)
        fail_fast()
    t_end = time.perf_counter()
    # one more tick flushes the emissions the double-buffer still holds
    sink = engine.broker.topic(handle.plan.physical_plan.topic)
    last, stable_since = -1, time.perf_counter()
    while True:
        size = sum(sink.end_offsets())
        if size != last:
            last, stable_since = size, time.perf_counter()
        elif time.perf_counter() - stable_since >= 0.5:
            # a tick holds the server's engine lock from its poll to its
            # last sink record (a block's records land together, at the
            # end of its dispatch): with the lock in hand no tick is
            # half-way, whatever the sink's size said meanwhile
            with srv.engine_lock:
                if sum(sink.end_offsets()) == size:
                    break
        time.sleep(0.05)
        fail_fast()
    warm_n = n - t_first[1] if t_first else 0
    say(phase, step="drain", events=n, seconds_total=round(t_end - t_start, 2),
        events_per_s_after_first_batches=(
            round(warm_n / (t_end - t_first[0]), 1) if warm_n else None),
        events_after_first_batches=warm_n, sink_records=last)

    check_on_device(engine, handle, backend, platform, "after the drain")
    ingest = dict(ex.native_ingest_rows)
    check(sum(ingest.values()) > 0, "native ingest decoded no rows", ingest)

    # ---- the store: live (key, window) entries on the device
    occ = np.asarray(ex.device.state["occ"])
    live = int(occ[..., :-1].sum())
    say(phase, step="store", live_entries=live,
        reference_entries=len(corpus.counts), slots=sizes.state_slots,
        native_ingest_rows=ingest)
    check(live >= sizes.min_live, "live store entries", live)
    check(live == len(corpus.counts), "live entries != reference", live,
          len(corpus.counts))

    # ---- SHOW QUERIES over HTTP
    shown = json.loads(post(url, "/ksql", {"ksql": "SHOW QUERIES;"}))[0]["rows"]
    row = next(r for r in shown if r["id"] == qid)
    say(phase, step="show_queries", **{k: row[k] for k in
                                       ("id", "status", "backend", "health")})
    check(row["status"] == "RUNNING" and row["backend"] == backend
          and row["health"] in ("HEALTHY", "IDLE"), "SHOW QUERIES", row)

    # ---- pull lookups: hot, middling and cold keys, all their windows
    by_url: Dict[str, Dict[int, int]] = {}
    for (u, w), c in corpus.counts.items():
        by_url.setdefault(u, {})[w] = c
    ranked = sorted(by_url, key=lambda u: (-sum(by_url[u].values()), u))
    third = sizes.pull_keys // 3
    mid = len(ranked) // 2
    keys = ranked[:third] + ranked[mid:mid + third] + ranked[-third:]
    keys.append(url_of(sizes.urls + 1))  # a URL no event carries
    t_pull = time.perf_counter()
    for u in keys:
        res = json.loads(post(url, "/query", {
            "ksql": f"SELECT URL, WINDOWSTART, CNT FROM PV_COUNTS WHERE URL = '{u}';"
        }))
        cols = res["columnNames"]
        got = {
            r[cols.index("WINDOWSTART")]: r[cols.index("CNT")]
            for r in res["rows"]
        }
        check(got == by_url.get(u, {}), "pulled counts != reference", u, got,
              by_url.get(u, {}))
    say(phase, step="pull", keys=len(keys), all_equal_reference=True,
        windows_seen=len({w for u in keys for w in by_url.get(u, {})}),
        seconds=round(time.perf_counter() - t_pull, 2))

    # ---- one push session over /query-stream
    want_uid = 7
    matching = np.nonzero(corpus.user[:200_000] == want_uid)[0].tolist()[:25]
    limit = len(matching)
    check(limit >= 3, "corpus has too few rows for the push query", limit)
    body = post(url, "/query-stream", {
        "sql": "SELECT URL, USER_ID, VIEWTIME FROM PAGE_VIEWS "
               f"WHERE USER_ID = {want_uid} EMIT CHANGES LIMIT {limit};"
    }, headers={"X-Query-Timeout-Seconds": "120"}, timeout=180.0)
    lines = [json.loads(x) for x in body.splitlines() if x.strip()]
    header, rows = lines[0], [x for x in lines[1:] if isinstance(x, list)]
    want_rows = [
        [url_of(int(corpus.url_idx[i])), want_uid, corpus.ts[i]]
        for i in matching
    ]
    check(header["columnNames"] == ["URL", "USER_ID", "VIEWTIME"],
          "push header", header)
    check(rows == want_rows, "push rows != reference", rows[:3], want_rows[:3])
    say(phase, step="push", rows=len(rows), all_equal_reference=True)

    # ---- the sink topic, materialized: every (key, window) = the reference
    mat: Dict[Tuple[str, int], int] = {}
    for r in sink.all_records():
        key = r.key[0] if isinstance(r.key, tuple) else r.key
        mat[(key, r.window[0])] = (
            None if r.value is None else json.loads(r.value)["CNT"]
        )
    per_window = collections.Counter(w for _, w in mat)
    ref_per_window = collections.Counter(w for _, w in corpus.counts)
    check(per_window == ref_per_window, "sink rows per window != reference",
          dict(per_window), dict(ref_per_window))
    check(mat == dict(corpus.counts), "materialized sink != reference")
    say(phase, step="sink", rows_per_window=dict(sorted(per_window.items())),
        all_equal_reference=True)

    facts: Dict[str, Any] = {}
    if shards:
        facts.update(_check_mesh(phase, url, ex, qid, shards, platform))

    # ---- where the time went, by the system's own spans
    rec = engine.trace_recorders.get(qid)
    stages = rec.stage_stats() if rec is not None else {}
    compile_s = round(stages.get("device.compile", {}).get("total_ms", 0.0) / 1e3, 2)
    check("device.compile" in stages, "no device.compile span recorded")
    if shards:
        # every event crossed the all-to-all once, in whole buckets
        exch, buckets = stages.get("exchange", {}), shards * shards * ex.device.bucket_capacity
        check(exch.get("rows") == len(corpus.payloads)
              and exch["rows"] / shards <= exch["rows_fullest_shard"] <= exch["rows"]
              and exch["lanes"] == exch["steps"] * buckets
              and exch["wire_bytes"] > exch["bytes"] > 0,
              "exchange counters", exch)
    import jax

    mem = jax.devices()[0].memory_stats() or {}
    say(phase, step="spans", compile_seconds=compile_s, stages=stages)
    say(phase, step="memory",
        peak_bytes_in_use=mem.get("peak_bytes_in_use", "not reported"),
        bytes_limit=mem.get("bytes_limit", "not reported"),
        overload=engine.overload.stats()["actions-total"])
    facts.update(qid=qid, compile_seconds=compile_s, live_entries=live)
    return facts


def _check_mesh(phase, url, ex, qid, shards, platform) -> Dict[str, Any]:
    """Every store array in ``shards`` pieces on ``shards`` distinct devices,
    each a quarter of the slots; rows counted on every shard."""
    import numpy as np

    per_array = {}
    for name, leaf in ex.device.state.items():
        pieces = leaf.addressable_shards
        devs = {p.device for p in pieces}
        check(len(pieces) == shards and len(devs) == shards,
              "store array not on every device", name, len(pieces), len(devs))
        check(all(d.platform == platform for d in devs),
              "store array off the platform", name)
        check(all(p.data.shape[0] * shards == leaf.shape[0] for p in pieces),
              "shard does not hold 1/shards of the array", name,
              [p.data.shape for p in pieces], leaf.shape)
        per_array[name] = [str(p.data.shape) for p in pieces][0]
    occ = np.asarray(ex.device.state["occ"])[:, :-1].sum(axis=1)
    check((occ > 0).all(), "a shard holds no keys", occ.tolist())
    text = get(url, "/metrics", headers={"Accept": "text/plain"})
    rows = {}
    for line in text.splitlines():
        if line.startswith("ksql_query_shard_rows_total{") and qid in line:
            labels, value = line.rsplit(" ", 1)
            shard = labels.split('shard="')[1].split('"')[0]
            rows[shard] = float(value)
    check(len(rows) == shards and all(v > 0 for v in rows.values()),
          "ksql_query_shard_rows_total", rows)
    say(phase, step="mesh", shards=shards,
        devices=sorted(str(d) for d in
                       {p.device for p in ex.device.state["occ"].addressable_shards}),
        shard_shape_of=per_array, live_entries_per_shard=occ.tolist(),
        shard_rows_total=rows)
    return {"shards": shards}


# ------------------------------------------------ phase: steps (#2 .. #5)
def _twin(statements: Sequence[str], feeds, backend: str, platform: str,
          name: str) -> Tuple[list, Dict[str, Any]]:
    """Run ``statements`` on a fresh engine at its defaults, feed it chunk
    by chunk through ``poll_once``, and return the sink's records."""
    from ksql_tpu.common import config as cfg
    from ksql_tpu.common.config import KsqlConfig
    from ksql_tpu.engine.engine import KsqlEngine
    from ksql_tpu.runtime.topics import Record

    props = {cfg.RUNTIME_BACKEND: backend}
    if backend == "oracle":
        # the reference's own bookkeeping, not its answers: per-record
        # commit epochs deep-copy the oracle's stores, 6 minutes of the
        # 10^6-row table load of config #3
        props[cfg.COMMIT_PER_RECORD] = False
    e = KsqlEngine(KsqlConfig(props))
    try:
        for s in statements:
            results = e.execute_sql(s)
        qid = next(r.query_id for r in results if r.query_id)
        handle = e.queries[qid]
        if backend == "device":
            check_on_device(e, handle, "device", platform, f"{name} after CREATE")
        t0 = time.perf_counter()
        n = 0
        for topic, records in feeds:
            t = e.broker.topic(topic)
            for key, value, ts in records:
                t.produce(Record(key=key, value=value, timestamp=ts))
            n += len(records)
            while e.poll_once(max_records=1 << 17):
                pass
        while e.poll_once(max_records=1 << 17):
            pass
        dt = time.perf_counter() - t0
        facts: Dict[str, Any] = {"records": n, "seconds": round(dt, 2)}
        if backend == "device":
            check_on_device(e, handle, "device", platform, f"{name} after the feed")
            rec = e.trace_recorders.get(qid)
            st = rec.stage_stats() if rec is not None else {}
            comp = st.get("device.compile", {})
            facts.update(
                compile_seconds=round(comp.get("total_ms", 0.0) / 1e3, 2),
                compiles=int(comp.get("jit_miss", 0)),
                execute_ms=round(st.get("device.execute", {}).get("total_ms", 0.0), 1),
            )
            check(comp.get("jit_miss", 0) > 0, f"{name}: no compile recorded")
            # what the step programs count about themselves (joins too)
            facts.update({
                stage: {k: v for k, v in st[stage].items() if k not in ("n", "ticks")}
                for stage in ("table.upsert", "device.step") if stage in st
            })
        sink = e.broker.topic(handle.plan.physical_plan.topic)
        return [
            (r.key, r.value, r.timestamp, r.window) for r in sink.all_records()
        ], facts
    finally:
        e.shutdown()


#: relative tolerance for DOUBLE values against the oracle twin.  The chip
#: has no 64-bit float unit: XLA emulates float64 there to about 48 bits, so
#: a quotient (AVG) comes out within ~1e-14 of the IEEE value, not equal to
#: it (sums of exactly representable values do come out equal).  Set from
#: the dtype, ~140 units of 2^-47; nothing else is compared with a tolerance
DOUBLE_REL_TOL = 1e-12


def _same_row(a: Any, b: Any, worst: List[float]) -> bool:
    """Equal, floats to DOUBLE_REL_TOL (the largest relative difference
    seen lands in ``worst[0]``)."""
    if isinstance(a, float) and isinstance(b, float):
        if a == b:
            return True
        rel = abs(a - b) / max(abs(a), abs(b))
        worst[0] = max(worst[0], rel)
        return rel <= DOUBLE_REL_TOL
    if isinstance(a, dict) and isinstance(b, dict):
        return a.keys() == b.keys() and all(
            _same_row(a[k], b[k], worst) for k in a
        )
    if isinstance(a, (tuple, list)) and isinstance(b, (tuple, list)):
        return len(a) == len(b) and all(
            _same_row(x, y, worst) for x, y in zip(a, b)
        )
    return type(a) is type(b) and a == b


def _final_table(records: list) -> Dict[Any, Any]:
    """A table sink's changelog folded to its final state (tombstoned
    entries gone): what EMIT CHANGES promises per micro-batch is the latest
    row per key, so twins are compared on that."""
    state: Dict[Any, Any] = {}
    for key, value, ts, window in records:
        state[(key, window)] = None if value is None else (json.loads(value), ts)
    return {k: v for k, v in state.items() if v is not None}


def _chunks(records: list, size: int) -> List[list]:
    return [records[i:i + size] for i in range(0, len(records), size)]


def steps_cases(sizes: Sizes, seed: int):
    """(name, statements, feeds, kind) for BASELINE configs #2-#5.  Event
    times never go back, so no record is late on either twin."""
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    n, nk = sizes.step_events, sizes.step_keys

    # -- #2 hopping SUM/AVG/MIN/MAX over a DOUBLE; quarter-valued doubles
    # sum exactly in any order, so the twins must agree to the last bit
    keys = rng.zipf(1.3, n).astype(np.int64) % nk
    lat = rng.integers(0, 4000, n) / 4.0
    ts = TS0 + (np.arange(n, dtype=np.int64) * (3 * HOUR_MS)) // n
    pv = [
        (None, '{"URL":"/page/%d","USER_ID":%d,"LATENCY":%s}'
         % (k, 1 + i % 999, repr(float(v))), int(t))
        for i, (k, v, t) in enumerate(zip(keys.tolist(), lat.tolist(), ts.tolist()))
    ]
    yield (
        "config2_hopping_multi_udaf",
        [
            "CREATE STREAM PAGE_VIEWS (URL STRING, USER_ID BIGINT, LATENCY DOUBLE) "
            "WITH (KAFKA_TOPIC='page_views', VALUE_FORMAT='JSON');",
            "CREATE TABLE PV_STATS AS SELECT URL, SUM(LATENCY) AS S, "
            "AVG(LATENCY) AS A, MIN(LATENCY) AS MN, MAX(LATENCY) AS MX "
            "FROM PAGE_VIEWS WINDOW HOPPING (SIZE 1 HOUR, ADVANCE BY 15 MINUTES) "
            "GROUP BY URL EMIT CHANGES;",
        ],
        [("page_views", c) for c in _chunks(pv, 8192)],
        "table",
    )

    # -- #3 stream-table LEFT JOIN + WHERE: the table is loaded first
    nu = sizes.users
    users = [
        (k, '{"NAME":"user%d","REGION":"%s"}'
         % (k, "excluded" if k % 50 == 0 else "r%d" % (k % 50)), TS0)
        for k in range(nu)
    ]
    uid = rng.integers(0, 2 * nu, n)  # about half of the clicks match
    clicks = [
        (None, '{"USER_ID":%d,"URL":"/u/%d"}' % (u, u % 997), TS0 + 1 + 3 * i)
        for i, u in enumerate(uid.tolist())
    ]
    yield (
        "config3_stream_table_join",
        [
            "CREATE TABLE USERS (ID BIGINT PRIMARY KEY, NAME STRING, REGION STRING) "
            "WITH (KAFKA_TOPIC='users', VALUE_FORMAT='JSON');",
            "CREATE STREAM CLICKS (USER_ID BIGINT, URL STRING) "
            "WITH (KAFKA_TOPIC='clicks', VALUE_FORMAT='JSON');",
            "CREATE STREAM ENRICHED AS SELECT C.USER_ID, C.URL, U.REGION "
            "FROM CLICKS C LEFT JOIN USERS U ON C.USER_ID = U.ID "
            "WHERE U.REGION <> 'excluded' EMIT CHANGES;",
        ],
        [("users", c) for c in _chunks(users, 1 << 16)]
        + [("clicks", c) for c in _chunks(clicks, 8192)],
        "stream",
    )

    # -- #4 stream-stream JOIN WITHIN 10 SECONDS GRACE 1 SECOND: the two
    # sides arrive in alternating chunks, 10 ms apart overall — 1,000 rows
    # of each side in reach of a row, which is what the oracle twin scans
    # per record (it takes 3 ms a record at five times that)
    half = min(n, 10_000) // 2
    ids = rng.integers(0, max(nk * 4, 8), 2 * half)
    feeds = []
    for c in range(0, half, 1024):
        for side, off in (("lt", 0), ("rt", 1)):
            feeds.append((side, [
                (int(ids[2 * i + off]), '{"V":%d}' % (2 * i + off),
                 TS0 + 10 * (2 * i + off))
                for i in range(c, min(c + 1024, half))
            ]))
    yield (
        "config4_stream_stream_join",
        [
            "CREATE STREAM LEFTS (ID BIGINT KEY, V BIGINT) "
            "WITH (KAFKA_TOPIC='lt', VALUE_FORMAT='JSON');",
            "CREATE STREAM RIGHTS (ID BIGINT KEY, V BIGINT) "
            "WITH (KAFKA_TOPIC='rt', VALUE_FORMAT='JSON');",
            "CREATE STREAM J AS SELECT L.ID, L.V AS LV, R.V AS RV FROM LEFTS L "
            "LEFT JOIN RIGHTS R WITHIN 10 SECONDS GRACE PERIOD 1 SECOND "
            "ON L.ID = R.ID EMIT CHANGES;",
        ],
        feeds,
        "stream",
    )

    # -- #5 SESSION COUNT, 30 s gap over four minutes: the sparse keys of
    # the Zipf tail open several sessions each
    keys = rng.zipf(1.3, n).astype(np.int64) % nk
    ts = TS0 + (np.arange(n, dtype=np.int64) * 240_000) // n
    sess = [
        (None, '{"URL":"/page/%d","USER_ID":%d}' % (k, 1 + i % 999), int(t))
        for i, (k, t) in enumerate(zip(keys.tolist(), ts.tolist()))
    ]
    yield (
        "config5_session",
        [
            "CREATE STREAM PAGE_VIEWS (URL STRING, USER_ID BIGINT) "
            "WITH (KAFKA_TOPIC='page_views', VALUE_FORMAT='JSON');",
            "CREATE TABLE SESSIONS AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
            "WINDOW SESSION (30 SECONDS) GROUP BY URL EMIT CHANGES;",
        ],
        [("page_views", c) for c in _chunks(sess, 8192)],
        "table",
    )


def phase_steps(sizes: Sizes, seed: int, platform: str,
                only: Optional[str] = None) -> Dict[str, Any]:
    from ksql_tpu.common import config as cfg
    from ksql_tpu.common.config import KsqlConfig

    defaults = KsqlConfig({})
    say("steps", step="config", **{
        cfg.BATCH_CAPACITY: defaults.get(cfg.BATCH_CAPACITY),
        cfg.STATE_SLOTS: defaults.get(cfg.STATE_SLOTS),
    })
    done = {}
    for name, statements, feeds, kind in steps_cases(sizes, seed):
        if only is not None and only not in name:
            continue
        dev, dev_facts = _twin(statements, feeds, "device", platform, name)
        ora, ora_facts = _twin(statements, feeds, "oracle", platform, name)
        worst = [0.0]
        if kind == "table":
            got, want = _final_table(dev), _final_table(ora)
            windows = len({k[1] for k in want})
            differing = [
                (k, got.get(k), want[k]) for k in want
                if not _same_row(got.get(k), want[k], worst)
            ] if got.keys() == want.keys() else [("keys differ", len(got), len(want))]
        else:
            # every row, with its key, value and timestamp; not their
            # order across a micro-batch, which batched emission does not
            # promise (a LEFT JOIN's deferred null-pads leave after their
            # batch's matches; the oracle interleaves them per record)
            got, want = sorted(map(repr, dev)), sorted(map(repr, ora))
            windows = None
            differing = [
                (a, b) for a, b in zip(got, want) if a != b
            ] if len(got) == len(want) else [("counts differ", len(got), len(want))]
        check(len(want) > 0, f"{name}: the oracle twin emitted nothing")
        check(not differing, f"{name}: device sink != oracle twin",
              len(differing), differing[:4])
        say("steps", step=name, backend="device", rows_compared=len(want),
            sink_records_device=len(dev), sink_records_oracle=len(ora),
            windows=windows, equal_oracle_twin=True,
            same_order=dev == ora if kind == "stream" else None,
            double_rel_tol=DOUBLE_REL_TOL if worst[0] else None,
            double_max_rel_diff=worst[0] or None,
            device=dev_facts, oracle_seconds=ora_facts["seconds"])
        done[name] = dev_facts
    return done


# ---------------------------------------------------------- phase: taps
def phase_taps(sizes: Sizes, seed: int, platform: str) -> Dict[str, Any]:
    """``sizes.taps`` filtered push sessions over one stream: one shared
    pipeline, every residual in one fused device kernel."""
    from ksql_tpu.common.config import KsqlConfig
    from ksql_tpu.engine.engine import KsqlEngine
    from ksql_tpu.runtime.topics import Record
    from ksql_tpu.server.rest import PushQuerySession

    n_taps, n = sizes.taps, sizes.tap_events
    e = KsqlEngine(KsqlConfig({}))
    sessions: List[Any] = []
    try:
        e.execute_sql(PV_DDL)
        e.session_properties["auto.offset.reset"] = "latest"
        sessions = [
            PushQuerySession(
                e, "SELECT URL, VIEWTIME FROM PAGE_VIEWS "
                   f"WHERE USER_ID % {n_taps} = {i} EMIT CHANGES;")
            for i in range(n_taps)
        ]
        stats = e.push_registry.stats()
        check(stats["pipelines"] == 1 and stats["taps-total"] == n_taps
              and stats["residual"]["fused-taps"] == n_taps,
              "taps not fused onto one pipeline", stats)
        pipe = next(iter(e.push_registry.pipelines.values()))
        check(pipe.backend == "device", "shared pipeline backend", pipe.backend,
              plog_errors(e))
        t = e.broker.topic("page_views")
        got: List[List[dict]] = [[] for _ in sessions]
        t0 = time.perf_counter()
        for lo in range(0, n, 256):
            for i in range(lo, min(lo + 256, n)):
                t.produce(Record(
                    key=None, timestamp=TS0 + i,
                    value='{"URL":"/page/%d","USER_ID":%d,"VIEWTIME":%d}'
                          % (i % 97, i, TS0 + i),
                ))
            for s, rows in zip(sessions, got):
                rows.extend(s.poll())
        while True:
            more = 0
            for s, rows in zip(sessions, got):
                new = s.poll()
                rows.extend(new)
                more += len(new)
            if not more:
                break
        dt = time.perf_counter() - t0
        for k, rows in enumerate(got):
            want = [
                {"URL": "/page/%d" % (i % 97), "VIEWTIME": TS0 + i}
                for i in range(n) if i % n_taps == k
            ]
            check(rows == want, f"tap {k} rows != reference", len(rows), len(want))
        kernel = pipe.kernel
        res = e.push_registry.stats()["residual"]
        check(kernel is not None and kernel.degraded is None,
              "fused tap kernel degraded", getattr(kernel, "degraded", None))
        check(res["kernel-evals-total"] > 0 and res["degraded-total"] == 0,
              "fused kernel never ran", res)
        check(e.fallback_reasons == {} and not plog_errors(e),
              "taps fell back", dict(e.fallback_reasons), plog_errors(e)[:5])
        facts = dict(
            taps=n_taps, events=n, delivered=sum(map(len, got)),
            pipeline_backend=pipe.backend, kernel_degraded=kernel.degraded,
            kernel_evals=res["kernel-evals-total"],
            kernel_rows=res["kernel-rows-total"],
            compile_epochs=res["compile-epochs-total"],
            seconds=round(dt, 2), all_equal_reference=True,
        )
        say("taps", **facts)
        return facts
    finally:
        for s in sessions:
            s.close()
        e.shutdown()


# --------------------------------------------------------- phase: cache
def phase_cache(sizes: Sizes, seed: int, platform: str,
                counter: CacheCounter, cache_dir: str) -> Dict[str, Any]:
    """Build config #1 twice, each time on a fresh engine (fresh ``jax.jit``
    objects, so nothing in memory is shared) and step it once: the second
    build must be served by the persistent cache."""
    from ksql_tpu.common import config as cfg
    from ksql_tpu.common.config import KsqlConfig
    from ksql_tpu.engine.engine import KsqlEngine
    from ksql_tpu.runtime.topics import Record

    def build_and_step() -> Dict[str, int]:
        before = counter.snapshot()
        e = KsqlEngine(KsqlConfig({
            cfg.BATCH_CAPACITY: sizes.batch_capacity,
            cfg.STATE_SLOTS: sizes.state_slots,
        }))
        try:
            e.execute_sql(PV_DDL)
            e.execute_sql(PV_COUNTS)
            t = e.broker.topic("page_views")
            for i in range(64):
                t.produce(Record(
                    key=None, timestamp=TS0 + i,
                    value='{"URL":"/p/%d","USER_ID":1,"VIEWTIME":%d}' % (i % 5, TS0 + i),
                ))
            while e.poll_once():
                pass
            handle = next(iter(e.queries.values()))
            check(handle.backend == "device" and handle.state == "RUNNING",
                  "cache phase query", handle.backend, handle.state)
        finally:
            e.shutdown()
        after = counter.snapshot()
        return {k: after[k] - before[k] for k in after}

    first = build_and_step()
    second = build_and_step()
    say("cache", dir=cache_dir, first_build=first, second_build=second,
        entries_on_disk=len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0)
    check(second["requests"] > 0, "second build asked the cache nothing", second)
    check(second["hits"] > 0 and second["misses"] == 0,
          "second build of the same program missed the persistent cache "
          "(a cache key that moves?)", first, second)
    return {"first": first, "second": second}


# ------------------------------------------------------------------ main
def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20240921)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed config #1 on a "
                         "four-chip mesh")
    ap.add_argument("--phases", default="",
                    help="comma-separated subset of "
                         + ",".join(PHASES_ONE_CHIP) + " (one chip only)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever platform JAX has (no chip "
                         "needed); never reports a tpu it did not run on")
    args = ap.parse_args(argv)

    from ksql_tpu.runtime import compile_cache

    cache_dir = compile_cache.place()
    import jax

    if args.rehearse:
        # tiny programs compile in under JAX's one-second floor for the
        # persistent cache; the cache phase needs them written
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = CacheCounter()
    device = device_facts()
    platform = device["platform"]
    if platform != "tpu" and not args.rehearse:
        print(json.dumps({
            "ok": False, "device": device,
            "error": "no TPU: jax.devices()[0].platform is "
                     f"{platform!r} (--rehearse runs without one)",
        }))
        return 2
    if device["count"] < args.chips:
        print(json.dumps({
            "ok": False, "device": device,
            "error": f"needs {args.chips} devices, jax.devices() has "
                     f"{device['count']}",
        }))
        return 2
    if not args.rehearse:
        # what runs is what git holds: the native library is built here,
        # from the committed source, whatever the disk brought along (a
        # rehearsal shares its tree with test workers that have it loaded)
        from ksql_tpu import native

        for built in glob.glob(native.LIB_GLOB):
            os.unlink(built)
    sizes = TINY if args.rehearse else REAL
    say("start", device=device, rehearsal=args.rehearse, seed=args.seed,
        compile_cache=cache_dir, sizes=dataclasses.asdict(sizes))

    t0 = time.perf_counter()
    if args.chips == 4:
        phases = ["mesh"]
        phase_served(sizes, args.seed, platform, shards=4)
    else:
        run = {
            "served": phase_served,
            "steps": phase_steps,
            "taps": phase_taps,
            "cache": lambda *a: phase_cache(*a, counter, cache_dir),
        }
        phases = [p for p in args.phases.split(",") if p] or list(PHASES_ONE_CHIP)
        unknown = [p for p in phases if p not in run]
        if unknown:
            ap.error(f"unknown phase(s) {unknown}")
        for p in phases:
            run[p](sizes, args.seed, platform)
    say("done", phases=phases, seconds=round(time.perf_counter() - t0, 1),
        compile_cache_traffic=counter.snapshot())
    result: Dict[str, Any] = {"ok": True, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
