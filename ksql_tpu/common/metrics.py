"""Engine + per-query metrics — the MetricCollectors analog.

The reference wires Kafka's metrics library through MetricCollectors.java:53
and KsqlEngineMetrics.java:47: per-query consumption/production rates, error
rates, liveness, and engine-wide aggregates, surfaced over JMX and the REST
``DESCRIBE EXTENDED`` output.  Here the same shape is kept host-side and
surfaced over the REST ``/metrics`` endpoint (server/rest.py) and
``KsqlEngine.metrics_snapshot()``.

Rates are measured over a sliding window of recent marks (the Kafka
``Rate``/``SampledStat`` analog, 30s window by default) — cheap enough for
the per-batch hot path since marks carry counts, not per-record calls.
"""

from __future__ import annotations

import bisect
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

RATE_WINDOW_S = 30.0

#: e2e latency bucket upper bounds in seconds (Prometheus ``le`` values).
#: Spans sub-10ms device paths through replay/backfill scenarios where the
#: source timestamps are minutes-to-hours old; +Inf is implicit.
E2E_BUCKETS_S = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
    2.5, 5.0, 10.0, 30.0, 60.0, 300.0, 1800.0,
)


class Meter:
    """Total count + windowed rate (Kafka Rate/CumulativeCount analog)."""

    def __init__(self, window_s: float = RATE_WINDOW_S):
        self.total = 0
        self._window_s = window_s
        self._marks: deque = deque()  # (monotonic_ts, count)
        self._lock = threading.Lock()

    def mark(self, n: int = 1, now: Optional[float] = None) -> None:
        if n == 0:
            return
        now = time.monotonic() if now is None else now
        with self._lock:
            self.total += n
            self._marks.append((now, n))
            self._trim(now)

    def _trim(self, now: float) -> None:
        horizon = now - self._window_s
        while self._marks and self._marks[0][0] < horizon:
            self._marks.popleft()

    def rate_per_sec(self, now: Optional[float] = None) -> float:
        now = time.monotonic() if now is None else now
        with self._lock:
            self._trim(now)
            if not self._marks:
                return 0.0
            span = max(now - self._marks[0][0], 1e-3)
            return sum(c for _, c in self._marks) / span


class LatencyHistogram:
    """Sliding reservoir of recent batch latencies with percentile gauges
    (the Kafka metrics Percentiles / query processing-latency sensor)."""

    def __init__(self, capacity: int = 512):
        self._samples: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        # sorted view, invalidated per record(): percentile() is called
        # every poll tick by the health sampler, so an idle query must not
        # re-sort the reservoir tick after tick
        self._sorted: Optional[list] = None

    def record(self, seconds: float) -> None:
        with self._lock:
            self._samples.append(seconds * 1000.0)
            self._sorted = None

    def record_block(self, seconds: List[float]) -> None:
        """``record`` for each of a block's samples, in order, under one
        hold of the lock."""
        with self._lock:
            self._samples.extend(s * 1000.0 for s in seconds)
            self._sorted = None

    def percentile(self, p: float) -> Optional[float]:
        with self._lock:
            if not self._samples:
                return None
            if self._sorted is None:
                self._sorted = sorted(self._samples)
            xs = self._sorted
            idx = min(int(len(xs) * p), len(xs) - 1)
            return round(xs[idx], 3)


class E2eHistogram:
    """Fixed-bucket cumulative end-to-end latency histogram (record source
    timestamp → sink produce).  Unlike :class:`LatencyHistogram`'s sliding
    reservoir, bucket counts never forget — Prometheus histogram semantics
    require monotone cumulative counts, and the telemetry timeline derives
    per-interval distributions by differencing successive snapshots."""

    def __init__(self, bounds_s=E2E_BUCKETS_S):
        self.bounds = tuple(float(b) for b in bounds_s)
        # one count per finite bound plus the +Inf overflow bucket
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum_s = 0.0
        self.count = 0
        self._lock = threading.Lock()

    def record(self, seconds: float) -> None:
        idx = bisect.bisect_left(self.bounds, seconds)
        with self._lock:
            self.counts[idx] += 1
            self.count += 1
            self.sum_s += seconds

    def record_block(self, seconds: List[float]) -> None:
        """``record`` for each of a block's samples under one hold of the
        lock."""
        bounds, counts = self.bounds, self.counts
        with self._lock:
            for s in seconds:
                counts[bisect.bisect_left(bounds, s)] += 1
            self.count += len(seconds)
            self.sum_s += sum(seconds)

    def percentile(self, p: float) -> Optional[float]:
        """Interpolated percentile in ms (the +Inf bucket clamps to the
        last finite bound — a bound, not an estimate)."""
        with self._lock:
            total = self.count
            if not total:
                return None
            target = p * total
            cum = 0
            for i, c in enumerate(self.counts):
                if not c:
                    continue
                cum += c
                if cum >= target:
                    lo = self.bounds[i - 1] if i > 0 else 0.0
                    hi = (
                        self.bounds[i] if i < len(self.bounds)
                        else self.bounds[-1]
                    )
                    frac = (target - (cum - c)) / c
                    return round((lo + (hi - lo) * frac) * 1000.0, 3)
            return round(self.bounds[-1] * 1000.0, 3)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "bucketsS": list(self.bounds),
                "counts": list(self.counts),
                "sum": round(self.sum_s, 6),
                "count": self.count,
            }


class QueryMetrics:
    """Per-query collectors (ConsumerCollector/ProducerCollector analog)."""

    def __init__(self, query_id: str):
        self.query_id = query_id
        self.messages_in = Meter()
        self.messages_out = Meter()
        self.errors = Meter()
        self.latency = LatencyHistogram()
        self.last_message_at_ms: Optional[int] = None

    def snapshot(self) -> Dict[str, Any]:
        return {
            "messages-consumed-total": self.messages_in.total,
            "messages-consumed-per-sec": round(self.messages_in.rate_per_sec(), 3),
            "messages-produced-total": self.messages_out.total,
            "messages-produced-per-sec": round(self.messages_out.rate_per_sec(), 3),
            "processing-errors-total": self.errors.total,
            "processing-errors-per-sec": round(self.errors.rate_per_sec(), 3),
            "processing-latency-p50-ms": self.latency.percentile(0.50),
            "processing-latency-p99-ms": self.latency.percentile(0.99),
            "last-message-at-ms": self.last_message_at_ms,
        }


class MetricCollectors:
    """Engine-wide registry (MetricCollectors.java analog): per-query
    collectors plus the aggregate gauges KsqlEngineMetrics exposes."""

    def __init__(self) -> None:
        self._queries: Dict[str, QueryMetrics] = {}
        self._lock = threading.Lock()
        self.started_at = time.time()

    def for_query(self, query_id: str) -> QueryMetrics:
        with self._lock:
            qm = self._queries.get(query_id)
            if qm is None:
                qm = QueryMetrics(query_id)
                self._queries[query_id] = qm
            return qm

    def remove_query(self, query_id: str) -> None:
        with self._lock:
            self._queries.pop(query_id, None)

    def snapshot(self, engine=None) -> Dict[str, Any]:
        with self._lock:
            queries = {qid: qm.snapshot() for qid, qm in self._queries.items()}
        agg = {
            "messages-consumed-total": sum(
                q["messages-consumed-total"] for q in queries.values()
            ),
            "messages-consumed-per-sec": round(
                sum(q["messages-consumed-per-sec"] for q in queries.values()), 3
            ),
            "messages-produced-total": sum(
                q["messages-produced-total"] for q in queries.values()
            ),
            # the cumulative total keeps its honest name; "error-rate" is a
            # true windowed rate (it used to report the total under a
            # "rate" name, which read as a permanently-elevated error rate
            # long after the incident)
            "processing-errors-total": sum(
                q["processing-errors-total"] for q in queries.values()
            ),
            "error-rate": round(
                sum(q["processing-errors-per-sec"] for q in queries.values()), 3
            ),
            "uptime-seconds": round(time.time() - self.started_at, 1),
        }
        out: Dict[str, Any] = {"engine": agg, "queries": queries}
        if engine is not None:
            states: Dict[str, int] = {}
            health_states: Dict[str, int] = {}
            lags: Dict[str, int] = {}
            restarts_total = 0
            terminal_queries = []
            for qid, h in engine.queries.items():
                states[h.state] = states.get(h.state, 0) + 1
                lags[qid] = consumer_lag(h.consumer)
                restarts_total += h.restart_count
                if h.terminal:
                    terminal_queries.append(qid)
                prog = getattr(h, "progress", None)
                if prog is not None:
                    health_states[prog.health] = (
                        health_states.get(prog.health, 0) + 1
                    )
                if qid in out["queries"]:
                    out["queries"][qid]["state"] = h.state
                    out["queries"][qid]["backend"] = h.backend
                    out["queries"][qid]["consumer-lag"] = lags[qid]
                    out["queries"][qid]["restarts"] = h.restart_count
                    out["queries"][qid]["terminal"] = h.terminal
                    # processing-epoch counters: records re-consumed after
                    # a rewind (the bounded-duplicate window) and ticks the
                    # deadline watchdog had to abandon
                    out["queries"][qid]["replayed-records-total"] = getattr(
                        h, "replayed_records", 0
                    )
                    out["queries"][qid]["tick-deadline-exceeded-total"] = (
                        getattr(h, "tick_deadlines", 0)
                    )
                    # crash-consistent durability surface (ISSUE 20):
                    # rows between the restored positions and the topic
                    # ends at recovery time (the measured replay window),
                    # journal size, and snapshot staleness
                    out["queries"][qid]["recovery-replayed-rows-total"] = (
                        getattr(h, "recovery_replayed_rows", 0)
                    )
                    cl = getattr(engine, "_changelogs", {}).get(qid)
                    if cl is not None:
                        out["queries"][qid]["changelog-bytes"] = (
                            cl.size_bytes
                        )
                    saved_at = getattr(
                        engine, "_checkpoint_saved_at", {}
                    ).get(qid)
                    if saved_at:
                        out["queries"][qid]["checkpoint-age-seconds"] = (
                            round(max(0.0, time.time() - saved_at), 3)
                        )
                    if prog is not None:
                        # progress/health gauges (the tentpole's per-query
                        # freshness surface; Prometheus names below)
                        out["queries"][qid]["offset-lag"] = prog.offset_lag
                        out["queries"][qid]["watermark-ms"] = prog.watermark_ms
                        out["queries"][qid]["health"] = prog.health
                        out["queries"][qid]["e2e-latency-p50-ms"] = (
                            prog.e2e.percentile(0.50)
                        )
                        out["queries"][qid]["e2e-latency-p99-ms"] = (
                            prog.e2e.percentile(0.99)
                        )
                        # bucketed e2e distribution (the Prometheus
                        # histogram + timeline-interval substrate; the
                        # reservoir quantiles above stay for DESCRIBE)
                        hist = getattr(prog, "e2e_hist", None)
                        if hist is not None and hist.count:
                            out["queries"][qid][
                                "e2e-latency-histogram"
                            ] = hist.snapshot()
                        # standby-safe staleness gauge (sink-disabled
                        # replicas have no e2e latency; this is their
                        # freshness signal, also ridden by heartbeat gossip)
                        out["queries"][qid][
                            "materialization-freshness-ms"
                        ] = prog.freshness_ms()
                    # static memory model (analysis/mem_model): the
                    # admission-time footprint estimate, per report point
                    # (ksql_query_estimated_hbm_bytes{point} in Prometheus)
                    mem = getattr(h, "mem_report", None)
                    if mem is not None:
                        try:
                            # at_creation / at_growth_cap are PER-SHARD
                            # bytes (the scope the admission budget is
                            # expressed in); 'total' is the cluster-wide
                            # at-creation sum (n_shards x per-shard)
                            out["queries"][qid]["estimated-hbm-bytes"] = {
                                "at_creation": mem.per_shard_bytes(
                                    "at_creation"
                                ),
                                "at_growth_cap": mem.per_shard_bytes(
                                    "at_growth_cap"
                                ),
                                "total": mem.total_bytes("at_creation"),
                            }
                        except Exception:  # noqa: BLE001 — metrics must
                            pass  # never take down the snapshot endpoint
                    # elastic-mesh cutovers completed, per direction
                    # (ksql_query_reshard_total{direction} in Prometheus)
                    resh = getattr(h, "reshard_total", None)
                    if resh:
                        out["queries"][qid]["reshard-total"] = dict(resh)
                    # mesh fault domain: degraded-width gauge (1 while the
                    # query runs below its original shard width) and
                    # lifetime per-shard strike counters
                    if getattr(h, "backend", "") == "distributed":
                        out["queries"][qid]["mesh-degraded"] = (
                            1 if getattr(h, "mesh_degraded_from", None)
                            else 0
                        )
                    strikes = getattr(h, "shard_strikes_total", None)
                    if strikes:
                        out["queries"][qid]["shard-strikes-total"] = {
                            str(s): int(n) for s, n in strikes.items()
                        }
                    # distributed backend: per-shard rows in/out, exchange
                    # volume, and shard store occupancy (tentpole metrics)
                    shard_fn = getattr(h.executor, "shard_metrics", None)
                    if shard_fn is not None:
                        try:
                            out["queries"][qid]["shards"] = shard_fn()
                        except Exception:  # noqa: BLE001 — metrics must
                            pass  # never take down the snapshot endpoint
                    out["queries"][qid]["error-queue"] = [
                        {
                            "timestampMs": qe.timestamp_ms,
                            "message": qe.message,
                            "type": qe.error_type,
                        }
                        for qe in getattr(h, "error_queue", ())
                    ]
            out["engine"]["num-persistent-queries"] = len(engine.queries)
            out["engine"]["query-states"] = states
            out["engine"]["query-health"] = health_states
            out["engine"]["processing-log-dropped-total"] = getattr(
                engine, "plog_dropped", 0
            )
            out["engine"]["device-query-count"] = engine.device_query_count
            out["engine"]["distributed-query-count"] = getattr(
                engine, "distributed_query_count", 0
            )
            out["engine"]["total-consumer-lag"] = sum(lags.values())
            out["engine"]["query-restarts-total"] = restarts_total
            out["engine"]["push-session-restarts-total"] = getattr(
                engine, "push_session_restarts", 0
            )
            out["engine"]["terminal-error-queries"] = sorted(terminal_queries)
            # device fallback ladder + windowing-shape fallbacks (a hopping
            # query silently keeping the k-fold expansion instead of
            # slicing), per DeviceUnsupported reason string
            out["engine"]["fallback-reasons"] = dict(
                getattr(engine, "fallback_reasons", {}) or {}
            )
            # line-rate serde (ISSUE 17): rows decoded by the native C++
            # ingest tier per source format, and rows serialized through
            # the block-batched sink encoder (engine-wide totals; the
            # per-row fallback paths are NOT counted here by design —
            # these two series are the "is the fast path engaged" signal)
            native_rows: Dict[str, int] = {}
            batch_encoded = 0
            for h in engine.queries.values():
                rows = getattr(h.executor, "native_ingest_rows", None)
                if rows:
                    for fmt, cnt in rows.items():
                        key = str(fmt)
                        native_rows[key] = (
                            native_rows.get(key, 0) + int(cnt)
                        )
                wtr = getattr(h.executor, "sink_writer", None)
                if wtr is not None:
                    batch_encoded += int(
                        getattr(wtr, "batch_encoded_rows", 0)
                    )
            out["engine"]["native-ingest"] = {
                "rows-total": native_rows,
                "sink-batch-encoded-rows-total": batch_encoded,
            }
            # push registry (tentpole): shared serving pipelines + taps
            # fan-out gauges and delivered/evicted/gap counters
            registry = getattr(engine, "push_registry", None)
            if registry is not None:
                out["engine"]["push-registry"] = registry.stats()
            # overload manager (ISSUE 16): per-resource pressure levels,
            # engaged degradation actions, and shed/action counters
            overload = getattr(engine, "overload", None)
            if overload is not None:
                out["engine"]["overload"] = overload.stats()
            # multi-query optimizer (planner/mqo.py): shared-pipeline
            # gauges, cost-model verdicts, and attach refusals (runtime
            # refusals + cost rejects share one {reason} series)
            fam_members = getattr(engine, "family_members", None)
            if fam_members is not None:
                out["engine"]["mqo"] = {
                    "shared-pipelines": len(set(fam_members.values())),
                    "shared-members": len(fam_members),
                    "attach-refused-total": dict(
                        getattr(engine, "family_attach_refused", {}) or {}
                    ),
                    "decisions-total": dict(
                        getattr(engine, "mqo_decisions", {}) or {}
                    ),
                }
        return out


# ------------------------------------------------- Prometheus exposition
#
# text/plain (version 0.0.4) rendering of the metrics snapshot + the flight
# recorder's per-stage histograms, so the REST /metrics endpoint is
# scrapable by standard tooling (`Accept: text/plain` or
# `/metrics?format=prometheus`).  Cumulative totals export as counters
# (monotone for a query's lifetime); window-derived values (rates, stage
# percentiles) export as gauges.

import re as _re


def _prom_name(name: str) -> str:
    name = _re.sub(r"[^a-zA-Z0-9_:]", "_", str(name))
    if not name or not _re.match(r"[a-zA-Z_:]", name[0]):
        name = "_" + name
    return name


def _prom_escape(value: Any) -> str:
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace("\n", "\\n")
        .replace('"', '\\"')
    )


class _PromWriter:
    """Exposition writer with (name, labels) dedupe.  A query that
    restarts and re-registers its collectors must not emit the same series
    twice in one scrape — duplicates keep the LAST value.  Samples render
    grouped per metric name (one TYPE line each), names in
    first-appearance order."""

    def __init__(self) -> None:
        #: (name, rendered_labels) -> value; dict order = first appearance
        self._samples: Dict[tuple, Any] = {}
        self._types: Dict[str, str] = {}

    def sample(self, name: str, labels: Optional[Dict[str, Any]],
               value: Any, mtype: str = "gauge") -> None:
        if value is None or isinstance(value, bool) or not isinstance(
            value, (int, float)
        ):
            return
        name = _prom_name(name)
        self._types.setdefault(name, mtype)
        lbl = ""
        if labels:
            lbl = "{" + ",".join(
                f'{_prom_name(k)}="{_prom_escape(v)}"'
                for k, v in sorted(labels.items())
            ) + "}"
        self._samples[(name, lbl)] = value

    def text(self) -> str:
        by_name: Dict[str, list] = {}
        for (name, lbl), value in self._samples.items():
            by_name.setdefault(name, []).append(f"{name}{lbl} {value}")
        lines: list = []
        for name, samples in by_name.items():
            mtype = self._types[name]
            if mtype == "histogram":
                # exposition convention: one `# TYPE <base> histogram`
                # covers the _bucket/_sum/_count trio; the TYPE line
                # rides the _bucket series, the companions stay bare
                base = (
                    name[: -len("_bucket")]
                    if name.endswith("_bucket") else name
                )
                lines.append(f"# TYPE {base} histogram")
            elif mtype != "histogram_part":
                lines.append(f"# TYPE {name} {mtype}")
            lines.extend(samples)
        return "\n".join(lines) + "\n"


def _mtype_of(key: str) -> str:
    return "counter" if str(key).endswith("-total") else "gauge"


def _e2e_histogram_samples(w: "_PromWriter", labels: Dict[str, str],
                           h: Dict[str, Any]) -> None:
    """Emit one E2eHistogram snapshot as cumulative _bucket{le} samples
    plus _sum/_count (ksql_query_e2e_latency_seconds, pinned in
    metrics_registry.json)."""
    bounds = h.get("bucketsS") or []
    counts = list(h.get("counts") or [])
    if len(counts) < len(bounds) + 1:
        counts += [0] * (len(bounds) + 1 - len(counts))
    cum = 0
    for b, c in zip(bounds, counts):
        cum += c
        w.sample("ksql_query_e2e_latency_seconds_bucket",
                 {**labels, "le": f"{float(b):g}"}, cum, "histogram")
    cum += counts[len(bounds)]
    w.sample("ksql_query_e2e_latency_seconds_bucket",
             {**labels, "le": "+Inf"}, cum, "histogram")
    w.sample("ksql_query_e2e_latency_seconds_sum", labels,
             round(float(h.get("sum", 0.0)), 6), "histogram_part")
    w.sample("ksql_query_e2e_latency_seconds_count", labels,
             int(h.get("count", 0)), "histogram_part")


def prometheus_text(
    snapshot: Dict[str, Any],
    stage_stats: Optional[Dict[str, Dict[str, Any]]] = None,
    server: Optional[Dict[str, Any]] = None,
) -> str:
    """Render a metrics_snapshot() (plus optional per-query flight-recorder
    stage stats and server request counters) as Prometheus exposition."""
    w = _PromWriter()
    for k, v in (server or {}).items():
        w.sample(f"ksql_server_{k}_total", None, v, "counter")
    engine = snapshot.get("engine", {})
    for k, v in engine.items():
        if k == "query-states" and isinstance(v, dict):
            for state, n in sorted(v.items()):
                w.sample("ksql_engine_query_states", {"state": state}, n)
            continue
        if k == "query-health" and isinstance(v, dict):
            for state, n in sorted(v.items()):
                w.sample("ksql_engine_query_health", {"health": state}, n)
            continue
        if k == "terminal-error-queries":
            w.sample("ksql_engine_terminal_error_queries",
                     None, len(v) if isinstance(v, (list, tuple)) else v)
            continue
        if k == "fallback-reasons" and isinstance(v, dict):
            # reason strings interpolate per-query numbers (ring sizes,
            # slice widths, retentions) for EXPLAIN/logs; collapse them to
            # a stable label so the counter aggregates by root cause
            # instead of fragmenting one series per query shape
            import re as _re

            norm: Dict[str, float] = {}
            for reason, n in v.items():
                key2 = _re.sub(r"\d+", "N", str(reason))
                norm[key2] = norm.get(key2, 0) + n
            for reason, n in sorted(norm.items()):
                w.sample("ksql_engine_fallback_reasons_total",
                         {"reason": reason}, n, "counter")
            continue
        if k == "mqo" and isinstance(v, dict):
            # multi-query optimizer: shared-pipeline gauges + verdict and
            # refusal counters (stable reason codes, no normalization
            # needed — unlike fallback reasons these never interpolate
            # per-query numbers)
            w.sample("ksql_mqo_shared_pipelines", None,
                     v.get("shared-pipelines", 0))
            w.sample("ksql_mqo_shared_members", None,
                     v.get("shared-members", 0))
            for reason, n in sorted(
                (v.get("attach-refused-total") or {}).items()
            ):
                w.sample("ksql_query_family_attach_refused_total",
                         {"reason": reason}, n, "counter")
            for verdict, n in sorted(
                (v.get("decisions-total") or {}).items()
            ):
                w.sample("ksql_mqo_decisions_total",
                         {"verdict": verdict}, n, "counter")
            continue
        if k == "overload" and isinstance(v, dict):
            # overload manager (ISSUE 16): per-resource level gauges
            # (0=OK 1=ELEVATED 2=CRITICAL) + lifetime action counters
            for res, lvl in sorted((v.get("state") or {}).items()):
                w.sample("ksql_overload_state", {"resource": res}, lvl)
            for action, n in sorted((v.get("actions-total") or {}).items()):
                w.sample("ksql_overload_actions_total",
                         {"action": action}, n, "counter")
            continue
        if k == "native-ingest" and isinstance(v, dict):
            # line-rate serde: native decode rows per source format +
            # block-batched sink encode total (both lifetime counters)
            for fmt, n in sorted((v.get("rows-total") or {}).items()):
                w.sample("ksql_native_ingest_rows_total",
                         {"format": fmt}, n, "counter")
            w.sample("ksql_sink_batch_encoded_rows_total", None,
                     v.get("sink-batch-encoded-rows-total", 0), "counter")
            continue
        if k == "push-registry" and isinstance(v, dict):
            # push-serving fan-out: pipeline/tap gauges keyed by registry
            # (canonical shape), plus the cumulative serving counters
            w.sample("ksql_push_registry_pipelines", None,
                     v.get("pipelines", 0))
            for reg_key, n in sorted((v.get("taps") or {}).items()):
                w.sample("ksql_push_taps", {"registry": reg_key}, n)
            for jk, prom in (
                ("delivered-rows-total",
                 "ksql_push_registry_delivered_rows_total"),
                ("ring-evicted-total",
                 "ksql_push_registry_ring_evicted_total"),
                ("gap-markers-total",
                 "ksql_push_registry_gap_markers_total"),
                ("heals-total", "ksql_push_registry_heals_total"),
            ):
                if jk in v:
                    w.sample(prom, None, v[jk], "counter")
            res = v.get("residual")
            if isinstance(res, dict):
                # fused tap residuals (ISSUE 12): fused-vs-host tap split
                # + kernel pass/row/compile/degrade counters
                w.sample("ksql_push_residual_fused_taps", None,
                         res.get("fused-taps", 0))
                w.sample("ksql_push_residual_host_taps", None,
                         res.get("host-taps", 0))
                for jk, prom in (
                    ("kernel-evals-total",
                     "ksql_push_residual_kernel_evals_total"),
                    ("kernel-rows-total",
                     "ksql_push_residual_kernel_rows_total"),
                    ("compile-epochs-total",
                     "ksql_push_residual_compile_epochs_total"),
                    ("degraded-total",
                     "ksql_push_residual_degraded_total"),
                ):
                    if jk in res:
                        w.sample(prom, None, res[jk], "counter")
            continue
        w.sample(f"ksql_engine_{k}", None, v, _mtype_of(k))
    for qid, q in snapshot.get("queries", {}).items():
        labels = {"query": qid}
        state = q.get("state")
        if state is not None:
            w.sample("ksql_query_info", {
                "query": qid, "state": state,
                "backend": q.get("backend", ""),
                "health": q.get("health", ""),
            }, 1)
        for k, v in q.items():
            if k in ("state", "backend", "health", "error-queue"):
                continue
            if k == "terminal":
                w.sample("ksql_query_terminal", labels, 1 if v else 0)
                continue
            if k in ("e2e-latency-p50-ms", "e2e-latency-p99-ms"):
                # superseded in the exposition by the real histogram
                # below — the JSON snapshot keeps the reservoir quantiles
                # for DESCRIBE, Prometheus gets buckets it can aggregate
                continue
            if k == "e2e-latency-histogram" and isinstance(v, dict):
                _e2e_histogram_samples(w, labels, v)
                continue
            if k == "estimated-hbm-bytes" and isinstance(v, dict):
                # the static memory model's footprint estimate, one sample
                # per report point (at_creation / at_growth_cap / per_shard)
                for point, n in sorted(v.items()):
                    w.sample("ksql_query_estimated_hbm_bytes",
                             {**labels, "point": point}, n)
                continue
            if k == "reshard-total" and isinstance(v, dict):
                for direction, n in sorted(v.items()):
                    w.sample("ksql_query_reshard_total",
                             {**labels, "direction": direction}, n,
                             "counter")
                continue
            if k == "shard-strikes-total" and isinstance(v, dict):
                # mesh fault domain: lifetime strikes per suspect shard
                for s_id, n in sorted(v.items()):
                    w.sample("ksql_query_shard_strikes_total",
                             {**labels, "shard": str(s_id)}, n, "counter")
                continue
            if k == "checkpoint-age-seconds":
                # durability staleness: seconds since this query's last
                # fresh snapshot (alert substrate for a wedged rotation)
                w.sample("ksql_checkpoint_age_seconds", labels, v)
                continue
            if k == "changelog-bytes":
                # journal growth between rotations; the max.bytes cap
                # forces an early checkpoint when this runs away
                w.sample("ksql_changelog_bytes", labels, v)
                continue
            if k == "shards" and isinstance(v, dict):
                # pinned per-shard row counter (skew dashboards sum and
                # ratio this; the ksql_shard_* family below carries the
                # rest of the per-shard series)
                rows_in = v.get("rows-in")
                if isinstance(rows_in, (list, tuple)):
                    for i, x in enumerate(rows_in):
                        w.sample("ksql_query_shard_rows_total",
                                 {**labels, "shard": str(i)}, x, "counter")
                for sk, sv in v.items():
                    if isinstance(sv, (list, tuple)):
                        for i, x in enumerate(sv):
                            w.sample(
                                f"ksql_shard_{sk}", {**labels, "shard": str(i)},
                                x, _mtype_of(sk),
                            )
                    else:
                        w.sample(f"ksql_query_{sk}", labels, sv)
                continue
            w.sample(f"ksql_query_{k}", labels, v, _mtype_of(k))
    for qid, stages in (stage_stats or {}).items():
        for sname, st in stages.items():
            labels = {"query": qid, "stage": sname}
            w.sample("ksql_query_stage_invocations_total", labels,
                     st.get("n"), "counter")
            w.sample("ksql_query_stage_ms_total", labels,
                     st.get("total_ms"), "counter")
            for quant, key in (("0.5", "p50_ms"), ("0.99", "p99_ms")):
                w.sample("ksql_query_stage_latency_ms",
                         {**labels, "quantile": quant}, st.get(key))
            for k, v in st.items():
                if k in ("n", "ticks", "total_ms", "p50_ms", "p99_ms"):
                    continue
                w.sample(f"ksql_query_stage_{k}_total", labels, v, "counter")
    return w.text()


def consumer_lag(consumer) -> int:
    """Records available but not yet consumed (ConsumerCollector lag)."""
    lag = 0
    for tn in consumer.topic_names:
        t = consumer.broker.topic(tn)
        ends = t.end_offsets()
        for p in range(t.num_partitions):
            lag += max(ends[p] - consumer.positions.get((tn, p), 0), 0)
    return lag
