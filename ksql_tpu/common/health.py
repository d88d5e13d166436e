"""Per-query progress tracking + health watchdog.

PR 3's flight recorder answers "*where inside a tick* is time going"; this
module answers "*is this query keeping up*" — the signal streaming engines
treat as primary health (Kafka Streams task lag metrics, Flink watermark
progress, ksqlDB's LagReportingAgent/HeartbeatAgent pair).

Each persistent query owns one :class:`QueryProgress`, sampled by the
engine's poll loop (piggybacked — no extra thread in embedded mode):

* **Progress** — per source partition: committed offset, end offset and
  offset lag; the event-time **watermark** (max record timestamp consumed);
  and the end-to-end latency histogram (sink produce wall-time − record
  timestamp) fed per emit through the engine's emit callback.  A bounded
  ring of ``(wall_time, lag, watermark, e2e_p99)`` samples
  (``ksql.health.history.size``) backs the ``GET /query-lag/<id>`` time
  series and the Prometheus ``ksql_query_offset_lag`` /
  ``ksql_query_watermark_ms`` / ``ksql_query_e2e_latency_seconds`` gauges.

* **Watchdog** — every sample classifies the query::

      STALLED   committed offsets frozen while lag stays/grows, for
                ``ksql.health.stall.ticks`` consecutive samples (consumer
                stuck, device wedged, crash-looping restarts)
      LAGGING   offsets advancing but lag grew for the same streak length
                (consumer alive yet falling behind the producer)
      IDLE      caught up, nothing new to consume
      HEALTHY   making progress

  The verdict surfaces in ``SHOW QUERIES``, ``DESCRIBE EXTENDED``,
  ``/healthcheck`` (any STALLED query degrades the node), ``GET /alerts``,
  and rides the heartbeat gossip so ``/clusterStatus`` shows per-host
  per-query freshness.

Cheap enough to run always-on: one sample is a handful of dict reads per
partition plus a deque append; classification is integer compares.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ksql_tpu.common.metrics import E2eHistogram, LatencyHistogram

HEALTHY = "HEALTHY"
IDLE = "IDLE"
LAGGING = "LAGGING"
STALLED = "STALLED"

#: states the watchdog can report, in increasing order of concern
STATES = (IDLE, HEALTHY, LAGGING, STALLED)

#: states that constitute an alert (GET /alerts, degraded /healthcheck)
ALERT_STATES = (LAGGING, STALLED)


def _now_ms() -> int:
    return int(time.time() * 1000)


class QueryProgress:
    """Progress tracker + stall watchdog for one persistent query."""

    def __init__(self, query_id: str, history_size: int = 256,
                 stall_ticks: int = 8):
        self.query_id = query_id
        self.stall_ticks = max(1, int(stall_ticks))
        self.history: deque = deque(maxlen=max(1, int(history_size)))
        self.partitions: Dict[str, Dict[str, int]] = {}
        self.offset_lag = 0
        self.watermark_ms: Optional[int] = None
        #: e2e latency (sink produce wall-time − record timestamp); the
        #: shared LatencyHistogram gives the same p50/p99 surface the
        #: processing-latency sensor has
        self.e2e = LatencyHistogram()
        #: bucketed cumulative e2e distribution: the Prometheus
        #: ksql_query_e2e_latency_seconds histogram and the telemetry
        #: timeline's per-interval source (it differences snapshots)
        self.e2e_hist = E2eHistogram()
        self.health = IDLE
        self.health_since_ms = _now_ms()
        self.stalled_for = 0  # consecutive frozen-behind samples
        self.lagging_for = 0  # consecutive fell-further-behind samples
        self.samples_total = 0
        #: supervised ticks that blew past ksql.query.tick.timeout.ms
        self.tick_deadlines = 0
        #: samples left for which the verdict stays pinned STALLED after a
        #: tick deadline — without the hold, the next sample would see the
        #: hung tick's pre-hang durable commits as "progress" and wipe the
        #: verdict before any operator/alert poll could observe it
        self._deadline_hold = 0
        #: discrete watchdog events (tick.deadline / rescale / restart
        #: posture entries) riding /alerts
        self.events: deque = deque(maxlen=16)
        #: wall time of the last materialized-state write (standby-safe
        #: freshness: sink-disabled replicas still materialize)
        self.materialized_at_ms: Optional[int] = None
        self._prev: Optional[tuple] = None  # (committed_total, lag_total)
        self._lock = threading.Lock()

    # ------------------------------------------------------------- feeding
    def note_watermark(self, ts_ms: int) -> None:
        """Advance the event-time watermark (max record timestamp
        consumed); monotone by construction."""
        if self.watermark_ms is None or ts_ms > self.watermark_ms:
            self.watermark_ms = int(ts_ms)

    def record_e2e(self, event_ts_ms: int, now_ms: Optional[int] = None) -> None:
        """One sink emission: e2e latency = produce wall-time − record
        timestamp (clamped at 0 for future-dated/window-bound stamps)."""
        now_ms = _now_ms() if now_ms is None else now_ms
        seconds = max(now_ms - event_ts_ms, 0) / 1000.0
        self.e2e.record(seconds)
        self.e2e_hist.record(seconds)

    def record_emit_block(self, event_ts_ms: List[int],
                          now_ms: Optional[int] = None) -> None:
        """``record_e2e`` for each emission of a block and one
        ``note_materialized``, against one read of the clock: within a
        block the per-emit reads differ by the dispatch loop's own
        microseconds."""
        now_ms = _now_ms() if now_ms is None else now_ms
        seconds = [max(now_ms - ts, 0) / 1000.0 for ts in event_ts_ms]
        self.e2e.record_block(seconds)
        self.e2e_hist.record_block(seconds)
        self.materialized_at_ms = now_ms

    def note_materialized(self, now_ms: Optional[int] = None) -> None:
        """One materialized-state write (the engine's emit callback): the
        freshness clock for replicas whose sink is disabled (standbys have
        no e2e latency — this gauge is their staleness signal)."""
        self.materialized_at_ms = _now_ms() if now_ms is None else now_ms

    def freshness_ms(self, now_ms: Optional[int] = None) -> Optional[int]:
        """ksql_query_materialization_freshness_ms: wall-clock age of the
        newest materialized row, or None before anything materialized."""
        if self.materialized_at_ms is None:
            return None
        now_ms = _now_ms() if now_ms is None else now_ms
        return max(now_ms - self.materialized_at_ms, 0)

    def note_event(self, kind: str, now_ms: Optional[int] = None,
                   **fields: Any) -> None:
        """Record one discrete watchdog/controller event (rescale cutover,
        no-checkpoint restart posture, ...) on the bounded evidence ring
        that rides ``GET /alerts``."""
        now_ms = _now_ms() if now_ms is None else now_ms
        with self._lock:
            self.events.append({"wallMs": now_ms, "kind": kind, **fields})

    def note_tick_deadline(self, timeout_ms: int,
                           now_ms: Optional[int] = None,
                           kind: str = "tick.deadline") -> None:
        """A supervised deadline blew: the verdict flips STALLED
        *immediately* (the frozen-offset streak is set to the threshold,
        so the ERROR-backoff ticks that follow keep it STALLED until real
        progress resumes and clears the streak) and an evidence entry is
        recorded for ``GET /alerts``.  ``kind`` names which deadline —
        ``tick.deadline`` (ksql.query.tick.timeout.ms) or
        ``rebuild.deadline`` (ksql.query.rebuild.timeout.ms) — so the
        operator tunes the knob that actually fired."""
        now_ms = _now_ms() if now_ms is None else now_ms
        with self._lock:
            self.tick_deadlines += 1
            self._deadline_hold = self.stall_ticks
            self.stalled_for = max(self.stalled_for, self.stall_ticks)
            if self.health != STALLED:
                self.health = STALLED
                self.health_since_ms = now_ms
            self.events.append({
                "wallMs": now_ms,
                "kind": kind,
                "timeoutMs": int(timeout_ms),
            })

    # ------------------------------------------------------------ sampling
    def sample(self, consumer, now_ms: Optional[int] = None) -> str:
        """One poll-tick sample: refresh per-partition offsets/lag from the
        consumer, append to the ring, classify.  Returns the health state."""
        now_ms = _now_ms() if now_ms is None else now_ms
        parts: Dict[str, Dict[str, int]] = {}
        committed_total = 0
        lag_total = 0
        for tn in consumer.topic_names:
            try:
                t = consumer.broker.topic(tn)
            except Exception:  # noqa: BLE001 — topic dropped mid-flight
                continue
            ends = t.end_offsets()
            for p in range(t.num_partitions):
                pos = int(consumer.positions.get((tn, p), 0))
                lag = max(int(ends[p]) - pos, 0)
                parts[f"{tn}-{p}"] = {
                    "committedOffset": pos,
                    "endOffset": int(ends[p]),
                    "offsetLag": lag,
                }
                committed_total += pos
                lag_total += lag
        return self._classify(committed_total, lag_total, parts, now_ms)

    def sample_ring(self, cursor: int, lag: int,
                    now_ms: Optional[int] = None) -> str:
        """Per-tap progress sample (push registry): the tap owns no
        consumer — its cursor into the shared pipeline's emission ring
        stands in for the committed offset and the ring lag for the
        consumer lag, so the same stall/lag watchdog verdicts apply to
        taps."""
        now_ms = _now_ms() if now_ms is None else now_ms
        parts = {
            "ring": {
                "committedOffset": int(cursor),
                "endOffset": int(cursor) + max(int(lag), 0),
                "offsetLag": max(int(lag), 0),
            }
        }
        return self._classify(
            int(cursor), max(int(lag), 0), parts, now_ms
        )

    def _classify(self, committed_total: int, lag_total: int,
                  parts: Dict[str, Dict[str, int]], now_ms: int) -> str:
        with self._lock:
            prev = self._prev
            # first sample: anything consumed since start counts as progress
            progressed = (
                committed_total > prev[0] if prev is not None
                else committed_total > 0
            )
            lag_grew = prev is not None and lag_total > prev[1]
            if prev is None:
                pass  # first sample: no streak material yet
            elif progressed:
                self.stalled_for = 0
                self.lagging_for = self.lagging_for + 1 if lag_grew else 0
            elif lag_total == 0:
                self.stalled_for = 0
                self.lagging_for = 0
            elif lag_total >= prev[1]:
                # offsets frozen while the backlog stays or grows: the
                # stall signature (a wedged consumer under a live producer,
                # or a crash-looping restart cycle)
                self.stalled_for += 1
                self.lagging_for = 0
            self._prev = (committed_total, lag_total)
            if self._deadline_hold > 0:
                # a tick deadline pins STALLED for a full streak window;
                # the hold drains per sample, so a recovered query clears
                # with the watchdog's usual hysteresis
                self._deadline_hold -= 1
                health = STALLED
            elif self.stalled_for >= self.stall_ticks:
                health = STALLED
            elif self.lagging_for >= self.stall_ticks:
                health = LAGGING
            elif lag_total == 0 and not progressed:
                health = IDLE
            else:
                health = HEALTHY
            if health != self.health:
                self.health = health
                self.health_since_ms = now_ms
            self.partitions = parts
            self.offset_lag = lag_total
            self.samples_total += 1
            self.history.append((
                now_ms, lag_total, self.watermark_ms,
                self.e2e.percentile(0.99),
            ))
        return health

    # ------------------------------------------------------------- reading
    def snapshot(self) -> Dict[str, Any]:
        """Current progress view (the /query-lag body minus the series)."""
        with self._lock:
            return {
                "queryId": self.query_id,
                "health": self.health,
                "healthSinceMs": self.health_since_ms,
                "offsetLag": self.offset_lag,
                "watermarkMs": self.watermark_ms,
                "e2eP50Ms": self.e2e.percentile(0.50),
                "e2eP99Ms": self.e2e.percentile(0.99),
                "materializationFreshnessMs": self.freshness_ms(),
                "partitions": {k: dict(v) for k, v in self.partitions.items()},
                "tickDeadlines": self.tick_deadlines,
                # the bounded discrete-event ring (tick.deadline /
                # restart / changelog.replay ...): recovery evidence must
                # be operator-visible from the per-query progress view,
                # not only once a query degrades into /alerts (a clean
                # crash-recovery never alerts)
                "events": list(self.events),
                "stall": {
                    "ticks": self.stall_ticks,
                    "stalledFor": self.stalled_for,
                    "laggingFor": self.lagging_for,
                    "samples": self.samples_total,
                },
            }

    def series(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        """The bounded (wall_time, lag, watermark, e2e_p99) ring as dicts,
        oldest first."""
        with self._lock:
            samples = list(self.history)
        if n is not None:
            samples = samples[-n:]
        return [
            {"wallMs": w, "offsetLag": lag, "watermarkMs": wm, "e2eP99Ms": p99}
            for (w, lag, wm, p99) in samples
        ]

    def gossip(self) -> Dict[str, Any]:
        """The compact per-query freshness triple piggybacked on heartbeat
        gossip (LagReportingAgent payload analog)."""
        return {
            "lag": self.offset_lag,
            "watermark": self.watermark_ms,
            "health": self.health,
            # materialization freshness rides the gossip so a standby
            # replica (sink disabled, hence no e2e latency) still reports
            # how stale its materialized state is
            "freshnessMs": self.freshness_ms(),
        }

    def alert(self, state: str, extra: Optional[Dict[str, Any]] = None
              ) -> Dict[str, Any]:
        """One /alerts entry: verdict plus the evidence that produced it."""
        out = self.snapshot()
        out["state"] = state
        out["evidence"] = self.series(n=min(self.stall_ticks + 2, 16))
        with self._lock:
            out["events"] = list(self.events)
        out.update(extra or {})
        return out
