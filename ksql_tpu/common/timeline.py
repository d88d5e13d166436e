"""Retained telemetry timeline — per-query/per-pipeline time series.

PR 3's flight recorder answers "*what is happening now*": a 64-tick ring
that evaporates as the query runs.  Every runtime decision the ROADMAP's
direction 5 wants (de-share, re-share, load-model-driven rescale targets,
hot-key subpartitioning) needs *retained* evidence — "what happened across
the last 20 minutes when the cutover fired".  This module folds finished
:class:`~ksql_tpu.common.tracing.TickTrace`\\ s into fixed-interval frames
(``ksql.telemetry.interval.ms``, default 5s) kept in a bounded ring
(``ksql.telemetry.ring.intervals``, default 240 ⇒ 20 min retention):

* **throughput / rows / tick stats** per interval, folded inline from the
  flight recorder's ``record()`` observer — no new thread, no extra pass;
* **per-stage p50/p99** over this module's own stage set
  (``FOLD_STAGES``), from a bounded per-interval reservoir;
* **per-shard series** (rows, exchange bytes, store occupancy, watermark)
  from the distributed executor's carried shard stats, sampled once per
  interval by the engine poll loop and folded as *deltas*;
* **watermark lag** and **bucketed e2e latency** deltas from the query's
  :class:`~ksql_tpu.common.metrics.E2eHistogram`;
* **lifecycle annotations** (rebuilds, rescale cutovers, overload
  engage/clear, MQO attach/evict, mesh degrade/regrow, …) routed from the
  processing log onto the interval they landed in, so operators and
  direction-5 controllers see cause next to effect.

On top of the per-shard series sits the **skew detector**: a shard whose
row (or occupancy) share stays past ``ksql.telemetry.skew.ratio`` × its
fair share for ``ksql.telemetry.skew.intervals`` consecutive closed
intervals raises one ``telemetry.skew`` event per episode — the trigger
signal ROADMAP 5c's hot-key subpartitioning keys off.

Design constraints:

* **Bounded**: the frame ring is capped; interval closes with no ticks,
  rows, deltas, or annotations are *coalesced* (counted, not stored), so
  an idle week costs nothing.  Per-interval stage reservoirs are capped
  with stride-doubling downsampling.
* **Cheap**: one fold is dict arithmetic under a short private lock — no
  device work, no IO, no sleeps (the ``blocking-under-lock`` graftlint
  rule holds by construction).  Fold overhead is self-measured
  (``stats()``: ``foldMs`` beside ``tickMsFolded``);
  ``tests/test_timeline.py`` holds it under the tick time it folded.
* **Read-side only**: the store observes the engine; it never changes
  scheduling, state, or emission behavior.

Cursor contract (shared with ``/query-trace``): ``since(seq)`` returns
frames with ``seq > since`` plus the still-open frame (marked
``"open": true``); ``nextSince`` is the last *closed* frame's seq, so a
poller that passes it back re-reads the open frame until it closes and
never re-parses history.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

#: stages folded per interval: the compile / execute / exchange split,
#: both serde edges, the push-serving fan-out stages and the poll edge
#: (rows ride its counter) — everything else stays flight-recorder
#: material (the timeline is a retention layer, not a second recorder)
FOLD_STAGES = frozenset({
    "device.compile",
    "device.execute",
    "deserialize",
    "exchange",
    "sink.produce",
    "push.pipeline.step",
    "push.tap.deliver",
    "push.residual.kernel",
    "poll",
})

#: per-interval per-stage reservoir cap; stride-doubling keeps samples
#: spread across the interval once a hot query overflows it
STAGE_SAMPLES = 256

#: per-interval annotation cap (lifecycle events are rare; a chaos storm
#: must not let one interval grow without bound)
FRAME_ANNOTATIONS = 64

#: processing-log categories (the ``where`` prefix before the first
#: ``:``) that become timeline annotations — the lifecycle events whose
#: cause-next-to-effect placement the timeline exists to show.  Kept in
#: sync with plog_registry.json (tests/test_timeline.py).
ANNOTATION_CATEGORIES = frozenset({
    "rescale", "rescale.done", "rescale.revert", "rescale.refuse",
    "rescale.no-checkpoint", "restart.no-checkpoint",
    "mesh.shard.suspect", "mesh.degrade", "mesh.degrade.no-checkpoint",
    "mesh.regrow",
    "overload.engage", "overload.clear",
    "mqo.attach", "mqo.evict", "family.reslice.refuse",
    "deadline.hint", "deadline.autosize",
    "tick.deadline", "rebuild.deadline",
    "checkpoint.corrupt", "checkpoint.carry.lost",
    "changelog.corrupt-tail", "changelog.replay",
    "push.residual.degrade", "poison.bisect",
    "telemetry.skew",
})

#: categories whose ``where`` suffix names an action/resource rather than
#: a query — stamped onto EVERY live timeline (an overload engage affects
#: every query's interval)
ENGINE_WIDE_CATEGORIES = frozenset({
    "overload.engage", "overload.clear",
    "checkpoint.corrupt",
})


def plog_category(where: str) -> str:
    """The processing-log event category: the ``where`` prefix before the
    first ``:`` (``rescale.done:<qid>`` → ``rescale.done``)."""
    return str(where).split(":", 1)[0]


def since_param(qs: Dict[str, List[str]]) -> Optional[int]:
    """Shared cursor helper for ``/timeline`` and ``/query-trace``: the
    ``?since=<seq>`` value as an int, None when absent.  Raises
    ``ValueError`` on a non-integer value (the caller answers 400)."""
    vals = qs.get("since")
    if not vals:
        return None
    return int(vals[0])


def _percentile(sorted_xs: List[float], p: float) -> Optional[float]:
    if not sorted_xs:
        return None
    idx = min(int(len(sorted_xs) * p), len(sorted_xs) - 1)
    return round(sorted_xs[idx], 3)


class _StageAgg:
    """Per-interval per-stage fold: count/total plus a bounded reservoir
    for p50/p99.  Stride-doubling: when the reservoir fills, every other
    sample is dropped and the accept stride doubles, so retained samples
    stay spread across the interval instead of front-loaded."""

    __slots__ = ("n", "ms_total", "samples", "_stride", "_skip")

    def __init__(self) -> None:
        self.n = 0
        self.ms_total = 0.0
        self.samples: List[float] = []
        self._stride = 1
        self._skip = 0

    def add(self, ms: float) -> None:
        self.n += 1
        self.ms_total += ms
        if self._skip:
            self._skip -= 1
            return
        if len(self.samples) >= STAGE_SAMPLES:
            del self.samples[::2]
            self._stride *= 2
        self.samples.append(ms)
        self._skip = self._stride - 1

    def to_dict(self) -> Dict[str, Any]:
        xs = sorted(self.samples)
        return {
            "ticks": self.n,
            "p50Ms": _percentile(xs, 0.50),
            "p99Ms": _percentile(xs, 0.99),
            "totalMs": round(self.ms_total, 3),
        }


class _Frame:
    """One fixed interval's fold.  ``seq`` is the absolute interval index
    (``start_ms // interval_ms``) — globally monotone, stable across
    coalesced (dropped-empty) intervals, and therefore usable as the
    pagination cursor."""

    __slots__ = (
        "seq", "start_ms", "ticks", "err_ticks", "rows", "tick_ms",
        "stages", "annotations", "shard_rows", "shard_xbytes",
        "shard_occupancy", "shard_watermark_ms", "watermark_lag_ms",
        "e2e_counts", "e2e_count", "e2e_sum_s",
    )

    def __init__(self, seq: int, start_ms: int):
        self.seq = seq
        self.start_ms = start_ms
        self.ticks = 0
        self.err_ticks = 0
        self.rows = 0
        self.tick_ms = 0.0
        self.stages: Dict[str, _StageAgg] = {}
        self.annotations: List[Dict[str, Any]] = []
        # per-shard interval deltas (rows / exchange bytes) and
        # last-observed gauges (occupancy / watermark)
        self.shard_rows: Optional[List[int]] = None
        self.shard_xbytes: Optional[List[int]] = None
        self.shard_occupancy: Optional[List[int]] = None
        self.shard_watermark_ms: Optional[List[int]] = None
        self.watermark_lag_ms: Optional[int] = None
        # bucketed e2e latency deltas (bounds live on the store)
        self.e2e_counts: Optional[List[int]] = None
        self.e2e_count = 0
        self.e2e_sum_s = 0.0

    def is_empty(self) -> bool:
        """True when closing this interval would retain nothing an
        operator could read back: no ticks, no rows, no annotations, no
        shard/e2e movement.  Pure gauges (occupancy, watermark lag) do
        not rescue a frame — they re-sample identically next interval."""
        return (
            self.ticks == 0 and self.rows == 0
            and not self.annotations
            and not any(self.shard_rows or ())
            and not any(self.shard_xbytes or ())
            and self.e2e_count == 0
        )

    def to_dict(self, interval_ms: int, open_: bool = False
                ) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "seq": self.seq,
            "startMs": self.start_ms,
            "endMs": self.start_ms + interval_ms,
            "ticks": self.ticks,
            "errTicks": self.err_ticks,
            "rows": self.rows,
            "tickMs": round(self.tick_ms, 3),
            "throughputRps": round(
                self.rows / max(interval_ms / 1000.0, 1e-9), 3
            ),
            "stages": {
                name: agg.to_dict() for name, agg in self.stages.items()
            },
            "annotations": list(self.annotations),
        }
        if self.shard_rows is not None:
            d["shards"] = {
                "rows": self.shard_rows,
                "exchangeBytes": self.shard_xbytes,
                "storeOccupancy": self.shard_occupancy,
                "watermarkMs": self.shard_watermark_ms,
            }
        if self.watermark_lag_ms is not None:
            d["watermarkLagMs"] = self.watermark_lag_ms
        if self.e2e_count:
            d["e2e"] = {
                "counts": self.e2e_counts,
                "count": self.e2e_count,
                "sumS": round(self.e2e_sum_s, 6),
            }
        if open_:
            d["open"] = True
        return d


class TimelineStore:
    """Bounded retained time series for one query or push pipeline.

    Feeding (all engine-poll-loop inline, no thread):

    * ``fold(trace)`` — flight-recorder observer, one call per recorded
      tick;
    * ``observe(now_ms, shards=, watermark_lag_ms=, e2e=)`` — interval
      gauge sample (the engine gates it on ``gauge_due``);
    * ``annotate(kind, detail)`` — lifecycle event routed from the
      processing log.

    Reading: ``since(seq)`` (cursor pagination), ``stats()`` (fold
    overhead + ring occupancy), ``drain_events()`` (skew verdicts for the
    engine to publish as plog + /alerts evidence)."""

    def __init__(self, owner_id: str, interval_ms: int = 5000,
                 ring: int = 240, skew_ratio: float = 1.8,
                 skew_intervals: int = 3,
                 e2e_bounds_s: Optional[tuple] = None):
        self.owner_id = owner_id
        self.interval_ms = max(int(interval_ms), 1)
        self.ring = max(int(ring), 1)
        self.skew_ratio = max(float(skew_ratio), 1.0)
        self.skew_intervals = max(int(skew_intervals), 1)
        if e2e_bounds_s is None:
            from ksql_tpu.common.metrics import E2E_BUCKETS_S

            e2e_bounds_s = E2E_BUCKETS_S
        self.e2e_bounds_s = tuple(e2e_bounds_s)
        self._frames: deque = deque(maxlen=self.ring)
        self._cur: Optional[_Frame] = None
        self.coalesced = 0  # empty intervals dropped instead of stored
        self.annotations_dropped = 0
        # fold-overhead self-measurement (``stats()``: foldMs / tickMsFolded)
        self.folds = 0
        self.fold_ms = 0.0
        self.tick_ms_folded = 0.0
        self._fold_agg = _StageAgg()
        # interval gauge sampling bookkeeping
        self._last_gauge_ms = 0.0
        self._shard_base: Optional[Dict[str, List[int]]] = None
        self._e2e_base: Optional[List[int]] = None
        self._e2e_base_count = 0
        self._e2e_base_sum = 0.0
        # skew detector state (one event per sustained episode)
        self._skew_streak = 0
        self._skew_hot = -1
        self._skew_fired = False
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    # ------------------------------------------------------------- feeding
    def fold(self, trace: Any) -> None:
        """Fold one finished TickTrace (flight-recorder observer).  Pure
        dict arithmetic under the private lock — nothing blocking rides
        the poll loop."""
        t0 = time.perf_counter()
        stages = trace.stages
        poll_st = stages.get("poll") or stages.get("push.pipeline.step")
        rows = int(poll_st.get("rows", 0)) if poll_st else 0
        if not rows:
            deser = stages.get("deserialize")
            if deser:
                rows = int(deser.get("n", 0))
        with self._lock:
            f = self._frame_for(int(trace.started_at_ms))
            f.ticks += 1
            if trace.status != "OK":
                f.err_ticks += 1
            f.rows += rows
            f.tick_ms += float(trace.dur_ms or 0.0)
            for name, st in stages.items():
                if name not in FOLD_STAGES:
                    continue
                agg = f.stages.get(name)
                if agg is None:
                    agg = f.stages[name] = _StageAgg()
                agg.add(float(st.get("ms", 0.0)))
            self.folds += 1
            self.tick_ms_folded += float(trace.dur_ms or 0.0)
            dt_ms = (time.perf_counter() - t0) * 1000.0
            self.fold_ms += dt_ms
            self._fold_agg.add(dt_ms)

    def gauge_due(self, now_ms: int) -> bool:
        """True when an interval has passed since the last gauge sample —
        the engine's cheap pre-check before paying shard_metrics()."""
        return now_ms - self._last_gauge_ms >= self.interval_ms

    def observe(self, now_ms: int,
                shards: Optional[Dict[str, Any]] = None,
                watermark_lag_ms: Optional[int] = None,
                e2e: Optional[Dict[str, Any]] = None) -> None:
        """One interval gauge sample: per-shard cumulative stats become
        interval deltas (a rebuild/rescale resets the executor's counters
        — a shorter list or a negative delta re-bases instead of going
        negative), occupancy/watermark stay last-observed, and the e2e
        histogram's cumulative buckets become interval deltas."""
        with self._lock:
            self._last_gauge_ms = now_ms
            f = self._frame_for(now_ms)
            if watermark_lag_ms is not None:
                f.watermark_lag_ms = max(int(watermark_lag_ms), 0)
            if shards:
                self._fold_shards(f, shards)
            if e2e:
                self._fold_e2e(f, e2e)

    def _fold_shards(self, f: _Frame, sm: Dict[str, Any]) -> None:
        rows = [int(x) for x in (sm.get("rows-in") or ())]
        xbytes = [int(x) for x in (sm.get("exchange-bytes") or ())]
        if not xbytes:
            xbytes = [0] * len(rows)
        base = self._shard_base
        fresh = (
            base is None or len(base["rows"]) != len(rows)
            or any(c < b for c, b in zip(rows, base["rows"]))
        )
        if fresh:
            # first sample, width change (rescale), or counter reset
            # (executor rebuild): the cumulative values ARE the delta
            # since the rebuild — re-base on them
            d_rows, d_xbytes = rows, xbytes
        else:
            d_rows = [c - b for c, b in zip(rows, base["rows"])]
            d_xbytes = [
                max(c - b, 0) for c, b in zip(xbytes, base["xbytes"])
            ]
        self._shard_base = {"rows": rows, "xbytes": xbytes}
        if f.shard_rows is None or len(f.shard_rows) != len(d_rows):
            f.shard_rows = list(d_rows)
            f.shard_xbytes = list(d_xbytes)
        else:
            f.shard_rows = [a + b for a, b in zip(f.shard_rows, d_rows)]
            f.shard_xbytes = [
                a + b for a, b in zip(f.shard_xbytes, d_xbytes)
            ]
        occ = sm.get("store-occupancy")
        if occ is not None:
            f.shard_occupancy = [int(x) for x in occ]
        wm = sm.get("watermark-ms")
        if wm is not None:
            f.shard_watermark_ms = [int(x) for x in wm]

    def _fold_e2e(self, f: _Frame, hist: Dict[str, Any]) -> None:
        counts = [int(x) for x in (hist.get("counts") or ())]
        count = int(hist.get("count", 0))
        sum_s = float(hist.get("sum", 0.0))
        base = self._e2e_base
        if base is None or len(base) != len(counts) or any(
            c < b for c, b in zip(counts, base)
        ):
            d_counts = counts
            d_count, d_sum = count, sum_s
        else:
            d_counts = [c - b for c, b in zip(counts, base)]
            d_count = max(count - self._e2e_base_count, 0)
            d_sum = max(sum_s - self._e2e_base_sum, 0.0)
        self._e2e_base = counts
        self._e2e_base_count = count
        self._e2e_base_sum = sum_s
        if not any(d_counts):
            return
        if f.e2e_counts is None or len(f.e2e_counts) != len(d_counts):
            f.e2e_counts = list(d_counts)
        else:
            f.e2e_counts = [
                a + b for a, b in zip(f.e2e_counts, d_counts)
            ]
        f.e2e_count += d_count
        f.e2e_sum_s += d_sum

    def annotate(self, kind: str, detail: str = "",
                 now_ms: Optional[int] = None) -> None:
        """Stamp one lifecycle annotation onto the covering interval (an
        annotation alone keeps its interval from coalescing — cause must
        stay visible even when the query was otherwise idle)."""
        now_ms = int(time.time() * 1000) if now_ms is None else int(now_ms)
        with self._lock:
            f = self._frame_for(now_ms)
            if len(f.annotations) < FRAME_ANNOTATIONS:
                f.annotations.append({
                    "wallMs": now_ms,
                    "kind": str(kind),
                    "detail": str(detail)[:240],
                })
            else:
                self.annotations_dropped += 1

    # -------------------------------------------------- interval rollover
    def _frame_for(self, now_ms: int) -> _Frame:
        # lock held by caller
        idx = now_ms // self.interval_ms
        cur = self._cur
        if cur is not None and idx <= cur.seq:
            # same interval (or a minor wall-clock regression: fold into
            # the open frame rather than reopening history)
            return cur
        if cur is not None:
            self._close(cur)
        f = _Frame(idx, idx * self.interval_ms)
        self._cur = f
        return f

    def _close(self, frame: _Frame) -> None:
        # lock held by caller
        if frame.is_empty():
            self.coalesced += 1
            # an idle gap breaks any skew episode: sustained means
            # consecutive NON-EMPTY intervals with the same hot shard
            self._skew_streak = 0
            self._skew_fired = False
            return
        self._frames.append(frame)
        self._check_skew(frame)

    def _check_skew(self, frame: _Frame) -> None:
        # lock held by caller.  Sustained = the SAME hot shard past the
        # threshold for skew_intervals consecutive closed intervals; one
        # event per episode, re-armed by a balanced (or idle) interval.
        verdict = None
        for metric, xs in (
            ("rows", frame.shard_rows),
            ("occupancy", frame.shard_occupancy),
        ):
            if not xs or len(xs) < 2:
                continue
            total = sum(xs)
            if total <= 0:
                continue
            hot = max(range(len(xs)), key=xs.__getitem__)
            share = xs[hot] / total
            fair = 1.0 / len(xs)
            threshold = min(self.skew_ratio * fair, 0.95)
            if share >= threshold and share > fair:
                verdict = (hot, share, metric)
                break
        if verdict is None:
            self._skew_streak = 0
            self._skew_fired = False
            return
        hot, share, metric = verdict
        if hot == self._skew_hot:
            self._skew_streak += 1
        else:
            self._skew_hot = hot
            self._skew_streak = 1
            self._skew_fired = False
        if self._skew_streak >= self.skew_intervals and not self._skew_fired:
            self._skew_fired = True
            self._events.append({
                "kind": "telemetry.skew",
                "hotShard": hot,
                "share": round(share, 4),
                "metric": metric,
                "intervals": self._skew_streak,
                "seq": frame.seq,
                "wallMs": int(time.time() * 1000),
            })

    def drain_events(self) -> List[Dict[str, Any]]:
        """Pending skew verdicts, cleared on read — the engine publishes
        them as ``telemetry.skew:<qid>`` plog + /alerts evidence."""
        if not self._events:
            return []
        with self._lock:
            ev, self._events = self._events, []
        return ev

    # ------------------------------------------------------------- reading
    def since(self, since_seq: Optional[int] = None,
              limit: Optional[int] = None) -> Dict[str, Any]:
        """Frames with ``seq > since_seq`` (all retained frames when
        None), oldest first, plus the open frame (``"open": true``).
        ``nextSince`` is the last CLOSED frame's seq — pass it back to
        poll incrementally."""
        with self._lock:
            closed = [
                f for f in self._frames
                if since_seq is None or f.seq > since_seq
            ]
            if limit is not None and len(closed) > limit:
                closed = closed[:max(int(limit), 0)]
            out = [f.to_dict(self.interval_ms) for f in closed]
            next_since = (
                closed[-1].seq if closed
                else (self._frames[-1].seq if self._frames
                      else (since_seq if since_seq is not None else -1))
            )
            cur = self._cur
            if cur is not None and not cur.is_empty() and (
                since_seq is None or cur.seq > since_seq
            ) and (limit is None or len(out) < limit):
                out.append(cur.to_dict(self.interval_ms, open_=True))
        return {
            "ownerId": self.owner_id,
            "intervalMs": self.interval_ms,
            "ring": self.ring,
            "e2eBucketsS": list(self.e2e_bounds_s),
            "frames": out,
            "nextSince": next_since,
            "coalesced": self.coalesced,
        }

    def annotation_kinds(self) -> List[str]:
        """Distinct annotation kinds retained across the ring + the open
        frame (the chaos soaks' every-incident-is-visible assertion)."""
        with self._lock:
            frames = list(self._frames)
            if self._cur is not None:
                frames.append(self._cur)
            return sorted({
                a["kind"] for f in frames for a in f.annotations
            })

    def stats(self) -> Dict[str, Any]:
        """Fold-overhead + occupancy accounting (read by the tests)."""
        with self._lock:
            fold = self._fold_agg.to_dict()
            return {
                "frames": len(self._frames),
                "openSeq": self._cur.seq if self._cur is not None else None,
                "coalesced": self.coalesced,
                "annotationsDropped": self.annotations_dropped,
                "folds": self.folds,
                "foldMs": round(self.fold_ms, 3),
                "foldP50Ms": fold["p50Ms"],
                "foldP99Ms": fold["p99Ms"],
                "tickMsFolded": round(self.tick_ms_folded, 3),
            }
