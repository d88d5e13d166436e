"""Query flight recorder — low-overhead per-tick tracing.

The reference engine's operability rests on per-query rate/latency sensors
(MetricCollectors / KsqlEngineMetrics) and the processing log; this module
adds the missing *where does the time go* axis: each poll tick of each
persistent query records a trace — coarse spans (poll, process, drain,
device step) plus per-stage accumulators cheap enough for per-record hot
paths (deserialize, per-ExecutionStep oracle stages, sink produce) — into a
per-query ring buffer (the **flight recorder**).  The last N tick traces
answer "what did the slow/crashing tick actually do", and the aggregate
per-stage p50/p99 over the window feeds ``EXPLAIN ANALYZE``, the
``/query-trace/<id>`` REST endpoint, and the Prometheus ``/metrics``
exposition.

Design constraints:

* **Near-zero cost when disabled** (``ksql.trace.enable=false``): the
  engine never opens a tick, so ``active()`` is one thread-local read
  returning None and every instrumentation site is a single ``is None``
  check.
* **Cheap when enabled**: hot paths (one call per record) use stage
  *accumulators* (two ``perf_counter`` reads + a dict update), not span
  objects; spans are reserved for per-batch / per-tick boundaries.
* **Self time is recorded, not derived**: a span on exit adds its duration
  to its parent's child time and books ``self_ms`` (own duration minus
  child time) on its stage; a timed ``stage()`` call counts as a child of
  the span it was made under.  ``tick``'s ``self_ms`` is the part of a
  tick under no span at all.
* **By cause, not only by place**: beside ``self_ms`` a span books
  ``gc_ms``, the collector's pauses that fell inside it (one
  ``gc.callbacks`` hook for the process, held by the engines that trace;
  a pause is a timed stage ``gc.pause`` under the innermost open span,
  so no ``self_ms`` holds it; booked at every span exit and tick finish,
  0.0 where none fell; like ``ms`` and unlike ``self_ms`` it holds what
  fell inside a span's children).  ``off_cpu_ms`` is wall time less the
  thread's CPU time (``time.thread_time`` beside ``perf_counter``): the
  thread was not running.  That clock is a system call, 0.3 us here and
  5.7 us on the sealed machine that holds the chip, so it is read where
  the answer is worth it and not at every span: by the tick, by a span
  that waits for the device by design (``wait=True``: it keeps its
  off-CPU time to itself) and by a span that asks (``cpu=True``: it hands
  it up like every other span).  ``tick``'s ``off_cpu_ms`` is then the
  time the poll thread should have been running and was not (the GIL in
  another thread's hands, another thread's collection, the scheduler, a
  blocking transfer, a page fault), and a kept tick's span durations say
  where.
* **One clock with the device trace**: every span, and the tick itself
  (``ksql.tick#<query>#<seq>``), is also entered as a
  ``jax.profiler.TraceAnnotation`` once jax is loaded, so a profile of a
  served query holds the host's spans above the device's operations.  It
  records nothing while no profiler session is open.
* **No global registry**: recorders live on the engine
  (``KsqlEngine.trace_recorders``) so concurrent engines in one process
  (tests, sandboxes, multi-node clusters) never share or clobber traces.
  Only the *active* trace rides a thread-local, because executors have no
  engine reference.

Stage naming convention, in tick order (indented: nested under the stage
above; "span" has a place in time, "total" is an accumulator, "counters"
is never timed and reports no time):

==================  ========================================================
``tick``            the whole poll tick of one query (total, booked when
                    the tick ends; ``self_ms`` = time under no span,
                    ``gc_ms`` = every pause inside it, ``off_cpu_ms`` =
                    the thread not running outside the declared waits)
``poll``            span: Consumer.poll for the tick (``rows``)
``process``         span: the hand-over of a poll's records to the executor
                    (a block for the records it only buffers, the
                    per-record loop for the rest; ``rows`` handed,
                    ``block_rows`` of them in a block; a full micro-batch
                    runs the stages below inside it)
``deserialize``     decode_source_record (total, all backends); a span per
                    chunk in the native C++ tier (``off_cpu_ms``: the parse
                    runs with the GIL released and takes it back)
``stage:<ctx>``     total: one oracle ExecutionStep node (Filter/Join/...)
``drain``           span: the executor's end-of-tick flush
``batch.assemble``  span: key decode, column encode, dictionary learn and
                    the copy into the padded step buffers
``device.compile``  span: a device step that jit-traced/compiled (miss)
``device.execute``  span: a device step served from the jit cache (hit)
``step.dispatch``     span: h2d of the batch + enqueue of the step and of
                      its emits' host copies (``h2d_bytes``)
``step.wait``         span, a declared wait: the host blocked on the
                      step's outputs (a join-table step reads its four
                      load scalars there)
``store.evict``       span: a retention pass of the window store (every
                      64th batch, and off cadence when the load check finds
                      the store at 0.75: ``off_cadence``); the span holds
                      the enqueue, the device's part lands in the next wait
``emit.decode``       span: the step's emits read back, load check, row
                      building (``d2h_bytes``; ``lanes``, the emit mask's
                      length, and ``rows`` decoded from it); its self time
                      is the load check, the join's counts, the members
``emit.read``           span, a declared wait: the emits cross to the host
                        (one ``jax.device_get``; on the mesh every shard's
                        columns, a blocking read a leaf)
``emit.rows``           span: ``_decode_emits``, a ``SinkEmit`` and a row
                        dict a record from the host copy
``store.compact``       span: the in-place compaction after an off-cadence
                        pass (host rebuild of the store without its graves)
``table.grow``        span: a join table doubled (host rebuild; the steps
                      recompile at their next call)
``device.step``     counters the step program reports about its own work
                    (``probe_rounds`` and ``probe_lane_rounds``, the lanes
                    those rounds worked on, over ``sampled`` load checks;
                    ``sliced_lanes``, the lanes of the batch a sliced
                    hopping step's fold and emission visited: its chunk's
                    lanes x the chunks that held a row;
                    the load scalars read there: ``occupancy``, slots taken,
                    and ``graves`` among them, the mesh's of its fullest
                    shard;
                    a stream-table join's lookups: ``find_rounds``,
                    ``join_rows`` probed, ``join_matched``)
``table.upsert``    counters of the join-table steps (``rows``, ``steps``,
                    ``probe_rounds``, ``probe_lane_rounds``, ``grows``)
``exchange``        counters of the mesh's all-to-all, booked a step from
                    the per-shard row counts the host reads anyway and from
                    static shapes (``steps``; ``rows`` received, all shards;
                    ``rows_fullest_shard``, the most one shard received;
                    ``bytes``, an estimate: rows x the payload's row width;
                    ``lanes`` = n_shards^2 x ``bucket_capacity``, what the
                    collective ships whatever the rows, and ``wire_bytes``,
                    those lanes at the payload's row width)
``emit.dispatch``   span: block encode, then the emit callbacks and the sink
                    produce, for the block at once or emit by emit (``rows``
                    dispatched, ``block_rows`` of them as a block)
``emit.callbacks``    total: the block callback's one pass over an emission
                      block (the per-emit loop's callbacks stay the span's
                      self time)
``sink.produce``      total: SinkWriter's block encode, and its produce: one
                      stage a block (``n`` = its records) on the block path,
                      one an emit in the per-emit loop (all backends);
                      ``encode_ms`` is the block encode's part of its time
                      (``encode_batch``), the append is the rest
``commit``          span: the tick's commit point (commit cursor, state
                    epoch, changelog append, query metrics)
``gc.pause``        total: a collection of CPython's collector that this
                    thread ran inside a tick, under whichever span was open
                    (``gen2`` full collections, ``gen2_ms`` their time); no
                    entry among a tick's spans
``checkpoint``      engine state snapshot (recorded under ``__engine__``)
``push.pipeline.step``  one shared push-registry pipeline pump (poll →
                    process → drain; ``rows`` counts ring appends, from the
                    listener-mode emit fan-in too)
``push.tap.deliver``  one tap poll's residual-eval + delivery pass
                    (``rows`` delivered, ``ring_lag`` sampled per poll)
``push.residual.kernel``  one fused-residual kernel pass over a shared
                    emission span — ALL taps' predicates in one batched
                    device call (``rows``/``taps`` counters, jit_hit/miss;
                    a re-trace also records ``device.compile``)
``cutover.*``       reshard/rescale cutover phases (drain / checkpoint /
                    rebuild / restore, plus gather / repartition / insert
                    inside a reshard-restore) — recorded on the query's
                    recorder so a slow cutover is attributable to a phase
==================  ========================================================
"""

from __future__ import annotations

import gc
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

_perf = time.perf_counter
#: the calling thread's CPU time: wall time it does not cover, the thread
#: was not running
_cpu = time.thread_time

#: recorder key for engine-level (not per-query) work, e.g. checkpoints
ENGINE_RECORDER = "__engine__"

#: canonical display order for stage tables (EXPLAIN ANALYZE)
_STAGE_RANK = {
    "poll": 0,
    "process": 1,
    "deserialize": 2,
    # stage:<ctx> ranks 10 (alpha within)
    "drain": 18,
    "batch.assemble": 19,
    "device.compile": 20,
    "device.execute": 21,
    "step.dispatch": 22,
    "step.wait": 23,
    "store.evict": 24,
    "emit.decode": 25,
    "emit.read": 26,
    "emit.rows": 27,
    "store.compact": 28,
    "table.grow": 29,
    "device.step": 30,
    "table.upsert": 31,
    "exchange": 32,
    "emit.dispatch": 33,
    "emit.callbacks": 34,
    "sink.produce": 35,
    "commit": 36,
    "push.pipeline.step": 37,
    "push.tap.deliver": 38,
    "push.residual.kernel": 39,
    # unlisted stages rank 40
    # cutover.* phases rank 45 (alpha within), below checkpoint
    "checkpoint": 50,
    "gc.pause": 55,
    "tick": 60,  # the whole tick: the table's total row
}


def _cutover_rank(name: str):
    return (45, name) if name.startswith("cutover.") else None


def stage_sort_key(name: str):
    if name.startswith("stage:"):
        return (10, name)
    return _cutover_rank(name) or (_STAGE_RANK.get(name, 40), name)


_TL = threading.local()


def active() -> Optional["TickTrace"]:
    """The thread's open tick trace, or None (tracing off / outside a
    tick).  This is THE fast-path check every instrumentation site makes."""
    return getattr(_TL, "trace", None)


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


def span(name: str, wait: bool = False, cpu: bool = False):
    """Context manager recording a span on the active trace (no-op when
    tracing is off).  ``wait`` declares a span that waits for the device
    by design: it books ``off_cpu_ms`` and keeps that time to itself;
    ``cpu`` books it and hands it up."""
    tr = active()
    return tr.span(name, wait, cpu) if tr is not None else _NULL


def stage(name: str, dur_s: float = 0.0, **counters) -> None:
    """Accumulate one stage invocation on the active trace (no-op off)."""
    tr = active()
    if tr is not None:
        tr.stage(name, dur_s, **counters)


def counter(name: str, **counters) -> None:
    """Accumulate counters on a stage WITHOUT bumping its invocation count
    (byte/row accounting attached from inside a step)."""
    tr = active()
    if tr is not None:
        tr.counter(name, **counters)


def jit_cache_size(fns) -> int:
    """Sum the in-memory jit cache entries of jitted callables (None and
    non-jitted entries are skipped) — the shared accounting behind the
    compile-vs-execute split; both device backends feed their step
    functions through here."""
    n = 0
    for fn in fns:
        size = getattr(fn, "_cache_size", None)
        if size is not None:
            try:
                n += size()
            except Exception:  # noqa: BLE001 — accounting only
                pass
    return n


def _annotate(name: str):
    """Enter a ``jax.profiler.TraceAnnotation`` and return it (its caller
    exits it), or None until jax is loaded: no profiler session can be
    open before that, and an oracle-only engine never pays jax's import
    for its spans.  An annotation keeps the name it was entered with."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    annotation = profiler.TraceAnnotation(name)
    annotation.__enter__()
    return annotation


class _Span:
    """One open span.  ``name`` and ``n`` (invocations the stage counts
    for it) may be reassigned until exit: the device step learns only
    afterwards whether it compiled, the native decode how many rows of
    its chunk were good."""

    __slots__ = ("trace", "name", "n", "t0", "depth", "child_s", "gc_s",
                 "wait", "cpu", "wait_s", "_cpu0", "_annotation")

    def __init__(self, trace: "TickTrace", name: str, wait: bool = False,
                 cpu: bool = False):
        self.trace = trace
        self.name = name
        self.n = 1
        self.child_s = 0.0
        #: seconds of the collector's pauses inside the span
        self.gc_s = 0.0
        self.wait = wait
        self.cpu = cpu or wait
        #: off-CPU seconds the declared waits under the span kept
        self.wait_s = 0.0

    def __enter__(self):
        tr = self.trace
        self.depth = len(tr._open)
        tr._open.append(self)
        self._annotation = _annotate(self.name)
        if self.cpu:
            # the CPU clock is read outside the wall clock's interval on
            # both sides: a span that only computes reads 0, not noise
            self._cpu0 = _cpu()
        self.t0 = _perf()
        return self

    def __exit__(self, *exc):
        dur = _perf() - self.t0
        counters = {"self_ms": (dur - self.child_s) * 1000.0,
                    "gc_ms": self.gc_s * 1000.0}
        kept_s = self.wait_s
        if self.cpu:
            off = max(dur - (_cpu() - self._cpu0) - kept_s, 0.0)
            counters["off_cpu_ms"] = off * 1000.0
            if self.wait:
                kept_s += off
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        tr = self.trace
        try:
            tr._open.remove(self)
        except ValueError:
            pass
        parent = tr._open[-1] if tr._open else tr
        parent.child_s += dur
        parent.wait_s += kept_s
        tr.add_span(self.name, self.t0, dur, self.depth)
        tr._book(self.name, dur, self.n, counters)
        return False


class TickTrace:
    """One poll tick's trace: ordered coarse spans + per-stage totals."""

    __slots__ = (
        "query_id", "seq", "started_at_ms", "dur_ms", "spans", "stages",
        "status", "error", "keep", "child_s", "gc_s", "wait_s", "_t0",
        "_cpu0", "_open", "_pauses", "_dumped",
    )

    def __init__(self, query_id: str, seq: int):
        self.query_id = query_id
        self.seq = seq
        self.started_at_ms = int(time.time() * 1000)
        self.dur_ms = 0.0
        #: [{name, t0Ms (tick-relative), durMs, depth}] in completion order
        self.spans: List[Dict[str, Any]] = []
        #: stage -> {"ms": total, "n": invocations, <counter>: total, ...}
        self.stages: Dict[str, Dict[str, Any]] = {}
        self.status = "OK"
        self.error: Optional[str] = None
        self.keep = True  # engine clears for empty ticks (ring hygiene)
        #: seconds under depth-0 spans and timed stages outside any span
        self.child_s = 0.0
        #: seconds of the collector's pauses inside the tick
        self.gc_s = 0.0
        #: off-CPU seconds the tick's declared waits kept
        self.wait_s = 0.0
        self._cpu0 = _cpu()
        self._t0 = _perf()
        self._open: List[_Span] = []  # spans entered but not yet exited
        #: (perf_counter instant it ended, seconds) of each pause in the tick
        self._pauses: List[Tuple[float, float]] = []
        self._dumped = False

    # ------------------------------------------------------------ recording
    def span(self, name: str, wait: bool = False, cpu: bool = False) -> _Span:
        return _Span(self, name, wait, cpu)

    def add_span(self, name: str, t0: float, dur_s: float, depth: int) -> None:
        self.spans.append({
            "name": name,
            "t0Ms": round((t0 - self._t0) * 1000.0, 3),
            "durMs": round(dur_s * 1000.0, 3),
            "depth": depth,
        })

    def stage(self, name: str, dur_s: float = 0.0, n: int = 1,
              **counters) -> None:
        """Accumulate one timed stage invocation; its time counts as a
        child of the span it was made under (of the tick under none).  A
        pause of the collector inside it stays in the stage's own time (two
        clock reads are all its site takes) and is a child of that span
        already, as ``gc.pause``: it is not counted there twice."""
        if dur_s:
            child_s = dur_s
            if self._pauses:
                t0 = _perf() - dur_s
                for end, pause_s in reversed(self._pauses):
                    if end <= t0:
                        break
                    child_s -= pause_s
            (self._open[-1] if self._open else self).child_s += child_s
        self._book(name, dur_s, n, counters)

    def pause(self, end: float, dur_s: float, generation: int) -> None:
        """Book a collection this thread ran until ``end`` (on
        ``perf_counter``'s clock): ``gc.pause`` under the innermost open
        span, ``gc_ms`` on every open span and on the tick."""
        self._pauses.append((end, dur_s))
        for sp in self._open:
            sp.gc_s += dur_s
        self.gc_s += dur_s
        (self._open[-1] if self._open else self).child_s += dur_s
        full = generation == 2
        self._book("gc.pause", dur_s, 1, {
            "gen2": int(full), "gen2_ms": dur_s * 1000.0 if full else 0.0,
        })

    def counter(self, name: str, **counters) -> None:
        self._book(name, 0.0, 0, counters)

    def _book(self, name: str, dur_s: float, n: int,
              counters: Dict[str, Any]) -> None:
        st = self.stages.get(name)
        if st is None:
            st = self.stages[name] = {"ms": 0.0, "n": 0}
        st["ms"] += dur_s * 1000.0
        st["n"] += n
        for k, v in counters.items():
            st[k] = st.get(k, 0) + v

    def finish(self) -> None:
        """Close the tick: its duration, and the ``tick`` stage whose
        ``self_ms`` is the time no span or timed stage accounts for."""
        dur = _perf() - self._t0
        off = max(dur - (_cpu() - self._cpu0) - self.wait_s, 0.0)
        self.dur_ms = round(dur * 1000.0, 3)
        self._book("tick", dur, 1, {
            "self_ms": (dur - self.child_s) * 1000.0,
            "gc_ms": self.gc_s * 1000.0,
            "off_cpu_ms": off * 1000.0,
        })

    def to_dict(self) -> Dict[str, Any]:
        # a crash dump serializes mid-tick, before finish()/span exits run:
        # report elapsed time so far and include still-open spans (marked),
        # so the durable post-mortem shows what the tick was inside of
        spans = list(self.spans)
        now = _perf()
        for sp in self._open:
            spans.append({
                "name": sp.name,
                "t0Ms": round((sp.t0 - self._t0) * 1000.0, 3),
                "durMs": round((now - sp.t0) * 1000.0, 3),
                "depth": sp.depth,
                "open": True,
            })
        return {
            "queryId": self.query_id,
            "tick": self.seq,
            "startedAtMs": self.started_at_ms,
            "durMs": self.dur_ms or round((now - self._t0) * 1000.0, 3),
            "status": self.status,
            "error": self.error,
            "spans": spans,
            "stages": {
                name: {
                    k: (round(v, 3) if isinstance(v, float) else v)
                    for k, v in st.items()
                }
                for name, st in self.stages.items()
            },
        }


# ------------------------------------------------- the collector's pauses
#: re-entrant: a release may run as a dead engine's finalizer, inside a
#: collection that an allocation under the lock set off
_gc_lock = threading.RLock()
_gc_holders = 0
_gc_t0: Optional[float] = None


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    """The process's one ``gc.callbacks`` entry.  A collection runs on the
    thread whose allocation set it off, start to stop, and no second one
    starts meanwhile: one stamp serves.  A thread with no open tick books
    nothing; its collection is time off the CPU to a thread that has."""
    global _gc_t0
    if phase == "start":
        _gc_t0 = _perf() if getattr(_TL, "trace", None) is not None else None
        return
    t0, _gc_t0 = _gc_t0, None
    if t0 is not None:
        tr = getattr(_TL, "trace", None)
        if tr is not None:
            end = _perf()
            tr.pause(end, end - t0, info["generation"])


def hold_gc_hook() -> None:
    """Install the hook for one more holder (an engine that traces)."""
    global _gc_holders
    with _gc_lock:
        _gc_holders += 1
        if _gc_holders == 1:
            gc.callbacks.append(_on_gc)


def release_gc_hook() -> None:
    """One holder fewer; the last one takes the hook out."""
    global _gc_holders
    with _gc_lock:
        if _gc_holders == 0:
            return
        _gc_holders -= 1
        if _gc_holders == 0 and _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)


class tick:
    """Per-tick context manager: installs a fresh TickTrace as the thread's
    active trace and records it into the recorder on exit.  ``tick(None)``
    (tracing disabled) is a no-op that yields None."""

    __slots__ = ("rec", "trace", "_prev", "_annotation")

    def __init__(self, recorder: Optional["FlightRecorder"]):
        self.rec = recorder
        self.trace = None

    def __enter__(self) -> Optional[TickTrace]:
        if self.rec is None:
            return None
        tr = self.trace = self.rec.begin()
        self._prev = getattr(_TL, "trace", None)
        _TL.trace = tr
        self._annotation = _annotate(f"ksql.tick#{tr.query_id}#{tr.seq}")
        return tr

    def __exit__(self, et, ev, tb):
        tr = self.trace
        if tr is None:
            return False
        if self._annotation is not None:
            self._annotation.__exit__(et, ev, tb)
        _TL.trace = self._prev
        if et is not None and tr.status == "OK":
            tr.status = "ERROR"
            tr.error = f"{et.__name__}: {ev}"
        tr.finish()
        if tr.keep or tr.status == "ERROR":
            self.rec.record(tr)
        return False  # never swallow the tick's exception


def _percentile(sorted_xs: List[float], p: float) -> Optional[float]:
    if not sorted_xs:
        return None
    idx = min(int(len(sorted_xs) * p), len(sorted_xs) - 1)
    return round(sorted_xs[idx], 3)


class FlightRecorder:
    """Ring buffer of the last N tick traces for one query, plus cumulative
    per-stage totals that never trim (Prometheus counters must be monotone
    — window-derived values would regress as old ticks fall out)."""

    def __init__(self, query_id: str, ring_size: int = 64):
        self.query_id = query_id
        self._ring: deque = deque(maxlen=max(1, int(ring_size)))
        self._seq = 0
        self._cum: Dict[str, Dict[str, Any]] = {}
        self._lock = threading.Lock()
        # retention hook: called with each recorded trace AFTER the ring
        # lock is released (the telemetry timeline folds here; a hook
        # crash must never kill the tick that produced the trace)
        self.observer: Optional[Callable[[TickTrace], None]] = None

    def begin(self) -> TickTrace:
        with self._lock:
            self._seq += 1
            return TickTrace(self.query_id, self._seq)

    def record(self, trace: TickTrace) -> None:
        with self._lock:
            self._ring.append(trace)
            for name, st in trace.stages.items():
                cum = self._cum.get(name)
                if cum is None:
                    cum = self._cum[name] = {"ms": 0.0, "n": 0}
                for k, v in st.items():
                    cum[k] = cum.get(k, 0) + v
        obs = self.observer
        if obs is not None:
            try:
                obs(trace)
            except Exception:
                pass

    def last(self) -> Optional[TickTrace]:
        with self._lock:
            return self._ring[-1] if self._ring else None

    def window_ticks(self) -> int:
        with self._lock:
            return len(self._ring)

    def recent(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        with self._lock:
            traces = list(self._ring)
        if n is not None:
            traces = traces[-n:]
        return [t.to_dict() for t in traces]

    def stage_stats(self) -> Dict[str, Dict[str, Any]]:
        """Per-stage aggregate: p50/p99 of per-tick stage time over the
        recorder window, plus cumulative invocation counts / total ms /
        counters since the query started.  A stage that was never timed
        (``n`` = 0: byte and row counters) reports its counters and no
        ``total_ms``/``p50_ms``/``p99_ms`` — a 0 there would read as
        "this costs nothing"."""
        with self._lock:
            traces = list(self._ring)
            cum = {name: dict(st) for name, st in self._cum.items()}
        per_tick: Dict[str, List[float]] = {}
        for t in traces:
            for name, st in t.stages.items():
                per_tick.setdefault(name, []).append(st.get("ms", 0.0))
        out: Dict[str, Dict[str, Any]] = {}
        for name, c in cum.items():
            xs = sorted(per_tick.get(name, []))
            d: Dict[str, Any] = {"ticks": len(xs), "n": int(c.get("n", 0))}
            if d["n"]:
                d["total_ms"] = round(float(c.get("ms", 0.0)), 3)
                d["p50_ms"] = _percentile(xs, 0.50)
                d["p99_ms"] = _percentile(xs, 0.99)
            for k, v in c.items():
                if k not in ("ms", "n"):
                    d[k] = round(v, 3) if isinstance(v, float) else v
            out[name] = d
        return out
