"""Push registry: multiplex push sessions as filtered taps over shared
persistent pipelines.

The engine seam in the reference splits ``executeScalablePushQuery`` from
per-session transient queries (KsqlEngine.java:558 / ScalablePushRegistry)
because one-executor-per-subscriber cannot serve high fan-out: a million
subscribers to the same stream must not mean a million redundant
consumer + executor pipelines re-decoding the same topic.  This module is
that serving architecture:

* the FIRST push query of a given canonical shape (source + shared
  pre-ops; the per-session residual is excluded from the key) spins up ONE
  shared internal pipeline — an identity query over the source, built
  through the same device→oracle executor ladder persistent queries use —
  that materializes a bounded in-memory changelog ring of offset-stamped
  emissions;
* every subsequent compatible session becomes a cheap **tap**: a
  per-session residual (WHERE predicate + projection, the exact oracle
  ``FilterNode``/``SelectNode`` a dedicated session would run) evaluated
  host-side against the shared emissions, with a per-tap cursor into the
  ring;
* a slow tap that falls off the ring's tail is resumed past the gap with a
  gap marker naming the skipped offset span (the PR-5 gap-marker
  contract) — it never stalls the shared pipeline and never dies;
* a shared-pipeline fault self-heals exactly like a supervised session
  (classify → rewind → rebuild → backoff on the ``ksql.query.retry.*``
  knobs) and the heal lands ONE in-ring gap marker every tap observes at
  its own cursor position;
* the last tap detaching starts the ``ksql.push.registry.linger.ms``
  clock; an expired idle pipeline is reaped (refcounted teardown), an
  attach inside the window reuses the warm pipeline and its ring.

Two pipeline modes:

* **listener** — when a RUNNING persistent query materializes the source,
  the pipeline subscribes one callback through the engine's
  ``register_push_tap`` seam and fans its fence-guarded ``on_emit``
  emissions out to the taps (PR-6 zombie fencing applies unchanged: a
  fenced-off executor can never write the ring).  A terminated upstream
  fails the pipeline over to standalone mode with a gap marker.
* **standalone** — the pipeline owns a latest-offset consumer over the
  source topic and an executor built like the transient device path
  (device when the identity plan lowers, oracle otherwise; sink muted).
  All ``device.compile`` work happens HERE, once, on the shared pipeline's
  flight recorder — taps compile nothing.

Locking: one registry-wide RLock guards pipelines, rings, tap tables and
counters.  Lock order is engine_lock → registry lock everywhere (tap polls
run under the server's engine_lock; ``close()`` takes only the registry
lock and never the engine's).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ksql_tpu.common import config as cfg
from ksql_tpu.common import faults, tracing
from ksql_tpu.execution import expressions as ex
from ksql_tpu.execution import steps as st
from ksql_tpu.server.tap_kernel import (
    ResidualUnsupported,
    TapKernel,
    classify_residual,
)

#: ring entry kinds
ROW = 0
GAP = 1

#: pseudo-columns bound to the source record's topic position — the shared
#: emit stream does not carry them, so residuals referencing them keep a
#: dedicated session
_POSITIONAL_PSEUDO = ("ROWPARTITION", "ROWOFFSET")


def _now_ms() -> float:
    return time.time() * 1000.0


def residual_chain(plan) -> Optional[List[Any]]:
    """Classify a push-query plan for sharing: returns the step chain
    ``[root-side residual steps..., StreamSource]`` when the plan is a
    shareable shape — an optional sink over any number of
    StreamSelect/StreamFilter steps terminating in exactly a StreamSource —
    else None (aggregates, joins, windows, repartitions and table
    functions are stateful/positional residuals that keep a dedicated
    session)."""
    step = plan.physical_plan
    if isinstance(step, (st.StreamSink, st.TableSink)):
        step = step.source
    chain: List[Any] = []
    while isinstance(step, (st.StreamSelect, st.StreamFilter)):
        chain.append(step)
        step = step.source
    if type(step) is not st.StreamSource:
        return None
    for s in chain:
        exprs = (
            [s.predicate] if isinstance(s, st.StreamFilter)
            else [e for _, e in s.selects]
        )
        for e0 in exprs:
            for node in ex.walk(e0):
                if isinstance(node, ex.ColumnRef) and (
                    node.name in _POSITIONAL_PSEUDO
                ):
                    return None
    chain.append(step)
    return chain


class PushTap:
    """One session's subscription to a shared pipeline: a cursor into the
    ring plus the session's residual filter/projection nodes (the same
    oracle nodes a dedicated session would run, compiled once at attach).

    Delivery happens on the polling session's thread; per-tap state
    (cursor, counters) is written under the registry lock because the
    listener-mode emit path publishes ring entries concurrently."""

    def __init__(self, pipeline: "SharedPushPipeline", session,
                 residual_steps: List[Any]):
        from ksql_tpu.runtime.oracle import Compiler, FilterNode, SelectNode

        self.pipeline = pipeline
        self.session = session
        self.id = session.id
        engine = pipeline.engine
        compiler = Compiler(
            engine.registry,
            lambda expr, exc: engine._on_error(
                f"push-tap:{session.id}:{expr}", exc
            ),
        )
        # residual_steps is root-side-first; events flow source-side-first
        nodes = []
        for s in reversed(residual_steps):
            if isinstance(s, st.StreamFilter):
                nodes.append(FilterNode(s, compiler, is_table=False))
            else:
                nodes.append(SelectNode(s, compiler))
        self._nodes = nodes
        # projection-only view of the chain: fused delivery applies these
        # to rows the device mask already passed (filters skipped — the
        # kernel evaluated them), reproducing the oracle transform exactly
        self._select_nodes = [
            n for n in nodes if isinstance(n, SelectNode)
        ]
        # fused residual classification (ISSUE 12): join the pipeline's
        # predicate family when the WHERE chain lowers; unsupported
        # residuals keep the host path with the reason counted under
        # engine.fallback_reasons (the windowing_fallback contract).
        # Pure projections (no WHERE) stay host-side silently: with
        # nothing to filter, delivery already IS a plain gather.
        self.fused = False
        self.fused_fallback: Optional[str] = None
        kernel = pipeline.ensure_kernel()
        if kernel is not None:
            try:
                spec = classify_residual(
                    residual_steps, pipeline.out_schema
                )
                if spec is not None:
                    kernel.attach(session.id, spec)
                    self.fused = True
            except ResidualUnsupported as e:
                self.fused_fallback = str(e)
                reason = f"push residual stays host-side: {e}"
                engine.fallback_reasons[reason] = (
                    engine.fallback_reasons.get(reason, 0) + 1
                )
        self.cursor = pipeline.head_seq()  # attach at the live head
        self.delivered_rows = 0
        self.evicted_rows = 0
        self.gap_markers = 0
        self.closed = False

    def lag(self) -> int:
        """Ring rows published but not yet drained by this tap — the
        per-tap backpressure gauge ``/query-lag/<id>`` serves."""
        return max(self.pipeline.head_seq() - self.cursor, 0)

    # thread entrypoint: tap delivery — runs on whichever thread polls the
    # owning session (the server's HTTP handler threads), concurrently
    # with the listener-mode emit path appending to the shared ring
    # graftlint: entrypoint=push-tap-poll
    def poll(self) -> None:
        """Advance the shared pipeline, then deliver new emissions through
        this tap's residual into the owning session (rows via the
        session's ``_on_emit``, gap markers via ``_enqueue_gap``)."""
        pipe = self.pipeline
        pipe.advance()
        max_rows = int(pipe.engine.effective_property(
            cfg.PUSH_REGISTRY_MAX_POLL_ROWS, 4096
        ))
        # overload tap-clamp: while engaged, every tap drains in small
        # slices so N storming subscribers cannot monopolize the engine
        # (lock-free read — the clamp seam never takes the manager lock
        # under the registry lock)
        max_rows = pipe.engine.overload.tap_poll_rows(max_rows)
        entries, evicted, new_cursor = pipe.read_from(self.cursor, max_rows)
        if not entries and evicted is None:
            # idle poll: nothing to deliver, and an idle-poll trace would
            # be discarded anyway (keep=False) — skip the TickTrace
            # allocation + recorder lock entirely on the quiet-source
            # hot path 50 polling taps sit on
            self.cursor = new_cursor  # graftlint: owner=push-tap-poll
            return
        fused = None
        if self.fused and entries and pipe.kernel is not None:
            # ONE kernel evaluation per span serves every fused tap: the
            # span cache keys on (start, rows, membership epoch), so taps
            # polling in lockstep share the same bitmask pass.  None =
            # degraded/below-min-taps/uncached-failure: host path.
            fused = pipe.kernel.mask_for(
                self.id, new_cursor - len(entries), entries
            )
        # delivery ticks go to a SEPARATE "<pipeline>/taps" recorder: N
        # taps per pump would otherwise evict the pump's own ticks from
        # the 64-slot ring and reduce the (gated) push.pipeline.step p99
        # to a near-single-sample statistic under fan-out
        rec = pipe.engine.recorder_if_enabled(pipe.id + "/taps")
        with tracing.tick(rec):
            with tracing.span("push.tap.deliver"):
                delivered = self._deliver(entries, evicted, fused)
            # ring lag sampled once per delivering poll (sum over the
            # window / n = mean lag; the point-in-time gauge rides
            # /query-lag)
            tracing.counter(
                "push.tap.deliver", rows=delivered,
                ring_lag=max(pipe.head_seq() - new_cursor, 0),
            )
        self.cursor = new_cursor  # graftlint: owner=push-tap-poll

    def _deliver(self, entries, evicted, fused=None) -> int:
        """Deliver ``entries`` into the owning session — through the fused
        kernel's precomputed match bitmask when ``fused`` is set (a
        bitmask read + column gather: only matching rows pay host-side
        projection), else through the host residual chain row-at-a-time.
        Gap markers deliver identically on both paths; returns rows
        delivered."""
        from ksql_tpu.runtime.oracle import SinkEmit, StreamRow

        pipe = self.pipeline
        sess = self.session
        registry = pipe.registry
        if evicted is not None:
            # fell off the ring's tail: resume past the gap, never stall
            # the shared pipeline (PR-5 contract — span, not silence).
            # skippedRows counts ROWS (evicted markers excluded), so it
            # sums consistently with ksql_push_registry_ring_evicted_total
            skipped = evicted[2]
            marker = {
                "queryId": sess.id,
                "pipeline": pipe.id,
                "evicted": True,
                "fromSeq": evicted[0],
                "toSeq": evicted[1],
                "skippedRows": skipped,
                "error": (
                    f"tap lagged {skipped} rows past the shared ring "
                    f"(ksql.push.registry.ring.size={pipe.ring_size}); "
                    "resuming at the retained tail"
                ),
            }
            with registry._lock:
                self.evicted_rows += skipped
                self.gap_markers += 1
                registry.gap_markers += 1
            sess._enqueue_gap(marker)
        delivered = 0
        prog = getattr(sess, "progress", None)
        if fused is not None:
            # fused path: the kernel already evaluated every filter over
            # the whole span; visit only matching rows (+ interleaved gap
            # markers, in ring order).  The watermark advances once by the
            # span's max event time — the same fold the per-row path
            # reaches, without O(rows) Python.
            if prog is not None and fused["max_ts"] is not None:
                prog.note_watermark(fused["max_ts"])
            positions = np.flatnonzero(fused["mask"][: len(entries)])
            limit = getattr(sess, "limit", None)
            if limit is not None:
                # LIMIT-aware gather: don't even visit matches past the
                # session's remaining budget (the session still enforces
                # the cap authoritatively in _on_emit)
                remaining = max(int(limit) - int(sess._results), 0)
                positions = positions[:remaining]
            gap_positions = [
                i for i, (k, _) in enumerate(entries) if k == GAP
            ]
            if gap_positions:
                positions = sorted(set(positions.tolist()) | set(gap_positions))
            index_iter = positions
        else:
            index_iter = range(len(entries))
        for i in index_iter:
            kind, payload = entries[i]
            if kind == GAP:
                marker = dict(payload)
                marker["queryId"] = sess.id
                with registry._lock:
                    self.gap_markers += 1
                    registry.gap_markers += 1
                sess._enqueue_gap(marker)
                continue
            key, row, ts = payload
            if fused is not None:
                # mask passed: apply the projection chain only (filters
                # are already decided) to this matching row
                events: List[Any] = [StreamRow(key, row, ts, None)]
                for node in self._select_nodes:
                    events = [
                        ev2 for ev in events for ev2 in node.receive(0, ev)
                    ]
            else:
                if prog is not None:
                    # the tracker sees every shared emission (filtered-out
                    # rows still advance the tap's event-time watermark)
                    prog.note_watermark(ts)
                events = [StreamRow(key, row, ts, None)]
                for node in self._nodes:
                    nxt: List[Any] = []
                    for ev in events:
                        nxt.extend(node.receive(0, ev))
                    events = nxt
                    if not events:
                        break
            for ev in events:
                if sess._on_emit(SinkEmit(ev.key, ev.row, ev.ts, ev.window)):
                    delivered += 1
        if delivered:
            with registry._lock:
                self.delivered_rows += delivered
                registry.delivered_rows += delivered
        return delivered

    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        if self.fused and self.pipeline.kernel is not None:
            # lane free is a mask update — no retrace for the survivors
            self.pipeline.kernel.detach(self.id)
        self.pipeline.detach(self)


class SharedPushPipeline:
    """ONE internal pipeline serving every tap of a canonical shape: an
    identity query over the source materializing a bounded changelog ring
    of (key, full row, ts) emissions, offset-stamped by a monotone
    sequence.  See the module docstring for modes and healing."""

    def __init__(self, registry: "PushRegistry", key: str, source_name: str):
        self.registry = registry
        self.engine = registry.engine
        self.key = key
        self.id = f"pushreg_{next(registry._seq)}_{source_name.lower()}"
        self.source_name = source_name
        self._lock = registry._lock
        self.ring: List[Tuple[int, Any]] = []
        self.base_seq = 0
        # seqs of GAP entries that were evicted off the ring (bounded):
        # subtracts markers from lagging taps' skipped-ROW spans
        self._evicted_gap_seqs: List[int] = []
        self.ring_size = int(self.engine.effective_property(
            cfg.PUSH_REGISTRY_RING_SIZE, 8192
        ))
        self.taps: Dict[str, PushTap] = {}
        self.idle_since_ms: Optional[float] = None
        self.stopped = False
        # self-healing bookkeeping (the session ladder, pipeline-scoped)
        self.restart_count = 0
        self.retry_at_ms = 0.0
        self.retry_backoff_ms = 0.0
        self.terminal = False
        self._needs_rebuild = False
        # mode wiring
        self.mode = "standalone"
        self.upstream_qid: Optional[str] = None
        self._unsubscribe: Optional[Callable] = None
        self.consumer = None
        self.executor = None
        self.backend = "none"
        self._planned = None
        self._key_names: List[str] = []
        # fused tap residuals (ISSUE 12): the batched predicate kernel
        # (built lazily on the first compilable tap) + listener-mode
        # device emission blocks, keyed by their ring-seq span so the
        # kernel evaluates device-resident columns instead of re-encoding
        # host rows
        self.kernel: Optional[TapKernel] = None
        self.out_schema = None
        self._emit_blocks: deque = deque(maxlen=8)
        # block held between a batch callback and its last row append
        # ([start, n, blk, appended]) — committed only once complete
        self._pending_block: Optional[list] = None
        fused_on = cfg._bool(self.engine.effective_property(
            cfg.PUSH_FUSED_ENABLE, True
        ))
        attached = self.engine.register_push_tap(
            source_name, self._on_emit,
            # only a fused pipeline consumes emit blocks: without the
            # kernel the upstream must not pay per-batch device gathers
            batch_cb=self._on_emit_batch if fused_on else None,
        )
        if attached is not None:
            # listener mode: ride the running query's fence-guarded
            # on_emit fan-out — one listener for N taps
            self.upstream_qid, self._unsubscribe = attached
            self.mode = "listener"
            src = self.engine.metastore.get_source(source_name)
            self._key_names = (
                [c.name for c in src.schema.key_columns] if src else []
            )
            self.out_schema = src.schema if src else None
        else:
            self._build_standalone(from_beginning=False)

    # ------------------------------------------------------------- building
    def _build_standalone(self, from_beginning: bool) -> None:
        """Plan + build the internal identity pipeline over the source
        (the shared common prefix: consume + decode + identity
        projection), consuming from the topic's current end."""
        from ksql_tpu.analyzer.analyzer import analyze_query
        from ksql_tpu.runtime.topics import Consumer

        engine = self.engine
        prepared = engine.parse(
            f"SELECT * FROM {self.source_name} EMIT CHANGES;"
        )
        analysis = analyze_query(
            prepared[0].statement, engine.metastore, engine.registry
        )
        self._planned = engine.planner.plan(analysis, self.id)
        out_schema = self._planned.plan.physical_plan.schema
        with self._lock:
            # the emit path reads the key layout: swap it under the lock
            # (a listener-mode zombie emit may still race the failover)
            self._key_names = [c.name for c in out_schema.key_columns]
            self.out_schema = out_schema
        topics = sorted({
            step.topic
            for step in st.walk_steps(self._planned.plan.physical_plan)
            if hasattr(step, "topic")
            and not isinstance(step, (st.StreamSink, st.TableSink))
        })
        for t in topics:
            engine.broker.create_topic(t)
        self.consumer = Consumer(
            engine.broker, topics, from_beginning=from_beginning
        )
        self.executor = self._build_executor()
        self.mode = "standalone"

    def _build_executor(self):
        """The transient executor ladder: device when the identity plan
        lowers (ALL compile work lands here, on the one shared pipeline),
        oracle otherwise.  The sink is muted — the ring is the output."""
        from ksql_tpu.runtime.oracle import OracleExecutor

        engine = self.engine
        executor = None
        backend = str(
            engine.effective_property(cfg.RUNTIME_BACKEND, "device")
        ).lower()
        if backend != "oracle":
            from ksql_tpu.compiler.jax_expr import DeviceUnsupported
            from ksql_tpu.runtime.device_executor import DeviceExecutor

            device_plan = engine._wrap_transient_plan(
                self._planned.plan, self.id
            )
            try:
                executor = DeviceExecutor(
                    device_plan, engine.broker, engine.registry,
                    on_error=engine._on_error, emit_callback=self._on_emit,
                    batch_size=int(engine.config.get(cfg.BATCH_CAPACITY)),
                    per_record=True,  # taps expect per-record emit order
                    store_capacity=int(engine.config.get(cfg.STATE_SLOTS)),
                )
                self.backend = "device"
            except DeviceUnsupported:
                pass
            except Exception as e:  # noqa: BLE001 — the engine's rule:
                # only a plan that never lowered takes the oracle quietly
                engine._lowering_failed(
                    f"push-registry:{self.id}", e,
                    engine._classify_transient_static(self._planned.plan),
                )
        if executor is None:
            engine.annotate_serde_semantics(self._planned.plan)
            executor = OracleExecutor(
                self._planned.plan, engine.broker, engine.registry,
                on_error=engine._on_error, emit_callback=self._on_emit,
            )
            self.backend = "oracle"
        writer = getattr(executor, "sink_writer", None)
        if writer is not None:
            writer.enabled = False  # the ring is the only output
        return executor

    # ------------------------------------------------------- fused kernel
    def ensure_kernel(self) -> Optional[TapKernel]:
        """The pipeline's fused residual kernel (tap_kernel.py), built
        lazily on the first compilable tap — None when the feature is off
        or the output schema is unknown (listener over an unregistered
        source)."""
        with self._lock:
            if self.kernel is not None:
                return self.kernel
            engine = self.engine
            if self.out_schema is None or not cfg._bool(
                engine.effective_property(cfg.PUSH_FUSED_ENABLE, True)
            ):
                return None
            self.kernel = TapKernel(
                self, self.out_schema, self._lock,
                capacity_min=int(engine.effective_property(
                    cfg.PUSH_FUSED_CAPACITY_MIN, 8
                )),
                capacity_max=int(engine.effective_property(
                    cfg.PUSH_FUSED_CAPACITY_MAX, 4096
                )),
                min_taps=int(engine.effective_property(
                    cfg.PUSH_FUSED_MIN_TAPS, 2
                )),
            )
            return self.kernel

    # thread entrypoint: fires with the per-emit listener fan-out below,
    # once per decoded device batch, from the engine's process thread
    # graftlint: entrypoint=push-pipeline-emit
    def _on_emit_batch(self, emits, blk) -> None:
        """Listener-mode batch handoff: hold the upstream device
        executor's still-device-resident columnar emit block PENDING for
        the ring-seq span the per-emit appends right after this call will
        occupy — the tap kernel then evaluates residuals straight over the
        block instead of re-encoding host rows.

        The block only commits to ``_emit_blocks`` after all n rows
        actually appended (``_on_emit`` counts them down): if the
        upstream's emit fence flips mid-dispatch, the dropped tail's seqs
        are later occupied by the REBUILT executor's rows, and a block
        committed eagerly would hand the kernel the OLD executor's
        columns for them.  An incomplete batch simply never commits."""
        if blk is None:
            return
        with self._lock:
            if self.stopped or self.kernel is None:
                self._pending_block = None
                return  # no fused consumer: don't retain device arrays
            start = self.base_seq + len(self.ring)
            # [start seq, expected rows, block, rows appended so far]
            self._pending_block = [start, len(emits), blk, 0]

    # ------------------------------------------------------------ emission
    # thread entrypoint: in listener mode this fires from whichever thread
    # drives engine.poll_once (the server's process loop), concurrently
    # with tap HTTP threads reading the ring
    # graftlint: entrypoint=push-pipeline-emit
    def _on_emit(self, e) -> None:
        """Shared emit fan-in: stamp the emission with the next ring seq.
        The full row (key columns merged in, oracle decode layout) is what
        tap residuals evaluate against."""
        # ring-append accounting on the active tick — in listener mode the
        # active trace is the UPSTREAM query's, so its flight recorder (and
        # /query-trace) shows the fan-out rows its emissions feed; in
        # standalone mode this lands inside the pipeline's own
        # push.pipeline.step span (rows counter, no extra ms)
        tracing.counter("push.pipeline.step", rows=1)
        if e.row is None:
            row = None
        else:
            row = dict(zip(self._key_names, e.key))
            row.update(e.row)
        with self._lock:
            if self.stopped:
                return  # reaped pipeline: drop the stale emission
            seq = self.base_seq + len(self.ring)
            self.ring.append((ROW, (e.key, row, e.ts)))
            pend = self._pending_block
            if pend is not None:
                if seq == pend[0] + pend[3]:
                    pend[3] += 1
                    if pend[3] == pend[1]:
                        # every row of the batch landed: the block is
                        # provably aligned with these ring seqs — commit
                        self._emit_blocks.append(
                            (pend[0], pend[1], pend[2])
                        )
                        self._pending_block = None
                else:  # out-of-band append: the pending block can no
                    self._pending_block = None  # longer be trusted
            overflow = len(self.ring) - self.ring_size
            if overflow > 0:
                evicted_rows = 0
                for off, (k, _) in enumerate(self.ring[:overflow]):
                    if k == ROW:
                        evicted_rows += 1
                    else:
                        # remember evicted GAP seqs so a lagging tap's
                        # skipped-span accounting can subtract them —
                        # skippedRows must mean ROWS, matching the
                        # registry's ring-evicted counter
                        self._evicted_gap_seqs.append(self.base_seq + off)
                del self.ring[:overflow]
                self.base_seq += overflow
                if len(self._evicted_gap_seqs) > 256:
                    # bounded memory; gaps are one-per-incident rare.  A
                    # truncated entry can only OVERSTATE a span's row
                    # count by one, never hide a lost row.
                    del self._evicted_gap_seqs[:-256]
                self.registry.ring_evicted += evicted_rows

    def head_seq(self) -> int:
        with self._lock:
            return self.base_seq + len(self.ring)

    def read_from(self, cursor: int, max_rows: int):
        """Ring entries from ``cursor`` (bounded), the evicted span if the
        cursor fell off the tail — ``(from_seq, to_seq, skipped_rows)``
        with gap-marker entries excluded from the row count — and the new
        cursor."""
        with self._lock:
            evicted = None
            if cursor < self.base_seq:
                gaps_in_span = sum(
                    1 for s in self._evicted_gap_seqs
                    if cursor <= s < self.base_seq
                )
                evicted = (
                    cursor, self.base_seq,
                    max(self.base_seq - cursor - gaps_in_span, 0),
                )
                cursor = self.base_seq
            start = cursor - self.base_seq
            entries = list(self.ring[start:start + max_rows])
            return entries, evicted, cursor + len(entries)

    def _append_gap(self, marker: Dict[str, Any]) -> None:
        with self._lock:
            self._pending_block = None  # a gap entry breaks the span
            self.ring.append((GAP, dict(marker)))
            # gap markers never evict here: the next row append rebounds
            # the ring, and a marker is one entry per incident

    # ------------------------------------------------------------- driving
    def advance(self, max_records: int = 1024) -> None:
        """Pump the shared pipeline (called by every tap poll; serialized
        under the server's engine lock).  Listener mode nudges the engine
        loop; standalone mode polls its own consumer through the executor
        with the session self-healing ladder around it.

        Each pump is bounded by the ring size: a tap that polls keeps up
        with its own advances by construction — only a tap that stops
        polling while OTHERS drive the pipeline falls off the tail."""
        if self.terminal or self.stopped:
            return
        max_records = max(1, min(max_records, self.ring_size))
        engine = self.engine
        if self._now() < self.retry_at_ms:
            return  # backing off after a heal (failover retries included)
        if self.mode == "listener":
            h = engine.queries.get(self.upstream_qid)
            if h is None or not h.is_running():
                # upstream terminated/paused: fail over to a standalone
                # consumer at the live end, with a gap marker naming it.
                # One regime change per advance — the next poll drains
                # (and a FAILED failover must not fall through to the
                # rebuild branch and double-count the incident)
                self._failover_standalone()
            else:
                engine.run_until_quiescent(max_iters=1)
            return
        if self._needs_rebuild:
            try:
                if self.consumer is None or self._planned is None:
                    # a failed failover left no pipeline at all: rebuild
                    # the whole standalone side, not just the executor
                    self._build_standalone(from_beginning=False)
                else:
                    self.executor = self._build_executor()
                self._needs_rebuild = False
            except Exception as e:  # noqa: BLE001 — still failing: another
                self._failed(e, dict(self.consumer.positions)  # incident
                             if self.consumer is not None else {})
                return
        snapshot = dict(self.consumer.positions)
        rec = engine.recorder_if_enabled(self.id)
        try:
            # chaos seam: kill/hang the SHARED pipeline under many taps
            # (scripts/chaos_soak.py --fanout)
            faults.fault_point("push.pipeline.step", self.id)
            with tracing.tick(rec) as tick:
                with tracing.span("push.pipeline.step"):
                    records = self.consumer.poll(max_records)
                    if tick is not None:
                        tick.keep = bool(records)
                    for topic, r in records:
                        try:
                            self.executor.process(topic, r)
                        except Exception as pe:  # noqa: BLE001
                            if engine._is_poison(pe):
                                engine._on_error(
                                    f"poison:{self.id}:{topic}", pe
                                )
                                continue
                            raise
                    drain = getattr(self.executor, "drain", None)
                    if drain is not None:
                        drain()
            if records and self.restart_count:
                # healthy rows after a restart close the incident: the
                # retry budget bounds restarts PER incident, not over the
                # pipeline's lifetime (the session ladder's contract)
                self.restart_count = 0
                self.retry_backoff_ms = 0.0
        except Exception as e:  # noqa: BLE001 — pipeline self-healing
            self._failed(e, snapshot)

    def _failover_standalone(self) -> None:
        """Listener-mode upstream went away: detach the dead listener and
        rebuild as a standalone consumer from the live end, surfacing the
        regime change as one gap marker every tap sees."""
        if self._unsubscribe is not None:
            self._unsubscribe()
            self._unsubscribe = None  # graftlint: owner=push-tap-poll
        qid, self.upstream_qid = self.upstream_qid, None
        try:
            self._build_standalone(from_beginning=False)
        except Exception as e:  # noqa: BLE001 — source dropped too: hand
            # recovery to the standalone retry ladder (mode must flip, or
            # every poll would re-enter this failover path ahead of the
            # backoff and flood the ring with gap markers)
            self.mode = "standalone"
            self._needs_rebuild = True
            self._failed(e, {})
            return
        self.restart_count += 1
        with self._lock:
            self.registry.heals += 1
        self._append_gap({
            "pipeline": self.id,
            "error": f"upstream query {qid} is gone; shared pipeline "
                     "failed over to a standalone consumer at the live end",
            "restarts": self.restart_count,
        })

    def _failed(self, e: Exception, snapshot: Dict) -> None:
        """classify → rewind → rebuild → backoff, pipeline-scoped: the
        identity pipeline is stateless, so rewinding the consumer to the
        pre-poll snapshot replays the whole failed batch (no rows lost);
        every tap observes exactly one in-ring gap marker per incident."""
        engine = self.engine
        engine._on_error(f"push-registry:{self.id}", e)
        if self.consumer is not None:
            self.consumer.positions.clear()
            self.consumer.positions.update(snapshot)
        self.restart_count += 1
        with self._lock:
            self.registry.heals += 1
        marker = {
            "pipeline": self.id,
            "error": f"{type(e).__name__}: {e}",
            "restarts": self.restart_count,
        }
        retry_max = int(
            engine.effective_property(cfg.QUERY_RETRY_MAX, 2 ** 31)
        )
        if self.restart_count > retry_max:
            self.terminal = True
            marker["terminal"] = True
        else:
            initial = float(engine.effective_property(
                cfg.QUERY_RETRY_BACKOFF_INITIAL_MS, 15000
            ))
            maximum = float(engine.effective_property(
                cfg.QUERY_RETRY_BACKOFF_MAX_MS, 900000
            ))
            self.retry_backoff_ms = min(
                (self.retry_backoff_ms * 2) or initial, maximum
            )
            self.retry_at_ms = self._now() + self.retry_backoff_ms
            try:
                self.executor = self._build_executor()
                self._needs_rebuild = False
            except Exception as e2:  # noqa: BLE001 — rebuild failed: the
                # next advance retries it after the backoff
                self._needs_rebuild = True
                engine._on_error(f"push-registry:{self.id}:rebuild", e2)
        self._append_gap(marker)

    @staticmethod
    def _now() -> float:
        return _now_ms()

    # ------------------------------------------------------------ refcount
    def attach(self, tap: PushTap) -> None:
        with self._lock:
            self.taps[tap.id] = tap
            self.idle_since_ms = None

    def detach(self, tap: PushTap) -> None:
        with self._lock:
            self.taps.pop(tap.id, None)
            if not self.taps:
                self.idle_since_ms = _now_ms()
        self.registry.sweep()

    def stop(self) -> None:
        """Teardown: unhook the listener, drop consumer + executor.  Under
        the registry lock so a concurrent listener-mode emit observes
        ``stopped`` and drops its row instead of appending to a dead
        ring."""
        with self._lock:
            self.stopped = True
            if self._unsubscribe is not None:
                self._unsubscribe()
                self._unsubscribe = None
            self.consumer = None
            self.executor = None
            self._emit_blocks.clear()  # release retained device arrays
            self._pending_block = None

    def healthy_row_count(self) -> int:
        with self._lock:
            return sum(1 for k, _ in self.ring if k == ROW)


class PushRegistry:
    """Engine-wide registry of shared push pipelines (the
    ScalablePushRegistry analog, generalized from one narrow attach case
    to every filter/projection push shape).  Owned by the engine via its
    ``get_push_registry`` seam; surfaced in /metrics as
    ``ksql_push_registry_pipelines`` / ``ksql_push_taps{registry}`` plus
    delivered/evicted/gap-marker counters."""

    def __init__(self, engine):
        self.engine = engine
        self._lock = threading.RLock()
        self._seq = itertools.count(1)
        self.pipelines: Dict[str, SharedPushPipeline] = {}
        # cumulative counters (survive pipeline teardown)
        self.delivered_rows = 0
        self.ring_evicted = 0
        self.gap_markers = 0
        self.heals = 0
        # fused-residual counters (ISSUE 12): kernel passes/rows, compile
        # epochs (one per capacity tier / row bucket), and pipelines that
        # degraded to host residuals after a kernel failure
        self.residual_kernel_evals = 0
        self.residual_kernel_rows = 0
        self.residual_compile_epochs = 0
        self.residual_degraded = 0

    # ------------------------------------------------------------ attaching
    def try_attach(self, session, planned, analysis) -> Optional[PushTap]:
        """Attach a new push session as a tap when its shape shares;
        returns the tap, or None (caller falls back to the legacy
        scalable attach, then to a dedicated session)."""
        engine = self.engine
        if not cfg._bool(
            engine.effective_property(cfg.PUSH_REGISTRY_ENABLE, True)
        ):
            return None
        if not cfg._bool(
            engine.config.get("ksql.query.push.v2.enabled", True)
        ):
            # the operator's master scalable-push opt-out covers the
            # registry tier too: sessions keep dedicated catchup consumers
            return None
        if len(getattr(analysis, "sources", ())) != 1:
            return None
        chain = residual_chain(planned.plan)
        if chain is None:
            return None
        source_step = chain[-1]
        source_name = getattr(source_step, "source_name", None) or (
            analysis.sources[0].source.name
        )
        with self._lock:
            self.sweep()
            pipe = self.pipelines.get(source_name)
            if pipe is None or pipe.stopped or pipe.terminal:
                if pipe is not None and not pipe.stopped:
                    pipe.stop()  # replaced terminal pipeline: release it
                pipe = SharedPushPipeline(self, source_name, source_name)
                self.pipelines[source_name] = pipe
            tap = PushTap(pipe, session, chain[:-1])
            pipe.attach(tap)
        return tap

    # ------------------------------------------------------------- reaping
    def sweep(self, now_ms: Optional[float] = None) -> None:
        """Reap pipelines idle past the linger window (refcounted
        teardown, deferred by ``ksql.push.registry.linger.ms`` so a
        reconnecting subscriber reuses the warm pipeline)."""
        now_ms = _now_ms() if now_ms is None else now_ms
        linger = float(self.engine.effective_property(
            cfg.PUSH_REGISTRY_LINGER_MS, 5000
        ))
        with self._lock:
            for key, pipe in list(self.pipelines.items()):
                idle = pipe.idle_since_ms
                if pipe.taps or idle is None:
                    continue
                if pipe.terminal or now_ms - idle >= linger:
                    pipe.stop()
                    self.pipelines.pop(key, None)

    def stop_all(self) -> None:
        """Engine shutdown: tear every pipeline down regardless of
        refcounts or linger."""
        with self._lock:
            for pipe in self.pipelines.values():
                pipe.stop()
            self.pipelines.clear()

    # ------------------------------------------------------ overload seams
    def pressure(self) -> float:
        """Laggiest-tap ring occupancy across every shared pipeline: the
        slowest tap's lag as a fraction of its pipeline's ring size (0.0
        idle, >= 1.0 means a tap is a full ring behind and about to take
        eviction gaps).  Raw ring FILL is deliberately not a signal — the
        ring is a sliding changelog that stays full in steady state; what
        overloads the push tier is consumers falling behind within it.
        The push resource the overload monitor samples each tick."""
        worst = 0.0
        with self._lock:
            for pipe in self.pipelines.values():
                size = max(int(pipe.ring_size), 1)
                for tap in pipe.taps.values():
                    worst = max(worst, tap.lag() / size)
        return worst

    def shed_laggards(self, bound: int) -> int:
        """Overload action: disconnect every tap lagging more than
        ``bound`` rows (0 = one full ring) behind its shared pipeline,
        with a TERMINAL gap marker naming overload — a shed subscriber
        sees an explicit close on the wire, never a silently stalled
        stream.  Returns the number of taps disconnected."""
        victims = []
        with self._lock:
            for pipe in self.pipelines.values():
                limit = int(bound) if bound > 0 else int(pipe.ring_size)
                for tap in list(pipe.taps.values()):
                    lag = tap.lag()
                    if lag > limit:
                        victims.append((pipe, tap, lag, limit))
        # markers + closes run OUTSIDE the registry lock: _enqueue_gap
        # takes the session lock and close() re-enters the registry lock
        # via detach — keep the acquisition order one lock at a time
        for pipe, tap, lag, limit in victims:
            marker = {
                "queryId": tap.session.id,
                "pipeline": pipe.id,
                "terminal": True,
                "overload": True,
                "lag": lag,
                "error": (
                    f"tap shed by the overload manager: {lag} rows behind "
                    f"the shared ring exceeds the overload lag bound "
                    f"({limit}); reconnect when pressure clears"
                ),
            }
            with self._lock:
                tap.gap_markers += 1
                self.gap_markers += 1
            tap.session._enqueue_gap(marker)
            tap.close()
        return len(victims)

    # ------------------------------------------------------------- metrics
    def stats(self) -> Dict[str, Any]:
        """The /metrics ``push-registry`` section (JSON; prometheus_text
        renders the same dict as the fan-out gauge/counter series)."""
        with self._lock:
            taps = {key: len(p.taps) for key, p in self.pipelines.items()}
            fused_taps = sum(
                p.kernel.fused_tap_count()
                for p in self.pipelines.values()
                if p.kernel is not None
            )
            detail = {
                key: {
                    "id": p.id,
                    "mode": p.mode,
                    "backend": p.backend,
                    "taps": len(p.taps),
                    "fusedTaps": (
                        p.kernel.fused_tap_count()
                        if p.kernel is not None else 0
                    ),
                    "residualDegraded": (
                        p.kernel.degraded
                        if p.kernel is not None else None
                    ),
                    "headSeq": p.base_seq + len(p.ring),
                    "restarts": p.restart_count,
                    "terminal": p.terminal,
                }
                for key, p in self.pipelines.items()
            }
            return {
                "pipelines": len(self.pipelines),
                "taps-total": sum(taps.values()),
                "taps": taps,
                "delivered-rows-total": self.delivered_rows,
                "ring-evicted-total": self.ring_evicted,
                "gap-markers-total": self.gap_markers,
                "heals-total": self.heals,
                "residual": {
                    "fused-taps": fused_taps,
                    "host-taps": sum(taps.values()) - fused_taps,
                    "kernel-evals-total": self.residual_kernel_evals,
                    "kernel-rows-total": self.residual_kernel_rows,
                    "compile-epochs-total": self.residual_compile_epochs,
                    "degraded-total": self.residual_degraded,
                },
                "pipeline-detail": detail,
            }
