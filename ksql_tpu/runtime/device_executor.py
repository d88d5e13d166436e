"""DeviceExecutor — runs a persistent query on the XLA backend.

The engine-side adapter giving CompiledDeviceQuery (runtime/lowering.py) the
same record-at-a-time executor interface as OracleExecutor, so the engine's
poll loop can drive either backend through one seam — the analog of the
reference's ExecutionStep.build() double-dispatch into a runtime
(ksqldb-execution/.../plan/ExecutionStep.java:68 →
ksqldb-streams/.../KSPlanBuilder.java:62).

Records are deserialized with the shared source decoder, micro-batched up
to the configured batch size, stepped through the compiled device function,
and the resulting SinkEmits are written to the sink topic through the shared
SinkWriter — exactly the path oracle emissions take, so downstream queries,
pull-query materialization, and QTT observation are backend-agnostic.

Batching semantics: EMIT FINAL emission is watermark-driven inside the
device step and therefore batch-size invariant; EMIT CHANGES coalesces to
one change per key per batch, so when per-record changelog parity is
required (ksql.emit.per.record, the reference's cache-off behavior) the
executor runs with batch size 1.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

from ksql_tpu.common import faults, tracing
from ksql_tpu.common.batch import HostBatch
from ksql_tpu.compiler.jax_expr import DeviceUnsupported
from ksql_tpu.execution import steps as st
from ksql_tpu.functions.registry import FunctionRegistry
from ksql_tpu.runtime.lowering import CompiledDeviceQuery
from ksql_tpu.runtime.oracle import (
    SinkEmit,
    SinkWriter,
    StreamRow,
    _wrapped,
    decode_source_record,
)
from ksql_tpu.runtime.topics import Broker, Record


class DeviceExecutor:
    """OracleExecutor-interface adapter over the XLA backend."""

    backend = "device"

    def __init__(
        self,
        plan: st.QueryPlan,
        broker: Broker,
        registry: FunctionRegistry,
        on_error: Optional[Callable[[str, Exception], None]] = None,
        emit_callback: Optional[Callable[[SinkEmit], None]] = None,
        batch_size: int = 4096,
        per_record: bool = True,
        store_capacity: int = 1 << 17,
        sliced: Optional[bool] = None,
        slice_ring_max: int = 512,
    ):
        self.plan = plan
        self.broker = broker
        self.on_error = on_error or (lambda expr, e: None)
        # per-emit callback; where it carries a ``block`` attribute (the
        # engine's on_emit does), that is its twin for a whole emission
        # block, which _dispatch_emits calls when nothing wants the emits
        # one at a time
        self.emit_callback = emit_callback
        # batch-granularity emit hook (fused tap residuals): called once
        # per decoded emission batch, before the per-emit callback fan-out
        # (the engine wires it to the handle's push_batch_listeners)
        self.batch_emit_callback = None
        # some plan shapes require per-record stepping regardless of the
        # engine's batched default: fk joins (a right change fans out
        # store-wide) and self-joins (record-interleaved sides)
        per_record = per_record or _needs_per_record(plan)
        self.device = CompiledDeviceQuery(
            plan,
            registry,
            capacity=1 if (per_record and not _is_suppress(plan)) else batch_size,
            store_capacity=store_capacity,
            sliced=sliced,
            slice_ring_max=slice_ring_max,
        )
        # batched mode double-buffers: emission decode lags one batch so
        # host ingest overlaps device compute (flushed every drain tick)
        self.device.pipeline = not per_record and not _is_suppress(plan)
        # HAVING over an EMIT CHANGES table emits retractions on device via
        # the per-slot hpass verdict column (lowering._emit_agg)
        self.source_step = self.device.source
        self.table_step = self.device.table_source  # join right side or None
        self.right_step = self.device.right_source  # ss-join right or None
        if (
            self.right_step is not None
            and self.right_step.topic == self.source_step.topic
            and self.device.capacity > 1
        ):
            # self-join parity needs record-interleaved left/right steps
            raise DeviceUnsupported("batched self-join on device")
        self.sink_writer = SinkWriter(self.device.sink, broker, self.on_error)
        self._native_fields = self._native_ingest_spec()
        # rows decoded by the C++ tier, keyed by source format label
        # (surfaced as ksql_native_ingest_rows_total{format})
        self.native_ingest_rows: Dict[str, int] = {}
        self._raw: List[Record] = []
        self._rows: List[dict] = []
        self._ts: List[int] = []
        self._parts: List[int] = []
        self._offsets: List[int] = []
        # per-probe table-side buffers + topic -> probe-index routing
        self._tbuf: List[dict] = [
            {"rows": [], "ts": [], "del": [], "parts": [], "offs": []}
            for _ in self.device.join_chain
        ]
        self._join_topics = {
            js.table_source.topic: i
            for i, js in enumerate(self.device.join_chain)
        }
        self._rrows: List[dict] = []
        self._rts: List[int] = []
        self._rparts: List[int] = []
        self._roffs: List[int] = []
        self._changes: List[tuple] = []  # table-mode (key, old, new, ts)
        # table-table join: change buffer + topic -> side routing
        self._tt_buf: List[tuple] = []
        self._tt_topics = {}
        if self.device.tt_join is not None:
            self._tt_topics = {
                self.device.tt_left_source.topic: "l",
                self.device.tt_right_source.topic: "r",
            }
        self._fk_topics = {}
        if self.device.fk_join is not None:
            if self.device.capacity > 1:
                # a right change fans out store-wide: per-record only
                raise DeviceUnsupported("batched fk join on device")
            self._fk_topics = {
                self.device.fk_left_source.topic: "l",
                self.device.fk_right_source.topic: "r",
            }
        self.stream_time = -(2 ** 63)
        # records whose device step ran but whose emissions are still held
        # by the pipeline double-buffer (decoded next batch / at drain) —
        # those records are NOT durable yet for commit-point purposes
        self._pipeline_pending = 0

    # -------------------------------------------------------- epoch layer
    def pending_records(self) -> int:
        """Records handed to process() whose effects are not yet durable:
        host-buffered micro-batch rows plus (in pipeline mode) the batch
        whose emissions the double-buffer still holds.  The engine's
        per-record commit points only advance past records NOT counted
        here, so a mid-batch crash replays exactly the non-durable tail."""
        n = (len(self._raw) + len(self._rows) + len(self._changes)
             + len(self._tt_buf) + len(self._rrows))
        n += sum(len(b["rows"]) for b in self._tbuf)
        return n + self._pipeline_pending

    def _pipelines_held(self) -> bool:
        """True when the device's double-buffer actually defers emission
        decode — process_arrays with pipeline on, minus the paths that
        return their own emits synchronously (suppress disables the flag;
        session and ss-join steps bypass the hold)."""
        d = self.device
        return bool(
            getattr(d, "pipeline", False)
            and not getattr(d, "session", False)
            and d.ss_join is None
        )

    @property
    def record_synchronous(self) -> bool:
        """True when every record is fully through the device (emissions
        produced) before its process() returns — per-record micro-batches
        without pipelining.  Commit points are then per record, and a
        poison record is attributable to the exact process() call."""
        return (
            self.device.capacity == 1
            and not getattr(self.device, "pipeline", False)
        )

    @property
    def stateful(self) -> bool:
        """True when device state could double-count on replay (the engine
        then refuses in-place poison skips: device stores cannot roll back
        one record, so the poison path is replay-without-record)."""
        d = self.device
        return bool(
            d.agg is not None or d.join is not None or d.ss_join is not None
            or d.tt_join is not None or d.fk_join is not None
            or d.join_chain or d.table_mode or d.table_agg
        )

    # ----------------------------------------------------------- tracing
    def _device_step(self, fn, *args, **kw):
        """Run one device-step entry under the flight recorder, splitting
        jit-compile ticks from cache-hit executes: if the device's jit
        cache grew during the call, the wall time was dominated by
        trace+compile (``device.compile``, jit_miss count); otherwise it
        was pure dispatch+execute (``device.execute``, jit_hit)."""
        tr = tracing.active()
        if tr is None:
            return fn(*args, **kw)
        entries = getattr(self.device, "jit_cache_entries", None)
        before = entries() if entries is not None else 0
        with tr.span("device.execute") as sp:
            try:
                return fn(*args, **kw)
            finally:
                missed = (entries() if entries is not None else 0) - before
                if missed > 0:
                    sp.name = "device.compile"
                    tr.counter(sp.name, jit_miss=missed)
                else:
                    tr.counter(sp.name, jit_hit=1)

    # ------------------------------------------------------------- interface
    def process(self, topic: str, record: Record) -> List[SinkEmit]:
        """Buffer one record; runs the device step when the micro-batch is
        full.  The engine calls drain() at the end of each poll tick.

        With a join, stream and table records interleave: a topic switch
        flushes the other side's buffer first, so device steps observe the
        same record order the row oracle would."""
        if faults.armed():
            # device-dispatch seam: a raise here models an XLA dispatch /
            # transfer failure and exercises the engine's restart path
            faults.fault_point("device.dispatch", self.plan.query_id)
        if topic in self._join_topics:
            idx = self._join_topics[topic]
            step = self.device.join_chain[idx].table_source
            ev = decode_source_record(step, record, self.on_error)
            if ev is None:
                return []
            self.stream_time = max(self.stream_time, ev.ts)
            out = self._run_batch() if self._rows else []
            schema = step.schema
            if ev.new is not None:
                row = ev.new
            else:  # tombstone: key columns only
                row = {c.name: None for c in schema.columns()}
                for c, v in zip(schema.key_columns, ev.key):
                    row[c.name] = v
            buf = self._tbuf[idx]
            buf["rows"].append(row)
            buf["ts"].append(ev.ts)
            buf["del"].append(ev.new is None)
            buf["parts"].append(record.partition)
            buf["offs"].append(record.offset)
            if len(buf["rows"]) >= self.device.capacity:
                self._run_table_batch(idx)
            return out
        if self.device.fk_join is not None and topic in self._fk_topics:
            side = self._fk_topics[topic]
            ev = decode_source_record(
                self.device.fk_left_source if side == "l"
                else self.device.fk_right_source,
                record, self.on_error,
            )
            if ev is None:
                return []
            self.stream_time = max(self.stream_time, ev.ts)
            return self._run_fk_change(side, ev, record)
        if self.device.tt_join is not None and topic in self._tt_topics:
            side = self._tt_topics[topic]
            ev = decode_source_record(
                self.device.tt_left_source if side == "l"
                else self.device.tt_right_source,
                record, self.on_error,
            )
            if ev is None:
                return []
            self.stream_time = max(self.stream_time, ev.ts)
            out2: List[SinkEmit] = []
            if self._tt_buf and self._tt_buf[0][0] != side:
                out2.extend(self._run_tt_batch())  # keep cross-side order
            self._tt_buf.append(
                (side, ev.key, ev.old, ev.new, ev.ts,
                 record.partition, record.offset)
            )
            if len(self._tt_buf) >= self.device.capacity:
                out2.extend(self._run_tt_batch())
            return out2
        out: List[SinkEmit] = []
        if (
            (self.device.table_mode or self.device.table_agg)
            and topic == self.source_step.topic
        ):
            ev = decode_source_record(self.source_step, record, self.on_error)
            if ev is None:
                return []
            # event-time watermark advance for table-mode sources (the
            # stream-row paths below already do this at decode)
            self.stream_time = max(self.stream_time, ev.ts)
            self._changes.append(
                (ev.key, ev.old, ev.new, ev.ts, record.partition, record.offset)
            )
            if len(self._changes) >= self.device.capacity:
                return self._run_change_batch()
            return []
        if topic == self.source_step.topic:
            if (
                self._native_fields is not None
                and isinstance(record.value, (str, bytes))
            ):
                # native tier: defer decode, batch JSON -> arrays in C++
                # (stream time advances at parse, matching decode-time
                # advance on the per-record path)
                if self._rows:  # keep arrival order across decode tiers
                    out.extend(self._run_batch())
                self._raw.append(record)
                if len(self._raw) >= self.device.capacity:
                    out.extend(self._run_native_batch())
                return out
            if self._raw:
                # a non-JSON-payload record (tombstone, dict): keep order
                out.extend(self._run_native_batch())
            ev = decode_source_record(self.source_step, record, self.on_error)
            if (
                ev is not None
                and isinstance(ev, StreamRow)
                and ev.row is None
                and self._passes_null_rows()
            ):
                # a repartition recomputes the key from the key columns
                # alone (SelectKeyNode null-row semantics)
                out.extend(self._run_batch() if self._rows else [])
                key = ev.key
                for op in self.device.pre_ops:
                    if isinstance(op, st.StreamSelectKey):
                        src = {
                            c.name: v
                            for c, v in zip(
                                op.source.schema.key_columns, key or ()
                            )
                        }
                        key = tuple(
                            f(src) for f in self._null_keyers(op)
                        )
                emit = SinkEmit(key, None, ev.ts, ev.window)
                # per record: no emit.dispatch span (spans are per batch)
                self._dispatch_emits([emit])
                out.append(emit)
                return out
            if ev is not None and isinstance(ev, StreamRow) and ev.row is not None:
                if any(b["rows"] for b in self._tbuf):
                    self._run_table_batch()
                if self._rrows:
                    out.extend(self._run_right_batch())
                self.stream_time = max(self.stream_time, ev.ts)
                if self.device.flatmap is not None:
                    # UDTF explode runs host-side per record; the device
                    # pipeline consumes the exploded rows
                    for row in self._explode(ev):
                        self._rows.append(row)
                        self._ts.append(ev.ts)
                        self._parts.append(record.partition)
                        self._offsets.append(record.offset)
                else:
                    row = ev.row
                    if self.device.windowed_source and ev.window is not None:
                        # windowed-topic re-import: the key's window rides
                        # the batch as WINDOWSTART/WINDOWEND value columns
                        row = dict(row)
                        row["WINDOWSTART"], row["WINDOWEND"] = ev.window
                    self._rows.append(row)
                    self._ts.append(ev.ts)
                    self._parts.append(record.partition)
                    self._offsets.append(record.offset)
                if len(self._rows) >= self.device.capacity:
                    out.extend(self._run_batch())
        if self.right_step is not None and topic == self.right_step.topic:
            ev = decode_source_record(self.right_step, record, self.on_error)
            if ev is not None and isinstance(ev, StreamRow) and ev.row is not None:
                if self._rows:
                    out.extend(self._run_batch())
                self.stream_time = max(self.stream_time, ev.ts)
                self._rrows.append(ev.row)
                self._rts.append(ev.ts)
                self._rparts.append(record.partition)
                self._roffs.append(record.offset)
                if len(self._rrows) >= self.device.capacity:
                    out.extend(self._run_right_batch())
        return out

    def _passes_null_rows(self) -> bool:
        """Null-value stream records pass filter-less projections through
        unchanged (oracle SelectNode), dispatched at once; filters,
        aggregations and joins drop them."""
        dev = self.device
        return (
            dev.agg is None and dev.join is None and dev.ss_join is None
            and not any(isinstance(op, st.StreamFilter) for op in dev.pre_ops)
        )

    def buffer_block(self, topic: str, records: List[Record]) -> int:
        """``process(topic, r)`` for the leading records of one topic's
        polled run that it would only buffer: each would have returned
        ``[]``, run no device step, dispatched no emit, flushed no other
        buffer and raised nothing.  Returns how many were taken (0 when
        unsure); the caller goes on from there through ``process``, which
        keeps the record that fills a micro-batch, so the flush and its
        commit point fall where they always did.  The plan-shape reads
        happen once a block, not once a record."""
        dev = self.device
        cap = dev.capacity
        if (
            cap <= 1  # record-synchronous: every record is a device step
            or faults.armed()  # device.dispatch fires per record
            or _wrapped(self, "process", DeviceExecutor)
            or topic != self.source_step.topic
            or topic in self._join_topics
            or dev.fk_join is not None or dev.tt_join is not None
            or dev.table_mode or dev.table_agg
            or self._rrows or any(b["rows"] for b in self._tbuf)
        ):
            return 0
        if self._native_fields is not None:
            # native tier: the payloads wait in _raw for the C++ decoder
            if self._rows:
                return 0
            run = records[: max(cap - 1 - len(self._raw), 0)]
            for i, r in enumerate(run):
                if not isinstance(r.value, (str, bytes)):
                    run = run[:i]  # a tombstone or dict flushes _raw first
                    break
            self._raw.extend(run)
            return len(run)
        step = self.source_step
        if (
            self._raw or dev.flatmap is not None
            or not isinstance(step, st.StreamSource)
            or self._passes_null_rows()
        ):
            # a UDTF explode and a windowed re-import stay per record, and
            # so does a plan that passes null-value records through: they
            # dispatch at once
            return 0
        # Python tier, stream side: decode into the row buffers
        rows, on_error = self._rows, self.on_error
        stream_time = self.stream_time
        taken = 0
        for r in records:
            if len(rows) >= cap - 1:
                break  # this record may fill the batch
            ev = decode_source_record(step, r, on_error)
            taken += 1
            if ev is None or ev.row is None:
                continue  # dropped, as process() drops it
            if ev.ts > stream_time:
                stream_time = ev.ts
            rows.append(ev.row)
            self._ts.append(ev.ts)
            self._parts.append(r.partition)
            self._offsets.append(r.offset)
        self.stream_time = stream_time
        return taken

    # --------------------------------------------------- native ingest tier
    def _native_ingest_spec(self):
        return native_ingest_fields(self.device)

    def _run_native_batch(self) -> List[SinkEmit]:
        """Batch decode in C++ straight into device arrays.  Rows the
        native parser can't take replay through the Python per-record
        decoder (identical error/null semantics); the surrounding GOOD
        rows keep their columnar arrays — the chunk is walked as
        contiguous good/bad segments in arrival order, so emission order
        matches the pure-Python path exactly."""
        import numpy as np

        from ksql_tpu import native

        records, self._raw = self._raw, []
        dev = self.device
        cap = dev.capacity
        out: List[SinkEmit] = []
        tr = tracing.active()
        for s in range(0, len(records), cap):
            chunk = records[s : s + cap]
            n = len(chunk)
            # the native tier IS the good rows' deserialize: batch payloads
            # -> columnar arrays in C++, one span a chunk whose ``n`` counts
            # the rows (the per-record path accumulates the same stage
            # inside decode_source_record)
            with tracing.span("deserialize", cpu=True) as sp:
                try:
                    data, valid, row_ok, learned = native.parse_batch(
                        [r.value for r in chunk], self._native_fields
                    )
                except Exception:  # noqa: BLE001 — e.g. invalid UTF-8 in a
                    # learned string: replay the chunk through the
                    # per-record decoder, which drops exactly the
                    # offending records
                    data, valid, learned = {}, {}, []
                    row_ok = np.zeros(n, bool)
                dev.dictionary.learn_pairs(learned)
                if tr is not None:
                    sp.n = int(row_ok.sum())
            i = 0
            while i < n:
                j = i + 1
                good = bool(row_ok[i])
                while j < n and bool(row_ok[j]) == good:
                    j += 1
                if good:
                    columns = {
                        name: (d[i:j], valid[name][i:j])
                        for name, d in data.items()
                    }
                    out.extend(self._native_segment(chunk[i:j], columns))
                else:
                    for r in chunk[i:j]:
                        ev = decode_source_record(
                            self.source_step, r, self.on_error
                        )
                        if (
                            ev is not None
                            and isinstance(ev, StreamRow)
                            and ev.row is not None
                        ):
                            self.stream_time = max(self.stream_time, ev.ts)
                            self._rows.append(ev.row)
                            self._ts.append(ev.ts)
                            self._parts.append(r.partition)
                            self._offsets.append(r.offset)
                    out.extend(self._run_batch() if self._rows else [])
                i = j
        return out

    def _native_segment(self, chunk, columns) -> List[SinkEmit]:
        """Device-step one contiguous run of natively decoded records.
        ``columns`` holds the segment's (data, valid) slices per parsed
        value field; key columns are decoded (vectorized when the key
        shape allows) and merged here."""
        from ksql_tpu.common.batch import encode_column

        dev = self.device
        n = len(chunk)
        with tracing.span("batch.assemble"):
            key_cols = list(self.source_step.schema.key_columns)
            self.stream_time = max(
                self.stream_time, max(r.timestamp for r in chunk)
            )
            label = self._native_fields["format"]
            self.native_ingest_rows[label] = (
                self.native_ingest_rows.get(label, 0) + n
            )
            spec_names = {spec.name for spec in dev.layout.specs}
            columns = {
                name: cv for name, cv in columns.items() if name in spec_names
            }
            if key_cols:
                decoded = self._vectorized_keys(chunk, key_cols)
                if decoded is None:
                    decoded = self._per_record_keys(chunk, key_cols)
                for c in key_cols:
                    if c.name not in spec_names:
                        continue
                    kvals, kok = decoded[c.name]
                    enc = encode_column(kvals, kok, c.type)
                    if enc.dictionary is not None:
                        dev.dictionary.learn(enc.hashes64, enc.dictionary)
                        kd = enc.hashes64[enc.data]
                    else:
                        kd = enc.data
                    columns[c.name] = (kd, kok)
            arrays = self._native_arrays(
                n, columns,
                [r.timestamp for r in chunk],
                [r.offset for r in chunk],
                [r.partition for r in chunk],
            )
        emits = self._native_step(arrays)
        if self._pipelines_held():
            # the double-buffer now holds THIS segment's emissions (the
            # returned emits belong to the previous batch)
            self._pipeline_pending = n
        self._dispatch(emits)
        return emits

    def _native_arrays(self, n, columns, timestamps, offsets, partitions):
        """The step's input arrays from a natively decoded columnar
        segment.  ``assemble`` COPIES the slices into fresh padded buffers,
        so the decoder's output is never aliased into donated jit state.
        The distributed executor overrides this with the mesh lane split."""
        return self.device.layout.assemble(
            n, columns, timestamps, offsets=offsets, partitions=partitions
        )

    def _native_step(self, arrays) -> List[SinkEmit]:
        return self._device_step(self.device.process_arrays, arrays)

    def _vectorized_keys(self, chunk, key_cols):
        """Columnar key decode for the common shape — ONE scalar key
        column under a non-positional format, where deserialize_key
        reduces to _coerce(payload, type) per record.  When every key in
        the segment is already the column's host type (or None) the
        coercion is the identity and the whole loop collapses to an
        object-array build; anything else returns None and the caller
        runs the exact per-record path."""
        import numpy as np

        from ksql_tpu.common.types import SqlBaseType as B

        if len(key_cols) != 1:
            return None
        kf = str(self.source_step.formats.key_format or "").upper()
        if kf in ("DELIMITED", "PROTOBUF", "PROTOBUF_NOSR"):
            return None
        c = key_cols[0]
        keys = [r.key for r in chunk]
        kinds = set(map(type, keys))
        kinds.discard(type(None))
        base = c.type.base
        if base == B.STRING:
            identity = kinds <= {str}
        elif base in (B.BIGINT, B.INTEGER):
            # bool is a distinct type() from int, so boolean keys (which
            # _coerce rejects for int columns) never take the fast path
            identity = kinds <= {int}
        elif base == B.DOUBLE:
            identity = kinds <= {float}
        else:
            return None
        if not identity:
            return None
        karr = np.empty(len(keys), object)
        karr[:] = keys
        kok = np.array([k is not None for k in keys], bool)
        return {c.name: (karr, kok)}

    def _per_record_keys(self, chunk, key_cols):
        """Record-at-a-time key decode (multi-column, positional formats,
        cross-type coercions) — exact deserialize_key semantics."""
        import numpy as np

        from ksql_tpu.serde import formats as fmt

        n = len(chunk)
        kvals = {c.name: np.empty(n, object) for c in key_cols}
        kok = {c.name: np.zeros(n, bool) for c in key_cols}
        for i, r in enumerate(chunk):
            if r.key is None:
                continue
            row = fmt.deserialize_key(
                self.source_step.formats.key_format, r.key, key_cols,
                delimiter=getattr(
                    self.source_step.formats, "key_delimiter", None
                ),
            )
            for c in key_cols:
                v = row.get(c.name)
                kvals[c.name][i] = v
                kok[c.name][i] = v is not None
        return {c.name: (kvals[c.name], kok[c.name]) for c in key_cols}

    def _explode(self, ev: StreamRow) -> List[dict]:
        """Host flat-map: the ops below the StreamFlatMap plus the UDTF
        expansion itself, via the oracle's nodes (KudtfFlatMapper analog)."""
        chain = getattr(self, "_flatmap_chain", None)
        if chain is None:
            from ksql_tpu.runtime.oracle import (
                Compiler,
                FilterNode,
                FlatMapNode,
                SelectKeyNode,
                SelectNode,
            )

            compiler = Compiler(self.device.registry, self.on_error)

            def mk(op):
                if isinstance(op, st.StreamFilter):
                    return FilterNode(op, compiler, False)
                if isinstance(op, st.StreamSelect):
                    return SelectNode(op, compiler)
                if isinstance(op, st.StreamSelectKey):
                    return SelectKeyNode(op, compiler)
                return FlatMapNode(op, compiler)

            chain = [
                mk(op)
                for op in (*self.device.flatmap_pre_ops, self.device.flatmap)
            ]
            self._flatmap_chain = chain
        events = [ev]
        for node in chain:
            nxt = []
            for e in events:
                nxt.extend(node.receive(0, e))
            events = nxt
        return [e.row for e in events if e.row is not None]

    def _null_keyers(self, op):
        """Compiled key expressions for null-row repartition passthrough.
        Expressions touching value columns yield a null key component for
        null-value rows (oracle SelectKeyNode / PartitionByParamsFactory)."""
        cache = getattr(self, "_null_keyer_cache", None)
        if cache is None:
            cache = self._null_keyer_cache = {}
        fns = cache.get(id(op))
        if fns is None:
            from ksql_tpu.execution.expressions import referenced_columns
            from ksql_tpu.runtime.oracle import Compiler

            compiler = Compiler(self.device.registry, self.on_error)
            key_names = {c.name for c in op.source.schema.key_columns}
            fns = [
                (
                    compiler.expr(e, op.source.schema)
                    if all(n in key_names for n in referenced_columns(e))
                    else (lambda src: None)
                )
                for e in op.key_expressions
            ]
            cache[id(op)] = fns
        return fns

    def _run_change_batch(self) -> List[SinkEmit]:
        import numpy as np

        changes = self._changes
        self._changes = []
        schema = self.source_step.schema
        out: List[SinkEmit] = []
        cap = self.device.capacity
        for i in range(0, len(changes), cap):
            chunk = changes[i : i + cap]
            keys = [c[0] for c in chunk]
            ts = [c[3] for c in chunk]
            parts = [c[4] for c in chunk]
            offs = [c[5] for c in chunk]
            has_old = np.array([c[1] is not None for c in chunk], bool)
            has_new = np.array([c[2] is not None for c in chunk], bool)
            new_hb = HostBatch.from_rows(
                schema, [c[2] or {} for c in chunk], timestamps=ts,
                partitions=parts, offsets=offs,
            )
            old_hb = HostBatch.from_rows(
                schema, [c[1] or {} for c in chunk], timestamps=ts,
                partitions=parts, offsets=offs,
            )
            emits = self._device_step(
                self.device.process_table_changes,
                new_hb, old_hb, keys, has_new, has_old, ts,
            )
            self._dispatch(emits)
            out.extend(emits)
        return out

    @staticmethod
    def _change_batches(schema, changes):
        """(new_hb, old_hb, deletes, has_old) for table-change tuples of
        (key, old, new, ts, partition, offset); delete rows become
        key-only new rows so the change key always probes."""
        import numpy as np

        def as_row(key, row):
            if row is not None:
                return row
            r = {c.name: None for c in schema.columns()}
            for c, v in zip(schema.key_columns, key):
                r[c.name] = v
            return r

        ts = [c[3] for c in changes]
        parts = [c[4] for c in changes]
        offs = [c[5] for c in changes]
        new_hb = HostBatch.from_rows(
            schema, [as_row(c[0], c[2]) for c in changes], timestamps=ts,
            partitions=parts, offsets=offs,
        )
        old_hb = HostBatch.from_rows(
            schema, [c[1] or {} for c in changes], timestamps=ts,
            partitions=parts, offsets=offs,
        )
        deletes = np.array([c[2] is None for c in changes], np.int32)
        has_old = np.array([c[1] is not None for c in changes], bool)
        return new_hb, old_hb, deletes, has_old

    def _run_fk_change(self, side: str, ev, record: Record) -> List[SinkEmit]:
        """One fk-join table change through the device (per-record)."""
        src = (
            self.device.fk_left_source if side == "l"
            else self.device.fk_right_source
        )
        new_hb, old_hb, deletes, has_old = self._change_batches(
            src.schema,
            [(ev.key, ev.old, ev.new, ev.ts, record.partition, record.offset)],
        )
        emits = self._device_step(
            self.device.process_fk, side, new_hb, old_hb, deletes, has_old
        )
        self._dispatch(emits)
        return emits

    def _run_tt_batch(self) -> List[SinkEmit]:
        """One single-side batch of table-table-join changes through the
        device (rows carry their key columns; deletes are key-only)."""
        import numpy as np

        buf, self._tt_buf = self._tt_buf, []
        out: List[SinkEmit] = []
        cap = self.device.capacity
        for i in range(0, len(buf), cap):
            chunk = buf[i : i + cap]
            side = chunk[0][0]
            src = (
                self.device.tt_left_source if side == "l"
                else self.device.tt_right_source
            )
            new_hb, old_hb, deletes, has_old = self._change_batches(
                src.schema, [c[1:] for c in chunk]
            )
            emits = self._device_step(
                self.device.process_tt, side, new_hb, old_hb, deletes, has_old
            )
            self._dispatch(emits)
            out.extend(emits)
        return out

    def drain(self) -> List[SinkEmit]:
        """Flush the partial micro-batches (end of a poll tick)."""
        out: List[SinkEmit] = []
        if self._raw:
            out.extend(self._run_native_batch())
        if self._tt_buf:
            out.extend(self._run_tt_batch())
        if self._changes:
            out.extend(self._run_change_batch())
        if any(b["rows"] for b in self._tbuf):
            self._run_table_batch()
        if self._rrows:
            out.extend(self._run_right_batch())
        if self._rows:
            out.extend(self._run_batch())
        if self.device.pipeline:
            emits = self._device_step(self.device.flush_pipeline)
            self._pipeline_pending = 0
            self._dispatch(emits)
            out.extend(emits)
        if self.right_step is not None:
            # record-driven time advance: expire join buffers, emitting
            # deferred null-pads (oracle _advance_time after each record)
            emits = self._device_step(self.device.ss_expire_host)
            self._dispatch(emits)
            out.extend(emits)
        return out

    def flush_time(self, stream_time: int) -> List[SinkEmit]:
        """Advance event time explicitly (end-of-input flush for EMIT
        FINAL)."""
        out = self.drain()
        self.stream_time = max(self.stream_time, stream_time)
        emits = self._device_step(self.device.flush, self.stream_time)
        self._dispatch(emits)
        out.extend(emits)
        return out

    # -------------------------------------------------------------- internal
    def _run_table_batch(self, idx: int = None) -> None:
        import numpy as np

        indices = range(len(self._tbuf)) if idx is None else (idx,)
        cap = self.device.capacity
        for j in indices:
            buf = self._tbuf[j]
            if not buf["rows"]:
                continue
            schema = self.device.join_chain[j].table_source.schema
            rows, ts, dels = buf["rows"], buf["ts"], buf["del"]
            parts, offs = buf["parts"], buf["offs"]
            self._tbuf[j] = {
                "rows": [], "ts": [], "del": [], "parts": [], "offs": []
            }
            for i in range(0, len(rows), cap):
                with tracing.span("batch.assemble"):
                    hb = HostBatch.from_rows(
                        schema, rows[i : i + cap], timestamps=ts[i : i + cap],
                        partitions=parts[i : i + cap],
                        offsets=offs[i : i + cap],
                    )
                self._device_step(
                    self.device.process_table,
                    hb, np.asarray(dels[i : i + cap], bool), idx=j,
                )

    def _run_right_batch(self) -> List[SinkEmit]:
        schema = self.right_step.schema
        rows, ts = self._rrows, self._rts
        parts, offs = self._rparts, self._roffs
        self._rrows, self._rts = [], []
        self._rparts, self._roffs = [], []
        out: List[SinkEmit] = []
        cap = self.device.capacity
        for i in range(0, len(rows), cap):
            hb = HostBatch.from_rows(
                schema, rows[i : i + cap], timestamps=ts[i : i + cap],
                partitions=parts[i : i + cap], offsets=offs[i : i + cap],
            )
            emits = self._device_step(self.device.process_ss, hb, "r")
            self._dispatch(emits)
            out.extend(emits)
        return out

    def _run_batch(self) -> List[SinkEmit]:
        schema = self.device.device_source_schema()
        rows, ts = self._rows, self._ts
        parts, offs = self._parts, self._offsets
        self._rows, self._ts, self._parts, self._offsets = [], [], [], []
        out: List[SinkEmit] = []
        cap = self.device.capacity
        for i in range(0, len(rows), cap):
            with tracing.span("batch.assemble"):
                hb = HostBatch.from_rows(
                    schema,
                    rows[i : i + cap],
                    timestamps=ts[i : i + cap],
                    partitions=parts[i : i + cap],
                    offsets=offs[i : i + cap],
                )
            emits = self._device_step(self.device.process, hb)
            if self._pipelines_held():
                # pipelined: the returned emits are the PREVIOUS batch's;
                # this chunk's records stay non-durable until the next
                # process/flush decodes them
                self._pipeline_pending = len(rows[i : i + cap])
            self._dispatch(emits)
            out.extend(emits)
        return out

    def _dispatch(self, emits: List[SinkEmit]) -> None:
        if not emits:
            return
        with tracing.span("emit.dispatch"):
            self._dispatch_emits(emits)

    def _dispatch_emits(self, emits: List[SinkEmit]) -> None:
        if self.batch_emit_callback is not None:
            # batch boundary first: push pipelines stash the (possibly
            # device-resident) columnar block so their residual kernel can
            # evaluate it before the rows fan out one at a time below
            self.batch_emit_callback(emits)
        # block-batched sink encode: serialize the emission block's values
        # column-at-a-time up front
        writer = self.sink_writer
        precoded = writer.encode_batch(emits)
        callback = self.emit_callback
        # the callback's twin for a whole block (the engine hangs it on
        # its on_emit); it declines, untouched, when a subscriber wants
        # to see each emit before the next is produced
        block_callback = getattr(callback, "block", None)
        block_rows = 0
        if writer.block_ready(precoded) and (
            callback is None
            or (block_callback is not None
                and _timed_block_callback(block_callback, emits))
        ):
            # block dispatch: nothing observable asks for per-emit
            # treatment, so the callbacks ran in one pass and the sink
            # takes one append; a failed append entered nothing and
            # leaves the block to the per-emit produce and its retries
            if writer.produce_block(emits, precoded):
                block_rows = len(emits)
            else:
                for e, v in zip(emits, precoded):
                    writer.produce(e, precoded=v)
        # else the per-emit loop keeps its exact interleaving (callbacks,
        # emit_seq ordinals, fault context, retries) and just skips the
        # row serializer where precoded
        elif precoded is None:
            for e in emits:
                if callback is not None:
                    callback(e)
                writer.produce(e)
        else:
            for e, v in zip(emits, precoded):
                if callback is not None:
                    callback(e)
                writer.produce(e, precoded=v)
        tracing.counter("emit.dispatch", rows=len(emits), block_rows=block_rows)


def _timed_block_callback(block_callback, emits: List[SinkEmit]) -> bool:
    """The block callback's one pass over ``emits`` as the timed stage
    ``emit.callbacks``; where it declines, it has run nothing and books
    nothing."""
    tr = tracing.active()
    if tr is None:
        return block_callback(emits)
    t0 = time.perf_counter()
    took = block_callback(emits)
    if took:
        tr.stage("emit.callbacks", time.perf_counter() - t0)
    return took


class DistributedDeviceExecutor(DeviceExecutor):
    """DeviceExecutor variant that drives a DistributedDeviceQuery over the
    device mesh — the engine-facing productization of parallel/distributed.

    The record-at-a-time executor interface is inherited unchanged; the
    micro-batch entry points route through the sharded runner, which splits
    each batch round-robin into per-shard lanes (data parallelism), crosses
    rows to their key-owner shard over one ICI all-to-all (the
    repartition-topic analog), and folds into device-sharded state.  Plans
    the distribution layer does not cover yet raise DeviceUnsupported at
    construction, and the engine's fallback ladder drops them to the
    single-device DeviceExecutor (NOT the oracle — see _build_executor)."""

    backend = "distributed"

    def __init__(
        self,
        plan: st.QueryPlan,
        broker: Broker,
        registry: FunctionRegistry,
        on_error: Optional[Callable[[str, Exception], None]] = None,
        emit_callback: Optional[Callable[[SinkEmit], None]] = None,
        batch_size: int = 4096,
        per_record: bool = False,
        store_capacity: int = 1 << 17,
        n_shards: Optional[int] = None,
        sliced: Optional[bool] = None,
        slice_ring_max: int = 512,
    ):
        from ksql_tpu.parallel.distributed import DistributedDeviceQuery
        from ksql_tpu.parallel.mesh import make_mesh

        if per_record:
            raise DeviceUnsupported(
                "per-record emission cadence is not distributed (micro-batch "
                "lanes are the unit of mesh parallelism); run single-device"
            )
        if _needs_per_record(plan):
            # fk joins / self-joins auto-select record-synchronous stepping
            # on the single-device executor; a round-robin lane split would
            # break their record-interleaved semantics
            raise DeviceUnsupported(
                "plan requires per-record stepping (fk join / self join); "
                "not distributed — run single-device"
            )
        # distribution gaps derivable from the plan alone are rejected
        # BEFORE the single-device lowering below — otherwise every such
        # statement pays the full CompiledDeviceQuery construction twice
        # (once thrown away here, once in the engine's fallback rung)
        _reject_undistributable_plan(plan)
        mesh = make_mesh(n_shards)
        nd = int(len(mesh.devices.reshape(-1)))
        # ksql.batch.capacity is the HOST micro-batch bound: the mesh splits
        # it into n_shards lanes, so the per-shard static shape shrinks
        per_shard = max(1, batch_size // nd)
        super().__init__(
            plan, broker, registry,
            on_error=on_error, emit_callback=emit_callback,
            batch_size=per_shard, per_record=False,
            store_capacity=store_capacity,
            sliced=sliced, slice_ring_max=slice_ring_max,
        )
        compiled = self.device
        compiled.pipeline = False  # the sharded runner decodes per step
        self.device = DistributedDeviceQuery(compiled, mesh)
        # the C++ ingest tier stays engaged on the mesh: _native_arrays
        # routes decoded columns through the sharded runner's own
        # round-robin lane split (split_columns), so the bypass the
        # engine counted through PR 16 no longer exists for eligible plans
        self.native_ingest_bypassed = False

    def _native_arrays(self, n, columns, timestamps, offsets, partitions):
        # mesh-aware ingest: the sharded runner splits the decoder's column
        # slices into per-shard lanes and assembles each lane at the
        # per-shard static shape (the single-device whole-batch assemble
        # would bake the wrong capacity).  split_columns copies every
        # slice into fresh lane buffers, keeping decoder output out of
        # donated jit state.
        return self.device.split_columns(
            n, columns, timestamps, offsets, partitions
        )

    def _native_step(self, arrays) -> List[SinkEmit]:
        return self._device_step(self.device.process_encoded, arrays)

    def suspect_shard(self) -> Optional[int]:
        """Shard lane whose host-side dispatch section is (still) in
        flight — the engine's mesh fault domain reads it when a tick blows
        its deadline: a hang wedged inside ``mesh.shard.dispatch`` leaves
        the marker on the wedged lane, making the deadline attributable to
        ONE shard instead of the whole query."""
        return self.device.current_shard

    def shard_metrics(self) -> dict:
        """Per-shard gauges for /metrics (rows in/out, exchange volume,
        store occupancy — the shard-store observability of the tentpole)."""
        d = self.device
        return {
            "shards": d.n_shards,
            "rows-in": d.shard_rows_in.tolist(),
            "rows-out": d.shard_rows_out.tolist(),
            "exchange-rows": d.shard_exchange_rows.tolist(),
            # exchanged volume at the payload's row width (noted when the
            # step was traced: 0 until it has run; a stream-stream join's
            # two sides give the wider) — the telemetry timeline's
            # per-shard bytes series and the ksql_shard_exchange_bytes
            # Prometheus gauge
            "exchange-bytes": [
                r * max(d._exch_row_bytes.values(), default=0)
                for r in d.shard_exchange_rows.tolist()
            ],
            "store-occupancy": d.shard_store_occupancy.tolist(),
            "watermark-ms": d.shard_watermark_ms.tolist(),
        }


class FamilyMemberExecutor:
    """Executor stub for a query attached to a window-family primary.

    The member's records are consumed, deserialized, aggregated, and
    window-combined inside the PRIMARY query's shared sliced pipeline
    (CompiledDeviceQuery.attach_member); emissions arrive through the
    ``deliver`` callback the engine wired at attach time, produced to this
    member's own sink topic.  The member's own poll tick therefore only
    advances its consumer offsets — records are observed-and-dropped, since
    the primary already folded them (consuming them twice would
    double-count).

    On promotion (primary terminated), the engine rebuilds the member as a
    standalone executor: it resumes from its consumer position with FRESH
    window state — the PR-5 stateful-rebuild posture, with partially-filled
    windows re-derived from that offset forward."""

    backend = "device"
    device = None  # no compiled pipeline of its own
    stateful = False  # shared state lives (and checkpoints) on the primary
    pipeline = False

    def __init__(
        self,
        plan: st.QueryPlan,
        broker: Broker,
        primary_query_id: str,
        on_error: Optional[Callable[[str, Exception], None]] = None,
        emit_callback: Optional[Callable[[SinkEmit], None]] = None,
    ):
        self.plan = plan
        self.primary_query_id = primary_query_id
        self.on_error = on_error or (lambda expr, e: None)
        self.emit_callback = emit_callback
        sink = plan.physical_plan
        if not isinstance(sink, (st.StreamSink, st.TableSink)):
            raise DeviceUnsupported("family member plan without sink")
        self.sink_writer = SinkWriter(sink, broker, self.on_error)
        self.stream_time = -(2 ** 63)

    # thread entrypoint: called from the PRIMARY query's tick — under tick
    # supervision that is the primary's worker thread, not the thread
    # polling this member  # graftlint: entrypoint=family-delivery
    def deliver(self, emits: List[SinkEmit]) -> None:
        """Emission fan-out target the primary's device step calls with
        this member's decoded window combines (during the PRIMARY's tick)."""
        for e in emits:
            if self.emit_callback is not None:
                self.emit_callback(e)
            self.sink_writer.produce(e)

    # ---- engine poll-loop interface: observe offsets, process nothing
    def process(self, topic: str, record: Record) -> List[SinkEmit]:
        self.stream_time = max(self.stream_time, record.timestamp or 0)
        return []

    def drain(self) -> List[SinkEmit]:
        return []

    def flush_time(self, stream_time: int) -> List[SinkEmit]:
        self.stream_time = max(self.stream_time, stream_time)
        return []

    def pending_records(self) -> int:
        return 0


def native_ingest_fields(dev):
    """Decode spec for the C++ batch decoder over ``dev``
    (a CompiledDeviceQuery): a dict with ``mode`` (native.MODE_*),
    ``fields`` ((name, FT code) pairs), ``delimiter`` and a ``format``
    label for metrics — or None when the query's source needs the Python
    per-record path (unsupported format, timestamp/header extraction,
    nested/path/host-computed columns).  Module-level so the static
    backend classifier (analysis/plan_verifier) can report whether a
    distributed placement engages the native tier."""
    from ksql_tpu.common.types import SqlBaseType as B

    step = dev.source
    if (
        dev.table_mode or dev.table_agg or dev.ss_join is not None
        or dev.join is not None or dev.flatmap is not None
        or not isinstance(step, st.StreamSource)
    ):
        return None
    vf = str(step.formats.value_format).upper()
    if vf not in ("JSON", "DELIMITED"):
        return None
    if step.timestamp_column or getattr(step, "header_columns", ()):
        return None
    try:
        from ksql_tpu import native
    except Exception:  # noqa: BLE001
        return None
    if not native.available():
        return None
    code_of = {
        B.BIGINT: native.FT_BIGINT,
        B.INTEGER: native.FT_INT,
        B.DOUBLE: native.FT_DOUBLE,
        B.BOOLEAN: native.FT_BOOLEAN,
        B.STRING: native.FT_STRING,
    }
    value_cols = list(step.schema.value_columns)
    delimiter = ","
    if vf == "JSON":
        if (
            step.formats.wrap_single_values is False
            and len(value_cols) == 1
        ):
            # SerdeFeature UNWRAP_SINGLES: one bare JSON scalar per payload
            mode = native.MODE_JSON_SINGLE
        else:
            # multi-column schemas always wrap, regardless of the flag
            mode = native.MODE_JSON
    else:
        mode = native.MODE_DELIMITED
        raw = step.formats.value_delimiter
        if raw is not None:
            named = {"SPACE": " ", "TAB": "\t"}
            delimiter = named.get(str(raw).upper(), str(raw))
        if (
            len(delimiter) != 1 or not delimiter.isascii()
            or delimiter in ('"', "\n", "\r")
        ):
            return None
    key_names = {c.name for c in step.schema.key_columns}
    for spec in dev.layout.specs:
        if spec.name in key_names:
            continue
        if spec.path is not None or spec.host_fn is not None:
            return None
        if spec.sql_type.base not in code_of:
            return None
    # parse EVERY value column, not just the ones the query reads: the
    # Python decoder coerces the whole row, so a bad value in an unused
    # column must still drop the record (via the fallback replay)
    fields = []
    for c in value_cols:
        code = code_of.get(c.type.base)
        if code is None:
            return None
        if not c.name.isascii():
            # the native matcher folds case ASCII-only; a non-ASCII
            # field name needs Python's full-Unicode str.upper()
            return None
        fields.append((c.name, code))
    return {
        "mode": mode,
        "fields": fields,
        "delimiter": delimiter,
        "format": vf,
    }


def _reject_undistributable_plan(plan: st.QueryPlan) -> None:
    """Raise DeviceUnsupported for distribution gaps visible in the plan
    itself, before any lowering work is spent.  Gaps only the lowering
    analysis can see (EARLIEST/LATEST's arrival-sequence need) are still
    caught by DistributedDeviceQuery's constructor."""
    stj = 0
    for s in st.walk_steps(plan.physical_plan):
        if isinstance(s, (st.TableTableJoin, st.ForeignKeyTableTableJoin)):
            raise DeviceUnsupported(
                "distributed table-table/foreign-key joins pending; run "
                "them single-device"
            )
        if isinstance(s, st.TableSuppress):
            raise DeviceUnsupported(
                "EMIT FINAL is not yet distributed (per-shard flush "
                "pending); run it single-device or on the row oracle"
            )
        if isinstance(s, st.StreamTableJoin):
            stj += 1
    if stj > 1:
        raise DeviceUnsupported(
            "distributed n-way stream-table join chains pending; run "
            "them single-device"
        )
    # a CTAS over a table source (table transform / table aggregation)
    # steps through change batches, which have no lane decomposition yet
    src_types = [
        type(s) for s in st.walk_steps(plan.physical_plan)
        if isinstance(s, (st.TableSource, st.WindowedTableSource))
    ]
    if src_types and stj == 0:
        raise DeviceUnsupported(
            "distributed table-source transforms pending; run them "
            "single-device"
        )


def _is_suppress(plan: st.QueryPlan) -> bool:
    return any(
        isinstance(s, st.TableSuppress) for s in st.walk_steps(plan.physical_plan)
    )


def _needs_per_record(plan: st.QueryPlan) -> bool:
    """Plan shapes that auto-select per-record stepping under a batched
    engine default: fk joins and same-topic (self) joins."""
    topics = []
    for s in st.walk_steps(plan.physical_plan):
        if isinstance(s, st.ForeignKeyTableTableJoin):
            return True
        if isinstance(s, st.StreamSource):
            topics.append(s.topic)
    return len(topics) != len(set(topics))
