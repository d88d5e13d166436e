"""Row-mode plan executor — full streaming semantics.

One of the two backends over the ExecutionStep IR (the other is the columnar
XLA path in runtime/lowering.py), playing the role of the reference's
interpreter path (InterpretedExpressionFactory) generalized to whole
topologies.  It implements the complete Kafka-Streams-equivalent semantics
the reference gets from its runtime (KSPlanBuilder + Kafka Streams):

* per-record changelog emission (cache-off), table changes as
  (old, new) pairs with tombstones;
* event-time windows: tumbling, hopping, session (with merge + retraction),
  grace periods (default 24h, reference windows' legacy default), EMIT FINAL
  suppression on window close;
* stream-stream windowed joins with WITHIN (before, after) + GRACE —
  left/outer null-padding emitted only at window close (klip-36 semantics);
* stream-table, table-table, and foreign-key table-table joins with full
  retraction propagation;
* aggregate undo for table-source aggregations (KudafUndoAggregator).

This backend is the parity oracle for golden-file tests and the correctness
reference the device path is validated against.
"""

from __future__ import annotations

import dataclasses
import time as _time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ksql_tpu.common import faults, tracing
from ksql_tpu.common.errors import QueryRuntimeException
from ksql_tpu.common.schema import LogicalSchema
from ksql_tpu.execution import expressions as ex
from ksql_tpu.execution import steps as st
from ksql_tpu.execution.interpreter import ExpressionCompiler, TypeResolver
from ksql_tpu.functions.registry import FunctionRegistry
from ksql_tpu.parser.ast_nodes import JoinType, WindowType
from ksql_tpu.runtime.topics import Broker, Record, Topic
from ksql_tpu.serde import formats as fmt
from ksql_tpu.functions.udafs import _hashable

DEFAULT_GRACE_MS = 24 * 3600 * 1000  # reference legacy default grace


# ------------------------------------------------------------------ events


@dataclasses.dataclass
class StreamRow:
    key: Tuple[Any, ...]
    row: Dict[str, Any]
    ts: int
    window: Optional[Tuple[int, int]] = None
    part: Optional[int] = None  # source record partition (ROWPARTITION)
    offset: Optional[int] = None  # source record offset (ROWOFFSET)


@dataclasses.dataclass
class TableChange:
    key: Tuple[Any, ...]
    old: Optional[Dict[str, Any]]
    new: Optional[Dict[str, Any]]
    ts: int
    window: Optional[Tuple[int, int]] = None
    part: Optional[int] = None
    offset: Optional[int] = None


Event = Any  # StreamRow | TableChange


# ------------------------------------------------------------------- nodes


class Node:
    """A processor node.  ``receive(port, event)`` returns emitted events;
    ``on_time(stream_time)`` fires window-close actions."""

    def __init__(self, step: st.ExecutionStep):
        self.step = step
        self.schema: LogicalSchema = step.schema

    def receive(self, port: int, event: Event) -> List[Event]:
        raise NotImplementedError

    def on_time(self, stream_time: int) -> List[Event]:
        return []

    def on_flush(self, stream_time: int) -> List[Event]:
        """Explicit flush (end-of-stream / checkpoint): defaults to the
        record-driven time advance."""
        return self.on_time(stream_time)


def _key_of(row: Dict[str, Any], schema: LogicalSchema) -> Tuple[Any, ...]:
    return tuple(row.get(c.name) for c in schema.key_columns)


def _with_pseudo(
    row: Dict[str, Any],
    ts: int,
    window: Optional[Tuple[int, int]],
    event: Any = None,
) -> Dict[str, Any]:
    out = dict(row)
    out["ROWTIME"] = ts
    if event is not None:
        out["ROWPARTITION"] = getattr(event, "part", None)
        out["ROWOFFSET"] = getattr(event, "offset", None)
    if window is not None:
        out["WINDOWSTART"], out["WINDOWEND"] = window
    return out


class Compiler:
    """Compiles a step DAG into a Node pipeline."""

    def __init__(self, registry: FunctionRegistry, on_error: Callable[[str, Exception], None]):
        self.registry = registry
        self.on_error = on_error

    def expr(self, e: ex.Expression, schema: LogicalSchema, extra: Optional[Dict] = None):
        return self._compiler_for(schema, extra).compile(e)

    def expr_raw(self, e: ex.Expression, schema: LogicalSchema, extra: Optional[Dict] = None):
        """Unguarded compile: errors propagate (UDTF parameter contract)."""
        return self._compiler_for(schema, extra).compile_raw(e)

    def _compiler_for(self, schema: LogicalSchema, extra: Optional[Dict] = None):
        types = {c.name: c.type for c in schema.columns()}
        from ksql_tpu.common.schema import PSEUDOCOLUMNS, WINDOW_BOUNDS

        for n, t in {**PSEUDOCOLUMNS, **WINDOW_BOUNDS, **(extra or {})}.items():
            types.setdefault(n, t)
        return ExpressionCompiler(TypeResolver(types), self.registry, self.on_error)


# --------------------------------------------------------------- transforms


class FilterNode(Node):
    def __init__(self, step, compiler: Compiler, is_table: bool):
        super().__init__(step)
        self.pred = compiler.expr(step.predicate, step.source.schema)
        self.is_table = is_table

    def receive(self, port, event):
        if isinstance(event, StreamRow):
            if event.row is None:
                return []
            row = _with_pseudo(event.row, event.ts, event.window, event)
            if self.pred(row) is True:
                return [event]
            return []
        old_ok = (
            event.old is not None
            and self.pred(_with_pseudo(event.old, event.ts, event.window, event)) is True
        )
        new_ok = (
            event.new is not None
            and self.pred(_with_pseudo(event.new, event.ts, event.window, event)) is True
        )
        old = event.old if old_ok else None
        new = event.new if new_ok else None
        if old is None and new is None:
            return []
        return [TableChange(event.key, old, new, event.ts, event.window)]


class SelectNode(Node):
    def __init__(self, step, compiler: Compiler):
        super().__init__(step)
        src_schema = step.source.schema
        self.selects = [(name, compiler.expr(e, src_schema)) for name, e in step.selects]
        self.key_names = [c.name for c in step.schema.key_columns]
        self.src_key_names = [c.name for c in src_schema.key_columns]

    def _project(self, row, ts, window, event=None):
        src = _with_pseudo(row, ts, window, event)
        out = {}
        # carry (possibly renamed) key columns through
        for new_name, old_name in zip(self.key_names, self.src_key_names):
            out[new_name] = row.get(old_name)
        for name, f in self.selects:
            out[name] = f(src)
        return out

    def receive(self, port, event):
        if isinstance(event, StreamRow):
            if event.row is None:
                return [event]  # stream null-value records pass through
            return [StreamRow(event.key,
                              self._project(event.row, event.ts, event.window, event),
                              event.ts, event.window, event.part, event.offset)]
        old = (self._project(event.old, event.ts, event.window, event)
               if event.old is not None else None)
        new = (self._project(event.new, event.ts, event.window, event)
               if event.new is not None else None)
        return [TableChange(event.key, old, new, event.ts, event.window,
                            event.part, event.offset)]


class SelectKeyNode(Node):
    def __init__(self, step, compiler: Compiler):
        super().__init__(step)
        src_schema = step.source.schema
        self.src_key_columns = list(src_schema.key_columns)
        self.key_fns = [compiler.expr(e, src_schema) for e in step.key_expressions]
        # PartitionByParamsFactory evaluates an expression over key columns
        # only when every column it references is a key column; for null-value
        # rows any value-dependent expression yields a null key component.
        from ksql_tpu.execution.expressions import referenced_columns

        key_names = {c.name for c in self.src_key_columns}
        self.key_only = [
            all(n in key_names for n in referenced_columns(e))
            for e in step.key_expressions
        ]
        self.out_schema = step.schema

    def receive(self, port, event):
        assert isinstance(event, StreamRow)
        if event.row is None:
            # null-value records pass through a repartition: expressions over
            # key columns alone still evaluate; anything touching the (null)
            # value row becomes a null key component
            src = {
                c.name: v for c, v in zip(self.src_key_columns, event.key or ())
            }
            key_vals = tuple(
                f(src) if ko else None
                for f, ko in zip(self.key_fns, self.key_only)
            )
            return [StreamRow(key_vals, None, event.ts, event.window,
                              event.part, event.offset)]
        src = _with_pseudo(event.row, event.ts, event.window, event)
        key_vals = tuple(f(src) for f in self.key_fns)
        row = dict(event.row)
        for c, v in zip(self.out_schema.key_columns, key_vals):
            row[c.name] = v
        return [StreamRow(key_vals, row, event.ts, event.window,
                          event.part, event.offset)]


class FlatMapNode(Node):
    def __init__(self, step, compiler: Compiler):
        super().__init__(step)
        src_schema = step.source.schema
        self.on_error = compiler.on_error
        self.fns = []
        for name, call in step.table_functions:
            # unguarded arg evaluators: an error in UDTF parameter
            # evaluation (or in the UDTF itself) skips the WHOLE row via
            # the processing log — KudtfFlatMapper's try/catch contract —
            # rather than becoming a NULL parameter
            arg_fns = [compiler.expr_raw(a, src_schema) for a in call.args]
            arg_types = [f.sql_type for f in arg_fns]
            udtf = compiler.registry.udtf(call.name, arg_types)
            self.fns.append((name, arg_fns, udtf))

    def receive(self, port, event):
        assert isinstance(event, StreamRow)
        if event.row is None:
            return []
        src = _with_pseudo(event.row, event.ts, event.window, event)
        columns = []
        try:
            for name, arg_fns, udtf in self.fns:
                args = [f(src) for f in arg_fns]
                columns.append((name, udtf.fn(*args)))
        except Exception as e:  # noqa: BLE001 — per-row processing error
            self.on_error("flat-map", e)
            return []
        n = max((len(v) for _, v in columns), default=0)
        out = []
        for i in range(n):
            row = dict(event.row)
            for name, vals in columns:
                row[name] = vals[i] if i < len(vals) else None
            out.append(StreamRow(event.key, row, event.ts, event.window,
                                 event.part, event.offset))
        return out


# -------------------------------------------------------------- aggregation


class AggregateNode(Node):
    """GroupBy + Aggregate (+ windows).  port 0 receives StreamRow from the
    grouped stream, or TableChange for table aggregation."""

    def __init__(self, step, compiler: Compiler, window=None, from_table=False,
                 emit_final=False):
        super().__init__(step)
        self.emit_final = emit_final
        group_step = step.source
        src_schema = group_step.source.schema
        self.group_fns = [compiler.expr(g, src_schema) for g in
                          getattr(group_step, "group_by_expressions", ())]
        self.key_names = [c.name for c in step.schema.key_columns]
        self.window = window
        self.from_table = from_table
        self.aggs = []
        for i, call in enumerate(step.aggregations):
            arg_fns = [compiler.expr(a, src_schema) for a in call.args]
            arg_types = [f.sql_type or __import__("ksql_tpu.common.types", fromlist=["STRING"]).STRING
                         for f in arg_fns]
            udaf = compiler.registry.udaf(call.function, arg_types)
            self.aggs.append((f"KSQL_AGG_VARIABLE_{i}", arg_fns, udaf))
        # state: key -> [agg_state...]; windowed: (key, win_start) -> ...
        self.state: Dict[Any, List[Any]] = {}
        self.session_windows: Dict[Tuple, List[Tuple[int, int, List[Any]]]] = {}
        grace = getattr(window, "grace_ms", None) if window else None
        # EMIT FINAL defaults to zero grace (emit right at window end);
        # EMIT CHANGES keeps the legacy 24h default for late-record drops
        self.grace_ms = grace if grace is not None else (
            0 if emit_final else DEFAULT_GRACE_MS
        )

    # ------------------------------------------------------------ helpers
    def _group_key(self, row, ts, window, event=None) -> Tuple[Any, ...]:
        src = _with_pseudo(row, ts, window, event)
        return tuple(f(src) for f in self.group_fns)

    def _args(self, row, ts, window, arg_fns, event=None):
        src = _with_pseudo(row, ts, window, event)
        return [f(src) for f in arg_fns]

    def _init_states(self):
        return [udaf.init() for _, _, udaf in self.aggs]

    def _result_row(self, key, states, window) -> Dict[str, Any]:
        out = {}
        for name, k in zip(self.key_names, key):
            out[name] = k
        for (name, _, udaf), s in zip(self.aggs, states):
            out[name] = udaf.result(s)
        return out

    def _accumulate(self, states, row, ts, window):
        new_states = []
        for (name, arg_fns, udaf), s in zip(self.aggs, states):
            args = self._args(row, ts, window, arg_fns)
            new_states.append(udaf.accumulate(s, *args))
        return new_states

    def _undo(self, states, row, ts, window):
        new_states = []
        for (name, arg_fns, udaf), s in zip(self.aggs, states):
            if udaf.undo is None:
                raise QueryRuntimeException(
                    f"aggregate {udaf.name} does not support table retraction"
                )
            args = self._args(row, ts, window, arg_fns)
            new_states.append(udaf.undo(s, *args))
        return new_states

    # ------------------------------------------------------------ windows
    def _windows_for(self, ts: int) -> List[Tuple[int, int]]:
        w = self.window
        if w is None:
            return [None]
        if w.window_type == WindowType.TUMBLING:
            start = ts - ts % w.size_ms
            return [(start, start + w.size_ms)]
        if w.window_type == WindowType.HOPPING:
            out = []
            start = ts - ts % w.advance_ms
            while start + w.size_ms > ts and start >= 0:
                out.append((start, start + w.size_ms))
                start -= w.advance_ms
            return out[::-1]
        raise QueryRuntimeException(f"unsupported window type {w.window_type}")

    # ------------------------------------------------------------ receive
    def receive(self, port, event):
        if isinstance(event, TableChange):
            return self._receive_table_change(event)
        if event.row is None:
            return []
        row, ts = event.row, event.ts
        key = self._group_key(row, ts, event.window, event)
        if any(k is None for k in key):
            return []  # rows with a null grouping expression are excluded
        w = self.window
        if w is not None and w.window_type == WindowType.SESSION:
            return self._receive_session(key, row, ts)
        self.max_ts = max(getattr(self, "max_ts", -(2**63)), ts)
        out = []
        hkey = _hashable(key)
        for win in self._windows_for(ts):
            if win is not None:
                # late-record drop: a window is closed once stream time
                # reaches end + grace (inclusive, KIP-825 and pre-825 alike:
                # tumbling-windows.json 'out of order - explicit grace
                # period' drops a record arriving exactly at the close)
                if win[1] + self.grace_ms <= self.max_ts:
                    continue
            state_key = (hkey, win[0]) if win else hkey
            entry = self.state.get(state_key)
            old_row = None
            if entry is None:
                states, wmax = self._init_states(), ts
            else:
                states, wmax = entry
                old_row = self._result_row(key, states, win)
                wmax = max(wmax, ts)
            states = self._accumulate(states, row, ts, win)
            self.state[state_key] = (states, wmax)
            new_row = self._result_row(key, states, win)
            # windowed aggregate rows carry the max record ts in the window
            out.append(TableChange(key, old_row, new_row, wmax if win else ts, win))
        return out

    def _receive_table_change(self, event: TableChange):
        out = []
        old_key = (
            self._group_key(event.old, event.ts, None)
            if event.old is not None
            else None
        )
        if old_key is not None and not any(k is None for k in old_key):
            # null-group rows were never aggregated: nothing to undo
            key = old_key
            hkey = _hashable(key)
            entry = self.state.get(hkey)
            if entry is not None:
                states, wmax = entry
                old_row = self._result_row(key, states, None)
                states = self._undo(states, event.old, event.ts, None)
                self.state[hkey] = (states, wmax)
                out.append(TableChange(key, old_row, self._result_row(key, states, None), event.ts))
        if event.new is not None:
            key = self._group_key(event.new, event.ts, None)
            if any(k is None for k in key):
                return out  # null grouping expression: row excluded
            hkey = _hashable(key)
            entry = self.state.get(hkey)
            old_row = self._result_row(key, entry[0], None) if entry is not None else None
            states = self._accumulate(entry[0] if entry is not None else self._init_states(),
                                      event.new, event.ts, None)
            self.state[hkey] = (states, event.ts)
            out.append(TableChange(key, old_row, self._result_row(key, states, None), event.ts))
        return out

    def _receive_session(self, key, row, ts):
        self.max_ts = max(getattr(self, "max_ts", -(2**63)), ts)
        if ts + self.grace_ms + self.window.gap_ms < self.max_ts:
            # late record past gap+grace: its session window could no longer
            # merge with anything live (session-windows.json 'out of order -
            # explicit grace period': close = ts + gap + grace)
            return []
        gap = self.window.gap_ms
        hkey = _hashable(key)
        # session entries: (start, end, states, last_update_ts)
        sessions = self.session_windows.setdefault(hkey, [])
        # store retention: a session whose close (end + gap + grace) is
        # behind stream time is gone from the store — a new record in its
        # range starts a fresh session instead of merging
        sessions[:] = [
            s for s in sessions
            if s[1] + gap + self.grace_ms >= self.max_ts
        ]
        merged_start = merged_end = ts
        emit_ts = ts
        merged_states = self._init_states()
        removed, keep = [], []
        for entry in sessions:
            s, e, states, last_ts = entry
            if s - gap <= ts <= e + gap:
                merged_start = min(merged_start, s)
                merged_end = max(merged_end, e)
                emit_ts = max(emit_ts, last_ts)
                merged_states = [
                    udaf.merge(a, b)
                    for (nm, fns, udaf), a, b in zip(self.aggs, merged_states, states)
                ]
                removed.append(entry)
            else:
                keep.append(entry)
        merged_states = self._accumulate(merged_states, row, ts, (merged_start, merged_end))
        keep.append((merged_start, merged_end, merged_states, emit_ts))
        keep.sort(key=lambda t: t[0])
        self.session_windows[hkey] = keep
        out = []
        for (s, e, states, last_ts) in removed:
            # retract merged-away sessions; each tombstone keeps its own
            # session's record timestamp (KS SessionWindow merge semantics)
            out.append(
                TableChange(key, self._result_row(key, states, (s, e)), None, last_ts, (s, e))
            )
        win = (merged_start, merged_end)
        out.append(
            TableChange(key, None, self._result_row(key, merged_states, win), emit_ts, win)
        )
        return out


class SuppressNode(Node):
    """EMIT FINAL (KIP-825 EmitStrategy.onWindowClose semantics, matching
    KStreamWindowAggregate.maybeForwardFinalResult):

    * time windows emit once their close (end + grace) is at or before the
      observed stream time, but ONLY while still inside the store's
      retention horizon (start >= stream_time - retention, retention =
      max(RETENTION clause, size + grace)) — mirroring the reference's
      windowed-store eviction: a stream-time jump past close + size drops
      the final result exactly as the evicted RocksDB segment would
      (suppress.json "final results for tumbling/hopping windows");
    * session windows emit on the watermark alone: close <= stream_time;
    * a tombstone (session merged away) un-buffers the pending window;
    * each (key, window) emits at most once, with the aggregate's timestamp
      (max record ts in the window)."""

    def __init__(self, step, window, grace_ms: int):
        super().__init__(step)
        self.buffer: Dict[Tuple, TableChange] = {}
        self.session = bool(window) and window.window_type == WindowType.SESSION
        self.grace_ms = grace_ms
        size = getattr(window, "size_ms", None) or 0
        self.retention_ms = max(getattr(window, "retention_ms", None) or 0,
                                size + grace_ms)
        self.emitted: set = set()
        self.prev_time = -(2**63)

    def receive(self, port, event):
        assert isinstance(event, TableChange)
        if event.window is None:
            return [event]
        k = (event.key, event.window)
        if k in self.emitted:
            return []
        if event.new is None:
            self.buffer.pop(k, None)
            return []
        self.buffer[k] = event
        return []

    def on_time(self, stream_time):
        if stream_time == self.prev_time:
            return []
        self.prev_time = stream_time
        out = []
        for k in sorted(self.buffer, key=lambda kk: kk[1][1]):
            ev = self.buffer[k]
            closed = ev.window[1] + self.grace_ms <= stream_time
            if not closed:
                continue
            evicted = (not self.session
                       and ev.window[0] < stream_time - self.retention_ms)
            if evicted:
                del self.buffer[k]  # the store segment is gone; never emits
                continue
            out.append(TableChange(ev.key, None, ev.new, ev.ts, ev.window))
            self.emitted.add(k)
            del self.buffer[k]
        return out

    def on_flush(self, stream_time):
        """Force-close every window past its close time (watermark), e.g. at
        end-of-stream — unlike record-driven advancement (on_time), this
        skips the retention-horizon eviction, so windows the store would
        already have dropped still emit their final result."""
        out = []
        for k in sorted(self.buffer, key=lambda kk: kk[1][1]):
            ev = self.buffer[k]
            if ev.window[1] + self.grace_ms <= stream_time:
                out.append(TableChange(ev.key, None, ev.new, ev.ts, ev.window))
                self.emitted.add(k)
                del self.buffer[k]
        return out


# ------------------------------------------------------------------- joins


def _join_rows(left_row, right_row, left_schema, right_schema, out_schema, key, ts):
    row = {}
    for c in out_schema.key_columns:
        pass
    if left_row:
        row.update(left_row)
    if right_row:
        row.update(right_row)
    out = {}
    for c in out_schema.columns():
        out[c.name] = row.get(c.name)
    # the join key value fills the key column (it may only exist on one side)
    for c, v in zip(out_schema.key_columns, key):
        out[c.name] = v
    return out


class StreamStreamJoinNode(Node):
    def __init__(self, step: st.StreamStreamJoin, compiler: Compiler):
        super().__init__(step)
        self.left_schema = step.left.schema
        self.right_schema = step.right.schema
        self.left_key_fn = compiler.expr(step.left_key, self.left_schema)
        self.right_key_fn = compiler.expr(step.right_key, self.right_schema)
        self.before = step.before_ms
        self.after = step.after_ms
        # klip-36: an explicit GRACE PERIOD selects the fixed (deferred)
        # left/outer join semantics; without it, legacy eager null-padding
        self.deferred = step.grace_ms is not None
        self.grace = step.grace_ms if step.grace_ms is not None else DEFAULT_GRACE_MS
        # per-side window-store stream time: admission is gated by the OWN
        # store's observed max ts (segment expiry), not the task stream time
        self.side_max = [-(2 ** 63), -(2 ** 63)]
        self.retention = self.before + self.after + self.grace
        self.join_type = step.join_type
        # windowed-key sources join on (key, window): start for time windows
        # (reference TimeWindowedSerde serializes only the start), exact
        # (start, end) for sessions — verified against joins.json
        self.window_kind = self._window_kind(step)
        self.left_buf: Dict[Any, List[list]] = {}
        self.right_buf: Dict[Any, List[list]] = {}

    @staticmethod
    def _window_kind(step) -> Optional[str]:
        for s in st.walk_steps(step.left):
            if isinstance(s, (st.WindowedStreamSource, st.WindowedTableSource)):
                return "SESSION" if s.window_type == "SESSION" else "TIME"
        return None

    def _win_match(self, w1, w2) -> bool:
        if self.window_kind is None:
            return True
        if w1 is None or w2 is None:
            return w1 == w2
        if self.window_kind == "SESSION":
            return w1 == w2
        return w1[0] == w2[0]

    def receive(self, port, event):
        assert isinstance(event, StreamRow)
        if event.row is None:
            return []  # null-value stream records don't join (KS drops them)
        row, ts = event.row, event.ts
        src = _with_pseudo(row, ts, event.window)
        out = []
        self.stream_time = max(
            getattr(self, "stream_time", -(2 ** 63)), ts
        )
        self.side_max[port] = max(self.side_max[port], ts)
        # admission: the record enters its own window store only while its
        # segment is live (per-store stream time, retention = size + grace);
        # a late record still PROBES the other store regardless
        admitted = (
            not self.deferred
            or ts >= self.side_max[port] - self.retention
        )
        if port == 0:
            k = self.left_key_fn(src)
            entry = [ts, row, [False], k, event.window]
            if admitted:
                self.left_buf.setdefault(_hashable(k), []).append(entry)
            if k is not None:
                for rentry in self.right_buf.get(_hashable(k), ()):
                    rts, rrow, rmatched, _rk, rwin = rentry
                    if ts - self.before <= rts <= ts + self.after and self._win_match(
                        event.window, rwin
                    ):
                        entry[2][0] = True
                        rmatched[0] = True
                        out.append(self._emit(k, row, rrow, max(ts, rts), event.window))
            if not entry[2][0] and self.join_type in (JoinType.LEFT, JoinType.OUTER):
                if not self.deferred:
                    out.append(self._emit(k, row, None, ts, event.window))
                elif ts + self.after + self.grace < self.stream_time:
                    # window already closed on arrival: pad now (klip-36) —
                    # even for records too late to enter their own store
                    entry[2][0] = True
                    out.append(self._emit(k, row, None, ts, event.window))
        else:
            k = self.right_key_fn(src)
            entry = [ts, row, [False], k, event.window]
            if admitted:
                self.right_buf.setdefault(_hashable(k), []).append(entry)
            if k is not None:
                for lentry in self.left_buf.get(_hashable(k), ()):
                    lts, lrow, lmatched, _lk, lwin = lentry
                    if lts - self.before <= ts <= lts + self.after and self._win_match(
                        lwin, event.window
                    ):
                        entry[2][0] = True
                        lmatched[0] = True
                        out.append(self._emit(k, lrow, row, max(ts, lts), lwin))
            if not entry[2][0] and self.join_type in (JoinType.OUTER, JoinType.RIGHT):
                if not self.deferred:
                    out.append(self._emit(k, None, row, ts, event.window))
                elif ts + self.before + self.grace < self.stream_time:
                    entry[2][0] = True
                    out.append(self._emit(k, None, row, ts, event.window))
        return out

    def _emit(self, k, lrow, rrow, ts, window=None):
        row = _join_rows(lrow, rrow, self.left_schema, self.right_schema, self.schema, (k,), ts)
        return StreamRow((k,), row, ts, window if self.window_kind else None)

    def on_time(self, stream_time):
        """Emit deferred null-pads at window close (klip-36) and expire
        buffer entries by their own store's retention horizon — a padded
        entry stays resident and can still join a late arrival, matching
        the reference's window-store/outer-join-store split."""
        out = []
        for port, buf in ((0, self.left_buf), (1, self.right_buf)):
            window = self.after if port == 0 else self.before
            for hk in list(buf):
                keep = []
                for entry in buf[hk]:
                    ts, row, matched, k, win = entry
                    if self.deferred:
                        if not matched[0] and ts + window + self.grace < stream_time:
                            if port == 0 and self.join_type in (JoinType.LEFT, JoinType.OUTER):
                                out.append(self._emit(k, row, None, ts, win))
                            elif port == 1 and self.join_type in (JoinType.OUTER, JoinType.RIGHT):
                                out.append(self._emit(k, None, row, ts, win))
                            matched[0] = True
                        if ts >= self.side_max[port] - self.retention:
                            keep.append(entry)
                    elif ts + window + self.grace >= stream_time:
                        keep.append(entry)
                if keep:
                    buf[hk] = keep
                else:
                    del buf[hk]
        out.sort(key=lambda e: e.ts)
        return out


class StreamTableJoinNode(Node):
    def __init__(self, step: st.StreamTableJoin, compiler: Compiler):
        super().__init__(step)
        self.left_schema = step.left.schema
        self.right_schema = step.right.schema
        self.left_key_fn = compiler.expr(step.left_key, self.left_schema)
        self.join_type = step.join_type
        self.table: Dict[Any, dict] = {}

    def receive(self, port, event):
        if port == 1:
            assert isinstance(event, TableChange)
            k = event.key[0] if len(event.key) == 1 else event.key
            if event.new is None:
                self.table.pop(_hashable(k), None)
            else:
                self.table[_hashable(k)] = event.new
            return []
        assert isinstance(event, StreamRow)
        if event.row is None:
            return []
        src = _with_pseudo(event.row, event.ts, event.window)
        k = self.left_key_fn(src)
        rrow = self.table.get(_hashable(k)) if k is not None else None
        if rrow is None and self.join_type != JoinType.LEFT:
            return []
        row = _join_rows(event.row, rrow, self.left_schema, self.right_schema,
                         self.schema, (k,), event.ts)
        return [StreamRow((k,), row, event.ts)]


class TableTableJoinNode(Node):
    def __init__(self, step: st.TableTableJoin, compiler: Compiler):
        super().__init__(step)
        self.left_schema = step.left.schema
        self.right_schema = step.right.schema
        self.join_type = step.join_type
        self.left: Dict[Any, dict] = {}
        self.right: Dict[Any, dict] = {}

    def _join(self, k, lrow, rrow, ts):
        jt = self.join_type
        if lrow is None and rrow is None:
            return None
        if jt == JoinType.INNER and (lrow is None or rrow is None):
            return None
        if jt == JoinType.LEFT and lrow is None:
            return None
        if jt == JoinType.RIGHT and rrow is None:
            return None
        return _join_rows(lrow, rrow, self.left_schema, self.right_schema,
                          self.schema, (k,), ts)

    def receive(self, port, event):
        assert isinstance(event, TableChange)
        k = event.key[0] if len(event.key) == 1 else event.key
        hk = _hashable(k)
        if port == 0:
            old_l = self.left.get(hk)
            new_l = event.new
            if new_l is None:
                self.left.pop(hk, None)
            else:
                self.left[hk] = new_l
            r = self.right.get(hk)
            old_j = self._join(k, old_l, r, event.ts)
            new_j = self._join(k, new_l, r, event.ts)
        else:
            old_r = self.right.get(hk)
            new_r = event.new
            if new_r is None:
                self.right.pop(hk, None)
            else:
                self.right[hk] = new_r
            l = self.left.get(hk)
            old_j = self._join(k, l, old_r, event.ts)
            new_j = self._join(k, l, new_r, event.ts)
        if old_j is None and new_j is None:
            return []
        return [TableChange((k,), old_j, new_j, event.ts)]


class FkJoinNode(Node):
    """Foreign-key table-table join: left keyed by its own pk, joined on
    fk(left) = pk(right) (ForeignKeyTableTableJoinBuilder analog)."""

    def __init__(self, step: st.ForeignKeyTableTableJoin, compiler: Compiler):
        super().__init__(step)
        self.left_schema = step.left.schema
        self.right_schema = step.right.schema
        self.fk_fn = compiler.expr(step.foreign_key_expression, self.left_schema)
        self.join_type = step.join_type
        self.left: Dict[Any, dict] = {}
        self.right: Dict[Any, dict] = {}
        self.fk_index: Dict[Any, set] = {}

    def _join(self, lk, lrow, rrow, ts):
        if lrow is None:
            return None
        if rrow is None and self.join_type != JoinType.LEFT:
            return None
        return _join_rows(lrow, rrow, self.left_schema, self.right_schema,
                          self.schema, lk if isinstance(lk, tuple) else (lk,), ts)

    def _fk_of(self, row, ts):
        return self.fk_fn(_with_pseudo(row, ts, None)) if row is not None else None

    def receive(self, port, event):
        assert isinstance(event, TableChange)
        out = []
        if port == 0:
            lk = event.key
            hlk = _hashable(lk)
            old = self.left.get(hlk)
            old_fk = self._fk_of(old, event.ts)
            new_fk = self._fk_of(event.new, event.ts)
            if event.new is None:
                self.left.pop(hlk, None)
            else:
                self.left[hlk] = event.new
            if old_fk is not None and old_fk != new_fk:
                self.fk_index.get(_hashable(old_fk), set()).discard((hlk, lk))
            if new_fk is not None:
                self.fk_index.setdefault(_hashable(new_fk), set()).add((hlk, lk))
            old_j = self._join(lk, old, self.right.get(_hashable(old_fk)), event.ts)
            new_j = self._join(lk, event.new, self.right.get(_hashable(new_fk)), event.ts)
            # a left-row delete always tombstones the result, even when the
            # join value was already null (KS FK-join forwarding)
            left_delete = event.new is None and old is not None
            if old_j is not None or new_j is not None or left_delete:
                out.append(TableChange(lk, old_j, new_j, event.ts))
        else:
            rk = event.key[0] if len(event.key) == 1 else event.key
            hrk = _hashable(rk)
            old_r = self.right.get(hrk)
            if event.new is None:
                self.right.pop(hrk, None)
            else:
                self.right[hrk] = event.new
            for hlk, lk in sorted(self.fk_index.get(hrk, ()), key=repr):
                lrow = self.left.get(hlk)
                old_j = self._join(lk, lrow, old_r, event.ts)
                new_j = self._join(lk, lrow, event.new, event.ts)
                if old_j is not None or new_j is not None:
                    out.append(TableChange(lk, old_j, new_j, event.ts))
        return out


# ------------------------------------------------------------------ executor


@dataclasses.dataclass
class SinkEmit:
    """One sink emission, shared by every executor backend.

    ``ts`` is the emission's event time: the triggering record's (possibly
    TIMESTAMP-column-extracted) timestamp on row paths, the aggregate's
    event time on stateful paths.  The health subsystem measures e2e
    latency as ``produce wall-time − ts`` off this field, so backends must
    stamp real event time here — micro-batched device paths may
    batch-approximate (their coalesced emission carries the batch's decoded
    per-row timestamps), which biases e2e conservatively, never optimistically."""

    key: Tuple[Any, ...]
    row: Optional[Dict[str, Any]]  # None = tombstone
    ts: int
    window: Optional[Tuple[int, int]] = None


def decode_source_record(
    source_step, record: Record, on_error: Callable[[str, Exception], None]
) -> Optional[Event]:
    """Deserialize one source-topic record into a StreamRow/TableChange
    (serde + headers + timestamp extraction + table-changelog old/new
    tracking).  Shared by every executor backend — which makes it the one
    choke point for the flight recorder's ``deserialize`` stage."""
    tr = tracing.active()
    if tr is None:
        return _decode_source_record(source_step, record, on_error)
    t0 = _time.perf_counter()
    try:
        return _decode_source_record(source_step, record, on_error)
    finally:
        tr.stage("deserialize", _time.perf_counter() - t0)


def _decode_source_record(
    source_step, record: Record, on_error: Callable[[str, Exception], None]
) -> Optional[Event]:
    schema = source_step.schema
    # serde construction + column pruning are per-step constants: cache on
    # the step (this is the per-record hot path of every executor)
    cached = source_step.__dict__.get("_decode_cache")
    if cached is None:
        value_serde = fmt.of(
            source_step.formats.value_format,
            properties={
                "VALUE_DELIMITER": source_step.formats.value_delimiter,
                "PROTO_NULLABLE_ALL": source_step.__dict__.get(
                    "_proto_nullable_all", False
                ),
                "PROTO_FLOAT32": source_step.__dict__.get("_proto_float32", ()),
            },
            wrap_single_values=source_step.formats.wrap_single_values,
        )
        header_cols = dict(getattr(source_step, "header_columns", ()) or ())
        value_columns = [
            c for c in schema.value_columns if c.name not in header_cols
        ]
        cached = (value_serde, header_cols, value_columns)
        source_step.__dict__["_decode_cache"] = cached
    value_serde, header_cols, value_columns = cached
    try:
        value_row = value_serde.deserialize(record.value, value_columns) \
            if record.value is not None else None
        key_row = {}
        if record.key is not None and schema.key_columns:
            key_row = fmt.deserialize_key(
                source_step.formats.key_format, record.key, schema.key_columns,
                delimiter=getattr(source_step.formats, "key_delimiter", None),
            )
    except Exception as e:
        on_error(f"deserialize:{source_step.topic}", e)
        return None
    if header_cols and value_row is not None:
        headers = list(record.headers or ())
        for col, hkey in header_cols.items():
            if hkey is None:
                value_row[col] = [
                    {"KEY": k, "VALUE": v} for k, v in headers
                ]
            else:
                value_row[col] = next(
                    (v for k, v in reversed(headers) if k == hkey), None
                )
    ts = record.timestamp
    if source_step.timestamp_column and value_row is not None:
        tv = value_row.get(source_step.timestamp_column)
        if tv is None and source_step.timestamp_column in key_row:
            tv = key_row[source_step.timestamp_column]
        if tv is not None:
            if isinstance(tv, str) and source_step.timestamp_format:
                from ksql_tpu.functions.udfs import _string_to_ts

                try:
                    tv = _string_to_ts(tv, source_step.timestamp_format)
                except Exception as e:
                    on_error("timestamp-extract", e)
                    return None
            try:
                ts = int(tv)
            except (TypeError, ValueError) as e:
                on_error("timestamp-extract", e)
                return None
            if ts < 0:
                # negative extracted timestamps drop the record
                # (reference MetadataTimestampExtractor semantics)
                return None
    is_table = isinstance(source_step, (st.TableSource, st.WindowedTableSource))
    if record.key is None and schema.key_columns:
        if is_table:
            return None  # table upsert with null key: skipped (KTable source)
        key: tuple = ()  # null key payload: stays a null key on passthrough
    else:
        key = tuple(key_row.get(c.name) for c in schema.key_columns)
        if is_table and key and all(k is None for k in key):
            return None
    if value_row is None:
        row = None
    else:
        row = dict(key_row)
        row.update(value_row)
    if is_table:
        if not hasattr(source_step, "_table_state"):
            source_step.__dict__["_table_state"] = {}
        state = source_step.__dict__["_table_state"]
        hkey = _hashable(key)
        old = state.get(hkey)
        if row is None:
            if hkey in state:
                del state[hkey]
        else:
            state[hkey] = row
        if old is None and row is None:
            return None
        return TableChange(key, old, row, ts, record.window,
                           record.partition, record.offset)
    return StreamRow(key, row, ts, record.window,
                     record.partition, record.offset)



def _apply_path_default(row, path, default):
    """Substitute ``default`` at a nested struct ``path`` whose value is
    null (SR-schema-id sinks; copy-on-write so shared rows stay intact)."""

    def rec(obj, i):
        if not isinstance(obj, dict):
            return obj
        k = path[i]
        key = k if k in obj else next(
            (kk for kk in obj if kk.upper() == k.upper()), k
        )
        v = obj.get(key)
        if i == len(path) - 1:
            if v is None:
                obj = dict(obj)
                obj[key] = default
            return obj
        nv = rec(v, i + 1)
        if nv is not v:
            obj = dict(obj)
            obj[key] = nv
        return obj

    return rec(row, 0)


def _wrapped(obj, name: str, cls) -> bool:
    """True when ``obj.<name>`` is no longer ``cls``'s own method: set on
    the instance (tests and operators wrap the produce seams so) or
    overridden below ``cls``."""
    return getattr(getattr(obj, name), "__func__", None) is not getattr(cls, name)


#: sentinel for SinkWriter.produce's ``precoded`` parameter — None is a
#: meaningful precoded value (a tombstone's payload), so absence needs
#: its own marker
_UNSET = object()


def _json_scalar_frag(v):
    """``json.dumps(_jsonable(v))`` for scalar runtime types — the
    per-column fragment of JsonFormat.serialize's envelope, byte-exact
    (separators only affect containers, which raise here and fall back
    to the per-emit serializer)."""
    import json as _json

    if v is None:
        return "null"
    t = type(v)
    if t is bool:
        return "true" if v else "false"
    if t is int or t is float:
        if t is float:
            # Jackson renders non-finite doubles as strings (see _jsonable)
            if v != v:
                return '"NaN"'
            if v == float("inf"):
                return '"Infinity"'
            if v == float("-inf"):
                return '"-Infinity"'
        return repr(v)  # json.dumps delegates to int/float __repr__
    if t is str:
        return _json.dumps(v)  # ensure_ascii escapes, exactly
    raise TypeError(f"non-scalar sink value {t.__name__}")


def _delim_field_encoder(serde, first_field: bool):
    """One column's DelimitedFormat.serialize mirror (bool/bytes/float/str
    rendering + commons-csv minimal quoting).  The DECIMAL special case is
    unreachable: batch encode is gated to scalar non-DECIMAL columns."""
    import base64 as _b64

    quote = serde._quote

    def enc(v):
        if v is None:
            return ""
        if isinstance(v, bool):
            return quote("true" if v else "false", first_field)
        if isinstance(v, bytes):
            return quote(_b64.b64encode(v).decode("ascii"), first_field)
        if isinstance(v, float):
            from ksql_tpu.execution.interpreter import java_double_str

            return quote(java_double_str(v), first_field)
        return quote(str(v), first_field)

    return enc


class SinkWriter:
    """Serializes SinkEmits and produces them to the sink topic (the
    SinkBuilder.java:43/89 analog: value/key serde + sink timestamp column).
    Shared by every executor backend.

    ``enabled=False`` puts the query in STANDBY: it keeps consuming and
    materializing state (replica for pulls + warm failover) but publishes
    nothing — the num.standby.replicas analog for a shared data plane."""

    enabled = True
    #: bounded per-emit produce retries before the failure escalates to a
    #: tick replay (the engine arms this on micro-batched backends, where
    #: replaying the whole batch over one transient produce fault is the
    #: expensive alternative); retries are safe because a failed produce
    #: raises before the record enters the log
    produce_retries = 0
    #: effectively-once fence (runtime/changelog.py): emissions whose
    #: ordinal is at-or-below this durable high-water were already
    #: journaled + re-appended by recovery, so a post-restart replay
    #: suppresses them instead of duplicating (dupes across a process
    #: death stay bounded by the single in-flight tick)
    fence_seq = 0
    #: emissions the fence suppressed (metrics / test observability)
    fenced_out = 0
    #: when armed (a list), each successful produce appends
    #: ``(topic, key, value, ts, window)`` here; the engine drains it
    #: into the tick's changelog frame at the commit point
    journal_buf = None

    def __init__(self, sink_step, broker: Broker,
                 on_error: Callable[[str, Exception], None]):
        self.sink_step = sink_step
        self.broker = broker
        self.on_error = on_error
        #: 1-based logical emit ordinal — the sink.produce fault context
        #: (``<topic>#<n>#``) and the per-emit commit-point unit
        self.emit_seq = 0
        #: produce attempts that failed and were retried (metrics)
        self.retries_used = 0
        #: rows serialized by the batched column-at-a-time encoder
        #: (ksql_sink_batch_encoded_rows_total)
        self.batch_encoded_rows = 0
        #: precoded-value hand-off from produce() to _produce(); an instance
        #: stash keeps _produce a wrappable one-arg seam
        self._precoded = _UNSET
        broker.create_topic(sink_step.topic)
        self.value_serde = fmt.of(
            sink_step.formats.value_format,
            properties={
                "VALUE_DELIMITER": sink_step.formats.value_delimiter,
                "PROTO_NULLABLE_ALL": sink_step.__dict__.get(
                    "_proto_nullable_all", False
                ),
                "PROTO_FLOAT32": sink_step.__dict__.get("_proto_float32", ()),
            },
            wrap_single_values=sink_step.formats.wrap_single_values,
        )

    def encode_batch(self, emits: List[SinkEmit]) -> Optional[list]:
        """Array-at-a-time value encode for an emission block — the
        device-block handoff lifted to sinks.  Per-column encoders walk
        the block column-wise; the fragments join per row byte-identical
        to ``value_serde.serialize``.  Returns one precoded value per
        emit for ``produce(e, precoded=...)`` (``_UNSET`` where that row
        must serialize per-emit, e.g. an unexpected runtime type), or
        None when the whole block is ineligible: non-JSON/DELIMITED
        serde, armed fault proxy (serde fault points must fire per
        emit), DECIMAL or nested columns, path-shaped value_defaults.
        Per-emit semantics — emit_seq ordinals, the sink.produce fault
        context, retries, standby muting, timestamp extraction — all
        stay in produce()."""
        from ksql_tpu.common.types import SqlBaseType as B

        if not self.enabled or not emits:
            return None
        serde = self.value_serde
        cols = list(self.sink_step.schema.value_columns)
        if not cols:
            return None
        defaults = getattr(self.sink_step, "value_defaults", ()) or ()
        if any(not isinstance(n, str) for n, _ in defaults):
            return None  # nested-path defaults: per-emit serialize
        scalar = (B.BIGINT, B.INTEGER, B.DOUBLE, B.BOOLEAN, B.STRING)
        if any(c.type.base not in scalar for c in cols):
            return None
        if type(serde) is fmt.JsonFormat:
            delimited = False
        elif type(serde) is fmt.DelimitedFormat:
            delimited = True
        else:
            return None  # _FaultingFormat proxy, Avro envelope, protobuf...
        tr = tracing.active()
        t0 = _time.perf_counter() if tr is not None else 0.0
        flat = dict(defaults)
        rows = []
        for e in emits:
            row = e.row
            if row is not None and flat:
                row = {**flat, **row}
            rows.append(row)
        n = len(rows)
        columns: List[list] = []
        if delimited:
            encoders = [
                _delim_field_encoder(serde, i == 0) for i in range(len(cols))
            ]
        else:
            encoders = [_json_scalar_frag] * len(cols)
        for c, enc in zip(cols, encoders):
            name = c.name
            col = []
            for row in rows:
                if row is None:
                    col.append(None)
                else:
                    try:
                        col.append(enc(row.get(name)))
                    except Exception:  # noqa: BLE001 — per-emit fallback
                        col.append(_UNSET)
            columns.append(col)
        out: list = []
        encoded = 0
        if delimited:
            join = serde.delimiter.join
        else:
            import json as _json

            prefixes = [_json.dumps(c.name) + ":" for c in cols]
            unwrapped = not serde.wrap and len(cols) == 1
        for i in range(n):
            if rows[i] is None:
                out.append(None)  # tombstone: serialize returns None
                continue
            frags = [col[i] for col in columns]
            if any(f is _UNSET for f in frags):
                out.append(_UNSET)
                continue
            if delimited:
                out.append(join(frags))
            elif unwrapped:
                out.append(frags[0])
            else:
                out.append(
                    "{"
                    + ",".join(p + f for p, f in zip(prefixes, frags))
                    + "}"
                )
            encoded += 1
        self.batch_encoded_rows += encoded
        if tr is not None:
            # the block encode IS these emits' serialize time; produce()
            # still records its (now serialization-free) per-emit stage
            dur_s = _time.perf_counter() - t0
            tr.stage("sink.produce", dur_s, n=encoded, encode_ms=dur_s * 1e3)
        return out

    def produce(self, e: SinkEmit, precoded=_UNSET) -> None:
        if not self.enabled:
            return  # standby: materialize-only, nothing published
        # _produce stays a one-arg seam (tests and operators wrap it with
        # single-argument shims); a precoded value from the block-batched
        # encoder is handed over via an instance stash cleared on exit
        self._precoded = precoded
        tr = tracing.active()
        try:
            if tr is None:
                return self._produce(e)
            t0 = _time.perf_counter()
            try:
                return self._produce(e)
            finally:
                tr.stage("sink.produce", _time.perf_counter() - t0)
        finally:
            self._precoded = _UNSET

    def _key_serializer(self):
        """The sink's key serializer as the sink step stands now."""
        formats = self.sink_step.formats
        return fmt.key_serializer(
            formats.key_format, self.sink_step.schema.key_columns,
            wrapped=getattr(formats, "key_wrapped", False),
            delimiter=getattr(formats, "key_delimiter", None),
        )

    def block_ready(self, precoded: Optional[list]) -> bool:
        """Whether ``produce_block`` may take the block ``encode_batch``
        precoded: nothing observable wants its emits produced one at a
        time.  That is a standby writer (it produces nothing), armed
        faults (the ``sink.produce`` and ``topic.produce`` points fire per
        emit, with its ordinal), a wrapped ``produce`` / ``_produce`` or
        ``Topic.produce``, ordinals still under the effectively-once
        fence, a sink timestamp column, and a row that ``encode_batch``
        left to the row serializer."""
        step = self.sink_step
        return not (
            precoded is None
            or not self.enabled
            or faults.armed()
            or self.emit_seq < self.fence_seq
            or step.timestamp_column
            or _wrapped(self, "produce", SinkWriter)
            or _wrapped(self, "_produce", SinkWriter)
            or _wrapped(self.broker.topic(step.topic), "produce", Topic)
            or any(v is _UNSET for v in precoded)
        )

    def produce_block(self, emits: List[SinkEmit], precoded: list) -> bool:
        """``produce(e, precoded=v)`` for a whole emission block (one that
        is ``block_ready``) in one append to the sink topic: the same
        records in the same order, the same ``emit_seq`` and
        ``journal_buf``, one ``sink.produce`` stage.  A failed append
        enters nothing into the log: it counts as the block's first
        attempt, is reported like a retry, and returns False for the
        caller to send the block through ``produce``."""
        step = self.sink_step
        tr = tracing.active()
        t0 = _time.perf_counter() if tr is not None else 0.0
        serialize = self._key_serializer()
        keys = [serialize(e.key) for e in emits]
        try:
            records = self.broker.topic(step.topic).produce_block(
                keys, precoded, [e.ts for e in emits], [e.window for e in emits],
            )
        except Exception as exc:  # noqa: BLE001 — as a failed produce
            self.retries_used += 1
            self.on_error(f"sink-produce-retry:{step.topic}", exc)
            return False
        self.emit_seq += len(records)
        if self.journal_buf is not None:
            name = step.topic
            self.journal_buf.extend(
                (name, r.key, r.value, r.timestamp, r.window) for r in records
            )
        if tr is not None:
            tr.stage("sink.produce", _time.perf_counter() - t0, n=len(records))
        return True

    def _produce(self, e: SinkEmit) -> None:
        precoded = self._precoded
        self.emit_seq += 1
        if faults.armed():
            # per-emit chaos seam: the ordinal context lets a rule like
            # sink.produce@#5# kill exactly the 5th emit (replay-window
            # tests); fired once per LOGICAL emit, outside the retry loop,
            # so an injected kill always escalates deterministically
            faults.fault_point(
                "sink.produce", f"{self.sink_step.topic}#{self.emit_seq}#"
            )
        if self.emit_seq <= self.fence_seq:
            # effectively-once: this ordinal's record was durable in the
            # changelog journal and already re-appended by recovery — the
            # replayed derivation is suppressed, not re-published
            self.fenced_out += 1
            return
        schema = self.sink_step.schema
        if precoded is not _UNSET:
            # batched column-at-a-time encode already produced the exact
            # bytes (value_defaults applied there); skip the row serializer
            value = precoded
        else:
            row = e.row
            defaults = getattr(self.sink_step, "value_defaults", ()) or ()
            if row is not None and defaults:
                flat = {n: d for n, d in defaults if isinstance(n, str)}
                if flat:
                    row = {**flat, **row}
                for n, d in defaults:
                    if isinstance(n, (tuple, list)):
                        row = _apply_path_default(row, tuple(n), d)
            value = (
                self.value_serde.serialize(row, list(schema.value_columns))
                if row is not None
                else None
            )
        key = self._key_serializer()(e.key)
        ts = e.ts
        if self.sink_step.timestamp_column and e.row is not None:
            tv = e.row.get(self.sink_step.timestamp_column)
            if tv is not None:
                if isinstance(tv, str):
                    from ksql_tpu.functions.udfs import _string_to_ts

                    try:
                        tv = _string_to_ts(
                            tv,
                            getattr(self.sink_step, "timestamp_format", None)
                            or "yyyy-MM-dd'T'HH:mm:ssX",
                        )
                    except Exception as ex_:
                        self.on_error("timestamp-sink", ex_)
                        return
                ts = int(tv)
                if ts < 0:
                    return  # negative timestamps drop the record
        topic = self.broker.topic(self.sink_step.topic)
        record = Record(key=key, value=value, timestamp=ts, partition=-1,
                        window=e.window)
        attempts = int(self.produce_retries) + 1
        for i in range(attempts):
            try:
                topic.produce(record)
                if self.journal_buf is not None:
                    # durable-emission capture for the changelog frame;
                    # only records that actually entered the log count
                    self.journal_buf.append(
                        (self.sink_step.topic, key, value, ts, e.window)
                    )
                return
            except Exception as exc:  # noqa: BLE001 — transient produce
                # faults retry per emit; exhausting the budget escalates to
                # the engine's tick-replay path
                if i + 1 >= attempts:
                    raise
                self.retries_used += 1
                self.on_error(f"sink-produce-retry:{self.sink_step.topic}", exc)


class OracleExecutor:
    """Executes one QueryPlan over in-process topics, row at a time."""

    def __init__(
        self,
        plan: st.QueryPlan,
        broker: Broker,
        registry: FunctionRegistry,
        on_error: Optional[Callable[[str, Exception], None]] = None,
        emit_callback: Optional[Callable[[SinkEmit], None]] = None,
    ):
        self.plan = plan
        self.broker = broker
        self.registry = registry
        self.on_error = on_error or (lambda expr, e: None)
        self.emit_callback = emit_callback
        self.compiler = Compiler(registry, self.on_error)
        self.stream_time = -(2**63)
        # topic -> list of (source_step, path) ; path = [(node, port), ...]
        self.source_routes: Dict[str, List[Tuple[st.ExecutionStep, List[Tuple[Node, int]]]]] = {}
        self.nodes: List[Node] = []
        self.sink_step: Optional[st.ExecutionStep] = None
        self.sink_serde = None
        self._build(plan.physical_plan, [])
        self._window_grace = self._find_grace(plan.physical_plan)

    # ------------------------------------------------------------- building
    def _find_grace(self, step) -> int:
        for s in st.walk_steps(step):
            w = getattr(s, "window", None)
            if w is not None and getattr(w, "grace_ms", None) is not None:
                return w.grace_ms
        return DEFAULT_GRACE_MS

    def _find_window(self, step):
        for s in st.walk_steps(step):
            w = getattr(s, "window", None)
            if w is not None:
                return w
        return None

    def _build(self, step: st.ExecutionStep, path_above: List[Tuple[Node, int]]):
        """Recursively build nodes; ``path_above`` is the node chain from this
        step's parent up to the root (with input port numbers)."""
        t = type(step)
        if t in (st.StreamSource, st.WindowedStreamSource, st.TableSource, st.WindowedTableSource):
            self.source_routes.setdefault(step.topic, []).append((step, list(path_above)))
            return
        if t in (st.StreamFilter, st.TableFilter):
            node = FilterNode(step, self.compiler, t is st.TableFilter)
        elif t in (st.StreamSelect, st.TableSelect):
            node = SelectNode(step, self.compiler)
        elif t in (st.StreamSelectKey, st.TableSelectKey):
            node = SelectKeyNode(step, self.compiler)
        elif t is st.StreamFlatMap:
            node = FlatMapNode(step, self.compiler)
        elif t in (st.StreamAggregate, st.TableAggregate):
            node = AggregateNode(step, self.compiler, window=None,
                                 from_table=t is st.TableAggregate)
        elif t is st.StreamWindowedAggregate:
            node = AggregateNode(
                step, self.compiler, window=step.window,
                emit_final=any(isinstance(n, SuppressNode) for n, _ in path_above),
            )
        elif t is st.StreamStreamJoin:
            node = StreamStreamJoinNode(step, self.compiler)
        elif t is st.StreamTableJoin:
            node = StreamTableJoinNode(step, self.compiler)
        elif t is st.TableTableJoin:
            node = TableTableJoinNode(step, self.compiler)
        elif t is st.ForeignKeyTableTableJoin:
            node = FkJoinNode(step, self.compiler)
        elif t is st.TableSuppress:
            w = self._find_window(step)
            g = getattr(w, "grace_ms", None) if w is not None else None
            node = SuppressNode(step, w, g if g is not None else 0)
        elif t in (st.StreamSink, st.TableSink):
            self.sink_step = step
            self.sink_writer = SinkWriter(step, self.broker, self.on_error)
            self._build(step.source, path_above)
            return
        elif t in (st.StreamGroupBy, st.StreamGroupByKey, st.TableGroupBy):
            # folded into the aggregate node above it
            self._build(step.source, path_above)
            return
        else:
            raise QueryRuntimeException(f"oracle cannot execute step {t.__name__}")

        self.nodes.append(node)
        children = step.sources()
        if t in (st.StreamAggregate, st.StreamWindowedAggregate, st.TableAggregate):
            # skip the group-by marker step
            group = step.source
            children = group.sources()
        for port, child in enumerate(children):
            self._build(child, [(node, port)] + path_above)

    # ------------------------------------------------------------- running
    def process(self, topic: str, record: Record) -> List[SinkEmit]:
        """Push one record through the topology; returns sink emissions."""
        routes = self.source_routes.get(topic)
        if not routes:
            return []
        out: List[SinkEmit] = []
        for source_step, path in routes:
            ev = decode_source_record(source_step, record, self.on_error)
            if ev is None:
                continue
            self.stream_time = max(self.stream_time, ev.ts)
            out.extend(self._push(ev, path))
        # time-driven flushes (window close, suppression, join expiry)
        out.extend(self._advance_time())
        return out

    def flush_time(self, stream_time: int) -> List[SinkEmit]:
        """Advance stream time explicitly (end-of-input flush for EMIT FINAL
        and left-join close in tests)."""
        self.stream_time = max(self.stream_time, stream_time)
        return self._advance_time(force=True)

    # ------------------------------------------------------- state epochs
    #: every record is fully processed (and its emits produced) before
    #: process() returns — the engine's per-record commit points and
    #: in-place poison rollback rely on this
    record_synchronous = True

    @property
    def stateful(self) -> bool:
        """True when the topology holds state a replay could double-count
        (aggregates, joins, suppression buffers, table-source changelogs)."""
        cached = self.__dict__.get("_stateful")
        if cached is None:
            from ksql_tpu.runtime.checkpoint import _ORACLE_STATE_ATTRS

            cached = any(
                type(n).__name__ in _ORACLE_STATE_ATTRS for n in self.nodes
            ) or any(
                isinstance(s, (st.TableSource, st.WindowedTableSource))
                for s in st.walk_steps(self.plan.physical_plan)
            )
            self.__dict__["_stateful"] = cached
        return cached

    def state_epoch(self) -> Dict[str, Any]:
        """Deep snapshot of every stateful node's state plus the
        table-source decode changelogs — the per-record commit-point epoch
        the engine rolls back to (atomic poison skip) or restores into a
        rebuilt executor on a self-healing restart."""
        import copy

        from ksql_tpu.runtime.checkpoint import _ORACLE_STATE_ATTRS

        nodes = []
        for node in self.nodes:
            attrs = _ORACLE_STATE_ATTRS.get(type(node).__name__, ())
            nodes.append({
                a: copy.deepcopy(getattr(node, a))
                for a in attrs if hasattr(node, a)
            })
        tables = {}
        for i, step in enumerate(st.walk_steps(self.plan.physical_plan)):
            ts_ = step.__dict__.get("_table_state")
            if ts_ is not None:
                tables[i] = copy.deepcopy(ts_)
        return {"nodes": nodes, "tables": tables,
                "stream_time": self.stream_time}

    def restore_state_epoch(self, epoch: Dict[str, Any]) -> None:
        """Install an epoch taken by :meth:`state_epoch` (same plan, nodes
        rebuilt in the same deterministic order).  The stored epoch is
        deep-copied on the way in so it survives being restored more than
        once (rollback now, restart later)."""
        import copy

        epoch = copy.deepcopy(epoch)
        for node, nd in zip(self.nodes, epoch["nodes"]):
            for a, v in nd.items():
                setattr(node, a, v)
        for i, step in enumerate(st.walk_steps(self.plan.physical_plan)):
            if i in epoch["tables"]:
                step.__dict__["_table_state"] = epoch["tables"][i]
            else:
                # decode state accumulated after the epoch must not leak
                # into the replay's old/new tracking
                step.__dict__.pop("_table_state", None)
        if epoch.get("stream_time") is not None:
            self.stream_time = epoch["stream_time"]

    def changelog_dirty_state(self) -> Dict[str, Any]:
        """Dirty-set seam for the incremental changelog journal
        (runtime/changelog.py): one commit-point capture in
        checkpoint-serde shape.  _snapshot_oracle returns LIVE node
        references; the journal host-copies the capture before diffing,
        so this stays as cheap as the checkpoint path."""
        from ksql_tpu.runtime.checkpoint import _snapshot_oracle

        return _snapshot_oracle(self)

    def changelog_apply_state(self, data: Dict[str, Any]) -> None:
        """Restore a (possibly journal-patched) capture."""
        from ksql_tpu.runtime.checkpoint import _restore_oracle

        _restore_oracle(self, data)

    def _advance_time(self, force: bool = False) -> List[SinkEmit]:
        out = []
        for i, node in enumerate(self.nodes):
            evs = node.on_flush(self.stream_time) if force else node.on_time(self.stream_time)
            if not evs:
                continue
            # events continue from above this node
            path = self._path_above(node)
            for ev in evs:
                out.extend(self._push_from(ev, path))
        return out

    def _path_above(self, node: Node) -> List[Tuple[Node, int]]:
        # nodes were appended root-first during build; path above node =
        # reversed prefix of nodes list... simpler: recompute via search
        for topic_routes in self.source_routes.values():
            for _, path in topic_routes:
                for i, (n, port) in enumerate(path):
                    if n is node:
                        return path[i + 1 :]
        return []

    def _push(self, ev: Event, path: List[Tuple[Node, int]]) -> List[SinkEmit]:
        return self._push_from(ev, path)

    def _push_from(self, ev: Event, path: List[Tuple[Node, int]]) -> List[SinkEmit]:
        chaos = faults.armed()
        tr = tracing.active()
        if tr is None:
            events = [ev]
            for node, port in path:
                if chaos:
                    # per-stage chaos seam: a hang-mode rule here blocks the
                    # tick body mid-pipeline (the tick-deadline test seam)
                    faults.fault_point(
                        "stage.process",
                        f"{self.plan.query_id}:{node.step.ctx}",
                    )
                next_events = []
                for e in events:
                    next_events.extend(node.receive(port, e))
                events = next_events
                if not events:
                    return []
            return [emit for e in events for emit in self._emit(e)]
        # traced variant: per-ExecutionStep stage accumulation (the oracle's
        # node-at-a-time analog of the device backend's fused step timing)
        events = [ev]
        for node, port in path:
            if chaos:
                faults.fault_point(
                    "stage.process", f"{self.plan.query_id}:{node.step.ctx}"
                )
            t0 = _time.perf_counter()
            next_events = []
            for e in events:
                next_events.extend(node.receive(port, e))
            events = next_events
            tr.stage(f"stage:{node.step.ctx}", _time.perf_counter() - t0)
            if not events:
                return []
        return [emit for e in events for emit in self._emit(e)]

    # ------------------------------------------------------------ decoding
    # ------------------------------------------------------------ emitting
    def _emit(self, event: Event) -> List[SinkEmit]:
        if isinstance(event, StreamRow):
            emits = [SinkEmit(event.key, event.row, event.ts, event.window)]
        else:
            emits = [SinkEmit(event.key, event.new, event.ts, event.window)]
        out = []
        for e in emits:
            if self.emit_callback is not None:
                self.emit_callback(e)
            if self.sink_step is not None:
                self._produce(e)
            out.append(e)
        return out

    def _produce(self, e: SinkEmit):
        self.sink_writer.produce(e)