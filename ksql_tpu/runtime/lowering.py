"""XlaPlanBuilder — lowers the ExecutionStep DAG to one jitted device step.

The backend seam analog: where the reference's KSPlanBuilder
(ksqldb-streams/.../KSPlanBuilder.java:62) visits each ExecutionStep and
emits Kafka Streams DSL nodes (one processor per step, record-at-a-time),
this builder fuses the *entire* supported pipeline —

    Source → Filter*/Select*/SelectKey* → GroupBy → [Windowed]Aggregate
           → TableSelect*/TableFilter(HAVING) → [Suppress] → Sink

— into a single ``step(state, batch) → (state, emits)`` function compiled
once by XLA (static shapes, donated state, no host round-trips).  Per-step
processors would defeat XLA fusion; the step DAG remains the serialization
and planning boundary, not the execution granularity.

Unsupported steps or expressions raise DeviceUnsupported and the engine
falls back to the row oracle (runtime/oracle.py) — same posture as the
reference's codegen→interpreter fallback.

Semantic deltas vs the record-at-a-time oracle (documented, by design):
* EMIT CHANGES coalesces to one change per key per micro-batch (equivalent
  to Kafka Streams with its record cache enabled — the production default);
* late-record grace is evaluated against the stream time at batch start.

HAVING pass→fail transitions emit tombstones via the per-slot ``hpass``
verdict column (the oracle's retraction semantics).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ksql_tpu.common import tracing
from ksql_tpu.common import types as T
from ksql_tpu.common.batch import HostBatch
from ksql_tpu.common.errors import QueryRuntimeException
from ksql_tpu.common.schema import LogicalSchema
from ksql_tpu.common.types import SqlBaseType, SqlType
from ksql_tpu.compiler.jax_expr import (
    DCol,
    DeviceUnsupported,
    JaxExprCompiler,
    _dtype_for as _dtype_of_probe,
)
from ksql_tpu.execution import expressions as ex
from ksql_tpu.execution import steps as st
from ksql_tpu.execution.interpreter import ExpressionCompiler, TypeResolver
from ksql_tpu.functions.registry import FunctionRegistry
from ksql_tpu.ops import session_merge
from ksql_tpu.ops import window as W
from ksql_tpu.ops.device_aggs import DeviceAgg, compile_device_agg
from ksql_tpu.ops.hash_store import (
    AggComponent,
    StoreLayout,
    combine_hash,
    init_store,
    probe_find,
    probe_insert,
    scatter_combine,
    winners_per_slot,
)
from ksql_tpu.parser.ast_nodes import WindowType
from ksql_tpu.runtime.device import BatchLayout, DictionaryServer, decode_value
from ksql_tpu.runtime.oracle import DEFAULT_GRACE_MS, SinkEmit

# the device path is int64/float64 throughout (timestamps, hashes, BIGINT);
# enable x64 once at import — flipping the process-global flag per query
# construction would invalidate jit caches of concurrently-running queries
jax.config.update("jax_enable_x64", True)


#: the stage that carries each direction's bytes: the span that moves them
_TRANSFER_STAGE = {"h2d_bytes": "step.dispatch", "d2h_bytes": "emit.decode"}


def _note_transfer(key: str, arrays: Dict[str, Any]) -> None:
    """Account host<->device bytes on the flight recorder, on the stage of
    the span that moves them (``.nbytes`` is metadata — no device sync)."""
    tr = tracing.active()
    if tr is None:
        return
    tr.counter(
        _TRANSFER_STAGE[key],
        **{key: int(sum(getattr(v, "nbytes", 0) for v in arrays.values()))},
    )

_HASHED = (
    SqlBaseType.STRING, SqlBaseType.BYTES,
    # nested values are opaque dictionary codes on device (see device.py)
    SqlBaseType.ARRAY, SqlBaseType.MAP, SqlBaseType.STRUCT,
)

#: HBM budget for a store's aggregate state arrays at construction: wide
#: vector components (collect caps up to 4096 elements/key, slice rings)
#: trade initial slot count for width.  An eighth of one device's memory
#: where the device reports it (2 GB of a v5e's 16), and never under 256 MiB
_VEC_STATE_BUDGET_BYTES = 256 << 20
_VEC_STATE_DEVICE_SHARE = 8
_NESTED_BASES = (SqlBaseType.ARRAY, SqlBaseType.MAP, SqlBaseType.STRUCT)


def _device_memory_bytes() -> int:
    """One device's memory as its runtime reports it; 0 where it reports
    none (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return int((stats or {}).get("bytes_limit", 0))


def _vec_state_budget_bytes() -> int:
    return max(
        _VEC_STATE_BUDGET_BYTES,
        _device_memory_bytes() // _VEC_STATE_DEVICE_SHARE,
    )


def _collect_struct_paths(exprs, schema):
    """(struct_paths, flattened_roots) for struct columns dereferenced to
    scalar leaves: each path becomes a synthetic flat column ``ROOT->F.G``.
    A struct whose every use is a path drops from the layout; one also used
    whole keeps its (dictionary-coded) column next to the path columns."""
    paths: Dict[str, Tuple[str, Tuple[str, ...], SqlType]] = {}
    bare_structs: set = set()
    struct_cols = {
        c.name: c.type
        for c in schema.columns()
        if c.type.base == SqlBaseType.STRUCT
    }

    def leaf_type(root: str, fields: Tuple[str, ...]) -> Optional[SqlType]:
        t = struct_cols.get(root)
        for f in fields:
            if t is None or t.base != SqlBaseType.STRUCT:
                return None
            t = next(
                (ft for fn, ft in (t.fields or ()) if fn.upper() == f.upper()),
                None,
            )
        if t is None or t.base in _NESTED_BASES:
            return None
        return t

    def scan(node):
        if isinstance(node, ex.Dereference):
            from ksql_tpu.compiler.jax_expr import (
                deref_fields,
                deref_root,
                deref_synth_name,
            )

            cur = deref_root(node)
            if isinstance(cur, ex.ColumnRef) and cur.name in struct_cols:
                fields = deref_fields(node)
                lt = leaf_type(cur.name, fields)
                if lt is None:
                    bare_structs.add(cur.name)
                else:
                    paths[deref_synth_name(cur.name, fields)] = (
                        cur.name, fields, lt,
                    )
                return
            scan(cur)
            return
        if isinstance(node, ex.ColumnRef):
            if node.name in struct_cols:
                bare_structs.add(node.name)
            return
        if dataclasses.is_dataclass(node) and not isinstance(node, type):
            for f in dataclasses.fields(node):
                v = getattr(node, f.name)
                if isinstance(v, ex.Expression):
                    scan(v)
                elif isinstance(v, (list, tuple)):
                    for item in v:
                        if isinstance(item, ex.Expression):
                            scan(item)
                        elif (
                            isinstance(item, tuple)
                            and len(item) == 2
                            and isinstance(item[1], ex.Expression)
                        ):
                            scan(item[1])

    for e in exprs:
        scan(e)
    # paths extract even when the struct is ALSO used whole (the bare
    # column rides as a dictionary code next to its flat path columns);
    # only fully-flattened roots leave the layout
    out = [
        (synth, root, fields, lt)
        for synth, (root, fields, lt) in sorted(paths.items())
    ]
    roots = {root for _s, root, _f, _t in out} - bare_structs
    return out, roots


def _repr64(col: DCol) -> jnp.ndarray:
    """Raw 64-bit key repr of a column (hash for strings, bitcast for f64,
    widened int otherwise)."""
    b = col.sql_type.base
    if b in _HASHED:
        return col.data
    if b in (SqlBaseType.DOUBLE, SqlBaseType.DECIMAL):
        return jax.lax.bitcast_convert_type(col.data.astype(jnp.float64), jnp.int64)
    return col.data.astype(jnp.int64)


def _decode_repr(data: np.ndarray, sql_type: SqlType) -> np.ndarray:
    if sql_type.base in (SqlBaseType.DOUBLE, SqlBaseType.DECIMAL):
        return data.view(np.float64)
    return data


def _host_repr64(value, sql_type: SqlType) -> Optional[int]:
    """Host-side mirror of _repr64 for one literal key value (keyed pull
    lookups).  None = no stable repr (nested literals) — caller scans."""
    if value is None:
        return None
    b = sql_type.base
    if b in _HASHED:
        if isinstance(value, (str, bytes)):
            from ksql_tpu.common.batch import stable_hash64

            return int(stable_hash64(value))
        return None
    if b in (SqlBaseType.DOUBLE, SqlBaseType.DECIMAL):
        return int(np.float64(value).view(np.int64))
    if b == SqlBaseType.BOOLEAN:
        return int(bool(value))
    return int(value)


@dataclasses.dataclass
class _AggSpec:
    fname: str
    arg_exprs: Tuple[ex.Expression, ...]
    device: DeviceAgg
    out_name: str


#: slice-ring combines cover exactly the monoid component kinds
_DECOMPOSABLE = ("add", "min", "max")

#: lanes one visit of the sliced fold, claim and combine works on.  A TPU
#: scatter costs by index and a gather by element, whether or not the lane
#: holds a row, so a sliced step wider than this visits the chunks of
#: consecutive lanes that hold an active row and skips the others, as
#: ``hash_store.probe_insert`` does since PR 26; a step at or under it runs
#: all its lanes at once.  Read on one v5e chip (my chip runs, PR 35: the
#: benchmark's hopping step alone, 32,768 lanes, 2^21 slots, ring 8, nine
#: components), ms a step at chunks of 1,024 / 2,048 / 4,096 lanes: 4,096
#: rows leading the batch 110.0 / 103.4 / 108.5 (all lanes at once 295.2);
#: 480 rows 80.7 / 85.0 / 109.0 (288.1); a full batch 383 / 365 / 369
#: (349).  Some 75 ms of each are passes over the whole ring arrays that no
#: width moves (PERF.md §5).
_SLICED_CHUNK = 2048


def _pad_lanes(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """``x`` with its leading axis padded to whole chunks of ``width``
    (zeros: a padding lane is inactive)."""
    pad = -x.shape[0] % width
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) if pad else x


def _flat_cell(row: Any, col: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    """Index of cell (``row``, ``col``) in a ``rows × cols`` array laid out
    flat, row by row; int32 where every cell's index fits."""
    dtype = jnp.int32 if rows * cols < 2 ** 31 else jnp.int64
    return jnp.asarray(row, dtype) * cols + col.astype(dtype)


def _sliced_lanes_of(emits: Dict[str, Any]) -> Dict[str, int]:
    """A sliced step's ``sliced_lanes`` as a ``device.step`` counter field
    (the mesh: its busiest shard's); nothing for a step that has none."""
    if "sliced_lanes" not in emits:
        return {}
    return {"sliced_lanes": int(np.asarray(emits["sliced_lanes"]).max())}


def _visit_occupied_chunks(
    active: jnp.ndarray, width: int, carry: Any,
    visit: Callable[[jnp.ndarray, Any], Any],
) -> Tuple[Any, jnp.ndarray]:
    """``visit(lo, carry) -> carry`` for every chunk of ``width``
    consecutive lanes of ``active`` (whole chunks) that holds a true lane,
    in lane order, ``lo`` the chunk's first lane; a chunk with none is not
    visited.  Returns the last carry and the lanes visited (``width`` a
    chunk, int32).  ``carry`` must derive from varying inputs, as
    ``probe_insert``'s, so that the loop is well typed under shard_map."""
    n_chunks = active.shape[0] // width
    chunk_ids = jnp.arange(n_chunks, dtype=jnp.int32)
    occupied = jnp.any(active.reshape(n_chunks, width), axis=1)

    def next_chunk(after):
        return jnp.min(
            jnp.where(occupied & (chunk_ids > after), chunk_ids, n_chunks)
        )

    def body(loop):
        chunk, lanes, carry = loop
        return next_chunk(chunk), lanes + width, visit(chunk * width, carry)

    zero = jnp.sum(active.astype(jnp.int32) * 0)
    _, lanes, carry = jax.lax.while_loop(
        lambda loop: loop[0] < n_chunks, body,
        (next_chunk(zero - 1), zero, carry),
    )
    return carry, lanes


class FamilyAttachRefused(DeviceUnsupported):
    """A shared-pipeline attach the runtime must refuse — classified and
    observable: ``reason_code`` is the stable label of
    ``ksql_query_family_attach_refused_total{reason}`` (shared with the
    cost model's reject codes, planner/mqo.py) and ``details`` feeds the
    ``family.reslice.refuse`` plog + /alerts evidence entry."""

    def __init__(self, reason_code: str, msg: str, **details):
        super().__init__(msg)
        self.reason_code = reason_code
        self.details = details


@dataclasses.dataclass
class _MemberSpec:
    """One query of a window family sharing a sliced device pipeline.

    The primary query is ``members[0]``; attached queries differ in
    (size, advance, grace, retention), their post-aggregation
    projection/sink schema, and — since the MQO generalization — their
    aggregate SET: ``agg_map`` maps each member-local aggregate to its
    index in the pipeline's shared (union) partial set, which is what
    lets one per-(key, slice) partial store serve every member's window
    combine.  ``agg_map=None`` means the full shared set in order (the
    pre-MQO exact-match family)."""

    query_id: Optional[str]
    size_ms: int
    advance_ms: int
    grace_ms: int
    retention_ms: int
    agg_schema: LogicalSchema  # aggregate output schema (key column names)
    post_ops: List["st.ExecutionStep"]
    sink_schema: LogicalSchema  # emitted row schema
    deliver: Optional[Callable[[List["SinkEmit"]], None]] = None
    agg_map: Optional[List[int]] = None


@dataclasses.dataclass
class _PrefixMemberSpec:
    """One stateless query riding a shared source-prefix pipeline: the
    member's full filter/project chain (source-side-first suffix past the
    shared prefix is its residual) plus its sink schema.  Evaluated as an
    extra branch of the primary's stateless device step — the push
    registry's tap seam lifted from identity pipelines to arbitrary
    shared prefixes."""

    query_id: str
    pre_ops: List["st.ExecutionStep"]
    sink_schema: LogicalSchema
    deliver: Optional[Callable[[List["SinkEmit"]], None]] = None


def _op_fingerprint(op) -> tuple:
    """Structural identity of one Filter/Select step — the unit of
    shared-prefix matching across member chains."""
    if isinstance(op, st.StreamFilter):
        return ("filter", repr(op.predicate))
    return (
        "select",
        tuple((n, repr(e)) for n, e in getattr(op, "selects", ())),
        # key renames change the step's output env: two Selects that
        # differ only here must not fingerprint as one shared step
        tuple(getattr(op, "key_names", ()) or ()),
    )


def _refs_of_ops(ops) -> set:
    """Source columns referenced anywhere in a step chain."""
    out: set = set()
    for s in ops:
        if hasattr(s, "predicate"):
            out.update(ex.referenced_columns(s.predicate))
        if hasattr(s, "selects"):
            for _, e in s.selects:
                out.update(ex.referenced_columns(e))
        if hasattr(s, "key_expressions"):
            for e in s.key_expressions:
                out.update(ex.referenced_columns(e))
    return out


@dataclasses.dataclass
class _JoinSpec:
    """One stream-table probe of an n-way join chain (deepest-first)."""

    step: "st.StreamTableJoin"
    table_source: "st.TableSource"
    table_pre_ops: List["st.ExecutionStep"]
    # stream-side ops between the PREVIOUS probe (or the source) and this one
    between_ops: List["st.ExecutionStep"]
    layout: Optional[BatchLayout] = None
    cols: List = dataclasses.field(default_factory=list)
    capacity: int = 0
    seen_overflow: int = 0


class CompiledDeviceQuery:
    """A query lowered to the XLA backend.

    Host API: ``process(HostBatch) -> List[SinkEmit]`` for the stream
    source; ``flush(stream_time)`` forces suppressed (EMIT FINAL) windows
    out; ``state`` is the device store pytree (checkpointable).
    """

    def __init__(
        self,
        plan: st.QueryPlan,
        registry: FunctionRegistry,
        capacity: int = 8192,
        store_capacity: int = 1 << 17,
        table_store_capacity: int = 1 << 16,
        ss_buffer_capacity: int = 2048,
        ss_out_capacity: Optional[int] = None,
        analyze_only: bool = False,
        sliced: Optional[bool] = None,
        slice_ring_max: int = 512,
    ):
        self.plan = plan
        self.registry = registry
        self.capacity = capacity
        self.store_capacity = store_capacity
        self.dictionary = DictionaryServer()

        # ---- structural analysis (reject anything not yet device-lowered)
        self.sink: Optional[st.ExecutionStep] = None
        self.suppress = False
        self.windowed_source = False  # WindowedStreamSource re-import
        self.post_ops: List[st.ExecutionStep] = []  # TableSelect/TableFilter
        self.agg: Optional[st.ExecutionStep] = None
        self.group: Optional[st.ExecutionStep] = None
        self.pre_ops: List[st.ExecutionStep] = []  # Filter/Select/SelectKey
        self.mid_ops: List[st.ExecutionStep] = []  # ops between join and agg/sink
        self.join: Optional[st.StreamTableJoin] = None
        self.join_chain: List[_JoinSpec] = []
        self.table_source: Optional[st.TableSource] = None
        self.table_pre_ops: List[st.ExecutionStep] = []
        self.ss_join: Optional[st.StreamStreamJoin] = None
        self.right_source: Optional[st.StreamSource] = None
        self.right_pre_ops: List[st.ExecutionStep] = []
        self.table_mode = False  # table-to-table transform (per-change)
        self.table_agg = False  # aggregation over a TABLE source (undo+apply)
        self.tt_join: Optional[st.TableTableJoin] = None
        self.tt_left_source: Optional[st.TableSource] = None
        self.tt_right_source: Optional[st.TableSource] = None
        self.tt_left_ops: List[st.ExecutionStep] = []
        self.tt_right_ops: List[st.ExecutionStep] = []
        self.flatmap: Optional[st.StreamFlatMap] = None
        self.flatmap_pre_ops: List[st.ExecutionStep] = []
        self.fk_join: Optional[st.ForeignKeyTableTableJoin] = None
        self.fk_left_source: Optional[st.TableSource] = None
        self.fk_right_source: Optional[st.TableSource] = None
        self.fk_left_ops: List[st.ExecutionStep] = []
        self.fk_right_ops: List[st.ExecutionStep] = []
        self.source: Optional[st.StreamSource] = None
        self._analyze(plan.physical_plan)

        self.window = getattr(self.agg, "window", None) if self.agg is not None else None
        self.session = (
            self.window is not None
            and self.window.window_type == WindowType.SESSION
        )
        if self.session and self.suppress:
            raise DeviceUnsupported("EMIT FINAL SESSION windows on device")
        if self.session and self.join is not None:
            raise DeviceUnsupported("SESSION windows over a join on device")
        self.session_slots = 4  # concurrent sessions tracked per key (grows)
        grace = getattr(self.window, "grace_ms", None) if self.window else None
        # EMIT FINAL defaults to zero grace (emit right at window end);
        # EMIT CHANGES keeps the legacy 24h default (oracle AggregateNode)
        self.grace_ms = grace if grace is not None else (
            0 if self.suppress else DEFAULT_GRACE_MS
        )
        # windowed-store retention (KS: max(explicit retention, size+grace))
        self.retention_ms: Optional[int] = None
        if self.window is not None and self.window.window_type != WindowType.SESSION:
            size = self.window.size_ms
            self.retention_ms = max(
                getattr(self.window, "retention_ms", None) or 0,
                size + self.grace_ms,
            )
        # hopping windows expand each batch k-fold before the shuffle
        self.expansion = 1
        if self.window is not None and self.window.window_type == WindowType.HOPPING:
            self.expansion = W.hopping_expansion(
                self.window.size_ms, self.window.advance_ms
            )

        # ---- host-computed expression columns: scalar expressions with no
        # device lowering (string ops, subscripts, struct/array construction,
        # lambdas) evaluate host-side at encode and ride in as columns
        self._host_exprs: List[Tuple[str, Any, SqlType, Tuple[str, ...]]] = []
        self._extract_host_exprs()

        # ---- aggregation specs
        self.agg_specs: List[_AggSpec] = []
        self.key_types: List[SqlType] = []
        if self.agg is not None:
            self._build_agg_specs()

        # ---- stream slicing (hopping windows): per-(key, slice) partials
        # replace the k-fold expansion when every aggregate decomposes
        self._setup_slicing(sliced, slice_ring_max)

        # ---- ingress layout: only the columns the pipeline reads.
        # Shared source-prefix members (attach_prefix_member) widen the
        # layout to the union of every member chain's reads — empty here.
        self.prefix_members: List[_PrefixMemberSpec] = []
        #: leading self.pre_ops steps every prefix member shares (applied
        #: once per batch; each member then runs only its residual suffix)
        self._prefix_shared_len = 0
        self._build_ingress_layout()

        # ---- table-side ingress + device table store (stream-table join)
        self.table_layout: Optional[BatchLayout] = None
        self.table_schema: Optional[LogicalSchema] = None
        self.table_cols: List = []
        self.table_store_capacity = 0
        if self.join is not None:
            # downstream reads: mid ops, later probes' keys/between ops,
            # post ops, grouping, agg args, sink — a probe's store holds
            # only right-side columns something above it actually reads
            down = _refs_of_ops(self.mid_ops) | _refs_of_ops(self.post_ops)
            if self.group is not None:
                for e in getattr(self.group, "group_by_expressions", ()):
                    down.update(ex.referenced_columns(e))
            for spec in self.agg_specs:
                for e in spec.arg_exprs:
                    down.update(ex.referenced_columns(e))
            down.update(c.name for c in self._emit_schema().columns())
            for jspec in self.join_chain:
                down.update(ex.referenced_columns(jspec.step.left_key))
                down.update(_refs_of_ops(jspec.between_ops))
                down.update(c.name for c in jspec.step.schema.key_columns)
            for jspec in self.join_chain:
                tsrc = jspec.table_source.schema
                tneeded = _refs_of_ops(jspec.table_pre_ops)
                tneeded.update(ex.referenced_columns(jspec.step.right_key))
                tneeded &= {c.name for c in tsrc.columns()}
                tneeded.update(c.name for c in tsrc.key_columns)
                jspec.layout = BatchLayout(
                    tsrc, sorted(tneeded), capacity, self.dictionary
                )
                jspec.cols = [
                    c for c in jspec.step.right.schema.value_columns
                    if c.name in down
                ]
                jspec.capacity = table_store_capacity
            last = self.join_chain[-1]
            self.table_layout = last.layout
            self.table_schema = last.step.right.schema
            self.table_cols = last.cols
            self.table_store_capacity = table_store_capacity

        # ---- stream-stream join: right ingress + device ring buffers
        self.right_layout: Optional[BatchLayout] = None
        self.ss_cols: Dict[str, List] = {}
        if self.ss_join is not None:
            from ksql_tpu.parser.ast_nodes import JoinType

            ss = self.ss_join
            rsrc = self.right_source.schema
            rneeded = _refs_of_ops(self.right_pre_ops)
            rneeded.update(ex.referenced_columns(ss.right_key))
            rneeded &= {c.name for c in rsrc.columns()}
            rneeded.update(c.name for c in rsrc.key_columns)
            self.right_layout = BatchLayout(
                rsrc, sorted(rneeded), capacity, self.dictionary
            )
            down = _refs_of_ops(self.mid_ops)
            down.update(c.name for c in self._emit_schema().columns())
            down.update(c.name for c in ss.schema.key_columns)
            for side, step in (("l", ss.left), ("r", ss.right)):
                # nested columns buffer as dictionary codes like strings
                self.ss_cols[side] = [
                    c for c in step.schema.columns() if c.name in down
                ]
            self.ss_before = ss.before_ms
            self.ss_after = ss.after_ms
            # klip-36: explicit GRACE selects deferred (emit-at-close)
            # left/outer semantics; without it, legacy eager null-padding
            self.ss_deferred = ss.grace_ms is not None
            self.ss_grace = (
                ss.grace_ms if ss.grace_ms is not None else DEFAULT_GRACE_MS
            )
            self.ss_pad_sides = set()
            if ss.join_type in (JoinType.LEFT, JoinType.OUTER):
                self.ss_pad_sides.add("l")
            if ss.join_type in (JoinType.RIGHT, JoinType.OUTER):
                self.ss_pad_sides.add("r")
            # window-store retention (admission horizon vs the OWN side's
            # stream time): size + grace, as the reference's join stores
            self.ss_retention = self.ss_before + self.ss_after + self.ss_grace
            self.ss_capacity = max(ss_buffer_capacity, capacity)
            self.ss_out_cap = ss_out_capacity or max(64, 2 * capacity)

        # ---- table-table join: per-side ingress + two-sided device store
        self.tt_layouts: Dict[str, BatchLayout] = {}
        self.tt_cols: Dict[str, List] = {}
        self.tt_store_capacity = 0
        if self.tt_join is not None:
            down = _refs_of_ops(self.pre_ops)
            down.update(c.name for c in self._emit_schema().columns())
            down.update(c.name for c in self.tt_join.schema.key_columns)
            for side, src, ops, key_expr in (
                ("l", self.tt_left_source, self.tt_left_ops, self.tt_join.left_key),
                ("r", self.tt_right_source, self.tt_right_ops, self.tt_join.right_key),
            ):
                sschema = src.schema
                needed2 = _refs_of_ops(ops)
                needed2.update(ex.referenced_columns(key_expr))
                if not ops:
                    needed2.update(down)
                needed2 &= {c.name for c in sschema.columns()}
                needed2.update(c.name for c in sschema.key_columns)
                self.tt_layouts[side] = BatchLayout(
                    sschema, sorted(needed2), capacity, self.dictionary
                )
                post = ops[-1].schema if ops else sschema
                self.tt_cols[side] = [
                    c for c in post.columns() if c.name in down
                ]
            self.tt_store_capacity = table_store_capacity

        # ---- fk join: per-side ingress + left(pk,fk)/right(pk) stores
        self.fk_layouts: Dict[str, BatchLayout] = {}
        self.fk_cols: Dict[str, List] = {}
        self.fk_store_capacity = 0
        if self.fk_join is not None:
            down = _refs_of_ops(self.pre_ops)
            down.update(c.name for c in self._emit_schema().columns())
            down.update(c.name for c in self.fk_join.schema.key_columns)
            for side, src, ops in (
                ("l", self.fk_left_source, self.fk_left_ops),
                ("r", self.fk_right_source, self.fk_right_ops),
            ):
                sschema = src.schema
                needed2 = _refs_of_ops(ops)
                if side == "l":
                    needed2.update(
                        ex.referenced_columns(
                            self.fk_join.foreign_key_expression
                        )
                    )
                if not ops:
                    needed2.update(down)
                needed2 &= {c.name for c in sschema.columns()}
                needed2.update(c.name for c in sschema.key_columns)
                self.fk_layouts[side] = BatchLayout(
                    sschema, sorted(needed2), capacity, self.dictionary
                )
                post = ops[-1].schema if ops else sschema
                self.fk_cols[side] = [
                    c for c in post.columns() if c.name in down
                ]
            self.fk_store_capacity = table_store_capacity

        self.store_layout: Optional[StoreLayout] = None
        self._needs_seq = False
        if self.agg is not None:
            comps = self._agg_components()
            # wide vector state (collect caps / slice rings) shrinks the
            # initial slot count to a bounded HBM budget; the store still
            # grows on demand.  The bound is bytes alone: cut below what
            # fits, a store sized to hold its keys doubles its way back,
            # each time with a host rebuild and a recompile of every step
            # (about a minute for the sliced hopping step at 32,768 lanes;
            # PERF.md, PR 34)
            row_bytes = sum(
                np.dtype(c.dtype).itemsize * c.width for c in comps
            )
            budget_slots = max(
                1024, _vec_state_budget_bytes() // max(row_bytes, 1)
            )
            while store_capacity > 1024 and store_capacity > budget_slots:
                store_capacity //= 2
            self.store_capacity = store_capacity
            self.store_layout = StoreLayout(
                capacity=store_capacity,
                num_keys=len(self.key_types),
                components=tuple(comps),
                windowed=self.window is not None,
            )
            # EARLIEST/LATEST aggs order by a global arrival sequence
            self._needs_seq = any(c.combine == "argset" for c in comps)

        self._state: Optional[Dict[str, jnp.ndarray]] = None  # lazy
        if analyze_only:
            # the static classifier's probe (analysis/plan_verifier): every
            # plan-derivable DeviceUnsupported above has had its chance to
            # raise — stop before jit wrapping and the abstract traces, so
            # classification costs plan analysis only
            return
        self._compile_steps()

        # abstract trace now: any DeviceUnsupported (expression/function not
        # lowered) must surface at construction so the engine can fall back
        # to the oracle BEFORE the query starts (no XLA compile, no alloc)
        state_shapes = jax.eval_shape(self.init_state)
        if self.ss_join is not None:
            jax.eval_shape(
                self._trace_ss_l, state_shapes, self.layout.array_structs()
            )
            jax.eval_shape(
                self._trace_ss_r, state_shapes, self.right_layout.array_structs()
            )
            jax.eval_shape(self._trace_ss_expire, state_shapes)
        elif self.table_agg:
            jax.eval_shape(
                self._trace_table_agg_step, state_shapes,
                self.layout.array_structs(), self.layout.array_structs(),
            )
        elif self.tt_join is not None:
            for side in ("l", "r"):
                structs = self.tt_layouts[side].array_structs()
                structs_new = dict(structs)
                structs_new["delete"] = jax.ShapeDtypeStruct(
                    (self.capacity,), np.int32
                )
                jax.eval_shape(
                    lambda st_, an, ao, s=side: self._trace_tt_step(
                        st_, an, ao, s
                    ),
                    state_shapes, structs_new, structs,
                )
        elif self.fk_join is not None:
            for side, trace in (
                ("l", self._trace_fk_left), ("r", self._trace_fk_right)
            ):
                structs = self.fk_layouts[side].array_structs()
                sn = dict(structs)
                sn["delete"] = jax.ShapeDtypeStruct(
                    (self.capacity,), np.int32
                )
                jax.eval_shape(trace, state_shapes, sn, structs)
        else:
            jax.eval_shape(
                self._trace_step, state_shapes, self.layout.array_structs()
            )
        for i in range(len(self.join_chain)):
            jax.eval_shape(
                lambda st_, ar, i=i: self._trace_table_step(st_, ar, i),
                state_shapes,
                self._table_array_structs(i),
            )

    def _trace_ss_l(self, state, arrays):
        return self._trace_ss_step("l", state, arrays)

    def _trace_ss_r(self, state, arrays):
        return self._trace_ss_step("r", state, arrays)

    def _compile_steps(self) -> None:
        if self.ss_join is not None:
            # no donation: a match-overflow / buffer-overwrite batch is
            # re-run on the pre-step state after growth
            self._ss_l = jax.jit(self._trace_ss_l)
            self._ss_r = jax.jit(self._trace_ss_r)
            self._ss_expire = jax.jit(self._trace_ss_expire)
            return
        # session steps run undonated: a sessions-per-key overflow grows
        # the slot count and re-runs the batch on the pre-step state
        donate = () if self.session else (0,)
        self._step = jax.jit(self._trace_step, donate_argnums=donate)
        self._evict = jax.jit(self._trace_evict, donate_argnums=0)
        if self.join is not None:
            self._table_steps = {
                i: jax.jit(
                    lambda st_, ar, i=i: self._trace_table_step(st_, ar, i),
                    donate_argnums=0,
                )
                for i in range(len(self.join_chain))
            }
            self._table_step = self._table_steps[len(self.join_chain) - 1]
        if self.table_agg:
            self._ta_step = jax.jit(
                self._trace_table_agg_step, donate_argnums=0
            )

    @property
    def state(self) -> Dict[str, jnp.ndarray]:
        if self._state is None:
            self._state = self.init_state()
        return self._state

    @state.setter
    def state(self, value: Dict[str, jnp.ndarray]) -> None:
        self._state = value

    def device_state_bytes(self) -> Dict[str, int]:
        """Live device-state bytes per memory-model component — the
        introspection seam the static footprint model
        (analysis/mem_model.py) is pinned against: sums each state
        array's ``nbytes`` (metadata only, no device sync) grouped by
        the model's one key->component classification."""
        from ksql_tpu.analysis.mem_model import measure_state_bytes

        return measure_state_bytes(self.state, sliced=self.sliced)

    # ------------------------------------------------------------ analysis
    def _analyze(self, step: st.ExecutionStep) -> None:
        cur = step
        if isinstance(cur, (st.StreamSink, st.TableSink)):
            self.sink = cur
            cur = cur.source
        else:
            raise DeviceUnsupported("plan without sink")
        if isinstance(cur, st.TableSuppress):
            self.suppress = True
            cur = cur.source
        while isinstance(cur, (st.TableSelect, st.TableFilter)):
            self.post_ops.append(cur)
            cur = cur.source
        self.post_ops.reverse()
        if isinstance(cur, (st.StreamAggregate, st.StreamWindowedAggregate)):
            self.agg = cur
            cur = cur.source
            if not isinstance(cur, (st.StreamGroupBy, st.StreamGroupByKey)):
                raise DeviceUnsupported(f"aggregate over {type(cur).__name__}")
            self.group = cur
            cur = cur.source
        elif isinstance(cur, st.TableAggregate):
            # table aggregation: every source change undoes the old row's
            # contributions at its old group key and applies the new row's
            # at its new key (KudafUndoAggregator + KudafAggregator)
            if self.suppress:
                raise DeviceUnsupported("suppress over a table aggregation")
            self.agg = cur
            self.table_agg = True
            cur = cur.source
            if not isinstance(cur, st.TableGroupBy):
                raise DeviceUnsupported(
                    f"table aggregate over {type(cur).__name__}"
                )
            self.group = cur
            cur = cur.source
            ops: List[st.ExecutionStep] = []
            while isinstance(cur, (st.TableFilter, st.TableSelect)):
                ops.append(cur)
                cur = cur.source
            ops.reverse()
            self.pre_ops = ops
            if not isinstance(cur, st.TableSource):
                raise DeviceUnsupported(
                    f"table aggregate source {type(cur).__name__} on device"
                )
            self.source = cur
            return
        elif self.post_ops or self.suppress or isinstance(cur, st.TableTableJoin):
            # table-to-table transform (CTAS without aggregation): lower the
            # TableFilter/TableSelect chain as a stateless per-change
            # pipeline; old/new verdicts drive tombstones host-side
            # (TableFilterBuilder/TableSelectBuilder analog)
            if self.suppress:
                raise DeviceUnsupported("suppress without aggregation")
            # post_ops was collected sink-downwards then reversed; its first
            # element's source chain must end at a TableSource (or a
            # pk-equi TableTableJoin of two TableSources)
            chain = list(self.post_ops)
            base = chain[0].source if chain else cur
            if isinstance(base, st.TableTableJoin):
                self._analyze_tt_join(base, chain)
                return
            if isinstance(base, st.ForeignKeyTableTableJoin):
                self._analyze_fk_join(base, chain)
                return
            if not isinstance(base, st.TableSource):
                raise DeviceUnsupported(
                    "table transforms without aggregation over "
                    f"{type(base).__name__ if base is not None else 'nothing'}"
                )
            self.table_mode = True
            self.pre_ops = chain
            self.post_ops = []
            self.source = base
            return
        while isinstance(cur, (st.StreamFilter, st.StreamSelect, st.StreamSelectKey)):
            self.pre_ops.append(cur)
            cur = cur.source
        self.pre_ops.reverse()
        if isinstance(cur, st.StreamFlatMap):
            # UDTF explode: variable fan-out is XLA-hostile, so the flat-map
            # (and anything below it) runs host-side per record and the
            # device pipeline starts at the exploded schema
            self.flatmap = cur
            cur = cur.source
            ops2: List[st.ExecutionStep] = []
            while isinstance(
                cur, (st.StreamFilter, st.StreamSelect, st.StreamSelectKey)
            ):
                ops2.append(cur)
                cur = cur.source
            ops2.reverse()
            self.flatmap_pre_ops = ops2
            if not isinstance(cur, st.StreamSource):
                raise DeviceUnsupported(
                    f"flat-map source {type(cur).__name__} on device"
                )
            self.source = cur
            return
        if isinstance(cur, st.StreamTableJoin):
            # stream-table join (possibly an n-way chain A⋈B⋈C): the stream
            # side keeps flowing through the row pipeline; each table side
            # materializes into its own keyed device store, probed in chain
            # order (StreamTableJoinBuilder analog,
            # ksqldb-streams/.../StreamTableJoinBuilder.java:43)
            from ksql_tpu.parser.ast_nodes import JoinType

            self.mid_ops = self.pre_ops
            chain_rev: List[Tuple] = []  # outermost-first while walking down
            while isinstance(cur, st.StreamTableJoin):
                if cur.join_type not in (JoinType.INNER, JoinType.LEFT):
                    raise DeviceUnsupported(
                        f"{cur.join_type} stream-table join on device"
                    )
                tops: List[st.ExecutionStep] = []
                rcur = cur.right
                while isinstance(
                    rcur, (st.TableSelect, st.TableFilter, st.TableSelectKey)
                ):
                    tops.append(rcur)
                    rcur = rcur.source
                tops.reverse()
                if not isinstance(rcur, st.TableSource):
                    raise DeviceUnsupported(
                        f"join right source {type(rcur).__name__} on device"
                    )
                ops: List[st.ExecutionStep] = []
                lcur = cur.left
                while isinstance(
                    lcur, (st.StreamFilter, st.StreamSelect, st.StreamSelectKey)
                ):
                    ops.append(lcur)
                    lcur = lcur.source
                ops.reverse()
                # `ops` sit between this join and whatever feeds its left
                chain_rev.append((cur, rcur, tops, ops))
                cur = lcur
            if not isinstance(cur, st.StreamSource):
                raise DeviceUnsupported(
                    f"join left source {type(cur).__name__} on device"
                )
            self.source = cur
            # deepest-first probe order; each spec's between_ops run BEFORE
            # its probe (they transform that join's left input)
            for join_step, tsrc, tops, between in reversed(chain_rev):
                self.join_chain.append(
                    _JoinSpec(join_step, tsrc, tops, between)
                )
            topics = [j.table_source.topic for j in self.join_chain]
            if len(set(topics)) != len(topics):
                # two probes of one changelog topic (self-join via aliases)
                # can't be routed topic->probe; the oracle handles it
                raise DeviceUnsupported(
                    "same-topic stream-table join chain on device"
                )
            deepest = self.join_chain[0]
            self.pre_ops = list(deepest.between_ops)
            deepest.between_ops = []
            self.join = self.join_chain[-1].step
            self.table_source = self.join_chain[-1].table_source
            self.table_pre_ops = self.join_chain[-1].table_pre_ops
            return
        if isinstance(cur, st.StreamStreamJoin):
            # stream-stream windowed join: both sides buffer in device ring
            # stores; each incoming batch matches the opposite buffer over
            # the WITHIN window, with klip-36 eager/deferred null-padding
            # (StreamStreamJoinBuilder.java:33,114 analog)
            if self.agg is not None or self.post_ops or self.suppress:
                raise DeviceUnsupported(
                    "aggregation over a stream-stream join on device"
                )
            self.ss_join = cur
            self.mid_ops = self.pre_ops
            for attr, src_attr, ops_attr in (
                ("source", "left", "pre_ops"),
                ("right_source", "right", "right_pre_ops"),
            ):
                c2 = getattr(cur, src_attr)
                ops: List[st.ExecutionStep] = []
                while isinstance(
                    c2, (st.StreamFilter, st.StreamSelect, st.StreamSelectKey)
                ):
                    ops.append(c2)
                    c2 = c2.source
                ops.reverse()
                setattr(self, ops_attr, ops)
                if not isinstance(c2, st.StreamSource):
                    raise DeviceUnsupported(
                        f"join {src_attr} source {type(c2).__name__} on device"
                    )
                setattr(self, attr, c2)
            return
        if isinstance(cur, st.WindowedStreamSource):
            # windowed-topic re-import: rows carry (key, windowStart, end)
            # keys; WINDOWSTART/WINDOWEND ride the batch as value columns
            # and re-attach to emitted rows.  Stateless pipelines only —
            # re-aggregating a windowed stream stays on the oracle.
            if self.agg is not None or self.post_ops or self.suppress:
                raise DeviceUnsupported(
                    "aggregation over a windowed source on device"
                )
            self.windowed_source = True
            self.source = cur
            return
        if not isinstance(cur, st.StreamSource):
            raise DeviceUnsupported(f"device source {type(cur).__name__}")
        self.source = cur

    def _analyze_fk_join(
        self, join: "st.ForeignKeyTableTableJoin", chain
    ) -> None:
        """Foreign-key table-table join: left keyed by its own pk, joined
        on fk(left) = pk(right).  A right change fans out to every left
        row with that fk — a vectorized full scan of the left store's fk
        column (the device reading of the reference's subscription/response
        topology, ForeignKeyTableTableJoinBuilder)."""
        from ksql_tpu.parser.ast_nodes import JoinType

        if join.join_type not in (JoinType.INNER, JoinType.LEFT):
            raise DeviceUnsupported(
                f"{join.join_type} foreign-key join on device"
            )
        self.fk_join = join
        self.pre_ops = chain
        self.post_ops = []
        for side, attr_src, attr_ops in (
            ("left", "fk_left_source", "fk_left_ops"),
            ("right", "fk_right_source", "fk_right_ops"),
        ):
            cur2 = getattr(join, side)
            ops: List[st.ExecutionStep] = []
            while isinstance(cur2, (st.TableSelect, st.TableFilter)):
                ops.append(cur2)
                cur2 = cur2.source
            ops.reverse()
            setattr(self, attr_ops, ops)
            if not isinstance(cur2, st.TableSource):
                raise DeviceUnsupported(
                    f"fk join {side} source {type(cur2).__name__} on device"
                )
            setattr(self, attr_src, cur2)
        if self.fk_left_source.topic == self.fk_right_source.topic:
            raise DeviceUnsupported("same-topic fk join on device")
        if len(join.left.schema.key_columns) != 1:
            raise DeviceUnsupported("multi-column fk-join left key on device")
        self.source = self.fk_left_source

    def _analyze_tt_join(self, join: "st.TableTableJoin", chain) -> None:
        """Primary-key table-table join: both tables materialize into ONE
        two-sided device store keyed by the pk; each change joins against
        the resident other side and flows through the post-join transform
        chain (TableTableJoinBuilder analog)."""
        from ksql_tpu.parser.ast_nodes import JoinType

        if join.join_type not in (JoinType.INNER, JoinType.LEFT,
                                  JoinType.RIGHT, JoinType.OUTER):
            raise DeviceUnsupported(
                f"{join.join_type} table-table join on device"
            )
        self.table_mode = True
        self.tt_join = join
        self.pre_ops = chain  # post-join transforms (per-change pipeline)
        self.post_ops = []
        for side, attr_src, attr_ops in (
            ("left", "tt_left_source", "tt_left_ops"),
            ("right", "tt_right_source", "tt_right_ops"),
        ):
            cur2 = getattr(join, side)
            ops: List[st.ExecutionStep] = []
            while isinstance(cur2, (st.TableSelect, st.TableFilter)):
                ops.append(cur2)
                cur2 = cur2.source
            ops.reverse()
            setattr(self, attr_ops, ops)
            if not isinstance(cur2, st.TableSource):
                raise DeviceUnsupported(
                    f"table-table join {side} source "
                    f"{type(cur2).__name__} on device"
                )
            setattr(self, attr_src, cur2)
        if self.tt_left_source.topic == self.tt_right_source.topic:
            # per-record left/right interleaving of a self-join needs the
            # oracle's port routing; topic->side routing can't express it
            raise DeviceUnsupported("same-topic table-table join on device")
        self.source = self.tt_left_source

    def device_source_schema(self) -> LogicalSchema:
        """Schema of the rows entering the device pipeline: the flat-map's
        exploded schema when one runs host-side, else the source's.
        Windowed sources append WINDOWSTART/WINDOWEND as value columns —
        the executor injects them from each record's windowed key."""
        if self.flatmap is not None:
            return self.flatmap.schema
        if self.windowed_source:
            cached = self.__dict__.get("_windowed_src_schema")
            if cached is None:
                b = LogicalSchema.builder()
                for c in self.source.schema.key_columns:
                    b.key_column(c.name, c.type)
                for c in self.source.schema.value_columns:
                    b.value_column(c.name, c.type)
                b.value_column("WINDOWSTART", T.BIGINT)
                b.value_column("WINDOWEND", T.BIGINT)
                cached = self.__dict__["_windowed_src_schema"] = b.build()
            return cached
        return self.source.schema

    def _pre_agg_schema(self) -> LogicalSchema:
        if self.mid_ops:
            return self.mid_ops[-1].schema
        if self.join is not None:
            return self.join.schema
        return (
            self.pre_ops[-1].schema
            if self.pre_ops
            else self.device_source_schema()
        )

    def _emit_schema(self) -> LogicalSchema:
        """Schema of rows leaving the device (sink schema)."""
        return self.sink.schema

    def _build_ingress_layout(self) -> None:
        """(Re)derive the ingress BatchLayout: only the columns the
        pipeline reads — the primary's own chain, grouping and aggregate
        arguments, plus (shared-prefix pipelines) the union of every
        attached member chain's reads and sink columns.  Re-run on
        prefix-member attach/detach and on shared-partial-set extension;
        the executor reads ``self.layout`` per batch, so a rebuild takes
        effect at the next encode."""
        needed = _refs_of_ops(self.pre_ops) | _refs_of_ops(self.mid_ops)
        scope_exprs: List[ex.Expression] = []
        for s_ in [*self.pre_ops, *self.mid_ops]:
            if hasattr(s_, "predicate"):
                scope_exprs.append(s_.predicate)
            for _n, e_ in getattr(s_, "selects", ()):
                scope_exprs.append(e_)
            for e_ in getattr(s_, "key_expressions", ()):
                scope_exprs.append(e_)
        if self.group is not None:
            for e in getattr(self.group, "group_by_expressions", ()):
                needed.update(ex.referenced_columns(e))
                scope_exprs.append(e)
        for spec in self.agg_specs:
            for e in spec.arg_exprs:
                needed.update(ex.referenced_columns(e))
                scope_exprs.append(e)
        src_schema = self.device_source_schema()
        src_cols = {c.name for c in src_schema.columns()}
        # stateless pipelines need every sink column that maps to a source col
        if self.agg is None:
            needed.update(c.name for c in self._emit_schema().columns())
        for m in self.prefix_members:
            needed |= _refs_of_ops(m.pre_ops)
            for s_ in m.pre_ops:
                if hasattr(s_, "predicate"):
                    scope_exprs.append(s_.predicate)
                for _n, e_ in getattr(s_, "selects", ()):
                    scope_exprs.append(e_)
            needed.update(c.name for c in m.sink_schema.columns())
        needed &= src_cols
        # key columns always ride along (key passthrough in Select)
        needed.update(c.name for c in src_schema.key_columns)
        if self.windowed_source:
            # emitted rows must re-attach the source window
            needed.update(("WINDOWSTART", "WINDOWEND"))
        # struct columns touched ONLY through scalar field paths flatten to
        # synthetic path columns extracted at encode (the struct itself
        # never reaches HBM)
        struct_paths, flattened_roots = _collect_struct_paths(
            scope_exprs, src_schema
        )
        needed -= flattened_roots
        self.layout = BatchLayout(
            src_schema, sorted(needed), self.capacity, self.dictionary,
            struct_paths=struct_paths,
            host_exprs=self._host_exprs,
        )

    # ------------------------------------- host-computed expression columns
    def _having_retract(self) -> bool:
        """Whether this query tracks per-slot HAVING verdicts for
        retraction emission (EMIT CHANGES aggregation with a HAVING
        filter; EMIT FINAL and sessions filter at emission instead)."""
        return (
            not self.suppress
            and not self.session
            and any(isinstance(op, st.TableFilter) for op in self.post_ops)
        )

    def _probe_compilable(self, e, types: Dict[str, SqlType]) -> bool:
        """Can the device expression compiler lower ``e`` over these column
        types?  Probed eagerly on 1-row arrays (construction-time only)."""
        env = {
            name: DCol(
                jnp.zeros((1,), _dtype_of_probe(t)), jnp.zeros((1,), bool), t
            )
            for name, t in types.items()
        }
        try:
            JaxExprCompiler(env, 1, DictionaryServer()).compile(e)
            return True
        except Exception:  # noqa: BLE001 — anything untraceable stays host
            return False

    def _extract_host_exprs(self) -> None:
        """Rewrite source-scope expressions the device cannot lower into
        references to host-computed encode columns.

        The reference evaluates every expression on CPU anyway (Janino
        codegen); here only the expressions XLA cannot express stay on the
        host — the rest of the query remains device-resident.  An
        expression qualifies when every column it references traces back
        unchanged to the physical source row (so encode can evaluate it)."""
        if self.source is None or self.ss_join is not None:
            return

        # DECIMAL note: extraction and decimals compose safely — an
        # extracted expression runs on the host with exact decimal
        # arithmetic, while decimal expressions the device CAN lower keep
        # their existing f64 semantics (documented deviation, ≤15-digit
        # columns only; wider columns still reject at layout build).
        from ksql_tpu.common.schema import PSEUDOCOLUMNS
        from ksql_tpu.runtime.oracle import Compiler as _OracleCompiler

        src_schema = self.device_source_schema()
        src_names = {c.name for c in src_schema.columns()}
        # probe-env types: source columns + pseudocolumns + struct-path
        # synthetic leaves (collected over the original expressions)
        types: Dict[str, SqlType] = {
            c.name: c.type for c in src_schema.columns()
        }
        for n_, t_ in PSEUDOCOLUMNS.items():
            types.setdefault(n_, t_)
        scope: List[ex.Expression] = []
        for op in self.pre_ops:
            scope.append(getattr(op, "predicate", None))
            scope.extend(e2 for _n2, e2 in getattr(op, "selects", ()))
            scope.extend(getattr(op, "key_expressions", ()))
        if self.group is not None:
            scope.extend(getattr(self.group, "group_by_expressions", ()))
        if self.agg is not None:
            for call in self.agg.aggregations:
                scope.extend(call.args)
        for synth, _root, _fields, lt in _collect_struct_paths(
            [e2 for e2 in scope if e2 is not None], src_schema
        )[0]:
            types[synth] = lt
        # name -> source column it still transparently aliases (None = opaque)
        mapping: Dict[str, Optional[str]] = {n2: n2 for n2 in src_names}
        for n2 in PSEUDOCOLUMNS:
            mapping.setdefault(n2, n2)
        oracle_c = _OracleCompiler(self.registry, lambda w, err: None)

        def free_refs(node, scope=frozenset()):
            """Column refs free in ``node`` (lambda params are bound within
            their body only — a same-named OUTER ref stays free)."""
            if isinstance(node, ex.LambdaExpression):
                yield from free_refs(node.body, scope | set(node.params))
                return
            if isinstance(node, ex.ColumnRef):
                if node.name not in scope:
                    yield node
                return
            if isinstance(node, ex.Expression):
                for f in dataclasses.fields(node):
                    yield from free_refs(getattr(node, f.name), scope)
            elif isinstance(node, (list, tuple)):
                for item in node:
                    yield from free_refs(item, scope)

        def try_extract(e):
            """Return a replacement expression, or None to keep ``e``."""
            if e is None or self._probe_compilable(e, types):
                return None
            bound = {
                p
                for node in ex.walk(e)
                if isinstance(node, ex.LambdaExpression)
                for p in node.params
            }
            refs = list(free_refs(e))
            if not refs:
                return None
            if bound & {r.name for r in refs}:
                # a lambda param shadows a FREE outer column of the same
                # name: the name-based rewrite below cannot distinguish
                # them, so this expression stays unextracted
                return None
            sub = {}
            for r in refs:
                if r.source or mapping.get(r.name) is None:
                    return None  # opaque/qualified input: stays unsupported
                sub[r.name] = mapping[r.name]
            rewritten = ex.rewrite(
                e,
                lambda nd: (
                    ex.ColumnRef(name=sub[nd.name], source=None)
                    if isinstance(nd, ex.ColumnRef) and nd.name in sub
                    else nd
                ),
            )
            try:
                compiled = oracle_c.expr(rewritten, src_schema)
            except Exception:  # noqa: BLE001 — let the normal path fail
                return None
            synth = f"__HX{len(self._host_exprs)}"
            self._host_exprs.append((
                synth, compiled, compiled.sql_type or T.STRING,
                tuple(dict.fromkeys(
                    r2.name for r2 in free_refs(rewritten)
                )),
            ))
            types[synth] = compiled.sql_type or T.STRING
            mapping[synth] = None
            return ex.ColumnRef(name=synth, source=None)

        new_pre: List[st.ExecutionStep] = []
        for op in self.pre_ops:
            changed = {}
            if getattr(op, "predicate", None) is not None:
                r = try_extract(op.predicate)
                if r is not None:
                    changed["predicate"] = r
            if getattr(op, "selects", ()):
                new_sel = []
                sel_changed = False
                for alias, e2 in op.selects:
                    r = try_extract(e2)
                    new_sel.append((alias, r if r is not None else e2))
                    sel_changed = sel_changed or r is not None
                if sel_changed:
                    changed["selects"] = tuple(new_sel)
            if getattr(op, "key_expressions", ()):
                new_keys = []
                k_changed = False
                for e2 in op.key_expressions:
                    r = try_extract(e2)
                    new_keys.append(r if r is not None else e2)
                    k_changed = k_changed or r is not None
                if k_changed:
                    changed["key_expressions"] = tuple(new_keys)
            new_op = dataclasses.replace(op, **changed) if changed else op
            new_pre.append(new_op)
            if getattr(op, "selects", ()):
                # projection: downstream names remap through this op
                out_map: Dict[str, Optional[str]] = {}
                out_types: Dict[str, SqlType] = {}
                for c2 in op.schema.key_columns:
                    out_map[c2.name] = mapping.get(c2.name)
                    out_types[c2.name] = c2.type
                for alias, e2 in op.selects:
                    if isinstance(e2, ex.ColumnRef) and not e2.source:
                        out_map[alias] = mapping.get(e2.name)
                    else:
                        out_map[alias] = None
                for c2 in op.schema.columns():
                    out_types[c2.name] = c2.type
                for n2, t2 in PSEUDOCOLUMNS.items():
                    out_map.setdefault(n2, n2)
                    out_types.setdefault(n2, t2)
                # synthetic columns stay visible below the projection
                for s2, _f2, t2, _r2 in self._host_exprs:
                    out_types[s2] = t2
                    out_map.setdefault(s2, None)
                mapping.clear()
                mapping.update(out_map)
                types.clear()
                types.update(out_types)
        self.pre_ops = new_pre
        if self.group is not None:
            exprs = tuple(getattr(self.group, "group_by_expressions", ()))
            if exprs:
                new_g = tuple(
                    (try_extract(e2) or e2) for e2 in exprs
                )
                if new_g != exprs:
                    self.group = dataclasses.replace(
                        self.group, group_by_expressions=new_g
                    )
        if self.agg is not None:
            new_calls = []
            a_changed = False
            for call in self.agg.aggregations:
                new_args = tuple((try_extract(a2) or a2) for a2 in call.args)
                if new_args != call.args:
                    call = dataclasses.replace(call, args=new_args)
                    a_changed = True
                new_calls.append(call)
            if a_changed:
                self.agg = dataclasses.replace(
                    self.agg, aggregations=tuple(new_calls)
                )

    def _build_agg_specs(self) -> None:
        src_schema = self._pre_agg_schema()
        types = {c.name: c.type for c in src_schema.columns()}
        from ksql_tpu.common.schema import PSEUDOCOLUMNS, WINDOW_BOUNDS

        for n, t in {**PSEUDOCOLUMNS, **WINDOW_BOUNDS}.items():
            types.setdefault(n, t)
        for synth, _fn, t, _refs in self._host_exprs:
            types[synth] = t
        resolver = ExpressionCompiler(
            TypeResolver(types), self.registry, lambda w, e: None
        )
        for i, call in enumerate(self.agg.aggregations):
            arg_types = [resolver.compile(a).sql_type for a in call.args]
            udaf = self.registry.udaf(call.function, arg_types)
            if udaf.device_kind is None:
                raise DeviceUnsupported(f"UDAF {call.function} on device")
            if call.distinct:
                raise DeviceUnsupported("DISTINCT aggregation on device")
            rt = udaf.returns
            result_type = rt(arg_types) if callable(rt) else rt
            for t in [*arg_types, result_type]:
                if t.base == SqlBaseType.DECIMAL and (t.precision or 0) > 15:
                    # f64 carries <=15 significant digits exactly; wider
                    # decimal aggregation keeps the (exact) oracle
                    raise DeviceUnsupported("DECIMAL aggregation on device")
            lits: List[object] = []
            if udaf.literal_params:
                from ksql_tpu.execution import expressions as ex2

                for a in call.args[len(call.args) - udaf.literal_params:]:
                    if isinstance(a, (ex2.IntegerLiteral, ex2.LongLiteral)):
                        lits.append(int(a.value))
                    elif isinstance(a, ex2.BooleanLiteral):
                        lits.append(bool(a.value))
                    else:
                        lits.append(None)
            device = compile_device_agg(
                udaf.device_kind, arg_types, result_type, fname=call.function,
                literals=lits,
            )
            if self.session and any(
                c.width > 1 for c in device.components
            ):
                # session segment-merge folds components pairwise; vector
                # state (collect/topk) has no pairwise combine formulation
                raise DeviceUnsupported(
                    f"{call.function} over SESSION windows on device"
                )
            if self.table_agg and device.undo_contribs is None and any(
                c.combine != "add" for c in device.components
            ):
                # table retractions need sign-invertible state: pure 'add'
                # decompositions (count/sum/avg/stddev/correlation) undo by
                # negation, histogram by signed decrement (undo_contribs);
                # min/max/collect/topk keep the oracle
                raise DeviceUnsupported(
                    f"{call.function} over a table aggregation on device"
                )
            self.agg_specs.append(
                _AggSpec(call.function, call.args, device, f"KSQL_AGG_VARIABLE_{i}")
            )
        self.key_types = [c.type for c in self.agg.schema.key_columns]

    # ------------------------------------------------------- stream slicing
    def _agg_components(self) -> List[AggComponent]:
        """Store component list for the aggregate state arrays.  Sliced
        stores widen every (scalar, monoid) component to a per-key ring of
        ``slice_ring`` slice partials; the expansion path keeps the
        per-(key, window) scalar layout."""
        comps: List[AggComponent] = [
            AggComponent("max", "int64", np.iinfo(np.int64).min)
        ]
        for spec in self.agg_specs:
            comps.extend(spec.device.components)
        if self.sliced:
            comps = [
                dataclasses.replace(c, width=self.slice_ring) for c in comps
            ]
        return comps

    def _slice_ineligibility(self, ring_max: int) -> Optional[str]:
        """Why this hopping aggregation must keep the k-fold expansion path
        (None = sliced-eligible).  Every string here is a windowing-shape
        fallback reason the engine counts in ``fallback_reasons``."""
        w = self.window
        if self.suppress:
            return (
                "EMIT FINAL hopping windows keep the expansion path "
                "(per-window close tracking on slices pending)"
            )
        if self._having_retract():
            return (
                "HAVING retraction over hopping windows keeps the "
                "expansion path (per-window verdict state)"
            )
        for spec in self.agg_specs:
            if any(
                c.combine not in _DECOMPOSABLE for c in spec.device.components
            ):
                return (
                    f"non-decomposable aggregate {spec.fname} keeps the "
                    "expansion path (no monoid merge for its device state)"
                )
        if W.hopping_expansion(w.size_ms, w.advance_ms) < 2:
            return (
                "hopping ADVANCE equals SIZE (k=1): the expansion path is "
                "already slice-optimal"
            )
        sw = W.slice_width(w.size_ms, w.advance_ms)
        ring = self.retention_ms // sw + 2
        if ring > ring_max:
            return (
                f"hopping slice ring of {ring} slices exceeds "
                f"ksql.slicing.max.ring={ring_max} (slice width {sw}ms, "
                f"retention {self.retention_ms}ms) — set an explicit GRACE "
                "PERIOD or raise the cap; keeping the expansion path"
            )
        return None

    def _setup_slicing(self, sliced_opt: Optional[bool], ring_max: int) -> None:
        self.sliced = False
        self.slice_width = 0
        self.slice_ring = 0
        self.slice_ring_max = ring_max
        #: widest member retention — drives sliced eviction and admission
        self.family_retention_ms = self.retention_ms or 0
        #: hopping fan-out of the PRIMARY window (EXPLAIN surfaces it even
        #: on the sliced path, where the batch itself no longer expands)
        self.hop_k = self.expansion
        #: why a hopping query stayed on the expansion path (None when
        #: sliced, or not a hopping aggregation at all)
        self.windowing_fallback: Optional[str] = None
        #: fused-tap-residual handoff (ISSUE 12): when armed, _decode_emits
        #: keeps each batch's columnar emit arrays (device-resident, scalar
        #: columns) in last_raw_block for the push registry's batch
        #: listeners — the tap kernel evaluates over them directly instead
        #: of re-encoding the fanned-out host rows
        self.collect_raw_emits = False
        self.last_raw_block: Optional[Dict[str, Any]] = None
        self.members: List[_MemberSpec] = []
        hopping = (
            self.window is not None
            and self.window.window_type == WindowType.HOPPING
        )
        if not hopping:
            if sliced_opt is True:
                raise DeviceUnsupported(
                    "sliced aggregation requires a HOPPING windowed "
                    "aggregation"
                )
            return
        reason = self._slice_ineligibility(ring_max)
        if reason is None and sliced_opt is False:
            reason = (
                "hopping runs the expansion path (slicing disabled for "
                "this executor)"
            )
        if reason is not None:
            if sliced_opt is True:
                raise DeviceUnsupported(reason)
            self.windowing_fallback = reason
            return
        self.sliced = True
        self.expansion = 1  # no k-fold batch blow-up before the shuffle
        w = self.window
        self.slice_width = W.slice_width(w.size_ms, w.advance_ms)
        self.slice_ring = self.retention_ms // self.slice_width + 2
        self.family_retention_ms = self.retention_ms
        self.members = [
            _MemberSpec(
                query_id=None,
                size_ms=w.size_ms,
                advance_ms=w.advance_ms,
                grace_ms=self.grace_ms,
                retention_ms=self.retention_ms,
                agg_schema=self.agg.schema,
                post_ops=list(self.post_ops),
                sink_schema=self._emit_schema(),
                # the primary's own aggregates are the head of the shared
                # (union) partial set; extensions only ever append
                agg_map=list(range(len(self.agg_specs))),
            )
        ]

    # ------------------------------------------------ window-family sharing
    def family_signature(self) -> Optional[tuple]:
        """Hashable identity of this query's window family, or None when
        the shape cannot share a sliced pipeline.  Two queries with equal
        signatures differ only in window (size, advance, grace, retention)
        and post-aggregation projection — they can share one per-(key,
        slice) partial store with per-query combine fan-out."""
        if not self.sliced or self.source is None:
            return None
        if self.join is not None or self.join_chain or self.flatmap is not None:
            return None  # join/table state is per-pipeline; don't share it
        if any(isinstance(op, st.TableFilter) for op in self.post_ops):
            return None  # HAVING members would need per-member verdicts
        pre = tuple(
            (
                type(op).__name__,
                repr(getattr(op, "predicate", None)),
                repr(tuple(getattr(op, "selects", ()))),
                repr(tuple(getattr(op, "key_expressions", ()))),
            )
            for op in self.pre_ops
        )
        group = tuple(
            repr(e)
            for e in getattr(self.group, "group_by_expressions", ())
        )
        aggs = tuple(
            (spec.fname, repr(spec.arg_exprs)) for spec in self.agg_specs
        )
        fmts = getattr(self.source, "formats", None)
        return (
            self.source.topic,
            str(getattr(fmts, "value_format", "")),
            str(getattr(fmts, "key_format", "")),
            pre,
            group,
            aggs,
            tuple(c.type.base for c in self.agg.schema.key_columns),
        )

    def correlated_signature(self) -> Optional[tuple]:
        """The MQO's *correlated-window* grouping key (Factor Windows):
        :meth:`family_signature` minus the aggregate set — same source /
        formats / pre-ops / GROUP BY / key types, ANY sizes, advances and
        aggregates.  Members grouped by this signature share one slice
        ring through the shared (union) partial set."""
        sig = self.family_signature()
        if sig is None:
            return None
        return sig[:5] + sig[6:]  # drop the aggs element

    def agg_signature_keys(self) -> List[tuple]:
        """Identity of each shared aggregate partial — (function, args);
        the unit the shared-partial merge dedupes on."""
        return [(s.fname, repr(s.arg_exprs)) for s in self.agg_specs]

    def plan_family_merge(self, probe: "CompiledDeviceQuery") -> Dict[str, Any]:
        """What attaching ``probe`` would do to this shared pipeline:
        post-gcd slice width, re-priced ring span, the member's agg_map
        into the shared partial set, the genuinely NEW partials, and the
        live store size.  Pure planning — no mutation; shared by
        :meth:`attach_member` and the cost model (planner/mqo.py) so the
        two can never disagree."""
        import math as _math

        w = probe.window
        new_sw = _math.gcd(
            self.slice_width, W.slice_width(w.size_ms, w.advance_ms)
        )
        shared = {k: i for i, k in enumerate(self.agg_signature_keys())}
        agg_map: List[int] = []
        new_specs: List[_AggSpec] = []
        for spec in probe.agg_specs:
            k = (spec.fname, repr(spec.arg_exprs))
            j = shared.get(k)
            if j is None:
                j = len(self.agg_specs) + len(new_specs)
                shared[k] = j
                new_specs.append(spec)
            agg_map.append(j)
        new_ring = (
            max(
                self.retention_ms,
                probe.retention_ms,
                *[m.retention_ms for m in self.members],
            )
            // new_sw
            + 2
        )
        return {
            "width_ms": new_sw,
            "width_changed": new_sw != self.slice_width,
            "ring": new_ring,
            "agg_map": agg_map,
            "new_specs": new_specs,
            "store_rows": self._store_rows(),
        }

    def attach_member(
        self,
        plan: "st.QueryPlan",
        query_id: str,
        deliver: Callable[[List["SinkEmit"]], None],
        probe: Optional["CompiledDeviceQuery"] = None,
    ) -> None:
        """Join ``plan`` (correlated window: same source/pre-ops/GROUP BY,
        any size/advance/aggregate set) onto this sliced pipeline: one
        consumer, one device dispatch per tick, shared (union) partials,
        per-member window combine at emission.  Raises DeviceUnsupported
        when the plan is not family-compatible (the caller then builds it
        a standalone executor) and FamilyAttachRefused for the classified
        runtime refusals (re-gcd or new partials over a non-empty store,
        ring cap).  ``probe`` reuses a caller's analyze-only lowering of
        the same plan instead of re-analyzing."""
        if not self.sliced:
            raise DeviceUnsupported(
                "window-family sharing requires a sliced primary pipeline"
            )
        if probe is None:
            probe = CompiledDeviceQuery(
                plan, self.registry, capacity=1, analyze_only=True,
                slice_ring_max=self.slice_ring_max,
            )
        if not probe.sliced:
            raise DeviceUnsupported(
                probe.windowing_fallback
                or "family member is not sliced-eligible"
            )
        if probe.correlated_signature() != self.correlated_signature():
            raise DeviceUnsupported(
                "window family signature mismatch (source / pre-ops / "
                "GROUP BY / key types must be identical to share a "
                "sliced pipeline)"
            )
        merge = self.plan_family_merge(probe)
        new_sw, new_ring = merge["width_ms"], merge["ring"]
        store_rows = merge["store_rows"]
        if merge["width_changed"] and store_rows:
            raise FamilyAttachRefused(
                "reslice",
                f"window family slice-width change ({self.slice_width}ms "
                f"-> {new_sw}ms) requires an empty slice store "
                f"({store_rows} key slots live) — attach family members "
                "before data flows (or terminate and restart the family)",
                oldWidthMs=self.slice_width, newWidthMs=new_sw,
                storeRows=store_rows,
            )
        if merge["new_specs"] and store_rows:
            raise FamilyAttachRefused(
                "new-partials",
                f"{len(merge['new_specs'])} aggregate partial(s) new to "
                "the shared set require an empty slice store "
                f"({store_rows} key slots live) — already-folded slices "
                "hold no contributions for them",
                newPartials=len(merge["new_specs"]),
                storeRows=store_rows,
            )
        if new_ring > self.slice_ring_max:
            raise FamilyAttachRefused(
                "ring-cap",
                f"window family slice ring of {new_ring} slices exceeds "
                f"ksql.slicing.max.ring={self.slice_ring_max}",
                ring=new_ring, ringMax=self.slice_ring_max,
            )
        spec = _MemberSpec(
            query_id=query_id,
            size_ms=probe.window.size_ms,
            advance_ms=probe.window.advance_ms,
            grace_ms=probe.grace_ms,
            retention_ms=probe.retention_ms,
            agg_schema=probe.agg.schema,
            post_ops=list(probe.post_ops),
            sink_schema=probe._emit_schema(),
            deliver=deliver,
            agg_map=merge["agg_map"],
        )
        # atomic attach: every validation above has passed — mutate, and
        # roll everything back if the re-layout/recompile still raises, so
        # a failed attach can never leave a half-attached member spec
        # producing to the member's sink (nor a torn shared layout)
        snap = (
            list(self.members), self.family_retention_ms,
            list(self.agg_specs), self.layout, self.store_layout,
            self.slice_width, self.slice_ring, self._state,
        )
        # idempotent per query id: a member restart re-attaches in place
        self.members = [m for m in self.members if m.query_id != query_id]
        self.members.append(spec)
        self.family_retention_ms = max(m.retention_ms for m in self.members)
        try:
            if merge["new_specs"]:
                self._extend_shared_specs(merge["new_specs"])
            self._resize_ring(new_sw, max(new_ring, self.slice_ring))
            # eager shape check (the __init__ contract): any aggregate or
            # post-op expression the device cannot lower must surface NOW
            # — at the member's attach — not crash the primary's next tick
            jax.eval_shape(
                self._trace_step, jax.eval_shape(self.init_state),
                self.layout.array_structs(),
            )
        except Exception:
            (self.members, self.family_retention_ms, self.agg_specs,
             self.layout, self.store_layout, self.slice_width,
             self.slice_ring, self._state) = snap
            self._compile_steps()
            raise

    def detach_member(self, query_id: str) -> None:
        """Remove a terminated member; the ring keeps its width (slices
        already folded at the family slice width stay combinable)."""
        before = len(self.members)
        self.members = [m for m in self.members if m.query_id != query_id]
        if len(self.members) != before:
            self.family_retention_ms = max(
                m.retention_ms for m in self.members
            )
            self._compile_steps()

    def shared_member_ids(self) -> List[str]:
        return [m.query_id for m in self.members if m.query_id is not None]

    def _store_empty(self) -> bool:
        return self._store_rows() == 0

    def _store_rows(self) -> int:
        """Live key slots in the slice store (0 = empty; the precondition
        for width changes and shared-partial-set extensions)."""
        if self._state is None:
            return 0
        return int(jnp.sum(self._state["occ"][:-1]))

    def _extend_shared_specs(self, new_specs: List[_AggSpec]) -> None:
        """Grow the shared (union) partial set — empty store only, the
        caller has verified: append the new aggregates' components to the
        store layout, widen the ingress layout to cover their argument
        columns, and drop the (empty) state for lazy re-init at the new
        shapes.  Existing members' agg_maps stay valid: extension only
        ever appends."""
        base = len(self.agg_specs)
        self.agg_specs = list(self.agg_specs) + [
            dataclasses.replace(s, out_name=f"KSQL_AGG_VARIABLE_{base + i}")
            for i, s in enumerate(new_specs)
        ]
        comps = self._agg_components()
        self.store_layout = dataclasses.replace(
            self.store_layout, components=tuple(comps)
        )
        self._build_ingress_layout()
        self._state = None
        # mutate-then-recompile contract (graftlint jit-retrace): the
        # traced steps close over agg_specs/store_layout — re-jit here
        # (idempotent: the attach's _resize_ring recompiles again)
        self._compile_steps()

    def _spec_comp_starts(self) -> List[int]:
        """Starting store-component index of each shared aggregate spec
        (component 0 is the per-slot ts watermark)."""
        starts: List[int] = []
        idx = 1
        for spec in self.agg_specs:
            starts.append(idx)
            idx += len(spec.device.components)
        return starts

    # ------------------------------------------- shared source prefixes
    def prefix_signature(self) -> Optional[tuple]:
        """Hashable identity of this pipeline's shareable source prefix,
        or None when the shape cannot share a source scan: stateless
        Filter/Select chains over a plain StreamSource with a stream
        sink.  Members grouped by this signature run as residual branches
        of ONE shared device step (planner/mqo.py decides whether they
        should)."""
        if (
            self.agg is not None or self.join is not None or self.join_chain
            or self.ss_join is not None or self.tt_join is not None
            or self.fk_join is not None or self.flatmap is not None
            or self.table_mode or self.windowed_source or self.suppress
            or self.source is None or not isinstance(self.sink, st.StreamSink)
        ):
            return None
        if self._host_exprs:
            # host-computed encode columns are per-pipeline; a shared
            # layout cannot carry every member's host closures
            return None
        if any(
            not isinstance(op, (st.StreamFilter, st.StreamSelect))
            for op in self.pre_ops
        ):
            return None  # SelectKey repartitions don't share a scan
        fmts = getattr(self.source, "formats", None)
        src_schema = getattr(self.source, "schema", None)
        return (
            "prefix",
            self.source.topic,
            str(getattr(fmts, "value_format", "")),
            str(getattr(fmts, "key_format", "")),
            # the full declared source schema: two streams over ONE topic
            # with same-named differently-typed columns (a legitimate
            # multi-stream-per-topic pattern) must never share a scan —
            # the shared ingress layout encodes per the primary's types
            # and the member would decode garbage
            tuple(
                (c.name, repr(c.type))
                for c in (src_schema.columns() if src_schema else ())
            ),
            str(getattr(self.source, "timestamp_column", None)),
            str(getattr(self.source, "timestamp_format", None)),
        )

    def attach_prefix_member(
        self,
        plan: "st.QueryPlan",
        query_id: str,
        deliver: Callable[[List["SinkEmit"]], None],
        probe: Optional["CompiledDeviceQuery"] = None,
    ) -> None:
        """Join a compatible stateless query onto this pipeline's shared
        source prefix: the member's filter/project chain becomes a
        residual branch of the shared device step (its suffix past the
        common prefix), its rows delivered through ``deliver`` to its own
        sink.  Stateless — re-layout + recompile are always safe."""
        if probe is None:
            probe = CompiledDeviceQuery(
                plan, self.registry, capacity=1, analyze_only=True,
            )
        sig = probe.prefix_signature()
        if sig is None or sig != self.prefix_signature():
            raise DeviceUnsupported(
                "source-prefix signature mismatch (stateless "
                "filter/project chain over the same source topic and "
                "formats required to share a scan)"
            )
        spec = _PrefixMemberSpec(
            query_id=query_id,
            pre_ops=list(probe.pre_ops),
            sink_schema=probe._emit_schema(),
            deliver=deliver,
        )
        old = list(self.prefix_members)
        # idempotent per query id: a member restart re-attaches in place
        self.prefix_members = [
            m for m in self.prefix_members if m.query_id != query_id
        ]
        self.prefix_members.append(spec)
        try:
            self._rebuild_prefix_plumbing()
        except Exception:
            self.prefix_members = old
            self._rebuild_prefix_plumbing()
            raise

    def detach_prefix_member(self, query_id: str) -> None:
        """Remove a terminated prefix member and shrink the shared layout
        back to the surviving chains."""
        before = len(self.prefix_members)
        self.prefix_members = [
            m for m in self.prefix_members if m.query_id != query_id
        ]
        if len(self.prefix_members) != before:
            self._rebuild_prefix_plumbing()

    def shared_prefix_member_ids(self) -> List[str]:
        return [m.query_id for m in self.prefix_members]

    def _rebuild_prefix_plumbing(self) -> None:
        """Recompute the shared prefix (longest structurally-common run of
        leading steps across the primary's and every member's chain),
        widen the ingress layout to the union of reads, recompile, and
        eagerly shape-check so an unlowerable member residual surfaces at
        attach, not on the primary's next tick."""
        chains = [self.pre_ops] + [m.pre_ops for m in self.prefix_members]
        shared = 0
        if self.prefix_members:
            limit = min(len(c) for c in chains)
            while shared < limit:
                fps = {_op_fingerprint(c[shared]) for c in chains}
                if len(fps) != 1:
                    break
                shared += 1
        self._prefix_shared_len = shared
        self._build_ingress_layout()
        self._compile_steps()
        jax.eval_shape(
            self._trace_step, jax.eval_shape(self.init_state),
            self.layout.array_structs(),
        )

    #: host mirrors driving pre-dispatch ring sizing: a LOWER bound on the
    #: device stream clock (read back with the per-batch load counters) and
    #: the oldest slice index any batch could have written
    _mirror_max_ts: int = -(2 ** 62)
    _host_min_slice: int = 2 ** 62

    def ensure_ring_for(self, ts: np.ndarray, valid: np.ndarray) -> None:
        """Pre-dispatch ring sizing: the ring must span every slice that is
        simultaneously LIVE this batch — from the admission floor (the
        oldest slice a still-open window can cover: stream time − family
        retention) up to the batch's newest slice — or two live slices
        would fold into one ring cell.  Timestamps are host-visible before
        dispatch, and the floor is conservatively bounded by host mirrors
        (a lagging lower bound on the device stream clock, and the oldest
        slice ever sent), so growth here is exact-or-conservative and the
        in-trace horizon cut only ever fires past the hard
        ksql.slicing.max.ring cap."""
        if not self.sliced or ts.size == 0:
            return
        v = np.asarray(valid, bool)
        if not v.any():
            return
        tt = np.asarray(ts)[v]
        width = self.slice_width
        smin = int(tt.min()) // width
        smax = int(tt.max()) // width
        self._host_min_slice = min(self._host_min_slice, smin)
        floor = self._host_min_slice
        if self._mirror_max_ts > -(2 ** 61):
            # the admission cut in-trace uses the batch-START stream clock:
            # anything below clock − retention never reaches a ring cell,
            # so the ring need not span it (an ancient replayed record in
            # an old batch must not keep the sizing pinned wide forever)
            floor = max(
                floor,
                (self._mirror_max_ts - self.family_retention_ms) // width,
            )
        needed = smax - min(floor, smax) + 2
        target = min(needed, self.slice_ring_max)
        if needed > self.slice_ring and target != self.slice_ring:
            # skip the no-op resize once pinned at the cap: _resize_ring
            # recompiles unconditionally (load-bearing for attach/detach),
            # and a per-batch retrace would collapse throughput
            self._resize_ring(self.slice_width, target)
        # after THIS batch folds, the device clock is ≥ the batch max —
        # advance the mirror host-side so the next batch's floor is tight
        # even before (or without) a device readback
        self._mirror_max_ts = max(self._mirror_max_ts, int(tt.max()))

    def _resize_ring(self, new_sw: int, new_ring: int) -> None:
        """Re-shape the slice ring for a changed family (slice width and/or
        ring span).  Live partials are remapped host-side by their absolute
        slice index; a width change only happens on an empty store (checked
        by the caller), so no partial ever needs splitting."""
        width_changed = new_sw != self.slice_width
        ring_changed = new_ring != self.slice_ring
        self.slice_width = new_sw
        self.slice_ring = new_ring
        if ring_changed or width_changed:
            self.store_layout = dataclasses.replace(
                self.store_layout,
                components=tuple(
                    dataclasses.replace(c, width=new_ring)
                    for c in self.store_layout.components
                ),
            )
            if self._state is not None and not self._store_empty():
                self._regrow_ring(new_ring)
            else:
                self._state = None  # lazy re-init at the new shapes
        self._compile_steps()

    def _regrow_ring(self, new_ring: int) -> None:
        """Host-side ring regrow: every live (slot, slice) partial moves to
        ``slice_id % new_ring`` in the widened arrays (new_ring >= the live
        span, so no two live slices of one key collide)."""
        old = {
            k: np.asarray(v) for k, v in jax.device_get(dict(self.state)).items()
        }
        new = dict(old)
        ids = old["slice_id"]
        live = ids >= 0
        rix, cix = np.nonzero(live)
        npos = (ids[rix, cix] % new_ring).astype(np.int64)
        c1 = ids.shape[0]
        nid = np.full((c1, new_ring), -1, np.int64)
        nid[rix, npos] = ids[rix, cix]
        new["slice_id"] = nid
        for j, comp in enumerate(self.store_layout.components):
            col = old[f"a{j}"]
            ncol = np.full(
                (c1, new_ring), comp.init, dtype=np.dtype(comp.dtype)
            )
            ncol[rix, npos] = col[rix, cix]
            new[f"a{j}"] = ncol
        # jnp.array (copy), not asarray: rebuilt host buffers must never be
        # zero-copy aliased into donated jit state
        self.state = {k: jnp.array(v) for k, v in new.items()}

    # ----------------------------------------------- sliced fold + combine
    @jax.named_scope("scatter_combine")
    def _sliced_scatter(
        self,
        store: Dict[str, jnp.ndarray],
        slots: jnp.ndarray,
        payload: Dict[str, jnp.ndarray],
        contribs: Sequence[jnp.ndarray],
    ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
        """Fold per-row contributions into each key slot's slice ring, the
        occupied chunks of ``_SLICED_CHUNK`` lanes in lane order: (store,
        lanes visited).  The ring arrays are carried from chunk to chunk
        flat, ring position by ring position (a cell at ``ring_pos * slots
        + slot``): the TPU scatters by index into one-dimensional arrays
        in place, and what turns a (slots, ring) array into one and back
        is a pass over the whole array, as the chip lays it out a ring
        position at a time (0.9 + 0.9 ms a 32-bit half of a 2^21 x 8
        array on a v5e, 1.8 + 0.9 slot by slot; inside the chunk loop XLA
        makes that pass once a visit: PERF.md, PR 35) — so it is made
        here, once a step."""
        n = int(slots.shape[0])
        width = min(n, _SLICED_CHUNK)
        lanes = (slots, payload["active"], payload["wstart"], *contribs)
        cells = (*(f"a{j}" for j in range(len(contribs))), "slice_id")
        rings = {name: store[name].T.reshape(-1) for name in cells}
        rings.update(slast=store["slast"], dirty=store["dirty"])
        if n == width:
            rings = self._fold_lanes(rings, *lanes)
            visited = jnp.sum(slots * 0) + n
        else:
            lanes = [_pad_lanes(x, width) for x in lanes]

            def visit(lo, rings):
                return self._fold_lanes(rings, *(
                    jax.lax.dynamic_slice_in_dim(x, lo, width) for x in lanes
                ))

            rings, visited = _visit_occupied_chunks(
                lanes[1], width, rings, visit
            )
        rings.update(
            (name, rings[name].reshape(store[name].shape[::-1]).T)
            for name in cells
        )
        return {**store, **rings}, visited

    def _fold_lanes(
        self,
        rings: Dict[str, jnp.ndarray],
        slots: jnp.ndarray,
        active: jnp.ndarray,
        wstart: jnp.ndarray,
        *contribs: jnp.ndarray,
    ) -> Dict[str, jnp.ndarray]:
        """Fold the lanes given into cell ``(slice_index % slice_ring) *
        slots + slot`` of the flat ring arrays.  A targeted cell whose
        stored slice_id differs is a recycled cell from an earlier ring
        wrap: it resets to the component inits first (idempotent — every
        batch row targeting one cell carries the SAME slice index,
        guaranteed by the pre_exchange ring-wrap horizon cut).  The cell's
        slice_id is read from, and written to, the rings handed in: of two
        chunks of one batch that target one recycled cell the second finds
        it current and leaves the first's contributions in it."""
        rings = dict(rings)
        dump = jnp.int32(self.store_capacity)
        ring = self.slice_ring
        sidx = wstart // self.slice_width  # absolute slice index
        eff = jnp.where(active, slots, dump)
        live = active & (slots != dump)
        c1 = self.store_capacity + 1
        dump_cell = _flat_cell(0, dump, ring, c1)
        cell = _flat_cell(jnp.remainder(sidx, ring), eff, ring, c1)
        stale = live & (rings["slice_id"][cell] != sidx)
        tgt_stale = jnp.where(stale, cell, dump_cell)
        for j, comp in enumerate(self.store_layout.components):
            col = rings[f"a{j}"]
            init = jnp.asarray(comp.init, col.dtype)
            # duplicate cell writers all write the same init value, so
            # the unordered scatter-set stays deterministic
            col = col.at[tgt_stale].set(init)
            ref = col.at[cell]
            contrib = contribs[j]
            if comp.combine == "add":
                col = ref.add(contrib.astype(col.dtype))
            elif comp.combine == "min":
                col = ref.min(contrib.astype(col.dtype))
            else:  # 'max' — _slice_ineligibility admits only the monoids
                col = ref.max(contrib.astype(col.dtype))
            rings[f"a{j}"] = col
        tgt_live = jnp.where(live, cell, dump_cell)
        rings["slice_id"] = rings["slice_id"].at[tgt_live].set(sidx)
        rings["slast"] = rings["slast"].at[eff].max(
            jnp.where(live, wstart, -(2 ** 62))
        )
        dirty = rings["dirty"].at[eff].set(True)
        rings["dirty"] = dirty.at[self.store_capacity].set(False)
        return rings

    def _combine_windows(
        self,
        store: Dict[str, jnp.ndarray],
        slot_lane: jnp.ndarray,
        w_lane: jnp.ndarray,
        member: _MemberSpec,
    ) -> Tuple[Dict[str, DCol], jnp.ndarray, jnp.ndarray]:
        """Monoid-merge the covering slices of each (slot, window) lane and
        finalize into an expression env over the aggregate schema.

        ``w_lane`` is the window start in SLICE units; the window covers
        slices ``w .. w + spw - 1``.  A ring cell whose slice_id mismatches
        the expected absolute index reads as the component init (identity),
        which is how empty and recycled cells drop out of the merge."""
        nn = int(slot_lane.shape[0])
        S = W.slices_per_window(member.size_ms, self.slice_width)
        t = jnp.arange(S, dtype=jnp.int64)
        slice_ids = w_lane[:, None] + t[None, :]  # (nn, S)
        pos = jnp.remainder(slice_ids, self.slice_ring).astype(jnp.int32)
        slot2 = slot_lane[:, None]
        idok = store["slice_id"][slot2, pos] == slice_ids
        view: Dict[str, jnp.ndarray] = {}
        for j, comp in enumerate(self.store_layout.components):
            col = store[f"a{j}"][slot2, pos]  # (nn, S)
            init = jnp.asarray(comp.init, col.dtype)
            colm = jnp.where(idok, col, init)
            if comp.combine == "add":
                view[f"a{j}"] = jnp.sum(colm, axis=1)
            elif comp.combine == "min":
                view[f"a{j}"] = jnp.min(colm, axis=1)
            else:  # 'max'
                view[f"a{j}"] = jnp.max(colm, axis=1)
        view["knull"] = store["knull"][slot_lane]
        view["wstart"] = w_lane * self.slice_width
        for i in range(len(self.key_types)):
            view[f"key{i}"] = store[f"key{i}"][slot_lane]
        ident = jnp.arange(nn, dtype=jnp.int32)
        return self._finalized_env(
            view, ident, nn, wsize_ms=member.size_ms,
            agg_schema=member.agg_schema, agg_map=member.agg_map,
        )

    def _member_emit(
        self,
        env: Dict[str, DCol],
        row_ts: jnp.ndarray,
        dec_exceeded: jnp.ndarray,
        mask: jnp.ndarray,
        member: _MemberSpec,
        nn: int,
    ) -> Dict[str, jnp.ndarray]:
        """Post-aggregation ops + emission packing for one family member.
        Sliced pipelines never carry HAVING-retraction state (ineligible),
        so TableFilter here only narrows the mask."""
        for op in member.post_ops:
            c = JaxExprCompiler(env, nn, self.dictionary)
            if isinstance(op, st.TableFilter):
                pred = c.compile(op.predicate)
                mask = mask & pred.valid & pred.data.astype(bool)
            else:  # TableSelect
                new_env: Dict[str, DCol] = {}
                src_keys = [k.name for k in op.source.schema.key_columns]
                out_keys = [k.name for k in op.schema.key_columns]
                for new_name, old_name in zip(out_keys, src_keys):
                    if old_name in env:
                        new_env[new_name] = env[old_name]
                for name, e in op.selects:
                    new_env[name] = c.compile(e)
                for p in ("ROWTIME", "WINDOWSTART", "WINDOWEND"):
                    if p in env:
                        new_env[p] = env[p]
                env = new_env
        emits = self._pack_emits(
            env, mask, row_ts, schema=member.sink_schema
        )
        emits["dec_envelope"] = jnp.sum(
            (dec_exceeded & mask).astype(jnp.int64)
        ).reshape(1)
        return emits

    @jax.named_scope("emit_compact")
    def _sliced_member_emits(
        self,
        store: Dict[str, jnp.ndarray],
        slots: jnp.ndarray,
        payload: Dict[str, jnp.ndarray],
        member: _MemberSpec,
        max_ts_pre: jnp.ndarray,
    ) -> Dict[str, jnp.ndarray]:
        """One member's per-batch emission: every still-open window of this
        member covering a touched slice emits one coalesced change (the
        expansion path's one-change-per-(key, window)-per-batch cadence,
        at O(touched · k) combine lanes instead of O(rows · k) state
        lanes).  The emit columns are ``k × n`` lanes, lane ``hop * n +
        row``; a step wider than ``_SLICED_CHUNK`` claims and then combines
        the occupied chunks of the batch, ``k × _SLICED_CHUNK`` lanes a
        visit, and leaves the lanes of the others masked out and zero."""
        n = int(slots.shape[0])
        S = W.slices_per_window(member.size_ms, self.slice_width)
        k = W.hopping_expansion(member.size_ms, member.advance_ms)
        nn = n * k
        width = min(n, _SLICED_CHUNK)
        rows = [
            _pad_lanes(x, width)
            for x in (slots, payload["active"], payload["wstart"])
        ]

        def chunk_lanes(lo):
            return self._window_lanes(
                *(jax.lax.dynamic_slice_in_dim(x, lo, width) for x in rows),
                member, max_ts_pre, lo, n,
            )

        def claim_lanes(lo, claim):
            _slot_lane, _w_lane, _mask, cell, lane_idx = chunk_lanes(lo)
            return claim.at[cell].min(lane_idx)

        # one lane per distinct (slot, window) — two touched slices of one
        # key can cover the same window — and of those the lowest lane
        # index of the whole batch, as a stable sort by (slot, window)
        # would pick: every lane claims before any is combined.  Claimed
        # by scatter-min into a cell per (slot, window), not sorted: XLA's
        # TPU sort of these nn lanes (6 operand words) took 3 minutes to
        # compile at the engine's default capacity.  The ring-wrap cut in
        # pre_exchange keeps a batch's live slices within ring - 2 of each
        # other and a window starts at most S - 1 slices before a slice it
        # covers, so one slot's masked windows span fewer than ring + S
        # slices and `window mod (ring + S)` names each of them apart.  The
        # cells lie in one flat array, which the TPU scatters into as it is.
        zero = jnp.sum(slots * 0)  # varying under shard_map, as the rows
        claim = zero + jnp.full(
            (self.store_capacity + 1) * (self.slice_ring + S), nn, jnp.int32
        )
        if n == width:
            claim = claim_lanes(0, claim)
        else:
            claim, _ = _visit_occupied_chunks(
                rows[1], width, claim, claim_lanes
            )

        def emit_lanes(lo):
            slot_lane, w_lane, mask, cell, lane_idx = chunk_lanes(lo)
            winner = mask & (claim[cell] == lane_idx)
            env, row_ts, dec_exceeded = self._combine_windows(
                store, slot_lane, w_lane, member
            )
            return self._member_emit(
                env, row_ts, dec_exceeded, winner, member, k * width
            )

        if n == width:
            return emit_lanes(0)
        n_pad = int(rows[1].shape[0])

        def emit_chunk(lo, emits):
            out = {}
            for name, lanes in emit_lanes(lo).items():
                if name == "dec_envelope":
                    out[name] = emits[name] + lanes
                    continue
                # the chunk's k * width lanes are a (k, width) block of
                # the (k, n) column
                at = (jnp.int32(0), lo) + (jnp.int32(0),) * (lanes.ndim - 1)
                out[name] = jax.lax.dynamic_update_slice(
                    emits[name],
                    lanes.reshape((k, width) + lanes.shape[1:]), at,
                )
            return out

        shapes = jax.eval_shape(emit_lanes, jax.ShapeDtypeStruct((), jnp.int32))
        emits = {
            name: jnp.broadcast_to(
                zero,
                shape.shape if name == "dec_envelope"
                else (k, n_pad) + shape.shape[1:],
            ).astype(shape.dtype)
            for name, shape in shapes.items()
        }
        emits, _ = _visit_occupied_chunks(rows[1], width, emits, emit_chunk)
        return {
            name: lanes if name == "dec_envelope"
            else lanes[:, :n].reshape((nn,) + lanes.shape[2:])
            for name, lanes in emits.items()
        }

    def _window_lanes(
        self,
        slots: jnp.ndarray,
        active: jnp.ndarray,
        wstart: jnp.ndarray,
        member: _MemberSpec,
        max_ts_pre: jnp.ndarray,
        lo: Any,
        n: int,
    ) -> Tuple[jnp.ndarray, ...]:
        """The ``k`` window lanes of each row given, hop by hop: (slot,
        window start in slice units, mask of the still-open windows that
        cover the row's slice, the (slot, window)'s claim cell — the dump
        slot's for a lane masked out —, the lane's index ``hop * n + lo +
        row`` in a batch of ``n`` rows of which these start at ``lo``)."""
        dump = jnp.int32(self.store_capacity)
        rows = int(slots.shape[0])
        width = self.slice_width
        S = W.slices_per_window(member.size_ms, width)
        A = member.advance_ms // width
        k = W.hopping_expansion(member.size_ms, member.advance_ms)
        sidx = wstart // width
        newest = sidx - jnp.remainder(sidx, A)  # newest covering window
        hops = jnp.repeat(jnp.arange(k, dtype=jnp.int64), rows)
        w_lane = jnp.tile(newest, k) - hops * A  # window start, slice units
        s_lane = jnp.tile(sidx, k)
        slot_lane = jnp.tile(slots, k)
        act_lane = jnp.tile(active & (slots != dump), k)
        covers = (w_lane + S > s_lane) & (w_lane >= 0)
        open_w = (
            w_lane * width + member.size_ms + member.grace_ms > max_ts_pre
        )
        mask = act_lane & covers & open_w
        cells = self.slice_ring + S
        cell = _flat_cell(
            jnp.remainder(w_lane, cells), jnp.where(mask, slot_lane, dump),
            cells, self.store_capacity + 1,
        )
        lane_idx = (
            hops.astype(jnp.int32) * n + lo
            + jnp.tile(jnp.arange(rows, dtype=jnp.int32), k)
        )
        return slot_lane, w_lane, mask, cell, lane_idx

    # ----------------------------------------------------------- state mgmt
    def changelog_dirty_state(self) -> Dict[str, Any]:
        """Dirty-set seam for the incremental changelog journal
        (runtime/changelog.py): one commit-point host capture in
        checkpoint-serde shape.  The journal diffs consecutive captures,
        so only the ring/agg/join cells a tick actually touched reach
        the frame."""
        from ksql_tpu.runtime.checkpoint import _snapshot_device

        return _snapshot_device(self)

    def changelog_apply_state(self, data: Dict[str, Any]) -> None:
        """Inverse of changelog_dirty_state — restore a (possibly
        journal-patched) capture.  Host arrays are copied on the way in
        (_unflatten_state uses jnp.array) so journal-decoded buffers
        never alias donated jit state."""
        from ksql_tpu.runtime.checkpoint import _restore_device

        _restore_device(self, data)

    def init_state(self) -> Dict[str, jnp.ndarray]:
        if self.store_layout is None:
            state = {"max_ts": jnp.array(np.iinfo(np.int64).min, jnp.int64)}
            if self.tt_join is not None:
                state["ttab"] = self._init_tt_store()
            if self.fk_join is not None:
                state["fkl"] = self._init_fk_store("l")
                state["fkr"] = self._init_fk_store("r")
            for i in range(len(self.join_chain)):
                state[self._jtab_key(i)] = self._init_table_store(i)
            if self.ss_join is not None:
                b1 = self.ss_capacity + 1
                for s in ("l", "r"):
                    state[f"ss{s}_ts"] = jnp.zeros(b1, jnp.int64)
                    state[f"ss{s}_krepr"] = jnp.zeros(b1, jnp.int64)
                    state[f"ss{s}_kval"] = jnp.zeros(b1, bool)
                    state[f"ss{s}_live"] = jnp.zeros(b1, bool)
                    state[f"ss{s}_matched"] = jnp.zeros(b1, bool)
                    state[f"ss{s}_seq"] = jnp.zeros(b1, jnp.int64)
                    for col in self.ss_cols[s]:
                        state[f"ss{s}_v_{col.name}"] = jnp.zeros(
                            b1, self._table_col_dtype(col)
                        )
                        state[f"ss{s}_m_{col.name}"] = jnp.zeros(b1, bool)
                    state[f"ss{s}_cursor"] = jnp.zeros((), jnp.int64)
                    state[f"ss{s}_smax"] = jnp.array(
                        np.iinfo(np.int64).min, jnp.int64
                    )
            return state
        state = init_store(self.store_layout)
        if self.sliced:
            c1 = self.store_capacity + 1
            # absolute slice index stored per ring cell (-1 = empty); a
            # gather whose expected index mismatches reads as identity —
            # that is how stale cells from a previous ring wrap die
            state["slice_id"] = jnp.full(
                (c1, self.slice_ring), -1, jnp.int64
            )
            # newest slice start folded per key slot (drives eviction)
            state["slast"] = jnp.full(c1, -(2 ** 62), jnp.int64)
        if self._needs_seq:
            state["agg_seq"] = jnp.zeros((), jnp.int64)
        if self._having_retract():
            # per-slot "previously passed HAVING": a pass->fail transition
            # on an EMIT CHANGES table emits a tombstone (the oracle's
            # HAVING retraction semantics, TableFilterBuilder)
            state["hpass"] = jnp.zeros(self.store_capacity + 1, bool)
        if self.session:
            c1 = self.store_capacity + 1
            state["sess_start"] = jnp.zeros(c1, jnp.int64)
            state["sess_end"] = jnp.zeros(c1, jnp.int64)
        for i in range(len(self.join_chain)):
            state[self._jtab_key(i)] = self._init_table_store(i)
        if self.suppress:
            # EMIT FINAL emission clock: stream time over ALL source records
            # (even rows later dropped by filters / null group keys), matching
            # the oracle executor's stream_time; `max_ts` (the aggregate's
            # clock, post-filter rows only) keeps driving late-record drops
            state["emit_clock"] = jnp.array(np.iinfo(np.int64).min, jnp.int64)
            # first-touch order per slot: ties in final-emission order (same
            # window end) break by window creation order, as the oracle's
            # insertion-ordered buffer does
            state["born"] = jnp.full(
                self.store_capacity + 1, np.iinfo(np.int64).max, jnp.int64
            )
            state["row_clock"] = jnp.zeros((), jnp.int64)
            # a window emits its final result exactly once: late-but-in-grace
            # records may re-dirty an emitted slot (the oracle accepts them
            # into state but its `emitted` set blocks re-emission)
            state["emitted"] = jnp.zeros(self.store_capacity + 1, bool)
        return state

    # --------------------------------------------- join table store (device)
    def _table_col_dtype(self, col) -> Any:
        return np.int64 if col.type.base in _HASHED else col.type.device_dtype()

    def _init_table_store(self, idx: int = -1) -> Dict[str, jnp.ndarray]:
        """Device table store for one join probe's right side: a keyed hash
        store (pk repr in key0) whose per-column value arrays are
        overwritten last-write-wins — the RocksDB-materialized KTable analog
        (SourceBuilderBase forced materialization)."""
        jspec = self.join_chain[idx]
        lay = StoreLayout(capacity=jspec.capacity, num_keys=1, components=())
        s = init_store(lay)
        c1 = jspec.capacity + 1
        for col in jspec.cols:
            s[f"v_{col.name}"] = jnp.zeros(c1, self._table_col_dtype(col))
            s[f"m_{col.name}"] = jnp.zeros(c1, bool)
        return s

    def _table_array_structs(self, idx: int = -1) -> Dict[str, Any]:
        out = self.join_chain[idx].layout.array_structs()
        out["delete"] = jax.ShapeDtypeStruct((self.capacity,), np.bool_)
        return out

    def _trace_table_step(
        self, state: Dict[str, jnp.ndarray], arrays: Dict[str, jnp.ndarray],
        idx: int = -1,
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """Fold one batch of table-changelog records into one join probe's
        device table store.  Upserts overwrite last-write-wins (one winner
        per slot per batch); tombstones free the slot (grave — probe chains
        stay intact until the host rebuild compacts)."""
        jspec = self.join_chain[idx]
        key = self._jtab_key(idx)
        n = self.capacity
        env = self._source_env(arrays, jspec.layout)
        active = arrays["row_valid"]
        env, active = self._apply_ops(jspec.table_pre_ops, env, active, n)
        c = JaxExprCompiler(env, n, self.dictionary)
        kcol = c.compile(jspec.step.right_key)
        krepr = _repr64(kcol)
        khash = combine_hash([krepr])
        act = active & kcol.valid
        cap_t = jspec.capacity
        dump = jnp.int32(cap_t)
        zeros64 = jnp.zeros(n, jnp.int64)
        jt, slots, rounds, lane_rounds = probe_insert(
            dict(state[key]), cap_t, khash, zeros64, [krepr],
            jnp.zeros(n, jnp.int32), act,
        )
        rowidx = jnp.arange(n, dtype=jnp.int32)
        last = jnp.full(cap_t + 1, -1, jnp.int32).at[
            jnp.where(act, slots, dump)
        ].max(rowidx)
        winner = act & (slots != dump) & (last[slots] == rowidx)
        delete = arrays["delete"]
        up = winner & ~delete
        tgt = jnp.where(up, slots, dump)
        for col in jspec.cols:
            d = env[col.name]
            dt = self._table_col_dtype(col)
            jt[f"v_{col.name}"] = jt[f"v_{col.name}"].at[tgt].set(
                d.data.astype(dt)
            )
            jt[f"m_{col.name}"] = jt[f"m_{col.name}"].at[tgt].set(d.valid)
        dl = winner & delete
        tgtd = jnp.where(dl, slots, dump)
        occ = jt["occ"].at[tgtd].set(False).at[cap_t].set(False)
        grave = jt["grave"].at[tgtd].set(True).at[cap_t].set(False)
        # deleted-then-reinserted within a batch resolved by the winner; a
        # delete winner leaves a grave, a later batch's insert reclaims it
        jt["occ"], jt["grave"] = occ, grave
        state = dict(state)
        state[key] = jt
        metrics = {
            "occupancy": jnp.sum(occ | grave),
            "overflow": jt["overflow"],
            "probe_rounds": rounds,
            "probe_lane_rounds": lane_rounds,
        }
        return state, metrics

    def _jtabs_of(self, state) -> Dict[str, Dict[str, jnp.ndarray]]:
        """The chain's join stores keyed by their state names."""
        return {
            self._jtab_key(i): state[self._jtab_key(i)]
            for i in range(len(self.join_chain))
        }

    def _jtab_key(self, idx: int) -> str:
        """State key for probe ``idx``: the outermost store keeps the legacy
        name 'jtab' (distributed replication + checkpoints address it);
        inner probes of an n-way chain get 'jtab<i>'."""
        if idx < 0:
            idx += len(self.join_chain)
        return "jtab" if idx == len(self.join_chain) - 1 else f"jtab{idx}"

    # ------------------------------------------------- table aggregation
    def _ta_side(
        self, store: Dict[str, jnp.ndarray], arrays: Dict[str, jnp.ndarray],
        undo: bool,
    ):
        """One side of a table-aggregation step: pre-ops + group keys +
        (sign-adjusted) contributions folded into the store.  Undo probes
        find-only (a missing group means the old row never aggregated)."""
        n = self.capacity
        cap = self.store_capacity
        dump = jnp.int32(cap)
        env = self._source_env(arrays)
        active = arrays["row_valid"]
        env, active = self._apply_ops(self.pre_ops, env, active, n)
        ts = arrays["ts"]
        c = JaxExprCompiler(env, n, self.dictionary)
        group_exprs = tuple(getattr(self.group, "group_by_expressions", ()))
        if group_exprs:
            key_cols = [c.compile(e) for e in group_exprs]
        else:
            key_cols = [env[col.name] for col in self.group.schema.key_columns]
        reprs = [_repr64(kc) for kc in key_cols]
        knull = jnp.zeros(n, jnp.int32)
        for i, kc in enumerate(key_cols):
            knull = knull | (~kc.valid).astype(jnp.int32) << i
        active = active & (knull == 0)
        khash = combine_hash(reprs + [knull.astype(jnp.int64)])
        contribs: List[jnp.ndarray] = [
            jnp.where(active, ts, np.iinfo(np.int64).min)
        ]
        for spec in self.agg_specs:
            args = [c.compile(e) for e in spec.arg_exprs]
            if undo and spec.device.undo_contribs is not None:
                cs = spec.device.undo_contribs(args, active)
            else:
                cs = spec.device.contribs(args, active, None)
                if undo:
                    cs = [-x for x in cs]  # all-'add': undo = negate
            contribs.extend(cs)
        zeros64 = jnp.zeros(n, jnp.int64)
        if undo:
            slots, _ = probe_find(store, cap, khash, zeros64, active)
            active = active & (slots != dump)
        else:
            store, slots, _, _ = probe_insert(
                store, cap, khash, zeros64, reprs, knull, active
            )
        slot_or_dump = jnp.where(active, slots, dump)
        store = scatter_combine(
            store, self.store_layout, slot_or_dump, contribs,
            # removal (negative vec heads, collect_list undo) traces only
            # into the undo side — the apply side never carries them
            vec_undo=undo,
        )
        return store, slot_or_dump, active, ts

    def _trace_table_agg_step(
        self,
        state: Dict[str, jnp.ndarray],
        a_new: Dict[str, jnp.ndarray],
        a_old: Dict[str, jnp.ndarray],
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """Aggregate one batch of table changes: undo old rows, apply new
        rows, emit one change per touched group per side — the batched
        KGroupedTable subtractor/adder (KudafUndoAggregator analog)."""
        store = dict(state)
        n = self.capacity
        store, slots_old, act_old, ts_old = self._ta_side(store, a_old, True)
        e_old = self._emit_agg(
            store, slots_old,
            winners_per_slot(slots_old, act_old, self.store_capacity),
            n, ts_override=ts_old,
        )
        store, slots_new, act_new, ts_new = self._ta_side(store, a_new, False)
        e_new = self._emit_agg(
            store, slots_new,
            winners_per_slot(slots_new, act_new, self.store_capacity),
            n, ts_override=ts_new,
        )
        emits = {
            k: jnp.concatenate([e_old[k], e_new[k]]) for k in e_old
        }
        neg = np.iinfo(np.int64).min
        batch_max = jnp.maximum(
            jnp.max(jnp.where(act_old, ts_old, neg)),
            jnp.max(jnp.where(act_new, ts_new, neg)),
        )
        store["max_ts"] = jnp.maximum(store["max_ts"], batch_max)
        emits["occupancy"] = jnp.sum(store["occ"] | store["grave"])
        emits["graves"] = jnp.sum(store["grave"])
        emits["overflow"] = store["overflow"]
        return store, emits

    def _upsert_side(
        self, store, cols, env, touched, slots, has_new, act_valid, cap,
        prefix: str = "", live_key: str = "live",
    ):
        """Last-writer-wins upsert of one side's columns + liveness (shared
        by the table-table and fk join store updates); returns the write
        targets so callers can add side-specific columns (e.g. fk reprs)."""
        dump = jnp.int32(cap)
        n = touched.shape[0]
        rowidx = jnp.arange(n, dtype=jnp.int32)
        found = slots != dump
        last = jnp.full(cap + 1, -1, jnp.int32).at[
            jnp.where(touched, slots, dump)
        ].max(rowidx)
        winner = touched & found & (last[slots] == rowidx)
        up = winner & has_new
        tgt = jnp.where(up, slots, dump)
        for col in cols:
            d = env[col.name]
            dt = self._table_col_dtype(col)
            store[f"{prefix}v_{col.name}"] = store[
                f"{prefix}v_{col.name}"
            ].at[tgt].set(d.data.astype(dt))
            store[f"{prefix}m_{col.name}"] = store[
                f"{prefix}m_{col.name}"
            ].at[tgt].set(d.valid & act_valid)
        live = store[live_key].at[tgt].set(True)
        tgtd = jnp.where(winner & ~has_new, slots, dump)
        live = live.at[tgtd].set(False).at[cap].set(False)
        store[live_key] = live
        return tgt

    # ------------------------------------------------- foreign-key join
    def _init_fk_store(self, side: str) -> Dict[str, jnp.ndarray]:
        """Keyed store for one fk-join side; the left side also carries its
        fk repr (scanned on right changes) and both carry liveness."""
        lay = StoreLayout(
            capacity=self.fk_store_capacity, num_keys=1, components=()
        )
        s = init_store(lay)
        c1 = self.fk_store_capacity + 1
        s["live"] = jnp.zeros(c1, bool)
        if side == "l":
            s["fkrepr"] = jnp.zeros(c1, jnp.int64)
            s["fkvalid"] = jnp.zeros(c1, bool)
        for col in self.fk_cols[side]:
            s[f"v_{col.name}"] = jnp.zeros(c1, self._table_col_dtype(col))
            s[f"m_{col.name}"] = jnp.zeros(c1, bool)
        return s

    def _fk_env(
        self, side: str, arrays: Dict[str, jnp.ndarray]
    ) -> Tuple[Dict[str, DCol], jnp.ndarray]:
        ops = self.fk_left_ops if side == "l" else self.fk_right_ops
        env = self._source_env(arrays, self.fk_layouts[side])
        active = arrays["row_valid"]
        return self._apply_ops(ops, env, active, self.capacity)

    def _fk_joined(
        self, lenv: Dict[str, DCol], l_present: jnp.ndarray,
        renv: Dict[str, DCol], r_present: jnp.ndarray, n: int,
    ) -> Tuple[Dict[str, DCol], jnp.ndarray]:
        """Joined env + validity: INNER needs both sides, LEFT pads right."""
        from ksql_tpu.parser.ast_nodes import JoinType

        env: Dict[str, DCol] = {}
        for col in self.fk_cols["l"]:
            d = lenv[col.name]
            env[col.name] = DCol(d.data, d.valid & l_present, col.type)
        for col in self.fk_cols["r"]:
            d = renv[col.name]
            env[col.name] = DCol(d.data, d.valid & r_present, col.type)
        if self.fk_join.join_type == JoinType.INNER:
            jok = l_present & r_present
        else:  # LEFT
            jok = l_present
        return env, jok

    def _trace_fk_left(self, state, a_new, a_old):
        """One batch of LEFT-table changes: update the left store (pk, fk,
        columns), join old/new rows against the resident right row for
        their fk, run the transform chain, emit rows/tombstones."""
        n = self.capacity
        cap = self.fk_store_capacity
        dump = jnp.int32(cap)
        fkl = dict(state["fkl"])
        fkr = state["fkr"]
        env_new, act_new = self._fk_env("l", a_new)
        env_old, act_old = self._fk_env("l", a_old)
        has_new = a_new["delete"] == 0
        has_old = a_old["row_valid"]
        key_col = self.fk_join.left.schema.key_columns[0]
        kcol = env_new[key_col.name]
        krepr = _repr64(kcol)
        khash = combine_hash([krepr])
        touched = a_new["row_valid"] & kcol.valid
        zeros64 = jnp.zeros(n, jnp.int64)
        fkl, slots, _, _ = probe_insert(
            fkl, cap, khash, zeros64, [krepr], jnp.zeros(n, jnp.int32),
            touched,
        )
        cfk = JaxExprCompiler(env_new, n, self.dictionary)
        fk_new = cfk.compile(self.fk_join.foreign_key_expression)
        cfo = JaxExprCompiler(env_old, n, self.dictionary)
        fk_old = cfo.compile(self.fk_join.foreign_key_expression)

        def right_of(fk):
            rh = combine_hash([_repr64(fk)])
            rslots, _ = probe_find(
                fkr, cap, rh, jnp.zeros(n, jnp.int64), fk.valid
            )
            rfound = fk.valid & (rslots != dump) & fkr["live"][rslots]
            renv = {
                col.name: DCol(
                    fkr[f"v_{col.name}"][rslots],
                    fkr[f"m_{col.name}"][rslots] & rfound,
                    col.type,
                )
                for col in self.fk_cols["r"]
            }
            return renv, rfound

        renv_old, rok_old = right_of(fk_old)
        renv_new, rok_new = right_of(fk_new)
        l_old = act_old & has_old
        l_new = act_new & a_new["row_valid"] & has_new
        jenv_old, jok_old = self._fk_joined(env_old, l_old, renv_old, rok_old, n)
        jenv_new, jok_new = self._fk_joined(env_new, l_new, renv_new, rok_new, n)
        for out_key in self.fk_join.schema.key_columns:
            # the result key is the left pk: valid even for delete rows
            jenv_old[out_key.name] = kcol
            jenv_new[out_key.name] = kcol
        fenv_new, fok_new = self._apply_ops(self.pre_ops, jenv_new, jok_new, n)
        _, fok_old = self._apply_ops(self.pre_ops, jenv_old, jok_old, n)
        # a left-row delete forwards a (null, null) change; it survives to
        # the sink as a tombstone only through a filter-free chain (the
        # oracle's FilterNode drops a change neither side of which passes)
        if any(isinstance(op, st.TableFilter) for op in self.pre_ops):
            left_delete = jnp.zeros(n, bool)
        else:
            left_delete = a_new["row_valid"] & ~has_new & has_old
        tgt = self._upsert_side(
            fkl, self.fk_cols["l"], env_new, touched, slots, has_new,
            act_new, cap,
        )
        fkl["fkrepr"] = fkl["fkrepr"].at[tgt].set(_repr64(fk_new))
        fkl["fkvalid"] = fkl["fkvalid"].at[tgt].set(fk_new.valid)
        state = dict(state)
        state["fkl"] = fkl
        emits = self._pack_emits(
            fenv_new, fok_new | fok_old | left_delete, a_new["ts"]
        )
        emits["tombstone"] = ~fok_new
        emits["occupancy"] = jnp.sum(fkl["occ"] | fkl["grave"])
        emits["overflow"] = fkl["overflow"] + fkr["overflow"]
        return state, emits

    def _trace_fk_right(self, state, a_new, a_old):
        """One RIGHT-table change (per-record): update the right store,
        then fan out over every resident left row whose fk matches —
        a vectorized scan of the left store's fk column."""
        n = self.capacity
        cap = self.fk_store_capacity
        dump = jnp.int32(cap)
        fkr = dict(state["fkr"])
        fkl = state["fkl"]
        env_new, act_new = self._fk_env("r", a_new)
        env_old, act_old = self._fk_env("r", a_old)
        has_new = a_new["delete"] == 0
        has_old = a_old["row_valid"]
        key_col = self.fk_join.right.schema.key_columns[0]
        kcol = env_new[key_col.name]
        krepr = _repr64(kcol)
        khash = combine_hash([krepr])
        touched = a_new["row_valid"] & kcol.valid
        zeros64 = jnp.zeros(n, jnp.int64)
        fkr, slots, _, _ = probe_insert(
            fkr, cap, khash, zeros64, [krepr], jnp.zeros(n, jnp.int32),
            touched,
        )
        # store update first: the fan-out reads left rows, not the right
        # store (old/new right values come from this change)
        self._upsert_side(
            fkr, self.fk_cols["r"], env_new, touched, slots, has_new,
            act_new, cap,
        )
        state = dict(state)
        state["fkr"] = fkr
        # ---- fan-out over the left store (per-record: row 0 is the change)
        m = cap + 1
        match = (
            fkl["live"]
            & fkl["fkvalid"]
            & (fkl["fkrepr"] == krepr[0])
            & touched[0]
        )
        lenv = {
            col.name: DCol(
                fkl[f"v_{col.name}"], fkl[f"m_{col.name}"] & match, col.type
            )
            for col in self.fk_cols["l"]
        }

        def bcast(env_side, present_row):
            return (
                {
                    col.name: DCol(
                        jnp.broadcast_to(d.data[:1], (m,) + d.data.shape[1:]),
                        jnp.broadcast_to(d.valid[:1], (m,)) & present_row,
                        col.type,
                    )
                    for col in self.fk_cols["r"]
                    for d in (env_side[col.name],)
                },
                jnp.broadcast_to(present_row, (m,)),
            )

        renv_old, r_old_p = bcast(env_old, (act_old & has_old)[:1])
        renv_new, r_new_p = bcast(
            env_new, (act_new & a_new["row_valid"] & has_new)[:1]
        )
        jenv_old, jok_old = self._fk_joined(lenv, match, renv_old, r_old_p, m)
        jenv_new, jok_new = self._fk_joined(lenv, match, renv_new, r_new_p, m)
        lkey_t = self.fk_join.left.schema.key_columns[0].type
        lkey = DCol(self._decode_key64(fkl["key0"], lkey_t), match, lkey_t)
        for out_key in self.fk_join.schema.key_columns:
            jenv_old[out_key.name] = lkey
            jenv_new[out_key.name] = lkey
        fenv_new, fok_new = self._apply_ops(self.pre_ops, jenv_new, jok_new, m)
        _, fok_old = self._apply_ops(self.pre_ops, jenv_old, jok_old, m)
        ts = jnp.broadcast_to(a_new["ts"][:1], (m,))
        emits = self._pack_emits(fenv_new, fok_new | fok_old, ts)
        emits["tombstone"] = ~fok_new
        emits["occupancy"] = jnp.sum(fkr["occ"] | fkr["grave"])
        emits["overflow"] = fkl["overflow"] + fkr["overflow"]
        return state, emits

    def process_fk(
        self, side: str, new_batch: HostBatch, old_batch: HostBatch,
        deletes: np.ndarray, has_old: np.ndarray,
    ) -> List[SinkEmit]:
        """Host entry for one single-side batch of fk-join changes (right
        changes run one record per step: the fan-out is store-wide)."""
        if not hasattr(self, "_fk_steps"):
            self._fk_steps = {
                "l": jax.jit(self._trace_fk_left, donate_argnums=0),
                "r": jax.jit(self._trace_fk_right, donate_argnums=0),
            }
        layout = self.fk_layouts[side]
        a_new = layout.encode(new_batch)
        a_old = layout.encode(old_batch)
        pad = np.zeros(self.capacity, np.int32)
        pad[: len(deletes)] = deletes
        a_new["delete"] = pad
        ho = np.zeros(self.capacity, bool)
        ho[: len(has_old)] = has_old
        a_old["row_valid"] = ho
        ov_before = int(self.state["fkl"]["overflow"]) + int(
            self.state["fkr"]["overflow"]
        )
        self.state, emits = self._fk_steps[side](self.state, a_new, a_old)
        if int(emits["overflow"]) > ov_before:
            raise QueryRuntimeException(
                "device fk-join store overflowed; "
                f"capacity={self.fk_store_capacity}"
            )
        if (
            int(emits["occupancy"]) + self.capacity
            > 0.75 * self.fk_store_capacity
        ):
            self._grow_fk()
        out = self._decode_emits(emits, sort=False)
        if side == "r":
            # the oracle fans out in repr-sorted left-key order
            from ksql_tpu.functions.udafs import _hashable

            out.sort(key=lambda e2: repr((_hashable(
                e2.key[0] if len(e2.key) == 1 else e2.key
            ), e2.key)))
        return out

    def _grow_fk(self, factor: int = 2) -> None:
        self.fk_store_capacity *= factor
        self._rebuild_keyed_store(
            "fkl", self.fk_store_capacity, lambda: self._init_fk_store("l")
        )
        self._rebuild_keyed_store(
            "fkr", self.fk_store_capacity, lambda: self._init_fk_store("r")
        )
        if hasattr(self, "_fk_steps"):
            del self._fk_steps

    # ------------------------------------------------- table-table join
    def _init_tt_store(self) -> Dict[str, jnp.ndarray]:
        """Two-sided keyed store for a pk table-table join: one slot per
        pk holds BOTH tables' resident rows + per-side liveness — the
        device analog of the two materialized KTables the reference joins
        (TableTableJoinBuilder)."""
        lay = StoreLayout(
            capacity=self.tt_store_capacity, num_keys=1, components=()
        )
        s = init_store(lay)
        c1 = self.tt_store_capacity + 1
        for side in ("l", "r"):
            s[f"{side}_live"] = jnp.zeros(c1, bool)
            for col in self.tt_cols[side]:
                s[f"{side}_v_{col.name}"] = jnp.zeros(
                    c1, self._table_col_dtype(col)
                )
                s[f"{side}_m_{col.name}"] = jnp.zeros(c1, bool)
        return s

    def _tt_joined_env(
        self, side: str, env_s: Dict[str, DCol], present_s: jnp.ndarray,
        tt: Dict[str, jnp.ndarray], slots: jnp.ndarray, found: jnp.ndarray,
    ) -> Tuple[Dict[str, DCol], jnp.ndarray]:
        """(joined env, join-valid mask) for one side's change rows against
        the resident other side."""
        from ksql_tpu.parser.ast_nodes import JoinType

        other = "r" if side == "l" else "l"
        o_live = tt[f"{other}_live"][slots] & found
        env: Dict[str, DCol] = {}
        for col in self.tt_cols[side]:
            d = env_s.get(col.name)
            if d is None:
                raise DeviceUnsupported(
                    f"join column {col.name} not on device"
                )
            env[col.name] = DCol(d.data, d.valid & present_s, col.type)
        for col in self.tt_cols[other]:
            env[col.name] = DCol(
                tt[f"{other}_v_{col.name}"][slots],
                tt[f"{other}_m_{col.name}"][slots] & o_live,
                col.type,
            )
        jt = self.tt_join.join_type
        l_p = present_s if side == "l" else o_live
        r_p = present_s if side == "r" else o_live
        if jt == JoinType.INNER:
            jok = l_p & r_p
        elif jt == JoinType.LEFT:
            jok = l_p
        elif jt == JoinType.RIGHT:
            jok = r_p
        else:  # OUTER
            jok = l_p | r_p
        # the join result's key column carries the pk (valid even when the
        # present side is the other one — the change key is always known)
        key_expr = (
            self.tt_join.left_key if side == "l" else self.tt_join.right_key
        )
        kcol = JaxExprCompiler(env_s, self.capacity, self.dictionary).compile(
            key_expr
        )
        for out_key in self.tt_join.schema.key_columns:
            env[out_key.name] = kcol
        return env, jok

    def _trace_tt_step(
        self, state, a_new, a_old, side: str,
    ):
        """One batch of side ``side`` table changes: update the side's
        resident columns, join old/new rows against the other side, run the
        post-join transform chain on both, and emit rows / tombstones with
        the oracle's TableChange semantics."""
        n = self.capacity
        cap = self.tt_store_capacity
        dump = jnp.int32(cap)
        layout = self.tt_layouts[side]
        ops = self.tt_left_ops if side == "l" else self.tt_right_ops
        key_expr = (
            self.tt_join.left_key if side == "l" else self.tt_join.right_key
        )
        tt = dict(state["ttab"])

        def side_env(arrays):
            env = self._source_env(arrays, layout)
            active = arrays["row_valid"]
            return self._apply_ops(ops, env, active, n)

        env_new, act_new = side_env(a_new)
        env_old, act_old = side_env(a_old)
        has_new = a_new["delete"] == 0
        # the change key comes from the NEW batch's key columns (key-only
        # rows for deletes), so every change row can probe
        c = JaxExprCompiler(env_new, n, self.dictionary)
        kcol = c.compile(key_expr)
        krepr = _repr64(kcol)
        khash = combine_hash([krepr])
        touched = a_new["row_valid"] & kcol.valid
        zeros64 = jnp.zeros(n, jnp.int64)
        tt, slots, _, _ = probe_insert(
            tt, cap, khash, zeros64, [krepr], jnp.zeros(n, jnp.int32), touched
        )
        found = slots != dump
        # joined envs BEFORE the side update (the other side is untouched
        # by this single-side batch; the s side reads its own change rows)
        jenv_old, jok_old = self._tt_joined_env(
            side, env_old, act_old & a_old["row_valid"], tt, slots, found
        )
        jenv_new, jok_new = self._tt_joined_env(
            side, env_new, act_new & a_new["row_valid"] & has_new,
            tt, slots, found,
        )
        # post-join transform chain: full pipeline on new, verdict on old
        fenv_new, fok_new = self._apply_ops(self.pre_ops, jenv_new, jok_new, n)
        _, fok_old = self._apply_ops(self.pre_ops, jenv_old, jok_old, n)
        # side update: last writer per slot wins; a delete clears liveness
        self._upsert_side(
            tt, self.tt_cols[side], env_new, touched, slots, has_new,
            act_new, cap, prefix=f"{side}_", live_key=f"{side}_live",
        )
        state = dict(state)
        state["ttab"] = tt
        ts = a_new["ts"]
        emits = self._pack_emits(fenv_new, fok_new | fok_old, ts)
        emits["tombstone"] = ~fok_new
        emits["occupancy"] = jnp.sum(tt["occ"] | tt["grave"])
        emits["overflow"] = tt["overflow"]
        return state, emits

    def process_tt(
        self, side: str, new_batch: HostBatch, old_batch: HostBatch,
        deletes: np.ndarray, has_old: np.ndarray,
    ) -> List[SinkEmit]:
        """Host entry for one single-side batch of table-table-join
        changes."""
        if not hasattr(self, "_tt_steps"):
            self._tt_steps = {
                s: jax.jit(
                    lambda st_, an, ao, s=s: self._trace_tt_step(st_, an, ao, s),
                    donate_argnums=0,
                )
                for s in ("l", "r")
            }
        layout = self.tt_layouts[side]
        a_new = layout.encode(new_batch)
        a_old = layout.encode(old_batch)
        pad = np.zeros(self.capacity, np.int32)
        pad[: len(deletes)] = deletes
        a_new["delete"] = pad
        ho = np.zeros(self.capacity, bool)
        ho[: len(has_old)] = has_old
        a_old["row_valid"] = ho
        ov_before = int(self.state["ttab"]["overflow"])
        self.state, emits = self._tt_steps[side](self.state, a_new, a_old)
        if int(emits["overflow"]) > ov_before:
            raise QueryRuntimeException(
                "device table-table join store overflowed; "
                f"capacity={self.tt_store_capacity}"
            )
        if int(emits["occupancy"]) + self.capacity > 0.75 * self.tt_store_capacity:
            self._grow_tt()
        return self._decode_emits(emits, sort=False)

    def _grow_tt(self, factor: int = 2) -> None:
        """Double the two-sided join store (host rebuild + recompile)."""
        self.tt_store_capacity *= factor
        self._rebuild_keyed_store(
            "ttab", self.tt_store_capacity, self._init_tt_store
        )
        if hasattr(self, "_tt_steps"):
            del self._tt_steps  # shapes changed: recompile on next batch

    def process_table(
        self, batch: HostBatch, deletes: np.ndarray, idx: int = -1
    ) -> None:
        """Host entry for one table-side micro-batch (rows + tombstone
        mask) of join probe ``idx``."""
        if idx < 0:
            idx += len(self.join_chain)
        jspec = self.join_chain[idx]
        with tracing.span("batch.assemble"):
            arrays = jspec.layout.encode(batch)
            pad = np.zeros(self.capacity, bool)
            pad[: len(deletes)] = deletes
            arrays["delete"] = pad
        with tracing.span("step.dispatch"):
            _note_transfer("h2d_bytes", arrays)
            self.state, metrics = self._table_steps[idx](self.state, arrays)
        with tracing.span("step.wait", wait=True):
            # one blocking read of the step's four load scalars: the host
            # waits for the step here, and for nothing else
            load = {k: int(v) for k, v in jax.device_get(metrics).items()}
        tracing.counter(
            "table.upsert", rows=batch.num_rows, steps=1,
            probe_rounds=load["probe_rounds"],
            probe_lane_rounds=load["probe_lane_rounds"],
        )
        if load["overflow"] > jspec.seen_overflow:
            jspec.seen_overflow = load["overflow"]
            raise QueryRuntimeException(
                f"device join-table store overflowed ({load['overflow']} "
                "rows); growth failed to keep pace with key cardinality"
            )
        if load["occupancy"] + self.capacity > 0.75 * jspec.capacity:
            with tracing.span("table.grow"):
                self._grow_table(idx=idx)
            tracing.counter("table.upsert", grows=1)

    _table_seen_overflow = 0

    def _rebuild_keyed_store(self, state_key: str, capacity: int, init_fn) -> None:
        """Host-side rebuild of a keyed sub-store into fresh arrays of
        ``capacity``: live slots re-insert (numpy probe), per-slot columns
        follow, scalars (overflow counters) carry over.  Shared by the
        join-table and table-table-join growth paths."""
        state = dict(self.state)
        old = {
            k: np.asarray(v)
            for k, v in jax.device_get(state.pop(state_key)).items()
        }
        new = {k: np.array(v) for k, v in jax.device_get(init_fn()).items()}
        live = np.nonzero(old["occ"][:-1])[0]
        if live.size:
            from ksql_tpu.ops.hash_store import host_insert

            slots = host_insert(
                new["occ"], new["khash"], new["wstart"], capacity,
                old["khash"][live], old["wstart"][live],
            )
            for name in old:
                if name in ("occ", "khash", "wstart") or old[name].ndim == 0:
                    continue
                new[name][slots] = old[name][live]
        for name in old:
            if old[name].ndim == 0:  # overflow, max_ts
                new[name] = old[name]
        # jnp.array (copy) — a zero-copy view over the host rebuild buffer
        # would alias memory the next (donating) step hands to XLA to
        # recycle while numpy still owns it: intermittent heap corruption
        state[state_key] = {k: jnp.array(v) for k, v in new.items()}
        self.state = state

    def _grow_table(self, factor: int = 2, idx: int = -1) -> None:
        """Double one join-table store: host-side rebuild, then recompile
        (the step functions capture the capacity as a static)."""
        if idx < 0:
            idx += len(self.join_chain)
        jspec = self.join_chain[idx]
        jspec.capacity *= factor
        if idx == len(self.join_chain) - 1:
            self.table_store_capacity = jspec.capacity
        self._rebuild_keyed_store(
            self._jtab_key(idx), jspec.capacity,
            lambda: self._init_table_store(idx),
        )
        self._compile_steps()

    def _apply_join(
        self, env: Dict[str, DCol], active: jnp.ndarray, n: int,
        jtabs: Dict[str, Dict[str, jnp.ndarray]],
        stats: Optional[Dict[str, jnp.ndarray]] = None,
    ) -> Tuple[Dict[str, DCol], jnp.ndarray]:
        """Per-row probe of each join store in chain order (an n-way join is
        a sequence of probes with its between-ops applied before each):
        gather right-side columns for matches; INNER drops non-matches,
        LEFT null-pads (StreamTableJoinNode semantics, oracle.py).  Into
        ``stats``, where given, go the probes' own counts, summed over the
        chain: ``find_rounds`` of ``probe_find``'s loop, ``join_rows``
        probed and ``join_matched`` found, each an int32 scalar."""
        from ksql_tpu.parser.ast_nodes import JoinType

        for idx, jspec in enumerate(self.join_chain):
            env, active = self._apply_ops(jspec.between_ops, env, active, n)
            jtab = jtabs[self._jtab_key(idx)]
            c = JaxExprCompiler(env, n, self.dictionary)
            kcol = c.compile(jspec.step.left_key)
            krepr = _repr64(kcol)
            khash = combine_hash([krepr])
            look = active & kcol.valid
            cap_t = jspec.capacity
            slots, rounds = probe_find(
                jtab, cap_t, khash, jnp.zeros(n, jnp.int64), look
            )
            found = look & (slots != cap_t)
            if stats is not None:
                for name, count in (
                    ("find_rounds", rounds),
                    ("join_rows", jnp.sum(look)),
                    ("join_matched", jnp.sum(found)),
                ):
                    stats[name] = stats.get(name, 0) + count.astype(jnp.int32)
            if jspec.step.join_type == JoinType.INNER:
                active = found
            for col in jspec.cols:
                data = jtab[f"v_{col.name}"][slots]
                valid = jtab[f"m_{col.name}"][slots] & found
                env[col.name] = DCol(data, valid, col.type)
            # the right side's pk column (stored as the probe key repr)
            for kc in jspec.step.right.schema.key_columns:
                kdata = jtab["key0"][slots]
                if kc.type.base in (SqlBaseType.DOUBLE, SqlBaseType.DECIMAL):
                    kdata = jax.lax.bitcast_convert_type(kdata, jnp.float64)
                elif kc.type.base not in _HASHED:
                    kdata = kdata.astype(kc.type.device_dtype())
                env[kc.name] = DCol(kdata, found, kc.type)
            # the join result's key column carries the join key value
            for out_key in jspec.step.schema.key_columns:
                env[out_key.name] = kcol
        return env, active

    # ----------------------------------------- stream-stream join (device)
    def _decode_key64(self, data: jnp.ndarray, sql_type: SqlType) -> jnp.ndarray:
        if sql_type.base in (SqlBaseType.DOUBLE, SqlBaseType.DECIMAL):
            return jax.lax.bitcast_convert_type(data, jnp.float64)
        if sql_type.base not in _HASHED:
            return data.astype(sql_type.device_dtype())
        return data

    def ss_routing_hash(
        self, side: str, arrays: Dict[str, jnp.ndarray]
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(join-key group hash, post-filter active) per row of one ss-join
        side — the shard router for the distributed path (both sides of a
        key must land on the ring buffers of one shard; rows this side's
        pre-op filters drop must not burn exchange bucket slots)."""
        n = arrays["row_valid"].shape[0]
        layout = self.layout if side == "l" else self.right_layout
        pre = self.pre_ops if side == "l" else self.right_pre_ops
        env = self._source_env(arrays, layout)
        env, active = self._apply_ops(pre, env, arrays["row_valid"], n)
        key_expr = self.ss_join.left_key if side == "l" else self.ss_join.right_key
        kcol = JaxExprCompiler(env, n, self.dictionary).compile(key_expr)
        return combine_hash([_repr64(kcol)]), active

    @jax.named_scope("ss_join_match")
    def _trace_ss_step(
        self, side: str, state: Dict[str, jnp.ndarray],
        arrays: Dict[str, jnp.ndarray],
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """One batch of side ``side`` against the opposite ring buffer.

        Vectorized WITHIN-window equi-match (n×B mask → static-size nonzero
        compaction), eager null-padding for legacy LEFT/OUTER, buffer
        insertion with overwrite-loss accounting.  Oracle parity: matching
        sees the buffer *before* this batch's expiry (the executor runs the
        expire kernel after, as OracleExecutor._advance_time does)."""
        ss = self.ss_join
        n = arrays["row_valid"].shape[0]  # >= capacity post-exchange
        layout = self.layout if side == "l" else self.right_layout
        pre = self.pre_ops if side == "l" else self.right_pre_ops
        env = self._source_env(arrays, layout)
        active = arrays["row_valid"]
        env, active = self._apply_ops(pre, env, active, n)
        key_expr = ss.left_key if side == "l" else ss.right_key
        c = JaxExprCompiler(env, n, self.dictionary)
        kcol = c.compile(key_expr)
        krepr = _repr64(kcol)
        ts = arrays["ts"]
        o = "r" if side == "l" else "l"
        B = self.ss_capacity
        b1 = B + 1
        ots = state[f"ss{o}_ts"]
        key_eq = (
            (krepr[:, None] == state[f"ss{o}_krepr"][None, :])
            & kcol.valid[:, None]
            & state[f"ss{o}_kval"][None, :]
        )
        if side == "l":
            tw = (ts[:, None] - self.ss_before <= ots[None, :]) & (
                ots[None, :] <= ts[:, None] + self.ss_after
            )
        else:
            tw = (ots[None, :] - self.ss_before <= ts[:, None]) & (
                ts[:, None] <= ots[None, :] + self.ss_after
            )
        m = active[:, None] & state[f"ss{o}_live"][None, :] & key_eq & tw
        total = jnp.sum(m)
        oc = self.ss_out_cap
        (flat,) = jnp.nonzero(m.reshape(-1), size=oc, fill_value=0)
        mvalid = jnp.arange(oc) < total
        mi = (flat // b1).astype(jnp.int32)
        mj = (flat % b1).astype(jnp.int32)
        row_matched = jnp.any(m, axis=1)
        # running stream times (per row, record order): global for pad
        # timing, per-side for store admission — the oracle's
        # stream_time/side_max split
        neg64 = np.iinfo(np.int64).min
        cm_global = jnp.maximum(
            W.running_max(jnp.where(arrays["row_valid"], ts, neg64)),
            state["max_ts"],
        )
        cm_side = jnp.maximum(
            W.running_max(jnp.where(arrays["row_valid"], ts, neg64)),
            state[f"ss{side}_smax"],
        )
        swin = self.ss_after if side == "l" else self.ss_before
        pad = jnp.zeros(n, bool)
        if side in self.ss_pad_sides:
            if self.ss_deferred:
                # window already closed on arrival: pad now (klip-36)
                pad = active & ~row_matched & (
                    ts + swin + self.ss_grace < cm_global
                )
            else:
                pad = active & ~row_matched
        admitted = active & (
            ts >= cm_side - self.ss_retention if self.ss_deferred
            else jnp.ones(n, bool)
        )

        # ---------------- emission env: oc match rows + n pad rows
        nn = oc + n
        out_env: Dict[str, DCol] = {}
        for s2 in ("l", "r"):
            for col in self.ss_cols[s2]:
                if s2 == side:
                    d = env[col.name]
                    mdata = d.data[mi]
                    mval = d.valid[mi] & mvalid
                    pdata, pval = d.data, d.valid & pad
                else:
                    mdata = state[f"ss{s2}_v_{col.name}"][mj]
                    mval = state[f"ss{s2}_m_{col.name}"][mj] & mvalid
                    pdata = jnp.zeros(n, mdata.dtype)
                    pval = jnp.zeros(n, bool)
                out_env[col.name] = DCol(
                    jnp.concatenate([mdata, pdata]),
                    jnp.concatenate([mval, pval]),
                    col.type,
                )
        for out_key in ss.schema.key_columns:
            out_env[out_key.name] = DCol(
                jnp.concatenate([kcol.data[mi], kcol.data]),
                jnp.concatenate([kcol.valid[mi] & mvalid, kcol.valid & pad]),
                out_key.type,
            )
        out_ts = jnp.concatenate([jnp.maximum(ts[mi], ots[mj]), ts])
        out_env["ROWTIME"] = DCol(out_ts, jnp.ones(nn, bool), T.BIGINT)
        mask = jnp.concatenate([mvalid, pad])
        out_env, mask = self._apply_ops(self.mid_ops, out_env, mask, nn)
        emits = self._pack_emits(out_env, mask, out_ts)
        # oracle emission order: per incoming row, matches in buffer
        # insertion (seq) order, then the row's own eager null-pad
        emits["ord_a"] = jnp.concatenate(
            [mi.astype(jnp.int64), jnp.arange(n, dtype=jnp.int64)]
        )
        emits["ord_b"] = jnp.concatenate(
            [state[f"ss{o}_seq"][mj],
             jnp.full(n, np.iinfo(np.int64).max, jnp.int64)]
        )
        emits["ss_matchovf"] = jnp.maximum(total - oc, 0)

        # ------- insert the batch's ADMITTED rows into its own ring buffer
        state = dict(state)
        cnt = jnp.cumsum(admitted.astype(jnp.int64))
        seq0 = state[f"ss{side}_cursor"]
        seqs = seq0 + cnt - 1
        tgt = jnp.where(admitted, (seqs % B).astype(jnp.int32), jnp.int32(B))
        batch_max = jnp.max(
            jnp.where(arrays["row_valid"], arrays["ts"], np.iinfo(np.int64).min)
        )
        new_max = jnp.maximum(state["max_ts"], batch_max)
        new_smax = jnp.maximum(state[f"ss{side}_smax"], batch_max)
        unexpired = (
            state[f"ss{side}_ts"] + self.ss_retention >= new_smax
            if self.ss_deferred
            else state[f"ss{side}_ts"] + swin + self.ss_grace >= new_max
        )
        emits["ss_lost"] = jnp.sum(
            admitted & state[f"ss{side}_live"][tgt] & unexpired[tgt]
        )
        state[f"ss{side}_ts"] = state[f"ss{side}_ts"].at[tgt].set(ts)
        state[f"ss{side}_krepr"] = state[f"ss{side}_krepr"].at[tgt].set(krepr)
        state[f"ss{side}_kval"] = state[f"ss{side}_kval"].at[tgt].set(kcol.valid)
        state[f"ss{side}_seq"] = state[f"ss{side}_seq"].at[tgt].set(seqs)
        state[f"ss{side}_matched"] = (
            state[f"ss{side}_matched"].at[tgt].set(row_matched | pad)
        )
        state[f"ss{side}_live"] = (
            state[f"ss{side}_live"].at[tgt].set(True).at[B].set(False)
        )
        for col in self.ss_cols[side]:
            d = env[col.name]
            dt = self._table_col_dtype(col)
            state[f"ss{side}_v_{col.name}"] = (
                state[f"ss{side}_v_{col.name}"].at[tgt].set(d.data.astype(dt))
            )
            state[f"ss{side}_m_{col.name}"] = (
                state[f"ss{side}_m_{col.name}"].at[tgt].set(d.valid)
            )
        state[f"ss{side}_cursor"] = seq0 + jnp.sum(admitted)
        state[f"ss{o}_matched"] = state[f"ss{o}_matched"] | jnp.any(m, axis=0)
        state["max_ts"] = new_max
        state[f"ss{side}_smax"] = new_smax
        return state, emits

    def _trace_ss_expire(
        self, state: Dict[str, jnp.ndarray]
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """Expire buffered entries past window+grace; klip-36 deferred mode
        emits null-padded LEFT/OUTER/RIGHT rows at close (the oracle's
        StreamStreamJoinNode.on_time)."""
        ss = self.ss_join
        t = state["max_ts"]
        b1 = self.ss_capacity + 1
        state = dict(state)
        nn = 2 * b1
        out_env: Dict[str, DCol] = {}
        emit_masks: Dict[str, jnp.ndarray] = {}
        for side in ("l", "r"):
            win = self.ss_after if side == "l" else self.ss_before
            live = state[f"ss{side}_live"]
            closed = live & (
                state[f"ss{side}_ts"] + win + self.ss_grace < t
            )
            if self.ss_deferred and side in self.ss_pad_sides:
                emit_masks[side] = closed & ~state[f"ss{side}_matched"]
            else:
                emit_masks[side] = jnp.zeros(b1, bool)
            if self.ss_deferred:
                # a padded entry stays resident (late matches may still
                # arrive); eviction follows the own store's retention
                state[f"ss{side}_matched"] = (
                    state[f"ss{side}_matched"] | emit_masks[side]
                )
                state[f"ss{side}_live"] = live & (
                    state[f"ss{side}_ts"] + self.ss_retention
                    >= state[f"ss{side}_smax"]
                )
            else:
                state[f"ss{side}_live"] = live & ~closed
        # env: [left-part rows (b1) | right-part rows (b1)]
        for s2 in ("l", "r"):
            for col in self.ss_cols[s2]:
                own_d = state[f"ss{s2}_v_{col.name}"]
                own_m = state[f"ss{s2}_m_{col.name}"]
                zero_d = jnp.zeros(b1, own_d.dtype)
                zero_m = jnp.zeros(b1, bool)
                if s2 == "l":
                    data = jnp.concatenate([own_d, zero_d])
                    valid = jnp.concatenate([own_m & emit_masks["l"], zero_m])
                else:
                    data = jnp.concatenate([zero_d, own_d])
                    valid = jnp.concatenate([zero_m, own_m & emit_masks["r"]])
                out_env[col.name] = DCol(data, valid, col.type)
        for out_key in ss.schema.key_columns:
            parts_d, parts_v = [], []
            for s2 in ("l", "r"):
                parts_d.append(
                    self._decode_key64(state[f"ss{s2}_krepr"], out_key.type)
                )
                parts_v.append(state[f"ss{s2}_kval"] & emit_masks[s2])
            out_env[out_key.name] = DCol(
                jnp.concatenate(parts_d), jnp.concatenate(parts_v),
                out_key.type,
            )
        out_ts = jnp.concatenate([state["ssl_ts"], state["ssr_ts"]])
        out_env["ROWTIME"] = DCol(out_ts, jnp.ones(nn, bool), T.BIGINT)
        mask = jnp.concatenate([emit_masks["l"], emit_masks["r"]])
        out_env, mask = self._apply_ops(self.mid_ops, out_env, mask, nn)
        emits = self._pack_emits(out_env, mask, out_ts)
        # oracle on_time sorts by ts (stable over left-then-right iteration)
        emits["ord_a"] = out_ts
        side_rank = jnp.concatenate(
            [jnp.zeros(b1, jnp.int64), jnp.full(b1, 1 << 40, jnp.int64)]
        )
        emits["ord_b"] = side_rank + jnp.concatenate(
            [state["ssl_seq"], state["ssr_seq"]]
        )
        return state, emits

    # ------------------------------------------------------ ss join host API
    def process_ss(self, batch: HostBatch, side: str) -> List[SinkEmit]:
        layout = self.layout if side == "l" else self.right_layout
        arrays = layout.encode(batch)
        _note_transfer("h2d_bytes", arrays)
        while True:
            step = self._ss_l if side == "l" else self._ss_r
            new_state, emits = step(self.state, arrays)
            if int(emits["ss_matchovf"]) > 0:
                self._grow_ss(out=True)  # re-run this batch, larger match cap
                continue
            if int(emits["ss_lost"]) > 0:
                self._grow_ss(buf=True)  # re-run, larger ring buffers
                continue
            break
        self.state = new_state
        return self._decode_emits(emits)

    def ss_expire_host(self) -> List[SinkEmit]:
        self.state, emits = self._ss_expire(self.state)
        return self._decode_emits(emits)

    def ss_flush(self, stream_time: int) -> List[SinkEmit]:
        state = dict(self.state)
        state["max_ts"] = jnp.maximum(
            state["max_ts"], jnp.asarray(stream_time, jnp.int64)
        )
        self.state = state
        return self.ss_expire_host()

    def _grow_ss(self, buf: bool = False, out: bool = False) -> None:
        if out:
            self.ss_out_cap *= 2
        if buf:
            old_cap = self.ss_capacity
            self.ss_capacity = old_cap * 2
            b1 = self.ss_capacity + 1
            old = {
                k: np.asarray(v)
                for k, v in jax.device_get(self.state).items()
            }
            new = dict(self.state)
            for s in ("l", "r"):
                live = np.nonzero(old[f"ss{s}_live"][:-1])[0]
                # compact by seq: relative order (and thus ord_b ordering)
                # is preserved under reassignment
                live = live[np.argsort(old[f"ss{s}_seq"][live])]
                k = live.size
                for key in list(old):
                    if not key.startswith(f"ss{s}_"):
                        continue
                    v = old[key]
                    if v.ndim == 0:
                        continue
                    grown = np.zeros(b1, v.dtype)
                    grown[:k] = v[live]
                    # jnp.array (copy), not asarray: the ss steps run
                    # undonated today, but a rebuild buffer zero-copy-aliased
                    # into state is one donate_argnums change away from the
                    # PR-2 heap corruption — the aliasing lint keeps every
                    # grow path copying
                    new[key] = jnp.array(grown)
                newseq = np.zeros(b1, np.int64)
                newseq[:k] = np.arange(k)
                new[f"ss{s}_seq"] = jnp.array(newseq)
                newlive = np.zeros(b1, bool)
                newlive[:k] = True
                new[f"ss{s}_live"] = jnp.array(newlive)
                new[f"ss{s}_cursor"] = jnp.asarray(k, jnp.int64)
            self.state = new
        self._compile_steps()

    # ------------------------------------------------------------- tracing
    def _source_env(
        self, arrays: Dict[str, jnp.ndarray], layout: Optional[BatchLayout] = None
    ) -> Dict[str, DCol]:
        env: Dict[str, DCol] = {}
        for spec in (layout or self.layout).specs:
            env[spec.name] = DCol(
                arrays[f"v_{spec.name}"], arrays[f"m_{spec.name}"], spec.sql_type
            )
        # shape-derived, not self.capacity: the distributed ss-join path
        # feeds post-exchange arrays wider than the ingest capacity
        ones = jnp.ones(arrays["ts"].shape[0], bool)
        env["ROWTIME"] = DCol(arrays["ts"], ones, T.BIGINT)
        env["ROWOFFSET"] = DCol(arrays["offset"], ones, T.BIGINT)
        env["ROWPARTITION"] = DCol(arrays["partition"], ones, T.INTEGER)
        return env

    def _apply_ops(
        self, ops: Sequence[st.ExecutionStep], env: Dict[str, DCol],
        active: jnp.ndarray, n: int,
    ) -> Tuple[Dict[str, DCol], jnp.ndarray]:
        for op in ops:
            c = JaxExprCompiler(env, n, self.dictionary)
            if isinstance(op, (st.StreamFilter, st.TableFilter)):
                pred = c.compile(op.predicate)
                active = active & pred.valid & pred.data.astype(bool)
            elif isinstance(op, (st.StreamSelect, st.TableSelect)):
                new_env: Dict[str, DCol] = {}
                src_keys = [k.name for k in op.source.schema.key_columns]
                out_keys = [k.name for k in op.schema.key_columns]
                for new_name, old_name in zip(out_keys, src_keys):
                    if old_name in env:
                        new_env[new_name] = env[old_name]
                for name, e in op.selects:
                    new_env[name] = c.compile(e)
                for p in ("ROWTIME", "ROWOFFSET", "ROWPARTITION",
                          "WINDOWSTART", "WINDOWEND"):
                    if p in env:
                        new_env[p] = env[p]
                env = new_env
            elif isinstance(op, (st.StreamSelectKey, st.TableSelectKey)):
                for col, e in zip(op.schema.key_columns, op.key_expressions):
                    env[col.name] = c.compile(e)
            else:  # pragma: no cover
                raise DeviceUnsupported(type(op).__name__)
        return env, active

    def _apply_pre_ops(
        self, env: Dict[str, DCol], active: jnp.ndarray, n: int
    ) -> Tuple[Dict[str, DCol], jnp.ndarray]:
        return self._apply_ops(self.pre_ops, env, active, n)

    def _trace_step(
        self, state: Dict[str, jnp.ndarray], arrays: Dict[str, jnp.ndarray]
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        if self.agg is None:
            n = self.capacity
            with jax.named_scope("source_decode"):
                env = self._source_env(arrays)
                active = arrays["row_valid"]
                # shared source prefix: the structurally-common leading steps
                # run ONCE; the primary and every prefix member branch off the
                # post-prefix env with only their residual suffixes (with no
                # members the prefix is empty and this is the plain chain)
                shared_n = self._prefix_shared_len if self.prefix_members else 0
                env, active = self._apply_ops(
                    self.pre_ops[:shared_n], env, active, n
                )
                penv, pactive = env, active
                env, active = self._apply_ops(
                    self.pre_ops[shared_n:], env, active, n
                )
                join_stats: Dict[str, jnp.ndarray] = {}
                if self.join is not None:
                    env, active = self._apply_join(
                        env, active, n, self._jtabs_of(state), join_stats
                    )
                    env, active = self._apply_ops(self.mid_ops, env, active, n)
            ts = arrays["ts"]
            batch_max_ts = jnp.max(jnp.where(active, ts, np.iinfo(np.int64).min))
            emits = self._emit_stateless(env, active, ts)
            emits.update(join_stats)
            for m in self.prefix_members:
                menv, mact = self._apply_ops(
                    m.pre_ops[shared_n:], penv, pactive, n
                )
                sub = self._pack_emits(menv, mact, ts, schema=m.sink_schema)
                # query-id-keyed lanes (see the fam: lanes above): decode
                # routes by identity, never by list position
                for k2, v2 in sub.items():
                    emits[f"pfx:{m.query_id}:{k2}"] = v2
            state = dict(state)
            state["max_ts"] = jnp.maximum(state["max_ts"], batch_max_ts)
            return state, emits
        if self.session:
            return self._trace_session_step(state, arrays)
        payload = self.pre_exchange(
            state["max_ts"], arrays, state.get("emit_clock"),
            jtabs=self._jtabs_of(state), seq_base=state.get("agg_seq"),
        )
        store, emits = self.post_exchange(state, payload)
        if self._needs_seq:
            store["agg_seq"] = state["agg_seq"] + self.capacity
        return store, emits

    # --------------------------------------------------- SESSION aggregation
    def _trace_session_step(
        self, state: Dict[str, jnp.ndarray], arrays: Dict[str, jnp.ndarray]
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """SESSION windows as a sort + segmented interval-merge.

        The reference merges sessions record-at-a-time inside the session
        store (StreamAggregateBuilder.java:142-352, SessionWindows).  The
        columnar formulation: batch rows become singleton sessions, the
        (≤ session_slots) stored sessions of every key present in the batch
        are gathered, everything is sorted by (key, start), and one
        segmented cummax scan merges intervals whose gap is within the
        inactivity gap.  Merged segments are scattered back as the key's new
        session set; every touched stored session emits a tombstone and
        every row-containing segment emits its merged aggregate — exactly
        the oracle's remove-then-put emission (_receive_session)."""
        payload = self.pre_session_exchange(
            state["max_ts"], arrays, seq_base=state.get("agg_seq")
        )
        return self.post_session_exchange(state, payload)

    def pre_session_exchange(
        self,
        max_ts: jnp.ndarray,
        arrays: Dict[str, jnp.ndarray],
        seq_base: Optional[jnp.ndarray] = None,
    ) -> Dict[str, jnp.ndarray]:
        """Per-row phase of the SESSION step before the shuffle boundary:
        transforms, group-key hashing, late-record drop, aggregate
        contributions.  The flat payload crosses the ICI all-to-all in the
        multi-chip path, exactly like pre_exchange for fixed windows."""
        n = self.capacity
        env = self._source_env(arrays)
        active = arrays["row_valid"]
        env, active = self._apply_pre_ops(env, active, n)
        ts = arrays["ts"]
        c = JaxExprCompiler(env, n, self.dictionary)
        group_exprs = tuple(getattr(self.group, "group_by_expressions", ()))
        if group_exprs:
            key_cols = [c.compile(e) for e in group_exprs]
        else:
            key_cols = [env[col.name] for col in self.group.schema.key_columns]
        reprs = [_repr64(kc) for kc in key_cols]
        knull_ok = jnp.ones(n, bool)
        for kc in key_cols:
            knull_ok = knull_ok & kc.valid
        active = active & knull_ok
        khash = combine_hash(reprs + [jnp.zeros(n, jnp.int64)])
        # late-record drop past session grace (running per-record stream
        # time in ARRIVAL order — computed before any exchange, matching
        # the oracle's max_ts-at-receive semantics)
        cm = jnp.maximum(
            W.running_max(
                jnp.where(arrays["row_valid"], ts, np.iinfo(np.int64).min)
            ),
            max_ts,
        )
        active = active & (ts + self.grace_ms + self.window.gap_ms >= cm)
        # row aggregate contributions (component 0 = ts watermark)
        contribs: List[jnp.ndarray] = [jnp.where(active, ts, np.iinfo(np.int64).min)]
        rseq = None
        if self._needs_seq:
            rseq = seq_base + jnp.arange(n, dtype=jnp.int64)
        for spec in self.agg_specs:
            args = [c.compile(e) for e in spec.arg_exprs]
            contribs.extend(spec.device.contribs(args, active, rseq))
        payload: Dict[str, jnp.ndarray] = {
            "khash": khash, "ts": ts, "active": active, "cm": cm,
        }
        for k, r in enumerate(reprs):
            payload[f"repr{k}"] = r
        for j, arr in enumerate(contribs):
            payload[f"c{j}"] = arr
        return payload

    @jax.named_scope("session_merge")
    def post_session_exchange(
        self, state: Dict[str, jnp.ndarray], payload: Dict[str, jnp.ndarray]
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """State-owning phase of the SESSION step after the shuffle: gather
        the key's stored sessions, segmented interval-merge, rewrite the
        store, emit tombstones + merged aggregates."""
        ncomp = len(self.store_layout.components)
        nkeys = len(self.key_types)
        n = payload["ts"].shape[0]
        khash, ts = payload["khash"], payload["ts"]
        active, cm = payload["active"], payload["cm"]
        reprs = [payload[f"repr{k}"] for k in range(nkeys)]
        contribs = [payload[f"c{j}"] for j in range(ncomp)]
        cap = self.store_capacity
        gap = self.window.gap_ms
        S = self.session_slots
        m = n * (S + 1)
        neg = np.iinfo(np.int64).min

        # ---- the one sort of the step: rows by (key, ts); it also yields
        # the first active occurrence of each key in the batch
        row_order = session_merge.sort_rows(khash, ts, active)
        first_occ = row_order.first_occ

        # ---- item arrays: [rows | store session i=0..S-1 per first-occ row]
        it_kh = [jnp.where(active, khash, 0)]
        it_start = [ts]
        it_end = [ts]
        it_alive = [active]
        it_isrow = [active]
        it_slot = [jnp.full(n, cap, jnp.int32)]
        it_rowidx = [jnp.arange(n, dtype=jnp.int64)]
        it_reprs = [[r for r in reprs]]
        it_comps = [contribs]
        # jnp.max(cm) == the arrival-order cummax's last element on a
        # single device, and stays correct when exchange scrambles rows
        batch_stream_time = jnp.maximum(state["max_ts"], jnp.max(cm))
        for i in range(S):
            slots_i, _ = probe_find(
                state, cap, khash, jnp.full(n, i, jnp.int64), first_occ
            )
            found = first_occ & (slots_i != cap)
            # store retention: expired sessions (end + gap + grace behind
            # stream time) still DELETE from the store but no longer merge
            unexpired = (
                state["sess_end"][slots_i] + self.window.gap_ms + self.grace_ms
                >= batch_stream_time
            )
            it_kh.append(jnp.where(found & unexpired, khash, 0))
            it_start.append(state["sess_start"][slots_i])
            it_end.append(state["sess_end"][slots_i])
            it_alive.append(found & unexpired)
            it_isrow.append(jnp.zeros(n, bool))
            it_slot.append(slots_i)
            it_rowidx.append(jnp.arange(n, dtype=jnp.int64))
            it_reprs.append([state[f"key{k}"][slots_i] for k in range(nkeys)])
            it_comps.append([state[f"a{j}"][slots_i] for j in range(ncomp)])
        kh = jnp.concatenate(it_kh)
        start = jnp.concatenate(it_start)
        end = jnp.concatenate(it_end)
        alive = jnp.concatenate(it_alive)
        isrow = jnp.concatenate(it_isrow)
        slot = jnp.concatenate(it_slot)
        rowidx = jnp.concatenate(it_rowidx)
        reprs_m = [
            jnp.concatenate([p[k] for p in it_reprs]) for k in range(nkeys)
        ]
        comps_m = [
            jnp.concatenate([p[j] for p in it_comps]) for j in range(ncomp)
        ]
        # dead items take a unique sentinel key so they never merge
        kh = jnp.where(alive, kh, jnp.arange(m, dtype=jnp.int64) + (1 << 62))
        start = jnp.where(alive, start, 0)
        end = jnp.where(alive, end, 0)

        # ---- (key, start) order and segmented interval-merge
        orderm = session_merge.merged_order(
            row_order, active,
            jnp.stack(it_alive[1:]), jnp.stack(it_start[1:]),
        )
        kh, start, end = kh[orderm], start[orderm], end[orderm]
        alive, isrow, slot = alive[orderm], isrow[orderm], slot[orderm]
        rowidx = rowidx[orderm]
        reprs_m = [r[orderm] for r in reprs_m]
        comps_m = [cm[orderm] for cm in comps_m]

        def seg_combine(a, b):
            ka, ea = a
            kb, eb = b
            return kb, jnp.where(ka == kb, jnp.maximum(ea, eb), eb)

        _, segend = jax.lax.associative_scan(seg_combine, (kh, end))
        prev_kh = jnp.concatenate([jnp.full(1, -1, jnp.int64), kh[:-1]])
        prev_segend = jnp.concatenate([jnp.full(1, neg, jnp.int64), segend[:-1]])
        boundary = (kh != prev_kh) | (start > prev_segend + gap)
        seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1

        seg_start = jax.ops.segment_min(start, seg, num_segments=m)
        seg_end = jax.ops.segment_max(end, seg, num_segments=m)
        seg_alive = (
            jax.ops.segment_max(alive.astype(jnp.int32), seg, num_segments=m) > 0
        )
        seg_has_row = (
            jax.ops.segment_max(
                (isrow & alive).astype(jnp.int32), seg, num_segments=m
            ) > 0
        )
        seg_kh = jax.ops.segment_max(jnp.where(alive, kh, neg), seg, num_segments=m)
        big = np.iinfo(np.int64).max
        seg_minrow = jax.ops.segment_min(
            jnp.where(isrow & alive, rowidx, big), seg, num_segments=m
        )
        seg_reprs = [
            jax.ops.segment_max(jnp.where(alive, r, neg), seg, num_segments=m)
            for r in reprs_m
        ]
        seg_comps = []
        comp_list = list(self.store_layout.components)
        last_order_j = 0
        for j, comp in enumerate(comp_list):
            v = comps_m[j]
            fill = jnp.asarray(comp.init, v.dtype)
            v = jnp.where(alive, v, fill)
            if comp.combine == "add":
                seg_comps.append(jax.ops.segment_sum(v, seg, num_segments=m))
                last_order_j = j
            elif comp.combine == "min":
                seg_comps.append(jax.ops.segment_min(v, seg, num_segments=m))
                last_order_j = j
            elif comp.combine == "max":
                seg_comps.append(jax.ops.segment_max(v, seg, num_segments=m))
                last_order_j = j
            else:  # argset: payload of the preceding order component's winner
                order_vals = jnp.where(
                    alive,
                    comps_m[last_order_j],
                    jnp.asarray(
                        comp_list[last_order_j].init,
                        comps_m[last_order_j].dtype,
                    ),
                )
                winner = alive & (
                    order_vals == seg_comps[last_order_j][seg]
                ) & (
                    order_vals
                    != jnp.asarray(
                        comp_list[last_order_j].init, order_vals.dtype
                    )
                )
                seg_comps.append(
                    jax.ops.segment_sum(
                        jnp.where(winner, v, jnp.zeros_like(v)),
                        seg,
                        num_segments=m,
                    )
                )

        # ---- rewrite the store: drop every gathered session, re-insert the
        # merged session set (fresh slot indices 0..count-1 per key)
        state = dict(state)
        del_mask = ~isrow & alive
        tgt_del = jnp.where(del_mask, slot, jnp.int32(cap))
        occ = state["occ"].at[tgt_del].set(False).at[cap].set(False)
        grave = state["grave"].at[tgt_del].set(True).at[cap].set(False)
        state["occ"], state["grave"] = occ, grave
        # rank of each segment within its key (new slot index)
        key_boundary = kh != prev_kh
        key_id = jnp.cumsum(key_boundary.astype(jnp.int32)) - 1
        key_first_seg = jax.ops.segment_min(seg, key_id, num_segments=m)
        rank = seg - key_first_seg[key_id]  # per item; valid at boundaries
        winner = boundary & seg_alive[seg]
        sess_ovf = jnp.sum(winner & (rank >= S))
        ins_act = winner & (rank < S)
        state, ins_slots, _, _ = probe_insert(
            state, cap, kh, rank.astype(jnp.int64),
            [r[seg] for r in seg_reprs],
            jnp.zeros(m, jnp.int32), ins_act,
        )
        tgt_ins = jnp.where(ins_act, ins_slots, jnp.int32(cap))
        state["sess_start"] = state["sess_start"].at[tgt_ins].set(seg_start[seg])
        state["sess_end"] = state["sess_end"].at[tgt_ins].set(seg_end[seg])
        for j in range(ncomp):
            col = state[f"a{j}"]
            state[f"a{j}"] = col.at[tgt_ins].set(seg_comps[j][seg].astype(col.dtype))
        state["dirty"] = state["dirty"].at[tgt_ins].set(True)
        state["dirty"] = state["dirty"].at[cap].set(False)
        batch_max = jnp.max(jnp.where(active, ts, neg))
        state["max_ts"] = jnp.maximum(state["max_ts"], batch_max)
        if self._needs_seq:
            state["agg_seq"] = state["agg_seq"] + n

        # ---- emissions: tombstones for touched stored sessions (part A,
        # per item), merged aggregates per row-containing segment (part B,
        # at boundary items)
        tomb = del_mask & seg_has_row[seg]
        emit_seg = winner & seg_has_row[seg]
        nn = 2 * m
        out_env: Dict[str, DCol] = {}
        for k, colk in enumerate(self.agg.schema.key_columns):
            data_a = self._decode_key64(reprs_m[k], colk.type)
            data_b = self._decode_key64(seg_reprs[k][seg], colk.type)
            out_env[colk.name] = DCol(
                jnp.concatenate([data_a, data_b]),
                jnp.concatenate([tomb, emit_seg]),
                colk.type,
            )
        comp_idx = 1
        row_ts_a = comps_m[0]
        row_ts_b = seg_comps[0][seg]
        for spec in self.agg_specs:
            nc = len(spec.device.components)
            ca = [comps_m[comp_idx + j] for j in range(nc)]
            cb = [seg_comps[comp_idx + j][seg] for j in range(nc)]
            da, va = spec.device.finalize(ca)
            db, vb = spec.device.finalize(cb)
            out_env[spec.out_name] = DCol(
                jnp.concatenate([da, db]),
                jnp.concatenate([va & tomb, vb & emit_seg]),
                spec.device.result_type,
            )
            comp_idx += nc
        out_ts = jnp.concatenate([row_ts_a, row_ts_b])
        ones = jnp.ones(nn, bool)
        out_env["ROWTIME"] = DCol(out_ts, ones, T.BIGINT)
        out_env["WINDOWSTART"] = DCol(
            jnp.concatenate([start, seg_start[seg]]), ones, T.BIGINT
        )
        out_env["WINDOWEND"] = DCol(
            jnp.concatenate([end, seg_end[seg]]), ones, T.BIGINT
        )
        mask = jnp.concatenate([tomb, emit_seg])
        # post-agg projections (HAVING rejected upstream for sessions)
        for op in self.post_ops:
            c2 = JaxExprCompiler(out_env, nn, self.dictionary)
            if isinstance(op, st.TableSelect):
                new_env: Dict[str, DCol] = {}
                src_keys = [k2.name for k2 in op.source.schema.key_columns]
                out_keys = [k2.name for k2 in op.schema.key_columns]
                for nname, oname in zip(out_keys, src_keys):
                    if oname in out_env:
                        new_env[nname] = out_env[oname]
                for name, e in op.selects:
                    new_env[name] = c2.compile(e)
                for p in ("ROWTIME", "WINDOWSTART", "WINDOWEND"):
                    new_env[p] = out_env[p]
                out_env = new_env
            else:
                raise DeviceUnsupported(f"{type(op).__name__} over SESSION")
        emits = self._pack_emits(out_env, mask, out_ts)
        emits["tombstone"] = jnp.concatenate(
            [jnp.ones(m, bool), jnp.zeros(m, bool)]
        )
        # per-record oracle order: a record's tombstones (by session start),
        # then its merged session
        ord_row = jnp.where(seg_minrow[seg] == big, 0, seg_minrow[seg])
        emits["ord_a"] = jnp.concatenate([ord_row, ord_row])
        emits["ord_b"] = jnp.concatenate([start, jnp.full(m, big, jnp.int64)])
        emits["sess_ovf"] = sess_ovf
        emits["occupancy"] = jnp.sum(state["occ"] | state["grave"])
        emits["graves"] = jnp.sum(state["grave"])
        emits["overflow"] = state["overflow"]
        return state, emits

    def pre_exchange(
        self,
        max_ts: jnp.ndarray,
        arrays: Dict[str, jnp.ndarray],
        emit_clock: Optional[jnp.ndarray] = None,
        jtabs: Optional[Dict[str, Dict[str, jnp.ndarray]]] = None,
        seq_base: Optional[jnp.ndarray] = None,
    ) -> Dict[str, jnp.ndarray]:
        """Per-row phase before the shuffle boundary: transforms, window
        assignment, group-key hashing, aggregate contributions.  The returned
        flat payload is exactly what crosses the ICI all-to-all in the
        multi-chip path (the repartition-topic analog, SURVEY §2.3)."""
        n = self.capacity
        with jax.named_scope("source_decode"):
            env = self._source_env(arrays)
            active = arrays["row_valid"]
            env, active = self._apply_pre_ops(env, active, n)
            if self.join is not None:
                env, active = self._apply_join(env, active, n, jtabs)
                env, active = self._apply_ops(self.mid_ops, env, active, n)
        ts = arrays["ts"]

        with jax.named_scope("window_assign"):
            # ---------------- window assignment (expand for hopping)
            w = self.window
            if w is None:
                wstart = jnp.zeros(n, jnp.int64)
                wsize = 0
                k = 1
            elif w.window_type == WindowType.TUMBLING:
                wstart = W.tumbling_starts(ts, w.size_ms)
                wsize = w.size_ms
                k = 1
            elif w.window_type == WindowType.HOPPING and self.sliced:
                # stream slicing: each row lands in exactly ONE slice; the
                # per-window combine happens at emission (post_exchange), so
                # nothing expands before the shuffle
                wstart = W.slice_starts(ts, self.slice_width)
                wsize = w.size_ms
                k = 1
                # admission = the expansion path's any-window-open rule, per
                # family member: the NEWEST window covering the record's slice
                # ends at advance-aligned(ts) + size, and a record whose every
                # covering window is closed (end + grace <= stream time at
                # batch start) never reaches state on either path
                open_any = jnp.zeros(n, bool)
                for m in self.members:
                    newest = ts - jnp.remainder(ts, m.advance_ms)
                    open_any = open_any | (
                        newest + m.size_ms + m.grace_ms > max_ts
                    )
                # ring-wrap safety cut: live slices must span < slice_ring
                # slices, or two batch rows could fold different slices into
                # one ring cell.  The cut sits at the family retention horizon
                # (ring = retention/width + 2), so it only drops records the
                # retention pass would evict this batch anyway — evaluated
                # against the IN-BATCH max ts, the one place the sliced path
                # is stricter than the expansion path's batch-start clock.
                batch_max = jnp.maximum(
                    max_ts,
                    jnp.max(jnp.where(active, ts, np.iinfo(np.int64).min)),
                )
                horizon_ok = (
                    wstart + (self.slice_ring - 1) * self.slice_width > batch_max
                )
                active = active & open_any & horizon_ok
            elif w.window_type == WindowType.HOPPING:
                wstart, in_win = W.hopping_starts(ts, w.size_ms, w.advance_ms)
                wsize = w.size_ms
                k = W.hopping_expansion(w.size_ms, w.advance_ms)
                env = {
                    name: DCol(W.expand(c.data, k), W.expand(c.valid, k), c.sql_type)
                    for name, c in env.items()
                }
                active = W.expand(active, k) & in_win
                ts = W.expand(ts, k)
            else:  # pragma: no cover
                raise DeviceUnsupported(f"window {w.window_type}")
        nn = n * k

        # ---------------- group key
        group_exprs = tuple(getattr(self.group, "group_by_expressions", ()))
        c = JaxExprCompiler(env, nn, self.dictionary)
        if group_exprs:
            key_cols = [c.compile(e) for e in group_exprs]
        else:  # GROUP BY KEY (GroupByKey): existing key columns
            key_cols = [env[col.name] for col in self.group.schema.key_columns]
        reprs = [_repr64(kc) for kc in key_cols]
        knull = jnp.zeros(nn, jnp.int32)
        for i, kc in enumerate(key_cols):
            knull = knull | (~kc.valid).astype(jnp.int32) << i
        # rows with a null grouping expression are excluded (KS GroupBy);
        # note: the store's knull column is therefore always 0 today — kept
        # in the layout for formats that may re-admit null keys
        active = active & (knull == 0)
        khash = combine_hash(reprs + [knull.astype(jnp.int64)])

        # Late-record handling: a window is closed once stream time reaches
        # end + grace (inclusive).  EMIT FINAL uses the per-record stream
        # time (running max over rows reaching the aggregation, seeded with
        # the pre-batch stream time — the batched equivalent of the oracle's
        # `max_ts` advance; tiled hopping copies repeat each record's ts,
        # which leaves the running max's value set unchanged) because its
        # emission depends on the exact watermark sequence.  EMIT CHANGES
        # evaluates grace against the batch-start stream time (documented
        # delta: keeps the cummax scan off the hot path).
        if self.suppress:
            cm = jnp.maximum(
                W.running_max(jnp.where(active, ts, np.iinfo(np.int64).min)),
                max_ts,
            )
            active = active & (wstart + wsize + self.grace_ms > cm)
            # emission clock: per-record stream time over ALL raw source
            # rows (pre-filter, pre-expansion; length n not nn — the
            # emission test only needs the sorted watermark value set)
            cm_emit = W.running_max(
                jnp.where(arrays["row_valid"], arrays["ts"], np.iinfo(np.int64).min)
            )
            if emit_clock is not None:
                cm_emit = jnp.maximum(cm_emit, emit_clock)
        elif w is not None and not self.sliced:
            active = active & (wstart + wsize + self.grace_ms > max_ts)

        payload: Dict[str, jnp.ndarray] = {
            "khash": khash,
            "wstart": wstart,
            "knull": knull,
            "ts": ts,
            "active": active,
        }
        if self.suppress:
            payload["cm"] = cm_emit
        for i, r in enumerate(reprs):
            payload[f"repr{i}"] = r
        # contributions (component 0 is the per-slot ts watermark)
        contribs: List[jnp.ndarray] = [
            jnp.where(active, ts, np.iinfo(np.int64).min)
        ]
        seq = None
        if self._needs_seq:
            # arrival sequence: identical across a row's hopping copies so
            # per-(key,window) ordering follows arrival, not tiling
            base = seq_base if seq_base is not None else jnp.int64(0)
            seq = base + jnp.arange(n, dtype=jnp.int64)
            if k > 1:
                seq = W.expand(seq, k)
        for spec in self.agg_specs:
            args = [c.compile(e) for e in spec.arg_exprs]
            contribs.extend(spec.device.contribs(args, active, seq))
        for j, contrib in enumerate(contribs):
            payload[f"c{j}"] = contrib
        return payload

    def post_exchange(
        self, state: Dict[str, jnp.ndarray], payload: Dict[str, jnp.ndarray]
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """State-owning phase after the shuffle boundary: probe/insert the
        keyed store, fold contributions, emit coalesced changes."""
        active = payload["active"]
        nn = active.shape[0]
        reprs = [payload[f"repr{i}"] for i in range(len(self.key_types))]
        # sliced stores key per GROUP KEY only (the slice ring hangs off the
        # key slot); expansion keys per (group key, window start)
        probe_w = (
            jnp.zeros_like(payload["wstart"])
            if self.sliced
            else payload["wstart"]
        )
        store, slots, probe_rounds, probe_lane_rounds = probe_insert(
            state,
            self.store_capacity,
            payload["khash"],
            probe_w,
            reprs,
            payload["knull"],
            active,
        )
        ncomp = len(self.store_layout.components)
        contribs = [payload[f"c{j}"] for j in range(ncomp)]
        dump = jnp.int32(self.store_capacity)
        slot_or_dump = jnp.where(active, slots, dump)
        if self.sliced:
            # the per-batch emission mask must see the stream time AT BATCH
            # START (the expansion path's documented EMIT CHANGES clock) —
            # capture it before the fold advances max_ts
            max_ts_pre = state["max_ts"]
            store, sliced_lanes = self._sliced_scatter(
                store, slot_or_dump, payload, contribs
            )
        else:
            store = scatter_combine(
                store, self.store_layout, slot_or_dump, contribs
            )
        batch_max_ts = jnp.max(
            jnp.where(active, payload["ts"], np.iinfo(np.int64).min)
        )
        store["max_ts"] = jnp.maximum(store["max_ts"], batch_max_ts)

        # ---------------- emission (one change per touched key per batch)
        if self.suppress:
            # EMIT FINAL: a window emits iff some observed stream time T
            # lands in [close, start + retention] (close = end + grace) —
            # past the horizon the store segment is evicted unemitted, the
            # reference's windowed-store retention behavior (see
            # oracle.SuppressNode).  The per-record stream-time sequence is
            # non-decreasing, so searchsorted finds the first T >= close.
            size = self.window.size_ms
            cm = jnp.sort(payload["cm"])  # non-decreasing; sort guards the
            # post-shuffle case where rows arrive key-partitioned
            m = cm.shape[0]
            ws = store["wstart"]
            close = ws + size + self.grace_ms
            horizon = ws + self.retention_ms
            pos = jnp.searchsorted(cm, close)
            t_first = cm[jnp.minimum(pos, m - 1)]
            reachable = (pos < m) & (t_first <= horizon)
            final_t = cm[m - 1]
            store["emit_clock"] = jnp.maximum(store["emit_clock"], final_t)
            # record first-touch order for this batch's rows
            order = store["row_clock"] + jnp.arange(nn, dtype=jnp.int64)
            store["born"] = store["born"].at[slot_or_dump].min(
                jnp.where(active, order, np.iinfo(np.int64).max)
            )
            store["row_clock"] = store["row_clock"] + nn
            cand = store["occ"] & store["dirty"] & ~store["emitted"]
            emit_now = cand & reachable
            evict_now = cand & (close <= final_t) & ~reachable
            store["dirty"] = store["dirty"] & ~(emit_now | evict_now)
            store["emitted"] = store["emitted"] | emit_now
            store["occ"] = store["occ"] & ~evict_now
            store["grave"] = store["grave"] | evict_now
            store["born"] = jnp.where(
                evict_now, np.iinfo(np.int64).max, store["born"]
            )
            for j, comp in enumerate(self.store_layout.components):
                col = store[f"a{j}"]
                mask2 = evict_now[:, None] if col.ndim == 2 else evict_now
                store[f"a{j}"] = jnp.where(
                    mask2, jnp.asarray(comp.init, col.dtype), col
                )
            emits: Dict[str, jnp.ndarray] = {
                "emit_mask": jnp.zeros(nn, bool),
                "suppress_emit": emit_now,
            }
        elif self.sliced:
            # per-member window combine + emission: members[0] is this
            # query's own window; attached family members ride prefixed
            emits = self._sliced_member_emits(
                store, slots, payload, self.members[0], max_ts_pre
            )
            for member in self.members[1:]:
                sub = self._sliced_member_emits(
                    store, slots, payload, member, max_ts_pre
                )
                # lanes key by QUERY ID, not position: a pipelined batch's
                # emits outlive the member list that traced them — a
                # detach/re-attach between trace and decode must never
                # shift one member's rows onto another's sink
                for k2, v2 in sub.items():
                    emits[f"fam:{member.query_id}:{k2}"] = v2
        else:
            winners = winners_per_slot(slots, active, self.store_capacity)
            emits = self._emit_agg(store, slots, winners, nn)
        # load metrics, read host-side by process() to trigger growth
        # (graves hold probe-chain slots until compaction, so they count)
        emits["occupancy"] = jnp.sum(store["occ"] | store["grave"])
        emits["graves"] = jnp.sum(store["grave"])
        emits["overflow"] = store["overflow"]
        # the store's own account of this step: rounds of its probe loop
        # and the lanes they worked on, read beside the load scalars
        emits["probe_rounds"] = probe_rounds.astype(jnp.int32)
        emits["probe_lane_rounds"] = probe_lane_rounds.astype(jnp.int32)
        if self.sliced:
            # the lanes of the batch the sliced fold and emission visited
            emits["sliced_lanes"] = sliced_lanes.astype(jnp.int32)
            # host mirror of the stream clock (rides the existing per-batch
            # load readback): lower-bounds the admission floor ensure_ring_for
            # sizes the ring against
            emits["smax_ts"] = store["max_ts"]
        return store, emits

    def _finalized_env(
        self,
        store: Dict[str, jnp.ndarray],
        slots: jnp.ndarray,
        nn: int,
        wsize_ms: Optional[int] = None,
        agg_schema: Optional[LogicalSchema] = None,
        agg_map: Optional[List[int]] = None,
    ) -> Tuple[Dict[str, DCol], jnp.ndarray]:
        """Gather + finalize store state at ``slots`` into an expression env
        over the aggregate's output schema.  Also returns the per-lane
        exactness-envelope verdict (True = this lane's accumulator passed
        its exact_abs_bound and the finalized value may have drifted);
        callers mask out dump-slot lanes before acting on it.  ``wsize_ms``
        overrides the window size for WINDOWEND (family members share one
        slice store but emit their own window bounds).  ``agg_map``
        restricts finalization to a member's own subset of the shared
        (union) partial set, re-bound to the member-local
        KSQL_AGG_VARIABLE_<i> names its post-ops and sink reference."""
        exceeded = jnp.zeros(nn, bool)
        env: Dict[str, DCol] = {}
        key_cols = (agg_schema or self.agg.schema).key_columns
        knull = store["knull"][slots]
        for i, col in enumerate(key_cols):
            data = store[f"key{i}"][slots]
            valid = (knull >> i & 1) == 0
            if col.type.base in (SqlBaseType.DOUBLE, SqlBaseType.DECIMAL):
                data = jax.lax.bitcast_convert_type(data, jnp.float64)
            elif col.type.base not in _HASHED:
                data = data.astype(col.type.device_dtype())
            env[col.name] = DCol(data, valid, col.type)
        row_ts = store["a0"][slots]
        starts = self._spec_comp_starts()
        indices = agg_map if agg_map is not None else range(len(self.agg_specs))
        for i, j in enumerate(indices):
            spec = self.agg_specs[j]
            ncomp = len(spec.device.components)
            base = starts[j]
            comps = [store[f"a{base + t}"][slots] for t in range(ncomp)]
            if spec.device.exact_abs_bound is not None:
                exceeded = exceeded | (
                    jnp.abs(comps[0]) > spec.device.exact_abs_bound
                )
            out_name = (
                spec.out_name if agg_map is None
                else f"KSQL_AGG_VARIABLE_{i}"
            )
            fin = spec.device.finalize(comps)
            if len(fin) == 4:  # map result: (keys2d, row_valid, present2d, counts2d)
                data, valid, present, counts = fin
                env[out_name] = DCol(
                    data, present, spec.device.result_type,
                    elem_valid=present, aux=counts,
                )
            elif len(fin) == 3:  # vector result: (data2d, present2d, elem_valid2d)
                data, valid, ev = fin
                env[out_name] = DCol(
                    data, valid, spec.device.result_type, elem_valid=ev
                )
            else:
                data, valid = fin
                env[out_name] = DCol(data, valid, spec.device.result_type)
        ones = jnp.ones(nn, bool)
        env["ROWTIME"] = DCol(row_ts, ones, T.BIGINT)
        if self.session:
            env["WINDOWSTART"] = DCol(store["sess_start"][slots], ones, T.BIGINT)
            env["WINDOWEND"] = DCol(store["sess_end"][slots], ones, T.BIGINT)
        elif self.window is not None:
            ws = store["wstart"][slots]
            size = wsize_ms if wsize_ms is not None else self.window.size_ms
            env["WINDOWSTART"] = DCol(ws, ones, T.BIGINT)
            env["WINDOWEND"] = DCol(ws + size, ones, T.BIGINT)
        return env, row_ts, exceeded

    @jax.named_scope("emit_compact")
    def _emit_agg(
        self,
        store: Dict[str, jnp.ndarray],
        slots: jnp.ndarray,
        mask: jnp.ndarray,
        nn: int,
        ts_override: Optional[jnp.ndarray] = None,
    ) -> Dict[str, jnp.ndarray]:
        env, row_ts, dec_exceeded = self._finalized_env(store, slots, nn)
        if ts_override is not None:
            # table-change emissions carry the triggering record's timestamp
            # (oracle _receive_table_change), not the slot watermark
            row_ts = ts_override
            env["ROWTIME"] = DCol(
                ts_override, jnp.ones(nn, bool), T.BIGINT
            )
        # post-agg projection / HAVING
        tomb_h = None
        for op in self.post_ops:
            c = JaxExprCompiler(env, nn, self.dictionary)
            if isinstance(op, st.TableFilter):
                pred = c.compile(op.predicate)
                pass_now = pred.valid & pred.data.astype(bool)
                if "hpass" in store:
                    # HAVING retraction: a slot that previously emitted a
                    # passing row and now fails emits a tombstone.  hpass
                    # updates IN PLACE in the caller's store dict (both
                    # callers pass a fresh dict they keep using).
                    dump = jnp.int32(self.store_capacity)
                    prev = store["hpass"][slots]
                    t = mask & prev & ~pass_now
                    tomb_h = t if tomb_h is None else (tomb_h | t)
                    touched = jnp.where(mask, slots, dump)
                    store["hpass"] = store["hpass"].at[touched].set(pass_now)
                    mask = mask & (pass_now | t)
                else:
                    mask = mask & pass_now
            else:  # TableSelect
                new_env: Dict[str, DCol] = {}
                src_keys = [k.name for k in op.source.schema.key_columns]
                out_keys = [k.name for k in op.schema.key_columns]
                for new_name, old_name in zip(out_keys, src_keys):
                    if old_name in env:
                        new_env[new_name] = env[old_name]
                for name, e in op.selects:
                    new_env[name] = c.compile(e)
                for p in ("ROWTIME", "WINDOWSTART", "WINDOWEND"):
                    if p in env:
                        new_env[p] = env[p]
                env = new_env
        emits = self._pack_emits(env, mask, row_ts)
        if tomb_h is not None:
            emits["tombstone"] = tomb_h
        # exactness-envelope verdict for the EMITTED lanes only (dump-slot
        # gathers hold accumulated garbage and must not trip it); rank-1 so
        # the table-agg old/new emit concatenation composes
        emits["dec_envelope"] = jnp.sum(
            (dec_exceeded & mask).astype(jnp.int64)
        ).reshape(1)
        return emits

    def _emit_stateless(
        self, env: Dict[str, DCol], active: jnp.ndarray, ts: jnp.ndarray
    ) -> Dict[str, jnp.ndarray]:
        return self._pack_emits(env, active, ts)

    def _pack_emits(
        self,
        env: Dict[str, DCol],
        mask: jnp.ndarray,
        ts: jnp.ndarray,
        schema: Optional[LogicalSchema] = None,
    ) -> Dict[str, jnp.ndarray]:
        out: Dict[str, jnp.ndarray] = {"emit_mask": mask, "emit_ts": ts}
        schema = schema if schema is not None else self._emit_schema()
        for col in schema.columns():
            d = env.get(col.name)
            if d is None:
                raise DeviceUnsupported(f"sink column {col.name} not computed on device")
            out[f"v_{col.name}"] = d.data
            out[f"m_{col.name}"] = d.valid
            if d.data.ndim == 2:  # vector column: per-element null bits
                out[f"e_{col.name}"] = (
                    d.elem_valid if d.elem_valid is not None else d.valid
                )
                if d.aux is not None:  # map column: per-element counts
                    out[f"c_{col.name}"] = d.aux
        if (self.window is not None or self.windowed_source) and "WINDOWSTART" in env:
            out["ws"] = env["WINDOWSTART"].data
            out["we"] = env["WINDOWEND"].data
        return out

    @jax.named_scope("evict")
    def _trace_evict(
        self, store: Dict[str, jnp.ndarray]
    ) -> Dict[str, jnp.ndarray]:
        """Retention pass: free slots whose window left retention, resetting
        components so reclaimed slots start clean.  Run periodically from
        the host (amortized — the RocksDB-compaction analog), not per step.
        Suppressed-but-unflushed windows are kept until flush()."""
        store = dict(store)
        if self.sliced:
            # sliced slots are per KEY: a slot expires only once its NEWEST
            # slice left the family retention window (individual stale ring
            # cells recycle in place at the next wrap)
            expired = store["occ"] & (
                store["slast"] + self.family_retention_ms < store["max_ts"]
            )
            store["slast"] = jnp.where(expired, -(2 ** 62), store["slast"])
            store["slice_id"] = jnp.where(
                expired[:, None], jnp.int64(-1), store["slice_id"]
            )
        else:
            expired = store["occ"] & (
                store["wstart"] + self.retention_ms < store["max_ts"]
            )
        if self.suppress:
            expired = expired & ~store["dirty"]
        store["occ"] = store["occ"] & ~expired
        store["grave"] = store["grave"] | expired
        store["dirty"] = store["dirty"] & ~expired
        if "hpass" in store:
            store["hpass"] = store["hpass"] & ~expired
        if "born" in store:
            store["born"] = jnp.where(
                expired, np.iinfo(np.int64).max, store["born"]
            )
            store["emitted"] = store["emitted"] & ~expired
        for j, comp in enumerate(self.store_layout.components):
            col = store[f"a{j}"]
            mask2 = expired[:, None] if col.ndim == 2 else expired
            store[f"a{j}"] = jnp.where(
                mask2, jnp.asarray(comp.init, col.dtype), col
            )
        return store

    # ------------------------------------------------------------ host API
    EVICT_INTERVAL = 64  # batches between retention passes

    #: jitted step attributes (dict-valued entries hold per-side/per-probe
    #: jits) — enumerated for the flight recorder's jit-cache accounting
    _JIT_ATTRS = (
        "_step", "_evict", "_ss_l", "_ss_r", "_ss_expire", "_ta_step",
        "_verdict", "_table_steps", "_fk_steps", "_tt_steps",
    )

    def jit_cache_entries(self) -> int:
        """Total in-memory jit cache entries across this query's compiled
        steps.  The executor samples it around each device call: a growing
        cache means that call paid a trace+compile (flight-recorder
        ``device.compile`` / jit_miss), a flat one was a cache hit."""
        fns = []
        for name in self._JIT_ATTRS:
            f = getattr(self, name, None)
            fns.extend(f.values() if isinstance(f, dict) else (f,))
        return tracing.jit_cache_size(fns)

    #: when True (batched engine mode), emission decode lags one batch so
    #: host encode of batch i+1 overlaps device compute of batch i — the
    #: double-buffered DMA row of SURVEY §2.3.  Per-record parity mode
    #: keeps it off (emissions must surface with their record).
    pipeline = False
    _pending_emits: Optional[Dict[str, jnp.ndarray]] = None

    def process(self, batch: HostBatch) -> List[SinkEmit]:
        if self.ss_join is not None:
            return self.process_ss(batch, "l")
        with tracing.span("batch.assemble"):
            arrays = self.layout.encode(batch)
        return self.process_arrays(arrays)

    def process_arrays(self, arrays: Dict[str, np.ndarray]) -> List[SinkEmit]:
        """One encoded micro-batch through the device step (the entry the
        native ingest tier feeds directly, bypassing HostBatch)."""
        with tracing.span("step.dispatch"):
            _note_transfer("h2d_bytes", arrays)
            if self.sliced:
                self.ensure_ring_for(arrays["ts"], arrays["row_valid"])
            new_state, emits = self._step(self.state, arrays)
            if not self.session and not self.suppress:
                # the read-back of _finish_step, queued behind the step: the
                # copies run as soon as it ends, not when the host asks
                for leaf in emits.values():
                    leaf.copy_to_host_async()
        while self.session:
            with tracing.span("step.wait", wait=True):
                overflowed = int(emits["sess_ovf"]) > 0
            if not overflowed:
                break
            # more concurrent sessions per key than tracked slots: grow
            # and re-run the batch (steps are undonated)
            self._grow_sessions()
            with tracing.span("step.dispatch"):
                new_state, emits = self._step(self.state, arrays)
        self.state = new_state
        result: Optional[List[SinkEmit]] = None
        if self.suppress:
            # windows the step closed this batch — emitted BEFORE the
            # retention pass / store growth below, which remap or reset
            # slots (dirty already cleared in-trace; values stay resident)
            # one read: the store-shaped mask and the load scalars (the
            # rows come from the store, not from the emit columns)
            host = jax.device_get({
                k: v for k, v in emits.items()
                if k == "suppress_emit" or not v.ndim
            })
            idx = np.nonzero(host["suppress_emit"])[0]
            result = self._emit_slots(idx)
        if self.agg is not None:
            self._batches += 1
            if (
                self.retention_ms is not None
                and self._batches % self.EVICT_INTERVAL == 0
            ):
                with tracing.span("store.evict"):
                    self.state = self._evict(self.state)
        if result is not None:
            self._react_to_load(host)
            return result
        react = self.agg is not None
        if self.pipeline and not self.suppress and not self.session:
            emits, self._pending_emits = self._pending_emits, emits
            if emits is None:
                return []
            # sample the load check: in pipelined mode the 0.75-occupancy
            # growth threshold leaves several batches of headroom
            react = react and self._batches % 4 == 0
        return self._finish_step(emits, react)

    def _finish_step(
        self, emits: Dict[str, jnp.ndarray], react: bool
    ) -> List[SinkEmit]:
        """The host's half of a step: wait for its outputs, read them back
        in one transfer, then check the load (``react``) and decode the
        emitted rows from the host copy — a blocking read a leaf cost
        0.4-1.4 ms each, ~11 ms a step (``PERF.md`` §6, PR 31).  The wait
        is explicit so that its span holds the host blocked on the device
        and nothing else."""
        if not self.session:  # the session step's overflow read has waited
            with tracing.span("step.wait", wait=True):
                jax.block_until_ready(emits)
        with tracing.span("emit.decode"):
            with tracing.span("emit.read", wait=True):
                host = jax.device_get(emits)
            if react:
                self._react_to_load(host)
            self._note_join_stats(host)
            self._deliver_members(host)
            if self.collect_raw_emits:
                # the raw block gathers on the device: decode from the
                # step's own arrays, whose host copies the read has cached
                host = {k: emits[k] for k in host}
            with tracing.span("emit.rows"):
                return self._decode_emits(host)

    _JOIN_STATS = ("find_rounds", "join_rows", "join_matched")

    def _note_join_stats(self, emits: Dict[str, np.ndarray]) -> None:
        """Book the stream-table join's own counts of a step (the lookup
        loop's rounds, rows probed, rows matched) on ``device.step``, every
        step, from the step's read-back."""
        if "find_rounds" in emits and tracing.active() is not None:
            tracing.counter(
                "device.step", sampled=1,
                **{k: int(emits[k]) for k in self._JOIN_STATS},
            )

    def _deliver_members(self, emits: Dict[str, jnp.ndarray]) -> None:
        """Decode + deliver the attached members' emission blocks
        (``fam:<qid>:`` window-family lanes and ``pfx:<qid>:`` shared
        source-prefix lanes of the shared device step).  Delivered lanes
        are REMOVED from ``emits`` so the primary's own decode (and its
        d2h transfer accounting) never sees them twice.  Lanes route by
        QUERY ID: a pipelined batch decoded after a detach/re-attach must
        never shift one member's rows onto another's sink."""
        lanes = [
            (f"fam:{m.query_id}:", m) for m in self.members[1:]
        ] + [
            (f"pfx:{m.query_id}:", m) for m in self.prefix_members
        ]
        for prefix, member in lanes:
            sub = {
                key[len(prefix):]: emits.pop(key)
                for key in list(emits)
                if key.startswith(prefix)
            }
            if not sub or member.deliver is None:
                continue
            rows = self._decode_emits(sub, schema=member.sink_schema)
            if rows:
                member.deliver(rows)
        # lanes of members detached between the batch's trace and this
        # (pipelined) decode: DROP them — the member is gone or mid-
        # rebuild, and its parked rows must not reach any other sink
        for key in list(emits):
            if key.startswith("fam:") or key.startswith("pfx:"):
                emits.pop(key)

    def _trace_verdict(self, arrays: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """Filter verdict only (no emission) — evaluates the table pipeline
        over a batch of OLD rows to decide tombstones."""
        n = self.capacity
        env = self._source_env(arrays)
        active = arrays["row_valid"]
        _env, active = self._apply_pre_ops(env, active, n)
        return active

    def process_table_changes(
        self, new_batch: HostBatch, old_batch: HostBatch,
        keys: List[tuple], has_new: np.ndarray, has_old: np.ndarray,
        ts: List[int],
    ) -> List[SinkEmit]:
        """Table-to-table transform step: one device pass over the NEW rows
        (projection + filter) and one verdict pass over the OLD rows; a
        change whose new row fails (or is a delete) while its old row passed
        emits a tombstone (reference TableFilter forwarding semantics)."""
        if self.table_agg:
            a_new = self.layout.encode(new_batch)
            a_old = self.layout.encode(old_batch)
            pad_old = np.zeros(self.capacity, bool)
            pad_old[: len(keys)] = has_old
            a_old["row_valid"] = pad_old
            pad_new = np.zeros(self.capacity, bool)
            pad_new[: len(keys)] = has_new
            a_new["row_valid"] = pad_new
            self.state, emits = self._ta_step(self.state, a_new, a_old)
            self._react_to_load(emits)
            return self._decode_emits(emits, sort=False)
        if not hasattr(self, "_verdict"):
            self._verdict = jax.jit(self._trace_verdict)
        arrays_new = self.layout.encode(new_batch)
        self.state, emits = self._step(self.state, arrays_new)
        old_ok = np.zeros(len(keys), bool)
        if has_old.any():
            old_ok_dev = np.asarray(self._verdict(self.layout.encode(old_batch)))
            old_ok = old_ok_dev[: len(keys)] & has_old
        new_mask = np.asarray(emits["emit_mask"])[: len(keys)] & has_new
        rows = self._decode_emits(emits, sort=False)
        by_index: Dict[int, SinkEmit] = {}
        order = np.nonzero(np.asarray(emits["emit_mask"]))[0]
        for pos, e in zip(order, rows):
            if pos < len(keys):
                by_index[int(pos)] = e
        out: List[SinkEmit] = []
        for i, key in enumerate(keys):
            if new_mask[i]:
                e = by_index.get(i)
                if e is not None:
                    out.append(SinkEmit(key, e.row, ts[i], e.window))
            elif old_ok[i]:
                out.append(SinkEmit(key, None, ts[i], None))
        return out

    def flush_pipeline(self) -> List[SinkEmit]:
        """Decode the deferred batch (poll-tick boundary)."""
        emits, self._pending_emits = self._pending_emits, None
        if emits is None:
            return []
        return self._finish_step(emits, self.agg is not None)

    _seen_overflow = 0
    _batches = 0

    def _react_to_load(self, emits: Dict[str, jnp.ndarray]) -> None:
        """Grow the store before it can overflow (and surface data loss
        loudly if it somehow did — slot exhaustion drops aggregates)."""
        if "smax_ts" in emits:
            self._mirror_max_ts = max(
                self._mirror_max_ts, int(emits["smax_ts"])
            )
        overflow = int(emits["overflow"])
        if overflow > self._seen_overflow:
            self._seen_overflow = overflow
            raise QueryRuntimeException(
                f"device state store overflowed ({overflow} rows lost); "
                f"store_capacity={self.store_capacity} is undersized for the "
                "key×window cardinality — restart the query from its "
                "changelog with a larger store"
            )
        occupancy = int(emits["occupancy"])
        if "probe_rounds" in emits and tracing.active() is not None:
            # the step's own account of its store work, read where the
            # host reads the load scalars anyway; ``sampled`` (not ``n``)
            # is the denominator: pipelined ticks check every 4th batch.
            # The load scalars ride along: slots taken, and how many of
            # them are graves
            tracing.counter(
                "device.step",
                probe_rounds=int(emits["probe_rounds"]),
                probe_lane_rounds=int(emits["probe_lane_rounds"]),
                occupancy=occupancy,
                graves=int(emits["graves"]),
                sampled=1,
                **_sliced_lanes_of(emits),
            )
        headroom = self.capacity * self.expansion
        if self.pipeline:
            headroom *= 4  # load checks are sampled every 4th batch
        if occupancy + headroom > 0.75 * self.store_capacity:
            if self.retention_ms is not None:
                # evict expired windows now (off-cadence), then compact the
                # tombstones away in place — the RocksDB compaction analog;
                # grow only if the table is still dense with LIVE entries
                with tracing.span("store.evict"):
                    self.state = self._evict(self.state)
                tracing.counter("store.evict", off_cadence=1)
                with tracing.span("store.compact"):
                    live = self._grow(factor=1)
                if (
                    live + headroom > 0.5 * self.store_capacity
                    and self._grow_allowed()
                ):
                    self._grow()
            elif self._grow_allowed():
                self._grow()

    def _grow_sessions(self, factor: int = 2) -> None:
        """More concurrent sessions per key: probe identities (khash, slot)
        stay valid, only the gather loop bound changes — recompile."""
        self.session_slots *= factor
        self._step = jax.jit(self._trace_step)

    #: HBM admission budget enforced at store-growth time (bytes; 0 = no
    #: gate).  Wired by the engine from ksql.analysis.memory.budget.bytes,
    #: with ``on_grow_refuse`` carrying the refusal into the processing
    #: log + /alerts evidence.  ``_grow_refused_at`` memoizes one refusal
    #: per capacity so a saturated store logs once, not once per batch.
    memory_budget_bytes = 0
    on_grow_refuse = None
    _grow_refused_at = -1

    def _grow_allowed(self, factor: int = 2) -> bool:
        """Gate a store doubling against the HBM budget: project the
        post-grow footprint from the LIVE per-component measurement
        (store-capacity-scaled components double; separately-sized
        join-table / ss-buffer stores do not) and refuse the grow when it
        would overflow ``ksql.analysis.memory.budget.bytes`` — the query
        keeps serving at its current capacity, with the store overflow
        counters making saturation visible (and the eventual overflow
        loud).

        Deliberately NOT gated: ``_grow_sessions`` — the sess_ovf retry
        loop cannot complete the in-flight batch without more session
        slots, so refusing there would spin forever or fail the query
        outright; the admission-time at-growth-cap price remains the
        sizing control for session state (documented in README)."""
        budget = int(self.memory_budget_bytes or 0)
        if not budget or factor <= 1:
            return True
        if self._grow_refused_at == self.store_capacity:
            return False  # already refused (and logged) at this capacity
        from ksql_tpu.analysis.mem_model import measure_state_bytes

        comps = measure_state_bytes(self.state, sliced=self.sliced)
        fixed = ("join.table", "ss.buffer", "tt.store", "fk.store")
        proj = sum(
            b if c.startswith(fixed) else b * factor
            for c, b in comps.items()
        )
        if proj <= budget:
            return True
        self._grow_refused_at = self.store_capacity
        scaled = {c: b for c, b in comps.items() if not c.startswith(fixed)}
        dom = max(scaled, key=scaled.get) if scaled else "store"
        msg = (
            f"store growth {self.store_capacity}->"
            f"{self.store_capacity * factor} slots refused: projected "
            f"footprint {proj} bytes > ksql.analysis.memory.budget.bytes="
            f"{budget} (dominant component {dom}="
            f"{scaled.get(dom, 0)}B live); serving continues at current "
            "capacity — watch the store overflow counter"
        )
        cb = self.on_grow_refuse
        if cb is not None:
            try:
                cb(msg, dom, int(proj), budget)
            except Exception:  # noqa: BLE001 — a logging failure must not
                pass  # turn a refusal into a query crash
        return False

    def _grow(self, factor: int = 2) -> int:
        """Rebuild the store host-side (numpy reinsert of live slots),
        dropping tombstones; factor=1 compacts in place, factor>1 also
        doubles capacity and recompiles for the new shapes.  Returns the
        number of live slots."""
        cur = dict(self.state)
        jtab = cur.pop("jtab", None)  # join-table store is sized separately
        old = {k: np.asarray(v) for k, v in jax.device_get(cur).items()}
        self.store_capacity *= factor
        self.store_layout = dataclasses.replace(
            self.store_layout, capacity=self.store_capacity
        )
        init = dict(self.init_state())
        init.pop("jtab", None)
        new = {
            k: np.array(v)  # writable copies: device_get arrays are read-only
            for k, v in jax.device_get(init).items()
        }
        scalars = {n for n, v in old.items() if v.ndim == 0}
        live = np.nonzero(old["occ"][:-1])[0]
        if live.size:
            from ksql_tpu.ops.hash_store import host_insert

            slots = host_insert(
                new["occ"],
                new["khash"],
                new["wstart"],
                self.store_capacity,
                old["khash"][live],
                old["wstart"][live],
            )
            for name in old:
                if name in scalars or name in ("occ", "khash", "wstart"):
                    continue
                new[name][slots] = old[name][live]
        for name in scalars:  # max_ts, overflow, emit_clock
            new[name] = old[name]
        # jnp.array (copy), not asarray: the rebuilt host arrays must not be
        # zero-copy aliased into state the donating step later recycles
        grown = {k: jnp.array(v) for k, v in new.items()}
        if jtab is not None:
            grown["jtab"] = jtab
        self.state = grown
        if factor != 1:  # shapes changed: recompile every store-shaped step
            self._compile_steps()
        return int(live.size)

    def _decode_emits(
        self,
        emits: Dict[str, jnp.ndarray],
        sort: bool = True,
        schema: Optional[LogicalSchema] = None,
    ) -> List[SinkEmit]:
        _note_transfer("d2h_bytes", emits)
        # a stale raw block must never outlive its batch: misalignment
        # with the fanned-out emits would hand the tap kernel the wrong
        # rows (the dispatcher validates n, so clearing is the guarantee)
        self.last_raw_block = None
        if "dec_envelope" in emits:
            n_drift = int(np.asarray(emits["dec_envelope"]).sum())
            if n_drift:
                # never emit a silently drifted decimal sum: the accumulated
                # value passed the float64-exact envelope the static gate
                # certified headroom for (device_aggs.exact_abs_bound)
                raise QueryRuntimeException(
                    f"DECIMAL SUM exceeded the 2^53-exact envelope on "
                    f"{n_drift} emitted aggregate(s); rerun this query on "
                    "the oracle backend (ksql.runtime.backend=oracle) for "
                    "unbounded decimal arithmetic"
                )
        mask = np.asarray(emits["emit_mask"])
        idx = np.nonzero(mask)[0]
        # how much of what was read back is rows: the mask's static length
        # against the emits it marks
        tracing.counter(
            "emit.decode", lanes=int(mask.shape[0]), rows=int(idx.size)
        )
        if idx.size == 0:
            return []
        if "ord_a" in emits:
            # explicit emission order (join match/expiry sequencing)
            oa = np.asarray(emits["ord_a"])[idx]
            ob = np.asarray(emits["ord_b"])[idx]
            idx = idx[np.lexsort((ob, oa))]
            sort = False
        schema = schema if schema is not None else self._emit_schema()
        cols: Dict[str, List[Any]] = {}
        for col in schema.columns():
            data = np.asarray(emits[f"v_{col.name}"])[idx]
            valid = np.asarray(emits[f"m_{col.name}"])[idx]
            if data.ndim == 2 and f"c_{col.name}" in emits:
                # map column (histogram): present elements decode as keys,
                # the count companion as values, regrouped per row
                nums = np.asarray(emits[f"c_{col.name}"])[idx]
                flat_present = valid.reshape(-1)
                keys = decode_value(
                    data.reshape(-1)[flat_present],
                    np.ones(int(flat_present.sum()), bool),
                    col.type.key or col.type.element, self.dictionary,
                )
                vals = nums.reshape(-1)[flat_present]
                counts = valid.sum(axis=1)
                bounds = np.cumsum(counts)[:-1]
                cols[col.name] = [
                    dict(zip(kp, (int(x) for x in vp)))
                    for kp, vp in zip(
                        np.split(np.asarray(keys, object), bounds),
                        np.split(vals, bounds),
                    )
                ]
                continue
            if data.ndim == 2:
                # vector column (collect/topk): decode only the present
                # elements, regroup into per-row lists by row counts
                ev = np.asarray(emits[f"e_{col.name}"])[idx]
                flat_present = valid.reshape(-1)
                elems = decode_value(
                    data.reshape(-1)[flat_present],
                    ev.reshape(-1)[flat_present],
                    col.type.element, self.dictionary,
                )
                counts = valid.sum(axis=1)
                bounds = np.cumsum(counts)[:-1]
                # element-wise object array: np.asarray would promote
                # equal-length list elements (nested ARRAY values) to 2-D
                flat = np.empty(len(elems), object)
                for i2, v2 in enumerate(elems):
                    flat[i2] = v2
                cols[col.name] = [
                    list(part) for part in np.split(flat, bounds)
                ]
                continue
            cols[col.name] = decode_value(data, valid, col.type, self.dictionary)
        ts = np.asarray(emits["emit_ts"])[idx]
        ws = np.asarray(emits["ws"])[idx] if "ws" in emits else None
        we = np.asarray(emits["we"])[idx] if "we" in emits else None
        tomb = (
            np.asarray(emits["tombstone"])[idx] if "tombstone" in emits else None
        )
        out: List[SinkEmit] = []
        key_names = [c.name for c in schema.key_columns]
        val_names = [c.name for c in schema.value_columns]
        collapse_null_keys = (
            self.agg is None
            and self.join is None
            and self.ss_join is None
            and not any(
                isinstance(op, (st.StreamSelectKey, st.TableSelectKey))
                for op in self.pre_ops
            )
        )
        for j in range(idx.size):
            key = tuple(cols[kn][j] for kn in key_names)
            if collapse_null_keys and key and all(k is None for k in key):
                # key passthrough of a null-key record: the oracle carries
                # an empty key tuple, which the sink writes as a null key
                key = ()
            if tomb is not None and tomb[j]:
                row = None
            else:
                row = {kn: cols[kn][j] for kn in key_names}
                row.update({vn: cols[vn][j] for vn in val_names})
            window = (int(ws[j]), int(we[j])) if ws is not None else None
            out.append(SinkEmit(key, row, int(ts[j]), window))
        if sort:
            # ts-major, window-start-minor: matches the oracle's per-record
            # ascending-window emission order for hopping expansions
            if self.collect_raw_emits:
                # keep the emit-order permutation so the raw block below
                # stays row-aligned with the fanned-out emits
                order = sorted(
                    range(len(out)),
                    key=lambda j: (out[j].ts, out[j].window or (0, 0)),
                )
                out = [out[j] for j in order]
                idx = idx[np.asarray(order, np.intp)]
            else:
                out.sort(key=lambda e: (e.ts, e.window or (0, 0)))
        if self.collect_raw_emits and out:
            # fused-residual handoff: the emission batch's scalar columns,
            # gathered on device in final emit order.  Vector/map columns
            # are skipped (the tap kernel host-paths spans that need them)
            raw_cols: Dict[str, Tuple[jnp.ndarray, jnp.ndarray]] = {}
            for col in schema.columns():
                data = emits.get(f"v_{col.name}")
                if data is None or data.ndim != 1:
                    continue
                raw_cols[col.name] = (
                    data[idx], emits[f"m_{col.name}"][idx]
                )
            self.last_raw_block = {
                "cols": raw_cols,
                "ts": emits["emit_ts"][idx],
                "row_none": np.fromiter(
                    (e.row is None for e in out), bool, count=len(out)
                ),
                "n": len(out),
                # identity of the emit list this block is aligned with —
                # the dispatcher checks it, so a member-lane decode can
                # never hand its block to the primary's fan-out
                "emits_id": id(out),
            }
        return out

    # --------------------------------------------- suppress (EMIT FINAL)
    def flush(self, stream_time: Optional[int] = None) -> List[SinkEmit]:
        """Emit & evict closed windows (EMIT FINAL path; host-side scan —
        off the hot loop, the TableSuppressBuilder analog)."""
        if self.ss_join is not None:
            if stream_time is None:
                return self.ss_expire_host()
            return self.ss_flush(stream_time)
        if not self.suppress or self.store_layout is None:
            return []
        state = jax.device_get(self.state)
        if stream_time is None:
            stream_time = int(state["max_ts"])
        occ = state["occ"]
        ws = state["wstart"]
        size = self.window.size_ms
        closed = (
            occ
            & state["dirty"]
            & ~state["emitted"]
            & (ws + size + self.grace_ms <= stream_time)
        )
        self.state = dict(self.state)
        # the flush watermark advances the emission clock even when nothing
        # closes (oracle flush_time semantics)
        self.state["emit_clock"] = jnp.maximum(
            self.state["emit_clock"], jnp.int64(stream_time)
        )
        idx = np.nonzero(closed)[0]
        if idx.size == 0:
            return []
        result = self._emit_slots(idx)
        # mark flushed windows clean (suppressed windows emit exactly once)
        slots = jnp.asarray(idx.astype(np.int32))
        self.state["dirty"] = self.state["dirty"].at[slots].set(False)
        self.state["emitted"] = self.state["emitted"].at[slots].set(True)
        return result

    def scan_store(self) -> List[SinkEmit]:
        """Materialized-state scan: every live slot of the HBM store,
        finalized + post-op'd + decoded.  Serves pull queries straight from
        device state (KsMaterializedTableIQv2 analog) instead of a host-side
        shadow dict.  EMIT FINAL tables expose only already-emitted windows
        (matching what downstream consumers have observed)."""
        if self.store_layout is None:
            return []
        occ = np.asarray(jax.device_get(self.state["occ"]))[:-1]
        if self.suppress:
            occ = occ & np.asarray(jax.device_get(self.state["emitted"]))[:-1]
        return self._emit_slots(np.nonzero(occ)[0])

    def lookup_store(self, key_tuples) -> Optional[List[SinkEmit]]:
        """Keyed pull fast path (KeyedTableLookupOperator vs
        TableScanOperator — PullPhysicalPlanBuilder.java:247-256): match the
        store's key-repr columns against the WHERE clause's exact keys on
        device, transfer and decode ONLY the matching slots.  Windowed
        stores return every window of the key.  Returns None when this
        store can't serve keyed lookups (no layout, or a key value with no
        64-bit repr) — the caller falls back to scan_store()."""
        if self.store_layout is None:
            return None
        reprs_per_tuple: List[List[int]] = []
        for kt in key_tuples:
            reprs = []
            for v, t in zip(kt, self.key_types):
                r = _host_repr64(v, t)
                if r is None:
                    return None
                reprs.append(r)
            reprs_per_tuple.append(reprs)
        occ = self.state["occ"][:-1]
        if self.suppress:
            occ = occ & self.state["emitted"][:-1]
        nonnull = self.state["knull"][:-1] == 0
        m_any = jnp.zeros_like(occ)
        for reprs in reprs_per_tuple:
            m = occ & nonnull
            for i, r in enumerate(reprs):
                m = m & (self.state[f"key{i}"][:-1] == jnp.int64(r))
            m_any = m_any | m
        idx = np.nonzero(np.asarray(jax.device_get(m_any)))[0]
        return self._emit_slots(idx)

    #: slots decoded by the most recent scan_store/lookup_store call — the
    #: store metric proving keyed pulls touch O(matches) slots, not
    #: O(live-slots) like a scan
    last_pull_slots_decoded: int = 0

    def _emit_slots_sliced(self, idx: np.ndarray) -> List[SinkEmit]:
        """Materialized-state decode for a SLICED store: expand each key
        slot's live slices into the (slot, window) pairs of the PRIMARY
        member still inside retention, monoid-merge the covering slices per
        window, and decode — the pull-query view of a sliced hopping
        aggregation.  Off the hot loop (host lane construction + eager
        device combine).

        Parity note: a late-but-in-grace record lands in its slice once,
        so a window that was already closed at its arrival still absorbs
        it HERE (the expansion store would not) — sliced pull results over
        closed-but-retained windows may include late records the
        per-window grace check dropped from emission on both paths."""
        self.last_pull_slots_decoded = int(idx.size)
        if idx.size == 0:
            return []
        member = self.members[0]
        sid = np.asarray(jax.device_get(self.state["slice_id"]))[idx]
        max_ts = int(jax.device_get(self.state["max_ts"]))
        width = self.slice_width
        S = W.slices_per_window(member.size_ms, width)
        A = member.advance_ms // width
        k = W.hopping_expansion(member.size_ms, member.advance_ms)
        pairs = set()
        rows, cols = np.nonzero(sid >= 0)
        for r, c in zip(rows, cols):
            s = int(sid[r, c])
            g = s - s % A
            for j in range(k):
                w = g - j * A
                if w < 0 or w + S <= s:
                    continue
                # mirror the expansion store's retention pass: windows past
                # wstart + retention are evicted, not scanned
                if w * width + member.retention_ms < max_ts:
                    continue
                pairs.add((int(idx[r]), w))
        if not pairs:
            return []
        # window-start-major, slot-minor: the windowed-scan order of the
        # expansion store's _emit_slots (ws then creation)
        lanes = sorted(pairs, key=lambda p: (p[1], p[0]))
        slot_lane = jnp.asarray(
            np.asarray([p[0] for p in lanes], np.int32)
        )
        w_lane = jnp.asarray(np.asarray([p[1] for p in lanes], np.int64))
        env, row_ts, dec_exceeded = self._combine_windows(
            self.state, slot_lane, w_lane, member
        )
        mask = jnp.ones(len(lanes), bool)
        emits = self._member_emit(
            env, row_ts, dec_exceeded, mask, member, len(lanes)
        )
        self.last_pull_slots_decoded = len(lanes)
        return self._decode_emits(emits, sort=False)

    def _emit_slots(self, idx: np.ndarray) -> List[SinkEmit]:
        """Finalize + post-op + decode the given store slots (EMIT FINAL
        emission path, shared by the per-batch close and end-of-stream
        flush), ordered by window start."""
        if self.sliced:
            return self._emit_slots_sliced(idx)
        self.last_pull_slots_decoded = int(idx.size)
        if idx.size == 0:
            return []
        ws_host = np.asarray(self.state["wstart"])[idx]
        born = (
            np.asarray(self.state["born"])[idx]
            if "born" in self.state
            else np.zeros(idx.size, np.int64)
        )
        # window-end-major (ws + fixed size), creation-order-minor — the
        # oracle SuppressNode's emission order
        idx = idx[np.lexsort((born, ws_host))]
        slots = jnp.asarray(idx.astype(np.int32))
        env, row_ts, dec_exceeded = self._finalized_env(
            self.state, slots, idx.size
        )
        mask = jnp.ones(idx.size, bool)
        # post-agg ops on the emitted rows
        for op in self.post_ops:
            c = JaxExprCompiler(env, idx.size, self.dictionary)
            if isinstance(op, st.TableFilter):
                pred = c.compile(op.predicate)
                mask = mask & pred.valid & pred.data.astype(bool)
            else:
                new_env = {}
                src_keys = [k.name for k in op.source.schema.key_columns]
                out_keys = [k.name for k in op.schema.key_columns]
                for nname, oname in zip(out_keys, src_keys):
                    if oname in env:
                        new_env[nname] = env[oname]
                for name, e in op.selects:
                    new_env[name] = c.compile(e)
                for p in ("ROWTIME", "WINDOWSTART", "WINDOWEND"):
                    if p in env:
                        new_env[p] = env[p]
                env = new_env
        emits = self._pack_emits(env, mask, row_ts)
        emits["dec_envelope"] = jnp.sum(
            (dec_exceeded & mask).astype(jnp.int64)
        ).reshape(1)
        # idx is already in emission order (window end, then creation) —
        # keep it; ts-sorting would break the oracle's suppress ordering
        return self._decode_emits(emits, sort=False)
