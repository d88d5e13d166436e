"""Engine: statement lifecycle — parse, analyze, plan, execute.

Analog of ksqldb-engine's KsqlEngine (KsqlEngine.java:104: parse():285,
prepare():290, plan():298, execute():308, executeTransientQuery():343) plus
the query registry (QueryRegistryImpl.java:68).  Persistent queries run on
the XLA device backend when the plan lowers (DeviceExecutor, the
KSPlanBuilder-seam analog) and fall back to the row oracle otherwise,
selected by ``ksql.runtime.backend``; the engine also serves pull queries
from sink materializations.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import threading
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ksql_tpu.common import health as qhealth
from ksql_tpu.common import tracing
from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.common.errors import AnalysisException, KsqlException, PlanningException
from ksql_tpu.common.schema import LogicalSchema
from ksql_tpu.analyzer.analyzer import analyze_query
from ksql_tpu.execution import steps as st
from ksql_tpu.execution.interpreter import ExpressionCompiler, TypeResolver, make_caster
from ksql_tpu.functions.registry import FunctionRegistry, default_registry
from ksql_tpu.metastore.metastore import (
    DataSource,
    DataSourceType,
    KeyFormat,
    MetaStore,
)
from ksql_tpu.parser import ast_nodes as ast
from ksql_tpu.parser.parser import parse_statements
from ksql_tpu.planner.logical import LogicalPlanner, PlannedQuery
from ksql_tpu.runtime.oracle import OracleExecutor, SinkEmit
from ksql_tpu.common import config as cfg
from ksql_tpu.runtime.topics import Broker, Consumer, Record


@dataclasses.dataclass
class QueryError:
    """One classified query error (reference QueryError + type enum)."""

    timestamp_ms: int
    message: str
    error_type: str  # USER | SYSTEM | UNKNOWN


def _marker_hit(text: str, markers) -> bool:
    """Case-insensitive marker match.  Single-word markers require a
    leading word boundary — a plain substring check made 'broadcast' trip
    the 'cast' USER rule.  Only the LEADING edge is bounded so markers
    still match as CamelCase prefixes ('overflow' in OverflowError, 'XLA'
    in XlaRuntimeError) and as stems ('deserialize' in deserialization).
    Multi-word markers ('does not exist') stay substrings."""
    import re as _re

    for m in markers:
        if " " in m:
            if m.lower() in text.lower():
                return True
        elif _re.search(rf"(?<![A-Za-z0-9]){_re.escape(m)}", text, _re.IGNORECASE):
            return True
    return False


def classify_error(e: Exception, custom_rules: str = "") -> str:
    """QueryErrorClassifier chain analog: built-in classifiers
    (RegexClassifier, MissingTopicClassifier, ...) fold to one verdict;
    ksql.error.classifier.regex supplies extra 'TYPE:regex' rules
    (semicolon-separated)."""
    import re as _re

    text = f"{type(e).__name__}: {e}"
    for rule in str(custom_rules or "").split(";"):
        rule = rule.strip()
        if not rule or ":" not in rule:
            continue
        etype, pattern = rule.split(":", 1)
        try:
            if _re.search(pattern, text):
                return etype.strip().upper()
        except _re.error:
            continue
    from ksql_tpu.common.faults import FaultInjected

    if isinstance(e, FaultInjected):
        # injected faults model infrastructure failures, whatever their
        # message mentions (a serde-point fault contains 'deserialize',
        # which would otherwise win the USER check below)
        return "SYSTEM"
    user_markers = (
        "SerdeException", "deserialize", "FunctionException", "cast",
        "arithmetic", "Decimal", "overflow", "JSONDecodeError",
    )
    system_markers = ("Topic", "does not exist", "OSError", "IOError",
                      "MemoryError", "XLA", "FaultInjected")
    if _marker_hit(text, user_markers):
        return "USER"
    if _marker_hit(text, system_markers):
        return "SYSTEM"
    return "UNKNOWN"


@dataclasses.dataclass
class QueryHandle:
    """PersistentQueryMetadata analog."""

    query_id: str
    plan: st.QueryPlan
    sink_name: Optional[str]
    executor: Any  # OracleExecutor | DeviceExecutor
    consumer: Consumer
    state: str = "RUNNING"  # RUNNING | PAUSED | TERMINATED | ERROR
    sql: str = ""
    backend: str = "oracle"  # which runtime executes this query
    # sink materialization for pull queries and standby promotion:
    # key -> (row, window, key, emit_ts)
    materialized: Dict[Any, Tuple] = dataclasses.field(default_factory=dict)
    # scalable-push subscribers: called with each SinkEmit as it happens
    # (ScalablePushRegistry/ProcessingQueue analog)
    push_listeners: List[Callable] = dataclasses.field(default_factory=list)
    # batch-level push subscribers (fused tap residuals, ISSUE 12): called
    # once per decoded emission batch with (emits, raw_block) BEFORE the
    # per-emit fan-out, where raw_block carries the still-device-resident
    # columnar emit arrays when this query runs on the device backend —
    # the shared push pipeline feeds its residual kernel from them instead
    # of re-encoding host rows
    push_batch_listeners: List[Callable] = dataclasses.field(
        default_factory=list
    )
    # classified error queue (QueryMetadata.getQueryErrors, bounded by
    # ksql.query.error.max.queue.size) + restart backoff bookkeeping
    error_queue: List[QueryError] = dataclasses.field(default_factory=list)
    retry_at_ms: float = 0.0
    retry_backoff_ms: float = 0.0
    # self-healing bookkeeping: restarts attempted so far, and the terminal
    # flag set once ksql.query.retry.max is exhausted (no further restarts;
    # /healthcheck flips unhealthy and /metrics carries the counts)
    restart_count: int = 0
    terminal: bool = False
    # standby replica: keeps consuming/materializing but publishes nothing
    # (shared-data-plane num.standby.replicas analog)
    standby: bool = False
    # progress tracker + stall watchdog (common/health.py): per-partition
    # offsets/lag, event-time watermark, e2e latency, bounded sample ring
    progress: Optional[qhealth.QueryProgress] = None
    # processing-epoch bookkeeping (ksql.commit.per.record): the durable
    # commit point the current tick has reached, the state epoch matching
    # it (record-synchronous backends), records to drop on replay
    # (poison replay-without-record), and the replay/deadline counters
    # surfaced in /metrics
    commit_positions: Optional[Dict[Tuple[str, int], int]] = None
    epoch: Optional[Dict[str, Any]] = None
    poison_skip: set = dataclasses.field(default_factory=set)
    replayed_records: int = 0
    tick_deadlines: int = 0
    # non-attributable-poison bisection: when a deterministic USER error
    # hides inside a batched device flush (buffered records from earlier
    # process() calls), each re-crash halves the records the next tick may
    # poll ({"limit": n}) until the window is ONE record — which IS
    # attributable and gets skipped atomically via poison_skip.  Cleared by
    # the first clean tick.  Bounded by ksql.query.retry.max like any
    # crash-loop.
    poison_bisect: Optional[Dict[str, Any]] = None
    # elastic-mesh bookkeeping (health-driven live rescale): a per-query
    # shard-count override the next executor (re)build honors, the
    # in-flight cutover descriptor, verdict streaks feeding the
    # hysteresis, the cooldown clock, and completed cutovers per direction
    # (ksql_query_reshard_total{direction})
    shard_override: Optional[int] = None
    pending_rescale: Optional[Dict[str, Any]] = None
    rescale_lag_streak: int = 0
    rescale_idle_streak: int = 0
    last_rescale_ms: float = 0.0
    # cooldown multiplier, doubled on every REVERTED cutover (a reshard the
    # state has proven it cannot perform must not re-pay checkpoint + two
    # recompiles every plain cooldown forever); reset by a completed one
    rescale_penalty: int = 0
    reshard_total: Dict[str, int] = dataclasses.field(default_factory=dict)
    # mesh fault domain (shard-level failure containment): consecutive
    # strikes per shard (reset by any clean tick), lifetime strike totals
    # (ksql_query_shard_strikes_total{shard}), the query's ORIGINAL shard
    # width while running degraded (None = not degraded; the regrow probe
    # restores it once the fault clears), and the wall clock of the last
    # strike (the regrow cooldown's "fault cleared" evidence)
    shard_strikes: Dict[int, int] = dataclasses.field(default_factory=dict)
    shard_strikes_total: Dict[int, int] = dataclasses.field(
        default_factory=dict
    )
    mesh_degraded_from: Optional[int] = None
    last_shard_strike_ms: float = 0.0
    # emit fence: a kill switch captured by the CURRENT executor's emit
    # callback; revoked at the deadline fence and on every executor
    # rebuild, so an abandoned zombie worker that already holds the old
    # callback reference can never write stale materialized rows or wake
    # push listeners (closes the TOCTOU left by nulling emit_callback)
    emit_fence: Optional[Dict[str, bool]] = None
    # rebuild fence (ksql.query.rebuild.timeout.ms): identity token bound
    # at the start of each supervised executor rebuild; the deadline
    # handler swaps it, so an abandoned rebuild worker (hung XLA compile
    # that later wakes) fails its alive() test and can never install its
    # executor, swap the emit fence, or touch family registrations
    rebuild_token: Optional[object] = None
    rebuild_deadlines: int = 0
    # memoized EXPLAIN classification: (classification-input key, decision)
    # — the plan never changes after creation, so the deep lowering probe
    # runs at most once per effective-config combination
    static_decision: Optional[Tuple[Tuple[str, bool], Any]] = None
    # static device-memory footprint report (analysis/mem_model), computed
    # once at admission: feeds EXPLAIN's 'Device memory (static)' table and
    # the ksql_query_estimated_hbm_bytes{point} gauge.  None = the plan
    # does not lower to the device backend (no modeled HBM)
    mem_report: Optional[Any] = None
    # multi-query optimizer verdict (planner/mqo.MqoDecision) from this
    # query's last build: the cost model's accept/reject reasoning EXPLAIN
    # prints.  None = no shared pipeline was in scope at build time
    mqo_decision: Optional[Any] = None
    # overload-manager shedding order (ksql.query.priority, higher = more
    # important): under source pacing, below-top-tier queries are clamped
    # harder.  Captured at CREATE from the effective config.
    priority: int = 100

    def is_running(self) -> bool:
        return self.state == "RUNNING"

    @property
    def health(self) -> str:
        return self.progress.health if self.progress is not None else qhealth.IDLE


#: sentinel for "expression is not a literal" in pull-constraint analysis
#: (None is a real value: WHERE key = NULL)
_NO_LITERAL = object()

#: fallback_reasons entry for a distributed query whose source the C++
#: ingest tier could decode single-device but whose executor kept the
#: Python HostBatch path.  Since the mesh-aware lane split landed
#: (DistributedDeviceQuery.split_columns) eligible plans engage the
#: native tier directly, so this counter staying at zero is itself a
#: pinned invariant; the constant remains for dashboards and the
#: regression test that asserts it no longer fires
NATIVE_INGEST_BYPASS_REASON = (
    "native C++ ingest bypassed in distributed mode; rows decode via "
    "the shared Python path"
)

#: EXPLAIN ``Backend (static)`` note for a distributed placement whose
#: source the C++ ingest tier batch-decodes (the static classifier in
#: analysis/plan_verifier surfaces it; the runtime counter is
#: ksql_native_ingest_rows_total)
NATIVE_INGEST_ENGAGED_NOTE = (
    "native C++ ingest engaged (mesh-aware lane split inside the batch "
    "decoder)"
)


@dataclasses.dataclass
class StatementResult:
    kind: str  # 'ddl' | 'query' | 'rows' | 'ok'
    message: str = ""
    query_id: Optional[str] = None
    rows: Optional[List[dict]] = None
    columns: Optional[List[str]] = None


def _parse_wrap(raw) -> bool:
    """The one boolean parse for WRAP_SINGLE_VALUE (shared by schema
    inference and serde validation so they always agree)."""
    return raw if isinstance(raw, bool) else str(raw).strip().lower() == "true"


def _validate_wrap_property(raw, value_format: str, value_columns) -> Optional[bool]:
    """WRAP_SINGLE_VALUE property validation (SerdeFeaturesFactory
    .getValueWrapping): only single-field schemas, only formats where
    wrapping is configurable."""
    if raw is None:
        return None
    from ksql_tpu.serde import formats as _fmt

    wrap = _parse_wrap(raw)
    f = value_format.upper()
    supported = _fmt.WRAPPABLE if wrap else _fmt.UNWRAPPABLE_VALUES
    if f not in supported:
        raise KsqlException(
            f"Format '{f}' does not support 'WRAP_SINGLE_VALUE' set to "
            f"'{str(wrap).lower()}'."
        )
    if len(list(value_columns)) != 1:
        raise KsqlException(
            "'WRAP_SINGLE_VALUE' is only valid for single-field value schemas"
        )
    return wrap


def _parses_unwrapped(raw) -> bool:
    """True when WRAP_SINGLE_VALUE is explicitly set and parses false."""
    return raw is not None and not _parse_wrap(raw)


def _avro_nested_defaults(prefix: tuple, avro_type) -> list:
    """(path, default) for every non-optional Avro record field below
    ``avro_type`` that declares a schema default — a null written at that
    path is replaced by the default (Connect AvroData substitution)."""
    out: list = []

    def is_null(b):
        return b == "null" or (isinstance(b, dict) and b.get("type") == "null")

    def walk(path, t):
        if isinstance(t, list):
            for b in t:
                if not is_null(b):
                    walk(path, b)
            return
        if isinstance(t, dict) and t.get("type") == "record":
            for f in t.get("fields", ()):
                ft = f["type"]
                nullable = isinstance(ft, list) and any(is_null(b) for b in ft)
                if "default" in f and not nullable:
                    out.append((path + (f["name"],), f["default"]))
                walk(path + (f["name"],), ft)

    walk(prefix, avro_type)
    return out


def _schemas_compatible(query_schema, target_schema) -> bool:
    """INSERT INTO schema check: equal, or each query column implicitly
    coerces to the target column (numeric widening INT -> BIGINT ->
    DECIMAL -> DOUBLE; reference DefaultSqlValueCoercer.canImplicitlyCast)."""
    from ksql_tpu.common.types import SqlBaseType as B

    order = {B.INTEGER: 0, B.BIGINT: 1, B.DECIMAL: 2, B.DOUBLE: 3}

    def ok(src, dst) -> bool:
        if src == dst:
            return True
        sb, db = src.base, dst.base
        if sb in order and db in order and order[sb] <= order[db]:
            return True
        return False

    for group in ("key_columns", "value_columns"):
        qs, ts = list(getattr(query_schema, group)), list(getattr(target_schema, group))
        if len(qs) != len(ts):
            return False
        for q, t in zip(qs, ts):
            if q.name != t.name or not ok(q.type, t.type):
                return False
    return True


class _TickSupervisionWorker:
    """Persistent per-query tick-supervision worker.

    The deadline supervisor submits each non-empty tick body here instead
    of spawning a thread per tick (the ~50–100µs per-tick spawn the
    ROADMAP flagged).  The submitting poll loop blocks on the done event —
    worker and supervisor stay serialized exactly like the joined per-tick
    workers this replaces — or abandons at the deadline, after which the
    worker finishes its hung tick as a fenced zombie (the tick body's own
    ``alive()``/emit-fence guards mute its late writes) and EXITS: it must
    never pick up a later tick whose fences it predates."""

    def __init__(self, query_id: str):
        import queue

        self._q: Any = queue.Queue()
        self._abandoned = False
        self.thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"tick-supervision-{query_id}",
        )
        self.thread.start()

    # graftlint: entrypoint=tick-supervision
    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            fn, done = item
            try:
                fn()
            finally:
                done.set()
            if self._abandoned:
                return

    def submit(self, fn) -> threading.Event:
        done = threading.Event()
        self._q.put((fn, done))
        return done

    def alive(self) -> bool:
        return self.thread.is_alive() and not self._abandoned

    def abandon(self) -> None:
        """Deadline blown: mark the worker a zombie.  The sentinel wakes a
        worker that already finished the hung tick and is idle-blocked on
        the queue, so abandoned workers always exit instead of leaking."""
        # single-writer set-once flag: only the supervising poll loop ever
        # writes it, the worker only reads it between tasks
        self._abandoned = True  # graftlint: owner=main
        self._q.put(None)

    def stop(self, join_timeout_s: float = 1.0) -> None:
        """Terminate path: shut the worker down and join it (a worker
        still wedged inside a hung tick can't be joined — bounded wait)."""
        self._abandoned = True  # graftlint: owner=main
        self._q.put(None)
        self.thread.join(join_timeout_s)


class KsqlEngine:
    def __init__(
        self,
        config: Optional[KsqlConfig] = None,
        broker: Optional[Broker] = None,
        registry: Optional[FunctionRegistry] = None,
    ):
        self.config = config or KsqlConfig()
        # arm the chaos layer before any topic/serde/executor exists so
        # every seam (including cached serdes) sees the fault proxy;
        # idempotent per spec, so engine forks don't reset one-shot rules
        from ksql_tpu.common import faults as _faults

        _faults.install_from_config(
            str(self.config.get(cfg.FAULT_INJECTION_RULES) or "")
        )
        self.broker = broker or Broker()
        self.registry = registry or default_registry()
        if registry is None:
            # UserFunctionLoader.java:45 analog: scan ksql.extension.dir for
            # decorator-declared functions; registered into a per-engine
            # registry fork so extensions never leak into the process-wide
            # built-in registry (sandboxes share the fork via registry=)
            ext_dir = str(self.config.get(cfg.EXTENSION_DIR) or "")
            if ext_dir and os.path.isdir(ext_dir):
                from ksql_tpu.functions.loader import load_extensions

                fork = self.registry.copy()
                if load_extensions(ext_dir, fork):
                    self.registry = fork
        from ksql_tpu.serde.schema_registry import SchemaRegistry

        self.schema_registry = SchemaRegistry()
        self.metastore = MetaStore()
        self.planner = LogicalPlanner(self.registry)
        self.queries: Dict[str, QueryHandle] = {}
        self.variables: Dict[str, str] = {}
        self.session_properties: Dict[str, Any] = {}
        self._query_seq = itertools.count(1)
        self._lock = threading.RLock()
        self.processing_log: List[Tuple[str, str]] = []
        # queries actually running on the XLA backend (vs oracle fallback)
        self.device_query_count = 0
        # of those, queries sharded across the device mesh (backend=
        # distributed); a distribution gap that fell back single-device
        # counts under device_query_count instead
        self.distributed_query_count = 0
        # True on engine forks used for pre-execution validation
        self.is_sandbox = False
        from ksql_tpu.common.metrics import MetricCollectors

        self.metrics = MetricCollectors()
        # why plans fell back to the oracle (reason -> count); surfaced by
        # scripts/device_coverage.py, /metrics (fallback-reasons), and
        # useful for lowering roadmaps.  Windowing-shape fallbacks (a
        # hopping query silently keeping the k-fold expansion path instead
        # of slicing) count here too, so they are observable.
        self.fallback_reasons: Dict[str, int] = {}
        # multi-query-optimizer sharing registries: window-family signature
        # (correlated signature under ksql.optimizer.mqo.enabled, exact
        # family signature otherwise) -> primary query id; source-prefix
        # signature -> primary query id; and member query id -> its
        # primary — both kinds — (engine-level view of
        # CompiledDeviceQuery.attach_member / attach_prefix_member)
        self.window_families: Dict[tuple, str] = {}
        self.prefix_pipelines: Dict[tuple, str] = {}
        self.family_members: Dict[str, str] = {}
        # MQO observability: runtime attach refusals + cost-model rejects
        # per stable reason code (ksql_query_family_attach_refused_total
        # {reason}) and cost-model verdicts (ksql_mqo_decisions_total
        # {verdict})
        self.family_attach_refused: Dict[str, int] = {}
        self.mqo_decisions: Dict[str, int] = {}
        # flight recorders (common/tracing.py): per-query ring buffers of
        # recent tick traces, engine-owned so concurrent engines in one
        # process never share trace state.  Feeds EXPLAIN ANALYZE, the
        # /query-trace/<id> endpoint, and the Prometheus /metrics stage
        # histograms.
        self.trace_enabled = cfg._bool(self.config.get(cfg.TRACE_ENABLE, True))
        self.trace_ring = int(self.config.get(cfg.TRACE_RING_SIZE, 64))
        self.trace_recorders: Dict[str, tracing.FlightRecorder] = {}
        # the process's one gc.callbacks hook books the collector's pauses
        # on the open tick: held from here to shutdown() (or to the
        # engine's own collection, where nobody calls that)
        self._gc_hook_release = None
        if self.trace_enabled:
            tracing.hold_gc_hook()
            self._gc_hook_release = weakref.finalize(
                self, tracing.release_gc_hook
            )
        # entries trimmed off the processing-log ring so far (the ring is
        # bounded by ksql.processing.log.buffer.size, cached here — the
        # append sits on the per-record error path); /metrics surfaces it
        self.plog_dropped = 0
        self._plog_cap = int(
            self.config.get(cfg.PROCESSING_LOG_BUFFER_SIZE, 10000)
        )
        # supervised push-query sessions (server/rest.py) report their
        # self-healing restarts here so /metrics carries the counter
        self.push_session_restarts = 0
        # persistent per-query tick-supervision workers (amortize the
        # per-tick thread spawn); abandoned workers are replaced, stopped
        # workers joined on TERMINATE; deadline-abandoned zombies are
        # remembered so shutdown() can give them a bounded join too
        self._tick_workers: Dict[str, _TickSupervisionWorker] = {}
        self._abandoned_workers: List[_TickSupervisionWorker] = []
        # push registry (tentpole): shared serving pipelines multiplexing
        # compatible push sessions as filtered taps.  Lazily built by
        # get_push_registry so engines that never serve push queries pay
        # nothing; metrics_snapshot and shutdown() read it when present.
        self.push_registry: Optional[Any] = None
        # overload manager (engine/overload.py): resource-pressure
        # monitors -> OK/ELEVATED/CRITICAL -> prioritized degradation
        # ladder.  Cheap to construct (no thread); sampling piggybacks on
        # poll_once, server mode adds a dedicated monitor thread.
        from ksql_tpu.engine.overload import OverloadManager

        self.overload = OverloadManager(self)
        # telemetry timelines (common/timeline.py): retained per-query /
        # per-pipeline interval series folded from finished tick traces
        # via the flight-recorder observer.  Lazily built per owner; the
        # skew detector's verdicts drain into telemetry_events for the
        # /alerts "telemetry" section (note_event evidence only surfaces
        # for LAGGING/STALLED queries — a skewed-but-healthy query must
        # still alert).
        self.telemetry_enabled = cfg._bool(
            self.config.get(cfg.TELEMETRY_ENABLE, True)
        )
        self.timelines: Dict[str, Any] = {}
        self.telemetry_events: deque = deque(maxlen=32)
        # incremental changelog journals (runtime/changelog.py): one per
        # journaled query, chained to the checkpoint generation id below.
        # None until a generation exists — frames need a base snapshot.
        self._changelogs: Dict[str, Any] = {}
        self._ckpt_id: Optional[str] = None
        # per-query wall time of the last fresh snapshot
        # (ksql_checkpoint_age_seconds)
        self._checkpoint_saved_at: Dict[str, float] = {}
        # queries already noted as seam-less (changelog.skip is loud ONCE)
        self._changelog_skip_noted: set = set()
        # raised when a journal passes ksql.changelog.max.bytes; the next
        # poll-loop gate checkpoints early (rotation truncates the file)
        self._changelog_force_ckpt = False

    def timeline_store(self, owner_id: str):
        """Lazy per-owner TimelineStore (owner = query id or push
        pipeline id), config-shaped once at creation."""
        tl = self.timelines.get(owner_id)
        if tl is None:
            from ksql_tpu.common.timeline import TimelineStore

            tl = self.timelines[owner_id] = TimelineStore(
                owner_id,
                interval_ms=int(
                    self.config.get(cfg.TELEMETRY_INTERVAL_MS, 5000)
                ),
                ring=int(
                    self.config.get(cfg.TELEMETRY_RING_INTERVALS, 240)
                ),
                skew_ratio=float(
                    self.config.get(cfg.TELEMETRY_SKEW_RATIO, 1.8)
                ),
                skew_intervals=int(
                    self.config.get(cfg.TELEMETRY_SKEW_INTERVALS, 3)
                ),
            )
        return tl

    def trace_recorder(self, query_id: str) -> tracing.FlightRecorder:
        rec = self.trace_recorders.get(query_id)
        if rec is None:
            rec = self.trace_recorders[query_id] = tracing.FlightRecorder(
                query_id, self.trace_ring
            )
            if self.telemetry_enabled:
                # retention hook: every recorded tick (queries AND push
                # pipeline pumps — both create recorders through here)
                # folds into the owner's timeline
                rec.observer = self.timeline_store(query_id).fold
        return rec

    def recorder_if_enabled(
        self, query_id: str
    ) -> Optional[tracing.FlightRecorder]:
        """The query's flight recorder, or None when tracing is off —
        the guard every `with tracing.tick(...)` site needs."""
        return self.trace_recorder(query_id) if self.trace_enabled else None

    def metrics_snapshot(self) -> Dict[str, Any]:
        """Engine + per-query gauges (KsqlEngineMetrics analog)."""
        return self.metrics.snapshot(engine=self)

    def annotate_serde_semantics(self, plan: st.QueryPlan) -> None:
        """Attach metastore-held serde semantics (PROTOBUF nullable
        representation, float32 fields) to the plan's source/sink steps as
        runtime annotations — plan JSON stays format-stable."""
        for step in st.walk_steps(plan.physical_plan):
            src_name = getattr(step, "source_name", None)
            target = None
            if src_name:
                target = self.metastore.get_source(src_name)
            elif isinstance(step, (st.StreamSink, st.TableSink)) and plan.sink_name:
                target = self.metastore.get_source(plan.sink_name)
            if target is None:
                continue
            if getattr(target, "proto_nullable_rep", None):
                step.__dict__["_proto_nullable_all"] = True
            if getattr(target, "proto_float32", ()):
                step.__dict__["_proto_float32"] = tuple(target.proto_float32)

    # ------------------------------------------------------- scalable push
    def register_push_tap(
        self, source_name: str, cb, batch_cb=None
    ) -> Optional[Tuple[str, Callable]]:
        """Push-registry seam: attach a subscriber to the RUNNING
        persistent query materializing ``source_name`` — the fan-out rides
        the query's fence-guarded ``on_emit`` (PR-6 zombie fencing and the
        PR-8 race rules apply to the delivery path unchanged).  Returns
        ``(query_id, unsubscribe)`` so the caller can watch the upstream's
        lifecycle, or None when no running query writes the source (the
        shared pipeline then owns a catchup consumer instead).

        ``batch_cb`` additionally subscribes at BATCH granularity:
        ``batch_cb(emits, raw_block)`` fires once per decoded emission
        batch before the per-emit fan-out; when the upstream runs on the
        device backend ``raw_block`` carries the emission batch's columnar
        arrays still device-resident (fused-residual handoff — the shared
        pipeline's tap kernel evaluates straight over them instead of
        bouncing through host rows)."""
        if not cfg._bool(self.config.get("ksql.query.push.v2.enabled", True)):
            return None
        for qid, h in list(self.queries.items()):
            if h.sink_name == source_name and h.is_running():
                h.push_listeners.append(cb)
                if batch_cb is not None:
                    h.push_batch_listeners.append(batch_cb)
                    self._arm_raw_emit_blocks(h)

                def unsubscribe(h=h, cb=cb, batch_cb=batch_cb):
                    try:
                        h.push_listeners.remove(cb)
                    except ValueError:
                        pass
                    if batch_cb is not None:
                        try:
                            h.push_batch_listeners.remove(batch_cb)
                        except ValueError:
                            pass
                        # last batch listener gone -> stop paying the
                        # per-batch device gather + block retention
                        self._arm_raw_emit_blocks(h)

                return qid, unsubscribe
        return None

    @staticmethod
    def _arm_raw_emit_blocks(handle: "QueryHandle") -> None:
        """Flip raw-block collection on the handle's CURRENT device
        executor (rebuilds re-arm via _build_executor) so the next decode
        keeps its columnar emit arrays for the batch listeners."""
        dev = getattr(handle.executor, "device", None)
        if dev is not None and getattr(
            handle.executor, "backend", ""
        ) == "device":
            dev.collect_raw_emits = bool(handle.push_batch_listeners)

    def register_push_listener(self, source_name: str, cb) -> Optional[Callable]:
        """ScalablePushRegistry analog (legacy single-session attach):
        like :meth:`register_push_tap` but returns only the unsubscribe
        callable, or None when no running query writes the source (caller
        falls back to a catchup consumer)."""
        attached = self.register_push_tap(source_name, cb)
        return attached[1] if attached is not None else None

    def get_push_registry(self):
        """Engine-side push-registry seam (tentpole): lazily construct the
        shared-pipeline registry that multiplexes compatible push sessions
        as filtered taps (server/push_registry.py).  Engine-owned so
        embedded callers, the REST server, metrics and shutdown all see
        the same instance."""
        if self.push_registry is None:
            from ksql_tpu.server.push_registry import PushRegistry

            self.push_registry = PushRegistry(self)
        return self.push_registry

    # ------------------------------------------------------------- sandbox
    #: statement types that mutate engine state and therefore validate on a
    #: sandbox fork first (SandboxedExecutionContext analog — the reference
    #: executes every distributed statement against a sandbox engine before
    #: enqueueing it, ksqldb-engine KsqlEngine.createSandbox)
    _MUTATING = ()

    def create_sandbox(self) -> "KsqlEngine":
        """Fork this engine for validation: copied metastore / schema
        registry / properties, a throwaway broker, no running queries.
        Executing a statement on the sandbox performs every check and
        planning step the real execution would, with all side effects
        landing on the fork."""
        sb_broker = Broker()
        for name in self.broker.list_topics():
            # mirror topic *metadata* (partition counts feed co-partitioning
            # checks) but none of the records — sandbox produces are dropped
            sb_broker.create_topic(name, self.broker.topic(name).num_partitions)
        sb = KsqlEngine(config=self.config, broker=sb_broker, registry=self.registry)
        sb.metastore = self.metastore.copy()
        sb.schema_registry = self.schema_registry.copy()
        sb.variables = dict(self.variables)
        sb.session_properties = dict(self.session_properties)
        sb.is_sandbox = True
        # validation must not pay an XLA compile per statement; the oracle
        # performs the identical plan/schema checks.  device-only is kept:
        # its lowering failure IS a validation error.
        if str(self.effective_property(cfg.RUNTIME_BACKEND, "device")).lower() in (
            "device", "distributed"
        ):
            sb.session_properties[cfg.RUNTIME_BACKEND] = "oracle"
        return sb

    # ------------------------------------------------------------ plumbing
    def effective_property(self, name: str, default=None):
        """Config value with session-property override (SET statement /
        request-scoped overrides take precedence, KsqlConfig semantics)."""
        if name in self.session_properties:
            return self.session_properties[name]
        return self.config.get(name, default)

    def _plog_append(self, where: str, message: str) -> None:
        """Host-side processing-log append with the shared retention cap
        (ksql.processing.log.buffer.size; exceeding it trims the oldest
        half and counts the drop)."""
        self.processing_log.append((where, message))
        if len(self.processing_log) > self._plog_cap:
            drop = max(self._plog_cap // 2, 1)
            del self.processing_log[:drop]
            self.plog_dropped += drop
        if getattr(self, "telemetry_enabled", False):
            try:
                self._timeline_annotate(where, message)
            except Exception:  # noqa: BLE001 — annotations never break
                pass  # the error path that produced the log entry

    def _timeline_annotate(self, where: str, message: str) -> None:
        """Route one processing-log entry onto timeline(s) as a lifecycle
        annotation.  Query-scoped categories (``rescale.done:<qid>``) land
        on that owner's timeline; engine-wide categories (overload
        engage/clear) stamp every LIVE timeline — a store is never created
        just to hold an annotation for an owner that has no series yet,
        except when the suffix names a known query (so cause is retained
        even for a query that has not ticked since startup)."""
        from ksql_tpu.common import timeline as tlm

        cat = tlm.plog_category(where)
        if cat not in tlm.ANNOTATION_CATEGORIES:
            return
        detail = message if ":" not in where else (
            where.split(":", 1)[1] + " — " + message
        )
        if cat in tlm.ENGINE_WIDE_CATEGORIES:
            for tl in list(self.timelines.values()) or [
                self.timeline_store("_engine")
            ]:
                tl.annotate(cat, detail)
            return
        target = where.split(":", 1)[1] if ":" in where else ""
        if target in self.timelines:
            self.timelines[target].annotate(cat, detail)
        elif target in self.queries:
            self.timeline_store(target).annotate(cat, detail)
        else:
            # no owner of that name: broadcast so the incident stays
            # observable ("_engine" backstops a pre-first-tick engine)
            for tl in list(self.timelines.values()) or [
                self.timeline_store("_engine")
            ]:
                tl.annotate(cat, detail)

    def _on_error(self, where: str, e: Exception) -> None:
        self._plog_append(where, f"{type(e).__name__}: {e}")
        if not self.is_sandbox:
            try:
                self._produce_processing_log(where, e)
            except Exception:  # noqa: BLE001 — the log must never recurse
                pass

    #: KSQL_PROCESSING_LOG record types (ProcessingLogMessageSchema)
    _PLOG_DESERIALIZATION_ERROR = 0
    _PLOG_RECORD_PROCESSING_ERROR = 2
    _plog_ready = False

    def _produce_processing_log(self, where: str, e: Exception) -> None:
        """Structured, queryable processing log (ProcessingLoggerImpl.java:23
        analog): every runtime error lands on the
        <service id>ksql_processing_log topic, and the KSQL_PROCESSING_LOG
        stream over it is auto-registered (ProcessingLogServerUtils)."""
        if not cfg._bool(self.config.get(cfg.PROCESSING_LOG_TOPIC_AUTO_CREATE)):
            return
        import json as _json
        import time as _time

        service_id = str(self.config.get(cfg.SERVICE_ID, "default_"))
        topic = f"{service_id}ksql_processing_log"
        if not self._plog_ready:
            self.broker.create_topic(topic)
            if self.metastore.get_source("KSQL_PROCESSING_LOG") is None:
                from ksql_tpu.common import types as T
                from ksql_tpu.common.types import SqlType

                b = LogicalSchema.builder()
                b.value_column("LOGGER", T.STRING)
                b.value_column("LEVEL", T.STRING)
                b.value_column("TIME", T.BIGINT)
                b.value_column(
                    "MESSAGE",
                    SqlType.struct(
                        [
                            ("TYPE", T.INTEGER),
                            ("ERRORMESSAGE", T.STRING),
                            ("CONTEXT", T.STRING),
                        ]
                    ),
                )
                self.metastore.put_source(
                    DataSource(
                        name="KSQL_PROCESSING_LOG",
                        source_type=DataSourceType.STREAM,
                        schema=b.build(),
                        topic=topic,
                        value_format="JSON",
                        sql_expression="-- auto-created processing log",
                    )
                )
            self._plog_ready = True
        mtype = (
            self._PLOG_DESERIALIZATION_ERROR
            if where.startswith("deserialize")
            else self._PLOG_RECORD_PROCESSING_ERROR
        )
        self.broker.topic(topic).produce(
            Record(
                key=None,
                value=_json.dumps(
                    {
                        "LOGGER": where,
                        "LEVEL": "ERROR",
                        "TIME": int(_time.time() * 1000),
                        "MESSAGE": {
                            "TYPE": mtype,
                            "ERRORMESSAGE": f"{type(e).__name__}: {e}",
                            "CONTEXT": where,
                        },
                    },
                    separators=(",", ":"),
                ),
                timestamp=int(_time.time() * 1000),
            )
        )

    def parse(self, sql: str) -> List[ast.PreparedStatement]:
        return parse_statements(
            sql, variables=self.variables, type_registry=self.metastore.all_types()
        )

    # --------------------------------------------------------------- entry
    def execute_sql(self, sql: str) -> List[StatementResult]:
        return [self.execute_statement(p) for p in self.parse(sql)]

    def execute_statement(self, prepared: ast.PreparedStatement) -> StatementResult:
        s = prepared.statement
        handler = self._HANDLERS.get(type(s))
        if handler is None:
            raise KsqlException(f"Unsupported statement: {type(s).__name__}")
        if (
            not self.is_sandbox
            and isinstance(s, self._MUTATING)
            and not prepared.__dict__.pop("_prevalidated", False)
        ):
            # validate on a fork first: a failing statement must leave the
            # metastore / schema registry / topics untouched
            self.create_sandbox().execute_statement(prepared)
        return handler(self, s, prepared.text)

    def validate_statement(self, prepared: ast.PreparedStatement) -> None:
        """Sandbox-only validation (SandboxedExecutionContext): raises on a
        bad statement without mutating engine state — a distributing server
        calls this BEFORE appending to the shared command log so user
        errors never poison peers' tail loops.  Marks the statement so the
        immediately-following execute does not sandbox a second time."""
        s = prepared.statement
        if isinstance(s, self._MUTATING):
            self.create_sandbox().execute_statement(prepared)
            prepared.__dict__["_prevalidated"] = True

    # ----------------------------------------------------------------- DDL
    @staticmethod
    def schema_from_elements(elements) -> LogicalSchema:
        b = LogicalSchema.builder()
        for el in elements:
            if el.constraint == ast.ColumnConstraint.KEY:
                b.key_column(el.name, el.type)
            elif el.constraint == ast.ColumnConstraint.PRIMARY_KEY:
                b.key_column(el.name, el.type)
            else:
                # HEADERS columns are value columns populated from record
                # headers, not the value payload (reference Column HEADERS
                # namespace)
                b.value_column(el.name, el.type)
        return b.build()

    @staticmethod
    def header_columns_of(elements):
        """[(column_name, header_key-or-None)] for HEADERS-backed columns,
        with type validation (HeadersColumnValidation analog)."""
        from ksql_tpu.common import types as T
        from ksql_tpu.common.types import SqlBaseType, SqlType

        out = []
        for el in elements:
            if el.constraint != ast.ColumnConstraint.HEADERS:
                continue
            if el.header_key is None:
                expected = SqlType.array(
                    SqlType.struct([("KEY", T.STRING), ("VALUE", T.BYTES)])
                )
                if el.type != expected:
                    raise KsqlException(
                        f"Invalid type for HEADERS column '{el.name}': "
                        "expected ARRAY<STRUCT<`KEY` STRING, `VALUE` BYTES>>, "
                        f"got {el.type}"
                    )
            else:
                if el.type.base != SqlBaseType.BYTES:
                    raise KsqlException(
                        f"Invalid type for HEADER('{el.header_key}') column "
                        f"'{el.name}': expected BYTES, got {el.type}"
                    )
            out.append((el.name, el.header_key))
        return tuple(out)

    def _prop(self, props: Dict[str, Any], name: str, default=None):
        for k, v in props.items():
            if k.upper() == name.upper():
                return v
        return default

    def _create_source(self, s, is_table: bool, text: str) -> StatementResult:
        props = s.properties
        existing = self.metastore.get_source(s.name)
        if existing is not None:
            if s.if_not_exists:
                return StatementResult("ddl", f"Source {s.name} already exists.")
            if not s.or_replace:
                raise KsqlException(
                    f"Cannot add {'table' if is_table else 'stream'} '{s.name}': "
                    "A source with the same name already exists"
                )
        if s.or_replace and (s.is_source or (existing is not None and existing.is_source)):
            kind_l = "table" if is_table else "stream"
            raise KsqlException(
                f"Cannot add {kind_l} '{s.name}': CREATE OR REPLACE is not "
                f"supported on source {kind_l}s."
            )
        topic_name = str(self._prop(props, "KAFKA_TOPIC", s.name))
        partitions = int(self._prop(props, "PARTITIONS", 1))
        from ksql_tpu.common.config import DEFAULT_KEY_FORMAT, DEFAULT_VALUE_FORMAT

        vf = self._prop(
            props, "VALUE_FORMAT",
            self._prop(props, "FORMAT",
                       self.effective_property(DEFAULT_VALUE_FORMAT) or None),
        )
        if vf is None:
            raise KsqlException(
                "Statement is missing the 'VALUE_FORMAT' property from the WITH "
                "clause. Either provide one or set a default via the "
                "'ksql.persistence.default.format.value' config."
            )
        value_format = str(vf).upper()
        key_format = str(self._prop(
            props, "KEY_FORMAT",
            self._prop(props, "FORMAT",
                       self.effective_property(DEFAULT_KEY_FORMAT) or "KAFKA"),
        )).upper()
        from ksql_tpu.serde import formats as _fmt

        if value_format not in _fmt.supported_formats():
            raise KsqlException(f"Unknown format: {value_format}")
        if key_format not in _fmt.supported_formats():
            raise KsqlException(f"Unknown format: {key_format}")
        if key_format == "NONE" and any(
            el.constraint in (ast.ColumnConstraint.KEY, ast.ColumnConstraint.PRIMARY_KEY)
            for el in s.elements
        ):
            raise KsqlException(
                "Key format specified as NONE for a source with key columns. "
                "The NONE format can only be used when no columns are defined."
            )
        from ksql_tpu.common.schema import PSEUDOCOLUMNS, WINDOW_BOUNDS

        for el in s.elements:
            if el.name in PSEUDOCOLUMNS or el.name in WINDOW_BOUNDS:
                raise KsqlException(
                    f"'{el.name}' is a reserved column name. You cannot use it "
                    "as a name for a column."
                )
            if is_table and el.constraint == ast.ColumnConstraint.KEY:
                raise KsqlException(
                    f"Column `{el.name}` is a 'KEY' column: please use "
                    "'PRIMARY KEY' for tables."
                )
            if not is_table and el.constraint == ast.ColumnConstraint.PRIMARY_KEY:
                raise KsqlException(
                    f"Column `{el.name}` is a 'PRIMARY KEY' column: please use "
                    "'KEY' for streams."
                )
        key_sid = self._prop(props, "KEY_SCHEMA_ID")
        value_sid = self._prop(props, "VALUE_SCHEMA_ID")
        from ksql_tpu.serde.schema_registry import SR_FORMATS

        if key_sid is not None:
            if key_format not in SR_FORMATS:
                raise KsqlException(
                    "KEY_FORMAT should support schema inference when "
                    f"KEY_SCHEMA_ID is provided. Current format is {key_format}."
                )
            if any(
                el.constraint in (ast.ColumnConstraint.KEY, ast.ColumnConstraint.PRIMARY_KEY)
                for el in s.elements
            ):
                raise KsqlException(
                    "Table elements and KEY_SCHEMA_ID cannot both exist for "
                    "create statement."
                )
        if value_sid is not None:
            if value_format not in SR_FORMATS:
                raise KsqlException(
                    "VALUE_FORMAT should support schema inference when "
                    f"VALUE_SCHEMA_ID is provided. Current format is {value_format}."
                )
            if any(
                el.constraint
                not in (ast.ColumnConstraint.KEY, ast.ColumnConstraint.PRIMARY_KEY,
                        ast.ColumnConstraint.HEADERS)
                for el in s.elements
            ):
                raise KsqlException(
                    "Table elements and VALUE_SCHEMA_ID cannot both exist for "
                    "create statement."
                )
        header_cols = self.header_columns_of(s.elements)
        schema = self.schema_from_elements(s.elements)
        schema = self._infer_schema(
            schema, topic_name, key_format, value_format, s.name,
            header_cols=header_cols,
            key_schema_id=int(key_sid) if key_sid is not None else None,
            value_schema_id=int(value_sid) if value_sid is not None else None,
            key_full_name=self._prop(props, "KEY_SCHEMA_FULL_NAME"),
            value_full_name=self._prop(props, "VALUE_SCHEMA_FULL_NAME"),
            value_unwrap=_parses_unwrapped(self._prop(props, "WRAP_SINGLE_VALUE")),
        )
        if is_table and not schema.key_columns:
            raise KsqlException(
                "Tables require a PRIMARY KEY. Please define the PRIMARY KEY."
            )
        if self._prop(props, "WINDOW_TYPE") and not schema.key_columns:
            raise KsqlException("Windowed sources require a key column.")
        for c in schema.key_columns:
            if _fmt.contains_map(c.type):
                raise KsqlException(
                    "Map keys, including types that contain maps, are not "
                    "supported as they may lead to unexpected behavior due to "
                    f"inconsistent serialization. Key column name: `{c.name}`. "
                    f"Column type: {c.type}"
                )
        _fmt.check_schema_support(value_format, schema.value_columns, "value")
        _fmt.check_schema_support(key_format, schema.key_columns, "key")
        wrap_raw = self._prop(props, "WRAP_SINGLE_VALUE")
        if wrap_raw is None and len(list(schema.value_columns)) == 1:
            # config default applies only when the user explicitly set it
            wrap_raw = self.session_properties.get(
                "ksql.persistence.wrap.single.values",
                self.config.explicit("ksql.persistence.wrap.single.values"),
            )
        wrap = _validate_wrap_property(wrap_raw, value_format, schema.value_columns)
        wt = self._prop(props, "WINDOW_TYPE")
        wsize = self._prop(props, "WINDOW_SIZE")
        if wt and str(wt).upper() == "SESSION" and wsize:
            raise KsqlException(
                "'WINDOW_SIZE' should not be set for SESSION windows."
            )
        window_size_ms = None
        if wsize:
            from ksql_tpu.parser.parser import Parser

            p = Parser(str(wsize))
            window_size_ms = p.parse_duration_ms()
        ts_col = self._prop(props, "TIMESTAMP")
        ts_fmt = self._prop(props, "TIMESTAMP_FORMAT")
        for pname, fmt_of in (
            ("VALUE_AVRO_SCHEMA_FULL_NAME", value_format),
            ("KEY_AVRO_SCHEMA_FULL_NAME", key_format),
            ("VALUE_SCHEMA_FULL_NAME", value_format),
            ("KEY_SCHEMA_FULL_NAME", key_format),
        ):
            fsn = self._prop(props, pname)
            if fsn is None:
                continue
            if not str(fsn).strip():
                raise KsqlException(
                    "fullSchemaName cannot be empty. Format configuration: "
                    "{fullSchemaName=}"
                )
            if "AVRO" in pname and fmt_of not in ("AVRO",):
                raise KsqlException(
                    f"{fmt_of} does not support the following configs: [fullSchemaName]"
                )
            if "AVRO" not in pname and fmt_of not in ("AVRO", "PROTOBUF", "JSON_SR"):
                raise KsqlException(
                    f"{fmt_of} does not support the following configs: [fullSchemaName]"
                )
        self.broker.create_topic(topic_name, partitions)
        self._register_subject_schemas(topic_name, key_format, value_format, schema)
        source = DataSource(
            name=s.name,
            source_type=DataSourceType.TABLE if is_table else DataSourceType.STREAM,
            schema=schema,
            topic=topic_name,
            key_format=KeyFormat(
                format=key_format,
                window_type=str(wt).upper() if wt else None,
                window_size_ms=window_size_ms,
                wrapped=getattr(self, "_inferred_wrapped_key", False),
            ),
            value_format=value_format,
            wrap_single_values=wrap,
            value_delimiter=(
                str(self._prop(props, "VALUE_DELIMITER"))
                if self._prop(props, "VALUE_DELIMITER") is not None
                else None
            ),
            key_delimiter=(
                str(self._prop(props, "KEY_DELIMITER"))
                if self._prop(props, "KEY_DELIMITER") is not None
                else None
            ),
            timestamp_column=str(ts_col).upper() if ts_col else None,
            timestamp_format=ts_fmt,
            sql_expression=text,
            is_source=s.is_source,
            header_columns=header_cols,
            proto_nullable_rep=(
                str(self._prop(props, "VALUE_PROTOBUF_NULLABLE_REPRESENTATION")).upper()
                if self._prop(props, "VALUE_PROTOBUF_NULLABLE_REPRESENTATION")
                else None
            ),
            proto_float32=getattr(self, "_inferred_proto_float32", ()),
        )
        self.metastore.put_source(source, allow_replace=s.or_replace or existing is not None)
        kind = "Table" if is_table else "Stream"
        return StatementResult("ddl", f"{kind} created")

    def _infer_schema(
        self, schema: LogicalSchema, topic: str, key_format: str, value_format: str,
        source_name: str, header_cols=(),
        key_schema_id=None, value_schema_id=None,
        key_full_name=None, value_full_name=None,
        value_unwrap: bool = False,
    ) -> LogicalSchema:
        """Schema inference from the registry (DefaultSchemaInjector analog):
        undeclared key/value columns come from the <topic>-key / <topic>-value
        subjects when the format is SR-backed; partial schemas (key declared,
        value inferred, or vice versa) are supported."""
        from ksql_tpu.serde.schema_registry import SR_FORMATS, columns_from_schema

        self._inferred_wrapped_key = False
        self._inferred_proto_float32 = ()
        header_names = {n for n, _ in header_cols}
        payload_value_columns = [
            c for c in schema.value_columns if c.name not in header_names
        ]
        need_key = not schema.key_columns and (
            key_format.upper() in SR_FORMATS or key_schema_id is not None
        )
        need_value = not payload_value_columns and (
            value_format.upper() in SR_FORMATS or value_schema_id is not None
        )
        if not (need_key or need_value):
            if not schema.key_columns and not schema.value_columns:
                raise KsqlException(
                    f"The statement does not define any columns and {source_name} "
                    "requires schema inference, which needs a schema registry "
                    "(not configured)."
                )
            return schema
        b = LogicalSchema.builder()
        if need_key:
            reg = (
                self.schema_registry.get_by_id(key_schema_id)
                if key_schema_id is not None
                else self.schema_registry.latest(f"{topic}-key")
            )
            if reg is not None and reg.schema_type == "PROTOBUF":
                # PROTOBUF does not support UNWRAP_SINGLES: the key message's
                # fields become the key columns and stay wrapped
                for name, t in columns_from_schema(
                    reg.schema_type, reg.schema, reg.references,
                    full_name=key_full_name,
                ):
                    b.key_column(name or "ROWKEY", t)
                    if name:
                        self._inferred_wrapped_key = True
            elif reg is not None:
                # key inference always yields ONE unwrapped column: the whole
                # physical schema (record keys become ROWKEY STRUCT<...>) —
                # DefaultSchemaInjector "key schema inference always results
                # in an unwrapped key" + SerdeUtils.wrapSingle(isKey=true)
                from ksql_tpu.serde.schema_registry import sql_type_from_schema

                t = sql_type_from_schema(
                    reg.schema_type, reg.schema, reg.references,
                    full_name=key_full_name,
                )
                b.key_column("ROWKEY", t)
        else:
            for c in schema.key_columns:
                b.key_column(c.name, c.type)
        inferred_value = False
        if need_value:
            reg = (
                self.schema_registry.get_by_id(value_schema_id)
                if value_schema_id is not None
                else self.schema_registry.latest(f"{topic}-value")
            )
            if reg is not None:
                inferred_value = True
                if value_unwrap:
                    # WRAP_SINGLE_VALUE=false: the whole schema is the single
                    # anonymous ROWVAL column (SerdeUtils.wrapSingle)
                    from ksql_tpu.serde.schema_registry import (
                        sql_type_from_schema,
                    )

                    b.value_column(
                        "ROWVAL",
                        sql_type_from_schema(
                            reg.schema_type, reg.schema, reg.references,
                            full_name=value_full_name,
                        ),
                    )
                else:
                    for name, t in columns_from_schema(
                        reg.schema_type, reg.schema, reg.references,
                        full_name=value_full_name,
                    ):
                        b.value_column(name or "ROWVAL", t)
                if reg.schema_type == "PROTOBUF":
                    from ksql_tpu.serde.schema_registry import protobuf_float_fields

                    self._inferred_proto_float32 = protobuf_float_fields(
                        reg.schema, reg.references, full_name=value_full_name
                    )
                # header-backed columns are not part of the payload schema;
                # they survive inference
                for c in schema.value_columns:
                    if c.name in header_names:
                        b.value_column(c.name, c.type)
        if not inferred_value:
            for c in schema.value_columns:
                b.value_column(c.name, c.type)
        out = b.build()
        if not out.key_columns and not out.value_columns:
            raise KsqlException(
                f"The statement does not define any columns and {source_name} "
                "requires schema inference, but no schema is registered for "
                f"topic {topic}."
            )
        return out

    def _h_create_stream(self, s: ast.CreateStream, text):
        return self._create_source(s, is_table=False, text=text)

    def _h_create_table(self, s: ast.CreateTable, text):
        return self._create_source(s, is_table=True, text=text)

    # ------------------------------------------------------- CSAS/CTAS/IAS
    def _persistent_query(self, s, query: ast.Query, is_table: bool, text: str,
                          sink_name: str, properties: Dict[str, Any],
                          insert_into: bool = False) -> StatementResult:
        existing = self.metastore.get_source(sink_name)
        if existing is not None and not insert_into:
            if getattr(s, "if_not_exists", False):
                return StatementResult("ddl", f"Source {sink_name} already exists.")
            if not getattr(s, "or_replace", False):
                raise KsqlException(
                    f"Cannot add {'table' if is_table else 'stream'} '{sink_name}': "
                    "A source with the same name already exists"
                )
        prefix = "INSERTQUERY" if insert_into else ("CTAS" if is_table else "CSAS")
        query_id = f"{prefix}_{sink_name}_{next(self._query_seq)}"
        analysis = analyze_query(query, self.metastore, self.registry, sink_name)
        self._validate_join_partitions(analysis)
        # explicit values only: several keys (e.g. wrap.single.values) change
        # behavior by mere presence; planner .get() calls supply defaults
        merged_config = dict(self.config._props)
        merged_config.update(self.session_properties)
        planned = self.planner.plan(
            analysis,
            query_id,
            sink_name=sink_name,
            sink_properties=properties,
            sink_is_table=is_table,
            config=merged_config,
        )
        planned = self._apply_schema_ids(planned, properties, sink_name)
        # verify BEFORE any registration side effect (sink source, topic,
        # SR subjects): a strict-mode rejection must leave no orphaned
        # metadata behind, exactly like the planner's own validations
        self._verify_plan_static(query_id, planned.plan)
        # memory admission rides the same pre-registration seam: an
        # over-budget strict rejection must also leave nothing behind
        mem_report = self._admit_memory_static(query_id, planned.plan)
        if planned.output_source is not None:
            self._register_subject_schemas(
                planned.output_source.topic,
                planned.output_source.key_format.format,
                planned.output_source.value_format,
                planned.output_source.schema,
            )
            # sink topics inherit a source topic's partition count unless
            # PARTITIONS is given; for joins the reference takes the RIGHT
            # side's count (JoinNode.getPartitions:196 returns
            # right.getPartitions), i.e. the rightmost source of the
            # left-deep join tree
            sink_topic = planned.output_source.topic
            if not self.broker.has_topic(sink_topic):
                p = properties.get("PARTITIONS") or properties.get("partitions")
                if p is not None:
                    n = int(p)
                else:
                    src_topic = analysis.sources[-1].source.topic
                    n = (
                        len(self.broker.topic(src_topic).partitions)
                        if self.broker.has_topic(src_topic)
                        else 1
                    )
                self.broker.create_topic(sink_topic, n)
        if insert_into:
            # target must exist and schemas must be compatible (implicit
            # numeric widening allowed, reference SchemaUtil.areCompatible)
            target = self.metastore.require_source(sink_name)
            if not _schemas_compatible(planned.output_source.schema, target.schema):
                raise PlanningException(
                    f"Incompatible schema between query and {sink_name}. "
                    f"Query schema: {planned.output_source.schema}. "
                    f"Target schema: {target.schema}."
                )
            planned = dataclasses.replace(planned, output_source=target)
        else:
            self.metastore.put_source(
                dataclasses.replace(planned.output_source, is_cas_target=True),
                allow_replace=getattr(s, "or_replace", False) or existing is not None,
            )
        self._start_query(query_id, planned, text, mem_report=mem_report)
        return StatementResult("query", f"Created query {query_id}", query_id=query_id)

    def _register_subject_schemas(self, topic, key_format, value_format, schema):
        """SR-backed formats register their subjects on creation (reference
        SchemaRegistryUtil): key first, then value, in statement order."""
        from ksql_tpu.serde.schema_registry import SR_FORMATS

        sr = self.schema_registry
        if str(key_format).upper() in SR_FORMATS and schema.key_columns:
            subj = f"{topic}-key"
            if not sr.has_subject(subj):
                sr.register(
                    subj, "KSQL", [(c.name, c.type) for c in schema.key_columns]
                )
        if str(value_format).upper() in SR_FORMATS and schema.value_columns:
            subj = f"{topic}-value"
            if not sr.has_subject(subj):
                sr.register(
                    subj, "KSQL", [(c.name, c.type) for c in schema.value_columns]
                )

    def _apply_schema_ids(self, planned: PlannedQuery, properties, sink_name):
        """KEY_SCHEMA_ID / VALUE_SCHEMA_ID on a CSAS/CTAS: the registered SR
        schema becomes the physical write schema.  The query's columns must be
        an in-order prefix of it (by name and type); schema columns beyond the
        query's are appended with their write-defaults (Avro field defaults,
        proto3 zero-values, JSON-schema null) — a required Avro field with no
        default is a serialization error (reference SchemaRegistryUtil)."""
        from ksql_tpu.serde.schema_registry import (
            NO_DEFAULT,
            columns_with_defaults,
        )
        from ksql_tpu.common.schema import LogicalSchema as _LS

        key_sid = self._prop(properties, "KEY_SCHEMA_ID")
        value_sid = self._prop(properties, "VALUE_SCHEMA_ID")
        if key_sid is None and value_sid is None:
            return planned
        sink = planned.plan.physical_plan
        schema = sink.schema
        new_formats = sink.formats
        value_defaults = []
        b = _LS.builder()

        def types_match(a, b):
            if a is None or b is None:
                return a is b
            if a.base != b.base:
                return False
            from ksql_tpu.common.types import SqlBaseType as _B

            if a.base == _B.STRUCT:
                af = [(n.upper(), t) for n, t in (a.fields or ())]
                bf = [(n.upper(), t) for n, t in (b.fields or ())]
                return len(af) == len(bf) and all(
                    an == bn and types_match(at, bt)
                    for (an, at), (bn, bt) in zip(af, bf)
                )
            if a.base in (_B.ARRAY, _B.MAP):
                return types_match(a.element, b.element)
            return True  # primitive params (decimal precision etc.) are lax

        def check_prefix(query_cols, sr_cols, what):
            mism = []
            for i, c in enumerate(query_cols):
                if (
                    i >= len(sr_cols)
                    or sr_cols[i][0].upper() != c.name.upper()
                    or not types_match(sr_cols[i][1], c.type)
                ):
                    mism.append(f"`{c.name}` {c.type}")
            if mism:
                sr_desc = ", ".join(f"`{n}` {t}" for n, t, _d in sr_cols)
                raise KsqlException(
                    f"The following {what} columns are changed, missing or "
                    f"reordered: [{', '.join(mism)}]. Schema from schema "
                    f"registry is [{sr_desc}]"
                )

        if key_sid is not None:
            reg = self.schema_registry.get_by_id(int(key_sid))
            if reg is None:
                raise KsqlException(f"Schema id {key_sid} not found.")
            if reg.schema_type == "PROTOBUF":
                # PROTOBUF keys stay wrapped: message fields are key columns
                sr_cols = columns_with_defaults(
                    reg.schema_type, reg.schema, reg.references
                )
                check_prefix(list(schema.key_columns), sr_cols, "key")
                for c in schema.key_columns:
                    b.key_column(c.name, c.type)
                new_formats = dataclasses.replace(new_formats, key_wrapped=True)
            else:
                # keys are always unwrapped: the SR schema is the single key
                # column's type (SerdeUtils.wrapSingle(isKey=true)); the
                # synthesized column keeps the query's key name
                from ksql_tpu.serde.schema_registry import (
                    NO_DEFAULT as _ND,
                    sql_type_from_schema,
                )

                kt = sql_type_from_schema(
                    reg.schema_type, reg.schema, reg.references
                )
                kcols = list(schema.key_columns)
                sr_kcols = [(kcols[0].name if kcols else "ROWKEY", kt, _ND)]
                check_prefix(kcols, sr_kcols, "key")
                for c in schema.key_columns:
                    b.key_column(c.name, c.type)
                new_formats = dataclasses.replace(
                    new_formats, key_wrapped=False
                )
        else:
            for c in schema.key_columns:
                b.key_column(c.name, c.type)
        if value_sid is not None:
            reg = self.schema_registry.get_by_id(int(value_sid))
            if reg is None:
                raise KsqlException(f"Schema id {value_sid} not found.")
            sr_cols = columns_with_defaults(reg.schema_type, reg.schema, reg.references)
            qcols = list(schema.value_columns)
            check_prefix(qcols, sr_cols, "value")
            if reg.schema_type == "AVRO" and isinstance(reg.schema, dict):
                # nested non-optional fields with schema defaults: a null
                # written there takes the default (Connect AvroData rules);
                # recorded as (path-tuple, default) entries
                sr_fields = list(reg.schema.get("fields", ()))
                for i, c in enumerate(qcols):
                    if i < len(sr_fields):
                        value_defaults.extend(
                            _avro_nested_defaults((c.name,), sr_fields[i]["type"])
                        )
            for i, (n, t, d) in enumerate(sr_cols):
                if i < len(qcols):
                    b.value_column(qcols[i].name, qcols[i].type)
                    continue
                b.value_column(n, t)
                if d is NO_DEFAULT:
                    raise KsqlException(
                        f"Error serializing message to topic: {sink.topic}. "
                        f"Missing default value for required Avro field: "
                        f"[{n.lower()}]. This field appears in Avro schema "
                        "in Schema Registry"
                    )
                value_defaults.append((n, d))
        else:
            for c in schema.value_columns:
                b.value_column(c.name, c.type)
        new_schema = b.build()
        new_sink = dataclasses.replace(
            sink,
            schema=new_schema,
            formats=new_formats,
            value_defaults=tuple(value_defaults),
        )
        new_plan = dataclasses.replace(planned.plan, physical_plan=new_sink)
        out_src = planned.output_source
        if out_src is not None:
            out_src = dataclasses.replace(
                out_src,
                schema=new_schema,
                key_format=dataclasses.replace(
                    out_src.key_format, wrapped=new_formats.key_wrapped
                ),
            )
        return dataclasses.replace(planned, plan=new_plan, output_source=out_src)

    def _validate_join_partitions(self, analysis) -> None:
        """Co-partitioning requirement: joined sources' topics must have the
        same partition count (reference JoinNode.validatePartitionCounts)."""
        from ksql_tpu.analyzer.analyzer import JoinInfo, _is_fk_join

        if not isinstance(analysis.relation, JoinInfo) or len(analysis.sources) < 2:
            return
        if _is_fk_join(analysis.relation):
            return  # FK joins do not require co-partitioning (reference JoinNode)
        counts = []
        for asrc in analysis.sources:
            if not self.broker.has_topic(asrc.source.topic):
                continue  # unknown count: skip just this source
            counts.append(
                (asrc.source.name, len(self.broker.topic(asrc.source.topic).partitions))
            )
        if not counts:
            return
        first_name, first_n = counts[0]
        for name, n in counts[1:]:
            if n != first_n:
                raise PlanningException(
                    f"Can't join `{first_name}` with `{name}` since the number "
                    f"of partitions don't match. `{first_name}` partitions = "
                    f"{first_n}; `{name}` partitions = {n}. Please repartition "
                    "either one so that the number of partitions match."
                )

    def _verify_plan_static(self, query_id: str, plan) -> None:
        """Static plan verification (ksql.analysis.verify.plans, default
        on): walk the ExecutionStep DAG before any executor exists and
        check the invariants every backend assumes — schema propagation,
        key consistency across repartitions, window/serde sanity.  The
        reference validates the serialized plan the same way before
        building the Streams topology; violations here log to the
        processing log (or reject the statement under
        ksql.analysis.verify.strict)."""
        if not cfg._bool(
            self.effective_property(cfg.ANALYSIS_VERIFY_PLANS, True)
        ):
            return
        from ksql_tpu.analysis import verify_plan

        violations = verify_plan(plan)
        if not violations:
            return
        detail = "; ".join(v.format() for v in violations)
        if cfg._bool(self.effective_property(cfg.ANALYSIS_VERIFY_STRICT)):
            raise KsqlException(
                f"plan failed static verification ({len(violations)} "
                f"violation(s)): {detail}"
            )
        self._plog_append(
            f"plan.verify:{query_id}",
            f"{len(violations)} static plan violation(s): {detail}",
        )

    # ------------------------------------------- static memory model (graftmem)
    def _memory_shards(self) -> int:
        """Mesh size the memory model prices a new plan at: the configured
        ksql.device.shards under backend=distributed (0 = all visible
        devices), 1 otherwise."""
        backend = str(self.effective_property(cfg.RUNTIME_BACKEND)).lower()
        if backend != "distributed":
            return 1
        n = int(self.effective_property(cfg.DEVICE_SHARDS, 0) or 0)
        if n:
            return n
        import jax as _jax

        return max(1, len(_jax.devices()))

    def _memory_report_static(self, plan):
        """Static device-memory footprint (analysis/mem_model) of a plan
        under the engine's effective lowering parameters, or None when it
        does not lower to the device backend — oracle plans hold no
        modeled HBM."""
        from ksql_tpu.analysis import analyze_plan_memory
        from ksql_tpu.runtime.device_executor import (
            _is_suppress,
            _needs_per_record,
        )

        if str(
            self.effective_property(cfg.RUNTIME_BACKEND)
        ).lower() == "oracle":
            return None  # the row oracle allocates no device memory
        self._install_function_limits()
        sliced_opt = (
            None
            if cfg._bool(self.effective_property(cfg.SLICING_ENABLE, True))
            else False
        )
        budget = int(
            self.effective_property(cfg.MEMORY_BUDGET_BYTES, 0) or 0
        )
        # mirror the runtime's effective batch capacity exactly, as the
        # backend classifier does: per-record cadence (configured or
        # plan-forced) constructs the device at capacity 1 (suppress
        # excepted), which sizes ss buffers and the transient
        # pipeline/exchange components
        per_record = (
            cfg._bool(self.effective_property(cfg.EMIT_CHANGES_PER_RECORD))
            or cfg._bool(self.effective_property(cfg.PARITY_MODE))
            or _needs_per_record(plan)
        )
        capacity = (
            1 if (per_record and not _is_suppress(plan))
            else int(self.config.get(cfg.BATCH_CAPACITY))
        )
        try:
            return analyze_plan_memory(
                plan, self.registry,
                capacity=capacity,
                store_capacity=int(self.config.get(cfg.STATE_SLOTS)),
                n_shards=self._memory_shards(),
                sliced=sliced_opt,
                slice_ring_max=int(
                    self.effective_property(cfg.SLICING_MAX_RING, 512)
                ),
                growth_budget_bytes=budget or None,
            )
        except Exception:  # noqa: BLE001 — DeviceUnsupported and any
            # probe-construction failure alike: the plan runs off-device,
            # where this model has nothing to say
            return None

    def _admit_memory_static(self, query_id: str, plan):
        """Memory admission gate (``ksql.analysis.memory.budget.bytes``):
        price the plan's per-shard at-creation footprint with the static
        model BEFORE any registration side effect.  Over budget: log a
        ``memory.admit`` plog entry naming the dominant components, or
        reject the statement under ``ksql.analysis.memory.budget.strict``
        (same contract as plan verification's strict mode).  Returns the
        report for the handle's EXPLAIN/gauge memo."""
        from ksql_tpu.analysis.mem_model import POINT_CREATION

        report = self._memory_report_static(plan)
        budget = int(
            self.effective_property(cfg.MEMORY_BUDGET_BYTES, 0) or 0
        )
        if report is None or not budget:
            return report
        need = report.per_shard_bytes(POINT_CREATION)
        shared_note = ""
        marginal = self._mqo_admission_marginal(plan, report)
        if marginal is not None:
            # the plan will ride a shared pipeline: the gate charges the
            # attach what it actually allocates — the shared ring's
            # marginal growth at the post-gcd width — not the phantom
            # standalone store the full report prices
            need, shared_note = marginal
        if need <= budget:
            return report
        if shared_note:
            # the rejected price is the shared ring's marginal growth —
            # the standalone report's components are the pipeline this
            # query will NOT build; steer at the levers that shrink the
            # marginal attach instead
            msg = (
                f"estimated per-shard device footprint {need} bytes"
                f"{shared_note} exceeds "
                f"{cfg.MEMORY_BUDGET_BYTES}={budget} — shrink the shared "
                "slice ring (an explicit GRACE PERIOD lowers retention, "
                f"{cfg.SLICING_MAX_RING} caps it) or raise the budget"
            )
        else:
            top = sorted(
                (c for c in report.components if c.at_creation),
                key=lambda c: -c.at_creation,
            )[:3]
            doms = ", ".join(
                f"{c.name}={c.at_creation}B"
                + (f" (cap {c.capacity})" if c.capacity else "")
                for c in top
            )
            msg = (
                f"estimated per-shard device footprint {need} bytes "
                f"exceeds "
                f"{cfg.MEMORY_BUDGET_BYTES}={budget}; dominant component(s): "
                f"{doms} — lower ksql.state.slots / ksql.batch.capacity or "
                "raise the budget"
            )
        if cfg._bool(self.effective_property(cfg.MEMORY_BUDGET_STRICT)):
            raise KsqlException(
                f"statement rejected by the memory admission gate: {msg}"
            )
        self._plog_append(f"memory.admit:{query_id}", msg)
        return report

    def _mqo_admission_marginal(self, plan, report):
        """When ``plan`` would attach to a running shared window family,
        return ``(marginal_bytes, note)`` — the attach's MARGINAL
        footprint (mem_model.family_attach_marginal: the shared ring
        re-priced at the post-gcd width with the union partial set) for
        the admission gate — else None (standalone pricing applies)."""
        if not self._mqo_enabled() or not self.window_families:
            return None
        if not cfg._bool(
            self.effective_property(cfg.SLICING_SHARE_FAMILIES, True)
        ):
            # build time runs the normal ladder when family sharing is
            # off — the gate must price the standalone store the query
            # will actually allocate, not a phantom attach
            return None
        from ksql_tpu.planner import mqo
        from ksql_tpu.runtime.lowering import CompiledDeviceQuery

        try:
            sliced_opt = (
                None
                if cfg._bool(self.effective_property(cfg.SLICING_ENABLE, True))
                else False
            )
            probe = CompiledDeviceQuery(
                plan, self.registry, capacity=1, analyze_only=True,
                sliced=sliced_opt,
                slice_ring_max=int(
                    self.effective_property(cfg.SLICING_MAX_RING, 512)
                ),
            )
            prim_qid, pex = self._find_family_primary(probe)
            if prim_qid is None:
                return None
            decision = mqo.decide_family_attach(
                pex.device, probe, primary_qid=prim_qid,
                max_members=int(
                    self.effective_property(cfg.MQO_MAX_MEMBERS, 32)
                ),
                standalone_bytes=report.per_shard_bytes(),
                budget_bytes=int(
                    self.effective_property(cfg.MEMORY_BUDGET_BYTES, 0) or 0
                ),
            )
            if not decision.share:
                return None
            return decision.marginal_bytes, (
                f" (marginal: shared window-family attach to {prim_qid} "
                f"at gcd width {decision.gcd_width_ms}ms)"
            )
        except Exception as e:  # noqa: BLE001 — the admission probe must
            # never block a statement: standalone pricing applies.  But a
            # broken cost model silently un-pricing every shared attach is
            # invisible otherwise — keep the signal.
            self._on_error("mqo-admission", e)
            return None

    def _classify_plan_static(self, plan, handle: Optional[QueryHandle] = None):
        """Ahead-of-time backend placement for EXPLAIN: replay the
        _build_executor fallback ladder without building an executor
        (no broker wiring, no state allocation, no XLA compile).  Running
        queries memoize the decision on their handle — the plan is
        immutable, so the deep probe runs once per effective config."""
        from ksql_tpu.analysis import classify_plan

        import re as _re

        backend = str(self.effective_property(cfg.RUNTIME_BACKEND)).lower()
        per_record = (
            cfg._bool(self.effective_property(cfg.EMIT_CHANGES_PER_RECORD))
            or cfg._bool(self.effective_property(cfg.PARITY_MODE))
        )
        capacity = int(self.config.get(cfg.BATCH_CAPACITY))
        store_capacity = int(self.config.get(cfg.STATE_SLOTS))
        # the memo key must cover EVERY classification input, or a SET /
        # ALTER SYSTEM between EXPLAINs serves a stale decision: backend,
        # cadence, the device capacities, and the function limits the
        # deep probe bakes into collect/topk state sizes
        limits = tuple(sorted(
            (str(k), str(v))
            for k, v in {**self.config.to_dict(),
                         **self.session_properties}.items()
            if _re.fullmatch(r"ksql\.functions\.\w+\.limit", str(k))
        ))
        sliced_opt = (
            None
            if cfg._bool(self.effective_property(cfg.SLICING_ENABLE, True))
            else False
        )
        ring_max = int(self.effective_property(cfg.SLICING_MAX_RING, 512))
        key = (backend, per_record, capacity, store_capacity, limits,
               sliced_opt, ring_max)
        if handle is not None and handle.static_decision is not None:
            cached_key, decision = handle.static_decision
            if cached_key == key:
                return decision
        self._install_function_limits()
        decision = classify_plan(
            plan, self.registry, backend=backend, per_record=per_record,
            capacity=capacity,
            store_capacity=store_capacity,
            deep=True,
            sliced=sliced_opt, slice_ring_max=ring_max,
        )
        if handle is not None:
            handle.static_decision = (key, decision)
        return decision

    def _wrap_transient_plan(self, plan, query_id: str):
        """The transient device path's plan prep, shared with its static
        classifier so EXPLAIN cannot drift from what stream_query builds:
        sinkless plans get a throwaway sink as the device emission
        boundary, serde semantics are annotated, function limits
        installed."""
        pp = plan.physical_plan
        if not isinstance(pp, (st.StreamSink, st.TableSink)):
            pp = st.StreamSink(
                source=pp,
                topic=f"__transient_{query_id}",
                formats=st.FormatInfo(),
                schema=pp.schema,
            )
        tplan = dataclasses.replace(plan, physical_plan=pp)
        self.annotate_serde_semantics(tplan)
        # collect/topk device state sizes from the configured caps
        self._install_function_limits()
        return tplan

    def _classify_transient_static(self, plan):
        """Ahead-of-time placement for EXPLAIN <query>: a sinkless plan
        describes the TRANSIENT path, which wraps it in a synthetic sink,
        runs per-record, and only probes the single-device rung (never
        distributed; device-only still degrades to the oracle there) —
        classifying the raw plan would report "oracle: plan without sink"
        for a query that actually runs on device."""
        from ksql_tpu.analysis import classify_plan

        if isinstance(plan.physical_plan, (st.StreamSink, st.TableSink)):
            return self._classify_plan_static(plan)
        backend = str(self.effective_property(cfg.RUNTIME_BACKEND)).lower()
        tplan = self._wrap_transient_plan(plan, "explain")
        return classify_plan(
            tplan, self.registry,
            backend="oracle" if backend == "oracle" else "device",
            per_record=True,
            capacity=int(self.config.get(cfg.BATCH_CAPACITY)),
            store_capacity=int(self.config.get(cfg.STATE_SLOTS)),
            deep=True,
        )

    def _h_csas(self, s: ast.CreateStreamAsSelect, text):
        return self._persistent_query(s, s.query, False, text, s.name, s.properties)

    def _h_ctas(self, s: ast.CreateTableAsSelect, text):
        return self._persistent_query(s, s.query, True, text, s.name, s.properties)

    def _h_insert_into(self, s: ast.InsertInto, text):
        target = self.metastore.require_source(s.target)
        if target.is_table():
            raise KsqlException("INSERT INTO can only be used to insert into a stream.")
        if target.is_source:
            raise KsqlException(
                f"Cannot insert into read-only stream: {s.target}"
            )
        if target.header_columns:
            raise KsqlException(
                f"Cannot insert into {s.target}: inserting into a stream with "
                "HEADER columns is not supported"
            )
        props = {
            "KAFKA_TOPIC": target.topic,
            "VALUE_FORMAT": target.value_format,
            "KEY_FORMAT": target.key_format.format,
            # synthesized from the target, not user-specified: exempt from
            # the keyless-sink KEY_FORMAT validation
            "__KEY_FORMAT_IMPLICIT__": True,
        }
        return self._persistent_query(
            s, s.query, False, text, s.target, props, insert_into=True
        )

    def _build_executor(self, handle: QueryHandle, live=None):
        """Construct the query's executor over the backend seam (device
        with oracle fallback) — used at start and by self-healing restarts.

        ``live`` is the rebuild fence (a zero-arg callable) when the call
        runs on a supervised rebuild worker: a worker abandoned at the
        rebuild deadline keeps executing this function as a zombie, so
        every mutation of shared handle/engine state below (emit-fence
        swap, backend gauges, family registration, member detach) is
        guarded — the zombie builds a muted, unregistered executor its
        caller then discards."""
        from ksql_tpu.functions.udafs import _hashable

        if live is None:
            def live() -> bool:
                return True

        query_id = handle.query_id
        plan = handle.plan
        qmetrics = self.metrics.for_query(query_id)

        # one fence per executor build: revoking the PREVIOUS build's fence
        # here makes "replaced executor" imply "silenced emit path" even
        # when the replaced executor's thread is a live zombie
        fence = {"live": True}
        if live():
            if handle.emit_fence is not None:
                handle.emit_fence["live"] = False
            handle.emit_fence = fence
        else:
            # fenced-off rebuild zombie: its executor is born muted and
            # must not revoke the fence a later successful build installed
            fence["live"] = False

        def on_emit(e: SinkEmit):
            if not fence["live"]:
                return  # fenced-off zombie executor: drop the stale emit
            k = (_hashable(e.key), e.window)
            handle.materialized[k] = (e.row, e.window, e.key, e.ts)
            qmetrics.messages_out.mark(1)
            if handle.progress is not None:
                # e2e latency = produce wall-time − record timestamp; the
                # emit's ts carries the record's event time on every
                # backend (device micro-batches may approximate a batch's
                # emissions with their batched decode timestamps)
                handle.progress.record_e2e(e.ts)
                # freshness clock for the materialized shadow — the gauge
                # standby replicas (sink disabled, no e2e samples) gossip
                handle.progress.note_materialized()
            for cb in list(handle.push_listeners):
                try:
                    cb(e)
                except Exception as exc:  # noqa: BLE001 — a slow/broken
                    self._on_error("scalable-push", exc)  # subscriber must
                    # not take down the persistent query

        def on_emit_block(emits: List[SinkEmit]) -> bool:
            """on_emit for a whole emission block in one pass: the same
            writes in emit order, one mark and one clock read for the
            block.  False, with nothing done, while a push listener is
            subscribed: it sees each emit before the next is produced,
            so its caller goes through on_emit emit by emit."""
            if handle.push_listeners:
                return False
            if not fence["live"]:
                return True  # fenced-off zombie executor: drop the block
            materialized = handle.materialized
            for e in emits:
                materialized[(_hashable(e.key), e.window)] = (
                    e.row, e.window, e.key, e.ts
                )
            qmetrics.messages_out.mark(len(emits))
            if handle.progress is not None:
                handle.progress.record_emit_block([e.ts for e in emits])
            return True

        # the executor finds the block twin on the callback it was given
        on_emit.block = on_emit_block

        def on_query_error(where: str, exc: Exception) -> None:
            qmetrics.errors.mark(1)
            self._on_error(where, exc)

        def note_backend(new: str) -> None:
            """Move the query between the backend-resident gauges — restarts
            can demote distributed→device→oracle (or re-promote), and a
            query must only ever count under the backend it runs on."""
            if not live():
                return  # fenced-off rebuild: gauges track the real build
            old = handle.backend
            if old == new:
                return
            if old == "device":
                self.device_query_count -= 1
            elif old == "distributed":
                self.distributed_query_count -= 1
            if new == "device":
                self.device_query_count += 1
            elif new == "distributed":
                self.distributed_query_count += 1
            handle.backend = new

        backend = str(self.effective_property(cfg.RUNTIME_BACKEND)).lower()
        if backend not in ("device", "oracle", "device-only", "distributed"):
            raise KsqlException(f"unknown {cfg.RUNTIME_BACKEND}: {backend}")
        # collect/topk device state is sized from the configured caps at
        # construction time — make the overrides visible before lowering
        self._install_function_limits()
        per_record = (
            cfg._bool(self.effective_property(cfg.EMIT_CHANGES_PER_RECORD))
            or cfg._bool(self.effective_property(cfg.PARITY_MODE))
        )
        sliced_opt = (
            None
            if cfg._bool(self.effective_property(cfg.SLICING_ENABLE, True))
            else False
        )
        ring_max = int(self.effective_property(cfg.SLICING_MAX_RING, 512))
        # a rebuild of a CURRENT family member must first detach its spec
        # from the primary's pipeline: if the ladder below ends standalone
        # (sharing disabled, signature drift, primary paused), a stale
        # member spec would keep producing to this query's sink alongside
        # the new executor — every member row emitted twice
        if live():
            self._detach_member_of(handle.query_id)
        executor = None
        if backend != "oracle" and not per_record and live():
            # multi-query optimizer: a sliced hopping plan correlated with
            # a running sliced pipeline attaches to it instead of building
            # its own consumer + device store, and a compatible stateless
            # chain rides a shared source-prefix pipeline (per-record
            # cadence keeps a standalone executor — member emission is
            # batch-coalesced)
            executor = self._try_attach_family(
                handle, on_emit, on_query_error, sliced_opt, ring_max
            )
            if executor is None:
                executor = self._try_attach_prefix(
                    handle, on_emit, on_query_error
                )
            if executor is not None:
                note_backend("device")
        if executor is None and backend == "distributed":
            # rung 1 of the fallback ladder: the full device mesh.  A
            # DeviceUnsupported here is a DISTRIBUTION gap (EMIT FINAL,
            # n-way join chains, per-record cadence, ...) — the plan may
            # still lower single-device, so fall through to rung 2 below
            # rather than straight to the oracle.
            from ksql_tpu.compiler.jax_expr import DeviceUnsupported
            from ksql_tpu.runtime.device_executor import (
                DistributedDeviceExecutor,
            )

            try:
                executor = DistributedDeviceExecutor(
                    plan, self.broker, self.registry,
                    on_error=on_query_error, emit_callback=on_emit,
                    batch_size=int(self.config.get(cfg.BATCH_CAPACITY)),
                    per_record=per_record,
                    store_capacity=int(self.config.get(cfg.STATE_SLOTS)),
                    # the live-rescale controller overrides the configured
                    # mesh size per query; a plain restart keeps whatever
                    # size the query last ran at
                    n_shards=int(
                        handle.shard_override
                        or self.effective_property(cfg.DEVICE_SHARDS, 0)
                        or 0
                    ) or None,
                    sliced=sliced_opt, slice_ring_max=ring_max,
                )
                note_backend("distributed")
                if live() and getattr(
                    executor, "native_ingest_bypassed", False
                ):
                    # the mesh-aware lane split keeps the C++ tier engaged
                    # for every eligible plan, so this counter should stay
                    # at zero — it remains armed so any future executor
                    # regression that reintroduces the bypass is counted
                    # (and tested) instead of silently degrading
                    reason = NATIVE_INGEST_BYPASS_REASON
                    self.fallback_reasons[reason] = (
                        self.fallback_reasons.get(reason, 0) + 1
                    )
            except DeviceUnsupported as e:
                if live():  # a fenced-off rebuild's discarded build must
                    # not count (nor lose-update) the live counters
                    self.fallback_reasons[str(e)] = (
                        self.fallback_reasons.get(str(e), 0) + 1
                    )
            except Exception as e:  # noqa: BLE001 — mesh/compile failures
                self._lowering_failed(
                    "distributed-lowering", e,
                    self._classify_plan_static(plan, handle),
                    rungs=("distributed",), count=live(),
                )
        if executor is None and backend != "oracle":
            from ksql_tpu.compiler.jax_expr import DeviceUnsupported
            from ksql_tpu.runtime.device_executor import DeviceExecutor

            try:
                executor = DeviceExecutor(
                    plan, self.broker, self.registry,
                    on_error=on_query_error, emit_callback=on_emit,
                    batch_size=int(self.config.get(cfg.BATCH_CAPACITY)),
                    # batched by default; per-record changelog cadence when
                    # explicitly requested or under golden-file parity mode
                    per_record=per_record,
                    store_capacity=int(self.config.get(cfg.STATE_SLOTS)),
                    sliced=sliced_opt, slice_ring_max=ring_max,
                )
                note_backend("device")
            except DeviceUnsupported as e:
                if backend == "device-only":
                    raise KsqlException(
                        f"plan does not lower to the device backend: {e}"
                    ) from e
                if live():
                    self.fallback_reasons[str(e)] = (
                        self.fallback_reasons.get(str(e), 0) + 1
                    )
            except Exception as e:  # noqa: BLE001 — any construction failure
                if backend == "device-only":
                    raise
                self._lowering_failed(
                    "device-lowering", e,
                    self._classify_plan_static(plan, handle), count=live(),
                )
        if executor is None:
            executor = OracleExecutor(
                plan, self.broker, self.registry,
                on_error=on_query_error, emit_callback=on_emit,
            )
            note_backend("oracle")
        dev = getattr(executor, "device", None)
        if dev is not None:
            # HBM budget enforcement at _grow time (graftmem follow-up):
            # the at-growth-cap price is advisory at admission; the gate
            # here BLOCKS a store doubling that would overflow the budget,
            # logging memory.grow.refuse once per refused capacity.  Set
            # on the wrapped compiled query for the distributed runner
            # (which does not grow online, but keeps the seam uniform).
            compiled_dev = getattr(dev, "c", dev)
            compiled_dev.memory_budget_bytes = int(
                self.effective_property(cfg.MEMORY_BUDGET_BYTES, 0) or 0
            )

            def on_grow_refuse(msg, component, projected, budget,
                               _qid=query_id):
                if not fence["live"]:
                    return  # a zombie's store cannot refuse for the live one
                self._plog_append(f"memory.grow.refuse:{_qid}", msg)
                if handle.progress is not None:
                    handle.progress.note_event(
                        "memory.grow.refuse", component=component,
                        projectedBytes=int(projected),
                        budgetBytes=int(budget),
                    )

            compiled_dev.on_grow_refuse = on_grow_refuse
            # a hopping query that lowered but kept the k-fold expansion
            # path is a windowing-SHAPE fallback inside the device backend:
            # count its DeviceUnsupported-style reason so the silently
            # k-fold-expanded query is visible in /metrics
            wf = getattr(dev, "windowing_fallback", None)
            if wf and live():
                self.fallback_reasons[wf] = (
                    self.fallback_reasons.get(wf, 0) + 1
                )
            if live():
                self._register_family(handle, executor)
            dec = getattr(handle, "mqo_decision", None)
            if live() and dec is not None and dec.share:
                # admitted at its shared-attach MARGINAL price but built
                # STANDALONE after all (attach refusal, primary gone,
                # promotion): the full standalone footprint materializes
                # now — re-check the budget LOUDLY.  Never fatal: killing
                # a query at failover is worse than over-budget evidence.
                budget = int(
                    self.effective_property(cfg.MEMORY_BUDGET_BYTES, 0) or 0
                )
                mem = handle.mem_report
                if budget and mem is not None:
                    need = mem.per_shard_bytes()
                    if need > budget:
                        msg = (
                            f"standalone build of {handle.query_id} "
                            f"materializes its full footprint {need} "
                            f"bytes past {cfg.MEMORY_BUDGET_BYTES}="
                            f"{budget} (admission priced the shared-"
                            "attach marginal; the shared pipeline is "
                            "gone or refused the attach)"
                        )
                        self._plog_append(
                            f"memory.admit:{handle.query_id}", msg
                        )
                        if handle.progress is not None:
                            handle.progress.note_event(
                                "memory.admit", projectedBytes=int(need),
                                budgetBytes=budget,
                            )
        from ksql_tpu.runtime.device_executor import FamilyMemberExecutor

        if dev is not None or isinstance(executor, FamilyMemberExecutor):
            # micro-batched backends get bounded per-emit produce retries:
            # replaying a whole micro-batch over one transient sink fault
            # is the expensive alternative (a failed produce raises before
            # the record enters the log, so retrying cannot duplicate)
            executor.sink_writer.produce_retries = int(
                self.effective_property(cfg.SINK_PRODUCE_RETRIES, 2)
            )
        executor.sink_writer.enabled = not handle.standby
        if self._changelog_for(handle) is not None:
            # arm the durable-emission capture BEFORE the first tick: the
            # changelog frame journals each tick's sink records alongside
            # the state delta (runtime/changelog.py)
            executor.sink_writer.journal_buf = []
        if dev is not None and getattr(executor, "backend", "") == "device":
            # batch-level push fan-out (fused tap residuals): one call per
            # decoded emission batch, carrying the still-device-resident
            # columnar emit block when collection is armed.  Fence-guarded
            # like on_emit — a zombie's batches never reach the taps.
            def on_emit_batch(emits, _dev=dev):
                if not fence["live"] or not handle.push_batch_listeners:
                    return
                blk = getattr(_dev, "last_raw_block", None)
                if blk is not None and (
                    blk.get("n") != len(emits)
                    or blk.get("emits_id") != id(emits)
                ):
                    blk = None  # misaligned (other decode): host path
                for bcb in list(handle.push_batch_listeners):
                    try:
                        bcb(emits, blk)
                    except Exception as exc:  # noqa: BLE001 — a broken
                        self._on_error("scalable-push-batch", exc)  # tap
                        # must not take down the persistent query

            executor.batch_emit_callback = on_emit_batch
            dev.collect_raw_emits = bool(handle.push_batch_listeners)
        return executor

    def _lowering_failed(self, where: str, exc: Exception, decision,
                         rungs=("device", "distributed"),
                         count: bool = True) -> None:
        """An executor build raised something other than DeviceUnsupported.

        Where the static classifier places the plan on the rung that just
        failed (``rungs``), the plan lowers and the failure is the
        device's own — an XLA compile error, an allocation, the mesh — so
        it must not be hidden behind the next rung down: the statement
        fails (a restart lands in the ERROR/retry ladder instead).  Where
        the classifier's own probe cannot construct the lowering either
        (plan analysis raising on both sides, as old serialized plans
        naming functions the registry has dropped do), the plan never was
        device-eligible and the next rung is the documented one, logged
        and counted like a DeviceUnsupported reason."""
        if decision.backend in rungs:
            raise KsqlException(
                f"{where} failed on a plan the static classifier places on "
                f"the {decision.backend} backend: {type(exc).__name__}: {exc}"
            ) from exc
        self._on_error(where, exc)
        if count:  # a fenced-off rebuild's discarded build does not count
            reason = f"construction failed: {exc}"
            self.fallback_reasons[reason] = (
                self.fallback_reasons.get(reason, 0) + 1
            )

    def _mqo_enabled(self) -> bool:
        return cfg._bool(self.effective_property(cfg.MQO_ENABLE, True))

    def _mqo_count(self, decision) -> None:
        """Cost-model verdict counters (ksql_mqo_decisions_total{verdict};
        rejects additionally count as attach refusals so cost-model
        rejects and runtime refusals aggregate in one series)."""
        v = decision.verdict
        self.mqo_decisions[v] = self.mqo_decisions.get(v, 0) + 1
        if not decision.share:
            code = decision.reason_code
            self.family_attach_refused[code] = (
                self.family_attach_refused.get(code, 0) + 1
            )

    #: refusal codes that are RUNTIME-refusal-class (the slice store's
    #: live contents or the ring cap force a standalone build) — loud:
    #: family.reslice.refuse plog + /alerts evidence, whether the cost
    #: model pre-empted them or lowering raised FamilyAttachRefused
    _FAMILY_REFUSAL_CODES = ("reslice", "new-partials", "ring-cap")

    def _family_refusal_evidence(self, handle, prim_qid, reason_code, msg,
                                 details=None) -> None:
        """Classified attach-refusal evidence: family.reslice.refuse plog
        + /alerts evidence naming the primary and the structured details
        (old->new width, store size)."""
        self._plog_append(f"family.reslice.refuse:{handle.query_id}", msg)
        if handle.progress is not None:
            handle.progress.note_event(
                "family.reslice.refuse", reason=reason_code,
                primary=prim_qid, message=msg,
                **{k: v for k, v in (details or {}).items()},
            )

    def _note_family_refusal(self, handle, prim_qid, reason_code, msg,
                             details=None) -> None:
        """A RUNTIME attach refusal (lowering.FamilyAttachRefused): count
        it under the {reason} series the cost-model rejects share, and
        surface the classified evidence."""
        self.family_attach_refused[reason_code] = (
            self.family_attach_refused.get(reason_code, 0) + 1
        )
        self.fallback_reasons[msg] = self.fallback_reasons.get(msg, 0) + 1
        self._family_refusal_evidence(
            handle, prim_qid, reason_code, msg, details
        )

    def _find_family_primary(self, probe):
        """The running single-device sliced primary ``probe`` could attach
        to, or (None, None): registry lookup by correlated signature when
        the MQO is enabled, exact family signature otherwise (the PR-7
        posture)."""
        from ksql_tpu.runtime.device_executor import (
            DeviceExecutor,
            DistributedDeviceExecutor,
        )

        sig = (
            probe.correlated_signature() if self._mqo_enabled()
            else probe.family_signature()
        )
        if sig is None:
            return None, None
        prim_qid = self.window_families.get(sig)
        if prim_qid is None:
            return None, None
        prim = self.queries.get(prim_qid)
        if prim is None or not prim.is_running():
            return None, None
        pex = prim.executor
        if not isinstance(pex, DeviceExecutor) or isinstance(
            pex, DistributedDeviceExecutor
        ):
            return None, None  # sharing is single-device only
        if not getattr(pex.device, "sliced", False):
            return None, None
        return prim_qid, pex

    def _try_attach_family(self, handle, on_emit, on_query_error,
                           sliced_opt, ring_max):
        """Attach ``handle``'s plan to a running window-family primary when
        the correlated signature matches AND the cost model accepts;
        returns the member executor stub, or None to run the normal
        fallback ladder."""
        if not cfg._bool(
            self.effective_property(cfg.SLICING_SHARE_FAMILIES, True)
        ) or not self.window_families:
            return None
        if self.overload.defer_elective():
            # a family attach costs a compile; under CRITICAL overload the
            # standalone ladder (which reuses the admission-gated footprint)
            # is the cheaper, safer path — the query still starts
            self.fallback_reasons["overload-deferred"] = (
                self.fallback_reasons.get("overload-deferred", 0) + 1
            )
            return None
        from ksql_tpu.compiler.jax_expr import DeviceUnsupported
        from ksql_tpu.planner import mqo
        from ksql_tpu.runtime.device_executor import FamilyMemberExecutor
        from ksql_tpu.runtime.lowering import (
            CompiledDeviceQuery,
            FamilyAttachRefused,
        )

        try:
            probe = CompiledDeviceQuery(
                handle.plan, self.registry, capacity=1, analyze_only=True,
                sliced=sliced_opt, slice_ring_max=ring_max,
            )
        except Exception:  # noqa: BLE001 — not device-lowerable: ladder
            return None
        prim_qid, pex = self._find_family_primary(probe)
        if prim_qid is None or prim_qid == handle.query_id:
            return None
        if self._mqo_enabled():
            # the cost model prices the attach: marginal shared-ring bytes
            # (post-gcd width, union partial set) vs the member's
            # standalone footprint the admission gate already computed
            mem = getattr(handle, "mem_report", None)
            try:
                decision = mqo.decide_family_attach(
                    pex.device, probe,
                    primary_qid=prim_qid,
                    max_members=int(
                        self.effective_property(cfg.MQO_MAX_MEMBERS, 32)
                    ),
                    standalone_bytes=(
                        mem.per_shard_bytes() if mem is not None else None
                    ),
                    budget_bytes=int(
                        self.effective_property(cfg.MEMORY_BUDGET_BYTES, 0)
                        or 0
                    ),
                )
            except Exception as e:  # noqa: BLE001 — a cost-model failure
                self._on_error("mqo-decide", e)  # must not block the
                return None  # ladder: build standalone
            handle.mqo_decision = decision
            self._mqo_count(decision)
            if not decision.share:
                # stable reason CODE, not the human text: interpolated
                # primary qids would mint one Prometheus series per table
                # name (unbounded label cardinality)
                key = f"mqo-reject:{decision.reason_code}"
                self.fallback_reasons[key] = (
                    self.fallback_reasons.get(key, 0) + 1
                )
                if decision.reason_code in self._FAMILY_REFUSAL_CODES:
                    # the cost model pre-empted a runtime refusal: same
                    # loud, classified evidence lowering would emit
                    self._family_refusal_evidence(
                        handle, prim_qid, decision.reason_code,
                        decision.reason,
                    )
                return None
        member = FamilyMemberExecutor(
            handle.plan, self.broker, prim_qid,
            on_error=on_query_error, emit_callback=on_emit,
        )
        try:
            pex.device.attach_member(
                handle.plan, handle.query_id, member.deliver, probe=probe
            )
        except FamilyAttachRefused as e:
            # classified runtime refusal (the cost model normally pre-empts
            # these; a race with inflowing data can still land here)
            self._note_family_refusal(
                handle, prim_qid, e.reason_code, str(e), e.details
            )
            return None
        except DeviceUnsupported as e:
            self.fallback_reasons[str(e)] = (
                self.fallback_reasons.get(str(e), 0) + 1
            )
            return None
        except Exception as e:  # noqa: BLE001 — recompile failure etc.
            self._on_error("family-attach", e)
            return None
        self.family_members[handle.query_id] = prim_qid
        self._plog_append(
            f"mqo.attach:{handle.query_id}",
            f"window-family member of {prim_qid}",
        )
        return member

    def _try_attach_prefix(self, handle, on_emit, on_query_error):
        """Attach ``handle``'s stateless plan as a residual consumer of a
        running shared source-prefix pipeline (the push-registry tap seam
        lifted to persistent queries); returns the member executor stub,
        or None to run the normal fallback ladder."""
        if not self._mqo_enabled() or not cfg._bool(
            self.effective_property(cfg.MQO_SHARE_PREFIX, True)
        ) or not self.prefix_pipelines:
            return None
        if self.overload.defer_elective():
            # see _try_attach_family: elective compile deferred under
            # CRITICAL overload; the normal ladder still runs the query
            self.fallback_reasons["overload-deferred"] = (
                self.fallback_reasons.get("overload-deferred", 0) + 1
            )
            return None
        from ksql_tpu.compiler.jax_expr import DeviceUnsupported
        from ksql_tpu.planner import mqo
        from ksql_tpu.runtime.device_executor import (
            DeviceExecutor,
            DistributedDeviceExecutor,
            FamilyMemberExecutor,
        )
        from ksql_tpu.runtime.lowering import CompiledDeviceQuery

        try:
            probe = CompiledDeviceQuery(
                handle.plan, self.registry, capacity=1, analyze_only=True,
            )
            sig = probe.prefix_signature()
        except Exception:  # noqa: BLE001 — not device-lowerable: ladder
            return None
        if sig is None:
            return None
        prim_qid = self.prefix_pipelines.get(sig)
        if prim_qid is None or prim_qid == handle.query_id:
            return None
        prim = self.queries.get(prim_qid)
        if prim is None or not prim.is_running():
            return None
        pex = prim.executor
        if not isinstance(pex, DeviceExecutor) or isinstance(
            pex, DistributedDeviceExecutor
        ):
            return None  # sharing is single-device only
        mem = getattr(handle, "mem_report", None)
        try:
            decision = mqo.decide_prefix_attach(
                pex.device, probe,
                primary_qid=prim_qid,
                max_members=int(
                    self.effective_property(cfg.MQO_MAX_MEMBERS, 32)
                ),
                standalone_bytes=(
                    mem.per_shard_bytes() if mem is not None else None
                ),
            )
        except Exception as e:  # noqa: BLE001 — cost-model failure: ladder
            self._on_error("mqo-decide", e)
            return None
        handle.mqo_decision = decision
        self._mqo_count(decision)
        if not decision.share:
            self.fallback_reasons[decision.reason] = (
                self.fallback_reasons.get(decision.reason, 0) + 1
            )
            return None
        member = FamilyMemberExecutor(
            handle.plan, self.broker, prim_qid,
            on_error=on_query_error, emit_callback=on_emit,
        )
        try:
            pex.device.attach_prefix_member(
                handle.plan, handle.query_id, member.deliver, probe=probe
            )
        except DeviceUnsupported as e:
            self.fallback_reasons[str(e)] = (
                self.fallback_reasons.get(str(e), 0) + 1
            )
            return None
        except Exception as e:  # noqa: BLE001 — recompile failure etc.
            self._on_error("prefix-attach", e)
            return None
        self.family_members[handle.query_id] = prim_qid
        self._plog_append(
            f"mqo.attach:{handle.query_id}",
            f"prefix member of {prim_qid}",
        )
        return member

    def _register_family(self, handle, executor) -> None:
        """After a (re)build of a device executor: register a sliced
        single-device pipeline as its family's primary (or a shareable
        stateless pipeline as its prefix group's), and re-attach any
        members that were riding the replaced executor (restart path).

        Re-attach is pop-then-reattach under ONE engine-lock step: every
        rider leaves ``family_members`` BEFORE its attach is attempted and
        re-enters only on success, so a re-attach that raises after the
        primary swap can never orphan an entry pointing at a pipeline
        that holds no member spec (the orphan would be RUNNING but
        silent forever)."""
        from ksql_tpu.runtime.device_executor import (
            DeviceExecutor,
            DistributedDeviceExecutor,
            FamilyMemberExecutor,
        )

        if not isinstance(executor, DeviceExecutor) or isinstance(
            executor, DistributedDeviceExecutor
        ):
            return
        dev = executor.device
        sliced = bool(getattr(dev, "sliced", False))
        if sliced:
            sig = (
                dev.correlated_signature() if self._mqo_enabled()
                else dev.family_signature()
            )
            if sig is not None:
                self.window_families.setdefault(sig, handle.query_id)
        else:
            # a non-shareable rebuild still runs the rider loop below: a
            # rider that can no longer attach must promote loudly, never
            # linger in family_members pointing at a spec-less pipeline
            psig = dev.prefix_signature()
            if psig is not None and self._mqo_enabled() and cfg._bool(
                self.effective_property(cfg.MQO_SHARE_PREFIX, True)
            ):
                self.prefix_pipelines.setdefault(psig, handle.query_id)
        with self._lock:
            riders = [
                m_qid for m_qid, p_qid in self.family_members.items()
                if p_qid == handle.query_id
            ]
            for m_qid in riders:
                self.family_members.pop(m_qid, None)
        # the attach itself (re-layout + recompile, possibly a ring
        # regrow transfer) runs OUTSIDE the lock: a rider is absent from
        # family_members while its attach is in flight — the safe
        # direction (detach no-ops; nothing can observe a spec-less
        # registry entry)
        for m_qid in riders:
            mh = self.queries.get(m_qid)
            mex = getattr(mh, "executor", None)
            if mh is None or not isinstance(mex, FamilyMemberExecutor):
                continue
            try:
                if sliced:
                    dev.attach_member(mh.plan, m_qid, mex.deliver)
                else:
                    dev.attach_prefix_member(mh.plan, m_qid, mex.deliver)
                with self._lock:
                    self.family_members[m_qid] = handle.query_id
                self._plog_append(
                    f"mqo.attach:{m_qid}",
                    f"re-attached to rebuilt {handle.query_id}",
                )
            except Exception as e:  # noqa: BLE001 — member can no
                # longer share (ring constraints changed): promote it
                # through the normal restart ladder as a standalone
                # query; it already left family_members above
                self._on_error("family-reattach", e)
                mh.state = "ERROR"
                mh.retry_at_ms = 0.0

    def _detach_member_of(self, query_id: str) -> bool:
        """If ``query_id`` is a riding member (window family or source
        prefix), remove its spec from the primary's pipeline and the
        engine registry.  True if it was."""
        p_qid = self.family_members.pop(query_id, None)
        if p_qid is None:
            return False
        prim = self.queries.get(p_qid)
        dev = getattr(getattr(prim, "executor", None), "device", None)
        if dev is not None:
            for det in ("detach_member", "detach_prefix_member"):
                fn = getattr(dev, det, None)
                if fn is None:
                    continue
                try:
                    fn(query_id)
                except Exception as e:  # noqa: BLE001 — detach must never
                    self._on_error("family-detach", e)  # block the caller
        self._plog_append(
            f"mqo.evict:{query_id}", f"detached from {p_qid}"
        )
        return True

    def _release_family(self, query_id: str) -> List[str]:
        """Shared-pipeline bookkeeping for a query going away (terminate):
        detach a member from its primary, or unregister a primary (both
        registries) and return the member query ids that must be promoted
        to standalone executors."""
        if self._detach_member_of(query_id):
            return []
        promoted = []
        for sig, pq in list(self.window_families.items()):
            if pq == query_id:
                self.window_families.pop(sig, None)
        for sig, pq in list(self.prefix_pipelines.items()):
            if pq == query_id:
                self.prefix_pipelines.pop(sig, None)
        for m_qid, pq in list(self.family_members.items()):
            if pq == query_id:
                self.family_members.pop(m_qid, None)
                promoted.append(m_qid)
        return promoted

    def set_query_standby(self, query_id: str, standby: bool) -> None:
        """Demote to / promote from standby: a standby keeps materializing
        replica state but publishes nothing to its sink topic.  Promotion of
        a TABLE sink republishes the replica's current state — changes the
        dead active emitted-but-lost during the failover detection window
        surface as upserts (changelog-compaction equivalence)."""
        handle = self.queries.get(query_id)
        if handle is None or handle.standby == standby:
            return
        handle.standby = standby
        writer = getattr(handle.executor, "sink_writer", None)
        if writer is not None:
            writer.enabled = not standby
        if not standby and writer is not None and isinstance(
            handle.plan.physical_plan, st.TableSink
        ):
            from ksql_tpu.runtime.oracle import SinkEmit

            # replay with each row's original materialization timestamp —
            # downstream consumers must not observe rewritten ROWTIMEs
            # after failover (the reference's changelog keeps timestamps)
            for row, window, key, ts in list(handle.materialized.values()):
                writer.produce(SinkEmit(key, row, ts, window))

    @staticmethod
    def _now_ms() -> int:
        import time as _t

        return int(_t.time() * 1000)

    def _start_query(self, query_id: str, planned: PlannedQuery, sql: str,
                     mem_report=None) -> QueryHandle:
        source_topics = sorted(
            {step.topic for step in st.walk_steps(planned.plan.physical_plan)
             if isinstance(step, (st.StreamSource, st.WindowedStreamSource,
                                  st.TableSource, st.WindowedTableSource))}
        )
        for t in source_topics:
            self.broker.create_topic(t)
        self.annotate_serde_semantics(planned.plan)
        handle = QueryHandle(
            query_id=query_id,
            plan=planned.plan,
            sink_name=planned.plan.sink_name,
            executor=None,  # set below (needs materialization hook)
            consumer=Consumer(self.broker, source_topics),
            sql=sql,
            progress=qhealth.QueryProgress(
                query_id,
                history_size=int(
                    self.effective_property(cfg.HEALTH_HISTORY_SIZE, 256)
                ),
                stall_ticks=int(
                    self.effective_property(cfg.HEALTH_STALL_TICKS, 8)
                ),
            ),
        )

        handle.mem_report = mem_report
        try:
            handle.priority = int(
                self.effective_property(cfg.QUERY_PRIORITY, 100)
            )
        except (TypeError, ValueError):
            handle.priority = 100
        handle.executor = self._build_executor(handle)
        with self._lock:
            self.queries[query_id] = handle
        self.metastore.add_source_references(
            query_id,
            reads=list(planned.plan.source_names),
            writes=[planned.plan.sink_name] if planned.plan.sink_name else [],
        )
        return handle

    # ----------------------------------------------------------- checkpoint
    _last_checkpoint_ms = 0.0

    def checkpoint(self) -> Optional[str]:
        """Snapshot broker + query state to STATE_CHECKPOINT_DIR (the
        changelog-flush analog; see runtime/checkpoint.py)."""
        directory = self.effective_property(cfg.STATE_CHECKPOINT_DIR)
        if not directory:
            return None
        from ksql_tpu.runtime.checkpoint import save_checkpoint

        import time as _time

        path = save_checkpoint(self, str(directory))
        self._last_checkpoint_ms = _time.time() * 1000
        return path

    def restore_checkpoint(self) -> bool:
        """Load state saved by checkpoint() — call after WAL replay has
        re-created the queries (StoreChangelogReader restore analog)."""
        directory = self.effective_property(cfg.STATE_CHECKPOINT_DIR)
        if not directory:
            return False
        from ksql_tpu.runtime.checkpoint import restore_checkpoint

        ok = restore_checkpoint(self, str(directory))
        if ok:
            # a full restore moved state + offsets to the snapshot: any
            # in-memory epochs predate/postdate it inconsistently
            for h in self.queries.values():
                h.epoch = None
        return ok

    def _maybe_checkpoint(self) -> None:
        directory = self.effective_property(cfg.STATE_CHECKPOINT_DIR)
        if not directory:
            return
        import time as _time

        now = _time.time() * 1000
        interval = int(self.effective_property(cfg.CHECKPOINT_INTERVAL_MS, 30000))
        forced = getattr(self, "_changelog_force_ckpt", False)
        if forced or now - self._last_checkpoint_ms >= interval:
            # a still-overweight journal re-raises the flag on its next
            # append, so a failed forced save retries without spinning
            self._changelog_force_ckpt = False
            # checkpoints are engine-level (all queries snapshot together):
            # their stage lands on the __engine__ flight recorder
            rec = (
                self.trace_recorder(tracing.ENGINE_RECORDER)
                if self.trace_enabled else None
            )
            try:
                with tracing.tick(rec):
                    with tracing.span("checkpoint"):
                        self.checkpoint()
            except Exception as e:  # noqa: BLE001 — snapshot failure must
                self._on_error("checkpoint", e)  # not kill the poll loop

    # ------------------------------------------ incremental changelog
    def _changelog_for(self, handle: QueryHandle):
        """The query's journal (created lazily), or None when journaling
        is off: no checkpoint dir, or ksql.changelog.enable=false."""
        directory = self.effective_property(cfg.STATE_CHECKPOINT_DIR)
        if not directory:
            return None
        if not cfg._bool(self.effective_property(cfg.CHANGELOG_ENABLE, True)):
            return None
        cl = self._changelogs.get(handle.query_id)
        if cl is None:
            from ksql_tpu.runtime.changelog import QueryChangelog

            os.makedirs(str(directory), exist_ok=True)
            cl = QueryChangelog(
                str(directory), handle.query_id,
                fsync=cfg._bool(
                    self.effective_property(cfg.CHANGELOG_FSYNC, True)
                ),
            )
            self._changelogs[handle.query_id] = cl
        return cl

    def _changelog_append(self, handle: QueryHandle, executor,
                          consumer) -> None:
        """Tick commit point: journal the dirty-state delta + the tick's
        durable sink emissions (runtime/changelog.py).  Never raises —
        a journal failure degrades the query to the plain checkpoint
        posture, it must not kill the poll loop."""
        try:
            wtr = getattr(executor, "sink_writer", None)
            sink_records: list = []
            if wtr is not None and wtr.journal_buf:
                # drain even when the frame is skipped below, so the
                # capture buffer never grows across ticks
                sink_records = list(wtr.journal_buf)
                del wtr.journal_buf[:]
            cl = self._changelog_for(handle)
            if cl is None or cl.ckpt_id is None:
                # no generation to chain to yet: the query journals from
                # its first checkpoint rotation onward
                return
            from ksql_tpu.runtime import changelog as clog

            snap = clog.capture_query_state(
                handle, executor, consumer.positions
            )
            if snap is None:
                if handle.query_id not in self._changelog_skip_noted:
                    self._changelog_skip_noted.add(handle.query_id)
                    self._plog_append(
                        f"changelog.skip:{handle.query_id}",
                        "executor exposes no dirty-set seam; query keeps "
                        "the full-checkpoint recovery posture",
                    )
                return
            size = cl.append(snap, sink_records)
            try:
                max_bytes = int(self.effective_property(
                    cfg.CHANGELOG_MAX_BYTES, 16 * 2 ** 20
                ))
            except (TypeError, ValueError):
                max_bytes = 16 * 2 ** 20
            if max_bytes > 0 and size > max_bytes:
                # journal over its size cap: force an early checkpoint at
                # the next poll-loop gate (rotation truncates the file)
                self._changelog_force_ckpt = True
        except Exception as e:  # noqa: BLE001 — journaling is best-effort
            self._on_error(f"changelog.append:{handle.query_id}", e)

    def _changelog_rotate(self, ckpt_id: str,
                          queries: Dict[str, Any]) -> None:
        """save_checkpoint hook: the fresh snapshot covers every journal
        frame, so each query's journal truncates and re-chains to the new
        generation (its diff base becomes the just-saved snapshot)."""
        import time as _time

        self._ckpt_id = ckpt_id
        now = _time.time()
        for qid, snap in queries.items():
            self._checkpoint_saved_at[qid] = now
            handle = self.queries.get(qid)
            if handle is None:
                continue
            try:
                cl = self._changelog_for(handle)
                if cl is not None:
                    cl.arm(ckpt_id, snap, reset=True)
            except Exception as e:  # noqa: BLE001 — cleanup, not correctness:
                # stale frames chain to the OLD id and restore skips them
                self._on_error(f"changelog.append:{qid}", e)

    def _changelog_note_restore(self, handle: QueryHandle, info: Dict[str,
                                Any], ckpt_id: Optional[str], *,
                                startup: bool = True) -> None:
        """Restore-path hook (runtime/checkpoint.py): account the replay
        window, surface the tail replay on the timeline, and re-arm the
        journal to append after its intact prefix."""
        qid = handle.query_id
        try:
            from ksql_tpu.runtime import changelog as clog

            window = clog.replay_window(handle)
            handle.recovery_replayed_rows = (
                getattr(handle, "recovery_replayed_rows", 0) + window
            )
            if info.get("applied"):
                self._plog_append(
                    f"changelog.replay:{qid}",
                    f"replayed {info['applied']}/{info['total']} journal "
                    f"frames onto checkpoint generation {ckpt_id}; "
                    f"replay window {window} rows",
                )
                prog = getattr(handle, "progress", None)
                if prog is not None:
                    prog.note_event(
                        "changelog.replay",
                        frames=info["applied"], window=window,
                    )
            self._ckpt_id = ckpt_id
            cl = self._changelog_for(handle)
            if cl is not None:
                # a tail that failed to apply re-bases the journal: the
                # next frame is a FULL snapshot (shadow None), so later
                # recoveries never patch sparse deltas over skipped state
                shadow = None if info.get("fence") else info.get("qd")
                cl.arm(
                    ckpt_id, shadow, reset=False,
                    seq=int(info.get("last_seq") or 0),
                    good_size=int(info.get("good_size") or 0),
                )
        except Exception as e:  # noqa: BLE001 — accounting must not block
            self._on_error(f"changelog.replay:{qid}", e)

    def _install_function_limits(self) -> None:
        """ksql.functions.<name>.limit overrides (CollectListUdaf et al read
        their cap from config); scoped to this engine's processing tick."""
        import re as _re

        from ksql_tpu.functions import udafs as _udafs

        limits = {}
        merged = {**self.config.to_dict(), **self.session_properties}
        for k, v in merged.items():
            m = _re.fullmatch(r"ksql\.functions\.(\w+)\.limit", str(k))
            if m:
                limits[m.group(1).lower()] = v
        _udafs._LIMIT_OVERRIDES = limits

    # --------------------------------------------------------- run the loop
    def poll_once(self, max_records: int = 4096) -> int:
        """Drain available records through all running queries (synchronous
        scheduler tick).  Returns number of records processed.

        Delivery semantics: at-least-once.  Consumer offsets are
        snapshotted before each tick; when the query crashes mid-batch the
        offsets REWIND to the snapshot, so the self-healed restart replays
        the whole batch instead of silently dropping the unprocessed tail
        (the pre-fix behavior was at-most-once: poll had already advanced).
        Replay can duplicate sink records for the batch prefix — the same
        window Kafka Streams' at_least_once guarantee has.

        Poison records: a record whose processing raises a deterministic
        USER-classified error (bad cast, serde corruption, arithmetic) is
        skipped and logged to the processing log (the LogAndContinue
        analog) — replaying it forever would crash-loop the query without
        ever making progress."""
        self._install_function_limits()
        # overload sampling piggybacks on the poll loop (interval-gated,
        # never raises) so embedded engines get pressure monitoring
        # without a thread; under source pacing each query's tick is
        # clamped by priority below
        self.overload.maybe_sample()
        n = 0
        for handle in list(self.queries.values()):
            if handle.state == "ERROR":
                self._maybe_restart(handle)
            if handle.is_running():
                n += self._poll_query_supervised(
                    handle, self.overload.poll_rows(handle, max_records)
                )
            # health watchdog, piggybacked on the poll loop (no extra
            # thread in embedded mode): EVERY tick samples progress — the
            # failed/ERROR ticks included, because a crash-looping query
            # has frozen offsets under a growing topic, which is exactly
            # the stall signature the watchdog exists to catch
            self._health_sample(handle)
            # telemetry timeline gauge sample (interval-gated, never
            # raises): per-shard deltas, watermark lag, e2e-histogram
            # deltas, and any pending skew verdicts
            self._timeline_sample(handle)
            # elastic mesh: the rescale controller rides the same verdicts
            # (sustained LAGGING -> grow, sustained IDLE -> shrink);
            # default off, distributed queries only
            self._maybe_rescale(handle)
            # mesh fault domain: a degraded mesh probes back toward its
            # original width once the fault has stayed clear
            self._maybe_mesh_regrow(handle)
        if n:
            self._maybe_checkpoint()
        return n

    def _poll_query_supervised(self, handle: QueryHandle,
                               max_records: int) -> int:
        """Run the query's tick body, under a deadline-supervised worker
        when ``ksql.query.tick.timeout.ms`` is set.  A tick that blows the
        deadline is abandoned (the worker keeps running but is fenced off:
        forked consumer, muted sink), the query is marked STALLED with
        ``tick.deadline`` evidence, and the restart ladder takes over —
        sibling queries keep polling instead of stalling behind the hang."""
        timeout_ms = float(
            self.effective_property(cfg.QUERY_TICK_TIMEOUT_MS, 0) or 0
        )
        if timeout_ms <= 0:
            return self._poll_query(handle, max_records)
        try:
            if handle.consumer.at_end():
                # idle tick: nothing to poll, nothing buffered across ticks
                # (drain runs every tick) — skip the worker entirely rather
                # than churn a thread per query per empty tick
                return 0
        except Exception:  # noqa: BLE001 — topic gone mid-flight: let the
            pass  # supervised tick surface the real error
        result: Dict[str, Any] = {}

        def body():
            try:
                result["n"] = self._poll_query(handle, max_records)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                result["err"] = e

        # persistent per-query worker (amortizes the per-tick thread
        # spawn); done.wait is the join-equivalent — a blown deadline
        # abandons the worker, which exits after its hung tick, and the
        # next tick gets a fresh one
        worker = self._tick_workers.get(handle.query_id)
        if worker is None or not worker.alive():
            worker = _TickSupervisionWorker(handle.query_id)
            self._tick_workers[handle.query_id] = worker
        done = worker.submit(body)
        if not done.wait(timeout_ms / 1000.0):
            worker.abandon()
            self._tick_workers.pop(handle.query_id, None)
            # prune zombies that already exited before remembering this
            # one: the list must stay bounded by LIVE zombies, not by
            # deadline incidents over the engine's lifetime
            self._abandoned_workers = [
                w for w in self._abandoned_workers if w.thread.is_alive()
            ]
            self._abandoned_workers.append(worker)
            self._tick_deadline_exceeded(handle, timeout_ms)
            return 0
        err = result.get("err")
        if err is not None:
            raise err
        return int(result.get("n", 0))

    def _stop_tick_worker(self, query_id: str) -> None:
        """TERMINATE/DROP path: shut down and join the query's persistent
        supervision worker (no-op when supervision never armed)."""
        w = self._tick_workers.pop(query_id, None)
        if w is not None:
            w.stop()

    def shutdown(self, join_timeout_s: float = 15.0) -> None:
        """Stop and join THIS engine's supervision workers (embedded-mode
        teardown).  A daemon worker killed by interpreter exit while it is
        inside an XLA dispatch aborts the whole process ('terminate called
        without an active exception'), so hosts that armed
        ``ksql.query.tick.timeout.ms`` should call this before exiting;
        abandoned zombies still wedged in a hung tick get a bounded join."""
        import time as _time

        # stop the overload monitor thread (server mode) before the
        # queries it samples go away
        self.overload.stop()
        if self._gc_hook_release is not None:
            self._gc_hook_release()  # at most once: a finalizer
        if self.push_registry is not None:
            # shared push pipelines hold broker consumers and (listener
            # mode) handle callbacks: tear them down before the queries go
            self.push_registry.stop_all()
        for qid in list(self._tick_workers):
            self._stop_tick_worker(qid)
        deadline = _time.time() + join_timeout_s
        for w in self._abandoned_workers:
            w.thread.join(max(0.0, deadline - _time.time()))
        self._abandoned_workers = [
            w for w in self._abandoned_workers if w.thread.is_alive()
        ]

    def _tick_deadline_exceeded(self, handle: QueryHandle,
                                timeout_ms: float) -> None:
        """The supervised tick hung: fence off the abandoned worker and
        recover.  The zombie keeps references to the old consumer (forked
        away here), the old executor (sink muted here, replaced by the
        restart), and the tick-local commit dict (reallocated next tick) —
        its late writes land on orphans, and the guarded mutation points in
        ``_poll_query`` no-op once ``handle.consumer`` changed.  Sink rows
        the worker produced before hanging stay durable; the restart
        replays from the commit point, so the duplicate window is the
        usual at-least-once one."""
        handle.tick_deadlines += 1
        old = handle.consumer
        commit = dict(handle.commit_positions or old.positions)
        handle.replayed_records += sum(
            max(pos - commit.get(k, pos), 0)
            for k, pos in old.positions.items()
        )
        handle.consumer = old.fork(commit)
        writer = getattr(handle.executor, "sink_writer", None)
        if writer is not None:
            writer.enabled = False  # a woken zombie must not publish
        if getattr(handle.executor, "emit_callback", None) is not None:
            # ...nor write stale rows into the shared materialization
            # shadow / push listeners through the orphan's emit callback
            handle.executor.emit_callback = None
        if handle.emit_fence is not None:
            # the zombie may already hold the callback reference (read
            # before the null above landed); the fence kills the callback
            # body itself, so even an in-flight dispatch loop cannot write
            # stale handle.materialized entries
            handle.emit_fence["live"] = False
        if handle.progress is not None:
            handle.progress.note_tick_deadline(int(timeout_ms))
        self._plog_append(
            f"tick.deadline:{handle.query_id}",
            f"tick exceeded {cfg.QUERY_TICK_TIMEOUT_MS}={int(timeout_ms)}ms;"
            " worker abandoned, query scheduled for restart",
        )
        exc = KsqlException(
            f"tick deadline exceeded ({cfg.QUERY_TICK_TIMEOUT_MS}="
            f"{int(timeout_ms)}ms): worker abandoned, replaying from the "
            "last commit point after restart"
        )
        # mesh fault domain: a distributed dispatch wedged inside ONE
        # shard's lane (hang at mesh.shard.dispatch) leaves the runner's
        # suspect-shard marker set — stamp the deadline error with it so
        # the strike bookkeeping can contain the failure to that shard
        sus = getattr(handle.executor, "suspect_shard", None)
        if callable(sus):
            try:
                shard = sus()
            except Exception:  # noqa: BLE001 — attribution is best-effort
                shard = None
            if shard is not None:
                exc.mesh_shard = int(shard)
                exc.mesh_deadline = True
        self._query_failed(handle, exc)

    def _poll_query(self, handle: QueryHandle, max_records: int) -> int:
        """One query's poll tick (the poll/process/drain body of
        ``poll_once``); returns records processed.

        Processing epochs (``ksql.commit.per.record``, default on): the
        tick is a sequence of durable sub-commits, not an all-or-nothing
        batch.  A ``commit`` cursor trails the records whose sink emissions
        are durable (on micro-batched executors, whatever
        ``pending_records()`` has not flushed stays uncommitted); a crash
        rewinds to the commit point, replaying only the non-durable tail.
        On the record-synchronous oracle backend a per-record state epoch
        rides along, so the restart restores state matching the commit
        point and a poison record rolls stores back to its pre-record
        epoch before being skipped (atomic poison skip).  Micro-batched
        backends cannot roll a store back one record, so an attributable
        poison record is instead dropped on replay
        (``handle.poison_skip`` — replay-without-record)."""
        import time as _time

        n = 0
        # poison bisection (non-attributable poison in a batched flush): a
        # previous crash halved the window this tick may poll, so the
        # deterministic crash point converges on ONE record — which is
        # attributable and skipped atomically
        if handle.poison_bisect is not None:
            max_records = max(
                1, min(max_records,
                       int(handle.poison_bisect.get("limit", max_records)))
            )
        # identity-bind consumer/executor: if the deadline watchdog abandons
        # this tick, the handle gets a forked consumer and every handle
        # mutation below must be suppressed (zombie-worker fence)
        consumer = handle.consumer
        executor = handle.executor
        offsets_before = dict(consumer.positions)
        per_record = cfg._bool(
            self.effective_property(cfg.COMMIT_PER_RECORD, True)
        )
        commit = dict(offsets_before)
        # tick-START binding, before this worker can possibly be abandoned
        # (the supervisor only fences after the deadline elapses) — the one
        # handle write that must run unfenced
        handle.commit_positions = commit  # graftlint: disable=unfenced-handle-mutation
        pending_fn = getattr(executor, "pending_records", None)
        stateful = bool(getattr(executor, "stateful", False))
        epoch_capable = (
            per_record and stateful and hasattr(executor, "state_epoch")
        )
        # consumed[i] is the handed index of records[i], the i-th record of
        # the tick's poll — None for a record SKIPPED without entering the
        # executor (replay-without-record), which is durable immediately;
        # a handed record is durable once the executor has flushed it
        # (its handed_idx < handed - pending()).  The commit cursor only
        # advances over a contiguous durable prefix, so a skip sitting
        # between still-buffered records can never commit them early.
        consumed: List[Optional[int]] = []
        committed_idx = 0
        handed = 0
        # per-record state epochs degrade gracefully on big state: once one
        # snapshot blows the budget, epochs (and with them the commit
        # cursor of epoch-capable queries) go per-TICK instead of
        # per-record — correctness keeps, the replay window widens
        epoch_budget_ms = float(
            self.effective_property(cfg.EPOCH_SNAPSHOT_BUDGET_MS, 2.0)
        )
        epoch_ok = True
        last_epoch_handed = -1

        def alive() -> bool:
            return handle.consumer is consumer

        def pending() -> int:
            return pending_fn() if pending_fn is not None else 0

        def advance_commit() -> None:
            nonlocal committed_idx
            durable_handed = handed - pending()
            while committed_idx < len(consumed):
                hidx = consumed[committed_idx]
                if hidx is not None and hidx >= durable_handed:
                    break
                tn_, r_ = records[committed_idx]
                commit[(tn_, r_.partition)] = r_.offset + 1
                committed_idx += 1

        def take_epoch_budgeted() -> None:
            nonlocal epoch_ok, last_epoch_handed
            t0 = _time.perf_counter()
            self._take_epoch(handle, executor, alive, commit)
            last_epoch_handed = handed
            if (_time.perf_counter() - t0) * 1000.0 > epoch_budget_ms:
                epoch_ok = False

        def note_durable() -> None:
            """Advance the commit cursor past newly-durable records, taking
            a matching state epoch when the query needs one (per record
            while snapshots stay in budget; the end-of-tick pass amortizes
            otherwise)."""
            if not per_record:
                return
            if epoch_capable and not epoch_ok:
                return  # commit holds at the last epoch point mid-tick
            before = committed_idx
            advance_commit()
            if epoch_capable and committed_idx > before:
                take_epoch_budgeted()

        def replay_window() -> int:
            """Records a rewind-to-commit would replay (polled offsets
            beyond the commit cursor) — the poison-bisection window."""
            return sum(
                max(pos - commit.get(k, pos), 0)
                for k, pos in consumer.positions.items()
            )

        def rewind_to_commit() -> None:
            replay = replay_window()
            consumer.positions.update(commit)
            if alive():
                handle.replayed_records += replay

        # flight recorder: one tick trace per query per poll (empty
        # ticks are discarded so the ring holds real work); tick(None)
        # when tracing is disabled — the instrumented seams then reduce
        # to a single thread-local None check
        rec = (
            self.trace_recorder(handle.query_id)
            if self.trace_enabled else None
        )
        with tracing.tick(rec) as tick:
            try:
                with tracing.span("poll"):
                    records = consumer.poll(max_records)
            except Exception as e:  # noqa: BLE001 — a torn read advanced
                # some positions already: rewind so nothing is dropped
                rewind_to_commit()
                if alive():
                    self._query_failed(handle, e)
                return 0
            if tick is not None:
                tick.keep = bool(records)
                # rows accounting for the telemetry timeline fold (the
                # trace itself is the transport; no extra plumbing)
                tick.counter("poll", rows=len(records))
            if records and handle.progress is not None:
                # event-time watermark: max record timestamp consumed
                handle.progress.note_watermark(
                    max(r.timestamp for _, r in records)
                )
            if epoch_capable and records:
                # the epoch matching the tick-start commit point (and the
                # pre-record store snapshot the first record's poison
                # rollback needs)
                take_epoch_budgeted()
            tick0 = _time.monotonic()
            # the hand-over, at two granularities: a run of one topic's
            # records that the executor will only buffer goes over as a
            # block (``buffer_block`` says how many of them it took), the
            # rest record by record.  A taken record needs none of the
            # per-record bookkeeping: it cannot fail, and the commit cursor
            # cannot pass it before a later step flushes it.  Poison replay,
            # bisection and per-record epochs want every record looked at.
            block_fn = None
            if not (handle.poison_skip or epoch_capable
                    or handle.poison_bisect is not None):
                block_fn = getattr(executor, "buffer_block", None)
            block_rows = 0
            pos = run_end = 0
            offer = False
            with tracing.span("process"):
                try:
                    while pos < len(records):
                        topic, rec_ = records[pos]
                        if block_fn is not None and pos >= run_end:
                            # a new run of one topic's records
                            run_end = pos + 1
                            while (run_end < len(records)
                                   and records[run_end][0] == topic):
                                run_end += 1
                            offer = True
                        if offer:
                            try:
                                took = block_fn(
                                    topic,
                                    [r for _, r in records[pos:run_end]],
                                )
                            except Exception as e:  # noqa: BLE001 — no one
                                # record is attributable: as a failed drain
                                if self._is_poison(e) and alive():
                                    self._note_poison_bisect(
                                        handle, replay_window()
                                    )
                                rewind_to_commit()
                                if alive():
                                    self._query_failed(handle, e)
                                return n
                            if not took:
                                # the executor is unsure: the loop as it
                                # is for the rest of the run
                                offer = False
                            else:
                                consumed.extend(range(handed, handed + took))
                                handed += took
                                n += took
                                block_rows += took
                                pos += took
                                # once a block: a record dropped at decode
                                # leaves nothing pending behind it
                                note_durable()
                                if pos >= run_end:
                                    continue
                                # the record the block stopped at goes
                                # through the loop; the next offer follows
                                topic, rec_ = records[pos]
                        pos += 1
                        rkey = (topic, rec_.partition, rec_.offset)
                        if rkey in handle.poison_skip:
                            # replay-without-record: this record poisoned a
                            # previous attempt on a micro-batched backend; the
                            # replay drops it so state never re-absorbs it
                            if alive():
                                handle.poison_skip.discard(rkey)
                            self._on_error(
                                f"poison:{handle.query_id}:{topic}",
                                KsqlException(
                                    "replay-without-record: skipping poison "
                                    f"record {topic}-{rec_.partition}"
                                    f"@{rec_.offset}"
                                ),
                            )
                            consumed.append(None)
                            n += 1
                            note_durable()
                            continue
                        # computed regardless of the commit knob: poison
                        # attribution (below) must not blame the flush-trigger
                        # record for a batched flush error when earlier records
                        # are still buffered
                        pending_before = pending()
                        try:
                            executor.process(topic, rec_)
                        except Exception as e:  # noqa: BLE001
                            if self._is_poison(e):
                                is_oracle = handle.backend == "oracle"
                                record_sync = is_oracle or bool(
                                    getattr(executor, "record_synchronous",
                                            False)
                                )
                                # atomic rollback needs an epoch matching the
                                # EXACT pre-record state (taken after the last
                                # handed record); a stale epoch must not
                                # un-absorb earlier records' state
                                rolled = (
                                    stateful and epoch_capable
                                    and handed == last_epoch_handed
                                    and self._rollback_epoch(
                                        handle, executor, alive
                                    )
                                )
                                if record_sync and (not stateful or rolled):
                                    # atomic in-place skip: stores rolled back
                                    # to the pre-record epoch (stateless paths
                                    # have nothing to diverge)
                                    self._on_error(
                                        f"poison:{handle.query_id}:{topic}", e
                                    )
                                    self.metrics.for_query(
                                        handle.query_id
                                    ).errors.mark(1)
                                    handed += 1
                                    consumed.append(handed - 1)
                                    n += 1  # offset advanced: skipping IS
                                    note_durable()  # progress
                                    continue
                                if is_oracle and not epoch_capable:
                                    # legacy PR-1 posture (commit-per-record
                                    # off): skip in place, absorbed state
                                    # stands — the documented one-record
                                    # divergence, preferred over crash-looping
                                    self._on_error(
                                        f"poison:{handle.query_id}:{topic}", e
                                    )
                                    self.metrics.for_query(
                                        handle.query_id
                                    ).errors.mark(1)
                                    handed += 1
                                    consumed.append(handed - 1)
                                    n += 1
                                    continue
                                if (record_sync or pending_before == 0) \
                                        and alive():
                                    # attributable to exactly this record, but
                                    # its state absorption cannot roll back:
                                    # restart and replay WITHOUT the record
                                    handle.poison_skip.add(rkey)
                                    self._on_error(
                                        f"poison:{handle.query_id}:{topic}",
                                        KsqlException(
                                            "poison record will be dropped on "
                                            f"replay: {type(e).__name__}: {e}"
                                        ),
                                    )
                                elif alive():
                                    # NON-attributable: earlier records are
                                    # still buffered in the batched flush, any
                                    # of them may be the poison — halve the
                                    # replay window for the next attempt
                                    self._note_poison_bisect(
                                        handle, replay_window()
                                    )
                            rewind_to_commit()
                            if alive():
                                self._query_failed(handle, e)
                            return n
                        handed += 1
                        consumed.append(handed - 1)
                        n += 1
                        note_durable()
                finally:
                    tracing.counter(
                        "process", rows=handed, block_rows=block_rows
                    )
            try:
                drain = getattr(executor, "drain", None)
                if drain is not None:
                    # flush the device executor's partial micro-batch
                    with tracing.span("drain"):
                        drain()
            except Exception as e:  # noqa: BLE001 — a crashing query must
                # not take down the engine; rewind so the restart replays
                if self._is_poison(e) and alive():
                    # a deterministic USER error inside the batched device
                    # flush: no single record is attributable — unless
                    # bisection already narrowed the window to one
                    if (len(consumed) - committed_idx == 1
                            and replay_window() == 1):
                        tn_, r_ = records[committed_idx]
                        rk = (tn_, r_.partition, r_.offset)
                        handle.poison_skip.add(rk)
                        handle.poison_bisect = None
                        self._on_error(
                            f"poison:{handle.query_id}:{rk[0]}",
                            KsqlException(
                                "poison record isolated by replay-window "
                                "bisection; dropped on replay: "
                                f"{type(e).__name__}: {e}"
                            ),
                        )
                    else:
                        self._note_poison_bisect(handle, replay_window())
                rewind_to_commit()
                if alive():
                    self._query_failed(handle, e)
                return n
            # the tick's commit point, as one span: commit cursor, state
            # epoch, changelog append, query metrics
            with tracing.span("commit"):
                if per_record and consumed:
                    # drained: every consumed record's emissions are durable.
                    # This end-of-tick pass also amortizes the state epoch for
                    # queries whose per-record snapshots blew the budget —
                    # one epoch per tick keeps commit == epoch consistent.
                    before = committed_idx
                    advance_commit()
                    if epoch_capable and committed_idx > before and alive():
                        take_epoch_budgeted()
                if records:
                    if not alive():
                        return n  # abandoned mid-tick: the fence owns the rest
                    # a healthy tick after a restart closes the incident: the
                    # retry budget bounds CONSECUTIVE failures (crash-loops),
                    # not unrelated transient faults across the query lifetime
                    if handle.restart_count:
                        handle.restart_count = 0
                        handle.retry_backoff_ms = 0.0
                    if handle.shard_strikes:
                        # consecutive-strike semantics: a clean tick clears
                        # every suspect shard's streak (lifetime totals keep)
                        handle.shard_strikes = {}
                    if handle.poison_bisect is not None:
                        # a clean tick ends the bisection: full-size polls
                        # resume (a later crash re-derives its own window)
                        handle.poison_bisect = None
                    # tick commit point: everything above is durable in the
                    # in-memory sense — journal the dirty-state delta + this
                    # tick's sink emissions (runtime/changelog.py) so a kill
                    # -9 replays ticks-since-last-checkpoint, not the batch
                    self._changelog_append(handle, executor, consumer)
                    qm = self.metrics.for_query(handle.query_id)
                    qm.messages_in.mark(len(records))
                    qm.latency.record(_time.monotonic() - tick0)
                    qm.last_message_at_ms = int(_time.time() * 1000)
        return n

    # ------------------------------------------------------- state epochs
    def _take_epoch(self, handle: QueryHandle, executor, alive=None,
                    commit=None) -> None:
        """Snapshot the record-synchronous executor's state as the current
        commit-point epoch, together with the host materialization shadow
        (which the emit callback mutates before a sink produce can fail).
        The epoch carries the commit positions it was taken at: the restart
        path only restores an epoch whose positions equal the consumer's
        rewound positions, so a fenced-off zombie worker racing a late
        epoch in (state ahead of the fork point) can never double-count."""
        try:
            ep = {
                "backend": handle.backend,
                "state": executor.state_epoch(),
                "materialized": dict(handle.materialized),
                "positions": dict(
                    commit if commit is not None else handle.consumer.positions
                ),
                # sink ordinal high-water rides the epoch so a rebuilt
                # executor's fresh SinkWriter continues the sequence —
                # changelog frames (runtime/changelog.py) stay monotone
                # across in-memory self-heals
                "emit_seq": int(getattr(
                    getattr(executor, "sink_writer", None), "emit_seq", 0
                ) or 0),
            }
        except Exception as e:  # noqa: BLE001 — an unsnapshottable state
            # drop the PREVIOUS epoch too: the commit cursor keeps
            # advancing, and restoring a stale epoch against newer offsets
            # would silently lose records from state — degrading to the
            # disk checkpoint is the consistent fallback
            self._on_error("epoch-snapshot", e)
            if alive is None or alive():
                handle.epoch = None
            return
        if alive is None or alive():
            handle.epoch = ep

    def _rollback_epoch(self, handle: QueryHandle, executor,
                        alive=None) -> bool:
        """Roll executor stores (and the materialization shadow) back to
        the last per-record epoch — the atomic-poison-skip undo.  Returns
        True when the rollback happened.  The materialization shadow is
        shared handle state, so an abandoned tick worker (``alive`` false)
        may only roll back its own orphaned executor, never the shadow."""
        ep = handle.epoch
        if (
            ep is None or ep.get("state") is None
            or ep.get("backend") != handle.backend
            or not hasattr(executor, "restore_state_epoch")
        ):
            return False
        try:
            executor.restore_state_epoch(ep["state"])
        except Exception as e:  # noqa: BLE001 — a failed undo must not
            self._on_error("epoch-rollback", e)  # mask the poison handling
            return False
        if ep.get("materialized") is not None and (alive is None or alive()):
            handle.materialized.clear()
            handle.materialized.update(ep["materialized"])
        return True

    # --------------------------------------------------- health / watchdog
    def _health_sample(self, handle: QueryHandle) -> None:
        """One watchdog sample for the query: refresh offsets/lag/watermark
        and classify HEALTHY/IDLE/LAGGING/STALLED.  RUNNING and ERROR
        queries sample (an error-backoff tick with frozen offsets is stall
        evidence); PAUSED/TERMINATED queries are deliberately not judged."""
        prog = handle.progress
        if prog is None or handle.state not in ("RUNNING", "ERROR"):
            return
        # fold in the executor's decoded event time: with a TIMESTAMP
        # column the event-time watermark can run ahead of (or behind) the
        # raw record timestamps the poll loop saw
        st = getattr(handle.executor, "stream_time", None)
        if st is not None and st > -(2 ** 62):
            prog.note_watermark(int(st))
        prog.sample(handle.consumer)

    def _timeline_sample(self, handle: QueryHandle) -> None:
        """One interval-gated telemetry gauge sample for the query:
        per-shard cumulative stats, watermark lag, and the e2e histogram
        fold into the timeline as interval deltas; then any skew verdicts
        the interval close produced are published (``telemetry.skew:<qid>``
        plog, watchdog evidence event, and the engine-level
        ``telemetry_events`` ring the /alerts "telemetry" section reads)."""
        if not self.telemetry_enabled:
            return
        import time as _time

        qid = handle.query_id
        tl = self.timelines.get(qid)
        if tl is None:
            # nothing folded yet (query has not ticked): no series to
            # gauge, and creating a store here would grow one per
            # never-ticking query
            return
        now_ms = int(_time.time() * 1000)
        if tl.gauge_due(now_ms):
            shards = None
            shard_fn = getattr(handle.executor, "shard_metrics", None)
            if shard_fn is not None:
                try:
                    shards = shard_fn()
                except Exception:  # noqa: BLE001 — telemetry must never
                    shards = None  # take down the poll loop
            prog = handle.progress
            lag_ms = None
            e2e = None
            if prog is not None:
                if prog.watermark_ms is not None:
                    lag_ms = now_ms - int(prog.watermark_ms)
                hist = getattr(prog, "e2e_hist", None)
                if hist is not None and hist.count:
                    e2e = hist.snapshot()
            tl.observe(
                now_ms, shards=shards, watermark_lag_ms=lag_ms, e2e=e2e
            )
        for ev in tl.drain_events():
            detail = (
                f"hot shard {ev['hotShard']} carries {ev['share']:.0%} "
                f"of {ev['metric']} over {ev['intervals']} intervals"
            )
            # the plog entry routes back through _timeline_annotate, so
            # the skew verdict is ALSO visible on the timeline it judged
            self._plog_append(f"telemetry.skew:{qid}", detail)
            prog = handle.progress
            if prog is not None:
                try:
                    prog.note_event(
                        "telemetry.skew",
                        hotShard=ev["hotShard"], share=ev["share"],
                        metric=ev["metric"], intervals=ev["intervals"],
                    )
                except Exception:  # noqa: BLE001
                    pass
            self.telemetry_events.append({
                "queryId": qid, "detail": detail, **ev,
            })

    def health_alerts(self) -> List[Dict[str, Any]]:
        """Current LAGGING/STALLED queries with their evidence — the body
        of ``GET /alerts`` (and the embedded-mode equivalent the chaos
        soak's ``--watch`` polls)."""
        out = []
        for qid, h in list(self.queries.items()):
            prog = h.progress
            if prog is None or prog.health not in qhealth.ALERT_STATES:
                continue
            out.append(prog.alert(h.state, {
                "terminal": h.terminal,
                "restarts": h.restart_count,
                "backend": h.backend,
            }))
        return out

    # ------------------------------------------------ elastic mesh rescale
    def _maybe_rescale(self, handle: QueryHandle) -> None:
        """Health-driven live rescale controller (``ksql.rescale.enable``,
        default off): a distributed query whose watchdog verdict holds
        LAGGING for ``ksql.rescale.hysteresis.ticks`` consecutive samples
        doubles its mesh toward ``ksql.device.shards.max``; IDLE for the
        same streak halves it toward ``ksql.device.shards.min``.  A
        cooldown (``ksql.rescale.cooldown.ms``) separates consecutive
        cutovers so a grow observes its effect before the controller may
        act again."""
        import time as _time

        if not cfg._bool(self.effective_property(cfg.RESCALE_ENABLE, False)):
            return
        if self.overload.defer_elective():
            return  # a rescale cutover costs a compile: not under CRITICAL
        prog = handle.progress
        if (
            handle.state != "RUNNING" or handle.backend != "distributed"
            or handle.pending_rescale is not None or prog is None
        ):
            handle.rescale_lag_streak = 0
            handle.rescale_idle_streak = 0
            return
        health = prog.health
        handle.rescale_lag_streak = (
            handle.rescale_lag_streak + 1 if health == qhealth.LAGGING else 0
        )
        handle.rescale_idle_streak = (
            handle.rescale_idle_streak + 1 if health == qhealth.IDLE else 0
        )
        hyst = int(self.effective_property(cfg.RESCALE_HYSTERESIS_TICKS, 8))
        cooldown = float(
            self.effective_property(cfg.RESCALE_COOLDOWN_MS, 60000)
        ) * max(1, handle.rescale_penalty)
        if _time.time() * 1000 - handle.last_rescale_ms < cooldown:
            return
        cur = int(getattr(
            getattr(handle.executor, "device", None), "n_shards", 0
        ) or 0)
        if not cur:
            return
        import jax as _jax

        smax = int(
            self.effective_property(cfg.DEVICE_SHARDS_MAX, 0) or 0
        ) or len(_jax.devices())
        smin = max(1, int(self.effective_property(cfg.DEVICE_SHARDS_MIN, 1)))
        if handle.rescale_lag_streak >= hyst and cur < smax:
            self._rescale_query(handle, min(cur * 2, smax), "grow")
        elif handle.rescale_idle_streak >= hyst and cur > smin:
            target = max(cur // 2, smin)
            if self._shrink_overflows_budget(handle, target):
                # refused, loudly: arm the cooldown + clear the streak so
                # the controller does not re-price the same shrink every
                # poll tick while the query stays IDLE
                handle.rescale_idle_streak = 0
                handle.last_rescale_ms = _time.time() * 1000
                return
            self._rescale_query(handle, target, "shrink")

    def _shrink_overflows_budget(self, handle: QueryHandle,
                                 target: int) -> bool:
        """Memory-model guard on mesh shrink (closing half the ROADMAP
        'doubles/halves blindly' gap): a shrink concentrates every key
        onto fewer shards and reshard-on-restore grows the per-shard
        store until the fullest target shard sits at <= 50% load — price
        THAT footprint with the static model before paying the cutover,
        and refuse when it would overflow
        ``ksql.analysis.memory.budget.bytes``."""
        budget = int(
            self.effective_property(cfg.MEMORY_BUDGET_BYTES, 0) or 0
        )
        if not budget:
            return False
        dev = getattr(handle.executor, "device", None)
        compiled = getattr(dev, "c", dev)  # DistributedDeviceQuery wraps
        if compiled is None:
            return False
        try:
            import jax as _jax
            import numpy as _np

            from ksql_tpu.analysis.mem_model import (
                POINT_CREATION,
                shrink_footprint,
            )

            occ = dev.state.get("occ") if hasattr(dev, "state") else None
            live = 0
            if occ is not None:
                # host readback of the occupancy bitmask only (bools, one
                # per slot) — the controller runs at poll-tick cadence
                # and ONLY when a shrink is already due
                live = int(_np.asarray(
                    _jax.device_get(occ)
                )[..., :-1].sum())
            proj = shrink_footprint(
                compiled, live, target, growth_budget_bytes=budget
            )
            need = proj.per_shard_bytes(POINT_CREATION)
        except Exception as e:  # noqa: BLE001 — a pricing failure must
            # not wedge the controller; the cutover keeps its own
            # refuse-loudly reshard guards
            self._on_error("rescale-memcheck", e)
            return False
        if need <= budget:
            return False
        dom = proj.dominant(POINT_CREATION)
        store_cap = next(
            (c.capacity for c in proj.components if c.name == "store"), 0
        )
        self._plog_append(
            f"rescale.refuse:{handle.query_id}",
            f"shrink to {target} shard(s) refused by the memory model: "
            f"{live} live keys concentrate to a per-shard store of "
            f"{store_cap} slots, projected footprint {need} bytes > "
            f"{cfg.MEMORY_BUDGET_BYTES}={budget}"
            + (f"; dominant component {dom.name}={dom.at_creation}B"
               if dom is not None else ""),
        )
        if handle.progress is not None:
            handle.progress.note_event(
                "rescale.refuse", target=target,
                projectedBytes=int(need), budgetBytes=int(budget),
                dominant=dom.name if dom is not None else "",
            )
        return True

    def _rescale_query(self, handle: QueryHandle, target: int,
                       direction: str) -> None:
        """Execute one resize as a supervised drain/cutover riding the
        restart ladder: commit-point checkpoint (the poll loop is between
        ticks here, so the executor is drained and the commit point equals
        the consumer positions) -> route through ``_maybe_restart`` with
        zero backoff, which fences the old executor (emit-fence swap +
        rebuild-token identity: a wedged old mesh becomes a muted zombie
        exactly like an abandoned rebuild), rebuilds at ``target`` shards,
        reshard-restores the checkpoint, and resumes from the commit
        point.  The rebuild deadline and the retry ladder are the failure
        path; a failed cutover reverts to the previous shard count."""
        import time as _time

        cur = int(getattr(
            getattr(handle.executor, "device", None), "n_shards", 0
        ) or 0)
        if target == cur or target < 1:
            return
        directory = self.effective_property(cfg.STATE_CHECKPOINT_DIR)
        stateful = bool(getattr(handle.executor, "stateful", False))
        if stateful and not directory:
            # stateful state can only cross meshes through the checkpoint
            # tier: without a directory the cutover would silently
            # cold-start the aggregation — refuse, loudly
            self._plog_append(
                f"rescale.no-checkpoint:{handle.query_id}",
                f"cannot {direction} {cur}->{target} shards: stateful "
                f"query and no {cfg.STATE_CHECKPOINT_DIR}; set it to "
                "enable elastic rescale",
            )
            handle.rescale_lag_streak = 0
            handle.rescale_idle_streak = 0
            handle.last_rescale_ms = _time.time() * 1000
            return
        init_phases: Dict[str, float] = {}
        if directory:
            # take the commit-point checkpoint UNCONDITIONALLY (stateless
            # queries included): the rebuild's restore path loads the last
            # snapshot's positions, and a stale periodic snapshot would
            # rewind a stateless query up to checkpoint.interval.ms of
            # offsets — re-emitting every record since it into the sink.
            # Both initiation phases land on the query's flight recorder
            # as cutover.* spans; their durations ride pending_rescale so
            # the rescale.done evidence event reports the WHOLE cutover
            # phase-by-phase (a slow cutover is attributable to a phase,
            # not a wall-clock blob)
            rec = self.recorder_if_enabled(handle.query_id)
            try:
                with tracing.tick(rec) as tk:
                    with tracing.span("cutover.drain"):
                        # the poll loop is between ticks, so this is a
                        # no-op flush — kept explicit so the commit-point
                        # invariant is enforced, not assumed
                        drain = getattr(handle.executor, "drain", None)
                        if drain is not None:
                            drain()
                    with tracing.span("cutover.checkpoint"):
                        self.checkpoint()  # the cutover's commit point
                    if tk is not None:
                        init_phases = {
                            name: round(st.get("ms", 0.0), 3)
                            for name, st in tk.stages.items()
                            if name.startswith("cutover.")
                        }
            except Exception as e:  # noqa: BLE001 — no snapshot, no cutover
                self._on_error("rescale-checkpoint", e)
                # arm the cooldown + clear the streaks like any other
                # aborted attempt: without this the controller would retry
                # a FULL engine checkpoint every poll tick in a tight loop
                handle.rescale_lag_streak = 0
                handle.rescale_idle_streak = 0
                handle.last_rescale_ms = _time.time() * 1000
                return
        handle.pending_rescale = {
            "target": target, "from": cur, "direction": direction,
            "prev_override": handle.shard_override,
            "phases": init_phases,
        }
        handle.shard_override = target
        handle.last_rescale_ms = _time.time() * 1000
        handle.rescale_lag_streak = 0
        handle.rescale_idle_streak = 0
        self._plog_append(
            f"rescale:{handle.query_id}",
            f"{direction} {cur}->{target} shards: supervised drain/cutover "
            "via the restart ladder",
        )
        if handle.progress is not None:
            handle.progress.note_event(
                f"rescale.{direction}", **{"from": cur, "to": target}
            )
        # drained cutover: between ticks nothing is buffered, so ERROR +
        # zero backoff hands the query to _maybe_restart on the next poll
        # iteration — rebuild supervision (deadline, fences) applies
        # unchanged, and a healthy post-cutover tick resets the budget
        handle.state = "ERROR"
        handle.retry_at_ms = 0.0

    def _revert_rescale(self, handle: QueryHandle, why: str) -> None:
        """A cutover failed before the new mesh could own the query:
        restore the previous shard override so the ladder's next rebuild
        comes back up at the PREVIOUS size, where the snapshot restores
        without resharding."""
        info = handle.pending_rescale
        if info is None:
            return
        handle.pending_rescale = None
        handle.shard_override = info.get("prev_override")
        if info.get("direction") in ("degrade", "regrow"):
            # a failed containment cutover: re-accrue strikes fresh at the
            # reverted width (the penalty below gates how soon the next
            # threshold crossing may re-pay the cutover cost)
            handle.shard_strikes = {}
        # escalate the cooldown multiplicatively: a refused reshard
        # (un-movable state) would otherwise re-pay the full cutover cost
        # (engine checkpoint + two recompiles + failed restore) every
        # plain cooldown period forever
        handle.rescale_penalty = min((handle.rescale_penalty or 1) * 2, 64)
        self._plog_append(
            f"rescale.revert:{handle.query_id}",
            f"{info.get('direction')} {info.get('from')}->"
            f"{info.get('target')} aborted ({why}); reverting to "
            f"{info.get('from')} shards",
        )
        if handle.progress is not None:
            handle.progress.note_event("rescale.revert",
                                       reason=str(why)[:200])

    def _note_poison_bisect(self, handle: QueryHandle, window: int) -> None:
        """A deterministic USER error hides somewhere in a batched flush of
        ``window`` replayable records: halve the records the next tick may
        poll.  Repeated deterministic re-crashes converge the window to one
        record in O(log window) restarts (each bounded by the normal retry
        ladder), at which point the crash IS attributable and the record is
        skipped atomically instead of crash-looping to terminal ERROR."""
        limit = max(1, int(window) // 2)
        handle.poison_bisect = {"limit": limit}
        self._plog_append(
            f"poison.bisect:{handle.query_id}",
            f"non-attributable poison in a batched flush of {window} "
            f"replayable records; next tick limited to {limit} records",
        )

    def _is_poison(self, e: Exception) -> bool:
        """True for deterministic USER-classified record errors: retrying
        them cannot succeed, so the record is skipped rather than the
        query crash-looped (ksql.fail.on.deserialization.error=false /
        LogAndContinueExceptionHandler analog).  Injected faults are never
        poison — they model transient infra failures and must take the
        restart+replay path regardless of what their message matches."""
        from ksql_tpu.common.faults import FaultInjected

        if isinstance(e, FaultInjected):
            return False
        etype = classify_error(
            e, str(self.effective_property("ksql.error.classifier.regex", ""))
        )
        return etype == "USER"

    # ----------------------------------------- error handling / self-healing
    def _query_failed(self, handle: QueryHandle, e: Exception) -> None:
        """Classify + enqueue the error, mark the query ERROR, and schedule
        a restart with exponential backoff (reference QueryMetadataImpl
        uncaught-exception handler + KsqlEngine restart path)."""
        import time as _time

        etype = classify_error(
            e, str(self.effective_property("ksql.error.classifier.regex", ""))
        )
        handle.error_queue.append(
            QueryError(int(_time.time() * 1000), f"{type(e).__name__}: {e}", etype)
        )
        max_q = int(self.effective_property("ksql.query.error.max.queue.size", 10))
        del handle.error_queue[:-max_q]
        self._on_error(f"query:{handle.query_id}:{etype}", e)
        self.metrics.for_query(handle.query_id).errors.mark(1)
        # post-mortem: the triggering tick's trace goes to the processing
        # log NOW (the ring also retains it, but a restart wipes executor
        # state — the log is the durable record of what the tick was
        # doing).  Only the ACTIVE tick is dumped: a failure outside any
        # tick (e.g. an executor rebuild in _maybe_restart) must not
        # relabel a retained earlier tick with an unrelated error.
        tr = tracing.active()
        if tr is not None and tr.query_id == handle.query_id:
            tr.status = "ERROR"
            tr.error = f"{type(e).__name__}: {e}"
            self._dump_trace(handle.query_id, tr)
        handle.state = "ERROR"
        retry_max = int(self.effective_property(cfg.QUERY_RETRY_MAX, 2147483647))
        if handle.restart_count >= retry_max:
            # restart budget exhausted: terminal ERROR — no more self-healing
            # attempts; /healthcheck flips unhealthy with this query id
            handle.terminal = True
            self._on_error(
                f"query:{handle.query_id}:terminal",
                KsqlException(
                    f"query {handle.query_id} exceeded {cfg.QUERY_RETRY_MAX}="
                    f"{retry_max} restarts; transitioning to terminal ERROR"
                ),
            )
            # a terminal PRIMARY must not strand its window-family members
            # (their emissions ride its device step): promote them to
            # standalone executors, same as TERMINATE does
            for m_qid in self._release_family(handle.query_id):
                mh = self.queries.get(m_qid)
                if mh is None or not mh.is_running():
                    continue
                try:
                    mh.executor = self._build_executor(mh)
                except Exception as me:  # noqa: BLE001 — promotion failure
                    self._query_failed(mh, me)  # takes the member's own ladder
            return
        initial = float(
            self.effective_property(cfg.QUERY_RETRY_BACKOFF_INITIAL_MS, 15000)
        )
        maximum = float(
            self.effective_property(cfg.QUERY_RETRY_BACKOFF_MAX_MS, 900000)
        )
        handle.retry_backoff_ms = min(
            (handle.retry_backoff_ms * 2) or initial, maximum
        )
        handle.retry_at_ms = _time.time() * 1000 + handle.retry_backoff_ms
        # mesh fault domain: a failure attributable to ONE shard of a
        # distributed mesh strikes that shard; past the threshold the
        # strike bookkeeping escalates to a degraded-mesh cutover (which
        # may zero the backoff above — the cutover IS the recovery)
        self._note_shard_strike(handle, e, etype)

    # ----------------------------------------- mesh fault domain (shards)
    def _note_shard_strike(self, handle: QueryHandle, e: Exception,
                           etype: str) -> None:
        """Shard-level failure containment: when a distributed query's
        failure names ONE shard — a classified-SYSTEM raise stamped with
        ``mesh_shard`` by the per-lane dispatch seam, or a tick deadline
        whose suspect-shard marker points at a wedged lane — the shard is
        marked suspect (``mesh.shard.suspect`` plog + /alerts evidence
        naming qid/shard/reason).  ``ksql.mesh.shard.fail.threshold``
        consecutive strikes (reset by any clean tick) trigger a
        degraded-mesh cutover instead of letting the single bad lane burn
        the whole query's retry ladder."""
        import time as _time

        if handle.backend != "distributed" or handle.terminal:
            return
        threshold = int(
            self.effective_property(cfg.MESH_FAIL_THRESHOLD, 3) or 0
        )
        if threshold <= 0:
            return
        shard = getattr(e, "mesh_shard", None)
        deadline = bool(getattr(e, "mesh_deadline", False))
        if shard is None or (etype != "SYSTEM" and not deadline):
            return  # not attributable to one shard: ordinary ladder
        shard = int(shard)
        strikes = handle.shard_strikes.get(shard, 0) + 1
        handle.shard_strikes[shard] = strikes
        handle.shard_strikes_total[shard] = (
            handle.shard_strikes_total.get(shard, 0) + 1
        )
        handle.last_shard_strike_ms = _time.time() * 1000
        reason = (
            f"tick deadline blown inside shard {shard}'s dispatch lane"
            if deadline else f"{type(e).__name__}: {e}"
        )
        self._plog_append(
            f"mesh.shard.suspect:{handle.query_id}",
            f"shard {shard} suspect ({strikes}/{threshold} consecutive "
            f"strikes): {reason}",
        )
        if handle.progress is not None:
            handle.progress.note_event(
                "mesh.shard.suspect", shard=shard, strikes=strikes,
                threshold=threshold, reason=str(reason)[:200],
            )
        if strikes >= threshold:
            self._degrade_mesh(handle, shard, reason, threshold)

    def _degrade_mesh(self, handle: QueryHandle, shard: int,
                      reason: str, threshold: int) -> None:
        """Execute the degraded-mesh cutover: rebuild the query at the
        next power of two BELOW its current width through the PR-9
        ``shard_override``/reshard-restore path, resuming from the last
        consistent checkpoint.  Runs from inside the failure path (the
        query is already ERROR with its offsets rewound to the commit
        point), so the engine checkpoint below carries each ERROR query's
        last CONSISTENT snapshot forward rather than snapshotting torn
        state.  A failed cutover reverts via ``rescale.revert`` exactly
        like a live rescale; un-movable state (ss-join ring buffers)
        refuses loudly in the reshard-restore.  ``mesh_degraded_from``
        remembers the original width for the regrow probe."""
        import time as _time

        if handle.pending_rescale is not None:
            return  # a cutover is already in flight
        cooldown = float(
            self.effective_property(cfg.RESCALE_COOLDOWN_MS, 60000)
        ) * max(1, handle.rescale_penalty)
        if (
            handle.rescale_penalty
            and _time.time() * 1000 - handle.last_rescale_ms < cooldown
        ):
            # a REVERTED cutover (un-movable state) must not re-pay the
            # checkpoint + two recompiles every threshold crossings: the
            # escalating penalty cooldown gates re-attempts, the plain
            # retry ladder keeps running meanwhile
            handle.shard_strikes[shard] = 0
            return
        cur = int(getattr(
            getattr(handle.executor, "device", None), "n_shards", 0
        ) or 0)
        if cur <= 1:
            # one shard IS the query: nothing to contain — plain ladder
            return
        target = 1 << ((cur - 1).bit_length() - 1)
        stateful = bool(getattr(handle.executor, "stateful", False))
        directory = self.effective_property(cfg.STATE_CHECKPOINT_DIR)
        if stateful and not directory:
            # exactly the rescale posture: stateful state only crosses
            # meshes through the checkpoint tier — refuse, loudly, and
            # leave the query to the ordinary retry ladder at full width
            self._plog_append(
                f"mesh.degrade.no-checkpoint:{handle.query_id}",
                f"cannot degrade {cur}->{target} shards around suspect "
                f"shard {shard}: stateful query and no "
                f"{cfg.STATE_CHECKPOINT_DIR}; set it to enable "
                "degraded-mesh cutovers",
            )
            handle.shard_strikes[shard] = 0
            return
        if directory:
            try:
                # the cutover's commit point: ERROR queries (this one)
                # carry their last consistent snapshot forward, healthy
                # siblings snapshot fresh (save_checkpoint contract)
                self.checkpoint()
            except Exception as e2:  # noqa: BLE001 — no snapshot, no
                self._on_error("mesh-degrade-checkpoint", e2)  # cutover
                handle.shard_strikes[shard] = 0
                return
        handle.pending_rescale = {
            "target": target, "from": cur, "direction": "degrade",
            "prev_override": handle.shard_override,
            "phases": {}, "suspect_shard": shard,
        }
        handle.shard_override = target
        handle.last_rescale_ms = _time.time() * 1000
        self._plog_append(
            f"mesh.degrade:{handle.query_id}",
            f"degraded-mesh cutover {cur}->{target} shards: shard {shard} "
            f"reached {cfg.MESH_FAIL_THRESHOLD}={threshold} consecutive "
            f"strikes ({reason}); rebuilding below the suspect width from "
            "the commit point",
        )
        if handle.progress is not None:
            handle.progress.note_event(
                "mesh.degrade", **{"from": cur, "to": target,
                                   "suspectShard": shard},
            )
        # the query is already ERROR (we run inside its failure path):
        # zero the backoff so the next poll iteration executes the cutover
        handle.retry_at_ms = 0.0

    def _maybe_mesh_regrow(self, handle: QueryHandle) -> None:
        """Regrow probe: once a degraded mesh has run strike-free for
        ``ksql.mesh.regrow.cooldown.ms`` (scaled by the revert penalty),
        cut back over to the query's original shard width.  If the fault
        has NOT cleared, the restored width strikes again and re-degrades
        — bounded by the same cooldown."""
        import time as _time

        if (
            handle.mesh_degraded_from is None
            or handle.state != "RUNNING"
            or handle.backend != "distributed"
            or handle.pending_rescale is not None
        ):
            return
        if self.overload.defer_elective():
            return  # regrow costs a compile: stay degraded until pressure clears
        cooldown = float(
            self.effective_property(cfg.MESH_REGROW_COOLDOWN_MS, 60000) or 0
        )
        if cooldown <= 0:
            return  # probe disabled: degraded until restart
        cooldown *= max(1, handle.rescale_penalty)
        quiet_since = max(handle.last_shard_strike_ms, handle.last_rescale_ms)
        if _time.time() * 1000 - quiet_since < cooldown:
            return
        target = int(handle.mesh_degraded_from)
        cur = int(getattr(
            getattr(handle.executor, "device", None), "n_shards", 0
        ) or 0)
        if not cur or target <= cur:
            handle.mesh_degraded_from = None  # already back at width
            return
        self._plog_append(
            f"mesh.regrow:{handle.query_id}",
            f"fault quiet for {int(cooldown)}ms: restoring the original "
            f"{target}-shard width ({cur}->{target} cutover)",
        )
        self._rescale_query(handle, target, "regrow")

    def _dump_trace(self, query_id: str, tr) -> None:
        """Write one tick trace (flight-recorder post-mortem) into the
        processing log — once per trace, however many times the error path
        re-touches it."""
        if getattr(tr, "_dumped", False):
            return
        import json as _json

        try:
            blob = _json.dumps(tr.to_dict(), separators=(",", ":"))
        except Exception:  # noqa: BLE001 — a trace must never break
            return  # the error path that is dumping it
        tr._dumped = True
        self._plog_append(f"trace:{query_id}", blob)
        if not self.is_sandbox:
            try:
                self._produce_processing_log(
                    f"trace:{query_id}", KsqlException(blob)
                )
            except Exception:  # noqa: BLE001 — the log must never recurse
                pass

    def _maybe_restart(self, handle: QueryHandle) -> None:
        """Self-healing restart once the backoff elapses: rebuild the
        executor fresh and restore its state from the last checkpoint (the
        reference restarts the streams runtime and restores every store
        from its changelog).  Terminal queries (retry budget exhausted)
        stay down.

        With ``ksql.query.rebuild.timeout.ms`` > 0 the rebuild+restore
        body runs on a supervised worker under the same zombie-fence
        discipline as tick supervision (the carried-forward ROADMAP gap:
        a hung XLA compile here used to block the WHOLE poll loop).  The
        fence is ``handle.rebuild_token`` identity: the deadline handler
        swaps it, every handle/engine mutation below is ``alive()``-
        guarded (machine-checked by graftlint's unfenced-handle-mutation
        rule), and ``_build_executor`` threads the same fence through its
        emit-fence swap and family registration."""
        import time as _time

        from ksql_tpu.common import faults

        if handle.terminal or _time.time() * 1000 < handle.retry_at_ms:
            return
        # pre-supervision bookkeeping: the worker does not exist yet, so
        # these two writes cannot race it
        handle.restart_count += 1  # graftlint: disable=unfenced-handle-mutation
        token = object()
        handle.rebuild_token = token  # graftlint: disable=unfenced-handle-mutation

        def alive() -> bool:
            return handle.rebuild_token is token

        def rebuild() -> None:
            # the whole rebuild+restore records as one tick on the query's
            # flight recorder, phase-split by cutover.* spans (rebuild /
            # restore here; a reshard-restore adds gather / repartition /
            # insert inside checkpoint._prepare_reshard) — /query-trace
            # shows where a slow restart or rescale cutover spent its time
            rec = self.recorder_if_enabled(handle.query_id)
            with tracing.tick(rec) as cutover_tick:
                self._rebuild_body(handle, alive, cutover_tick)

        timeout_ms = float(
            self.effective_property(cfg.QUERY_REBUILD_TIMEOUT_MS, 0) or 0
        )
        if timeout_ms <= 0:
            rebuild()
            return
        worker = threading.Thread(
            target=rebuild, daemon=True, name=f"rebuild-{handle.query_id}"
        )
        worker.start()
        worker.join(timeout_ms / 1000.0)
        if not worker.is_alive():
            return
        # the rebuild blew its deadline (a wedged compile): fence the
        # worker off and escalate through the retry ladder — sibling
        # queries resume polling immediately instead of hanging behind it.
        # The swap is the revocation itself, so it must run unconditionally
        handle.rebuild_token = None  # graftlint: disable=unfenced-handle-mutation
        handle.rebuild_deadlines += 1  # graftlint: disable=unfenced-handle-mutation
        if handle.progress is not None:
            # truthful evidence kind: /alerts must point the operator at
            # the REBUILD knob, not the (possibly disabled) tick knob
            handle.progress.note_tick_deadline(
                int(timeout_ms), kind="rebuild.deadline"
            )
        self._plog_append(
            f"rebuild.deadline:{handle.query_id}",
            f"executor rebuild exceeded {cfg.QUERY_REBUILD_TIMEOUT_MS}="
            f"{int(timeout_ms)}ms; worker abandoned, retry ladder "
            "escalates",
        )
        self._query_failed(handle, KsqlException(
            f"executor rebuild deadline exceeded "
            f"({cfg.QUERY_REBUILD_TIMEOUT_MS}={int(timeout_ms)}ms): "
            "worker abandoned, next retry after backoff"
        ))

    def _rebuild_body(self, handle: QueryHandle, alive, cutover_tick) -> None:
        """The rebuild+restore body of ``_maybe_restart`` (runs inline or
        on a supervised worker, under the rebuild-token fence ``alive``
        and a cutover-phase flight-recorder tick)."""
        from ksql_tpu.common import faults

        try:
            # chaos seam: `executor.rebuild@<qid>:hang` models the XLA
            # compile wedge the supervision exists for — INSIDE the
            # try, so a raise-mode fault is contained like any rebuild
            # failure (ladder + backoff), never a poll-loop abort or a
            # silently-dead worker with no backoff advance
            faults.fault_point("executor.rebuild", handle.query_id)
            with tracing.span("cutover.rebuild"):
                fresh = self._build_executor(handle, live=alive)
        except Exception as e:  # noqa: BLE001 — rebuild failed: back
            if alive():  # off more
                self._revert_rescale(handle, "rebuild failed")
                self._query_failed(handle, e)
            return
        if not alive():
            return  # fenced off mid-compile: discard the muted executor
        handle.executor = fresh
        # Rebuilding alone replays the rewound batch into EMPTY state —
        # an aggregation double-counts the prefix it had already
        # absorbed.  Restore preference: the in-memory commit-point
        # epoch (newest — taken per durable record this incident,
        # consumer already rewound to its exact offsets) wins over the
        # disk checkpoint (older, but state + offsets snapshotted
        # atomically, so it rewinds offsets to ITS point); neither
        # available degrades to the PR-1 posture (empty state + replay
        # from the rewound offsets, at-least-once).
        restored = False
        ep = handle.epoch
        ep_positions = ep.get("positions") if ep is not None else None
        with tracing.span("cutover.restore"):
            if (
                ep is not None and ep.get("state") is not None
                and ep.get("backend") == handle.backend
                and hasattr(fresh, "restore_state_epoch")
                # the epoch must match the replay point exactly — a stale
                # or zombie-raced epoch (state ahead of the rewound
                # offsets) would double-count the replayed records
                and (ep_positions is None
                     or ep_positions == dict(handle.consumer.positions))
            ):
                try:
                    fresh.restore_state_epoch(ep["state"])
                    if ep.get("materialized") is not None and alive():
                        handle.materialized.clear()
                        handle.materialized.update(ep["materialized"])
                    if ep.get("emit_seq") is not None and hasattr(
                        fresh, "sink_writer"
                    ):
                        # fresh SinkWriter would restart ordinals at 0;
                        # continue the sequence so changelog frames stay
                        # monotone across the self-heal
                        fresh.sink_writer.emit_seq = int(ep["emit_seq"])
                    restored = True
                except Exception as e:  # noqa: BLE001 — torn epoch: fall
                    self._on_error("epoch-restore", e)  # back
            directory = self.effective_property(cfg.STATE_CHECKPOINT_DIR)
            if not restored and directory and alive():
                from ksql_tpu.runtime.checkpoint import (
                    restore_query_checkpoint,
                )

                try:
                    if restore_query_checkpoint(
                        self, handle, str(directory), live=alive
                    ):
                        restored = True
                        if alive():
                            # the disk snapshot's offsets now define the
                            # replay point; the newer in-memory epoch no
                            # longer matches
                            handle.epoch = None
                except Exception as e:  # noqa: BLE001 — a torn/mismatched
                    # snapshot must not block recovery: fall back to the
                    # PR-1 posture (empty state + whole-batch replay,
                    # at-least-once)
                    self._on_error("checkpoint-restore", e)
                    if handle.pending_rescale is not None and alive():
                        # a refused/torn reshard-restore must not resume a
                        # stateful query cold: revert to the previous shard
                        # count and retry through the ladder — the next
                        # rebuild restores the same snapshot unresharded
                        self._revert_rescale(handle, f"restore failed: {e}")
                        self._query_failed(handle, KsqlException(
                            "rescale cutover aborted (reshard-restore "
                            f"failed): {e}"
                        ))
                        return
        if not restored and alive():
            stateful_fresh = bool(getattr(fresh, "stateful", False))
            if handle.pending_rescale is not None and stateful_fresh:
                # a CUTOVER (rescale or degraded-mesh) of a stateful
                # query found nothing to restore: resuming at the new
                # width would silently cold-start the aggregation —
                # revert to the previous shard count and retry through
                # the ladder (periodic checkpointing will produce a
                # restorable snapshot before the next attempt)
                self._revert_rescale(
                    handle, "no restorable epoch/checkpoint at cutover"
                )
                self._query_failed(handle, KsqlException(
                    "cutover aborted: stateful query with no restorable "
                    "state epoch or checkpoint"
                ))
                return
            # tier 3 of the recovery ladder: no epoch, no checkpoint
            # generation + changelog tail (tiers 1-2) — the query resumes
            # with EMPTY state and replays the rewound batch.  Delivery
            # stays at-least-once; for stateful queries the aggregate
            # state before the rewind point is GONE: say so loudly, in
            # the processing log AND the /alerts evidence ring
            self._plog_append(
                f"restart.no-checkpoint:{handle.query_id}",
                "recovery ladder exhausted: no state epoch, no intact "
                "checkpoint generation, no changelog tail "
                f"({cfg.STATE_CHECKPOINT_DIR}="
                f"{str(directory) or '<unset>'}): restarting with "
                "empty state + whole-batch replay (at-least-once; "
                "bounded replay needs a checkpoint dir"
                + ("; pre-rewind aggregate state is lost)"
                   if stateful_fresh else ")"),
            )
            if handle.progress is not None:
                handle.progress.note_event(
                    "restart.no-checkpoint",
                    checkpointDir=str(directory) or None,
                    stateful=stateful_fresh,
                )
        if alive():
            if handle.pending_rescale is not None:
                # cutover complete: the executor runs on the new mesh
                # and (stateful queries) the reshard-restore above
                # re-partitioned its state to the commit point
                info = handle.pending_rescale
                handle.pending_rescale = None
                direction = info.get("direction", "grow")
                handle.reshard_total[direction] = (
                    handle.reshard_total.get(direction, 0) + 1
                )
                handle.rescale_penalty = 0
                if direction == "degrade":
                    # running below the suspect width now: remember the
                    # ORIGINAL width (first degrade wins across repeated
                    # degrades) for the regrow probe, and give the new
                    # mesh a clean slate of strikes
                    if handle.mesh_degraded_from is None:
                        handle.mesh_degraded_from = (
                            int(info.get("from") or 0) or None
                        )
                    handle.shard_strikes = {}
                elif direction == "regrow":
                    # fault cleared and the original width restored
                    handle.mesh_degraded_from = None
                    handle.shard_strikes = {}
                # the initiation phases (drain + commit-point checkpoint,
                # stashed by _rescale_query) merge with this tick's
                # rebuild/restore/gather/repartition/insert spans: the
                # /alerts evidence names where the WHOLE cutover went
                phases = {
                    str(k): float(v)
                    for k, v in (info.get("phases") or {}).items()
                }
                if cutover_tick is not None:
                    for name, st in cutover_tick.stages.items():
                        if name.startswith("cutover."):
                            phases[name] = round(
                                phases.get(name, 0.0)
                                + float(st.get("ms", 0.0)), 3,
                            )
                self._plog_append(
                    f"rescale.done:{handle.query_id}",
                    f"{direction} cutover complete: "
                    f"{info.get('from')}->{info.get('target')} shards"
                    + (f"; phases(ms)={phases}" if phases else ""),
                )
                if handle.progress is not None:
                    handle.progress.note_event(
                        "rescale.done", direction=direction,
                        phasesMs=phases,
                        **{"from": info.get("from"),
                           "to": info.get("target")},
                    )
            handle.state = "RUNNING"
            # a completed rebuild/cutover is the moment a mis-sized
            # deadline becomes attributable: hint when a configured
            # tick/rebuild deadline sits below the observed cold-compile
            # p99 (the "kills every rebuilt tick" footgun, with evidence)
            self._deadline_hint(handle)

    def _deadline_hint(self, handle: QueryHandle) -> None:
        """Deadline auto-sizing: after a rebuild/cutover completes,
        compare the configured ``ksql.query.tick.timeout.ms`` /
        ``ksql.query.rebuild.timeout.ms`` against the cold-compile p99 the
        flight recorder actually observed for this query; a deadline sized
        below it would deadline-kill every rebuilt tick in a loop.

        Default posture (hint-only): log a ``deadline.hint`` plog entry
        and an /alerts evidence event naming the observed value.  With
        ``ksql.query.deadline.autosize`` on, go one step further and
        RAISE the undersized knob to observed p99 x
        ``ksql.query.deadline.autosize.margin`` (engine-wide session
        override — the same precedence a SET statement has), logging
        ``deadline.autosize`` with old->new.  Auto-sizing only ever
        raises: a generous deadline is never tightened."""
        rec = self.trace_recorders.get(handle.query_id)
        if rec is None:
            return
        st = rec.stage_stats().get("device.compile")
        p99 = st.get("p99_ms") if st else None
        if not p99:
            return
        autosize = cfg._bool(
            self.effective_property(cfg.DEADLINE_AUTOSIZE, False)
        )
        margin = float(
            self.effective_property(cfg.DEADLINE_AUTOSIZE_MARGIN, 2.0) or 2.0
        )
        for key in (cfg.QUERY_TICK_TIMEOUT_MS, cfg.QUERY_REBUILD_TIMEOUT_MS):
            configured = float(self.effective_property(key, 0) or 0)
            if not configured or configured >= p99:
                continue
            if autosize:
                raised = int(-(-float(p99) * max(margin, 1.0) // 1))
                self.session_properties[key] = raised
                self._plog_append(
                    f"deadline.autosize:{handle.query_id}",
                    f"{key} raised {int(configured)}ms -> {raised}ms: the "
                    f"configured deadline sat below the observed "
                    f"cold-compile p99 ({p99:.0f}ms) and would have "
                    "deadline-killed every rebuilt tick "
                    f"(ksql.query.deadline.autosize margin {margin:g}x)",
                )
                if handle.progress is not None:
                    handle.progress.note_event(
                        "deadline.autosize", knob=key,
                        oldMs=int(configured), newMs=raised,
                        observedColdCompileP99Ms=round(float(p99), 1),
                    )
                continue
            self._plog_append(
                f"deadline.hint:{handle.query_id}",
                f"{key}={int(configured)}ms is below the observed "
                f"cold-compile p99 ({p99:.0f}ms) for this query: a "
                "deadline sized under cold compile deadline-kills every "
                f"rebuilt tick — raise it above {p99:.0f}ms",
            )
            if handle.progress is not None:
                handle.progress.note_event(
                    "deadline.hint", knob=key,
                    configuredMs=int(configured),
                    observedColdCompileP99Ms=round(float(p99), 1),
                )

    def run_until_quiescent(self, max_iters: int = 1000) -> None:
        for _ in range(max_iters):
            if self.poll_once() == 0:
                return

    def flush_all_time(self, stream_time: int) -> None:
        """Advance event time across queries (closes windows; used by tests
        and the EMIT FINAL path)."""
        for handle in self.queries.values():
            if handle.is_running():
                handle.executor.flush_time(stream_time)
        self.run_until_quiescent()

    # ------------------------------------------------------- INSERT VALUES
    def _h_insert_values(self, s: ast.InsertValues, text):
        source = self.metastore.require_source(s.target)
        header_names = {n for n, _ in source.header_columns}
        if header_names and (
            not s.columns or any(c.upper() in header_names for c in s.columns)
        ):
            raise KsqlException(
                "Cannot insert into HEADER columns: "
                + ", ".join(sorted(header_names))
            )
        if source.is_source:
            raise KsqlException(
                f"Cannot insert values into read-only {'table' if source.is_table() else 'stream'}: "
                f"{s.target}"
            )
        schema = source.schema
        all_cols = list(schema.columns())
        if s.columns:
            cols = []
            for name in s.columns:
                c = schema.find_column(name)
                if c is None and name != "ROWTIME":
                    raise KsqlException(f"Column name {name} does not exist.")
                cols.append(c if c is not None else name)
        else:
            cols = all_cols
        if len(s.values) != len(cols):
            raise KsqlException(
                f"Expected a value for each column. Columns: {len(cols)}, "
                f"values: {len(s.values)}"
            )
        compiler = ExpressionCompiler(TypeResolver({}), self.registry)
        row: Dict[str, Any] = {}
        ts = None
        for c, vexpr in zip(cols, s.values):
            value = compiler.compile(vexpr)({})
            if c == "ROWTIME" or (not isinstance(c, str) and c.name == "ROWTIME"):
                ts = int(value)
                continue
            if value is not None:
                caster = make_caster(compiler.compile(vexpr).sql_type, c.type)
                value = caster(value)
            row[c.name] = value
        import time as _time

        if ts is None:
            ts = int(_time.time() * 1000)
        from ksql_tpu.serde import formats as fmt

        value_serde = fmt.of(
            source.value_format, wrap_single_values=source.wrap_single_values
        )
        key = tuple(row.get(c.name) for c in schema.key_columns)
        payload = value_serde.serialize(
            {c.name: row.get(c.name) for c in schema.value_columns},
            list(schema.value_columns),
        )
        self.broker.create_topic(source.topic)
        self.broker.topic(source.topic).produce(
            Record(key=fmt.serialize_key(source.key_format.format, key, schema.key_columns,
                                         wrapped=source.key_format.wrapped,
                                         delimiter=getattr(source, "key_delimiter", None)),
                   value=payload, timestamp=ts, partition=-1)
        )
        return StatementResult("ok", "Inserted")

    # ------------------------------------------------------------- queries
    def _h_query(self, q: ast.Query, text):
        """Transient query: push (EMIT CHANGES) or pull (no refinement)."""
        if q.refinement is not None and q.refinement.type == ast.RefinementType.CHANGES:
            return self._push_query(q, text)
        return self._pull_query(q, text)

    def _push_query(self, q: ast.Query, text) -> StatementResult:
        query_id = f"transient_{next(self._query_seq)}"
        analysis = analyze_query(q, self.metastore, self.registry)
        planned = self.planner.plan(analysis, query_id)
        rows: List[dict] = []
        limit = q.limit

        source_topics = sorted(
            {step.topic for step in st.walk_steps(planned.plan.physical_plan)
             if hasattr(step, "topic") and not isinstance(step, (st.StreamSink, st.TableSink))}
        )
        consumer = Consumer(self.broker, source_topics)
        out_schema = planned.plan.physical_plan.schema
        columns = [c.name for c in out_schema.key_columns] + [
            c.name for c in out_schema.value_columns
        ]

        def on_emit(e: SinkEmit):
            if limit is not None and len(rows) >= limit:
                return
            row = dict(zip([c.name for c in out_schema.key_columns], e.key))
            if e.row:
                row.update(e.row)
            if e.window is not None:
                row.setdefault("WINDOWSTART", e.window[0])
                row.setdefault("WINDOWEND", e.window[1])
            rows.append(row)

        # transient queries use the same backend seam as persistent ones:
        # device when the plan lowers, oracle otherwise (TransientQueryMetadata
        # runs on the shared runtime in the reference)
        executor = None
        backend = str(self.effective_property(cfg.RUNTIME_BACKEND)).lower()
        if backend != "oracle":
            from ksql_tpu.compiler.jax_expr import DeviceUnsupported
            from ksql_tpu.runtime.device_executor import DeviceExecutor

            device_plan = self._wrap_transient_plan(planned.plan, query_id)
            try:
                executor = DeviceExecutor(
                    device_plan, self.broker, self.registry,
                    on_error=self._on_error, emit_callback=on_emit,
                    batch_size=int(self.config.get(cfg.BATCH_CAPACITY)),
                    per_record=True,  # transient output order is per-record
                    store_capacity=int(self.config.get(cfg.STATE_SLOTS)),
                )
            except DeviceUnsupported:
                pass
            except Exception as e:  # noqa: BLE001
                if backend == "device-only":
                    raise
                self._lowering_failed(
                    "device-lowering", e,
                    self._classify_transient_static(planned.plan),
                )
        if executor is None:
            self.annotate_serde_semantics(planned.plan)
            executor = OracleExecutor(
                planned.plan, self.broker, self.registry,
                on_error=self._on_error, emit_callback=on_emit,
            )
        # synchronous drain (server mode runs this on a thread)
        while True:
            records = consumer.poll()
            if not records:
                break
            for topic, rec in records:
                executor.process(topic, rec)
            drain = getattr(executor, "drain", None)
            if drain is not None:
                drain()
            if limit is not None and len(rows) >= limit:
                break
        return StatementResult("rows", query_id=query_id, rows=rows, columns=columns)

    @staticmethod
    def _pull_key_constraints(where, key_names, key_types):
        """LookupConstraint extraction (PullQueryRewriter/QueryFilterNode):
        when the WHERE clause pins EVERY key column with top-level
        conjunctive equality or IN constraints, return the list of exact
        key tuples to probe; else None (table scan).  The full WHERE still
        runs as a residual filter, so over-approximation is safe."""
        from ksql_tpu.execution import expressions as ex
        from ksql_tpu.serde.formats import _coerce

        if where is None or not key_names:
            return None

        def literal_value(e):
            if isinstance(e, (ex.IntegerLiteral, ex.LongLiteral,
                              ex.DoubleLiteral, ex.BooleanLiteral,
                              ex.StringLiteral)):
                return e.value
            if isinstance(e, ex.NullLiteral):
                return None  # WHERE key = NULL: probes nothing, matches nothing
            if isinstance(e, ex.DecimalLiteral):
                import decimal as _d

                return _d.Decimal(e.text)
            return _NO_LITERAL

        def conjuncts(e):
            if isinstance(e, ex.LogicalBinary) and e.op == ex.LogicOp.AND:
                return conjuncts(e.left) + conjuncts(e.right)
            return [e]

        values = {}  # key col name -> list of candidate values
        for c in conjuncts(where):
            col = vals = None
            if isinstance(c, ex.Comparison) and c.op == ex.CompareOp.EQ:
                for a, b in ((c.left, c.right), (c.right, c.left)):
                    v = literal_value(b)
                    if isinstance(a, ex.ColumnRef) and v is not _NO_LITERAL:
                        col, vals = a.name, [v]
                        break
            elif (isinstance(c, ex.InList) and not c.negated
                  and isinstance(c.value, ex.ColumnRef)):
                items = [literal_value(i) for i in c.items]
                if all(v is not _NO_LITERAL for v in items):
                    col, vals = c.value.name, items
            if col in key_names and col not in values:
                t = key_types[key_names.index(col)]
                try:
                    values[col] = [
                        _coerce(v, t) if v is not None else None for v in vals
                    ]
                except Exception:  # noqa: BLE001 — uncoercible: scan instead
                    return None
        if set(values) != set(key_names):
            return None
        import itertools as _it

        return [
            tuple(combo)
            for combo in _it.product(*(values[n] for n in key_names))
        ]

    def _pull_query(self, q: ast.Query, text) -> StatementResult:
        if not isinstance(q.from_, ast.Table):
            raise KsqlException("Pull queries only support a single source table")
        source_name = q.from_.name
        source = self.metastore.require_source(source_name)
        # find the query materializing this source
        handle = None
        for h in self.queries.values():
            if h.sink_name == source_name:
                handle = h
                break
        if source.is_table() and handle is None:
            raise KsqlException(
                f"Can't pull from {source_name} as it's not a materialized table."
            )
        if source.is_stream():
            raise KsqlException(
                "Pull queries on streams are not supported (use EMIT CHANGES)."
            )
        if not cfg._bool(self.effective_property("ksql.query.pull.enable", True)):
            raise KsqlException("Pull queries are disabled on this server.")
        # staleness gate (ksql.query.pull.max.allowed.offset.lag): a pull
        # against a badly lagging materialization is rejected rather than
        # served stale — standby reads accept the lag instead
        max_lag = int(
            self.effective_property(
                "ksql.query.pull.max.allowed.offset.lag", 9223372036854775807
            )
        )
        if max_lag < 9223372036854775807 and not cfg._bool(
            self.effective_property(cfg.STANDBY_READS, False)
        ):
            from ksql_tpu.common.metrics import consumer_lag

            lag = consumer_lag(handle.consumer)
            if lag > max_lag:
                raise KsqlException(
                    f"Failed to get value from materialized table: lag {lag} "
                    f"exceeds ksql.query.pull.max.allowed.offset.lag {max_lag}."
                )
        schema = source.schema
        types = {c.name: c.type for c in schema.columns()}
        from ksql_tpu.common.schema import WINDOW_BOUNDS

        for n, t in WINDOW_BOUNDS.items():
            types.setdefault(n, t)
        compiler = ExpressionCompiler(TypeResolver(types), self.registry, self._on_error)
        where = compiler.compile(q.where) if q.where is not None else None
        out_rows = []
        key_names = [c.name for c in schema.key_columns]
        # device-backed queries serve pulls from the HBM store itself
        # (KsMaterializedTableIQv2 analog); oracle-backed queries fall back
        # to the host-side materialization shadow
        dev = getattr(handle.executor, "device", None) if handle else None
        if dev is not None and getattr(dev, "store_layout", None) is not None:
            emits = None
            key_types = [c.type for c in schema.key_columns]
            key_tuples = self._pull_key_constraints(q.where, key_names, key_types)
            if key_tuples is not None:
                # keyed fast path: hash-matched slots only (the reference's
                # KeyedTableLookupOperator via LookupConstraint analysis);
                # the full WHERE still runs below as the residual filter
                emits = dev.lookup_store(key_tuples)
            if emits is None:
                emits = dev.scan_store()
            entries = sorted(
                ((e.row, e.window, e.key) for e in emits),
                key=lambda t: repr((t[2], t[1])),
            )
        else:
            entries = [
                (row, win, key)
                for (_hkey, _window), (row, win, key, _ts) in sorted(
                    handle.materialized.items(), key=lambda kv: repr(kv[0])
                )
            ]
        for row, win, key in entries:
            if row is None:
                continue
            full = dict(zip(key_names, key))
            full.update(row)
            if win is not None:
                full["WINDOWSTART"], full["WINDOWEND"] = win
            if where is not None and where(full) is not True:
                continue
            out_rows.append(full)
        # project
        from ksql_tpu.execution import expressions as ex

        star = any(isinstance(item, ast.AllColumns) for item in q.select.items)
        result_rows = []
        if star:
            columns = key_names + (
                ["WINDOWSTART", "WINDOWEND"] if source.key_format.windowed else []
            ) + schema.value_column_names()
            result_rows = [{c: r.get(c) for c in columns} for r in out_rows]
        else:
            sel = []
            columns = []
            for i, item in enumerate(q.select.items):
                expr = item.expression
                if isinstance(expr, ex.ColumnRef) and expr.source is not None:
                    expr = ex.ColumnRef(name=expr.name)
                alias = item.alias or (
                    expr.name if isinstance(expr, ex.ColumnRef) else f"KSQL_COL_{i}"
                )
                columns.append(alias)
                sel.append((alias, compiler.compile(expr)))
            for r in out_rows:
                result_rows.append({a: f(r) for a, f in sel})
        if q.limit is not None:
            result_rows = result_rows[: q.limit]
        return StatementResult("rows", rows=result_rows, columns=columns)

    # ---------------------------------------------------------------- admin
    def _h_drop(self, s: ast.DropSource, text):
        source = self.metastore.get_source(s.name)
        kind = "Table" if s.is_table else "Stream"
        if source is None:
            if s.if_exists:
                return StatementResult("ddl", f"Source {s.name} does not exist.")
            # DropSourceFactory: named by the statement's source kind
            raise KsqlException(f"{kind} {s.name} does not exist.")
        if s.delete_topic and source.is_source:
            raise KsqlException(
                f"Cannot delete topic for read-only source: {s.name}"
            )
        # downstream sources (sinks of queries reading this one) block the
        # drop; the query writing INTO this source terminates implicitly
        # (reference DropSourceFactory referential-integrity semantics)
        downstream = sorted({
            self.queries[qid].sink_name
            for qid in self.metastore.readers_of(s.name)
            if qid in self.queries and self.queries[qid].sink_name
        })
        if downstream:
            raise KsqlException(
                f"Cannot drop {s.name}.\n"
                "The following streams and/or tables read from this source: "
                f"[{', '.join(downstream)}].\n"
                f"You need to drop them before dropping {s.name}."
            )
        for qid in sorted(self.metastore.writers_of(s.name)):
            h = self.queries.pop(qid, None)
            if h is not None:
                h.state = "TERMINATED"
            self._stop_tick_worker(qid)
            self.metastore.remove_query_references(qid)
        self.metastore.delete_source(s.name, check_constraints=False)
        if s.delete_topic:
            self.broker.delete_topic(source.topic)
        return StatementResult("ddl", f"Source {s.name} (topic: {source.topic}) was dropped.")

    def _h_terminate(self, s: ast.TerminateQuery, text):
        ids = [s.query_id] if s.query_id else list(self.queries)
        promoted: List[str] = []
        for qid in ids:
            h = self.queries.get(qid)
            if h is None:
                if s.query_id:
                    raise KsqlException(f"Unknown queryId: {qid}")
                continue
            promoted.extend(self._release_family(qid))
            h.state = "TERMINATED"
            if h.backend == "device":
                self.device_query_count -= 1
            elif h.backend == "distributed":
                self.distributed_query_count -= 1
            self.metastore.remove_query_references(qid)
            self.metrics.remove_query(qid)
            self.trace_recorders.pop(qid, None)
            self._stop_tick_worker(qid)
            del self.queries[qid]
        # members of a terminated primary promote to standalone executors,
        # resuming from their own consumer position with fresh window state
        # (the PR-5 stateful-rebuild posture)
        for m_qid in promoted:
            mh = self.queries.get(m_qid)
            if mh is None or not mh.is_running():
                continue
            try:
                mh.executor = self._build_executor(mh)
            except Exception as e:  # noqa: BLE001 — promotion failure goes
                # through the normal self-healing ladder, not TERMINATE
                self._query_failed(mh, e)
        return StatementResult("ok", f"Terminated {', '.join(ids) if ids else 'nothing'}")

    def _h_pause(self, s: ast.PauseQuery, text):
        for qid in ([s.query_id] if s.query_id else list(self.queries)):
            h = self.queries.get(qid)
            if h is None:
                raise KsqlException(f"Unknown queryId: {qid}")
            h.state = "PAUSED"
        return StatementResult("ok", "Paused")

    def _h_resume(self, s: ast.ResumeQuery, text):
        for qid in ([s.query_id] if s.query_id else list(self.queries)):
            h = self.queries.get(qid)
            if h is None:
                raise KsqlException(f"Unknown queryId: {qid}")
            h.state = "RUNNING"
        return StatementResult("ok", "Resumed")

    def _h_list_streams(self, s, text):
        rows = [
            {"name": d.name, "topic": d.topic, "keyFormat": d.key_format.format,
             "valueFormat": d.value_format, "windowed": d.key_format.windowed}
            for d in self.metastore.all_sources() if d.is_stream()
        ]
        return StatementResult("rows", rows=rows, columns=["name", "topic", "keyFormat", "valueFormat", "windowed"])

    def _h_list_tables(self, s, text):
        rows = [
            {"name": d.name, "topic": d.topic, "keyFormat": d.key_format.format,
             "valueFormat": d.value_format, "windowed": d.key_format.windowed}
            for d in self.metastore.all_sources() if d.is_table()
        ]
        return StatementResult("rows", rows=rows, columns=["name", "topic", "keyFormat", "valueFormat", "windowed"])

    def _h_list_topics(self, s, text):
        rows = [{"name": t} for t in self.broker.list_topics()]
        return StatementResult("rows", rows=rows, columns=["name"])

    def _h_list_queries(self, s, text):
        rows = [
            {"id": h.query_id, "status": h.state, "sink": h.sink_name,
             "backend": h.backend, "health": h.health, "sql": h.sql}
            for h in self.queries.values()
        ]
        return StatementResult(
            "rows", rows=rows,
            columns=["id", "status", "sink", "backend", "health", "sql"],
        )

    def _h_list_properties(self, s, text):
        props = self.config.to_dict()
        props.update(self.session_properties)
        rows = [{"name": k, "value": str(v)} for k, v in sorted(props.items())]
        return StatementResult("rows", rows=rows, columns=["name", "value"])

    def _h_list_functions(self, s, text):
        rows = [{"name": n, "type": t} for n, t in self.registry.list_functions()]
        return StatementResult("rows", rows=rows, columns=["name", "type"])

    def _h_list_types(self, s, text):
        rows = [{"name": n, "schema": str(t)} for n, t in sorted(self.metastore.all_types().items())]
        return StatementResult("rows", rows=rows, columns=["name", "schema"])

    def _h_list_variables(self, s, text):
        rows = [{"name": k, "value": v} for k, v in sorted(self.variables.items())]
        return StatementResult("rows", rows=rows, columns=["name", "value"])

    def _h_show_columns(self, s: ast.ShowColumns, text):
        d = self.metastore.require_source(s.source)
        rows = []
        for c in d.schema.key_columns:
            rows.append({"column": c.name, "type": str(c.type), "key": "KEY"})
        for c in d.schema.value_columns:
            rows.append({"column": c.name, "type": str(c.type), "key": ""})
        message = ""
        if s.extended:
            # DESCRIBE EXTENDED reports the runtime executing the
            # materializing query (reference runtime-statistics section)
            for h in self.queries.values():
                if h.sink_name == d.name:
                    message = f"Runtime: {h.backend}"
                    shards = getattr(
                        getattr(h.executor, "device", None), "n_shards", None
                    )
                    if shards is not None:
                        message += f" (shards={shards})"
                    if h.progress is not None:
                        p = h.progress
                        message += (
                            f" · Health: {p.health} (lag={p.offset_lag}, "
                            f"watermark={p.watermark_ms}, "
                            f"e2e_p99_ms={p.e2e.percentile(0.99)})"
                        )
                    break
        return StatementResult(
            "rows", message, rows=rows, columns=["column", "type", "key"]
        )

    def _h_describe_function(self, s: ast.DescribeFunction, text):
        return StatementResult("ok", self.registry.describe(s.name))

    def _h_explain(self, s: ast.Explain, text):
        if s.query_id is not None:
            h = self.queries.get(s.query_id)
            if h is None:
                raise KsqlException(f"Query with id:{s.query_id} does not exist")
            if getattr(s, "analyze", False):
                return self._explain_analyze(h)
            # running queries report WHICH runtime executes the plan (the
            # reference's EXPLAIN shows the physical Streams topology)
            runtime = f"Runtime: {h.backend}"
            dev = getattr(h.executor, "device", None)
            shards = getattr(dev, "n_shards", None)
            if shards is not None:
                runtime += f" (shards={shards})"
            wline = self._windowing_line(h)
            if wline:
                runtime += "\n" + wline
            oline = self._optimizer_line(h)
            if oline:
                runtime += "\n" + oline
            # the ahead-of-time decision next to the live one: agreement is
            # the plan-verifier contract (tested over the golden corpus);
            # divergence means the runtime hit a non-plan failure (OOM,
            # compile error) classification cannot see
            try:
                static = self._classify_plan_static(h.plan, handle=h).format()
            except Exception as e:  # noqa: BLE001 — EXPLAIN must not fail
                static = f"Backend (static): unavailable ({e})"
            static += "\n" + self._memory_line(h.plan, handle=h)
            return StatementResult(
                "ok",
                runtime + "\n" + static + "\n"
                + st.format_plan(h.plan.physical_plan),
            )
        if getattr(s, "analyze", False):
            raise KsqlException(
                "EXPLAIN ANALYZE requires a running query id (it reports "
                "the flight recorder's per-stage measurements, not a plan)."
            )
        inner = s.statement
        if isinstance(inner, ast.Query):
            analysis = analyze_query(inner, self.metastore, self.registry)
            planned = self.planner.plan(analysis, "EXPLAIN")
            from ksql_tpu.analysis import verify_plan

            lines = []
            try:
                lines.append(
                    self._classify_transient_static(planned.plan).format()
                )
            except Exception as e:  # noqa: BLE001 — EXPLAIN must not fail
                lines.append(f"Backend (static): unavailable ({e})")
            lines.append(
                self._memory_line(
                    self._wrap_transient_plan(planned.plan, "explain")
                )
            )
            try:
                violations = verify_plan(planned.plan)
            except Exception as e:  # noqa: BLE001 — EXPLAIN must not fail
                violations = []
                lines.append(f"Plan verification unavailable ({e})")
            for v in violations:
                lines.append(f"Plan violation: {v.format()}")
            lines.append(st.format_plan(planned.plan.physical_plan))
            return StatementResult("ok", "\n".join(lines))
        raise KsqlException("EXPLAIN supports queries only")

    def _memory_line(self, plan, handle: Optional[QueryHandle] = None) -> str:
        """EXPLAIN's ``Device memory (static)`` component table: the
        memory model's per-component at-creation / at-growth-cap bytes
        (per shard), memoized on the handle for running queries.  Plans
        that never reach the device report n/a — they hold no HBM."""
        try:
            report = handle.mem_report if handle is not None else None
            if report is None:
                report = self._memory_report_static(plan)
                if handle is not None:
                    handle.mem_report = report
            if report is None:
                return (
                    "Device memory (static): n/a (plan does not run on "
                    "the device backend)"
                )
            return report.format_table()
        except Exception as e:  # noqa: BLE001 — EXPLAIN must not fail
            return f"Device memory (static): unavailable ({e})"

    def _windowing_line(self, h: QueryHandle) -> Optional[str]:
        """The live windowing shape of a running hopping aggregation:
        sliced (with slice width / ring / hop fan-out and any family
        members sharing the pipeline) or expansion (with the reason it
        could not slice)."""
        from ksql_tpu.runtime.device_executor import FamilyMemberExecutor

        ex_ = h.executor
        if isinstance(ex_, FamilyMemberExecutor):
            prim = self.queries.get(ex_.primary_query_id)
            dev = getattr(getattr(prim, "executor", None), "device", None)
            if dev is None or not getattr(dev, "sliced", False):
                return None  # source-prefix member: no windowing to report
            return (
                f"Windowing: sliced (width={dev.slice_width}ms, "
                f"shared with {ex_.primary_query_id})"
            )
        dev = getattr(ex_, "device", None)
        if dev is None:
            return None
        if getattr(dev, "sliced", False):
            line = (
                f"Windowing: sliced (width={dev.slice_width}ms, "
                f"ring={dev.slice_ring}, k={dev.hop_k}"
            )
            shared = dev.shared_member_ids()
            if shared:
                line += f", shared with {', '.join(sorted(shared))}"
            return line + ")"
        wf = getattr(dev, "windowing_fallback", None)
        if wf:
            return (
                f"Windowing: expansion (k={getattr(dev, 'hop_k', 1)}): {wf}"
            )
        return None

    def _optimizer_line(self, h: QueryHandle) -> Optional[str]:
        """EXPLAIN's ``Optimizer`` section: the multi-query optimizer's
        cost decision for this query plus — when it shares a pipeline —
        the shared-plan DAG (source -> shared stage -> every member's
        combine/residual -> sink), rendered identically whether EXPLAIN
        targets the primary or a member."""
        from ksql_tpu.runtime.device_executor import FamilyMemberExecutor

        dec = getattr(h, "mqo_decision", None)
        ex_ = h.executor
        lines: List[str] = []
        if isinstance(ex_, FamilyMemberExecutor):
            prim_qid = ex_.primary_query_id
            prim = self.queries.get(prim_qid)
            dev = getattr(getattr(prim, "executor", None), "device", None)
            kind = (
                "window-family" if getattr(dev, "sliced", False)
                else "source-prefix"
            )
            lines.append(
                f"Optimizer: member of shared {kind} pipeline "
                f"(primary={prim_qid})"
            )
            if dec is not None:
                lines.append("  " + dec.format())
            if dev is not None:
                lines.extend(self._shared_dag_lines(prim_qid, dev))
        else:
            dev = getattr(ex_, "device", None)
            members = []
            if dev is not None:
                members = list(getattr(dev, "shared_member_ids", list)())
                members += list(
                    getattr(dev, "shared_prefix_member_ids", list)()
                )
            if members:
                lines.append(
                    f"Optimizer: shared-pipeline primary "
                    f"({1 + len(members)} queries share this pipeline)"
                )
                lines.extend(self._shared_dag_lines(h.query_id, dev))
            elif dec is not None and not dec.share:
                lines.append("Optimizer: " + dec.format())
        return "\n".join(lines) if lines else None

    def _shared_dag_lines(self, prim_qid: str, dev) -> List[str]:
        """The shared-plan DAG EXPLAIN prints under ``Optimizer``."""
        out: List[str] = []
        topic = getattr(getattr(dev, "source", None), "topic", "?")
        if getattr(dev, "sliced", False):
            out.append(
                f"  shared DAG: source {topic} -> scan/filter/project -> "
                f"slice-ring[width={dev.slice_width}ms "
                f"ring={dev.slice_ring} "
                f"partials={len(dev.agg_specs)}]"
            )
            for m in dev.members:
                qid = m.query_id or prim_qid
                n_aggs = len(
                    m.agg_map if m.agg_map is not None else dev.agg_specs
                )
                out.append(
                    f"    -> combine[size={m.size_ms}ms "
                    f"advance={m.advance_ms}ms aggs={n_aggs}] -> {qid}"
                )
        else:
            shared_n = getattr(dev, "_prefix_shared_len", 0)
            out.append(
                f"  shared DAG: source {topic} -> shared "
                f"prefix[{shared_n} op(s)]"
            )
            out.append(
                f"    -> residual[{len(dev.pre_ops) - shared_n} op(s)] "
                f"-> {prim_qid}"
            )
            for m in dev.prefix_members:
                out.append(
                    f"    -> residual[{len(m.pre_ops) - shared_n} op(s)] "
                    f"-> {m.query_id}"
                )
        return out

    def _explain_analyze(self, h: QueryHandle) -> StatementResult:
        """EXPLAIN ANALYZE <query_id>: the flight recorder's per-stage
        p50/p99 breakdown over the ring window — poll/deserialize/
        per-ExecutionStep stages, the device compile-vs-execute split (with
        jit hit/miss counts), host<->device transfer bytes, distributed
        exchange rows/bytes, and sink produce."""
        import json as _json

        rec = self.trace_recorders.get(h.query_id)
        stats = rec.stage_stats() if rec is not None else {}
        runtime = f"Runtime: {h.backend}"
        dev = getattr(h.executor, "device", None)
        shards = getattr(dev, "n_shards", None)
        if shards is not None:
            runtime += f" (shards={shards})"
        window = rec.window_ticks() if rec is not None else 0
        msg = f"{runtime} · flight recorder window: {window} ticks"
        if not self.trace_enabled:
            msg += " · tracing disabled (ksql.trace.enable=false)"
        rows = []
        for name in sorted(stats, key=tracing.stage_sort_key):
            st_ = stats[name]
            extra = {
                k: v for k, v in st_.items()
                if k not in ("n", "ticks", "p50_ms", "p99_ms", "total_ms")
            }
            rows.append({
                "stage": name,
                "count": st_["n"],
                # a counter-only stage (never timed) has no time to show
                "p50Ms": st_.get("p50_ms"),
                "p99Ms": st_.get("p99_ms"),
                "totalMs": st_.get("total_ms"),
                "extra": _json.dumps(extra, sort_keys=True) if extra else "",
            })
        return StatementResult(
            "rows", msg, rows=rows,
            columns=["stage", "count", "p50Ms", "p99Ms", "totalMs", "extra"],
        )

    def _h_set(self, s: ast.SetProperty, text):
        self.session_properties[s.name] = s.value
        return StatementResult("ok", f"Property {s.name} set to {s.value}")

    def _h_unset(self, s: ast.UnsetProperty, text):
        self.session_properties.pop(s.name, None)
        return StatementResult("ok", f"Property {s.name} unset")

    def _h_define(self, s: ast.DefineVariable, text):
        self.variables[s.name] = s.value
        return StatementResult("ok", f"Variable {s.name} defined")

    def _h_undefine(self, s: ast.UndefineVariable, text):
        self.variables.pop(s.name, None)
        return StatementResult("ok", f"Variable {s.name} undefined")

    def _h_alter_system(self, s: ast.AlterSystemProperty, text):
        """ALTER SYSTEM 'prop'='value': mutate the server-level default
        (KsqlResource's ALTER SYSTEM path via KsqlConfig; session SET still
        overrides it).  Only recognized ksql.* keys are alterable."""
        from ksql_tpu.common.config import _DEFS

        if s.name not in _DEFS:
            raise KsqlException(
                f"Unknown property: '{s.name}'. ALTER SYSTEM accepts only "
                "known ksql server properties."
            )
        self.config._props[s.name] = self.config._coerce(s.name, s.value)
        return StatementResult("ok", f"System property {s.name} set to {s.value}")

    def _h_alter_source(self, s: ast.AlterSource, text):
        """ALTER STREAM|TABLE ... ADD COLUMN: append value columns to the
        registered schema (AlterSourceFactory.java:45 validations +
        DdlCommandExec.executeAlterSource semantics).  Running queries keep
        the schema they planned against."""
        kind = "TABLE" if s.is_table else "STREAM"
        source = self.metastore.get_source(s.name)
        if source is not None and source.is_source:
            raise KsqlException(
                f"Cannot alter {kind.lower()} '{s.name}': ALTER operations "
                f"are not supported on source {kind.lower()}s."
            )
        if source is None:
            raise KsqlException(f"Source {s.name} does not exist.")
        if source.source_type != kind:
            raise KsqlException(
                f"Incompatible data source type is {source.source_type}, "
                f"but statement was ALTER {kind}"
            )
        if source.is_cas_target:
            raise KsqlException(
                "ALTER command is not supported for CREATE ... AS statements."
            )
        b = LogicalSchema.builder()
        for c in source.schema.key_columns:
            b.key_column(c.name, c.type)
        existing = {c.name for c in source.schema.columns()}
        for c in source.schema.value_columns:
            b.value_column(c.name, c.type)
        for el in s.new_columns:
            if el.name in existing:
                raise KsqlException(
                    f"Cannot add column `{el.name}` to schema. A column with "
                    "the same name already exists."
                )
            existing.add(el.name)
            b.value_column(el.name, el.type)
        self.metastore.put_source(
            dataclasses.replace(
                source, schema=b.build(),
                sql_expression=(source.sql_expression + "\n" + text).strip(),
            ),
            allow_replace=True,
        )
        return StatementResult("ddl", f"{kind} {s.name} altered.")

    # ----------------------------------------------------------- connectors
    @property
    def _connect_client(self):
        from ksql_tpu.services.connect import ConnectClient, client_for

        url = str(self.config.get("ksql.connect.url") or "")
        cached = self.__dict__.get("_connect_client_cached")
        if cached is None or cached[0] != url:
            # sandbox validation must not touch a real Connect cluster
            # (Sandboxed* service mirror): validate-only in-process client.
            # keyed by url so ALTER SYSTEM 'ksql.connect.url' takes effect
            c = ConnectClient() if self.is_sandbox else client_for(self.config)
            cached = self.__dict__["_connect_client_cached"] = (url, c)
        return cached[1]

    def _h_create_connector(self, s: ast.CreateConnector, text):
        """CREATE SOURCE|SINK CONNECTOR (ConnectExecutor.java:48): validate
        config, register through the Connect seam, record in the metastore
        registry for LIST/DESCRIBE/DROP."""
        from ksql_tpu.metastore.metastore import ConnectorInfo

        name = s.name
        if self.metastore.get_connector(name) is not None:
            if s.if_not_exists:
                return StatementResult(
                    "ok", f"Connector {name} already exists"
                )
            raise KsqlException(f"Connector {name} already exists")
        props = {str(k): str(v) for k, v in (s.properties or {}).items()}
        self._connect_client.create(name, props)
        self.metastore.put_connector(ConnectorInfo(
            name=name,
            connector_type=s.connector_type.upper(),
            properties=tuple(sorted(props.items())),
        ))
        return StatementResult("ok", f"Created connector {name}")

    def _h_drop_connector(self, s: ast.DropConnector, text):
        if self.metastore.get_connector(s.name) is None:
            if s.if_exists:
                return StatementResult("ok", f"Connector {s.name} does not exist.")
            raise KsqlException(f"Connector {s.name} does not exist.")
        self._connect_client.delete(s.name)
        self.metastore.drop_connector(s.name)
        return StatementResult("ok", f"Dropped connector {s.name}")

    def _h_list_connectors(self, s: ast.ListConnectors, text):
        rows = [
            {
                "name": c.name,
                "type": c.connector_type,
                "className": c.connector_class,
                "state": self._connect_client.status(c.name),
            }
            for c in self.metastore.list_connectors()
            if s.scope in ("ALL", c.connector_type)
        ]
        return StatementResult(
            "rows", rows=rows, columns=["name", "type", "className", "state"]
        )

    def _h_describe_connector(self, s: ast.DescribeConnector, text):
        c = self.metastore.get_connector(s.name)
        if c is None:
            raise KsqlException(f"Connector {s.name} does not exist.")
        rows = [{
            "name": c.name,
            "type": c.connector_type,
            "className": c.connector_class,
            "state": self._connect_client.status(c.name),
            "properties": dict(c.properties),
        }]
        return StatementResult(
            "rows", rows=rows,
            columns=["name", "type", "className", "state", "properties"],
        )

    def _h_register_type(self, s: ast.RegisterType, text):
        created = self.metastore.register_type(s.name, s.type, s.if_not_exists)
        return StatementResult("ddl", "Type registered" if created else "Type already exists")

    def _h_drop_type(self, s: ast.DropType, text):
        self.metastore.drop_type(s.name, s.if_exists)
        return StatementResult("ddl", "Type dropped")

    def _h_print(self, s: ast.PrintTopic, text):
        topic = self.broker.topic(s.topic)
        records = topic.all_records()
        if s.limit is not None:
            records = records[: s.limit]
        rows = [
            {"partition": r.partition, "offset": r.offset, "timestamp": r.timestamp,
             "key": r.key, "value": r.value}
            for r in records
        ]
        return StatementResult("rows", rows=rows,
                               columns=["partition", "offset", "timestamp", "key", "value"])

    _HANDLERS: Dict[type, Callable] = {}


KsqlEngine._MUTATING = (
    ast.CreateStream,
    ast.CreateTable,
    ast.CreateStreamAsSelect,
    ast.CreateTableAsSelect,
    ast.InsertInto,
    ast.InsertValues,
    ast.DropSource,
    ast.RegisterType,
    ast.DropType,
    ast.AlterSource,
    ast.CreateConnector,
    ast.DropConnector,
)

KsqlEngine._HANDLERS = {
    ast.CreateStream: KsqlEngine._h_create_stream,
    ast.CreateTable: KsqlEngine._h_create_table,
    ast.CreateStreamAsSelect: KsqlEngine._h_csas,
    ast.CreateTableAsSelect: KsqlEngine._h_ctas,
    ast.InsertInto: KsqlEngine._h_insert_into,
    ast.InsertValues: KsqlEngine._h_insert_values,
    ast.Query: KsqlEngine._h_query,
    ast.DropSource: KsqlEngine._h_drop,
    ast.TerminateQuery: KsqlEngine._h_terminate,
    ast.PauseQuery: KsqlEngine._h_pause,
    ast.ResumeQuery: KsqlEngine._h_resume,
    ast.ListStreams: KsqlEngine._h_list_streams,
    ast.ListTables: KsqlEngine._h_list_tables,
    ast.ListTopics: KsqlEngine._h_list_topics,
    ast.ListQueries: KsqlEngine._h_list_queries,
    ast.ListProperties: KsqlEngine._h_list_properties,
    ast.ListFunctions: KsqlEngine._h_list_functions,
    ast.ListTypes: KsqlEngine._h_list_types,
    ast.ListVariables: KsqlEngine._h_list_variables,
    ast.ShowColumns: KsqlEngine._h_show_columns,
    ast.DescribeFunction: KsqlEngine._h_describe_function,
    ast.Explain: KsqlEngine._h_explain,
    ast.SetProperty: KsqlEngine._h_set,
    ast.UnsetProperty: KsqlEngine._h_unset,
    ast.DefineVariable: KsqlEngine._h_define,
    ast.UndefineVariable: KsqlEngine._h_undefine,
    ast.RegisterType: KsqlEngine._h_register_type,
    ast.DropType: KsqlEngine._h_drop_type,
    ast.PrintTopic: KsqlEngine._h_print,
    ast.AlterSource: KsqlEngine._h_alter_source,
    ast.AlterSystemProperty: KsqlEngine._h_alter_system,
    ast.CreateConnector: KsqlEngine._h_create_connector,
    ast.DropConnector: KsqlEngine._h_drop_connector,
    ast.ListConnectors: KsqlEngine._h_list_connectors,
    ast.DescribeConnector: KsqlEngine._h_describe_connector,
}
