"""Multi-chip execution: shard_map over the mesh, all-to-all repartition,
device-sharded keyed state.

Layout (SURVEY §2.3 mapping):
* data parallelism — incoming micro-batches carry a leading [n_shards] axis
  split across devices (the Kafka-partition analog);
* shuffle — rows cross to the shard owning their key via one ICI all-to-all
  (parallel/repartition.py), replacing the repartition topic;
* state sharding — every store array carries the same leading axis, so each
  device owns the hash-range of keys that route to it (co-partitioned state,
  exactly Kafka Streams' task/store ownership);
* stream time is per state shard, matching the reference's per-task stream
  time semantics.

Stateless pipelines skip the exchange (pure DP) — the analog of a filter/
project query with no repartition topic.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ksql_tpu.common import faults, tracing
from ksql_tpu.common.batch import HostBatch
from ksql_tpu.compiler.jax_expr import DeviceUnsupported
from ksql_tpu.parallel.mesh import SHARD_AXIS
from ksql_tpu.parallel.repartition import all_to_all_exchange, shard_of
from ksql_tpu.runtime.lowering import (
    CompiledDeviceQuery, _session_work_of, _sliced_lanes_of, _topk_of,
)
from ksql_tpu.runtime.oracle import SinkEmit


def _take_rows(batch: HostBatch, sel: np.ndarray) -> HostBatch:
    """Row-subset view of a host batch (round-robin lanes, table chunks)."""
    return HostBatch(
        schema=batch.schema,
        num_rows=len(sel),
        columns={k: v[sel] for k, v in batch.columns.items()},
        valid={k: v[sel] for k, v in batch.valid.items()},
        timestamps=batch.timestamps[sel],
        partitions=None if batch.partitions is None else batch.partitions[sel],
        offsets=None if batch.offsets is None else batch.offsets[sel],
    )


class DistributedDeviceQuery:
    """A CompiledDeviceQuery executed across a device mesh.

    Beyond the library stepping API (process/process_table/process_ss) this
    also implements the executor-facing host surface DeviceExecutor drives —
    flush/ss_expire_host/flush_pipeline, sharded pull-query serving
    (scan_store / lookup_store routed by ``shard_of(key)``), and per-shard
    runtime stats — so the engine's backend seam can treat a mesh exactly
    like one device.  Attributes not defined here delegate to the wrapped
    CompiledDeviceQuery (plan analysis, layouts, sizing)."""

    #: distributed stepping has no host-side emission pipelining — emits
    #: decode at each sharded step (the all-to-all is the latency hider)
    pipeline = False

    def __init__(
        self,
        compiled: CompiledDeviceQuery,
        mesh: Mesh,
        bucket_capacity: Optional[int] = None,
    ):
        if compiled.suppress:
            raise DeviceUnsupported(
                "EMIT FINAL is not yet distributed (per-shard flush pending); "
                "run it single-device or on the row oracle"
            )
        # stream-stream joins distribute: both sides exchange to the shard
        # owning their join key, whose local ring buffers hold that key's
        # WITHIN-window state (see _build_ss below)
        if len(compiled.join_chain) > 1:
            raise DeviceUnsupported(
                "distributed n-way stream-table join chains pending; run "
                "them single-device"
            )
        if getattr(compiled, "_needs_seq", False):
            raise DeviceUnsupported(
                "distributed EARLIEST/LATEST pending (needs a global arrival "
                "sequence across shards); run them single-device"
            )
        self.c = compiled
        self.mesh = mesh
        self.n_shards = int(np.prod(mesh.devices.shape))
        # capacity × window-expansion is the always-safe bound (a batch that
        # hashes entirely to one shard still fits); production tuning
        # shrinks it and watches the overflow counter
        self.bucket_capacity = bucket_capacity or (
            compiled.capacity * compiled.expansion
        )
        nd = self.n_shards
        # per-shard runtime stats (cumulative; occupancy is last-observed) —
        # surfaced through /metrics by DistributedDeviceExecutor
        self.shard_rows_in = np.zeros(nd, np.int64)
        self.shard_rows_out = np.zeros(nd, np.int64)
        self.shard_exchange_rows = np.zeros(nd, np.int64)
        self.shard_store_occupancy = np.zeros(nd, np.int64)
        # per-shard event-time watermark (max record timestamp a shard's
        # lane ingested; -1 = nothing yet) — folds to the per-query
        # watermark in /query-lag and spots a starved/skewed lane
        self.shard_watermark_ms = np.full(nd, -1, np.int64)
        self.last_pull_slots_decoded = 0
        self.shards_touched_last_pull: List[int] = []
        # bytes one row of each step's all-to-all payload takes, by step
        # ("" the keyed step, "l" / "r" a stream-stream join's sides):
        # noted from the payload's own arrays when the step is traced
        # (_exchange), so a step that has not run yet has none
        self._exch_row_bytes: Dict[str, int] = {}
        self._qid = str(getattr(compiled.plan, "query_id", "") or "")
        # suspect-shard marker: set while a shard lane's host-side dispatch
        # section runs, cleared when the per-shard section completes.  A
        # hang wedged inside the ``mesh.shard.dispatch`` seam leaves it
        # set, so the tick-deadline watchdog can attribute the blown
        # deadline to the exact lane (engine mesh fault-domain containment)
        self.current_shard: Optional[int] = None
        self._build_steps()
        self.state = self.init_state()

    def _shard_fault_point(self, shard: int) -> None:
        """Per-shard-lane chaos seam (``mesh.shard.dispatch``, context
        ``<qid>#<shard>#`` so a rule can target one lane).  A raise is
        stamped with ``mesh_shard`` so the engine's strike bookkeeping can
        contain the failure to this shard; a hang sleeps with
        ``current_shard`` still set for the same attribution."""
        self.current_shard = shard
        try:
            faults.fault_point(
                "mesh.shard.dispatch", f"{self._qid}#{shard}#"
            )
        except Exception as e:  # noqa: BLE001 — annotate + re-raise
            e.mesh_shard = shard
            raise

    def jit_cache_entries(self) -> int:
        """Sharded-step jit cache entries + the wrapped compiled query's —
        the executor's compile-vs-execute split samples this around each
        device call (see DeviceExecutor._device_step)."""
        fns = [
            self.__dict__.get("_step"),
            self.__dict__.get("_ss_expire"),
            self.__dict__.get("_table_step"),
            self.__dict__.get("_evict"),
        ]
        fns.extend((self.__dict__.get("_ss_steps") or {}).values())
        return self.c.jit_cache_entries() + tracing.jit_cache_size(fns)

    def _exchange(self, payload, dest, step: str = ""):
        """One step's all-to-all (called while the step is traced): the
        received payload, the rows a full bucket turned away, the rows
        this shard received.  Notes the payload's row width for
        ``_account``: shapes and dtypes are static, nothing is read."""
        self._exch_row_bytes[step] = sum(
            v.dtype.itemsize * int(np.prod(v.shape[1:]))
            for v in payload.values()
        )
        recv, ovf = all_to_all_exchange(
            payload, dest, self.n_shards, self.bucket_capacity
        )
        return recv, ovf, jnp.sum(recv["active"].astype(jnp.int64))

    def __getattr__(self, name: str):
        # executor-facing delegation: anything not distributed-specific
        # reads through to the wrapped compiled query
        c = self.__dict__.get("c")
        if c is None or name.startswith("_"):
            raise AttributeError(name)
        return getattr(c, name)

    @property
    def capacity(self) -> int:
        """Host micro-batch capacity: the mesh absorbs ``n_shards`` lanes of
        the compiled per-shard capacity per step."""
        return self.n_shards * self.c.capacity

    def _build_steps(self) -> None:
        """(Re)build the jitted shard_map steps — also called by checkpoint
        restore after store capacities change."""
        compiled = self.c
        mesh = self.mesh
        nd = self.n_shards
        import jax.tree_util as jtu

        def strip(tree):
            return jtu.tree_map(lambda v: v[0], tree)

        def add_axis(tree):
            return jtu.tree_map(lambda v: v[None], tree)

        def local_step(state, arrays):
            state = strip(state)
            arrays = strip(arrays)
            if self.c.agg is None:
                state, emits = self.c._trace_step(state, arrays)
                emits["exch_rows"] = jnp.zeros((), jnp.int64)
            elif self.c.session:
                # SESSION windows: same exchange discipline as fixed
                # windows — per-row phase locally, rows cross to the shard
                # owning their key, the interval-merge runs shard-local
                payload = self.c.pre_session_exchange(state["max_ts"], arrays)
                dest = shard_of(payload["khash"], nd)
                recv, ovf, exch = self._exchange(payload, dest)
                state, emits = self.c.post_session_exchange(state, recv)
                state["overflow"] = state["overflow"] + ovf
                emits["overflow"] = state["overflow"]
                emits["exch_rows"] = exch
            else:
                payload = self.c.pre_exchange(
                    state["max_ts"], arrays,
                    jtabs=(
                        self.c._jtabs_of(state) if self.c.join_chain else None
                    ),
                )
                dest = shard_of(payload["khash"], nd)
                recv, ovf, exch = self._exchange(payload, dest)
                state, emits = self.c.post_exchange(state, recv)
                # fold exchange overflow in before emits surface it, so the
                # batch that dropped rows is the batch that reports them
                state["overflow"] = state["overflow"] + ovf
                emits["overflow"] = state["overflow"]
                emits["exch_rows"] = exch
            return add_axis(state), add_axis(emits)

        def build_step():
            # sessions stay undonated: a sess_ovf retry re-runs the same
            # state after growing session_slots (mirrors the single-device
            # process_arrays retry loop)
            return jax.jit(
                shard_map(
                    local_step,
                    mesh=mesh,
                    in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                    out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                ),
                donate_argnums=() if compiled.session else (0,),
            )

        self._build_step = build_step
        self._step = None
        if compiled.ss_join is None:
            self._step = build_step()

        if compiled.ss_join is not None:
            # per-side sharded ss-join step: route rows by join-key hash,
            # then run the ordinary buffer step shard-local (the trace is
            # shape-generic over the received width)
            def make_ss(side):
                trace = (
                    self.c._trace_ss_l if side == "l" else self.c._trace_ss_r
                )

                def local_ss(state, arrays):
                    state = strip(state)
                    arrays = strip(arrays)
                    khash, active = self.c.ss_routing_hash(side, arrays)
                    dest = shard_of(khash, nd)
                    payload = dict(arrays)
                    # only rows surviving this side's pre-op filters cross
                    # the ICI — dropped rows must not burn bucket slots;
                    # 'active' replaces (not duplicates) the row_valid lane
                    payload["active"] = payload.pop("row_valid") & active
                    # ...but every ingested row's timestamp still advances
                    # stream time everywhere (single-device cm_global/smax
                    # advance from pre-filter row_valid rows): pmax the
                    # batch max across shards and fold it in post-step
                    neg = jnp.asarray(np.iinfo(np.int64).min, jnp.int64)
                    batch_max = jnp.max(
                        jnp.where(arrays["row_valid"], arrays["ts"], neg)
                    )
                    gmax = jax.lax.pmax(batch_max, SHARD_AXIS)
                    recv, ovf, exch = self._exchange(payload, dest, side)
                    recv["row_valid"] = recv.pop("active")
                    state, emits = trace(state, recv)
                    state["max_ts"] = jnp.maximum(state["max_ts"], gmax)
                    smax_key = f"ss{side}_smax"
                    state[smax_key] = jnp.maximum(state[smax_key], gmax)
                    emits["ss_exch_ovf"] = ovf
                    emits["exch_rows"] = exch
                    return add_axis(state), add_axis(emits)

                return jax.jit(
                    shard_map(
                        local_ss,
                        mesh=mesh,
                        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                    ),
                    donate_argnums=0,
                )

            def local_ss_expire(state):
                state, emits = self.c._trace_ss_expire(strip(state))
                return add_axis(state), add_axis(emits)

            self._ss_steps = {"l": make_ss("l"), "r": make_ss("r")}
            self._ss_expire = jax.jit(
                shard_map(
                    local_ss_expire,
                    mesh=mesh,
                    in_specs=(P(SHARD_AXIS),),
                    out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                ),
                donate_argnums=0,
            )

        if compiled.join is not None:
            # the join table store is REPLICATED: every shard folds the same
            # full table batch into its local copy (broadcast changelog —
            # the GlobalKTable analog), so stream-side probes stay local and
            # no join-key exchange is needed.  The batch ships pre-stacked
            # [n_shards, ...] (one identical lane per shard) so every array
            # entering the trace is device-varying
            def local_table_step(state, arrays):
                state, emits = self.c._trace_table_step(
                    strip(state), strip(arrays)
                )
                return add_axis(state), add_axis(emits)

            self._table_step = jax.jit(
                shard_map(
                    local_table_step,
                    mesh=mesh,
                    in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                    out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                ),
                donate_argnums=0,
            )

        def local_evict(state):
            state = self.c._trace_evict(strip(state))
            return add_axis(state)

        self._evict = jax.jit(
            shard_map(
                local_evict,
                mesh=mesh,
                in_specs=(P(SHARD_AXIS),),
                out_specs=P(SHARD_AXIS),
            ),
            donate_argnums=0,
        )

    def init_state(self) -> Dict[str, jnp.ndarray]:
        import jax.tree_util as jtu

        base = self.c.init_state()
        spec = NamedSharding(self.mesh, P(SHARD_AXIS))
        return jtu.tree_map(
            lambda v: jax.device_put(
                jnp.broadcast_to(v[None], (self.n_shards,) + v.shape), spec
            ),
            base,
        )

    def device_state_bytes(self) -> Dict[str, int]:
        """PER-SHARD live state bytes per memory-model component (the
        leading ``[n_shards]`` axis divided out), matching the model's
        per-shard report point — total device bytes are ``n_shards x``
        these.  Same single classification loop as the single-device
        seam (analysis/mem_model.measure_state_bytes)."""
        from ksql_tpu.analysis.mem_model import measure_state_bytes

        return {
            comp: b // self.n_shards
            for comp, b in measure_state_bytes(
                self.state, sliced=self.c.sliced
            ).items()
        }

    def process_table(
        self,
        batch: HostBatch,
        deletes: Optional[np.ndarray] = None,
        idx: int = -1,
    ) -> None:
        """Fold one table-changelog batch into every shard's replica.
        ``idx`` matches the executor's join-chain routing signature — only
        single-probe chains distribute, so it is accepted and ignored."""
        if faults.armed():
            # the broadcast changelog folds into EVERY shard's replica:
            # each lane is a dispatch seam (a one-lane rule models one
            # replica's fold failing)
            faults.fault_point("mesh.encode", self._qid)
            for d in range(self.n_shards):
                self._shard_fault_point(d)
            self.current_shard = None
        cap = self.c.capacity
        for start in range(0, max(batch.num_rows, 1), cap):
            sel = np.arange(start, min(start + cap, batch.num_rows))
            hb = _take_rows(batch, sel) if batch.num_rows > cap else batch
            arrays = self.c.table_layout.encode(hb)
            pad = np.zeros(cap, bool)
            if deletes is not None:
                chunk_del = np.asarray(deletes)[sel]
                pad[: len(chunk_del)] = chunk_del
            arrays["delete"] = pad
            # one identical lane per shard (broadcast changelog)
            nd = self.n_shards
            arrays = {
                k: np.ascontiguousarray(
                    np.broadcast_to(v[None], (nd,) + np.asarray(v).shape)
                )
                for k, v in arrays.items()
            }
            with tracing.span("step.dispatch"):
                tracing.counter(
                    "step.dispatch",
                    h2d_bytes=int(sum(v.nbytes for v in arrays.values())),
                )
                self.state, metrics = self._table_step(self.state, arrays)
            with tracing.span("step.wait", wait=True):
                # one blocking read of the load scalars; every replica folds
                # the same batch, so the slowest sets the pace
                load = {
                    k: int(np.asarray(v).max())
                    for k, v in jax.device_get(metrics).items()
                }
            tracing.counter(
                "table.upsert", rows=hb.num_rows, steps=1,
                probe_rounds=load["probe_rounds"],
                probe_lane_rounds=load["probe_lane_rounds"],
            )
        occ = load["occupancy"]
        if occ > 0.6 * self.c.table_store_capacity:
            raise RuntimeError(
                "replicated join-table store nearing capacity "
                f"({occ}/{self.c.table_store_capacity}); restart with a "
                "larger table_store_capacity"
            )

    # ------------------------------------------------------------- host API
    def encode(self, batch: HostBatch, layout=None) -> Dict[str, np.ndarray]:
        """Split one host batch round-robin across shards and stack to the
        [n_shards, capacity] layout."""
        nd = self.n_shards
        layout = layout or self.c.layout
        armed = faults.armed()
        if armed:
            faults.fault_point("mesh.encode", self._qid)
        ts = np.asarray(batch.timestamps) if batch.num_rows else None
        stacked: Dict[str, List[np.ndarray]] = {}
        for d in range(nd):
            if armed:
                self._shard_fault_point(d)
            sel = np.arange(d, batch.num_rows, nd)
            self.shard_rows_in[d] += len(sel)
            if ts is not None and len(sel):
                self.shard_watermark_ms[d] = max(
                    self.shard_watermark_ms[d], int(ts[sel].max())
                )
            arrays = layout.encode(_take_rows(batch, sel))
            for k, v in arrays.items():
                stacked.setdefault(k, []).append(v)
        if armed:
            # lane split complete: later failures in this tick (exchange,
            # XLA step) are whole-mesh, not attributable to the last lane
            self.current_shard = None
        out = {k: np.stack(vs) for k, vs in stacked.items()}
        tracing.counter(
            "step.dispatch",
            h2d_bytes=int(sum(v.nbytes for v in out.values())),
        )
        return out

    def _account(self, emits: Dict[str, jnp.ndarray], step: str = "") -> None:
        """Fold one sharded step's emits (``step`` as ``_exchange`` was
        told) into the per-shard stat gauges."""
        if faults.armed():
            # whole-collective seam: the all-to-all is fused inside the
            # jitted step, so its host boundary is this accounting pass —
            # a raise here is NOT shard-attributable (ordinary ladder)
            faults.fault_point("mesh.exchange", self._qid)
        nd = self.n_shards
        if "emit_mask" in emits:
            self.shard_rows_out += (
                np.asarray(emits["emit_mask"]).reshape(nd, -1).sum(axis=1)
            )
        if "exch_rows" in emits:
            per_shard = (
                np.asarray(emits["exch_rows"]).reshape(nd).astype(np.int64)
            )
            self.shard_exchange_rows += per_shard
            row_bytes = self._exch_row_bytes.get(step)
            if row_bytes is not None:
                # fused into the sharded step, so no separate timing: the
                # volume counters are what EXPLAIN ANALYZE, Prometheus and
                # the benchmark's exchange metrics read.  ``bytes`` is an
                # estimate of what had to cross (rows received x the
                # payload's row width); ``wire_bytes`` is what the
                # collective ships whatever the rows: every (source,
                # destination) bucket at its full ``bucket_capacity``
                total = int(per_shard.sum())
                lanes = nd * nd * self.bucket_capacity
                tracing.counter(
                    "exchange", steps=1, rows=total,
                    rows_fullest_shard=int(per_shard.max()),
                    bytes=total * row_bytes,
                    lanes=lanes, wire_bytes=lanes * row_bytes,
                    bucket_capacity=self.bucket_capacity,
                )
        if "occupancy" in emits:
            self.shard_store_occupancy = (
                np.asarray(emits["occupancy"]).reshape(nd).astype(np.int64)
            )
        if "probe_rounds" in emits and tracing.active() is not None:
            # the step waits for its slowest shard: the longest probe
            # loop; the load scalars beside it are the fullest shard's
            fullest = int(np.argmax(self.shard_store_occupancy))
            tracing.counter(
                "device.step", sampled=1,
                probe_rounds=int(np.asarray(emits["probe_rounds"]).max()),
                probe_lane_rounds=int(
                    np.asarray(emits["probe_lane_rounds"]).max()
                ),
                occupancy=int(self.shard_store_occupancy[fullest]),
                graves=int(
                    np.asarray(emits["graves"]).reshape(nd)[fullest]
                ),
                **_sliced_lanes_of(emits),
                **_session_work_of(emits),
                **_topk_of(emits),
            )
        if "find_rounds" in emits and tracing.active() is not None:
            # a stream-table join's lookups: the longest loop among the
            # shards, the rows of all of them
            tracing.counter(
                "device.step", sampled=1,
                find_rounds=int(np.asarray(emits["find_rounds"]).max()),
                join_rows=int(np.asarray(emits["join_rows"]).sum()),
                join_matched=int(np.asarray(emits["join_matched"]).sum()),
            )

    def process_ss(self, batch: HostBatch, side: str) -> List[SinkEmit]:
        """One side's micro-batch through the sharded stream-stream join:
        key exchange, then the ordinary ring-buffer step shard-local.
        Buffer/match-cap sizing is fixed at construction in distributed
        mode — overflow stops loudly rather than resizing online."""
        layout = self.c.layout if side == "l" else self.c.right_layout
        arrays = self.encode(batch, layout=layout)
        self.state, emits = self._ss_steps[side](self.state, arrays)
        self._account(emits, side)
        lost = int(np.asarray(emits["ss_lost"]).sum())
        movf = int(np.asarray(emits["ss_matchovf"]).sum())
        xovf = int(np.asarray(emits["ss_exch_ovf"]).sum())
        if lost or movf or xovf:
            raise RuntimeError(
                "distributed ss-join overflow "
                f"(ring lost={lost}, match cap={movf}, exchange={xovf}); "
                "restart with larger ss_buffer_capacity / ss_out_capacity / "
                "bucket_capacity"
            )
        out = self.c._decode_emits(self._flatten(emits))
        # record-driven time advance: expire the shard-local buffers AFTER
        # matching, emitting deferred GRACE null-pads (the executor's
        # ss_expire_host cadence — oracle _advance_time after each record)
        out.extend(self.ss_expire_host())
        return out

    @staticmethod
    def _flatten(emits: Dict[str, jnp.ndarray]) -> Dict[str, np.ndarray]:
        """[n_shards, n, ...] emits → the flat [n_shards*n, ...] layout the
        compiled query's emission decoder expects."""
        return {
            k: np.asarray(v).reshape((-1,) + np.asarray(v).shape[2:])
            for k, v in emits.items()
        }

    def split_columns(
        self, n, columns, timestamps, offsets=None, partitions=None,
    ) -> Dict[str, np.ndarray]:
        """Mesh-aware native-ingest entry: split decoded (data, valid)
        column slices round-robin into per-shard lanes for
        ``process_encoded`` — the columnar analog of encode(), with the
        same fault seams and per-shard accounting.  Each lane is
        assembled at the per-shard static shape; assemble COPIES the
        decoder's slices into fresh padded buffers, so they are never
        aliased into donated jit state."""
        nd = self.n_shards
        layout = self.c.layout
        armed = faults.armed()
        if armed:
            faults.fault_point("mesh.encode", self._qid)
        ts = np.asarray(timestamps, np.int64)
        offs = (
            np.asarray(offsets, np.int64)
            if offsets is not None else np.zeros(n, np.int64)
        )
        parts = (
            np.asarray(partitions, np.int32)
            if partitions is not None else np.zeros(n, np.int32)
        )
        stacked: Dict[str, List[np.ndarray]] = {}
        for d in range(nd):
            if armed:
                self._shard_fault_point(d)
            sel = np.arange(d, n, nd)
            self.shard_rows_in[d] += len(sel)
            if len(sel):
                self.shard_watermark_ms[d] = max(
                    self.shard_watermark_ms[d], int(ts[sel].max())
                )
            lane = {k: (v[sel], ok[sel]) for k, (v, ok) in columns.items()}
            arrays = layout.assemble(
                len(sel), lane, ts[sel],
                offsets=offs[sel], partitions=parts[sel],
            )
            for k, v in arrays.items():
                stacked.setdefault(k, []).append(v)
        if armed:
            # lane split complete: later failures in this tick (exchange,
            # XLA step) are whole-mesh, not attributable to the last lane
            self.current_shard = None
        out = {k: np.stack(vs) for k, vs in stacked.items()}
        tracing.counter(
            "step.dispatch",
            h2d_bytes=int(sum(v.nbytes for v in out.values())),
        )
        return out

    _seen_overflow = 0
    _batches = 0

    def process(self, batch: HostBatch) -> List[SinkEmit]:
        if self.c.ss_join is not None:
            return self.process_ss(batch, "l")
        return self.process_encoded(self.encode(batch))

    def process_encoded(self, arrays: Dict[str, np.ndarray]) -> List[SinkEmit]:
        """The sharded step over already-lane-split arrays: session
        slot-growth retry, per-shard accounting, eviction cadence and
        overflow tripwires — shared by process() and the native tier's
        split_columns()."""
        with tracing.span("step.dispatch"):
            new_state, emits = self._step(self.state, arrays)
        while self.c.session:
            with tracing.span("step.wait", wait=True):
                overflowed = int(np.asarray(emits["sess_ovf"]).sum()) > 0
            if not overflowed:
                break
            # more concurrent sessions per key than tracked slots on some
            # shard: grow, recompile the sharded step, re-run
            self.c.session_slots *= 2
            self._step = self._build_step()
            with tracing.span("step.dispatch"):
                new_state, emits = self._step(self.state, arrays)
        self.state = new_state
        if not self.c.session:  # the session step's overflow read has waited
            with tracing.span("step.wait", wait=True):
                jax.block_until_ready(emits)
        if self.c.agg is not None:
            self._batches += 1
            if (
                self.c.retention_ms is not None
                and self._batches % self.c.EVICT_INTERVAL == 0
            ):
                with tracing.span("store.evict"):
                    self.state = self._evict(self.state)
        with tracing.span("emit.decode"):
            with tracing.span("emit.read", wait=True):
                # every shard's columns cross here, a blocking read a leaf;
                # the accounting, the checks and the decode below read
                # these host copies
                emits = self._flatten(emits)
            self._account(emits)
            if self.c.agg is not None:
                overflow = int(np.asarray(emits["overflow"]).sum())
                if overflow > self._seen_overflow:
                    self._seen_overflow = overflow
                    raise RuntimeError(
                        f"sharded state store / exchange overflowed "
                        f"({overflow} rows lost); raise store_capacity or "
                        "bucket_capacity"
                    )
                # online distributed growth is not implemented yet: stop
                # loudly BEFORE loss once any shard nears saturation
                occ = int(np.asarray(emits["occupancy"]).max())
                if occ > 0.6 * self.c.store_capacity:
                    raise RuntimeError(
                        "sharded state store nearing capacity "
                        f"({occ}/{self.c.store_capacity} on the fullest "
                        "shard); restart the query with a larger "
                        "store_capacity"
                    )
            with tracing.span("emit.rows"):
                return self.c._decode_emits(emits)

    # -------------------------------------------------- executor-facing API
    def flush_pipeline(self) -> List[SinkEmit]:
        """No deferred emissions in distributed mode (pipeline = False)."""
        return []

    def ss_expire_host(self) -> List[SinkEmit]:
        """Expire the shard-local ss-join ring buffers (deferred GRACE
        null-pads) — the drain-tick analog of CompiledDeviceQuery's."""
        self.state, emits = self._ss_expire(self.state)
        return self.c._decode_emits(self._flatten(emits))

    def flush(self, stream_time: Optional[int] = None) -> List[SinkEmit]:
        """Advance event time explicitly.  EMIT FINAL never reaches the
        distributed runner (rejected at construction); only ss-joins hold
        time-gated emission state to flush."""
        if self.c.ss_join is None:
            return []
        if faults.armed():
            faults.fault_point("mesh.exchange", self._qid)
        if stream_time is not None:
            state = dict(self.state)
            state["max_ts"] = jnp.maximum(
                state["max_ts"], jnp.asarray(stream_time, jnp.int64)
            )
            for side in ("l", "r"):
                k = f"ss{side}_smax"
                state[k] = jnp.maximum(
                    state[k], jnp.asarray(stream_time, jnp.int64)
                )
            self.state = state
        return self.ss_expire_host()

    # ------------------------------------------------- sharded pull serving
    def _shard_state_view(self, shard: int) -> Dict[str, jnp.ndarray]:
        import jax.tree_util as jtu

        return jtu.tree_map(lambda v: jnp.asarray(np.asarray(v[shard])),
                            self.state)

    def _with_shard_state(self, shard: int, fn):
        """Run ``fn()`` with the compiled query's state pointed at one
        shard's slice (read-only use: pull serving).

        The zero-copy shard view is deliberate: in distributed mode the
        wrapped compiled query's own (donating) step functions are never
        invoked — only scan/lookup run against this state, op-by-op with
        no donation — and copying the full shard store per pull would put
        an O(store) tax on the read path."""
        saved = self.c._state
        self.c.state = self._shard_state_view(shard)  # graftlint: disable=donated-aliasing
        try:
            return fn()
        finally:
            self.c._state = saved

    def shard_of_key(self, reprs: List[int]) -> int:
        """Owning shard for a key given its 64-bit column reprs — the same
        hash + high-bit routing the exchange uses (pre_exchange/shard_of)."""
        from ksql_tpu.ops.hash_store import combine_hash

        parts = [jnp.asarray([r], jnp.int64) for r in reprs]
        parts.append(jnp.zeros(1, jnp.int64))  # knull: stored keys are 0
        khash = combine_hash(parts)
        return int(np.asarray(shard_of(khash, self.n_shards))[0])

    def scan_store(self) -> List[SinkEmit]:
        """Materialized-state scan across every shard's store slice."""
        out: List[SinkEmit] = []
        decoded = 0
        for s in range(self.n_shards):
            out.extend(self._with_shard_state(s, self.c.scan_store))
            decoded += self.c.last_pull_slots_decoded
        self.last_pull_slots_decoded = decoded
        self.shards_touched_last_pull = list(range(self.n_shards))
        return out

    def lookup_store(self, key_tuples) -> Optional[List[SinkEmit]]:
        """Keyed pull fast path over the mesh: route each key to
        ``shard_of(key)`` and probe ONLY the owning shards' stores.  Returns
        None when a key has no 64-bit repr (caller falls back to scan)."""
        from ksql_tpu.runtime.lowering import _host_repr64

        if self.c.store_layout is None:
            return None
        by_shard: Dict[int, list] = {}
        for kt in key_tuples:
            reprs = []
            for v, t in zip(kt, self.c.key_types):
                r = _host_repr64(v, t)
                if r is None:
                    return None
                reprs.append(r)
            by_shard.setdefault(self.shard_of_key(reprs), []).append(kt)
        out: List[SinkEmit] = []
        decoded = 0
        for s in sorted(by_shard):
            kts = by_shard[s]
            got = self._with_shard_state(s, lambda: self.c.lookup_store(kts))
            if got is None:
                return None
            decoded += self.c.last_pull_slots_decoded
            out.extend(got)
        self.last_pull_slots_decoded = decoded
        self.shards_touched_last_pull = sorted(by_shard)
        return out

    def changelog_dirty_state(self) -> Dict[str, Any]:
        """Dirty-set seam for the incremental changelog journal
        (runtime/changelog.py): per-shard host capture (leading
        [n_shards] axis preserved) in checkpoint-serde shape, diffed
        against the previous tick by the journal."""
        from ksql_tpu.runtime.checkpoint import _snapshot_device_dist

        return _snapshot_device_dist(self)

    def changelog_apply_state(self, data: Dict[str, Any]) -> None:
        """Restore a (possibly journal-patched) capture; arrays re-enter
        through _unflatten_state's jnp.array copy so journal-decoded
        buffers never alias donated jit state."""
        from ksql_tpu.runtime.checkpoint import _restore_device_dist

        _restore_device_dist(self, data)
