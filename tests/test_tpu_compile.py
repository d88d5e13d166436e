"""The step programs, compiled by the TPU's own compiler for a described v5e.

No chip is attached here: ``jax.experimental.topologies`` describes a
``v5e:2x2`` host and libtpu compiles for it, raising what the chip's
compiler would raise (a refused op, a program that does not fit 16 GB) and
taking the time it would take.  Nothing runs, so these say nothing about
results or speed — ``chip_smoke.py`` on the chip does.  What they guard is
that every program of the main path still compiles for the chip, at the
sizes the smoke runs, in a time a tick deadline and a chip call can live
with (the SESSION step took 302 s here before PR 21; see
``ops/session_merge.py``).

All in this one file, the topology described inside a module-scoped
fixture: only one process may load libtpu, the suite runs under several
xdist workers, and every worker imports every test file — so nothing here
touches the topology at import, and the one worker that is handed this
file loads the library.  The compiles run in the test's own process.
"""

import json
import os
import re
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.runtime.lowering import CompiledDeviceQuery

HBM_BYTES = 16 * 10**9  # one v5e chip
#: ceiling on any one compile here.  Measured in this sandbox: the longest,
#: config #2's sliced hopping step, takes ~25 s since PR 35 (its fold, claim
#: and combine are loops over 2,048-lane chunks: three more loop bodies at
#: the engine's default 8,192 lanes, where one pass took ~19 s; at the
#: benchmark cell's 32,768 lanes the same step compiles in ~18 s where the
#: one pass over 131,072 emit lanes took ~56 s); a program back in the
#: minutes fails the test long before it fails a tick deadline
COMPILE_CEILING_S = 90.0

PV_DDL = (
    "CREATE STREAM PAGE_VIEWS (URL STRING, USER_ID BIGINT, LATENCY DOUBLE, "
    "VIEWTIME BIGINT) WITH (KAFKA_TOPIC='page_views', VALUE_FORMAT='JSON');"
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


def _compile(jitted, *shapes):
    """Compile one of the program's own ``jax.jit`` objects; returns the
    executable after holding it to the chip's memory and the time ceiling."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*shapes).compile()
    took = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    on_device = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    assert on_device < HBM_BYTES, mem
    assert took < COMPILE_CEILING_S, f"compile took {took:.0f} s"
    return compiled


def _lowered(statements, **sizes):
    """The CompiledDeviceQuery the engine's DeviceExecutor would build for
    the last statement, at ``sizes``."""
    e = KsqlEngine(KsqlConfig({}))
    for s in statements:
        results = e.execute_sql(s)
    qid = next(r.query_id for r in results if r.query_id)
    return CompiledDeviceQuery(e.queries[qid].plan, e.registry, **sizes)


def _state(dev, sharding):
    return _on(sharding, jax.eval_shape(dev.init_state))


TUMBLING = [
    PV_DDL,
    "CREATE TABLE PV_COUNTS AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
    "WINDOW TUMBLING (SIZE 1 HOUR) GROUP BY URL EMIT CHANGES;",
]


def test_config1_tumbling_count_at_the_smokes_size(one_chip):
    """The benchmark cell's shapes too (32,768 lanes, 2^21 slots): the
    store's probe loop nested in its chunk loop compiles under the ceiling
    (11.5 s here in PR 26, 7.8 s as one loop) and stays two loops."""
    dev = _lowered(TUMBLING, capacity=32_768, store_capacity=1 << 21)
    arrays = _on(one_chip, dev.layout.array_structs())
    step = _compile(dev._step, _state(dev, one_chip), arrays)
    assert len(re.findall(r"= .* while\(", step.as_text())) == 2
    _compile(dev._evict, _state(dev, one_chip))


def test_clicks_join_cell_stream_step_and_table_step(one_chip):
    """The benchmark cell ``clicks_join.backlog``'s two programs at its
    shapes: 32,768 lanes against the join table at the 2^16 slots it starts
    with and the generator's ten users never outgrow.  The stream step's one
    loop is ``probe_find``'s, the table step's two are ``probe_insert``'s
    chunk loop and its probe loop; both carry their own counts out."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark/configs/clicks_users_join.json")) as f:
        config = json.load(f)
    dev = _lowered(config["statements"],
                   capacity=config["engine_props"]["ksql.batch.capacity"])
    assert dev.capacity == 32_768 and dev.table_store_capacity == 1 << 16
    assert sorted(c.name for c in dev.table_cols) == [
        "USERS_ORIGINAL_GENDER", "USERS_ORIGINAL_REGIONID"]
    state = _state(dev, one_chip)
    arrays = _on(one_chip, dev.layout.array_structs())
    step = _compile(dev._step, state, arrays)
    assert len(re.findall(r"= .* while\(", step.as_text())) == 1
    emits = jax.eval_shape(dev._trace_step, state, arrays)[1]
    assert {"find_rounds", "join_rows", "join_matched"} <= set(emits)
    table_arrays = _on(one_chip, dev._table_array_structs())
    table_step = _compile(dev._table_step, state, table_arrays)
    assert len(re.findall(r"= .* while\(", table_step.as_text())) == 2
    metrics = jax.eval_shape(dev._trace_table_step, state, table_arrays)[1]
    assert set(metrics) == {"occupancy", "overflow", "probe_rounds", "probe_lane_rounds"}


def test_config2_hopping_multi_udaf_double(one_chip):
    dev = _lowered([
        PV_DDL,
        "CREATE TABLE PV_STATS AS SELECT URL, SUM(LATENCY) AS S, "
        "AVG(LATENCY) AS A, MIN(LATENCY) AS MN, MAX(LATENCY) AS MX "
        "FROM PAGE_VIEWS WINDOW HOPPING (SIZE 1 HOUR, ADVANCE BY 15 MINUTES) "
        "GROUP BY URL EMIT CHANGES;",
    ])
    assert dev.sliced, dev.windowing_fallback
    _compile(dev._step, _state(dev, one_chip),
             _on(one_chip, dev.layout.array_structs()))


def test_hopping_cell_step_at_its_shapes(one_chip, monkeypatch):
    """The benchmark cell ``pv_hopstats.backlog``'s step at its shapes
    (32,768 lanes, 2^21 slots, the ring at the 8 cells the fill leaves it
    with), whose fold, claim and combine are loops over the occupied
    2,048-lane chunks: it compiles under the ceiling, no ring array is
    copied on its way round a loop, the emit columns keep their 131,072
    lanes and the step counts the lanes it visited."""
    from ksql_tpu.runtime import lowering

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark/configs/pageviews_hopping_stats.json")) as f:
        config = json.load(f)
    # the store is sized from the device's memory, and there is none here
    monkeypatch.setattr(lowering, "_device_memory_bytes", lambda: HBM_BYTES)
    dev = _lowered(config["statements"],
                   capacity=config["engine_props"]["ksql.batch.capacity"],
                   store_capacity=config["engine_props"]["ksql.state.slots"])
    dev._resize_ring(dev.slice_width, 8)
    assert dev.sliced and (dev.capacity, dev.store_capacity) == (32_768, 1 << 21)
    state = _state(dev, one_chip)
    arrays = _on(one_chip, dev.layout.array_structs())
    text = _compile(dev._step, state, arrays).as_text()
    # nothing the size of a ring array (2,097,153 x 8) is copied
    assert not re.findall(r"(?:2097153,8|8,2097153|16777224)\]\S* copy\(", text)
    emits = jax.eval_shape(dev._trace_step, state, arrays)[1]
    assert emits["emit_mask"].shape == (4 * 32_768,)
    assert emits["sliced_lanes"].shape == () and emits["sliced_lanes"].dtype == np.int32


@pytest.mark.parametrize("table_slots", [1 << 16, 1 << 21])
def test_config3_stream_table_join_and_its_table_step(one_chip, table_slots):
    """At the store the engine starts a join table with, and at the one a
    10^6-row table has grown it to."""
    dev = _lowered([
        "CREATE TABLE USERS (ID BIGINT PRIMARY KEY, NAME STRING, REGION STRING) "
        "WITH (KAFKA_TOPIC='users', VALUE_FORMAT='JSON');",
        "CREATE STREAM CLICKS (USER_ID BIGINT, URL STRING) "
        "WITH (KAFKA_TOPIC='clicks', VALUE_FORMAT='JSON');",
        "CREATE STREAM ENRICHED AS SELECT C.USER_ID, C.URL, U.REGION "
        "FROM CLICKS C LEFT JOIN USERS U ON C.USER_ID = U.ID "
        "WHERE U.REGION <> 'excluded' EMIT CHANGES;",
    ], table_store_capacity=table_slots)
    state = _state(dev, one_chip)
    _compile(dev._step, state, _on(one_chip, dev.layout.array_structs()))
    _compile(dev._table_step, state, _on(one_chip, dev._table_array_structs()))


def test_config4_stream_stream_join_three_programs(one_chip):
    dev = _lowered([
        "CREATE STREAM LEFTS (ID BIGINT KEY, V BIGINT) "
        "WITH (KAFKA_TOPIC='lt', VALUE_FORMAT='JSON');",
        "CREATE STREAM RIGHTS (ID BIGINT KEY, V BIGINT) "
        "WITH (KAFKA_TOPIC='rt', VALUE_FORMAT='JSON');",
        "CREATE STREAM J AS SELECT L.ID, L.V AS LV, R.V AS RV FROM LEFTS L "
        "LEFT JOIN RIGHTS R WITHIN 10 SECONDS GRACE PERIOD 1 SECOND "
        "ON L.ID = R.ID EMIT CHANGES;",
    ])
    state = _state(dev, one_chip)
    _compile(dev._ss_l, state, _on(one_chip, dev.layout.array_structs()))
    _compile(dev._ss_r, state, _on(one_chip, dev.right_layout.array_structs()))
    _compile(dev._ss_expire, state)


def test_config5_session_at_engine_defaults(one_chip):
    dev = _lowered([
        PV_DDL,
        "CREATE TABLE SESSIONS AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
        "WINDOW SESSION (30 SECONDS) GROUP BY URL EMIT CHANGES;",
    ])
    assert (dev.capacity, dev.store_capacity) == (8192, 1 << 17)
    _compile(dev._step, _state(dev, one_chip),
             _on(one_chip, dev.layout.array_structs()))


def test_fused_tap_kernel_sixteen_lanes(one_chip):
    from ksql_tpu.server import tap_kernel
    from ksql_tpu.server.rest import PushQuerySession

    e = KsqlEngine(KsqlConfig({}))
    e.execute_sql(PV_DDL)
    e.session_properties["auto.offset.reset"] = "latest"
    sessions = [
        PushQuerySession(
            e, "SELECT URL, VIEWTIME FROM PAGE_VIEWS "
               f"WHERE USER_ID % 16 = {i} AND LATENCY > 1.5 EMIT CHANGES;")
        for i in range(16)
    ]
    try:
        kernel = next(iter(e.push_registry.pipelines.values())).kernel
        (group,) = kernel.groups.values()
        assert group.n_active() == 16
        rows = 8192
        datas, valids, _ = tap_kernel._dummy_cols(
            group.rep.col_names, kernel.schema_cols, rows
        )
        args = (
            datas, valids, group.P_i, group.P_f, group.active,
            np.ones(rows, bool), np.zeros(group.capacity, np.int64),
        )
        shapes = _on(one_chip, jax.eval_shape(lambda *a: a, *args))
        _compile(group.fn(), *shapes)
    finally:
        for s in sessions:
            s.close()
        e.shutdown()


@pytest.mark.parametrize("store_capacity", [1 << 21, 1 << 20],
                         ids=["smoke_slots", "pv_count_mesh4_slots"])
def test_four_shard_step_exchanges_and_shards_its_state(topo, store_capacity):
    """The shard_map step of config #1 on a Mesh of the four described
    chips, at the smoke's slots a shard and at the cell ``pv_count.mesh4``'s
    (``benchmark/configs/pageviews_count_mesh4.json``).
    DistributedDeviceQuery places its state as it is built, which a
    described device cannot take, so the state here is shapes."""
    from ksql_tpu.parallel.distributed import DistributedDeviceQuery
    from ksql_tpu.parallel.mesh import SHARD_AXIS

    n_shards = 4
    mesh = Mesh(np.array(topo.devices[:n_shards]), (SHARD_AXIS,))
    sharded = NamedSharding(mesh, P(SHARD_AXIS))

    def stacked(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (n_shards,) + s.shape, s.dtype, sharding=sharded
            ),
            tree,
        )

    class ShapesForState(DistributedDeviceQuery):
        def init_state(self):
            return stacked(jax.eval_shape(self.c.init_state))

    # the mesh splits the smoke's 32,768-row host batch into four lanes
    dev = _lowered(TUMBLING, capacity=32_768 // n_shards, store_capacity=store_capacity)
    dist = ShapesForState(dev, mesh)
    arrays = stacked(dev.layout.array_structs())
    compiled = _compile(dist._step, dist.state, arrays)
    assert "all-to-all" in compiled.as_text()
    whole = sum(
        int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
        for s in jax.tree.leaves((dist.state, arrays))
    )
    # a quarter of the arguments on each device, not a replica of them
    # (the chip lays bool arrays out wider than numpy does, hence the room)
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert whole / n_shards <= per_device < 1.25 * whole / n_shards
