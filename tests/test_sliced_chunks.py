"""The sliced step's fold and emission visit the occupied lane chunks (ISSUE 35).

A sliced hopping step wider than ``lowering._SLICED_CHUNK`` folds, claims
and combines the chunks of consecutive lanes that hold an active row and
skips the others.  That must be invisible: the same batches through the
same step at ``n == width`` (one pass over all lanes, the code as it was)
give the same emit columns on the masked lanes and the same store, wherever
in the batch the rows sit; and the step counts the lanes it visited.
"""

import numpy as np
import pytest

from ksql_tpu.common import tracing
from ksql_tpu.common.batch import HostBatch
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.parallel.distributed import DistributedDeviceQuery
from ksql_tpu.parallel.mesh import make_mesh
from ksql_tpu.runtime import lowering
from ksql_tpu.runtime.lowering import CompiledDeviceQuery

DDL = (
    "CREATE STREAM PAGE_VIEWS (URL STRING, USER_ID BIGINT, LATENCY DOUBLE) "
    "WITH (KAFKA_TOPIC='page_views', KEY_FORMAT='JSON', VALUE_FORMAT='JSON');"
)
#: slices of 1 s, a window of 4, a ring of 4 + 10 + 2 = 16 cells
STATS = (
    "CREATE TABLE T AS SELECT URL, COUNT(*) AS CNT, SUM(LATENCY) AS S, "
    "MIN(LATENCY) AS MN, MAX(LATENCY) AS MX FROM PAGE_VIEWS "
    "WINDOW HOPPING (SIZE 4 SECONDS, ADVANCE BY 1 SECOND, "
    "GRACE PERIOD 10 SECONDS) GROUP BY URL EMIT CHANGES;"
)
#: a second window over the same source and GROUP BY: a family member
MEMBER = (
    "CREATE TABLE T2 AS SELECT URL, COUNT(*) AS CNT FROM PAGE_VIEWS "
    "WINDOW HOPPING (SIZE 2 SECONDS, ADVANCE BY 1 SECOND, "
    "GRACE PERIOD 10 SECONDS) GROUP BY URL EMIT CHANGES;"
)
N = 32  # lanes of a step
WIDTH = 8  # the chunk the tests cut them into: four chunks
STORE = 256
RING = 16


def _plans():
    engine = KsqlEngine()
    engine.execute_sql(DDL)
    plans = []
    for sql in (STATS, MEMBER):
        qid = next(r.query_id for r in engine.execute_sql(sql) if r.query_id)
        plans.append(engine.queries[qid].plan)
    schema = engine.metastore.get_source(plans[0].source_names[0]).schema
    return engine, plans, schema


def _device(monkeypatch, width, member=False):
    """The STATS step at ``N`` lanes with the chunk constant at ``width``:
    the constant is read when the step is traced, at its first call, so an
    empty batch goes through here."""
    monkeypatch.setattr(lowering, "_SLICED_CHUNK", width)
    engine, plans, schema = _plans()
    dev = CompiledDeviceQuery(
        plans[0], engine.registry, capacity=N, store_capacity=STORE
    )
    assert dev.sliced and dev.slice_ring == RING and dev.hop_k == 4
    if member:
        dev.attach_member(plans[1], "MEMBER_Q", deliver=lambda rows: None)
        assert dev.slice_ring == RING
    _step(dev, _arrays(dev, schema, []))
    return dev, schema


def _host_batch(schema, rows):
    """``rows`` = [(lane, url, latency, ts)] as a host batch, in order."""
    return HostBatch.from_rows(
        schema,
        [{"URL": u, "USER_ID": 1, "LATENCY": v} for _, u, v, _ in rows],
        timestamps=[t for *_, t in rows],
    )


def _arrays(dev, schema, rows):
    """One step's input: ``rows`` = [(lane, url, latency, ts)], each row at
    the lane named, every other lane invalid."""
    hb = _host_batch(schema, rows)
    packed = dev.layout.encode(hb)
    lanes = np.array([lane for lane, *_ in rows], dtype=np.int64)
    assert len(set(lanes.tolist())) == len(rows)
    out = {}
    for name, col in packed.items():
        placed = np.zeros_like(col)
        placed[lanes] = col[: len(rows)]
        out[name] = placed
    return out


def _step(dev, arrays):
    dev.state, emits = dev._step(dev.state, arrays)
    return {k: np.asarray(v) for k, v in emits.items()}


def _groups(emits):
    """An emission block a member: {prefix: {column: lanes}}."""
    out = {}
    for name, col in emits.items():
        prefix, _, column = name.rpartition(":")
        if col.ndim and column != "dec_envelope":
            out.setdefault(prefix, {})[column] = col
    return out


def _assert_same_step(chunked, whole):
    """Emit columns equal on the masked lanes, same shapes and dtypes, and
    the step's scalars equal but for the lanes-visited counter."""
    assert chunked.keys() == whole.keys()
    for name in chunked:
        assert chunked[name].shape == whole[name].shape, name
        assert chunked[name].dtype == whole[name].dtype, name
        if not chunked[name].ndim or name.endswith("dec_envelope"):
            if name != "sliced_lanes":
                assert np.array_equal(chunked[name], whole[name]), name
    got, want = _groups(chunked), _groups(whole)
    for prefix in want:
        mask = want[prefix]["emit_mask"]
        assert np.array_equal(got[prefix]["emit_mask"], mask), prefix
        for column, lanes in want[prefix].items():
            assert np.array_equal(got[prefix][column][mask], lanes[mask]), (
                prefix, column)


def _assert_same_store(chunked, whole):
    """Every array of the state equal, the dump slot (which absorbs the
    writes of lanes that hold no row) aside."""
    assert chunked.state.keys() == whole.state.keys()
    for name in whole.state:
        got, want = np.asarray(chunked.state[name]), np.asarray(whole.state[name])
        if got.ndim and got.shape[0] == STORE + 1:
            got, want = got[:STORE], want[:STORE]
        assert np.array_equal(got, want), name


def _rows_at(lanes, seed, t0, keys=5, span_ms=2500):
    rng = np.random.default_rng(seed)
    return [
        (int(lane), f"/p/{rng.integers(keys)}", float(rng.integers(0, 4000)) / 4,
         t0 + int(rng.integers(span_ms)))
        for lane in lanes
    ]


#: where a batch's rows sit: name -> lanes of the N
LAYOUTS = {
    # a served tick: the rows lead the batch, an eighth of its lanes
    "prefix_eighth": list(range(N // 8)),
    # the mesh's received lanes: four buckets, each front-filled
    "four_buckets": [b * (N // 4) + i for b in range(4) for i in range(3)],
    "full": list(range(N)),
    "empty": [],
    # rows in the second and the last chunk only
    "holes": [WIDTH + 1, WIDTH + 5, 3 * WIDTH, 3 * WIDTH + 7],
}
#: chunks of WIDTH lanes each layout occupies
CHUNKS = {"prefix_eighth": 1, "four_buckets": 4, "full": 4, "empty": 0, "holes": 2}


@pytest.mark.parametrize("member", [False, True], ids=["alone", "family"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_chunked_step_equals_whole_step(monkeypatch, layout, member):
    """Three batches of each layout, event time moving on by 1.5 s a batch
    (windows open, change and overlap), through both steps."""
    chunked, schema = _device(monkeypatch, WIDTH, member)
    whole, _ = _device(monkeypatch, N, member)
    for b in range(3):
        rows = _rows_at(LAYOUTS[layout], seed=7 * b + len(layout), t0=1500 * b)
        got = _step(chunked, _arrays(chunked, schema, rows))
        want = _step(whole, _arrays(whole, schema, rows))
        _assert_same_step(got, want)
        _assert_same_store(chunked, whole)
        assert got["emit_mask"].shape == (4 * N,)
        assert int(got["sliced_lanes"]) == WIDTH * CHUNKS[layout]
        assert int(want["sliced_lanes"]) == N
        if member:
            assert got["fam:MEMBER_Q:emit_mask"].shape == (2 * N,)
        if rows:
            assert got["emit_mask"].any()


def test_unvisited_chunks_read_masked_out_and_zero(monkeypatch):
    chunked, schema = _device(monkeypatch, WIDTH)
    got = _step(chunked, _arrays(chunked, schema, _rows_at(LAYOUTS["holes"], 3, 0)))
    # emit lane hop * N + row: the rows of chunks 0 and 2 were never visited
    row = np.arange(4 * N) % N
    unvisited = (row < WIDTH) | ((row >= 2 * WIDTH) & (row < 3 * WIDTH))
    assert got["emit_mask"].any() and not got["emit_mask"][unvisited].any()
    for name, lanes in _groups(got)[""].items():
        assert not lanes[unvisited].any(), name
    empty = _step(chunked, _arrays(chunked, schema, []))
    assert int(empty["sliced_lanes"]) == 0 and not empty["emit_mask"].any()


def test_one_key_in_two_chunks_emits_a_window_once_from_the_lower_lane(monkeypatch):
    """A hot key sits in many chunks: the winner of a (slot, window) is the
    lowest lane ``hop * N + row`` of the whole batch, not of its chunk."""
    chunked, schema = _device(monkeypatch, WIDTH)
    whole, _ = _device(monkeypatch, N)
    rows = [(20, "/hot", 1.0, 5200), (2, "/hot", 2.0, 6100), (27, "/hot", 4.0, 5900)]
    got = _step(chunked, _arrays(chunked, schema, rows))
    want = _step(whole, _arrays(whole, schema, rows))
    _assert_same_step(got, want)
    mask = got["emit_mask"]
    starts = got["ws"][mask]
    # slices 5 and 6 lie in the windows starting 2 s..6 s: five, each once
    assert sorted(starts.tolist()) == [2000, 3000, 4000, 5000, 6000]
    lanes = np.nonzero(mask)[0]
    by_start = dict(zip(starts.tolist(), lanes.tolist()))
    # row 2 (slice 6, chunk 0) is hop h of window 6000 - 1000 h, rows 20
    # and 27 (slice 5, chunks 2 and 3) of window 5000 - 1000 h: the lower
    # hop wins a window, whichever chunk was visited first
    assert [by_start[w] for w in (6000, 5000, 4000, 3000, 2000)] == [
        2, 20, N + 20, 2 * N + 20, 3 * N + 20]
    counts = dict(zip(starts.tolist(), got["v_CNT"][mask].tolist()))
    assert counts == {2000: 2, 3000: 3, 4000: 3, 5000: 3, 6000: 1}


def test_two_chunks_writing_one_recycled_ring_cell(monkeypatch):
    """After a ring wrap a targeted cell still holds an old slice and is
    reset before the fold.  Two rows of one batch, in two chunks, target
    it: the second chunk must find the first's slice_id there and add to
    the first's contribution, not wipe it."""
    chunked, schema = _device(monkeypatch, WIDTH)
    whole, _ = _device(monkeypatch, N)
    first = [(0, "/k", 8.0, 500), (9, "/k", 16.0, 700)]  # slice 0: ring cell 0
    wrapped = [  # slice 16: ring cell 0 again, in chunks 0, 1 and 3
        (1, "/k", 1.0, 16_100), (12, "/k", 2.0, 16_300), (30, "/k", 4.0, 16_800)]
    for rows in (first, wrapped):
        got = _step(chunked, _arrays(chunked, schema, rows))
        want = _step(whole, _arrays(whole, schema, rows))
        _assert_same_step(got, want)
        _assert_same_store(chunked, whole)
    mask = got["emit_mask"]
    assert sorted(got["ws"][mask].tolist()) == [13_000, 14_000, 15_000, 16_000]
    # all three rows of the wrapped batch, and nothing of slice 0
    assert got["v_CNT"][mask].tolist() == [3] * 4
    assert got["v_S"][mask].tolist() == [7.0] * 4
    assert got["v_MN"][mask].tolist() == [1.0] * 4
    assert got["v_MX"][mask].tolist() == [4.0] * 4
    slot = int(np.nonzero(np.asarray(chunked.state["occ"]))[0][0])
    assert int(np.asarray(chunked.state["slice_id"])[slot, 0]) == 16


def test_device_step_books_the_lanes_visited(monkeypatch):
    """``device.step`` ``sliced_lanes`` = width × chunks visited, booked
    where the load scalars are read."""
    chunked, schema = _device(monkeypatch, WIDTH)
    recorder = tracing.FlightRecorder("sliced-chunks")
    visited = 0
    for layout in ("holes", "full", "empty", "prefix_eighth"):
        rows = _rows_at(LAYOUTS[layout], seed=len(layout), t0=0)
        hb = _host_batch(schema, rows)
        # process() packs rows as a prefix: ceil(rows / WIDTH) chunks
        visited += WIDTH * -(-len(rows) // WIDTH)
        with tracing.tick(recorder):
            chunked.process(hb)
    stats = recorder.stage_stats()["device.step"]
    assert stats["sampled"] == 4
    assert stats["sliced_lanes"] == visited == WIDTH * (1 + 4 + 0 + 1)


def _mesh_run(monkeypatch, width, batches):
    """The STATS step on four virtual devices (a lane of N // 4 rows a
    shard, N received lanes in four buckets a shard): the decoded emits of
    each batch and the ``device.step`` counters."""
    monkeypatch.setattr(lowering, "_SLICED_CHUNK", width)
    engine, plans, schema = _plans()
    compiled = CompiledDeviceQuery(
        plans[0], engine.registry, capacity=N // 4, store_capacity=STORE
    )
    assert compiled.sliced
    dist = DistributedDeviceQuery(compiled, make_mesh(4))
    recorder = tracing.FlightRecorder("sliced-chunks-mesh")
    out = []
    for rows in batches:
        hb = _host_batch(schema, rows)
        with tracing.tick(recorder):
            emits = dist.process(hb)
        out.append(sorted(
            (e.key, e.window, tuple(sorted(e.row.items()))) for e in emits
        ))
    return out, recorder.stage_stats()["device.step"], dist


def test_chunked_step_inside_the_mesh(monkeypatch):
    """Under ``shard_map`` a shard's rows arrive bucket by bucket, each
    bucket front-filled: the occupied chunks are not a prefix.  Same emits
    as the whole step, shard for shard the same store, and the counter is
    the busiest shard's."""
    batches = [
        _rows_at(range(N), seed=40 + b, t0=1500 * b, keys=9) for b in range(3)
    ]
    got, stats, chunked = _mesh_run(monkeypatch, WIDTH // 2, batches)
    want, whole_stats, whole = _mesh_run(monkeypatch, 4 * N, batches)
    assert got == want and all(got)
    for name in whole.state:
        a, b = np.asarray(chunked.state[name]), np.asarray(whole.state[name])
        if a.ndim > 1 and a.shape[1] == STORE + 1:
            a, b = a[:, :STORE], b[:, :STORE]
        assert np.array_equal(a, b), name
    assert stats["sampled"] == whole_stats["sampled"] == 3
    # a shard receives N lanes, four buckets of N // 4 = two chunks each:
    # a bucket's ~two rows lead it, so its second chunk is mostly skipped
    assert whole_stats["sliced_lanes"] == 3 * N
    assert 3 * WIDTH // 2 <= stats["sliced_lanes"] < 3 * N
    assert stats["sliced_lanes"] % (WIDTH // 2) == 0
