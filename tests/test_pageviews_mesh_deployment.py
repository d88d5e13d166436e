"""``pageviews_count_mesh4`` (the four-partition deployment: GROUP BY
repartition as the all-to-all, the window store sharded by key owner)
against the benchmark deployment's plain reference
(``benchmark/deployments/pageviews.py``), on four of ``conftest.py``'s
virtual CPU devices, at the configuration's ``rehearse`` engine properties
and key universe, through ``KsqlServer``'s engine and ``poll_once``.  Also
that the shards' shares add up to the whole, that the mesh's sink equals the
single-device backend's, and the ``exchange`` counters the cell's metrics
read.
"""

from __future__ import annotations

import collections
import importlib.util
import json
import os
import sys
import urllib.request

import jax
import numpy as np
import pytest

from ksql_tpu.common.config import KsqlConfig
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.parallel.repartition import np_shard_of
from ksql_tpu.runtime.topics import Record
from ksql_tpu.server.rest import KsqlServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_SHARDS = 4


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def _json(path: str):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


dep = _load("benchmark/deployments/pageviews.py", "bench_deployment_pageviews")
MESH, ONE_CHIP = (_json(f"benchmark/configs/{name}.json")
                  for name in ("pageviews_count_mesh4", "pageviews_count"))
#: the rehearsal's corpus shape, with an hour-window of 8,000 events so that
#: the run crosses three windows
SIZES = {**MESH["sizes"], **MESH["rehearse"]["sizes"], "events_per_window": 8000}
N_EVENTS = 20_000
CAPACITY = int(MESH["rehearse"]["engine_props"]["ksql.batch.capacity"])
PULLS = 9


def _post(url: str, path: str, body):
    req = urllib.request.Request(url + path, data=json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read().decode())


class Served:
    """One served run of ``config``'s statements over the seed's corpus (or
    over ``rows`` of ``(payload, timestamp)``, then without pulls), fed as
    the harness's fill feeds it: a batch produced, then ``poll_once`` until
    quiet under the server's engine lock."""

    def __init__(self, config, seed: int, rows=None):
        self.corpus = dep.make_corpus(seed, SIZES, N_EVENTS)
        self.seed = seed
        props = {**config["engine_props"], **config["rehearse"]["engine_props"]}
        self.engine = KsqlEngine(KsqlConfig(props))
        self.srv = KsqlServer(engine=self.engine, port=0)
        self.srv.start()
        try:
            self._run(config, rows)
        finally:
            self.srv.stop()

    def _run(self, config, own_rows) -> None:
        out = _post(self.srv.url, "/ksql", {"ksql": " ".join(config["statements"])})
        qid = [e["commandStatus"]["queryId"] for e in out
               if e.get("commandStatus", {}).get("queryId")][-1]
        handle = self.engine.queries[qid]
        want = config["engine_props"].get("ksql.runtime.backend", "device")
        assert handle.backend == want, dict(self.engine.fallback_reasons)
        self.ex, self.device = handle.executor, handle.executor.device
        assert (self.ex._native_fields is not None) == config["native_ingest"]
        topic = self.engine.broker.topic(self.corpus.source_topic)
        rows = own_rows or list(zip(self.corpus.payloads, self.corpus.ts))
        with self.srv.engine_lock:
            for lo in range(0, len(rows), CAPACITY):
                for value, ts in rows[lo:lo + CAPACITY]:
                    topic.produce(Record(key=None, value=value, timestamp=ts))
                while self.engine.poll_once(max_records=CAPACITY) or self.ex.pending_records():
                    pass
        assert handle.state == "RUNNING", list(self.engine.processing_log)
        self.stages = self.engine.trace_recorder(qid).stage_stats()
        sink = self.engine.broker.topic(handle.plan.physical_plan.topic)
        self.records = [(r.key, r.window, r.value) for r in sink.all_records()]
        self.store = dep.read_store(self.ex)
        self.state = {k: np.asarray(v) for k, v in self.device.state.items()}
        if own_rows is None:
            self.pulls = [
                (key, dep.read_pull(_post(self.srv.url, "/query", {"ksql": sql})))
                for key, sql in dep.pull_queries(self.corpus, N_EVENTS, self.seed, PULLS)]

    def compared(self):
        return dep.compare(self.corpus, N_EVENTS, self.records, self.store, self.pulls)


@pytest.fixture(scope="module")
def mesh():
    return Served(MESH, 0)


def _within(numbers) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())


# ---------------------------------------------- (a) the plain reference
@pytest.mark.parametrize("seed", [0, 4_294_967_311])
def test_mesh_equals_the_plain_reference(seed, mesh):
    run = mesh if seed == 0 else Served(MESH, seed)
    numbers = run.compared()
    assert set(numbers) == {"sink_keys_wrong", "sink_keys_extra", "sink_events_missing",
                            "sink_counts_backwards", "store_entries_diff", "pulls_wrong"}
    assert all(n["value"] == 0 and n["limit"] == 0 for n in numbers.values()), numbers
    assert len({w for _u, w in dep.reference(run.corpus, N_EVENTS)}) == 3
    # the pulls were answered from a shard each, not from a scan of all four
    assert len(run.device.shards_touched_last_pull) <= 1


# ------------------------------------------------ (b) the shares add up
def test_every_entry_lives_on_its_owner_shard_and_on_no_other(mesh):
    occ = mesh.state["occ"][:, :-1].astype(bool)
    assert occ.shape[0] == N_SHARDS
    per_shard = occ.sum(axis=1)
    want = dep.reference(mesh.corpus, N_EVENTS)
    assert per_shard.sum() == len(want) and (per_shard > 0).all()
    entries = []
    for shard in range(N_SHARDS):
        khash = mesh.state["khash"][shard, :-1][occ[shard]]
        wstart = mesh.state["wstart"][shard, :-1][occ[shard]]
        assert (np_shard_of(khash, N_SHARDS) == shard).all()
        entries.extend(zip(khash.tolist(), wstart.tolist()))
    assert len(set(entries)) == len(entries) == len(want)
    # one hash per URL, in as many windows as the reference has it in
    windows_of = collections.Counter(u for u, _w in want)
    assert (sorted(collections.Counter(k for k, _w in entries).values())
            == sorted(windows_of.values()))


# -------------------------------------- (c) the single-device backend's
def test_mesh_sink_equals_the_single_device_sink_record_for_record(mesh):
    one_chip = Served(ONE_CHIP, 0)
    assert one_chip.device.capacity == mesh.device.capacity == CAPACITY
    assert collections.Counter(mesh.records) == collections.Counter(one_chip.records)
    # EMIT CHANGES per batch: a record for each key a step touched
    assert len(dep.reference(mesh.corpus, N_EVENTS)) < len(mesh.records) <= N_EVENTS


# ----------------------------------------------------- (d) the controls
@pytest.mark.parametrize("kind", ["lost_event", "lost_tick", "stale_count"])
def test_a_broken_guarantee_reads_not_correct(kind, mesh):
    broken = dep.control_reference(mesh.corpus, N_EVENTS, kind, mesh.seed)
    assert not _within(dep.compare(mesh.corpus, N_EVENTS, broken, None, None))
    assert dep.fold_sink(broken)[0] != dep.fold_sink(mesh.records)[0]
    assert _within(mesh.compared())


# ------------------------------------------------- (e) the new counters
def test_exchange_counters_are_booked_from_rows_and_static_shapes(mesh):
    ex, dev = mesh.stages["exchange"], mesh.device
    steps = ex["steps"]
    assert steps == mesh.stages["step.wait"]["n"] == -(-N_EVENTS // CAPACITY)
    # COUNT(*) has no filter: every polled row survives pre_exchange
    assert ex["rows"] == mesh.stages["poll"]["rows"] == N_EVENTS
    assert ex["rows"] == dev.shard_exchange_rows.sum()
    assert ex["rows"] / N_SHARDS <= ex["rows_fullest_shard"] <= ex["rows"]
    assert dev.bucket_capacity == CAPACITY // N_SHARDS
    assert ex["bucket_capacity"] == steps * dev.bucket_capacity
    assert ex["lanes"] == steps * N_SHARDS * N_SHARDS * dev.bucket_capacity
    # the payload's row width, from the arrays pre_exchange hands over
    payload = jax.eval_shape(
        dev.c.pre_exchange, jax.ShapeDtypeStruct((), np.int64), dev.c.layout.array_structs())
    row_bytes = sum(np.dtype(v.dtype).itemsize * int(np.prod(v.shape[1:]))
                    for v in payload.values())
    assert row_bytes > 0 and dev._exch_row_bytes == {"": row_bytes}
    assert ex["wire_bytes"] == ex["lanes"] * row_bytes
    assert ex["bytes"] == ex["rows"] * row_bytes
    assert mesh.ex.shard_metrics()["exchange-bytes"] == [
        r * row_bytes for r in dev.shard_exchange_rows.tolist()]
    # the read-back counts all four shards' columns as they cross
    _state, emits = jax.eval_shape(
        dev._step, dev.state,
        {k: jax.ShapeDtypeStruct((N_SHARDS,) + v.shape, v.dtype)
         for k, v in dev.c.layout.array_structs().items()})
    step_bytes = sum(int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
                     for v in emits.values())
    assert mesh.stages["emit.decode"]["d2h_bytes"] == steps * step_bytes
    assert all(v.shape[0] == N_SHARDS for v in emits.values())
    # the step waits for its slowest shard: the longest probe loop
    assert mesh.stages["device.step"]["sampled"] == steps
    assert mesh.stages["device.step"]["probe_rounds"] >= steps


# ------------------------------------------------------- (f) the skew
def test_a_batch_of_one_key_neither_overflows_nor_loses_a_row():
    """Every row of two full host batches carries one URL: every lane's
    bucket for that key's owner is full to the last row, the others are
    empty."""
    n = 2 * CAPACITY
    url = dep.url_of(77)
    rows = [('{"URL":"%s","USER_ID":%d,"VIEWTIME":%d}' % (url, i, dep.TS0 + i), dep.TS0 + i)
            for i in range(n)]
    run = Served(MESH, 0, rows=rows)
    ex = run.stages["exchange"]
    assert ex["rows"] == ex["rows_fullest_shard"] == n
    assert int(run.state["overflow"].sum()) == 0
    assert run.store == {"live_entries": 1}
    table, backwards = dep.fold_sink(run.records)
    # a record a step: the key's count after each of the two batches
    assert table == {(url, dep.TS0): n} and backwards == 0 and len(run.records) == 2
    owner = int(np.flatnonzero(run.state["occ"][:, :-1].sum(axis=1))[0])
    assert run.device.shard_exchange_rows.tolist() == [
        n if s == owner else 0 for s in range(N_SHARDS)]
