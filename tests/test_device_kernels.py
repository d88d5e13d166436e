"""Device kernels held to the plain formulations they replaced (PR 21).

Three pieces of the step programs were rewritten so the TPU's compiler
takes seconds for them, not minutes, or so the store holds a deployment's
load; each must give exactly what the straightforward version gave, and
that version stays here as the reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ksql_tpu.ops import hash_store as hs
from ksql_tpu.ops import session_merge
from ksql_tpu.ops import window as W


# ----------------------------------------- session items: order without a sort
@pytest.mark.parametrize("n,slots,n_keys,ties", [
    (1, 1, 1, False),
    (2, 4, 1, True),
    (33, 4, 7, True),
    (64, 4, 40, False),
    (64, 8, 7, True),
    (257, 16, 3, False),
])
def test_merged_order_equals_the_sort_it_replaced(n, slots, n_keys, ties):
    """``jnp.lexsort((start, key))`` over all n*(S+1) items is what the
    session step did; live items must come out in exactly that order."""
    sort_rows = jax.jit(session_merge.sort_rows)
    merged_order = jax.jit(session_merge.merged_order)
    m = n * (slots + 1)
    for seed in range(12):
        rng = np.random.default_rng(1000 * n + seed)
        keys = rng.integers(-2**62, 2**62, n_keys)
        khash = keys[rng.integers(0, n_keys, n)]
        span = 20 if ties else 10_000
        ts = rng.integers(0, span, n).astype(np.int64)
        active = rng.random(n) < 0.8
        order = sort_rows(khash, ts, active)

        first = np.zeros(n, bool)
        seen = set()
        for r in range(n):
            if active[r] and khash[r] not in seen:
                seen.add(khash[r])
                first[r] = True
        assert (np.asarray(order.first_occ) == first).all()

        st_start = rng.integers(-5, span, (slots, n)).astype(np.int64)
        st_alive = np.zeros((slots, n), bool)
        st_alive[:, first] = rng.random((slots, int(first.sum()))) < 0.6
        got = np.asarray(merged_order(order, active, st_alive, st_start))
        assert sorted(got.tolist()) == list(range(m))  # a permutation

        alive = np.concatenate([active, st_alive.reshape(-1)])
        key = np.concatenate([khash] * (slots + 1))
        start = np.concatenate([ts, st_start.reshape(-1)])
        key = np.where(alive, key, np.arange(m) + (1 << 62))  # dead: unique
        want = np.lexsort((np.where(alive, start, 0), key))
        assert [q for q in got if alive[q]] == [q for q in want if alive[q]]
        assert alive[got[: alive.sum()]].all()  # the dead come last


def test_running_max_equals_cummax():
    rng = np.random.default_rng(3)
    x = rng.integers(-2**62, 2**62, 5000)
    x[::7] = np.iinfo(np.int64).min  # the padding rows' sentinel
    assert (np.asarray(jax.jit(W.running_max)(x)) == np.maximum.accumulate(x)).all()
    assert np.asarray(W.running_max(jnp.asarray(x[:1]))).tolist() == [x[0]]


# ------------------------------------------------- the store at a real load
def _fill(capacity, n_keys, batch, lanes):
    layout = hs.StoreLayout(capacity=capacity, num_keys=1, components=())
    store = hs.init_store(layout)
    insert = jax.jit(
        lambda st, kh, act: hs.probe_insert(
            st, capacity, kh, jnp.zeros_like(kh), [kh],
            jnp.zeros(kh.shape, jnp.int32), act, _chunk=lanes,
        )[:2]
    )
    rng = np.random.default_rng(capacity)
    keys = rng.integers(-2**62, 2**62, n_keys)
    slots = np.zeros(n_keys, np.int64)
    for lo in range(0, n_keys, batch):
        chunk = np.zeros(batch, np.int64)
        act = np.zeros(batch, bool)
        part = keys[lo:lo + batch]
        chunk[: len(part)], act[: len(part)] = part, True
        store, got = insert(store, chunk, act)
        slots[lo:lo + len(part)] = np.asarray(got)[: len(part)]
    return store, keys, slots


@pytest.mark.parametrize("chunk", [512, 128], ids=["one_chunk", "four_chunks"])
@pytest.mark.parametrize("capacity", [1 << 10, 1 << 13, 1 << 16])
def test_store_holds_seventy_percent_load(capacity, chunk):
    """The host grows a store at 75 % occupancy, so the store has to hold
    that: a fixed 32 probe rounds (what the loop was) lost rows from about
    45 % in tables this size and up — the longest probe sequence here is
    well past 32."""
    n_keys = int(0.7 * capacity)
    store, keys, slots = _fill(capacity, n_keys, batch=512, lanes=chunk)
    assert int(store["overflow"]) == 0
    assert int(np.asarray(store["occ"]).sum()) == n_keys
    assert len(set(slots.tolist())) == n_keys and (slots < capacity).all()
    found = np.asarray(jax.jit(
        lambda st, kh: hs.probe_find(
            st, capacity, kh, jnp.zeros_like(kh), jnp.ones(kh.shape, bool)
        )[0]
    )(store, keys))
    assert (found == slots).all()
    if capacity >= 1 << 13:
        mask = capacity - 1
        base = hs.np_mix64(keys ^ 0) & mask
        assert int(((slots - base) & mask).max()) > 32


@pytest.mark.parametrize("chunk", [24, 6], ids=["one_chunk", "four_chunks"])
def test_full_store_still_reports_overflow(chunk):
    """The probe loop is bounded by the table: rows that cannot be placed
    are counted, not looped over for ever."""
    capacity = 64
    store, _keys, _slots = _fill(capacity, capacity + 8, batch=24, lanes=chunk)
    assert int(np.asarray(store["occ"])[:-1].sum()) == capacity
    assert int(store["overflow"]) == 8


# --------------------------------------- the probe loop, a chunk of lanes at a time
def _chunked_insert(capacity, chunk):
    """``probe_insert`` at ``chunk`` lanes a pass, keyed by (khash, wstart),
    the key column holding ``~khash``: (store, slots, rounds, lane_rounds)."""
    return jax.jit(
        lambda st, kh, ws, act: hs.probe_insert(
            st, capacity, kh, ws, [~kh], jnp.zeros(kh.shape, jnp.int32), act,
            _chunk=chunk,
        )
    )


def _empty_store(capacity):
    return hs.init_store(hs.StoreLayout(capacity=capacity, num_keys=1, components=()))


def _entries(store):
    """(khash, wstart) -> (key, slot) of the live slots."""
    occ = np.asarray(store["occ"])[:-1]
    cols = [np.asarray(store[c])[:-1][occ].tolist() for c in ("khash", "wstart", "key0")]
    return {(kh, ws): (key, slot) for kh, ws, key, slot
            in zip(*cols, np.nonzero(occ)[0].tolist())}


@pytest.mark.parametrize("capacity,n,chunk,pool,fills", [
    (1 << 10, 512, 64, 100, False),     # 8 chunks, most keys recur across chunks
    (1 << 12, 500, 64, 2000, False),    # the last chunk is padded; keys mostly new
    (1 << 12, 1024, 128, 1500, False),  # past half load by the last batch
    (1 << 7, 96, 32, 200, True),        # the table fills: rows are lost
    (1 << 10, 256, 1, 100, False),      # a lane a pass
])
def test_chunked_insert_equals_the_one_chunk_insert(capacity, n, chunk, pool, fills):
    """Probing ``chunk`` lanes at a time builds the table the whole-batch
    loop builds: the same (khash, wstart) -> key entries, one slot a key,
    every placed row at its key's slot, every lost row counted.  Which slot
    a key takes may differ (chains cross chunks): slots are no contract."""
    rng = np.random.default_rng(capacity + n + chunk)
    keys = rng.integers(-2**62, 2**62, pool)
    whole, parts = _chunked_insert(capacity, n), _chunked_insert(capacity, chunk)
    st_whole, st_parts = _empty_store(capacity), _empty_store(capacity)
    lost = 0
    for _ in range(4):
        kh = keys[rng.integers(0, pool, n)]
        ws = rng.integers(0, 3, n) * 3_600_000
        act = rng.random(n) < 0.8
        st_whole, _, rounds_whole, lanes_whole = whole(st_whole, kh, ws, act)
        st_parts, slots, rounds, lanes = parts(st_parts, kh, ws, act)
        assert int(lanes_whole) == int(rounds_whole) * n
        assert int(lanes) == int(rounds) * chunk and int(rounds) >= int(rounds_whole)
        slots = np.asarray(slots)
        assert (slots[~act] == capacity).all()
        placed = act & (slots != capacity)
        lost += int((act & ~placed).sum())
        assert (np.asarray(st_parts["khash"])[slots[placed]] == kh[placed]).all()
        assert (np.asarray(st_parts["wstart"])[slots[placed]] == ws[placed]).all()
    want, got = _entries(st_whole), _entries(st_parts)
    assert int(st_parts["overflow"]) == lost
    if not fills:
        assert lost == 0 and int(st_whole["overflow"]) == 0
        assert {k: v[0] for k, v in got.items()} == {k: v[0] for k, v in want.items()}
    else:  # a full table: which keys found room depends on the order of claims
        assert len(got) == len(want) == capacity
        assert lost > 0 and int(st_whole["overflow"]) > 0
    assert all(key == ~kh for (kh, _ws), (key, _slot) in got.items())
    assert not np.asarray(st_parts["occ"])[capacity]


def test_a_key_that_recurs_across_chunks_takes_one_slot():
    capacity, n, chunk = 256, 64, 8
    kh = np.arange(n, dtype=np.int64) % 5 + 77   # five keys, in every chunk
    ws = np.zeros(n, np.int64)
    store, slots, _, _ = _chunked_insert(capacity, chunk)(
        _empty_store(capacity), kh, ws, np.ones(n, bool))
    slots = np.asarray(slots)
    assert int(np.asarray(store["occ"]).sum()) == 5
    for key in range(77, 82):
        assert len(set(slots[kh == key].tolist())) == 1
    assert len(set(slots.tolist())) == 5


def test_rows_inside_the_first_chunk_visit_one_chunk():
    """The chunk loop ends at the last chunk that holds a row: a batch whose
    rows end inside the first chunk runs the rounds of that chunk alone, and
    every one of them at ``chunk`` lanes."""
    capacity, n, chunk = 1 << 10, 512, 64
    rng = np.random.default_rng(5)
    kh = rng.integers(-2**62, 2**62, n)
    ws = np.zeros(n, np.int64)
    act = np.arange(n) < 40
    base, _, _, _ = _chunked_insert(capacity, n)(
        _empty_store(capacity), rng.integers(-2**62, 2**62, n), ws, np.ones(n, bool))
    _, slots, rounds, lanes = _chunked_insert(capacity, chunk)(base, kh, ws, act)
    _, first, first_rounds, _ = _chunked_insert(capacity, chunk)(
        base, kh[:chunk], ws[:chunk], act[:chunk])
    assert int(rounds) == int(first_rounds) > 0
    assert int(lanes) == int(rounds) * chunk
    assert (np.asarray(slots)[:chunk] == np.asarray(first)).all()
    assert (np.asarray(slots)[chunk:] == capacity).all()
    # rows in the last chunk alone: the empty chunks before it cost no round
    act_last = np.arange(n) >= n - 40
    _, _, rounds_last, lanes_last = _chunked_insert(capacity, chunk)(base, kh, ws, act_last)
    _, _, want_last, _ = _chunked_insert(capacity, chunk)(
        base, kh[-chunk:], ws[-chunk:], act_last[-chunk:])
    assert int(rounds_last) == int(want_last) > 0
    assert int(lanes_last) == int(want_last) * chunk


def test_a_batch_with_no_row_leaves_the_store_alone():
    capacity, n, chunk = 1 << 8, 128, 16
    rng = np.random.default_rng(6)
    kh = rng.integers(-2**62, 2**62, n)
    ws = np.zeros(n, np.int64)
    insert = _chunked_insert(capacity, chunk)
    store, _, _, _ = insert(_empty_store(capacity), kh, ws, np.ones(n, bool))
    after, slots, rounds, lanes = insert(store, kh[::-1].copy(), ws, np.zeros(n, bool))
    assert int(rounds) == 0 and int(lanes) == 0
    assert (np.asarray(slots) == capacity).all()
    assert sorted(after) == sorted(store)
    for name in store:
        assert (np.asarray(after[name]) == np.asarray(store[name])).all(), name


@pytest.mark.parametrize("chunk", [64, 8], ids=["one_chunk", "eight_chunks"])
def test_graves_are_reclaimed_by_their_key_and_walked_past_by_others(chunk):
    """A freed slot stays in its probe chain.  Its own key, inserted again
    from a later chunk, takes it back; another key that hashes onto it walks
    on to the next empty slot."""
    capacity, n = 64, 64
    mask = capacity - 1
    home = lambda k: int(hs.np_mix64(np.array([k], np.int64))[0] & mask)
    freed = 1000
    rival = next(k for k in range(2000, 9000) if home(k) == home(freed))
    insert = _chunked_insert(capacity, chunk)
    ws = np.zeros(n, np.int64)
    first = np.zeros(n, np.int64)
    first[0] = freed
    store, slots, _, _ = insert(_empty_store(capacity), first, ws, np.arange(n) == 0)
    slot = int(np.asarray(slots)[0])
    assert slot == home(freed)
    store = dict(store)                       # what an eviction leaves
    store["occ"] = store["occ"].at[slot].set(False)
    store["grave"] = store["grave"].at[slot].set(True)
    # the rival in the first chunk, the freed key back in the last
    again = np.zeros(n, np.int64)
    again[1], again[n - 2] = rival, freed
    act = np.zeros(n, bool)
    act[[1, n - 2]] = True
    store, slots, _, _ = insert(store, again, ws, act)
    slots = np.asarray(slots)
    assert slots[n - 2] == slot and slots[1] == (slot + 1) & mask
    assert bool(store["occ"][slot]) and not bool(store["grave"][slot])
    assert int(store["khash"][slot]) == freed and int(store["key0"][slot]) == ~freed
    assert int(np.asarray(store["occ"]).sum()) == 2 and int(store["overflow"]) == 0


def test_a_batch_no_wider_than_the_chunk_traces_the_one_loop():
    """At or under ``_PROBE_CHUNK`` lanes the program is the whole-batch
    loop as it was, and one multiplication for ``lane_rounds``."""
    capacity = 1 << 10

    def primitives(n):
        jaxpr = jax.make_jaxpr(lambda st, k: hs.probe_insert(
            st, capacity, k, k, [k], jnp.zeros(n, jnp.int32), k == 0))(
                _empty_store(capacity), jnp.zeros(n, jnp.int64))
        return [eqn.primitive.name for eqn in jaxpr.jaxpr.eqns]

    narrow = primitives(hs._PROBE_CHUNK)
    assert narrow[-1] == "mul" and narrow.count("while") == 1  # lane_rounds
    kh = jnp.zeros(hs._PROBE_CHUNK, jnp.int64)
    whole = jax.make_jaxpr(lambda st, k: hs._insert_lanes(
        st, capacity, k, k, [k], jnp.zeros(k.shape, jnp.int32), k == 0))(
            _empty_store(capacity), kh)
    assert narrow[:-1] == [eqn.primitive.name for eqn in whole.jaxpr.eqns]
    wide = primitives(4 * hs._PROBE_CHUNK)
    assert wide.count("while") == 1 and wide != narrow  # the chunk loop holds the other
