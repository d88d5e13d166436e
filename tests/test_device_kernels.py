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
def _fill(capacity, n_keys, batch):
    layout = hs.StoreLayout(capacity=capacity, num_keys=1, components=())
    store = hs.init_store(layout)
    insert = jax.jit(
        lambda st, kh, act: hs.probe_insert(
            st, capacity, kh, jnp.zeros_like(kh), [kh],
            jnp.zeros(kh.shape, jnp.int32), act,
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


@pytest.mark.parametrize("capacity", [1 << 10, 1 << 13, 1 << 16])
def test_store_holds_seventy_percent_load(capacity):
    """The host grows a store at 75 % occupancy, so the store has to hold
    that: a fixed 32 probe rounds (what the loop was) lost rows from about
    45 % in tables this size and up — the longest probe sequence here is
    well past 32."""
    n_keys = int(0.7 * capacity)
    store, keys, slots = _fill(capacity, n_keys, batch=512)
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


def test_full_store_still_reports_overflow():
    """The probe loop is bounded by the table: rows that cannot be placed
    are counted, not looped over for ever."""
    capacity = 64
    store, _keys, _slots = _fill(capacity, capacity + 8, batch=24)
    assert int(np.asarray(store["occ"])[:-1].sum()) == capacity
    assert int(store["overflow"]) == 8
