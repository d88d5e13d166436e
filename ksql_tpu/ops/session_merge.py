"""Merged (key, start) order of a SESSION step's items without sorting them.

The session step (runtime/lowering.py ``post_session_exchange``) lays its
items out as ``[batch rows | stored session 0 | ... | stored session S-1]``
(``S + 1`` blocks of ``n``), and needs them in (key, start, item index)
order for the segmented interval-merge.  Sorting all ``m = n * (S + 1)``
items is what the step used to do; XLA's TPU sort is a fully unrolled
network whose compile time grows with both the length and the number of
operand words (measured with the v5e compiler: ~30 s per 32-bit operand
word once the length passes 32,768 — minutes for the two-int64-key sort
of 40,960 items the engine defaults produce).

Only the ``n`` rows need a sort.  A key's stored sessions hang off the
key's first row (at most ``S`` of them), so every item's place in the
merged order is arithmetic over the sorted rows: a row sits after the
rows of its key that sort before it and the stored sessions that start
before it; a stored session sits after the rows at or before its start
and the stored sessions that start before it.  Dead items (padding rows,
absent or expired sessions) never merge with anything — the caller gives
them unique keys — so they take the positions past the live ones.

The rows' own sort is the same network's problem at the served batch: one
sort of 32,768 rows compiles in ~83 s (v5e compiler), and a batched sort
costs what one of its total length does.  So past ``SORT_RUN`` rows the
rows are sorted in runs of ``SORT_RUN`` by one sort inside a loop, compiled
once, and the runs merged pairwise by binary search (``_sort_keys``): the
step compiles in 28 s cold on the chip at 32,768 rows, 1 s from the cache.

Because the order puts the live items first, everything after it — the
item gathers, the segmented merge, the store rewrite and the emission — can
run over a prefix of the merged order.  A gather, a scatter or a segment
reduction costs per index on the chip, and at 32,768 rows the merge's
``m`` = 163,840 items took 210-240 ms of device time whatever the batch held
(the benchmark's NEXmark q11 cell: ~4,700 live items in a served tick of
4,096 bids; ``PERF.md``).  So the step runs that work over the first
``WORK_LANES`` merged positions when its live items fit them, and over all
``m`` when they do not: the same result either way, since a dead item never
merges, writes the store or emits.  What stays ``n`` wide or wider is the
row sort, the lookups of the stored sessions and this module's order.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_I64_MAX = np.iinfo(np.int64).max
#: most rows the step sorts in one piece (``_sort_keys``): the sort's
#: compile time grows faster than its length — measured with the v5e
#: compiler for these five operand words: 20 s at 16,384 rows, 83 s at
#: 32,768, and a batched sort costs what one of its total length does
SORT_RUN = 8192
#: merged positions the session step's per-item work runs over when the
#: live items fit them (the whole ``m`` items otherwise; a step whose ``m``
#: is no wider runs at ``m``): a served tick of 4,096 bids holds ~4,700
#: live items, a fill batch of 32,768 rows ~33-37k
WORK_LANES = 8192


class RowOrder(NamedTuple):
    """The batch rows sorted by (key, ts, row index), dead rows last."""

    rows: jnp.ndarray  # int32[n]: row index at each sorted position
    active: jnp.ndarray  # bool[n]: that row is live
    ts: jnp.ndarray  # int64[n]: its timestamp
    group: jnp.ndarray  # int32[n]: its key's rank among the batch's keys
    first_row: jnp.ndarray  # int32[n]: per group, lowest live row index (n if none)
    first_occ: jnp.ndarray  # bool[n], ROW order: first live row of its key


def _lex_less(a, b) -> jnp.ndarray:
    """(key, ts, row) ``a`` before ``b``, element-wise."""
    (ka, ta, ia), (kb, tb, ib) = a, b
    return (ka < kb) | ((ka == kb) & ((ta < tb) | ((ta == tb) & (ia < ib))))


def _sort_keys(keys: Tuple[jnp.ndarray, ...]) -> Tuple[jnp.ndarray, ...]:
    """``keys`` = (key, ts, row index) sorted by all three (the index makes
    every triple distinct; the top-K fold, ``hash_store._vec_topk``, sorts
    (slot, value descending, row index) with it).  Up to ``SORT_RUN`` items one sort does it;
    beyond, the items are sorted in runs of ``SORT_RUN`` by one sort inside
    a loop (compiled once), and the runs merged pairwise: an item's place
    in a merged pair is its place in its own run plus the items of the
    other run that precede it, found by binary search."""
    n = keys[0].shape[0]
    if n <= SORT_RUN:
        return tuple(jax.lax.sort(keys, num_keys=3))
    runs = 1 << (-(-n // SORT_RUN) - 1).bit_length()
    size = runs * SORT_RUN
    pad = size - n
    if pad:  # padding sorts past every item: (MAX, MAX, index >= n)
        keys = (
            jnp.concatenate([keys[0], jnp.full(pad, _I64_MAX, keys[0].dtype)]),
            jnp.concatenate([keys[1], jnp.full(pad, _I64_MAX, keys[1].dtype)]),
            jnp.arange(size, dtype=jnp.int32).at[:n].set(keys[2]),
        )
    keys = jax.lax.map(
        lambda run: jax.lax.sort(run, num_keys=3),
        tuple(k.reshape(runs, SORT_RUN) for k in keys),
    )
    keys = tuple(k.reshape(-1) for k in keys)
    pos = jnp.arange(size, dtype=jnp.int32)
    length = SORT_RUN
    while length < size:
        own = pos % length
        other = (pos // length ^ 1) * length  # the partner run's first item

        def halve(_, bounds):
            lo, hi = bounds
            mid = jnp.minimum((lo + hi) // 2, length - 1)
            before = _lex_less(tuple(k[other + mid] for k in keys), keys) & (lo < hi)
            return jnp.where(before, mid + 1, lo), jnp.where(before | (lo >= hi), hi, mid)

        count, _ = jax.lax.fori_loop(
            0, length.bit_length(), halve,
            (jnp.zeros(size, jnp.int32), jnp.full(size, length, jnp.int32)),
        )
        at = pos // (2 * length) * (2 * length) + own + count
        keys = tuple(
            jnp.zeros_like(k).at[at].set(k, unique_indices=True) for k in keys
        )
        length *= 2
    return tuple(k[:n] for k in keys)


def sort_rows(khash: jnp.ndarray, ts: jnp.ndarray, active: jnp.ndarray) -> RowOrder:
    n = khash.shape[0]
    # dead rows sort last: (MAX, MAX) can only tie with a live row whose
    # hash AND timestamp are both int64 max
    ks, tss, rows = _sort_keys((
        jnp.where(active, khash, _I64_MAX),
        jnp.where(active, ts, _I64_MAX),
        jnp.arange(n, dtype=jnp.int32),
    ))
    act = active[rows]
    new_key = jnp.concatenate([jnp.ones(1, bool), ks[1:] != ks[:-1]])
    group = jnp.cumsum(new_key.astype(jnp.int32)) - 1
    first_row = jax.ops.segment_min(
        jnp.where(act, rows, n), group, num_segments=n
    )
    is_first = act & (rows == first_row[group])
    first_occ = jnp.zeros(n, bool).at[rows].set(is_first, unique_indices=True)
    return RowOrder(rows, act, tss, group, first_row, first_occ)


def merged_order(
    order: RowOrder,
    active: jnp.ndarray,
    st_alive: jnp.ndarray,
    st_start: jnp.ndarray,
) -> jnp.ndarray:
    """Permutation putting the items in (key, start, item index) order.

    ``active`` is the rows' live mask in row order.  ``st_alive`` /
    ``st_start`` are ``[S, n]``: stored session ``i`` of the key whose
    first live row is ``r`` sits at ``[i, r]`` (alive nowhere else).
    Returns int32[m]: the item index at each merged position, live items
    first, in exactly the relative order ``jnp.lexsort((start, key))``
    gives them; dead items follow in item order."""
    S, n = st_alive.shape
    m = n * (S + 1)
    rows, act, ts, group = order.rows, order.active, order.ts, order.group

    # per group: its stored sessions, the size of both lists, and where
    # the group starts in the merged order
    has_rows = order.first_row < n
    at = jnp.minimum(order.first_row, n - 1)
    g_alive = st_alive[:, at] & has_rows
    g_start = st_start[:, at]
    n_rows = jax.ops.segment_sum(act.astype(jnp.int32), group, num_segments=n)
    n_stored = jnp.sum(g_alive, axis=0, dtype=jnp.int32)
    g_base = jnp.cumsum(n_rows + n_stored) - (n_rows + n_stored)

    # row j: rows of its key sorted before it + stored sessions starting
    # before it (at a tie the row comes first: lower item index)
    r_alive, r_start = g_alive[:, group], g_start[:, group]
    later = r_alive & act & (r_start < ts)  # [S, n]: row j is after session i
    in_group = (jnp.cumsum(act.astype(jnp.int32)) - 1) - (
        jnp.cumsum(n_rows) - n_rows
    )[group]
    row_pos = g_base[group] + in_group + jnp.sum(later, axis=0, dtype=jnp.int32)

    # stored session i: rows at or before its start + stored sessions
    # starting before it (ties by slot index: lower item index)
    rows_after = jax.ops.segment_sum(
        later.T.astype(jnp.int32), group, num_segments=n
    ).T  # [S, groups]
    i = jnp.arange(S)
    before = g_alive[None, :, :] & (
        (g_start[None, :, :] < g_start[:, None, :])
        | ((g_start[None, :, :] == g_start[:, None, :])
           & (i[None, :, None] < i[:, None, None]))
    )  # [i, i', groups]: session i' precedes session i
    g_pos = g_base + (n_rows - rows_after) + jnp.sum(before, axis=1, dtype=jnp.int32)

    # back to item order: [rows | stored 0 | ... | stored S-1]
    row_of = jnp.zeros(n, jnp.int32).at[rows].set(row_pos, unique_indices=True)
    group_of = jnp.zeros(n, jnp.int32).at[rows].set(group, unique_indices=True)
    alive = jnp.concatenate([active, st_alive.reshape(-1)])
    pos = jnp.concatenate([row_of, g_pos[:, group_of].reshape(-1)])
    n_alive = jnp.sum(alive, dtype=jnp.int32)
    dead_pos = n_alive + jnp.cumsum((~alive).astype(jnp.int32)) - 1
    pos = jnp.where(alive, pos, dead_pos)
    return jnp.zeros(m, jnp.int32).at[pos].set(
        jnp.arange(m, dtype=jnp.int32), unique_indices=True
    )
