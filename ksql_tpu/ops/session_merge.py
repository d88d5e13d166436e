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
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_I64_MAX = np.iinfo(np.int64).max


class RowOrder(NamedTuple):
    """The batch rows sorted by (key, ts, row index), dead rows last."""

    rows: jnp.ndarray  # int32[n]: row index at each sorted position
    active: jnp.ndarray  # bool[n]: that row is live
    ts: jnp.ndarray  # int64[n]: its timestamp
    group: jnp.ndarray  # int32[n]: its key's rank among the batch's keys
    first_row: jnp.ndarray  # int32[n]: per group, lowest live row index (n if none)
    first_occ: jnp.ndarray  # bool[n], ROW order: first live row of its key


def sort_rows(khash: jnp.ndarray, ts: jnp.ndarray, active: jnp.ndarray) -> RowOrder:
    n = khash.shape[0]
    # dead rows sort last: (MAX, MAX) can only tie with a live row whose
    # hash AND timestamp are both int64 max
    ks, tss, rows = jax.lax.sort(
        (
            jnp.where(active, khash, _I64_MAX),
            jnp.where(active, ts, _I64_MAX),
            jnp.arange(n, dtype=jnp.int32),
        ),
        num_keys=2,
        is_stable=True,
    )
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
