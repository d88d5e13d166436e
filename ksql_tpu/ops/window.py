"""Event-time window assignment — static-shape, branch-free.

The reference assigns windows per record inside Kafka Streams
(TimeWindows/SessionWindows via StreamAggregateBuilder.java:142-352).  On
device, assignment is columnar arithmetic over the timestamp vector:

* TUMBLING: one window per row — ``start = ts - ts mod size``.
* HOPPING: every row belongs to ``k = ceil(size/advance)`` windows (k is a
  compile-time constant), so the batch is expanded k-fold by tiling — XLA
  sees a static (k·n)-row batch; out-of-range expansions are masked, never
  branched.

SESSION windows are data-dependent merges: their device formulation is the
sort-free segment merge of ``ops/session_merge.py``, driven by the session
step in runtime/lowering.py (per-key session slots, grown on overflow); the
helpers here assign fixed windows only.

Stream slicing (the Partial Partial Aggregates / Enthuse formulation): the
k-fold hopping expansion is the *baseline*; decomposable aggregates instead
assign each record to exactly ONE slice of width ``gcd(size, advance)`` and
combine the covering slices per window at emission.  Slice boundaries
subdivide both the advance grid and the window-size grid, so every record
in a slice belongs to exactly the same set of covering windows — the
defining property that makes per-slice partials shareable across the
windows (and, one level up, across a whole *window family* of queries).
The helpers here are the pure slice-grid arithmetic; the ring-store layout
and combine kernels live in runtime/lowering.py.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def running_max(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running maximum (per-record stream time over a batch).

    Same values as ``jax.lax.cummax``, which lowers to a reduce-window: on
    int64 the v5e compiler takes 7 s for it at 32,768 elements, 54 s at
    40,960 and 81 s at 65,536; the scan form takes about 1 s at any of
    them."""
    return jax.lax.associative_scan(jnp.maximum, x)


def tumbling_starts(ts: jnp.ndarray, size_ms: int) -> jnp.ndarray:
    return ts - jnp.remainder(ts, size_ms)


def hopping_expansion(size_ms: int, advance_ms: int) -> int:
    return -(-size_ms // advance_ms)  # ceil


# ------------------------------------------------------------- stream slicing
def slice_width(size_ms: int, advance_ms: int) -> int:
    """Width of one slice for a (size, advance) hopping window — the finest
    grid on which both window starts (advance-aligned) and window ends
    (start + size) land, so a slice is never split by a window boundary."""
    return math.gcd(size_ms, advance_ms)


def slices_per_window(size_ms: int, width_ms: int) -> int:
    """Covering slices per window (width divides size by construction)."""
    return size_ms // width_ms


def slice_starts(ts: jnp.ndarray, width_ms: int) -> jnp.ndarray:
    """The one slice each record belongs to (cf. the k-fold
    hopping_starts expansion this replaces)."""
    return ts - jnp.remainder(ts, width_ms)


def hopping_starts(
    ts: jnp.ndarray, size_ms: int, advance_ms: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expand n rows to (k·n) window assignments.

    Returns (starts[k*n], in_window[k*n]); caller tiles the row columns with
    ``jnp.tile(col, k)`` to match.  Ordering: expansion-major (all rows for
    hop 0, then hop 1, ...), matching ``jnp.tile``.
    """
    k = hopping_expansion(size_ms, advance_ms)
    n = ts.shape[0]
    first = ts - jnp.remainder(ts, advance_ms)  # newest window start
    hops = jnp.repeat(jnp.arange(k, dtype=ts.dtype), n)  # [0..0,1..1,...]
    ts_t = jnp.tile(ts, k)
    starts = jnp.tile(first, k) - hops * advance_ms
    ok = (starts >= 0) & (starts + size_ms > ts_t)
    return starts, ok


def expand(col: jnp.ndarray, k: int) -> jnp.ndarray:
    """Tile a row column to match hopping_starts' (k·n) expansion."""
    return jnp.tile(col, k)
