"""HBM-resident keyed state store — the RocksDB analog.

The reference materializes every aggregation/table in RocksDB via JNI
(ksqldb-rocksdb-config-setter/.../KsqlBoundedMemoryRocksDBConfigSetter.java:35,
Materialized stores in StreamAggregateBuilder.java).  The TPU design keeps
state *on device*: an open-addressing hash table laid out as structure-of-
arrays in HBM, updated by vectorized gather/scatter — no sort, no host
round-trip, no dynamic shapes.

Layout (all arrays length ``capacity + 1``; the last slot is the *dump slot*
that absorbs writes from inactive/overflowed lanes so every scatter has a
static target):

* ``occ``      bool      — slot occupied
* ``khash``    int64     — combined group-key hash (probe identity)
* ``wstart``   int64     — window start ms (0 when unwindowed)
* ``key<i>``   int64     — raw 64-bit repr of key column i (for emission)
* ``knull``    int32     — bitmask of NULL key columns
* ``dirty``    bool      — updated since last suppress flush (EMIT FINAL)
* ``a<j>``     per-aggregate component arrays (see device_aggs.py)

Insert algorithm (per chunk of ``_PROBE_CHUNK`` consecutive lanes of a
batch, chunks in row order, fully vectorized over a chunk's rows):
repeat until every active row is resolved — gather candidate slot; if it
matches, resolve; if empty, *claim* it by scatter-min of the row index and
let the winner write its key (losers re-examine the slot next round: if the
winner had the same key they resolve to it, otherwise they advance along
the probe sequence).  The loop stops as soon as no row is pending, so a
chunk pays for the longest probe sequence it holds, not for a fixed count
(``MAX_PROBES`` only bounds it), and a batch pays for the chunks that hold
a row, not for its padded shape: every round costs what its lanes cost,
0.070 ms at 256 lanes against 6.92 ms at 32,768 on one v5e chip (2^21
slots at 47 % load; my chip runs, PR 26).  Rows still unresolved at the
bound land in the dump slot and are counted in ``overflow`` — the host
reacts by growing the table (host-side rebuild), the moral equivalent of
RocksDB compaction.

Why the bound is far above what a probe usually takes: linear probing's
LONGEST sequence grows with the table as well as with its load.  Filling
tables with random keys, the longest displacement was 23 at half load in
2^16 slots, 46 in 2^20, 39 in 2^22, and 63 / 86 / 158 at 0.7 load — so a
fixed 32 rounds (what this was until PR 21) lost rows near 45 % load in
any table of a deployment's size, long before the 75 % at which the host
grows it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MAX_PROBES = 1024


def _probe_bound(capacity: int) -> int:
    """Rounds after which a probe loop gives up: past ``capacity`` every
    slot has been looked at."""
    return min(MAX_PROBES, capacity)

_M1 = np.array(0xBF58476D1CE4E5B9, dtype=np.uint64).view(np.int64)
_M2 = np.array(0x94D049BB133111EB, dtype=np.uint64).view(np.int64)
_GOLD = np.array(0x9E3779B97F4A7C15, dtype=np.uint64).view(np.int64)


def mix64(h: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer (logical shifts; int64 throughout)."""
    h = h ^ jax.lax.shift_right_logical(h, 30)
    h = h * _M1
    h = h ^ jax.lax.shift_right_logical(h, 27)
    h = h * _M2
    h = h ^ jax.lax.shift_right_logical(h, 31)
    return h


def combine_hash(parts: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Fold per-key-column 64-bit reprs into one group hash."""
    h = jnp.full_like(parts[0], _GOLD)
    for p in parts:
        h = mix64(h ^ p + _GOLD)
    return h


@dataclasses.dataclass(frozen=True)
class AggComponent:
    """One scatter-combined state column of an aggregate.

    ``width`` > 1 declares per-slot VECTOR state (collect/topk families):
    the store column has shape (capacity+1, width).  Vector kinds:

    * ``vec_count`` — scalar int64 count heading a collect group; the two
      following components must be ``vec_data`` (values) and ``vec_valid``
      (per-element null bits), both width-K.  ``mode`` on the vec_data
      component selects the fold: 'append' (collect_list / earliest-N,
      capped at K), 'ring' (latest-N, circular overwrite), 'set'
      (collect_set, membership-deduped append).
    * ``topk`` — self-contained width-K descending top-K of non-sentinel
      contributions; ``mode='distinct'`` dedups values (topkdistinct).
    """

    combine: str  # 'add' | 'min' | 'max' | 'argset' | 'vec_count' | 'vec_data' | 'vec_valid' | 'topk'
    dtype: str  # numpy dtype name
    init: float  # fill value for empty slots
    width: int = 1
    mode: str = ""


@dataclasses.dataclass(frozen=True)
class StoreLayout:
    capacity: int  # power of two
    num_keys: int
    components: Tuple[AggComponent, ...]
    windowed: bool = False

    def __post_init__(self):
        if self.capacity & (self.capacity - 1):
            raise ValueError("store capacity must be a power of two")


def init_store(layout: StoreLayout) -> Dict[str, jnp.ndarray]:
    c1 = layout.capacity + 1
    store = {
        "occ": jnp.zeros(c1, bool),
        # tombstoned slots: freed (evicted/deleted) but still part of probe
        # chains — linear probing must walk past them or keys inserted
        # beyond would split into duplicate slots; compaction (host rebuild
        # in _grow) reclaims them
        "grave": jnp.zeros(c1, bool),
        "khash": jnp.zeros(c1, jnp.int64),
        "wstart": jnp.zeros(c1, jnp.int64),
        "knull": jnp.zeros(c1, jnp.int32),
        "dirty": jnp.zeros(c1, bool),
        "max_ts": jnp.array(np.iinfo(np.int64).min, jnp.int64),
        "overflow": jnp.zeros((), jnp.int64),
    }
    for i in range(layout.num_keys):
        store[f"key{i}"] = jnp.zeros(c1, jnp.int64)
    for j, comp in enumerate(layout.components):
        shape = c1 if comp.width == 1 else (c1, comp.width)
        store[f"a{j}"] = jnp.full(shape, comp.init, dtype=np.dtype(comp.dtype))
    return store


#: lanes one pass of the probe loop works on.  A round costs what its lanes
#: cost, whether or not their rows still probe (serial gathers and scatters),
#: so a batch wider than this is probed in chunks and the empty ones skipped.
#: Read on one v5e chip (my chip runs, PR 26: ``probe_insert`` alone, 2^21
#: slots at 47 % load, the benchmark cell's key draw), ms a round by lanes:
#: 32,768: 6.92 | 8,192: 1.77 | 4,096: 0.97 | 2,048: 0.49 | 1,024: 0.28 |
#: 512: 0.142 | 256: 0.070 | 128: 0.045 — halving the lanes halves a round
#: down to 256 and cuts it by 1.5-1.6 below: the per-round floor.  Smaller
#: chunks also waste fewer lanes on rows that are done (each chunk stops at
#: its own longest chain), so a whole call reads, in ms at chunks of 4,096 /
#: 1,024 / 256 / 128 lanes: 4,096 rows of 32,768: 23.1 / 22.0 / 17.7 / 18.5
#: (unchunked 153.8); 480 rows: 17.1 / 5.7 / 3.6 / 3.7; a full 32,768-row
#: batch: 183 / 168 / 133 / not read (unchunked 215).
_PROBE_CHUNK = 256

#: store columns ``probe_insert`` reads or writes: what its chunk loop carries
_PROBE_COLUMNS = ("occ", "grave", "khash", "wstart", "knull", "overflow")


def _insert_lanes(store, capacity, khash, wstart, key_reprs, knull, active):
    """One pass of the probe-and-claim loop over all the lanes given, and
    the key writes that follow it: (store, slots, rounds)."""
    n = khash.shape[0]
    mask = capacity - 1
    dump = jnp.int32(capacity)
    rowidx = jnp.arange(n, dtype=jnp.int32)
    big = jnp.int32(n)
    base = (mix64(khash ^ (wstart * _GOLD)) & mask).astype(jnp.int32)

    def pending(carry):
        rounds, _occ, _grave, _kh, _ws, _slots, done, _offset = carry
        return (rounds < _probe_bound(capacity)) & jnp.any(active & ~done)

    def body(carry):
        rounds, occ, grave, kh, ws, slots, done, offset = carry
        cand = ((base + offset) & mask).astype(jnp.int32)
        c_occ = occ[cand]
        c_grave = grave[cand]
        c_used = c_occ | c_grave
        # a matching grave is reclaimed (same key re-inserted after free)
        c_match = c_used & (kh[cand] == khash) & (ws[cand] == wstart)
        newly = ~done & active & c_match
        slots = jnp.where(newly, cand, slots)
        done = done | newly
        # claim truly-empty candidates: lowest row index wins the slot.
        # Graves are NOT claimable — the key may live further down the
        # chain; compaction reclaims them.
        want = ~done & active & ~c_used
        claim = jnp.full(capacity + 1, big, jnp.int32)
        claim = claim.at[jnp.where(want, cand, dump)].min(rowidx)
        winner = want & (claim[cand] == rowidx)
        target = jnp.where(winner, cand, dump)
        occ = occ.at[target].set(True)
        occ = occ.at[capacity].set(False)
        kh = kh.at[target].set(khash)
        ws = ws.at[target].set(wstart)
        slots = jnp.where(winner, cand, slots)
        done = done | winner
        # used-by-other: advance along probe sequence; claim losers
        # re-examine the same slot next round (winner may share their key)
        offset = offset + (~done & active & c_used & ~c_match)
        return rounds + 1, occ, grave, kh, ws, slots, done, offset

    # initial carries derive from varying inputs so the loop is well-typed
    # under shard_map's varying-manual-axes tracking (and a no-op otherwise)
    zero_i32 = (khash * 0).astype(jnp.int32)
    rounds, occ, grave, kh, ws, slots, done, _ = jax.lax.while_loop(
        pending,
        body,
        (
            jnp.sum(zero_i32),
            store["occ"],
            store["grave"],
            store["khash"],
            store["wstart"],
            zero_i32 + dump,
            zero_i32 != 0,
            zero_i32,
        ),
    )
    store = dict(store)
    store["khash"], store["wstart"] = kh, ws
    store["overflow"] = store["overflow"] + jnp.sum(active & ~done)
    # key reprs/null bits: idempotent writes (same key ⇒ same repr); matched
    # graves come back alive
    target = jnp.where(done, slots, dump)
    occ = occ.at[target].set(True)
    occ = occ.at[capacity].set(False)
    store["occ"] = occ
    store["grave"] = grave.at[target].set(False)
    for i, repr_col in enumerate(key_reprs):
        store[f"key{i}"] = store[f"key{i}"].at[target].set(repr_col)
    store["knull"] = store["knull"].at[target].set(knull)
    return store, jnp.where(done, slots, dump), rounds


@jax.named_scope("probe_insert")
def probe_insert(
    store: Dict[str, jnp.ndarray],
    capacity: int,
    khash: jnp.ndarray,
    wstart: jnp.ndarray,
    key_reprs: Sequence[jnp.ndarray],
    knull: jnp.ndarray,
    active: jnp.ndarray,
    *,
    _chunk: int = _PROBE_CHUNK,
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Resolve (and create) one slot per active row; returns (store, slots,
    rounds, lane_rounds).

    ``slots`` is int32 per row; inactive/overflowed rows get the dump slot
    ``capacity``.  Rows are probed ``W = min(n, _chunk)`` consecutive lanes
    at a time, in row order, and a chunk with no active row is not visited.
    ``rounds`` (int32 scalar) is how many times the probe loop ran, summed
    over the chunks visited: per chunk the longest probe sequence among
    its rows, claim retries included — 0 for a batch with no active row.
    ``lane_rounds`` is the lanes those rounds worked on: every round of a
    chunk pays for all ``W`` of its lanes, so ``rounds * W``.
    """
    n = khash.shape[0]
    width = min(n, _chunk)
    if n == width:
        store, slots, rounds = _insert_lanes(
            store, capacity, khash, wstart, key_reprs, knull, active
        )
        return store, slots, rounds, rounds * width
    n_chunks = -(-n // width)
    pad = n_chunks * width - n
    if pad:  # whole chunks: the padding lanes are inactive
        khash, wstart, knull, active, *key_reprs = (
            jnp.pad(x, (0, pad))
            for x in (khash, wstart, knull, active, *key_reprs)
        )
    columns = _PROBE_COLUMNS + tuple(f"key{i}" for i in range(len(key_reprs)))
    chunk_ids = jnp.arange(n_chunks, dtype=jnp.int32)
    occupied = jnp.any(active.reshape(n_chunks, width), axis=1)

    def next_chunk(after):
        """The first chunk past ``after`` that holds an active row."""
        return jnp.min(
            jnp.where(occupied & (chunk_ids > after), chunk_ids, n_chunks)
        )

    def visit(carry):
        chunk, rounds, tables, slots = carry
        lo = chunk * width
        khash_c, wstart_c, knull_c, active_c, *reprs_c = (
            jax.lax.dynamic_slice_in_dim(x, lo, width)
            for x in (khash, wstart, knull, active, *key_reprs)
        )
        tables, slots_c, rounds_c = _insert_lanes(
            tables, capacity, khash_c, wstart_c, reprs_c, knull_c, active_c
        )
        slots = jax.lax.dynamic_update_slice_in_dim(slots, slots_c, lo, 0)
        return next_chunk(chunk), rounds + rounds_c, tables, slots

    # initial carries derive from varying inputs, as in ``_insert_lanes``
    zero_i32 = (khash * 0).astype(jnp.int32)
    _, rounds, tables, slots = jax.lax.while_loop(
        lambda carry: carry[0] < n_chunks,
        visit,
        (
            next_chunk(jnp.sum(zero_i32) - 1),
            jnp.sum(zero_i32),
            {name: store[name] for name in columns},
            zero_i32 + jnp.int32(capacity),
        ),
    )
    return {**store, **tables}, slots[:n], rounds, rounds * width


@jax.named_scope("probe_find")
def probe_find(
    store: Dict[str, jnp.ndarray],
    capacity: int,
    khash: jnp.ndarray,
    wstart: jnp.ndarray,
    active: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Find-only probe (no insertion): (slots, rounds) — one slot per
    active row, or the dump slot ``capacity`` when the key is absent, and
    how many times the probe loop ran (as ``probe_insert``).  Used by join
    lookups against a keyed store."""
    mask = capacity - 1
    dump = jnp.int32(capacity)
    base = (mix64(khash ^ (wstart * _GOLD)) & mask).astype(jnp.int32)

    def pending(carry):
        rounds, _slots, done, _offset = carry
        return (rounds < _probe_bound(capacity)) & jnp.any(active & ~done)

    def body(carry):
        rounds, slots, done, offset = carry
        cand = ((base + offset) & mask).astype(jnp.int32)
        c_occ = store["occ"][cand]
        c_used = c_occ | store["grave"][cand]
        # live match only — a grave means the key was deleted
        c_match = c_occ & (store["khash"][cand] == khash) & (
            store["wstart"][cand] == wstart
        )
        newly = ~done & active & c_match
        slots = jnp.where(newly, cand, slots)
        # a truly-empty slot terminates the probe sequence: key absent
        # (graves are walked past — the key may live further down)
        done = done | newly | ~c_used
        offset = offset + (~done & active)
        return rounds + 1, slots, done, offset

    zero_i32 = (khash * 0).astype(jnp.int32)
    rounds, slots, _, _ = jax.lax.while_loop(
        pending, body,
        (jnp.sum(zero_i32), zero_i32 + dump, zero_i32 != 0, zero_i32),
    )
    return jnp.where(active, slots, dump), rounds


def _slot_ranks(eff: jnp.ndarray) -> jnp.ndarray:
    """Arrival-stable rank of each row within its slot group (rows at the
    dump slot still get ranks — callers mask them out)."""
    n = eff.shape[0]
    order = jnp.argsort(eff, stable=True)
    ss = eff[order]
    idx = jnp.arange(n, dtype=jnp.int32)
    prev = jnp.concatenate([jnp.full((1,), -1, ss.dtype), ss[:-1]])
    run_start = jax.lax.cummax(jnp.where(ss != prev, idx, -1).at[0].set(0))
    return jnp.zeros(n, jnp.int32).at[order].set(idx - run_start)


def _sort_desc(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.sort(x, axis=-1)[..., ::-1]


def _desc_key(vals: jnp.ndarray) -> jnp.ndarray:
    """A monotone-decreasing sort key (no overflow at dtype min)."""
    if jnp.issubdtype(vals.dtype, jnp.integer):
        return ~vals
    return -vals


def _batch_membership(cnt_col, data_col, vbit_col, K, eff0, vals, vbits):
    """Shared set-style batched dedup: (member-of-stored-prefix,
    first-in-batch-occurrence) masks for per-slot (value, null-bit) pairs —
    used by collect_set insertion and histogram/attr appends."""
    n = vals.shape[0]
    cnt_before_row = cnt_col[eff0]
    pos_idx = jnp.arange(K)
    occ_mask = pos_idx[None, :] < jnp.minimum(cnt_before_row, K)[:, None]
    eq = (data_col[eff0] == vals[:, None]) & (vbit_col[eff0] == vbits[:, None])
    member = jnp.any(eq & occ_mask, axis=1)
    order = jnp.lexsort((vbits, vals, eff0))
    so_eff, so_v, so_b = eff0[order], vals[order], vbits[order]
    diff = (
        (so_eff != jnp.concatenate([jnp.full((1,), -1, so_eff.dtype), so_eff[:-1]]))
        | (so_v != jnp.concatenate([so_v[:1] + 1, so_v[:-1]]))
        | (so_b != jnp.concatenate([so_b[:1] + 1, so_b[:-1]]))
    ).at[0].set(True)
    firsts = jnp.zeros(n, bool).at[order].set(diff)
    return member, firsts


def _vec_collect(store, layout, j, contribs, slots, dump):
    """collect_list/collect_set/earliest-N/latest-N group fold: components
    j (count), j+1 (values, width K), j+2 (element null bits, width K)."""
    data_comp = layout.components[j + 1]
    K = data_comp.width
    cnt_col = store[f"a{j}"]
    data_col = store[f"a{j + 1}"]
    vbit_col = store[f"a{j + 2}"]
    ok = contribs[j] > 0
    vals = contribs[j + 1].astype(data_col.dtype)
    vbits = contribs[j + 2].astype(vbit_col.dtype)
    n = vals.shape[0]
    contributing = ok & (slots != dump)
    if data_comp.mode == "set":
        # membership against stored elements (value + null-bit equality over
        # the occupied prefix), then in-batch first-occurrence dedup
        eff0 = jnp.where(contributing, slots, dump)
        member, firsts = _batch_membership(
            cnt_col, data_col, vbit_col, K, eff0, vals, vbits
        )
        new = contributing & ~member & firsts
    else:
        new = contributing
    eff = jnp.where(new, slots, dump)
    rank = _slot_ranks(eff)
    pos = cnt_col[eff].astype(jnp.int32) + rank
    if data_comp.mode == "ring":
        # >K contributions to one slot in a batch wrap the ring: keep only
        # the LAST K so scatter positions stay distinct (duplicate indices
        # in .at[].set resolve in undefined order)
        n_slot = jnp.zeros(layout.capacity + 1, jnp.int32).at[eff].add(
            new.astype(jnp.int32)
        )
        end_pos = cnt_col[eff].astype(jnp.int32) + n_slot[eff]
        write = new & (pos >= end_pos - K)
        tgt_pos = (pos % K).astype(jnp.int32)
    else:  # 'append' / 'set': capped at K, count keeps the logical total
        write = new & (pos < K)
        tgt_pos = jnp.clip(pos, 0, K - 1)
    tgt_slot = jnp.where(write, eff, dump)
    store[f"a{j + 1}"] = data_col.at[tgt_slot, tgt_pos].set(vals)
    store[f"a{j + 2}"] = vbit_col.at[tgt_slot, tgt_pos].set(vbits)
    store[f"a{j}"] = cnt_col.at[eff].add(new.astype(cnt_col.dtype))


def _vec_remove(store, layout, j, contribs, slots, dump):
    """Collect-list undo: remove the FIRST stored occurrence of each undo
    row's value from its slot's vector, compacting left (order-preserving)
    — CollectListUdaf.undo semantics for table-aggregation retractions.

    Duplicate undo rows for one (slot, value) claim successive occurrences;
    one winner row per touched slot gathers the slot's removal bitmap,
    compacts the K-vector, and scatters it back."""
    data_comp = layout.components[j + 1]
    K = data_comp.width
    cnt_col = store[f"a{j}"]
    data_col = store[f"a{j + 1}"]
    vbit_col = store[f"a{j + 2}"]
    head = contribs[j]
    vals = contribs[j + 1].astype(data_col.dtype)
    vbits = contribs[j + 2].astype(vbit_col.dtype)
    n = vals.shape[0]
    pos_idx = jnp.arange(K, dtype=jnp.int32)
    rowidx = jnp.arange(n, dtype=jnp.int32)
    removing = (head < 0) & (slots != dump)
    eff = jnp.where(removing, slots, dump)
    # rank among same-(slot, value) undo rows: the r-th duplicate claims
    # the r-th stored occurrence
    order = jnp.lexsort((rowidx, vbits, vals, eff))
    so_eff, so_v, so_b = eff[order], vals[order], vbits[order]
    prev_eff = jnp.concatenate([jnp.full((1,), -1, so_eff.dtype), so_eff[:-1]])
    prev_v = jnp.concatenate([so_v[:1] + 1, so_v[:-1]])
    prev_b = jnp.concatenate([so_b[:1] + 1, so_b[:-1]])
    new_run = (so_eff != prev_eff) | (so_v != prev_v) | (so_b != prev_b)
    sidx = jnp.arange(n, dtype=jnp.int32)
    run_start = jax.lax.cummax(jnp.where(new_run, sidx, 0))
    row_rank = jnp.zeros(n, jnp.int32).at[order].set(sidx - run_start)
    occ = pos_idx[None, :] < jnp.minimum(cnt_col[eff], K).astype(jnp.int32)[:, None]
    match = (
        (data_col[eff] == vals[:, None])
        & (vbit_col[eff] == vbits[:, None])
        & occ
    )
    pos_rank = jnp.cumsum(match, axis=1) - 1
    claim = match & (pos_rank == row_rank[:, None]) & removing[:, None]
    # one winner row per touched slot accumulates the slot's bitmap
    first = jnp.full(layout.capacity + 1, n, jnp.int32).at[eff].min(
        jnp.where(removing, rowidx, n)
    )
    wrow = jnp.where(removing, first[eff], n)  # n = discard row
    rem = jnp.zeros((n + 1, K), bool).at[wrow].max(claim)[:n]
    is_winner = removing & (first[eff] == rowidx)
    effw = jnp.where(is_winner, slots, dump)
    cnt_w = jnp.minimum(cnt_col[effw], K).astype(jnp.int32)
    cur_d = data_col[effw]
    cur_b = vbit_col[effw]
    keep = (~rem) & (pos_idx[None, :] < cnt_w[:, None])
    new_pos = (jnp.cumsum(keep, axis=1) - 1).astype(jnp.int32)
    tgt_pos = jnp.where(keep, new_pos, K - 1)
    out_d = jnp.zeros((n, K), cur_d.dtype).at[rowidx[:, None], tgt_pos].add(
        jnp.where(keep, cur_d, 0)
    )
    out_b = jnp.zeros((n, K), cur_b.dtype).at[rowidx[:, None], tgt_pos].add(
        jnp.where(keep, cur_b, 0)
    )
    n_removed = jnp.sum(rem & (pos_idx[None, :] < cnt_w[:, None]), axis=1)
    store[f"a{j + 1}"] = data_col.at[effw].set(out_d)
    store[f"a{j + 2}"] = vbit_col.at[effw].set(out_b)
    store[f"a{j}"] = cnt_col.at[effw].add(-n_removed.astype(cnt_col.dtype))


def _vec_hist(store, layout, j, contribs, slots, dump):
    """Histogram group fold: components j (distinct count head), j+1
    (value codes, width K), j+2 (element bits), j+3 (per-element counts).

    Phase 1 appends NEW distinct values set-style (insert rows only —
    head contribution > 0); phase 2 scatter-adds each row's signed head
    contribution to its value's count, so undo decrements in place and
    zero-count entries read as absent at finalize."""
    data_comp = layout.components[j + 1]
    K = data_comp.width
    cnt_col = store[f"a{j}"]
    data_col = store[f"a{j + 1}"]
    vbit_col = store[f"a{j + 2}"]
    num_col = store[f"a{j + 3}"]
    head = contribs[j]
    vals = contribs[j + 1].astype(data_col.dtype)
    vbits = contribs[j + 2].astype(vbit_col.dtype)
    n = vals.shape[0]
    contributing = (head != 0) & (slots != dump)
    inserting = (head > 0) & (slots != dump)
    pos_idx = jnp.arange(K)
    # ---- phase 1: set-style append of new distinct values (cap K)
    eff0 = jnp.where(inserting, slots, dump)
    member, firsts = _batch_membership(
        cnt_col, data_col, vbit_col, K, eff0, vals, vbits
    )
    new = inserting & ~member & firsts
    eff = jnp.where(new, slots, dump)
    rank = _slot_ranks(eff)
    pos = cnt_col[eff].astype(jnp.int32) + rank
    write = new & (pos < K)
    tgt_pos = jnp.clip(pos, 0, K - 1)
    tgt_slot = jnp.where(write, eff, dump)
    data_col = data_col.at[tgt_slot, tgt_pos].set(vals)
    vbit_col = vbit_col.at[tgt_slot, tgt_pos].set(vbits)
    cnt_col = cnt_col.at[eff].add(jnp.where(write, 1, 0).astype(cnt_col.dtype))
    # ---- phase 2: signed count increment at each row's member position
    eff2 = jnp.where(contributing, slots, dump)
    occ2 = pos_idx[None, :] < jnp.minimum(cnt_col[eff2], K)[:, None]
    eq2 = (
        (data_col[eff2] == vals[:, None])
        & (vbit_col[eff2] == vbits[:, None])
        & occ2
    )
    found = jnp.any(eq2, axis=1)
    pos2 = jnp.argmax(eq2, axis=1).astype(jnp.int32)
    t_slot = jnp.where(contributing & found, eff2, dump)
    num_col = num_col.at[t_slot, pos2].add(head.astype(num_col.dtype))
    store[f"a{j}"] = cnt_col
    store[f"a{j + 1}"] = data_col
    store[f"a{j + 2}"] = vbit_col
    store[f"a{j + 3}"] = num_col


def _vec_topk(store, comp, j, contrib, slots, dump):
    """Top-K fold: per-slot batch candidates (sorted) merged with the stored
    K values; sentinel (= comp.init, the dtype floor) marks empty entries."""
    K = comp.width
    col = store[f"a{j}"]
    dt = col.dtype
    sent = jnp.asarray(comp.init, dt)
    vals = contrib.astype(dt)
    n = vals.shape[0]
    eff = jnp.where((vals != sent) & (slots != dump), slots, dump)
    order = jnp.lexsort((jnp.arange(n), _desc_key(vals), eff))
    so_eff, so_v = eff[order], vals[order]
    if comp.mode == "distinct":
        # in-batch dedup BEFORE windowing: duplicates would otherwise
        # consume candidate-window slots and hide distinct values ranked
        # past position K
        dup = (
            (so_eff == jnp.concatenate([jnp.full((1,), -1, so_eff.dtype), so_eff[:-1]]))
            & (so_v == jnp.concatenate([so_v[:1], so_v[:-1]]))
        ).at[0].set(False)
        so_eff = jnp.where(dup, dump, so_eff)
        so_v = jnp.where(dup, sent, so_v)
        order2 = jnp.lexsort((jnp.arange(n), _desc_key(so_v), so_eff))
        so_eff, so_v = so_eff[order2], so_v[order2]
    idx = jnp.arange(n, dtype=jnp.int32)
    prev = jnp.concatenate([jnp.full((1,), -1, so_eff.dtype), so_eff[:-1]])
    run_start = jax.lax.cummax(jnp.where(so_eff != prev, idx, -1).at[0].set(0))
    winner = (idx == run_start) & (so_eff != dump)
    offs = idx[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
    gidx = jnp.minimum(offs, n - 1)
    cand = jnp.where(
        (so_eff[gidx] == so_eff[:, None]) & (offs < n), so_v[gidx], sent
    )
    allv = jnp.concatenate([cand, col[so_eff]], axis=1)
    if comp.mode == "distinct":
        s = _sort_desc(allv)
        dup = jnp.concatenate(
            [jnp.zeros((n, 1), bool), s[:, 1:] == s[:, :-1]], axis=1
        )
        allv = jnp.where(dup, sent, s)
    top = _sort_desc(allv)[:, :K]
    tgt = jnp.where(winner, so_eff, dump)
    store[f"a{j}"] = col.at[tgt].set(top)


@jax.named_scope("scatter_combine")
def scatter_combine(
    store: Dict[str, jnp.ndarray],
    layout: StoreLayout,
    slots: jnp.ndarray,
    contribs: Sequence[jnp.ndarray],
    vec_undo: bool = False,
) -> Dict[str, jnp.ndarray]:
    """Fold per-row contributions into the store (KudafAggregator.apply
    analog, batched: duplicate slots accumulate in one scatter).

    'argset' components carry the payload of an arg-min/max: after the
    nearest preceding orderable component is combined, the row whose
    contribution equals the slot's NEW order value (unique sequence numbers
    guarantee a single winner) writes the payload.  'vec_count'/'topk' head
    vector-state groups (collect/topk families, see AggComponent)."""
    store = dict(store)
    dump = jnp.int32(layout.capacity)
    last_order: int = 0
    j = 0
    ncomp = len(layout.components)
    while j < ncomp:
        comp = layout.components[j]
        contrib = contribs[j]
        col = store[f"a{j}"]
        if comp.combine == "vec_count":
            if comp.mode == "hist":
                _vec_hist(store, layout, j, contribs, slots, dump)
                j += 4
                continue
            if vec_undo:
                # table-aggregation undo side: negative head contributions
                # remove stored occurrences (no-op on the apply side)
                _vec_remove(store, layout, j, contribs, slots, dump)
            _vec_collect(store, layout, j, contribs, slots, dump)
            j += 3
            continue
        if comp.combine == "topk":
            _vec_topk(store, comp, j, contrib, slots, dump)
            j += 1
            continue
        ref = col.at[slots]
        if comp.combine == "add":
            store[f"a{j}"] = ref.add(contrib.astype(col.dtype))
            last_order = j
        elif comp.combine == "min":
            store[f"a{j}"] = ref.min(contrib.astype(col.dtype))
            last_order = j
        elif comp.combine == "max":
            store[f"a{j}"] = ref.max(contrib.astype(col.dtype))
            last_order = j
        elif comp.combine == "argset":
            order_new = store[f"a{last_order}"]
            winner = (slots != dump) & (
                contribs[last_order] == order_new[slots]
            )
            tgt = jnp.where(winner, slots, dump)
            store[f"a{j}"] = col.at[tgt].set(contrib.astype(col.dtype))
        else:  # pragma: no cover
            raise ValueError(comp.combine)
        j += 1
    store["dirty"] = store["dirty"].at[slots].set(True)
    store["dirty"] = store["dirty"].at[layout.capacity].set(False)
    return store


@jax.named_scope("emit_compact")
def winners_per_slot(slots: jnp.ndarray, active: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """Mask selecting one representative row per distinct touched slot
    (used to emit exactly one change per key per batch)."""
    n = slots.shape[0]
    rowidx = jnp.arange(n, dtype=jnp.int32)
    dump = jnp.int32(capacity)
    first = jnp.full(capacity + 1, n, jnp.int32)
    first = first.at[jnp.where(active, slots, dump)].min(rowidx)
    return active & (slots != dump) & (first[slots] == rowidx)


def np_mix64(h: np.ndarray) -> np.ndarray:
    """Host (numpy) replica of mix64 — must stay bit-identical; used when
    rebuilding a store into a larger capacity."""
    u = np.asarray(h).astype(np.int64).view(np.uint64).copy()
    u ^= u >> np.uint64(30)
    u *= np.uint64(0xBF58476D1CE4E5B9)
    u ^= u >> np.uint64(27)
    u *= np.uint64(0x94D049BB133111EB)
    u ^= u >> np.uint64(31)
    return u.view(np.int64)


def host_insert(
    occ: np.ndarray,
    kh: np.ndarray,
    ws: np.ndarray,
    capacity: int,
    khash: np.ndarray,
    wstart: np.ndarray,
) -> np.ndarray:
    """Vectorized numpy insert of unique (khash, wstart) keys into a store
    (occ/kh/ws mutated in place); returns per-key slots.  The host half of
    store growth — the RocksDB-compaction analog."""
    n = len(khash)
    mask = capacity - 1
    wmul = (
        np.asarray(wstart).astype(np.int64).view(np.uint64)
        * np.uint64(0x9E3779B97F4A7C15)
    ).view(np.int64)
    base = (np_mix64(np.asarray(khash) ^ wmul) & mask).astype(np.int64)
    slots = np.full(n, -1, np.int64)
    offset = np.zeros(n, np.int64)
    done = np.zeros(n, bool)
    for _ in range(_probe_bound(capacity)):
        if done.all():
            break
        cand = (base + offset) & mask
        c_occ = occ[cand]
        match = c_occ & (kh[cand] == khash) & (ws[cand] == wstart)
        newly = ~done & match
        slots[newly] = cand[newly]
        done |= newly
        want = ~done & ~c_occ
        claim = np.full(capacity, n, np.int64)
        np.minimum.at(claim, cand[want], np.nonzero(want)[0])
        winner = want & (claim[cand] == np.arange(n))
        occ[cand[winner]] = True
        kh[cand[winner]] = khash[winner]
        ws[cand[winner]] = wstart[winner]
        slots[winner] = cand[winner]
        done |= winner
        offset += (~done & c_occ & ~match).astype(np.int64)
    if not done.all():
        raise RuntimeError("host_insert: probe limit exceeded (table too full)")
    return slots


