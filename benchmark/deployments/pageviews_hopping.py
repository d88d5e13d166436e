"""Deployment ``pageviews_hopping``: a rolling-hour statistics view,
``COUNT(*), SUM, AVG, MIN, MAX`` of a DOUBLE ``GROUP BY URL`` over ``WINDOW
HOPPING (SIZE 1 HOUR, ADVANCE BY 15 MINUTES, GRACE PERIOD 15 MINUTES)``.

The corpus, the plain reference and the comparison for every configuration
whose ``deployment`` is ``pageviews_hopping``.  Nothing here imports the
program, nor ``pageviews.py``: ``url_of``, ``Corpus`` and the block-order
draw are copied from there so that the URL sequence is ``pageviews``' own.

Corpus: ``pageviews``' views (in each hour ``uniform_share`` of the events
uniform over ``urls`` URLs, the rest Zipf(``zipf_a``), one fixed draw whose
order inside ``seed_block_events``-sized blocks the seed draws), each with a
``LATENCY``: a quarter-valued double in [0, 1000), drawn once from
``key_draw`` and carried with its view, so that sums are exact in any order.
Event ``i`` has event time ``TS0 + i * HOUR / events_per_window``: event
time never goes back, so no event is late.

Every event belongs to ``SIZE / ADVANCE = 4`` windows, ``ws = ts - ts %
ADVANCE - j * ADVANCE`` for j = 0..3 (``TS0`` is far from the epoch, so the
windows that start before the first event exist too).  Retention is ``SIZE
+ GRACE`` = 75 minutes, as ksqlDB sets it: a window is retained (and pulled)
while ``ws + RETENTION >= stream time``.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, FrozenSet, List, Optional, Sequence, Tuple

import numpy as np

HOUR_MS = 3_600_000
SIZE_MS = HOUR_MS
ADVANCE_MS = 900_000
GRACE_MS = 900_000
RETENTION_MS = SIZE_MS + GRACE_MS
#: windows an event belongs to
HOPS = SIZE_MS // ADVANCE_MS
#: first event time, aligned to the hour
TS0 = 1_700_000_000_000 - 1_700_000_000_000 % HOUR_MS

SOURCE_TOPIC = "page_views"
#: ``A`` against the reference's ``S / CNT``, relative.  ``S`` and ``CNT``
#: are exact (quarter-valued doubles, integers), so ``A`` is one float64
#: division: exact to 1.1e-16 in IEEE arithmetic, to ~1e-14 on a chip that
#: emulates float64 with ~48 bits (measured <= 1.03e-14, ROADMAP A7).  A
#: float32 path misses by ~6e-8 and fails.
AVG_REL_ERR_LIMIT = 1e-12

#: (cnt, sum, min, max) of one (URL index, window start)
Stats = List[float]


def url_of(k: int) -> str:
    return f"/catalog/products/item-{k:07d}/view.html"


@dataclasses.dataclass
class Corpus:
    source_topic: str
    payloads: List[str]
    ts: List[int]
    #: table loads that precede the stream: (topic, [(key, value, ts), ...])
    preload: List[Tuple[str, List[Tuple[Any, str, int]]]]
    url_idx: np.ndarray  # int64[n]
    latency: np.ndarray  # float64[n], quarter-valued
    ts_ms: np.ndarray    # int64[n]
    #: events of the set-up (fill and warm ticks), the last of them by
    #: which the store has certainly made a retention pass (None: the set-up
    #: makes none), and the most events that a cadence of served ticks holds
    setup_events: int
    setup_pass_event: Optional[int]
    evict_cadence_events: int


def make_corpus(seed: int, sizes: Dict[str, Any], n_events: int) -> Corpus:
    """``n_events`` page views from ``seed``: ``pageviews.make_corpus``'s
    URLs in its order (the views and the order of their blocks from
    ``key_draw``, the order inside each block from ``seed``), each view
    with its latency."""
    urls, per_window = int(sizes["urls"]), int(sizes["events_per_window"])
    block = int(sizes["seed_block_events"])
    n_cold = int(round(per_window * float(sizes["uniform_share"])))
    draw = np.random.default_rng(int(sizes["key_draw"]))
    hours = []
    for _ in range(-(-(n_events + block) // per_window)):
        hot = draw.zipf(float(sizes["zipf_a"]), size=per_window - n_cold).astype(np.int64) % urls
        cold = draw.integers(0, urls, n_cold)
        hours.append(draw.permutation(np.concatenate([hot, cold])))
    n_blocks = -(-n_events // block)
    idx = np.concatenate(hours)[:n_blocks * block]
    # a generator of its own: the URL draw above stays pageviews' own
    quarters = np.random.default_rng([int(sizes["key_draw"]), 1]).integers(
        0, 4000, n_blocks * block)
    order = np.random.default_rng(seed).permuted(
        np.arange(n_blocks * block).reshape(n_blocks, block), axis=1).reshape(-1)[:n_events]
    idx, quarters = idx[order], quarters[order]
    ts = TS0 + (np.arange(n_events, dtype=np.int64) * HOUR_MS) // per_window
    user = 1 + (np.arange(n_events, dtype=np.int64) * 7919) % 999
    payloads = [
        '{"URL":"/catalog/products/item-%07d/view.html","USER_ID":%d,"LATENCY":%d.%s}'
        % (u, uid, q >> 2, ("0", "25", "5", "75")[q & 3])
        for u, uid, q in zip(idx.tolist(), user.tolist(), quarters.tolist())
    ]
    cadence = int(sizes["evict_cadence_batches"])
    return Corpus(SOURCE_TOPIC, payloads, ts.tolist(), [], idx, quarters / 4.0, ts,
                  int(sizes["fill_events"]) + int(sizes["warm_ticks"]) * int(sizes["warm_tick_events"]),
                  _setup_pass_event(sizes), cadence * int(sizes["warm_tick_events"]))


def _setup_pass_event(sizes: Dict[str, Any]) -> Optional[int]:
    """The store makes a retention pass after every ``evict_cadence_batches``
    -th batch.  The set-up's batches are known: the fill in batches of
    ``fill_batch_events`` (the last one short), then ``warm_ticks`` ticks of
    at least a batch each.  The index of the last event of the last set-up
    batch that certainly ended with a pass (a warm tick that the server's
    loop splits reaches that batch number sooner, and a batch holds an event
    at least), or None where the set-up has fewer batches than a cadence."""
    fill, batch = int(sizes["fill_events"]), int(sizes["fill_batch_events"])
    cadence = int(sizes["evict_cadence_batches"])
    fill_batches = -(-fill // batch)
    last = (fill_batches + int(sizes["warm_ticks"])) // cadence * cadence
    if not last:
        return None
    if last <= fill_batches:
        return min(last * batch, fill) - 1
    return fill - 1 + (last - fill_batches)


# ------------------------------------------------------------ the reference
def _fold(corpus: Corpus, n_events: int,
          dropped: FrozenSet[Tuple[int, int]] = frozenset()) -> Dict[Tuple[int, int], Stats]:
    """``(URL index, window start) -> [cnt, sum, min, max]`` over the first
    ``n_events``, by a loop over the events and their four window starts;
    ``dropped`` names (event, hop) memberships a control leaves out."""
    table: Dict[Tuple[int, int], Stats] = {}
    events = zip(corpus.url_idx[:n_events].tolist(), corpus.ts_ms[:n_events].tolist(),
                 corpus.latency[:n_events].tolist())
    for i, (u, ts, lat) in enumerate(events):
        newest = ts - ts % ADVANCE_MS
        for j in range(HOPS):
            ws = newest - j * ADVANCE_MS
            if ws < 0 or (dropped and (i, j) in dropped):
                continue
            cell = table.get((u, ws))
            if cell is None:
                table[(u, ws)] = [1, lat, lat, lat]
            else:
                cell[0] += 1
                cell[1] += lat
                if lat < cell[2]:
                    cell[2] = lat
                if lat > cell[3]:
                    cell[3] = lat
    return table


def reference(corpus: Corpus, n_events: int) -> Dict[Tuple[int, int], Stats]:
    """The view's final table: ``A`` is ``sum / cnt`` in Python floats."""
    return _fold(corpus, n_events)


def control_reference(corpus: Corpus, n_events: int, kind: str,
                      seed: int) -> List[Tuple[Any, Optional[Tuple[int, int]], Optional[str]]]:
    """The reference put in the program's place with one stated guarantee
    broken; returns sink records ``(key, window, value)`` as the program
    would leave them.

    ``lost_event``   one acknowledged event missing from all four of its
                     windows (at-most-once for a single record);
    ``lost_tick``    one served tick (4,096 events) missing;
    ``lost_window``  one event missing from one of its four windows only:
                     the fault a hopping path can have and a tumbling one
                     cannot;
    ``stale_stat``   one (URL, window)'s ``MX`` one quarter low (a stale or
                     approximate answer where the configuration says exact).
    """
    rng = np.random.default_rng(seed ^ 0x5EED)
    dropped: FrozenSet[Tuple[int, int]] = frozenset()
    if kind == "lost_event":
        i = int(rng.integers(0, n_events))
        dropped = frozenset((i, j) for j in range(HOPS))
    elif kind == "lost_tick":
        lo = int(rng.integers(0, max(1, n_events - 4096)))
        dropped = frozenset((i, j) for i in range(lo, min(lo + 4096, n_events))
                            for j in range(HOPS))
    elif kind == "lost_window":
        dropped = frozenset({(int(rng.integers(0, n_events)), int(rng.integers(0, HOPS)))})
    elif kind != "stale_stat":
        raise ValueError(f"unknown control {kind!r}")
    table = _fold(corpus, n_events, dropped)
    if kind == "stale_stat":
        table[sorted(table)[int(rng.integers(0, len(table)))]][3] -= 0.25
    return [
        (url_of(u), (ws, ws + SIZE_MS),
         json.dumps({"CNT": c, "S": s, "A": s / c, "MN": mn, "MX": mx}, separators=(",", ":")))
        for (u, ws), (c, s, mn, mx) in table.items()
    ]


# ------------------------------------------------------- reading the answers
def _url_index(key: Any) -> int:
    url = key[0] if isinstance(key, tuple) else key
    return int(url[23:30])


def fold_sink(records: Sequence[Tuple[Any, Optional[Tuple[int, int]], Optional[str]]]
              ) -> Tuple[Dict[Tuple[int, int], Optional[Dict[str, float]]], int]:
    """The sink changelog folded to its final table (the last record of a
    (URL index, window start)), and how many records stepped a pair's
    ``CNT`` backwards (EMIT CHANGES never does)."""
    table: Dict[Tuple[int, int], Optional[Dict[str, float]]] = {}
    backwards = 0
    for key, window, value in records:
        k = (_url_index(key), window[0])
        row = None if value is None else json.loads(value)
        prev = table.get(k)
        if prev is not None and (row is None or row["CNT"] < prev["CNT"]):
            backwards += 1
        table[k] = row
    return table, backwards


def read_store(executor) -> Dict[str, int]:
    """Live key slots in the device store: the sliced store keeps a slot a
    URL, its windows' slices in a ring on it."""
    occ = np.asarray(executor.device.state["occ"])
    return {"live_keys": int(occ[..., :-1].sum())}


def pull_queries(corpus: Corpus, n_events: int, seed: int, k: int) -> List[Tuple[int, str]]:
    """``k`` pull lookups drawn from the seed, as ``(URL index, sql)``: the
    hottest keys, keys of random events (old ones among them, whose windows
    left retention), and one key no event carries."""
    rng = np.random.default_rng(seed ^ 0xB0B)
    idx = corpus.url_idx[:n_events]
    hot = np.argsort(-np.bincount(idx))[:k // 3]
    drawn = idx[rng.integers(0, n_events, k - len(hot) - 1)]
    keys = [int(i) for i in np.r_[hot, drawn]] + [int(idx.max()) + 12345]
    return [
        (u, "SELECT URL, WINDOWSTART, CNT, S, A, MN, MX FROM PV_STATS "
            f"WHERE URL = '{url_of(u)}';")
        for u in keys
    ]


def read_pull(response: Dict[str, Any]) -> Dict[int, Dict[str, float]]:
    cols = response["columnNames"]
    at = {c: cols.index(c) for c in ("WINDOWSTART", "CNT", "S", "A", "MN", "MX")}
    return {
        r[at["WINDOWSTART"]]: {c: r[i] for c, i in at.items() if c != "WINDOWSTART"}
        for r in response["rows"]
    }


# ------------------------------------------------------------ the comparison
def _avg_rel_err(row: Dict[str, float], want: Stats) -> float:
    avg = want[1] / want[0]
    return abs(row["A"] - avg) / abs(avg) if avg else abs(row["A"])


def _exact(row: Optional[Dict[str, float]], want: Stats) -> bool:
    return row is not None and [row["CNT"], row["S"], row["MN"], row["MX"]] == want


def _urls_with_a_slice_from(corpus: Corpus, n_events: int, stream_time: int) -> int:
    """URLs with an event whose slice (its newest window) is inside
    retention at ``stream_time``."""
    ts = corpus.ts_ms[:n_events]
    inside = ts - ts % ADVANCE_MS + RETENTION_MS >= stream_time
    return len(np.unique(corpus.url_idx[:n_events][inside]))


def compare(corpus: Corpus, n_events: int,
            sink_records, store: Optional[Dict[str, int]],
            pulls: Optional[List[Tuple[int, Dict[int, Dict[str, float]]]]],
            ) -> Dict[str, Dict[str, float]]:
    """Every number compared, beside its limit.  All are exact (limit 0:
    integer counts, and quarter-valued doubles whose sums, minima and maxima
    have no rounding to allow for) but ``sink_avg_rel_err_max``
    (``AVG_REL_ERR_LIMIT``)."""
    want = reference(corpus, n_events)
    got, backwards = fold_sink(sink_records)
    wrong = sum(1 for k, w in want.items() if not _exact(got.get(k), w))
    extra = sum(1 for k in got if k not in want)
    # every event is in exactly four windows: memberships missing (or
    # counted twice), in events
    memberships = sum(row["CNT"] for row in got.values() if row is not None)
    events_missing = -(-abs(HOPS * n_events - memberships) // HOPS)
    avg_err = max((_avg_rel_err(row, want[k]) for k, row in got.items()
                   if row is not None and k in want), default=0.0)
    out = {
        "sink_rows_wrong": {"value": wrong, "limit": 0},
        "sink_rows_extra": {"value": extra, "limit": 0},
        "sink_events_missing": {"value": events_missing, "limit": 0},
        "sink_counts_backwards": {"value": backwards, "limit": 0},
        "sink_avg_rel_err_max": {"value": avg_err, "limit": AVG_REL_ERR_LIMIT},
    }
    stream_time = int(corpus.ts_ms[n_events - 1])
    if store is not None:
        # a URL with an event in a window still open at the close is live.
        # Beyond those the store may still hold what expired since its last
        # retention pass: the last one the set-up certainly made, or one
        # within the last cadence of served ticks where the run has served
        # that many.  A run with neither has nothing that bounds the store,
        # and then every key beyond the live ones counts as lingering
        must = _urls_with_a_slice_from(corpus, n_events, stream_time + 1)
        last_pass = corpus.setup_pass_event
        if n_events - corpus.evict_cadence_events >= corpus.setup_events:
            last_pass = n_events - 1 - corpus.evict_cadence_events
        may = must if last_pass is None else _urls_with_a_slice_from(
            corpus, n_events, int(corpus.ts_ms[last_pass]))
        out["store_keys_missing"] = {"value": max(0, must - store["live_keys"]), "limit": 0}
        out["store_keys_lingering"] = {"value": max(0, store["live_keys"] - may), "limit": 0}
    if pulls is not None:
        by_url: Dict[int, Dict[int, Stats]] = {u: {} for u, _ in pulls}
        for (u, ws), w in want.items():
            if u in by_url:
                by_url[u][ws] = w
        out["pulls_wrong"] = {
            "value": sum(1 for u, rows in pulls
                         if not _pull_right(rows, by_url[u], stream_time)),
            "limit": 0}
    return out


def _pull_right(rows: Dict[int, Dict[str, float]], want: Dict[int, Stats],
                stream_time: int) -> bool:
    """Every window the reference still retains is there and equal; any
    other returned window equals its final value."""
    retained = {ws for ws in want if ws + RETENTION_MS >= stream_time}
    return retained <= set(rows) and all(
        ws in want and _exact(row, want[ws])
        and _avg_rel_err(row, want[ws]) <= AVG_REL_ERR_LIMIT
        for ws, row in rows.items())


# ------------------------------------------- which event a result record is of
def result_event_index(corpus: Corpus, lo: int, hi: int, records) -> np.ndarray:
    """For each sink record, the index of the event it is the result of: a
    ``CNT = c`` record of (URL, window) is the result of the c-th event of
    that URL inside that window.  Only events ``lo <= i < hi`` are looked
    up; other records give -1."""
    ts = corpus.ts_ms[:hi]
    newest = (ts - ts % ADVANCE_MS - TS0) // ADVANCE_MS + HOPS  # > 0: window number
    # a key per (event, hop), event-major: a stable sort keeps a pair's
    # memberships in event order
    key = (corpus.url_idx[:hi, None] * 1024
           + (newest[:, None] - np.arange(HOPS))).reshape(-1)
    order = np.argsort(key, kind="stable")
    sk = key[order]
    first = np.r_[0, np.flatnonzero(sk[1:] != sk[:-1]) + 1]
    run_start = np.repeat(first, np.diff(np.r_[first, len(sk)]))
    ordinal = np.empty(len(key), dtype=np.int64)
    ordinal[order] = np.arange(len(key)) - run_start + 1
    lookup = dict(zip(
        zip(key[lo * HOPS:].tolist(), ordinal[lo * HOPS:].tolist()),
        np.repeat(np.arange(lo, hi), HOPS).tolist()))
    out = np.full(len(records), -1, dtype=np.int64)
    for r, (rkey, window, value) in enumerate(records):
        if value is None:
            continue
        k = _url_index(rkey) * 1024 + (window[0] - TS0) // ADVANCE_MS + HOPS
        out[r] = lookup.get((k, json.loads(value)["CNT"]), -1)
    return out
