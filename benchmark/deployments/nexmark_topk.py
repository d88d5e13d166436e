"""Deployment ``nexmark_topk``: NEXmark Query 19, "Auction TOP-10 Price" — the
ten best bids of every auction, kept as bids arrive: ``TOPK(PRICE, BIDDER,
DATETIME, 10) ... GROUP BY AUCTION`` with ``COUNT(*)`` beside it, unwindowed.

The corpus, the plain reference and the comparison for every configuration
whose ``deployment`` is ``nexmark_topk``.  Nothing here imports the program.

Corpus: the bid stream of ``nexmark_sessions.py`` (NEXmark q11's), loaded
from its file and not copied: the same fixed draw, the same JSON record, the
same arrival order and the same ``--seed`` rule (the bids inside each
``seed_block_events`` block reordered).  A bid's auction is, half the time
(``hot_auction_ratio``), the newest multiple of 100 among the auctions (the
hot auction moves every ~1,530 bids), else one of the newest
``num_in_flight_auctions`` plus a lead of 10; its price ``round(10^(6u) *
100)``.

The reference folds the bids in arrival order into a dict from auction to
its count and its list of ``(price, bidder, datetime)``: a bid is appended,
the list sorted by price descending with Python's stable sort and cut to
``k`` — ksqlDB's ``TopkKudaf`` rule, so at equal prices the older entry
comes first and a new entry that ties the k-th stays out.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

SinkRecord = Tuple[Any, Any, Optional[str]]
#: an auction's top list: (price, bidder, datetime) best first
TopList = Tuple[Tuple[Any, Any, Any], ...]


def _load_sessions():
    """``nexmark_sessions.py`` by its file path, as the harness loads a
    deployment (one module object however often it is asked for)."""
    name = "bench_deployment_nexmark_sessions"
    if name in sys.modules:
        return sys.modules[name]
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "nexmark_sessions.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


_sessions = _load_sessions()
SOURCE_TOPIC = _sessions.SOURCE_TOPIC
TS0 = _sessions.TS0


@dataclasses.dataclass
class Corpus:
    source_topic: str
    payloads: List[str]
    ts: List[int]
    #: table loads that precede the stream (none)
    preload: List[Tuple[str, List[Tuple[Any, str, int]]]]
    auction: np.ndarray   # int64[n], in arrival order
    bidder: np.ndarray    # int64[n]
    price: np.ndarray     # int64[n]
    datetime: np.ndarray  # int64[n]: event time (ms)
    k: int


def make_corpus(seed: int, sizes: Dict[str, Any], n_events: int) -> Corpus:
    """``n_events`` bids in arrival order: q11's corpus (its payloads and
    arrival times), with the auction and price of each bid beside it, taken
    from the same fixed draw in the same order."""
    base = _sessions.make_corpus(seed, sizes, n_events)
    # the order make_corpus gives the draw's bids, for the columns it keeps
    # in the payloads only; checked against the ones it keeps beside them
    block = int(sizes["seed_block_events"])
    n_blocks = -(-n_events // block)
    total = n_blocks * block
    margin = int(float(sizes["occasional_delay_sec"]) * int(sizes["first_event_rate"])) + block
    chunks = [_sessions._draw_chunk(c, sizes)
              for c in range(-(-(total + margin) // _sessions._CHUNK))]
    cols = {name: np.concatenate([ch[name] for ch in chunks])
            for name in ("auction", "bidder", "price", "ts", "arrival")}
    order = np.argsort(cols["arrival"], kind="stable")[:total]
    order = order[np.random.default_rng(seed).permuted(
        np.arange(total).reshape(n_blocks, block), axis=1).reshape(-1)[:n_events]]
    cols = {name: v[order] for name, v in cols.items()}
    if not (np.array_equal(cols["bidder"], base.bidder)
            and np.array_equal(cols["ts"], base.datetime)):
        raise ValueError("the bid columns do not line up with nexmark_sessions' corpus")
    return Corpus(base.source_topic, base.payloads, base.ts, [], cols["auction"],
                  base.bidder, cols["price"], base.datetime, int(sizes["k"]))


# ------------------------------------------------------------ the reference
def _fold(corpus: Corpus, n_events: int, dropped: frozenset = frozenset()
          ) -> Dict[int, Tuple[int, TopList]]:
    """The bids ``0 .. n_events - 1`` folded in arrival order: auction ->
    (bids counted, top list); ``dropped`` names bids a control leaves out.
    Appending a bid and stable-sorting descending changes nothing where the
    list is full and the bid's price is at most its last: that bid is not
    appended."""
    k = corpus.k
    table: Dict[int, List[Any]] = {}
    rows = zip(corpus.auction[:n_events].tolist(), corpus.price[:n_events].tolist(),
               corpus.bidder[:n_events].tolist(), corpus.datetime[:n_events].tolist())
    for i, (a, p, b, t) in enumerate(rows):
        if dropped and i in dropped:
            continue
        entry = table.get(a)
        if entry is None:
            entry = table[a] = [0, []]
        entry[0] += 1
        top = entry[1]
        if p is None or (len(top) == k and p <= top[-1][0]):
            continue
        top.append((p, b, t))
        top.sort(key=lambda x: x[0], reverse=True)
        del top[k:]
    return {a: (c, tuple(top)) for a, (c, top) in table.items()}


def reference(corpus: Corpus, n_events: int) -> Dict[int, Tuple[int, TopList]]:
    """Every auction the first ``n_events`` bids name, with its count and
    its top list."""
    return _fold(corpus, n_events)


def _value(count: int, top: TopList) -> str:
    return json.dumps({"TOP_BIDS": [{"sort_col": p, "col0": b, "col1": t} for p, b, t in top],
                       "BID_COUNT": count}, separators=(",", ":"))


def _records(table: Dict[int, Tuple[int, TopList]]) -> List[SinkRecord]:
    """The auctions as the program leaves them on the sink topic, one
    record each (the changelog folded to its last record a key)."""
    return [(a, None, _value(c, top)) for a, (c, top) in table.items()]


def control_reference(corpus: Corpus, n_events: int, kind: str,
                      seed: int) -> List[SinkRecord]:
    """The reference put in the program's place with one stated guarantee
    broken; returns sink records ``(key, window, value)`` as the program
    would leave them.

    ``lost_event``  one acknowledged bid is neither counted nor ranked;
    ``lost_tick``   one served tick (4,096 bids) is neither counted nor
                    ranked;
    ``stale_topk``  one auction's list lacks its best bid;
    ``tie_order``   two entries of one auction's list at the same price
                    change places (the newer one ahead of the older).
    """
    rng = np.random.default_rng(seed ^ 0x5EED)
    if kind == "lost_event":
        return _records(_fold(corpus, n_events, frozenset({int(rng.integers(0, n_events))})))
    if kind == "lost_tick":
        lo = int(rng.integers(0, max(1, n_events - 4096)))
        return _records(_fold(corpus, n_events, frozenset(range(lo, min(lo + 4096, n_events)))))
    table = _fold(corpus, n_events)
    if kind == "stale_topk":
        auctions = sorted(table)
        a = auctions[int(rng.integers(0, len(auctions)))]
        count, top = table[a]
        table[a] = (count, top[1:])
    elif kind == "tie_order":
        tied = sorted((a, i) for a, (_c, top) in table.items() for i in range(len(top) - 1)
                      if top[i][0] == top[i + 1][0] and top[i] != top[i + 1])
        if not tied:
            raise ValueError("no auction holds two entries at one price")
        a, i = tied[int(rng.integers(0, len(tied)))]
        count, top = table[a]
        table[a] = (count, top[:i] + (top[i + 1], top[i]) + top[i + 2:])
    else:
        raise ValueError(f"unknown control {kind!r}")
    return _records(table)


# ------------------------------------------------------- reading the answers
def _auction(key: Any) -> int:
    return int(key[0] if isinstance(key, (tuple, list)) else key)


def _top_of(rows: Sequence[Dict[str, Any]]) -> TopList:
    return tuple((r["sort_col"], r["col0"], r["col1"]) for r in rows)


def fold_sink(records: Sequence[SinkRecord]) -> Dict[int, Tuple[int, TopList]]:
    """The sink changelog folded to its last record a key: auction ->
    (BID_COUNT, top list)."""
    table: Dict[int, Tuple[int, TopList]] = {}
    for key, _window, value in records:
        if value is None:
            table.pop(_auction(key), None)
            continue
        row = json.loads(value)
        table[_auction(key)] = (row.get("BID_COUNT"), _top_of(row.get("TOP_BIDS") or []))
    return table


def _counts_backwards(records: Sequence[SinkRecord]) -> int:
    """Records whose BID_COUNT is below the one before them of their
    auction, in the topic's order."""
    last: Dict[int, int] = {}
    backwards = 0
    for key, _window, value in records:
        if value is None:
            continue
        a, c = _auction(key), int(json.loads(value).get("BID_COUNT") or 0)
        if c < last.get(a, 0):
            backwards += 1
        last[a] = c
    return backwards


class StoreEntries:
    """The store's live entries ``(auction, count, top list)``; the
    harness's log shows how many, not the list."""

    def __init__(self, rows) -> None:
        self.rows = list(rows)

    def __iter__(self):
        return iter(self.rows)

    def __len__(self) -> int:
        return len(self.rows)

    def __str__(self) -> str:
        return f"{len(self.rows)} auctions"


def read_store(executor) -> Dict[str, Any]:
    """Every live entry of the device store: a slot an auction, its key in
    ``key0``; of its aggregate components the top-K group (the count of
    ranked bids, the K prices, then a width-K column per extra column and
    one of their null bits) and the ``COUNT(*)`` after it."""
    state = executor.device.state
    comps = executor.device.store_layout.components
    j = next(i for i, c in enumerate(comps) if c.combine == "topk")
    occ = np.asarray(state["occ"])[:-1]
    live = np.flatnonzero(occ)

    def take(name):
        return np.asarray(state[name][live])

    auction = take("key0")
    ranked, price, bidder, dt, nulls, count = (take(f"a{i}") for i in range(j - 1, j + 5))
    rows = []
    for r in range(live.size):
        n = min(int(ranked[r]), price.shape[1])
        top = tuple((int(price[r, i]), None if nulls[r, i] & 1 else int(bidder[r, i]),
                     None if nulls[r, i] & 2 else int(dt[r, i])) for i in range(n))
        rows.append((int(auction[r]), int(count[r]), top))
    return {"live_auctions": int(live.size), "entries": StoreEntries(rows)}


def pull_queries(corpus: Corpus, n_events: int, seed: int, k: int) -> List[Tuple[int, str]]:
    """``k`` pull lookups drawn from the seed, as ``(auction, sql)``: the
    auctions with most bids, auctions of random bids, and one auction no
    bid names."""
    rng = np.random.default_rng(seed ^ 0xB0B)
    auctions = corpus.auction[:n_events]
    ids, counts = np.unique(auctions, return_counts=True)
    hot = ids[np.argsort(-counts, kind="stable")[:k // 3]]
    drawn = auctions[rng.integers(0, n_events, k - len(hot) - 1)]
    keys = [int(a) for a in np.r_[hot, drawn]] + [int(ids.max()) + 12345]
    return [(a, "SELECT AUCTION, TOP_BIDS, BID_COUNT FROM AUCTION_TOP_BIDS "
                f"WHERE AUCTION = {a};") for a in keys]


def read_pull(response: Dict[str, Any]) -> Optional[Tuple[int, TopList]]:
    cols = response["columnNames"]
    top_at, count_at = cols.index("TOP_BIDS"), cols.index("BID_COUNT")
    rows = [(r[count_at], _top_of(r[top_at] or [])) for r in response["rows"]]
    return rows[0] if len(rows) == 1 else (tuple(rows) or None)


# ------------------------------------------------------------ the comparison
def compare(corpus: Corpus, n_events: int, sink_records: Sequence[SinkRecord],
            store: Optional[Dict[str, Any]],
            pulls: Optional[List[Tuple[int, Any]]],
            ) -> Dict[str, Dict[str, float]]:
    """Every number compared, beside its limit: all exact, limit 0 (an
    auction's answer is integers: a count and ten (price, bidder,
    datetime) in order)."""
    want = reference(corpus, n_events)
    got = fold_sink(sink_records)
    out = {
        "sink_rows_wrong": {
            "value": sum(1 for a, row in got.items() if a in want and row != want[a]),
            "limit": 0},
        "sink_rows_extra": {"value": sum(1 for a in got if a not in want), "limit": 0},
        "sink_events_missing": {
            "value": abs(n_events - sum(int(c or 0) for c, _top in got.values())), "limit": 0},
        "sink_counts_backwards": {"value": _counts_backwards(sink_records), "limit": 0},
    }
    if store is not None:
        resident = {(a, c, top) for a, (c, top) in want.items()}
        have = set(store["entries"])
        out["store_entries_diff"] = {"value": len(resident ^ have), "limit": 0}
    if pulls is not None:
        out["pulls_wrong"] = {
            "value": sum(1 for a, row in pulls if row != want.get(a)), "limit": 0}
    return out


# ------------------------------------------- which event a result record is of
def result_event_index(corpus: Corpus, lo: int, hi: int,
                       records: Sequence[SinkRecord]) -> np.ndarray:
    """For each sink record, the index of the bid it is the result of: a row
    of ``BID_COUNT`` c is the result of its auction's c-th bid in arrival
    order.  Only bids ``lo <= i < hi`` are looked up; other records give
    -1."""
    auctions = corpus.auction[:hi]
    order = np.argsort(auctions, kind="stable")
    sorted_auctions = auctions[order]
    out = np.full(len(records), -1, dtype=np.int64)
    keys = np.full(len(records), -1, dtype=np.int64)
    nth = np.zeros(len(records), dtype=np.int64)
    for r, (key, _window, value) in enumerate(records):
        if value is not None:
            keys[r] = _auction(key)
            nth[r] = int(json.loads(value).get("BID_COUNT") or 0)
    first = np.searchsorted(sorted_auctions, keys, side="left")
    last = np.searchsorted(sorted_auctions, keys, side="right")
    ok = (keys >= 0) & (nth >= 1) & (first + nth - 1 < last)
    event = order[np.minimum(first + nth - 1, len(order) - 1)] if len(order) else first
    ok &= (event >= lo) & (event < hi)
    out[ok] = event[ok]
    return out
