"""Deployment ``nexmark_sessions``: NEXmark Query 11, "user sessions" — how
many bids each bidder made in each session of activity, ``COUNT(*) GROUP BY
BIDDER`` over ``WINDOW SESSION (10 SECONDS)`` with event time taken from the
bid's own ``DATETIME``.

The corpus, the plain reference and the comparison for every configuration
whose ``deployment`` is ``nexmark_sessions``.  Nothing here imports the
program.

Corpus: NEXmark's bid stream as the Beam / Flink generators define it.  The
event ids run in epochs of 50 (1 person, 3 auctions, 46 bids); the topic
carries the bids only.  Event id ``e`` is at ``TS0 + e * 100 us`` of event
time (``first_event_rate`` 10,000 a second).  A bid's bidder is, three times
in four (``hot_bidders_ratio``), the first person of the newest block of 100
persons (so the hot bidder moves every 5,000 events), else a person drawn
uniformly from the newest ``num_active_people`` plus ``person_id_lead``;
its auction likewise (hot half the time, else one of the newest
``num_in_flight_auctions`` plus a lead of 10); its price ``round(10^(6u) *
100)``; its channel and URL one of four hot pairs half the time, else one of
10,000; its ``extra`` pads Beam's size model to ``avg_bid_byte_size``.  A
tenth of the bids (``prob_delayed_event``) is held back by up to
``occasional_delay_sec`` of event time: the topic carries the bids in the
order of event time plus that delay, each with its own ``DATETIME``.  All of
this is one fixed draw from ``key_draw`` (made in chunks, so that a longer
corpus begins with a shorter one); ``--seed`` orders the bids inside
``seed_block_events``-sized blocks of that arrival order.

The reference folds the bids in arrival order per bidder with Kafka
Streams' session rule (``KStreamSessionWindowAggregate``): stream time is the
largest ``DATETIME`` seen; a bid at ``t`` with ``t + GAP + GRACE`` behind it
is dropped as late; otherwise it merges every stored session of its bidder
whose end is at or after ``t - GAP`` and whose start is at or before ``t +
GAP`` (sessions whose end + gap + grace is behind stream time have left the
store and merge nothing).  There is no ``GRACE PERIOD`` in the query, so
ksqlDB's default of 24 h holds.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, FrozenSet, List, Optional, Sequence, Tuple

import numpy as np

GAP_MS = 10_000
GRACE_MS = 24 * 3_600_000
#: first event time (ms)
TS0 = 1_700_000_000_000
FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
SOURCE_TOPIC = "bids"
#: bids a chunk of the fixed draw
_CHUNK = 1 << 20
HOT_CHANNELS = ("Google", "Facebook", "Baidu", "Apple")
CHANNELS = 10_000

SinkRecord = Tuple[Any, Optional[Tuple[int, int]], Optional[str]]
#: a session: (bidder, start, end)
SessionKey = Tuple[int, int, int]


@dataclasses.dataclass
class Corpus:
    source_topic: str
    payloads: List[str]
    ts: List[int]
    #: table loads that precede the stream (none)
    preload: List[Tuple[str, List[Tuple[Any, str, int]]]]
    bidder: np.ndarray    # int64[n], in arrival order
    datetime: np.ndarray  # int64[n]: event time (ms), in arrival order
    held_back: np.ndarray  # bool[n]: the bid arrives behind its event time


def _letters(rng, n: int) -> str:
    """``n`` characters as Beam's ``nextExactString`` draws them: a space one
    time in 13, else a lowercase letter."""
    codes = rng.integers(0, 26, n) + ord("a")
    codes[rng.integers(0, 13, n) == 0] = ord(" ")
    return codes.astype(np.uint8).tobytes().decode("ascii")


def _base_url(rng) -> str:
    parts = [_letters(rng, 5).replace(" ", "_") for _ in range(3)]
    return "https://www.nexmark.com/%s/%s/%s/item.htm?query=1" % tuple(parts)


def _draw_chunk(c: int, sizes: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Bids ``c * _CHUNK`` .. ``(c + 1) * _CHUNK - 1`` of the fixed draw, in
    event-id order."""
    rng = np.random.default_rng([int(sizes["key_draw"]), c])
    k = np.arange(c * _CHUNK, (c + 1) * _CHUNK, dtype=np.int64)
    bids_per_epoch = int(sizes["bid_proportion"])
    persons_per_epoch = int(sizes["person_proportion"])
    auctions_per_epoch = int(sizes["auction_proportion"])
    epoch_events = persons_per_epoch + auctions_per_epoch + bids_per_epoch
    epoch, offset = k // bids_per_epoch, k % bids_per_epoch
    event_id = epoch * epoch_events + persons_per_epoch + auctions_per_epoch + offset
    # the newest person and auction at a bid (GeneratorConfig's
    # lastBase0PersonId / lastBase0AuctionId for an event past both)
    last_person = epoch * persons_per_epoch + persons_per_epoch - 1
    last_auction = epoch * auctions_per_epoch + auctions_per_epoch - 1

    n = len(k)
    hot_batch = int(sizes["hot_bidder_batch"])
    hot = rng.integers(0, int(sizes["hot_bidders_ratio"]), n) > 0
    people = last_person + 1
    active = np.minimum(people, int(sizes["num_active_people"]))
    drawn = people - active + (rng.random(n) * (active + int(sizes["person_id_lead"]))).astype(np.int64)
    bidder = np.where(hot, last_person // hot_batch * hot_batch + 1, drawn) + FIRST_PERSON_ID

    hot_a = rng.integers(0, int(sizes["hot_auction_ratio"]), n) > 0
    in_flight = int(sizes["num_in_flight_auctions"])
    lo = np.maximum(last_auction - in_flight, 0)
    drawn_a = lo + (rng.random(n) * (last_auction - lo + 1 + 10)).astype(np.int64)
    hot_batch_a = int(sizes["hot_auction_batch"])
    auction = np.where(hot_a, last_auction // hot_batch_a * hot_batch_a, drawn_a) + FIRST_AUCTION_ID

    price = np.round(10.0 ** (rng.random(n) * 6.0) * 100.0).astype(np.int64)
    hot_c = rng.integers(0, 2, n) > 0
    channel = np.where(hot_c, rng.integers(0, len(HOT_CHANNELS), n),
                       len(HOT_CHANNELS) + rng.integers(0, CHANNELS, n))
    # Beam's size model: four longs, then ``extra`` pads to the average
    # +-20 %
    want = int(sizes["avg_bid_byte_size"]) - 32
    delta = int(round(want * 0.2))
    extra_len = want - delta + rng.integers(0, 2 * delta, n)
    extra_at = rng.integers(0, 1 << 16, n)

    ts = TS0 + event_id * 1_000_000 // int(sizes["first_event_rate"]) // 1000
    held = rng.random(n) < float(sizes["prob_delayed_event"])
    delay = np.where(held, (rng.random(n) * float(sizes["occasional_delay_sec"]) * 1000).astype(np.int64), 0)
    return {"bidder": bidder, "auction": auction, "price": price, "channel": channel,
            "extra_len": extra_len, "extra_at": extra_at, "ts": ts, "held": held & (delay > 0),
            "arrival": ts + delay, "k": k}


def make_corpus(seed: int, sizes: Dict[str, Any], n_events: int) -> Corpus:
    """``n_events`` bids in arrival order: the fixed draw's first bids by
    event time plus delay, ordered inside each ``seed_block_events`` block
    by ``seed``."""
    block = int(sizes["seed_block_events"])
    n_blocks = -(-n_events // block)
    total = n_blocks * block
    # a held-back bid arrives up to occasional_delay_sec late: draw past the
    # last bid wanted by more than that, so that none of them is missing
    margin = int(float(sizes["occasional_delay_sec"]) * int(sizes["first_event_rate"])) + block
    chunks = [_draw_chunk(c, sizes) for c in range(-(-(total + margin) // _CHUNK))]
    cols = {name: np.concatenate([ch[name] for ch in chunks]) for name in chunks[0]}
    order = np.argsort(cols["arrival"], kind="stable")[:total]
    order = order[np.random.default_rng(seed).permuted(
        np.arange(total).reshape(n_blocks, block), axis=1).reshape(-1)[:n_events]]
    cols = {name: v[order] for name, v in cols.items()}

    pool_rng = np.random.default_rng([int(sizes["key_draw"]), 1 << 30])
    pool = _letters(pool_rng, (1 << 16) + 256)
    urls = [_base_url(pool_rng) for _ in HOT_CHANNELS]
    names = list(HOT_CHANNELS)
    for ch in range(CHANNELS):
        names.append("channel-%d" % ch)
        url = _base_url(pool_rng)
        if pool_rng.integers(0, 10) > 0:
            url += "&channel_id=%d" % abs(int(np.int32(ch).byteswap()))
        urls.append(url)
    payloads = [
        '{"AUCTION":%d,"BIDDER":%d,"PRICE":%d,"CHANNEL":"%s","URL":"%s","DATETIME":%d,"EXTRA":"%s"}'
        % (a, b, p, names[c], urls[c], t, pool[x:x + n])
        for a, b, p, c, t, x, n in zip(
            cols["auction"].tolist(), cols["bidder"].tolist(), cols["price"].tolist(),
            cols["channel"].tolist(), cols["ts"].tolist(), cols["extra_at"].tolist(),
            cols["extra_len"].tolist())
    ]
    return Corpus(SOURCE_TOPIC, payloads, cols["arrival"].tolist(), [],
                  cols["bidder"], cols["ts"], cols["held"])


# ------------------------------------------------------------ the reference
@dataclasses.dataclass
class Fold:
    """The reference's sessions after a fold: ``(bidder, start, end) ->
    count``, the stream time it ended at, and (where asked) the bid after
    which each session state ``(bidder, start, end, count)`` first held."""

    sessions: Dict[SessionKey, int]
    stream_time: int
    made_by: Dict[Tuple[int, int, int, int], int]


def _fold(corpus: Corpus, n_events: int, dropped: FrozenSet[int] = frozenset(),
          alone: Optional[int] = None, track_from: Optional[int] = None) -> Fold:
    """The bids ``0 .. n_events - 1`` folded in arrival order by the
    session rule; ``dropped`` names bids a control leaves out, ``alone`` a
    bid a control keeps as a session of its own.  With ``track_from``,
    records the bid each session state of a bid at or after it came from."""
    live: Dict[int, List[List[int]]] = {}  # bidder -> [[start, end, count]]
    stray: List[Tuple[int, int]] = []  # (bidder, t) of the bid kept alone
    made_by: Dict[Tuple[int, int, int, int], int] = {}
    stream_time = -(1 << 63)
    bidders = corpus.bidder[:n_events].tolist()
    times = corpus.datetime[:n_events].tolist()
    for i, (b, t) in enumerate(zip(bidders, times)):
        if dropped and i in dropped:
            continue
        if t > stream_time:
            stream_time = t
        if t + GAP_MS + GRACE_MS < stream_time:
            continue  # late: dropped
        if i == alone:
            stray.append((b, t))
            continue
        lst = live.get(b)
        if lst is None:
            s = [t, t, 1]
            live[b] = [s]
        else:
            horizon = stream_time - GAP_MS - GRACE_MS
            hit = [s for s in lst if s[1] >= t - GAP_MS and s[0] <= t + GAP_MS and s[1] >= horizon]
            if not hit:
                s = [t, t, 1]
                lst.append(s)
                lst.sort()
            elif len(hit) == 1:
                s = hit[0]
                if t < s[0]:
                    s[0] = t
                elif t > s[1]:
                    s[1] = t
                s[2] += 1
            else:
                s = [min(t, min(h[0] for h in hit)), max(t, max(h[1] for h in hit)),
                     1 + sum(h[2] for h in hit)]
                lst[:] = sorted([x for x in lst if all(x is not h for h in hit)] + [s])
        if track_from is not None and i >= track_from:
            made_by.setdefault((b, s[0], s[1], s[2]), i)
    sessions = {(b, s, e): c for b, lst in live.items() for s, e, c in lst}
    for b, t in stray:
        sessions[(b, t, t)] = sessions.get((b, t, t), 0) + 1
    return Fold(sessions, stream_time, made_by)


def reference(corpus: Corpus, n_events: int) -> Dict[SessionKey, int]:
    """Every session the first ``n_events`` bids leave, with its count."""
    return _fold(corpus, n_events).sessions


def _value(start: int, end: int, count: int) -> str:
    return '{"BID_COUNT":%d,"STARTTIME":%d,"ENDTIME":%d}' % (count, start, end)


def _records(sessions: Dict[SessionKey, int]) -> List[SinkRecord]:
    """Sessions as the program leaves them on the sink topic, one record
    each (the changelog folded to its last record a session)."""
    return [(b, (s, e), _value(s, e, c)) for (b, s, e), c in sessions.items()]


def _out_of_order_bid(corpus: Corpus, n_events: int, rng) -> int:
    """A held-back bid that joined its bidder's session from before the
    session's start, or bridged two sessions: it arrived after a bid of its
    bidder with a later event time, within the gap of it."""
    newest: Dict[int, int] = {}
    candidates = []
    held = corpus.held_back[:n_events].tolist()
    for i, (b, t) in enumerate(zip(corpus.bidder[:n_events].tolist(),
                                   corpus.datetime[:n_events].tolist())):
        seen = newest.get(b)
        if held[i] and seen is not None and t < seen <= t + GAP_MS:
            candidates.append(i)
        if seen is None or t > seen:
            newest[b] = t
    if not candidates:
        raise ValueError("no held-back bid joins a session out of order")
    return candidates[int(rng.integers(0, len(candidates)))]


def control_reference(corpus: Corpus, n_events: int, kind: str,
                      seed: int) -> List[SinkRecord]:
    """The reference put in the program's place with one stated guarantee
    broken; returns sink records ``(key, window, value)`` as the program
    would leave them.

    ``lost_event``      one acknowledged bid is in no session;
    ``lost_tick``       one served tick (4,096 bids) is in no session;
    ``unbridged``       one held-back bid that joined its bidder's session
                        out of order (before its start, or between two
                        sessions) stays a session of its own that nothing
                        merges: the merge a late record forces did not
                        happen;
    ``lost_tombstone``  one session that a later bid merged away is left on
                        the sink beside the session that replaced it.
    """
    rng = np.random.default_rng(seed ^ 0x5EED)
    if kind == "lost_event":
        fold = _fold(corpus, n_events, frozenset({int(rng.integers(0, n_events))}))
    elif kind == "lost_tick":
        lo = int(rng.integers(0, max(1, n_events - 4096)))
        fold = _fold(corpus, n_events, frozenset(range(lo, min(lo + 4096, n_events))))
    elif kind == "unbridged":
        fold = _fold(corpus, n_events, alone=_out_of_order_bid(corpus, n_events, rng))
    elif kind == "lost_tombstone":
        fold = _fold(corpus, n_events, track_from=max(0, n_events - (1 << 17)))
        merged_away = sorted((b, s, e, c) for (b, s, e, c) in fold.made_by
                             if (b, s, e) not in fold.sessions)
        b, s, e, c = merged_away[int(rng.integers(0, len(merged_away)))]
        return _records(fold.sessions) + [(b, (s, e), _value(s, e, c))]
    else:
        raise ValueError(f"unknown control {kind!r}")
    return _records(fold.sessions)


# ------------------------------------------------------- reading the answers
def _bidder(key: Any) -> int:
    return int(key[0] if isinstance(key, (tuple, list)) else key)


def fold_sink(records: Sequence[SinkRecord]) -> Dict[SessionKey, Dict[str, int]]:
    """The sink changelog folded: a tombstone deletes a (bidder, start,
    end), a row upserts it."""
    table: Dict[SessionKey, Dict[str, int]] = {}
    for key, window, value in records:
        k = (_bidder(key), window[0], window[1])
        if value is None:
            table.pop(k, None)
        else:
            table[k] = json.loads(value)
    return table


class StoreSessions:
    """The store's live sessions ``(bidder, start, end, count)``; the
    harness's log shows how many, not the list."""

    def __init__(self, rows) -> None:
        self.rows = list(rows)

    def __iter__(self):
        return iter(self.rows)

    def __len__(self) -> int:
        return len(self.rows)

    def __str__(self) -> str:
        return f"{len(self.rows)} sessions"


def read_store(executor) -> Dict[str, Any]:
    """Every live session in the device store: the session store keeps a
    slot a (bidder, session), with the session's bounds beside the key and
    the count in the aggregate's component after the event-time one."""
    state = executor.device.state
    occ = np.asarray(state["occ"])[:-1]
    live = np.flatnonzero(occ)
    cols = [np.asarray(state[name])[:-1][live].tolist()
            for name in ("key0", "sess_start", "sess_end", "a1")]
    return {"live_sessions": int(live.size), "sessions": StoreSessions(zip(*cols))}


def pull_queries(corpus: Corpus, n_events: int, seed: int, k: int) -> List[Tuple[int, str]]:
    """``k`` pull lookups drawn from the seed, as ``(bidder, sql)``: the
    bidders with most bids, bidders of random bids, and one bidder no bid
    names."""
    rng = np.random.default_rng(seed ^ 0xB0B)
    bidders = corpus.bidder[:n_events]
    ids, counts = np.unique(bidders, return_counts=True)
    hot = ids[np.argsort(-counts, kind="stable")[:k // 3]]
    drawn = bidders[rng.integers(0, n_events, k - len(hot) - 1)]
    keys = [int(b) for b in np.r_[hot, drawn]] + [int(ids.max()) + 12345]
    return [(b, "SELECT BIDDER, WINDOWSTART, WINDOWEND, BID_COUNT FROM BID_SESSIONS "
                f"WHERE BIDDER = {b};") for b in keys]


def read_pull(response: Dict[str, Any]) -> Dict[Tuple[int, int], int]:
    cols = response["columnNames"]
    at = [cols.index(c) for c in ("WINDOWSTART", "WINDOWEND", "BID_COUNT")]
    return {(r[at[0]], r[at[1]]): r[at[2]] for r in response["rows"]}


# ------------------------------------------------------------ the comparison
def compare(corpus: Corpus, n_events: int, sink_records: Sequence[SinkRecord],
            store: Optional[Dict[str, Any]],
            pulls: Optional[List[Tuple[int, Dict[Tuple[int, int], int]]]],
            ) -> Dict[str, Dict[str, float]]:
    """Every number compared, beside its limit: all exact, limit 0 (a
    session is integers: a bidder, two instants and a count)."""
    fold = _fold(corpus, n_events)
    want = fold.sessions
    got = fold_sink(sink_records)
    wrong = sum(1 for k, row in got.items() if k in want and (
        row.get("BID_COUNT") != want[k] or row.get("STARTTIME") != k[1]
        or row.get("ENDTIME") != k[2]))
    out = {
        "sink_sessions_wrong": {"value": wrong, "limit": 0},
        "sink_sessions_extra": {"value": sum(1 for k in got if k not in want), "limit": 0},
        "sink_sessions_missing": {"value": sum(1 for k in want if k not in got), "limit": 0},
        "sink_events_missing": {
            "value": abs(n_events - sum(int(row.get("BID_COUNT") or 0) for row in got.values())),
            "limit": 0},
    }
    if store is not None:
        horizon = fold.stream_time - GAP_MS - GRACE_MS
        resident = {(b, s, e, c) for (b, s, e), c in want.items() if e >= horizon}
        have = set(map(tuple, store["sessions"]))
        out["store_sessions_diff"] = {"value": len(resident ^ have), "limit": 0}
    if pulls is not None:
        by_bidder: Dict[int, Dict[Tuple[int, int], int]] = {b: {} for b, _ in pulls}
        for (b, s, e), c in want.items():
            if b in by_bidder:
                by_bidder[b][(s, e)] = c
        out["pulls_wrong"] = {
            "value": sum(1 for b, rows in pulls if rows != by_bidder[b]), "limit": 0}
    return out


# ------------------------------------------- which event a result record is of
def result_event_index(corpus: Corpus, lo: int, hi: int,
                       records: Sequence[SinkRecord]) -> np.ndarray:
    """For each sink record, the index of the bid it is the result of: a row
    ``(bidder, start, end, BID_COUNT)`` is the result of the bid after which
    the bidder's session first held exactly that; a tombstone is the result
    of the bid whose row follows it (a step emits a session's retractions
    just before the row that replaces them).  Only bids ``lo <= i < hi``
    are looked up; other records give -1."""
    made_by = _fold(corpus, hi, track_from=lo).made_by
    out = np.full(len(records), -1, dtype=np.int64)
    waiting: Dict[int, List[int]] = {}  # bidder -> tombstones before its next row
    for r, (key, window, value) in enumerate(records):
        b = _bidder(key)
        if value is None:
            waiting.setdefault(b, []).append(r)
            continue
        row = json.loads(value)
        event = made_by.get((b, window[0], window[1], row.get("BID_COUNT")), -1)
        out[r] = event
        for t in waiting.pop(b, ()):
            out[t] = event
    return out
