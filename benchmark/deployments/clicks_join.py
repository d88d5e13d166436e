"""Deployment ``clicks_join``: ksqlDB's quickstart join, pageviews enriched
from the users table and filtered on a joined column (stream-table LEFT
JOIN + WHERE).

The corpus, the plain reference and the comparison for every configuration
whose ``deployment`` is ``clicks_join``.  Nothing here imports the program:
the reference is a ``dict`` folded over the table's changelog and a loop
over the pageviews.

Corpus: the records of ksql-datagen's two quickstarts, as their Avro
schemas define them (``ksqldb-examples/src/main/resources/``):

``users_schema.avro``      registertime long in [1487715775521, 1519273364600],
                           userid ``User_[1-9]{0,1}``, regionid ``Region_[1-9]?``,
                           gender one of MALE / FEMALE / OTHER; key ``userid``,
                           JSON values.  Every record is an upsert of one of
                           the ten users: the changelog is last-write-wins
                           from its eleventh record on.
``pageviews_schema.avro``  viewtime long, an iteration from 1 in steps of 10,
                           userid ``User_[1-9]{0,1}``, pageid ``Page_[1-9][0-9]?``;
                           DELIMITED values.  Pageview ``i`` has viewtime
                           ``1 + 10 i``, so a sink record names its pageview.

The users' changelog is loaded whole before the first pageview (the
harness's producer feeds one topic).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

TS0 = 1_700_000_000_000

SOURCE_TOPIC = "pageviews"
TABLE_TOPIC = "users"

#: every string ``User_[1-9]{0,1}`` / ``Region_[1-9]?`` / ``Page_[1-9][0-9]?`` matches
USERS = ["User_"] + [f"User_{d}" for d in range(1, 10)]
REGIONS = ["Region_"] + [f"Region_{d}" for d in range(1, 10)]
PAGES = [f"Page_{d}" for d in range(1, 10)] + [f"Page_{d}" for d in range(10, 100)]
GENDERS = ["MALE", "FEMALE", "OTHER"]
REGISTERTIME = (1487715775521, 1519273364600)
#: the gender the quickstart's ``pageviews_female`` keeps
KEPT = "FEMALE"
VIEWTIME0, VIEWTIME_STEP = 1, 10

SinkRecord = Tuple[Any, Optional[Tuple[int, int]], Optional[str]]
#: a users row as the join reads it: (regionid, gender)
UserRow = Tuple[str, str]
#: a result: (userid, pageid, regionid, gender)
Result = Tuple[str, str, str, str]


@dataclasses.dataclass
class Corpus:
    source_topic: str
    payloads: List[str]
    ts: List[int]
    #: table loads that precede the stream: (topic, [(key, value, ts), ...])
    preload: List[Tuple[str, List[Tuple[Any, Optional[str], int]]]]
    view_user: List[str]     # userid of each pageview
    view_page: List[str]     # pageid of each pageview
    #: the users' changelog: (userid, row), row None for a tombstone
    changelog: List[Tuple[str, Optional[UserRow]]]


def build_corpus(changelog: Sequence[Tuple[str, Optional[UserRow], int]],
                 view_user: Sequence[str], view_page: Sequence[str]) -> Corpus:
    """The records of a changelog ``(userid, (regionid, gender) | None,
    registertime)`` and of pageviews, as ksql-datagen writes them."""
    rows = [
        (u, None if row is None else
         '{"registertime":%d,"userid":"%s","regionid":"%s","gender":"%s"}' % (rt, u, *row), TS0)
        for u, row, rt in changelog
    ]
    n = len(view_user)
    payloads = [
        "%d,%s,%s" % (VIEWTIME0 + VIEWTIME_STEP * i, u, p)
        for i, (u, p) in enumerate(zip(view_user, view_page))
    ]
    return Corpus(SOURCE_TOPIC, payloads, list(range(TS0 + 1, TS0 + 1 + n)),
                  [(TABLE_TOPIC, rows)], list(view_user), list(view_page),
                  [(u, row) for u, row, _rt in changelog])


def make_corpus(seed: int, sizes: Dict[str, Any], n_events: int) -> Corpus:
    """``users_changelog_records`` users records and ``n_events`` pageviews.

    Every seed has the same work in another order, as ``pageviews.py`` has
    it: the users' changelog (so the table, and with it the share of the
    pageviews that the WHERE keeps) and which pageviews there are come from
    ``sizes["key_draw"]``; ``seed`` draws the order of the pageviews inside
    each ``seed_block_events``-sized block."""
    n_chg = int(sizes["users_changelog_records"])
    block = int(sizes["seed_block_events"])
    draw = np.random.default_rng(int(sizes["key_draw"]))

    # the table first, so that its draw does not depend on the pageviews' count
    chg_user = draw.integers(0, len(USERS), n_chg)
    chg_region = draw.integers(0, len(REGIONS), n_chg)
    chg_gender = draw.integers(0, len(GENDERS), n_chg)
    chg_time = draw.integers(REGISTERTIME[0], REGISTERTIME[1] + 1, n_chg)
    changelog = [
        (USERS[u], (REGIONS[r], GENDERS[g]), rt)
        for u, r, g, rt in zip(chg_user.tolist(), chg_region.tolist(),
                               chg_gender.tolist(), chg_time.tolist())
    ]

    n_blocks = -(-n_events // block)
    total = n_blocks * block
    # a pageview is one code (user, page), so that a block's order moves
    # the pair together
    view = draw.integers(0, len(USERS), total) * len(PAGES) + draw.integers(0, len(PAGES), total)
    view = np.random.default_rng(seed).permuted(
        view.reshape(n_blocks, block), axis=1).reshape(-1)[:n_events]
    return build_corpus(changelog,
                        [USERS[u] for u in (view // len(PAGES)).tolist()],
                        [PAGES[p] for p in (view % len(PAGES)).tolist()])


# ------------------------------------------------------------ the reference
def reference_table(corpus: Corpus, records: Optional[int] = None) -> Dict[str, UserRow]:
    """The changelog (its first ``records`` records; all of it by default)
    folded to its last write per key, tombstoned keys gone."""
    table: Dict[str, UserRow] = {}
    for user, row in corpus.changelog[:records]:
        if row is None:
            table.pop(user, None)
        else:
            table[user] = row
    return table


def reference(corpus: Corpus, n_events: int,
              table: Optional[Dict[str, UserRow]] = None,
              ) -> Tuple[Dict[str, UserRow], Dict[int, Result]]:
    """The table, and per pageview index among the first ``n_events`` that
    has a result its ``(userid, pageid, regionid, gender)``: the pageview's
    user is live in the table and the row's gender is the kept one."""
    table = reference_table(corpus) if table is None else table
    results: Dict[int, Result] = {}
    for i, (u, p) in enumerate(zip(corpus.view_user[:n_events], corpus.view_page)):
        row = table.get(u)
        if row is not None and row[1] == KEPT:
            results[i] = (u, p, row[0], row[1])
    return table, results


def _records(results: Dict[int, Result]) -> List[SinkRecord]:
    """Reference results as the program leaves them on the sink topic."""
    return [
        (u, None, "%d,%s,%s,%s" % (VIEWTIME0 + VIEWTIME_STEP * i, p, region, gender))
        for i, (u, p, region, gender) in results.items()
    ]


def control_reference(corpus: Corpus, n_events: int, kind: str,
                      seed: int) -> List[SinkRecord]:
    """The reference put in the program's place with one stated guarantee
    broken; returns sink records ``(key, window, value)`` as the program
    would leave them.

    ``lost_event``   one pageview that has a result is left without it;
    ``lost_tick``    one served tick (4,096 pageviews) has no results;
    ``stale_table``  the join reads the table as the first half of its
                     changelog left it, without the later writes.
    """
    rng = np.random.default_rng(seed ^ 0x5EED)
    if kind == "stale_table":
        stale = reference_table(corpus, len(corpus.changelog) // 2)
        return _records(reference(corpus, n_events, stale)[1])
    _table, results = reference(corpus, n_events)
    if kind == "lost_event":
        del results[sorted(results)[int(rng.integers(0, len(results)))]]
    elif kind == "lost_tick":
        lo = int(rng.integers(0, max(1, n_events - 4096)))
        for i in range(lo, lo + 4096):
            results.pop(i, None)
    else:
        raise ValueError(f"unknown control {kind!r}")
    return _records(results)


# ------------------------------------------------------- reading the answers
def parse_record(record: SinkRecord) -> Optional[Tuple[int, Result]]:
    """A sink record as ``(pageview index, (userid, pageid, regionid,
    gender))``; None for a record without a value, with other fields than
    the four, or whose viewtime no pageview has."""
    key, _window, value = record
    fields = [] if value is None else value.split(",")
    if len(fields) != 4 or not fields[0].isdigit():
        return None
    viewtime, page, region, gender = fields
    index, rest = divmod(int(viewtime) - VIEWTIME0, VIEWTIME_STEP)
    if rest:
        return None
    return index, (key[0] if isinstance(key, tuple) else key, page, region, gender)


def read_store(executor) -> Dict[str, Any]:
    """The join table as it lies in the device store: every live slot's
    key, REGIONID and GENDER (strings are int64 dictionary codes there)."""
    device = executor.device
    jtab = {k: np.asarray(v) for k, v in device.state["jtab"].items()}
    live = np.flatnonzero(jtab["occ"][:-1])
    lookup = device.dictionary.lookup

    def strings(codes, valid=None) -> List[Optional[str]]:
        names = {c: lookup(c) for c in np.unique(codes).tolist()}
        ok = [True] * len(codes) if valid is None else valid.tolist()
        return [names[c] if v else None for c, v in zip(codes.tolist(), ok)]

    def column(suffix: str) -> List[Optional[str]]:
        name = next(k[2:] for k in jtab if k.startswith("v_") and k.endswith(suffix))
        return strings(jtab["v_" + name][live], jtab["m_" + name][live])

    return {"live_entries": int(live.size), "users": strings(jtab["key0"][live]),
            "regions": column("REGIONID"), "genders": column("GENDER")}


def pull_queries(corpus: Corpus, n_events: int, seed: int, k: int) -> List[Tuple[str, str]]:
    """None: ``PAGEVIEWS_FEMALE`` is a stream, and a stream answers no pull
    query."""
    return []


def read_pull(response: Dict[str, Any]) -> Dict[str, Any]:
    return {}


# ------------------------------------------------------------ the comparison
def compare(corpus: Corpus, n_events: int, sink_records: Sequence[SinkRecord],
            store: Optional[Dict[str, Any]], pulls) -> Dict[str, Dict[str, float]]:
    """Every number compared, beside its limit.  All are exact (limit 0):
    a join result is a row of integers and strings, with no rounding to
    allow for."""
    table, want = reference(corpus, n_events)
    seen: set = set()
    wrong = extra = 0
    for record in sink_records:
        got = parse_record(record)
        if got is None or got[0] not in want or got[0] in seen:
            extra += 1  # of a pageview that has no result, or a second one
            continue
        seen.add(got[0])
        wrong += got[1] != want[got[0]]
    out = {
        "sink_rows_wrong": {"value": wrong, "limit": 0},
        "sink_rows_extra": {"value": extra, "limit": 0},
        "sink_events_missing": {"value": len(want) - len(seen), "limit": 0},
    }
    if store is not None:
        got_table = dict(zip(store["users"], zip(store["regions"], store["genders"])))
        out["table_entries_diff"] = {
            "value": abs(store["live_entries"] - len(table)), "limit": 0}
        out["table_values_wrong"] = {
            "value": sum(1 for k, row in table.items() if got_table.get(k) != row)
            + sum(1 for k in got_table if k not in table),
            "limit": 0}
    return out


# ------------------------------------------- which event a result record is of
def result_event_index(corpus: Corpus, lo: int, hi: int,
                       records: Sequence[SinkRecord]) -> np.ndarray:
    """For each sink record, the index of the pageview it is the result of
    (its viewtime names it).  Only pageviews ``lo <= i < hi`` are looked
    up; other records give -1."""
    out = np.full(len(records), -1, dtype=np.int64)
    for j, record in enumerate(records):
        got = parse_record(record)
        if got is not None and lo <= got[0] < hi:
            out[j] = got[0]
    return out
