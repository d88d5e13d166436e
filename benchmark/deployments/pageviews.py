"""Deployment ``pageviews``: tumbling COUNT(*) GROUP BY URL over page views.

The corpus, the plain reference and the comparison for every configuration
whose ``deployment`` is ``pageviews``.  Nothing here imports the program:
the reference is a ``collections.Counter`` over the generated events.

Corpus (after ``chip_smoke.py``'s ``make_corpus``, which ran on the chip in
PR 21): in each hour-window ``uniform_share`` of the events are uniform over
``urls`` URLs and the rest Zipf(``zipf_a``) folded into the same universe,
shuffled together; event ``i`` has event time ``TS0 + i * HOUR /
events_per_window``, so each run of ``events_per_window`` events is one
tumbling window.
"""

from __future__ import annotations

import collections
import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

HOUR_MS = 3_600_000
#: first event time, aligned to the hour
TS0 = 1_700_000_000_000 - 1_700_000_000_000 % HOUR_MS

SOURCE_TOPIC = "page_views"


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
    window: np.ndarray   # int64[n] window start of each event


def make_corpus(seed: int, sizes: Dict[str, Any], n_events: int) -> Corpus:
    """``n_events`` page views from ``seed``.

    Every seed has the same views in another order: the views, and the
    order their ``seed_block_events``-sized blocks arrive in, are drawn from
    ``sizes["key_draw"]``; ``seed`` draws the order of the views *inside*
    each block.  So any tick of at least a block hands the store the same
    keys whatever the seed.  A fresh draw of keys per seed changes the work,
    not the order: on one machine two runs of one seed read
    ``latency_p50_ms`` within 0.1-1.3 % and three seeds 9 % apart (ticks 9 %
    longer for one seed on both of its runs; PERF.md, Findings, PR 24)."""
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
    idx = np.concatenate(hours)[:n_blocks * block].reshape(n_blocks, block)
    idx = np.random.default_rng(seed).permuted(idx, axis=1).reshape(-1)[:n_events]
    ts = TS0 + (np.arange(n_events, dtype=np.int64) * HOUR_MS) // per_window
    user = 1 + (np.arange(n_events, dtype=np.int64) * 7919) % 999
    payloads = [
        '{"URL":"/catalog/products/item-%07d/view.html","USER_ID":%d,"VIEWTIME":%d}'
        % row for row in zip(idx.tolist(), user.tolist(), ts.tolist())
    ]
    return Corpus(SOURCE_TOPIC, payloads, ts.tolist(), [], idx, ts - ts % HOUR_MS)


# ------------------------------------------------------------ the reference
def reference(corpus: Corpus, n_events: int) -> "collections.Counter[Tuple[str, int]]":
    """Events per (URL, window start) among the first ``n_events``."""
    return collections.Counter(zip(
        map(url_of, corpus.url_idx[:n_events].tolist()),
        corpus.window[:n_events].tolist(),
    ))


def control_reference(corpus: Corpus, n_events: int, kind: str,
                      seed: int) -> List[Tuple[Any, Optional[Tuple[int, int]], Optional[str]]]:
    """The reference put in the program's place with one stated guarantee
    broken; returns sink records ``(key, window, value)`` as the program
    would leave them.

    ``lost_event``  one acknowledged event missing from the final table
                    (at-most-once for a single record);
    ``lost_tick``   one served tick (4,096 events) missing;
    ``stale_count`` one key's final count one behind (an approximate or
                    stale answer where the configuration says exact).
    """
    rng = np.random.default_rng(seed ^ 0x5EED)
    keep = np.ones(n_events, dtype=bool)
    if kind == "lost_event":
        keep[int(rng.integers(0, n_events))] = False
    elif kind == "lost_tick":
        lo = int(rng.integers(0, max(1, n_events - 4096)))
        keep[lo:lo + 4096] = False
    elif kind != "stale_count":
        raise ValueError(f"unknown control {kind!r}")
    counts = collections.Counter(zip(
        map(url_of, corpus.url_idx[:n_events][keep].tolist()),
        corpus.window[:n_events][keep].tolist(),
    ))
    if kind == "stale_count":
        victim = sorted(counts)[int(rng.integers(0, len(counts)))]
        counts[victim] -= 1
    return [
        (u, (w, w + HOUR_MS), '{"CNT":%d}' % c) for (u, w), c in counts.items()
    ]


# ------------------------------------------------------- reading the answers
def fold_sink(records: Sequence[Tuple[Any, Optional[Tuple[int, int]], Optional[str]]]
              ) -> Tuple[Dict[Tuple[str, int], Optional[int]], int]:
    """The sink changelog folded to its final table, and how many records
    stepped a key's count backwards (EMIT CHANGES never does)."""
    table: Dict[Tuple[str, int], Optional[int]] = {}
    backwards = 0
    for key, window, value in records:
        k = (key[0] if isinstance(key, tuple) else key, window[0])
        c = None if value is None else json.loads(value)["CNT"]
        prev = table.get(k)
        if prev is not None and (c is None or c < prev):
            backwards += 1
        table[k] = c
    return table, backwards


def read_store(executor) -> Dict[str, int]:
    """Live (key, window) entries in the device store (all shards)."""
    occ = np.asarray(executor.device.state["occ"])
    return {"live_entries": int(occ[..., :-1].sum())}


def pull_queries(corpus: Corpus, n_events: int, seed: int, k: int) -> List[Tuple[str, str]]:
    """``k`` pull lookups drawn from the seed, as ``(url, sql)``: the
    hottest keys, keys of random events, and one key no event carries."""
    rng = np.random.default_rng(seed ^ 0xB0B)
    idx = corpus.url_idx[:n_events]
    hot = np.argsort(-np.bincount(idx))[:k // 3]
    drawn = idx[rng.integers(0, n_events, k - len(hot) - 1)]
    urls = [url_of(int(i)) for i in np.r_[hot, drawn]]
    urls.append(url_of(int(idx.max()) + 12345))
    return [
        (u, f"SELECT URL, WINDOWSTART, CNT FROM PV_COUNTS WHERE URL = '{u}';")
        for u in urls
    ]


def read_pull(response: Dict[str, Any]) -> Dict[int, int]:
    cols = response["columnNames"]
    return {
        r[cols.index("WINDOWSTART")]: r[cols.index("CNT")]
        for r in response["rows"]
    }


# ------------------------------------------------------------ the comparison
def compare(corpus: Corpus, n_events: int,
            sink_records, store: Optional[Dict[str, int]],
            pulls: Optional[List[Tuple[str, Dict[int, int]]]],
            ) -> Dict[str, Dict[str, float]]:
    """Every number compared, beside its limit.  All are exact (limit 0):
    integer counts have no rounding to allow for."""
    want = reference(corpus, n_events)
    got, backwards = fold_sink(sink_records)
    wrong = sum(1 for k, c in want.items() if got.get(k) != c)
    extra = sum(1 for k in got if k not in want)
    events_missing = n_events - sum(c or 0 for c in got.values())
    out = {
        "sink_keys_wrong": {"value": wrong, "limit": 0},
        "sink_keys_extra": {"value": extra, "limit": 0},
        "sink_events_missing": {"value": abs(events_missing), "limit": 0},
        "sink_counts_backwards": {"value": backwards, "limit": 0},
    }
    if store is not None:
        out["store_entries_diff"] = {
            "value": abs(store["live_entries"] - len(want)), "limit": 0}
    if pulls is not None:
        by_url: Dict[str, Dict[int, int]] = {u: {} for u, _ in pulls}
        for (u, w), c in want.items():
            if u in by_url:
                by_url[u][w] = c
        out["pulls_wrong"] = {
            "value": sum(1 for u, got in pulls if got != by_url[u]), "limit": 0}
    return out


# ------------------------------------------- which event a result record is of
def result_event_index(corpus: Corpus, lo: int, hi: int, records) -> np.ndarray:
    """For each sink record, the index of the event it is the result of: a
    ``CNT = c`` record of (URL, window) is the result of that pair's c-th
    event.  Only events ``lo <= i < hi`` are looked up; other records give
    -1."""
    key = corpus.url_idx[:hi] * 8 + (corpus.window[:hi] - TS0) // HOUR_MS
    order = np.argsort(key, kind="stable")
    sk = key[order]
    first = np.r_[0, np.flatnonzero(sk[1:] != sk[:-1]) + 1]
    run_start = np.repeat(first, np.diff(np.r_[first, len(sk)]))
    ordinal = np.empty(hi, dtype=np.int64)
    ordinal[order] = np.arange(hi) - run_start + 1
    lookup = {
        (k, c): i for i, k, c in zip(
            range(lo, hi), key[lo:hi].tolist(), ordinal[lo:hi].tolist())
    }
    out = np.full(len(records), -1, dtype=np.int64)
    for j, (rkey, window, value) in enumerate(records):
        if value is None:
            continue
        u = rkey[0] if isinstance(rkey, tuple) else rkey
        k = int(u[23:30]) * 8 + (window[0] - TS0) // HOUR_MS
        out[j] = lookup.get((k, json.loads(value)["CNT"]), -1)
    return out
