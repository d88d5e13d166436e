"""Built-in aggregate functions.

The 14 UDAF families of the reference (ksqldb-engine/.../function/udaf/:
count, count_distinct, sum, min, max, avg (average), stddev, correlation,
topk, topkdistinct, collect_list, collect_set, histogram,
earliest/latest_by_offset).

Host semantics (init/accumulate/merge/result/undo) are the parity oracle and
power the per-record changelog path; ``device_kind`` maps each family onto
the XLA segment-reduction kernels in ops/segments.py.  ``undo`` mirrors
KudafUndoAggregator (table changelog retractions).
"""

from __future__ import annotations

import math
from typing import Any, List, Optional, Tuple

from ksql_tpu.common import types as T
from ksql_tpu.common.types import SqlBaseType, SqlType
from ksql_tpu.functions.registry import (
    FunctionRegistry,
    Udaf,
    t_any,
    t_base,
    t_numeric,
)

NUM = t_numeric()
STR = t_base(SqlBaseType.STRING)
ANY = t_any()
INT = t_base(SqlBaseType.INTEGER)
COMPARABLE = t_base(
    SqlBaseType.INTEGER, SqlBaseType.BIGINT, SqlBaseType.DOUBLE,
    SqlBaseType.DECIMAL, SqlBaseType.STRING, SqlBaseType.DATE,
    SqlBaseType.TIME, SqlBaseType.TIMESTAMP, SqlBaseType.BOOLEAN,
    # BYTES compare lexicographically unsigned (Java Bytes.compareTo ==
    # Python bytes ordering) — min-/max-/topk-distinct bytes cases
    SqlBaseType.BYTES,
)


def _sum_type(ts: List[SqlType]) -> SqlType:
    # reference SumKudaf: SUM(INT)->INT, SUM(BIGINT)->BIGINT, etc.
    return ts[0]


def register_all(reg: FunctionRegistry) -> None:
    # ----------------------------------------------------------- COUNT(*)
    reg.register_udaf(Udaf(
        name="COUNT",
        params=[],
        returns=T.BIGINT,
        init=lambda: 0,
        accumulate=lambda s: s + 1,
        merge=lambda a, b: a + b,
        result=lambda s: s,
        undo=lambda s: s - 1,
        device_kind="count_star",
        description="Count of records",
    ))
    # COUNT(col) — non-null count
    reg.register_udaf(Udaf(
        name="COUNT",
        params=[ANY],
        returns=T.BIGINT,
        init=lambda: 0,
        accumulate=lambda s, v: s + (v is not None),
        merge=lambda a, b: a + b,
        result=lambda s: s,
        undo=lambda s, v: s - (v is not None),
        device_kind="count",
    ))
    reg.register_udaf(Udaf(
        name="COUNT_DISTINCT",
        params=[ANY],
        returns=T.BIGINT,
        init=lambda: set(),
        accumulate=lambda s, v: (s.add(_hashable(v)) or s) if v is not None else s,
        merge=lambda a, b: a | b,
        result=lambda s: len(s),
        device_kind="count_distinct",
    ))
    # --------------------------------------------------------------- SUM
    # reference SumKudaf initializes to 0 and skips nulls (SUM of only-null
    # input is 0, not NULL)
    reg.register_udaf(Udaf(
        name="SUM",
        params=[NUM],
        returns=_sum_type,
        init=lambda: 0,
        accumulate=lambda s, v: s if v is None else s + v,
        merge=lambda a, b: a + b,
        result=lambda s: s,
        undo=lambda s, v: s if v is None else s - v,
        device_kind="sum",
    ))
    # ----------------------------------------------------------- MIN/MAX
    for name, better, kind in (("MIN", lambda a, b: b < a, "min"), ("MAX", lambda a, b: b > a, "max")):
        reg.register_udaf(Udaf(
            name=name,
            params=[COMPARABLE],
            returns=lambda ts: ts[0],
            init=lambda: None,
            accumulate=(lambda better: lambda s, v: s if v is None else (v if s is None or better(s, v) else s))(better),
            merge=(lambda better: lambda a, b: b if a is None else (a if b is None else (b if better(a, b) else a)))(better),
            result=lambda s: s,
            device_kind=kind,
        ))
    # --------------------------------------------------------------- AVG
    reg.register_udaf(Udaf(
        name="AVG",
        params=[NUM],
        returns=T.DOUBLE,
        init=lambda: (0.0, 0),
        accumulate=lambda s, v: s if v is None else (s[0] + v, s[1] + 1),
        merge=lambda a, b: (a[0] + b[0], a[1] + b[1]),
        result=lambda s: (s[0] / s[1]) if s[1] else None,
        undo=lambda s, v: s if v is None else (s[0] - v, s[1] - 1),
        device_kind="avg",
    ))
    # ------------------------------------------------------------ STDDEV
    # STDDEV_SAMPLE (StddevKudaf) returns the sample standard deviation;
    # STDDEV_SAMP is a DIFFERENT reference function that returns the sample
    # VARIANCE (observed reference behavior, standarddeviation.json)
    for stddev_name in ("STDDEV_SAMPLE",):
        reg.register_udaf(Udaf(
            name=stddev_name,
            params=[NUM],
            returns=T.DOUBLE,
            init=lambda: (0.0, 0.0, 0),  # sum, sumsq, n
            accumulate=lambda s, v: s if v is None else (s[0] + v, s[1] + v * v, s[2] + 1),
            merge=lambda a, b: (a[0] + b[0], a[1] + b[1], a[2] + b[2]),
            result=_stddev_samp,
            undo=lambda s, v: s if v is None else (s[0] - v, s[1] - v * v, s[2] - 1),
            device_kind="stddev",
        ))
    reg.register_udaf(Udaf(
        name="STDDEV_SAMP",
        params=[NUM],
        returns=T.DOUBLE,
        init=lambda: (0.0, 0.0, 0),
        accumulate=lambda s, v: s if v is None else (s[0] + v, s[1] + v * v, s[2] + 1),
        merge=lambda a, b: (a[0] + b[0], a[1] + b[1], a[2] + b[2]),
        result=_var_samp,
        undo=lambda s, v: s if v is None else (s[0] - v, s[1] - v * v, s[2] - 1),
        device_kind=None,  # variance result: no stddev device kernel match
    ))
    reg.register_udaf(Udaf(
        name="STDDEV_POP",
        params=[NUM],
        returns=T.DOUBLE,
        init=lambda: (0.0, 0.0, 0),
        accumulate=lambda s, v: s if v is None else (s[0] + v, s[1] + v * v, s[2] + 1),
        merge=lambda a, b: (a[0] + b[0], a[1] + b[1], a[2] + b[2]),
        result=_stddev_pop,
        device_kind="stddev",
    ))
    # ------------------------------------------------------- CORRELATION
    reg.register_udaf(Udaf(
        name="CORRELATION",
        params=[NUM, NUM],
        returns=T.DOUBLE,
        init=lambda: (0, 0.0, 0.0, 0.0, 0.0, 0.0),  # n, sx, sy, sxx, syy, sxy
        accumulate=_corr_acc,
        merge=lambda a, b: tuple(x + y for x, y in zip(a, b)),
        result=_corr_result,
        undo=_corr_undo,
        device_kind="correlation",
    ))
    # -------------------------------------------------------------- TOPK
    reg.register_udaf(Udaf(
        name="TOPK",
        params=[COMPARABLE, INT],
        returns=lambda ts: SqlType.array(ts[0]),
        init=lambda: [],
        accumulate=_topk_acc,
        merge=lambda a, b: _topk_merge(a, b, distinct=False),
        result=lambda s: [v for v, _ in s],
        device_kind="topk",
        literal_params=1,
    ))
    # TOPK with additional columns: TOPK(sort_col, col0..colN, k) returns
    # ARRAY<STRUCT<sort_col, col0, ...>> ordered by sort_col desc (reference
    # topk/TopkKudaf variadic form, topk-group-by.json struct cases)
    for extra in range(1, 5):
        reg.register_udaf(Udaf(
            name="TOPK",
            params=[COMPARABLE] + [ANY] * extra + [INT],
            returns=(lambda extra: lambda ts: SqlType.array(SqlType.struct(
                [("sort_col", ts[0])]
                + [(f"col{i}", ts[1 + i]) for i in range(extra)]
            )))(extra),
            init=lambda: [],
            accumulate=_topk_struct_acc,
            merge=_topk_struct_merge,
            result=(lambda extra: lambda s: [
                {"sort_col": v, **{f"col{i}": e[i] for i in range(extra)}}
                for v, e, _ in s
            ])(extra),
            # on the device: the sort values' top-K state with one width-K
            # payload column per extra column (numeric extras only)
            device_kind="topk",
            literal_params=1,
        ))
    reg.register_udaf(Udaf(
        name="TOPKDISTINCT",
        params=[COMPARABLE, INT],
        returns=lambda ts: SqlType.array(ts[0]),
        init=lambda: [],
        accumulate=_topk_distinct_acc,
        merge=lambda a, b: _topk_merge(a, b, distinct=True),
        result=lambda s: [v for v, _ in s],
        device_kind="topk",
        literal_params=1,
    ))
    # ----------------------------------------------------------- COLLECT
    # cap during accumulation like the reference (CollectListUdaf LIMIT 1000)
    reg.register_udaf(Udaf(
        name="COLLECT_LIST",
        params=[ANY],
        returns=lambda ts: SqlType.array(ts[0]),
        init=lambda: [],
        accumulate=_collect_list_acc,
        merge=lambda a, b: (a + b)[: _limit_of("collect_list")],
        result=lambda s: list(s),
        undo=_collect_undo,
        device_kind="collect",
    ))
    reg.register_udaf(Udaf(
        name="COLLECT_SET",
        params=[ANY],
        returns=lambda ts: SqlType.array(ts[0]),
        init=lambda: [],
        accumulate=_collect_set_acc,
        merge=lambda a, b: _dedupe(a + b)[: _limit_of("collect_set")],
        result=lambda s: list(s),
        device_kind="collect",
    ))
    # --------------------------------------------------------- HISTOGRAM
    reg.register_udaf(Udaf(
        name="HISTOGRAM",
        params=[STR],
        returns=SqlType.map(T.STRING, T.BIGINT),
        init=lambda: {},
        accumulate=_hist_acc,
        merge=_hist_merge,
        result=lambda s: dict(s),
        undo=_hist_undo,
        device_kind="histogram",
    ))
    # ------------------------------------------- EARLIEST/LATEST_BY_OFFSET
    # reference default ignoreNulls=true (EarliestByOffset.java/LatestByOffset)
    reg.register_udaf(Udaf(
        name="EARLIEST_BY_OFFSET",
        params=[ANY],
        returns=lambda ts: ts[0],
        init=lambda: _ABSENT,
        accumulate=lambda s, v: v if (s is _ABSENT and v is not None) else s,
        merge=lambda a, b: a if a is not _ABSENT else b,
        result=lambda s: None if s is _ABSENT else s,
        device_kind="earliest",
    ))
    reg.register_udaf(Udaf(
        name="LATEST_BY_OFFSET",
        params=[ANY],
        returns=lambda ts: ts[0],
        init=lambda: _ABSENT,
        accumulate=lambda s, v: v if v is not None else s,
        merge=lambda a, b: b if b is not _ABSENT else a,
        result=lambda s: None if s is _ABSENT else s,
        device_kind="latest",
    ))
    BOOL = t_base(SqlBaseType.BOOLEAN)
    # (col, ignoreNulls) variants
    for nm, earliest in (("EARLIEST_BY_OFFSET", True), ("LATEST_BY_OFFSET", False)):
        reg.register_udaf(Udaf(
            name=nm,
            params=[ANY, BOOL],
            returns=lambda ts: ts[0],
            init=lambda: _ABSENT,
            accumulate=(lambda earliest: lambda s, v, ignore_nulls: _el_acc(s, v, ignore_nulls, earliest))(earliest),
            merge=(lambda earliest: (lambda a, b: (a if a is not _ABSENT else b) if earliest else (b if b is not _ABSENT else a)))(earliest),
            result=lambda s: None if s is _ABSENT else s,
            device_kind="earliest" if earliest else "latest",
            literal_params=1,
        ))
        # (col, n) and (col, n, ignoreNulls): earliest/latest N as an array;
        # state entries carry (value, n) so merge can re-cap (like TOPK)
        for params, lits in (([ANY, INT], 1), ([ANY, INT, BOOL], 2)):
            reg.register_udaf(Udaf(
                name=nm,
                params=params,
                returns=lambda ts: SqlType.array(ts[0]),
                init=lambda: [],
                accumulate=(lambda earliest: lambda s, v, n, *rest: _eln_acc(s, v, n, (rest[0] if rest else True), earliest))(earliest),
                merge=(lambda earliest: lambda a, b: _eln_merge(a, b, earliest))(earliest),
                result=lambda s: [v for v, _ in s],
                device_kind="collect",
                literal_params=lits,
            ))
    # ---------------------------------------------------------------- ATTR
    # udaf/attr/Attr.java:34 — collect (value, count) entries; the result
    # is the single distinct value when exactly one has count>0, else NULL
    # (signals "expected a singular value but saw many"); TableUdaf w/ undo
    reg.register_udaf(Udaf(
        name="ATTR",
        params=[ANY],
        returns=lambda ts: ts[0],
        init=lambda: (),
        accumulate=lambda s, v: _attr_update(s, v, 1),
        merge=_attr_merge,
        result=_attr_result,
        undo=lambda s, v: _attr_update(s, v, -1),
        device_kind="attr",
        description="Collect as a singleton; NULL when multiple values seen",
    ))
    # ------------------------------------------------------------ SUM_LIST
    # udaf/sum/ListSumUdaf.java:28 — sums the elements of each list value
    for mk, t in ((t_exact_array(T.DOUBLE), T.DOUBLE),
                  (t_exact_array(T.INTEGER), T.INTEGER),
                  (t_exact_array(T.BIGINT), T.BIGINT)):
        reg.register_udaf(Udaf(
            name="SUM_LIST",
            params=[mk],
            returns=t,
            init=lambda: 0,
            accumulate=lambda s, v: s if v is None else s + sum(x for x in v if x is not None),
            merge=lambda a, b: a + b,
            result=lambda s: s,
            undo=lambda s, v: s if v is None else s - sum(x for x in v if x is not None),
            description="Returns the sum of elements contained in the list.",
        ))


# ------------------------------------------------------------------ helpers

_ABSENT = object()
_COLLECT_LIMIT = 1000
#: per-engine overrides from ksql.functions.<name>.limit, installed by the
#: engine's poll loop for the duration of its processing tick
_LIMIT_OVERRIDES: dict = {}


def _limit_of(name: str) -> int:
    try:
        return int(_LIMIT_OVERRIDES.get(name, _COLLECT_LIMIT))
    except (TypeError, ValueError):
        return _COLLECT_LIMIT


def _collect_list_acc(s, v):
    if len(s) < _limit_of("collect_list"):
        s = s + [v]
    return s


def _collect_undo(s, v):
    # remove first occurrence (reference CollectListUdaf undo)
    out = list(s)
    try:
        out.remove(v)
    except ValueError:
        pass
    return out


def _collect_set_acc(s, v):
    if len(s) < _limit_of("collect_set") and _hashable(v) not in {
        _hashable(x) for x in s
    }:
        s = s + [v]
    return s


def _el_acc(s, v, ignore_nulls, earliest):
    if v is None and ignore_nulls:
        return s
    if earliest:
        return v if s is _ABSENT else s
    return v


def _eln_acc(s, v, n, ignore_nulls, earliest):
    if v is None and ignore_nulls:
        return s
    s = s + [(v, n)]
    if len(s) > n:
        s = s[:n] if earliest else s[-n:]
    return s


def _eln_merge(a, b, earliest):
    merged = list(a) + list(b)
    if not merged:
        return []
    n = merged[0][1]
    if len(merged) > n:
        merged = merged[:n] if earliest else merged[-n:]
    return merged


def _hashable(v: Any) -> Any:
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


def _dedupe(xs: List[Any]) -> List[Any]:
    seen = set()
    out = []
    for x in xs:
        h = _hashable(x)
        if h not in seen:
            seen.add(h)
            out.append(x)
    return out


def _stddev_samp(s: Tuple[float, float, int]) -> Optional[float]:
    total, sumsq, n = s
    if n < 2:
        return 0.0 if n == 1 else None
    var = (sumsq - total * total / n) / (n - 1)
    return math.sqrt(max(var, 0.0))


def _var_samp(s: Tuple[float, float, int]) -> Optional[float]:
    total, sumsq, n = s
    if n < 2:
        return 0.0 if n == 1 else None
    return (sumsq - total * total / n) / (n - 1)


def _stddev_pop(s: Tuple[float, float, int]) -> Optional[float]:
    total, sumsq, n = s
    if n < 1:
        return None
    var = (sumsq - total * total / n) / n
    return math.sqrt(max(var, 0.0))


def _corr_acc(s, x, y):
    if x is None or y is None:
        return s
    n, sx, sy, sxx, syy, sxy = s
    return (n + 1, sx + x, sy + y, sxx + x * x, syy + y * y, sxy + x * y)


def _corr_undo(s, x, y):
    if x is None or y is None:
        return s
    n, sx, sy, sxx, syy, sxy = s
    return (n - 1, sx - x, sy - y, sxx - x * x, syy - y * y, sxy - x * y)


def _corr_result(s) -> Optional[float]:
    # matches Apache Commons PearsonsCorrelation: NaN until there are two
    # points / any variance (reference CorrelationUdaf)
    n, sx, sy, sxx, syy, sxy = s
    if n < 2:
        return float("nan")
    cov = sxy - sx * sy / n
    vx = sxx - sx * sx / n
    vy = syy - sy * sy / n
    if vx <= 0 or vy <= 0:
        return float("nan")
    return cov / math.sqrt(vx * vy)


def _topk_acc(s, v, k):
    if v is None:
        return s
    s = s + [(v, k)]
    s.sort(key=lambda t: t[0], reverse=True)
    return s[:k]


def _topk_distinct_acc(s, v, k):
    if v is None or any(x == v for x, _ in s):
        return s
    s = s + [(v, k)]
    s.sort(key=lambda t: t[0], reverse=True)
    return s[:k]


def _topk_struct_acc(s, v, *rest):
    extras, k = rest[:-1], rest[-1]
    if v is None:
        return s
    s = s + [(v, tuple(extras), k)]
    s.sort(key=lambda t: t[0], reverse=True)
    return s[:k]


def _topk_struct_merge(a, b):
    if not a and not b:
        return []
    k = (a or b)[0][2]
    merged = list(a) + list(b)
    merged.sort(key=lambda t: t[0], reverse=True)
    return merged[:k]


def _topk_merge(a, b, distinct: bool):
    if not a and not b:
        return []
    k = (a or b)[0][1]
    merged = list(a) + list(b)
    if distinct:
        seen = set()
        deduped = []
        for v, kk in merged:
            if v not in seen:
                seen.add(v)
                deduped.append((v, kk))
        merged = deduped
    merged.sort(key=lambda t: t[0], reverse=True)
    return merged[:k]


def _hist_acc(s, v):
    if v is None:
        return s
    if len(s) >= 1000 and v not in s:
        return s
    s = dict(s)
    s[v] = s.get(v, 0) + 1
    return s


def _hist_merge(a, b):
    out = dict(a)
    for k, v in b.items():
        out[k] = out.get(k, 0) + v
    return out


def _hist_undo(s, v):
    if v is None or v not in s:
        return s
    s = dict(s)
    s[v] -= 1
    if s[v] <= 0:
        del s[v]
    return s


# ------------------------------------------------------------------- ATTR


def t_exact_array(el: SqlType):
    """Matcher for ARRAY<el> exactly (SUM_LIST's per-element-type overloads)."""
    return lambda x: x.base == SqlBaseType.ARRAY and x.element == el


def _attr_update(s, v, count):
    """State: tuple of (hashable_key, original_value, count) entries —
    Attr.java's List<Struct{VALUE,COUNT}> with Math.max(0, n+count)."""
    k = _hashable(v)
    out = []
    found = False
    for ek, ev, n in s:
        if ek == k:
            found = True
            out.append((ek, ev, max(0, n + count)))
        else:
            out.append((ek, ev, n))
    if not found and count > 0:
        out.append((k, v, count))
    return tuple(out)


def _attr_merge(a, b):
    out = a
    for ek, ev, n in b:
        out = _attr_update(out, ev, n)
    return out


def _attr_result(s):
    live = [(ev, n) for _ek, ev, n in s if n > 0]
    if len(live) != 1:
        return None
    return live[0][0]
