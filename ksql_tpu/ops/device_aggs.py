"""Device aggregation kernels: UDAF families as scatter-combined components.

The XLA analog of KudafAggregator.apply (ksqldb-execution/.../udaf/
KudafAggregator.java:56): each supported ``device_kind`` (declared on the
host Udaf in functions/udafs.py) decomposes into 'add'/'min'/'max' state
components that hash_store.scatter_combine folds in O(batch) scatters, plus
a ``finalize`` that maps slot state → output column (the result() analog).

Families whose state is variable-size per key fold into bounded per-slot
vector state (collect_list / collect_set capped at their limit, topk at k,
histogram at 1,000 entries); count_distinct exact has no device
decomposition and keeps the query on the row oracle.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ksql_tpu.common import types as T
from ksql_tpu.common.types import SqlBaseType, SqlType
from ksql_tpu.compiler.jax_expr import DCol, DeviceUnsupported
from ksql_tpu.ops.hash_store import AggComponent

_F64_MAX = np.finfo(np.float64).max
_I64_MAX = np.iinfo(np.int64).max
_I32_MAX = np.iinfo(np.int32).max


@dataclasses.dataclass
class DeviceAgg:
    """A compiled device aggregate: components + per-row contributions +
    finalizer."""

    components: Tuple[AggComponent, ...]
    # (args, row_active) -> per-component contribution arrays
    contribs: Callable[[Sequence[DCol], jnp.ndarray], List[jnp.ndarray]]
    # component slot arrays -> (data, valid); a vector result adds its
    # element bits, a map its counts, and an ARRAY<STRUCT> result is a
    # whole DCol with its ``fields``
    finalize: Callable[[Sequence[jnp.ndarray]], Tuple[jnp.ndarray, jnp.ndarray]]
    result_type: SqlType
    # when set, table-aggregation undo uses these contributions instead of
    # component-wise negation (families whose fold inverts differently,
    # e.g. histogram's signed count increments)
    undo_contribs: Optional[Callable] = None
    # when set, |component 0| exceeding this bound at emission means the
    # finalized value no longer round-trips its float64 carrier exactly
    # (DECIMAL SUM's int64 scaled accumulator past 2^53): the runtime
    # raises instead of emitting a silently drifted value
    exact_abs_bound: Optional[int] = None


def _numeric_data(a: DCol) -> jnp.ndarray:
    return a.data


def _minmax_dtype(t: SqlType):
    if t.base in (SqlBaseType.DOUBLE, SqlBaseType.DECIMAL):
        return np.float64, np.inf  # ±inf sentinels: data may contain ±F64_MAX
    if t.base == SqlBaseType.INTEGER:
        return np.int32, _I32_MAX
    return np.int64, _I64_MAX


#: hard ceiling on per-key vector state width (collect/topk); wider caps
#: keep the query on the oracle rather than blow up HBM
MAX_VEC_WIDTH = 4096

#: DECIMAL SUM exactness envelope: the certified number of max-magnitude
#: addends a per-key sum absorbs before its int64 accumulator could pass
#: 2^53 scaled units (where the float64 finalize stops being exact).  With
#: 10^p bounding one addend, device eligibility requires
#: 10^p * HEADROOM <= 2^53 — i.e. result precision <= 12
SUM_ACCUM_HEADROOM_ROWS = 1000


def _vec_dtype(t: SqlType):
    """Element storage dtype for vector state (strings/bytes carry their
    dictionary hash codes, booleans int8)."""
    if t.base in (SqlBaseType.DOUBLE, SqlBaseType.DECIMAL):
        return np.float64
    if t.base == SqlBaseType.BOOLEAN:
        return np.int8
    if t.base == SqlBaseType.INTEGER:
        return np.int32
    return np.int64


def compile_device_agg(
    kind: str,
    arg_types: Sequence[SqlType],
    result_type: SqlType,
    fname: str = "",
    literals: Sequence[object] = (),
) -> DeviceAgg:
    """Build the device decomposition for one aggregation call.  ``fname``
    disambiguates families sharing a kind (STDDEV_POP vs STDDEV_SAMP);
    ``literals`` are the values of trailing literal params (TOPK's k,
    earliest/latest's n and ignoreNulls) when statically known."""
    if kind == "count_star":
        return DeviceAgg(
            components=(AggComponent("add", "int64", 0),),
            contribs=lambda args, act, seq=None: [act.astype(jnp.int64)],
            finalize=lambda comps: (comps[0], jnp.ones_like(comps[0], bool)),
            result_type=T.BIGINT,
        )
    if kind == "count":
        return DeviceAgg(
            components=(AggComponent("add", "int64", 0),),
            contribs=lambda args, act, seq=None: [(act & args[0].valid).astype(jnp.int64)],
            finalize=lambda comps: (comps[0], jnp.ones_like(comps[0], bool)),
            result_type=T.BIGINT,
        )
    if kind == "sum":
        t = result_type
        if t.base == SqlBaseType.DECIMAL:
            # exact decimal folding: accumulate the SCALED UNSCALED value in
            # int64 (each ≤15-digit addend recovers exactly from its f64
            # carrier via round), so in-precision sums never drift the way
            # a raw f64 fold would; finalize rescales (≤15 digits: f64-exact)
            if 10 ** int(t.precision or 0) * SUM_ACCUM_HEADROOM_ROWS > 2 ** 53:
                # the ACCUMULATED sum, not just each addend, must survive
                # finalize's int64→float64 conversion (exact only below
                # 2^53 scaled units).  10^precision bounds one addend;
                # reserve headroom for SUM_ACCUM_HEADROOM_ROWS max-magnitude
                # rows — beyond that the device cannot certify exactness
                # statically, and the oracle's unbounded decimal arithmetic
                # keeps the query instead of silently drifting
                raise DeviceUnsupported(
                    f"DECIMAL({t.precision},{t.scale}) SUM can exceed the "
                    "2^53-exact device envelope (int64 accumulator decodes "
                    "through float64)"
                )
            scale_f = float(10 ** (t.scale or 0))
            return DeviceAgg(
                components=(AggComponent("add", "int64", 0),),
                contribs=lambda args, act, seq=None: [
                    jnp.where(
                        act & args[0].valid,
                        jnp.round(args[0].data * scale_f), 0.0,
                    ).astype(jnp.int64)
                ],
                finalize=lambda comps: (
                    comps[0].astype(jnp.float64) / scale_f,
                    jnp.ones(comps[0].shape, bool),
                ),
                result_type=t,
                # runtime backstop for the static gate above: a key whose
                # ACCUMULATED sum still crosses 2^53 scaled units (more
                # than the certified headroom of max-magnitude rows) is
                # detected at emission rather than silently drifting
                exact_abs_bound=2 ** 53,
            )
        dt = (
            np.float64
            if t.base == SqlBaseType.DOUBLE
            else (np.int32 if t.base == SqlBaseType.INTEGER else np.int64)
        )
        return DeviceAgg(
            components=(AggComponent("add", np.dtype(dt).name, 0),),
            contribs=lambda args, act, seq=None: [
                jnp.where(act & args[0].valid, args[0].data, 0).astype(dt)
            ],
            # SumKudaf: 0-initialized, nulls skipped ⇒ always non-null
            finalize=lambda comps: (comps[0], jnp.ones(comps[0].shape, bool)),
            result_type=t,
        )
    if kind in ("min", "max"):
        t = arg_types[0]
        if t.base in (SqlBaseType.STRING, SqlBaseType.BYTES):
            raise DeviceUnsupported("MIN/MAX over strings on device")
        dt, sentinel = _minmax_dtype(t)
        sign = 1 if kind == "min" else -1
        fill = sentinel if kind == "min" else (-sentinel if dt == np.float64 else -sentinel - 1)
        combine = kind

        def contribs(args, act, seq=None, fill=fill, dt=dt):
            ok = act & args[0].valid
            return [
                jnp.where(ok, args[0].data.astype(dt), jnp.asarray(fill, dt)),
                ok.astype(jnp.int32),
            ]

        def finalize(comps):
            seen = comps[1] > 0
            return comps[0], seen

        return DeviceAgg(
            components=(
                AggComponent(combine, np.dtype(dt).name, fill),
                AggComponent("max", "int32", 0),
            ),
            contribs=contribs,
            finalize=finalize,
            result_type=t,
        )
    if kind == "avg":
        def contribs(args, act, seq=None):
            ok = act & args[0].valid
            return [
                jnp.where(ok, args[0].data.astype(jnp.float64), 0.0),
                ok.astype(jnp.int64),
            ]

        def finalize(comps):
            n = comps[1]
            return (
                comps[0] / jnp.where(n == 0, 1, n).astype(jnp.float64),
                n > 0,
            )

        return DeviceAgg(
            components=(
                AggComponent("add", "float64", 0.0),
                AggComponent("add", "int64", 0),
            ),
            contribs=contribs,
            finalize=finalize,
            result_type=T.DOUBLE,
        )
    if kind == "stddev":
        # (sum, sumsq, n); result() per _stddev_samp/_stddev_pop in
        # functions/udafs.py
        pop = fname.upper() == "STDDEV_POP"

        def contribs(args, act, seq=None):
            ok = act & args[0].valid
            x = jnp.where(ok, args[0].data.astype(jnp.float64), 0.0)
            return [x, x * x, ok.astype(jnp.int64)]

        def finalize(comps):
            s, ss, n = comps
            nf = n.astype(jnp.float64)
            mean_sq = s * s / jnp.where(n == 0, 1.0, nf)
            if pop:
                var = (ss - mean_sq) / jnp.where(n == 0, 1.0, nf)
                out = jnp.sqrt(jnp.maximum(var, 0.0))
                return out, n >= 1
            var = (ss - mean_sq) / jnp.where(n < 2, 1.0, nf - 1.0)
            out = jnp.sqrt(jnp.maximum(var, 0.0))
            out = jnp.where(n == 1, 0.0, out)
            return out, n >= 1

        return DeviceAgg(
            components=(
                AggComponent("add", "float64", 0.0),
                AggComponent("add", "float64", 0.0),
                AggComponent("add", "int64", 0),
            ),
            contribs=contribs,
            finalize=finalize,
            result_type=T.DOUBLE,
        )
    if kind == "correlation":
        def contribs(args, act, seq=None):
            ok = act & args[0].valid & args[1].valid
            x = jnp.where(ok, args[0].data.astype(jnp.float64), 0.0)
            y = jnp.where(ok, args[1].data.astype(jnp.float64), 0.0)
            return [ok.astype(jnp.int64), x, y, x * x, y * y, x * y]

        def finalize(comps):
            n, sx, sy, sxx, syy, sxy = comps
            nf = jnp.where(n == 0, 1.0, n.astype(jnp.float64))
            cov = sxy - sx * sy / nf
            vx = sxx - sx * sx / nf
            vy = syy - sy * sy / nf
            denom = jnp.sqrt(jnp.maximum(vx * vy, 0.0))
            out = jnp.where(denom > 0, cov / jnp.where(denom == 0, 1.0, denom), jnp.nan)
            return out, n > 0

        return DeviceAgg(
            components=tuple(
                AggComponent("add", "int64" if i == 0 else "float64", 0)
                for i in range(6)
            ),
            contribs=contribs,
            finalize=finalize,
            result_type=T.DOUBLE,
        )
    if kind in ("latest", "earliest"):
        # EARLIEST/LATEST_BY_OFFSET: argmin/argmax over a global arrival
        # sequence.  Component 0 orders (min/max-combined); the value/valid
        # components are 'argset': scatter_combine writes them from the row
        # that won component 0 (unique sequence numbers -> no ties).
        t = arg_types[0]
        if t.base in (SqlBaseType.ARRAY, SqlBaseType.MAP, SqlBaseType.STRUCT):
            raise DeviceUnsupported(f"{kind} over nested types on device")
        hashed = t.base in (SqlBaseType.STRING, SqlBaseType.BYTES)
        if t.base in (SqlBaseType.DOUBLE, SqlBaseType.DECIMAL):
            vdt = np.float64
        elif hashed or t.base != SqlBaseType.INTEGER:
            vdt = np.int64
        else:
            vdt = np.int32
        combine = "min" if kind == "earliest" else "max"
        init = _I64_MAX if kind == "earliest" else -_I64_MAX - 1

        def contribs(args, act, seq=None):
            v = args[0]
            if len(args) > 1:
                ignore_nulls = args[1].data.astype(bool)
            else:
                ignore_nulls = jnp.ones_like(act)
            cand = act & (v.valid | ~ignore_nulls)
            return [
                jnp.where(cand, seq, init),
                jnp.where(cand, v.data, 0).astype(vdt),
                (cand & v.valid).astype(np.int32),
            ]

        def finalize(comps):
            present = comps[0] != init
            return comps[1], present & (comps[2] != 0)

        return DeviceAgg(
            components=(
                AggComponent(combine, "int64", init),
                AggComponent("argset", np.dtype(vdt).name, 0),
                AggComponent("argset", "int32", 0),
            ),
            contribs=contribs,
            finalize=finalize,
            result_type=t,
        )
    if kind == "collect":
        # COLLECT_LIST / COLLECT_SET / EARLIEST_BY_OFFSET(n) /
        # LATEST_BY_OFFSET(n): bounded per-key vector state
        # (CollectListUdaf LIMIT cap; ring buffer for latest-N)
        # nested element types (ARRAY/MAP/STRUCT) ride as opaque int64
        # dictionary codes, exactly like strings — collect state stores the
        # codes and emission decodes elements through the dictionary
        t = arg_types[0]
        fn = fname.upper()
        ignore_nulls = True
        if fn == "COLLECT_LIST":
            from ksql_tpu.functions.udafs import _limit_of

            K, mode, collect_nulls = _limit_of("collect_list"), "append", True
        elif fn == "COLLECT_SET":
            from ksql_tpu.functions.udafs import _limit_of

            K, mode, collect_nulls = _limit_of("collect_set"), "set", True
        elif fn in ("EARLIEST_BY_OFFSET", "LATEST_BY_OFFSET"):
            K = literals[0] if literals else None
            mode = "append" if fn.startswith("EARLIEST") else "ring"
            collect_nulls = False
            if len(literals) > 1 and literals[1] is not None:
                ignore_nulls = bool(literals[1])
            elif len(literals) > 1:
                raise DeviceUnsupported(f"{fname} dynamic ignoreNulls on device")
        else:
            raise DeviceUnsupported(f"{fname} on device")
        if not isinstance(K, int) or K <= 0 or K > MAX_VEC_WIDTH:
            raise DeviceUnsupported(f"{fname} cap {K!r} on device")
        vdt = _vec_dtype(t)

        def contribs(args, act, seq=None):
            v = args[0]
            if collect_nulls:
                cand = act
            elif ignore_nulls:
                cand = act & v.valid
            else:
                cand = act
            return [
                cand.astype(jnp.int64),
                jnp.where(cand & v.valid, v.data, 0).astype(vdt),
                (cand & v.valid).astype(jnp.int8),
            ]

        ring = mode == "ring"
        undo_contribs = None
        if fn == "COLLECT_LIST":
            # table-aggregation undo: negative head removes the first
            # stored occurrence (_vec_remove; CollectListUdaf.undo)
            def undo_contribs(args, act, seq=None):
                v = args[0]
                return [
                    -act.astype(jnp.int64),
                    jnp.where(act & v.valid, v.data, 0).astype(vdt),
                    (act & v.valid).astype(jnp.int8),
                ]

        def finalize(comps):
            count, data, vbits = comps
            n = count.shape[0]
            if ring:
                start = jnp.where(count > K, count % K, 0).astype(jnp.int32)
                idx = (start[:, None] + jnp.arange(K, dtype=jnp.int32)) % K
                data = jnp.take_along_axis(data, idx, axis=1)
                vbits = jnp.take_along_axis(vbits, idx, axis=1)
            cnt = jnp.minimum(count, K).astype(jnp.int32)
            present = jnp.arange(K, dtype=jnp.int32)[None, :] < cnt[:, None]
            return data, present, (vbits != 0) & present

        return DeviceAgg(
            components=(
                AggComponent("vec_count", "int64", 0),
                AggComponent("vec_data", np.dtype(vdt).name, 0, width=K, mode=mode),
                AggComponent("vec_valid", "int8", 0, width=K),
            ),
            contribs=contribs,
            finalize=finalize,
            undo_contribs=undo_contribs,
            result_type=result_type,
        )
    if kind == "topk":
        # TOPK / TOPKDISTINCT over numerics/temporals: width-k sorted state.
        # The variadic TOPK(sort_col, col0, .., k) adds one width-k payload
        # component per extra column and one of their null bits; the fold
        # moves each payload with its sort value
        # (hash_store._vec_topk) and the result is ARRAY<STRUCT<..>>
        t = arg_types[0]
        extras = list(arg_types[1:-1])
        if t.base in (SqlBaseType.STRING, SqlBaseType.BYTES):
            raise DeviceUnsupported("string ordering on device")
        if t.base in (SqlBaseType.ARRAY, SqlBaseType.MAP, SqlBaseType.STRUCT):
            raise DeviceUnsupported(f"{fname} over nested types on device")
        for e in extras:
            if e.base in (SqlBaseType.STRING, SqlBaseType.BYTES,
                          SqlBaseType.ARRAY, SqlBaseType.MAP, SqlBaseType.STRUCT):
                raise DeviceUnsupported(
                    f"{fname} with a {e.base.name} column beside its sort "
                    "value on device"
                )
        k = literals[0] if literals else None
        if not isinstance(k, int) or k <= 0 or k > 256:
            raise DeviceUnsupported(f"{fname} k {k!r} on device")
        vdt = _vec_dtype(t)
        if vdt == np.float64:
            sentinel: object = -np.inf
        else:
            sentinel = np.iinfo(vdt).min

        distinct = fname.upper() == "TOPKDISTINCT"
        pdts = [_vec_dtype(e) for e in extras]

        def tk_contribs(args, act, seq=None):
            v = args[0]
            ok = act & v.valid
            out = [
                ok.astype(jnp.int32),
                jnp.where(ok, v.data, sentinel).astype(vdt),
            ]
            if extras:
                nulls = jnp.zeros(ok.shape, jnp.int8)
                for i, (e, pdt) in enumerate(zip(args[1:1 + len(extras)], pdts)):
                    out.append(jnp.where(ok & e.valid, e.data, 0).astype(pdt))
                    nulls = nulls | ((~e.valid).astype(jnp.int8) << i)
                out.append(nulls)
            return out

        def present_of(count, data):
            if distinct:
                # distinct count isn't tracked; dtype-floor values (-inf /
                # INT_MIN) read as absent — the one documented parity edge
                return data != jnp.asarray(sentinel, data.dtype)
            cnt = jnp.minimum(count, k).astype(jnp.int32)
            return jnp.arange(k, dtype=jnp.int32)[None, :] < cnt[:, None]

        def tk_finalize(comps):
            count, data = comps[:2]
            present = present_of(count, data)
            if not extras:
                return data, present, present
            names = [name for name, _ in result_type.element.fields]
            nulls = comps[-1]
            fields = [(names[0], data, present)] + [
                (names[1 + i], pay, present & ((nulls >> i) & 1 == 0))
                for i, pay in enumerate(comps[2:-1])
            ]
            return DCol(data, present, result_type, elem_valid=present,
                        fields=tuple(fields))

        payloads = tuple(
            AggComponent("topk_pay", np.dtype(pdt).name, 0, width=k)
            for pdt in pdts
        )
        if extras:
            payloads += (AggComponent("topk_pay", "int8", 0, width=k),)
        return DeviceAgg(
            components=(
                AggComponent("add", "int32", 0),
                AggComponent(
                    "topk", np.dtype(vdt).name, sentinel, width=k,
                    mode="distinct" if distinct else "",
                ),
            ) + payloads,
            contribs=tk_contribs,
            finalize=tk_finalize,
            result_type=result_type,
        )
    if kind in ("histogram", "attr"):
        # HISTOGRAM(string) -> MAP<STRING, BIGINT>: per-slot (value-code,
        # count) pairs.  Distinct values append set-style (capped at the
        # reference's 1000 entries — HistogramUdaf); every occurrence
        # scatter-adds ±1 to its element count, so the fold is invertible
        # and table-aggregation undo works by decrement (zero-count entries
        # read as absent, matching the oracle's _hist_undo deletion).
        t = arg_types[0]
        is_attr = kind == "attr"
        f64_repr = t.base in (SqlBaseType.DOUBLE, SqlBaseType.DECIMAL)
        K = 1000

        def _code64(v):
            if f64_repr:  # bitcast keeps doubles exact in the code column
                import jax

                return jax.lax.bitcast_convert_type(
                    v.data.astype(jnp.float64), jnp.int64
                )
            return v.data.astype(jnp.int64)

        def h_contribs(args, act, seq=None, sign=1):
            v = args[0]
            # histogram skips null values (_hist_acc); ATTR counts them as
            # an entry (Attr.java update with a null VALUE)
            cand = act if is_attr else act & v.valid
            head = jnp.where(cand, sign, 0).astype(jnp.int64)
            return [
                head,
                jnp.where(cand & v.valid, _code64(v), 0),
                (cand & v.valid).astype(jnp.int8),
                head,  # per-element count increment (carries the sign)
            ]

        def h_finalize(comps):
            cnt, data, vbits, nums = comps
            live = (
                jnp.arange(K, dtype=jnp.int32)[None, :]
                < jnp.minimum(cnt, K).astype(jnp.int32)[:, None]
            ) & (nums > 0)
            if is_attr:
                # the singleton entry's value, NULL when 0 or 2+ distinct
                # values are live (Attr.java map())
                n_live = jnp.sum(live, axis=1)
                pick = jnp.argmax(live, axis=1)
                rows = jnp.arange(cnt.shape[0])
                val = data[rows, pick]
                if f64_repr:
                    import jax

                    val = jax.lax.bitcast_convert_type(val, jnp.float64)
                valid = (n_live == 1) & (vbits[rows, pick] != 0)
                return val, valid
            ones = jnp.ones(cnt.shape[0], bool)
            return data, ones, live, nums

        return DeviceAgg(
            components=(
                AggComponent("vec_count", "int64", 0, mode="hist"),
                AggComponent("vec_data", "int64", 0, width=K, mode="hist"),
                AggComponent("vec_valid", "int8", 0, width=K),
                AggComponent("hist_count", "int64", 0, width=K),
            ),
            contribs=h_contribs,
            finalize=h_finalize,
            result_type=result_type,
            undo_contribs=lambda args, act, seq=None: h_contribs(
                args, act, seq, sign=-1
            ),
        )
    if kind == "collect_all_valid":
        # GenericVarArgUdaf/ObjVarColArgUdaf: append the FIRST argument's
        # value when EVERY argument (incl. variadic) is non-null
        t = arg_types[0]
        K = 1000
        vdt = _vec_dtype(t)

        def cav_contribs(args, act, seq=None):
            v = args[0]
            cand = act
            for a in args:
                cand = cand & a.valid
            return [
                cand.astype(jnp.int64),
                jnp.where(cand, v.data, 0).astype(vdt),
                cand.astype(jnp.int8),
            ]

        def cav_finalize(comps):
            count, data, vbits = comps
            cnt = jnp.minimum(count, K).astype(jnp.int32)
            present = jnp.arange(K, dtype=jnp.int32)[None, :] < cnt[:, None]
            return data, present, (vbits != 0) & present

        return DeviceAgg(
            components=(
                AggComponent("vec_count", "int64", 0),
                AggComponent("vec_data", np.dtype(vdt).name, 0, width=K, mode="append"),
                AggComponent("vec_valid", "int8", 0, width=K),
            ),
            contribs=cav_contribs,
            finalize=cav_finalize,
            result_type=result_type,
        )
    raise DeviceUnsupported(f"aggregate kind {kind} on device")
