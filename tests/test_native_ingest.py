"""Native (C++) batch JSON ingest: hash compatibility + engine parity."""

import json

import pytest

from ksql_tpu.common.batch import stable_hash64
from ksql_tpu.common.config import RUNTIME_BACKEND, KsqlConfig
from ksql_tpu.engine.engine import KsqlEngine
from ksql_tpu.runtime.topics import Record

native = pytest.importorskip("ksql_tpu.native")

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no native toolchain"
)


def test_hash_compatible_with_python():
    lib = native.get_lib()
    for s in ["", "a", "/page/7", "café \"x\"", "é中\U0001f600", "x" * 1000]:
        b = s.encode("utf-8")
        assert lib.ingest_hash_string(b, len(b)) == stable_hash64(s), s


def test_parse_batch_values_and_fallback():
    payloads = [
        '{"URL":"/a","N":42,"D":1.5,"B":true}',
        '{"url":"caf\\u00e9","N":null,"D":-2e3,"B":false}',
        '{"URL":"/b","EXTRA":{"x":[1,{"y":"}"}]},"N":7,"D":0,"B":true}',
        "not json",
        '{"URL":"/a","N":1,"D":5,"B":true}',
    ]
    data, valid, row_ok, learned = native.parse_json_batch(
        payloads,
        [("URL", native.FT_STRING), ("N", native.FT_BIGINT),
         ("D", native.FT_DOUBLE), ("B", native.FT_BOOLEAN)],
    )
    assert list(row_ok) == [True, True, True, False, True]
    assert list(data["N"][[0, 2, 4]]) == [42, 7, 1]
    assert not valid["N"][1]
    assert data["D"][1] == -2000.0
    assert data["URL"][1] == stable_hash64("café")
    assert dict(learned)[stable_hash64("café")] == "café"


def _run_engine(native_on):
    import ksql_tpu.native as nat

    saved = (nat._error, nat._lib)
    if not native_on:
        nat._error, nat._lib = "disabled by the test", None
    try:
        e = KsqlEngine(KsqlConfig({RUNTIME_BACKEND: "device-only"}))
        e.execute_sql(
            "CREATE STREAM S (ID INT KEY, URL STRING, V INT) "
            "WITH (kafka_topic='t', value_format='JSON');"
        )
        e.execute_sql(
            "CREATE TABLE A AS SELECT URL, COUNT(*) C, SUM(V) SV "
            "FROM S GROUP BY URL;"
        )
        t = e.broker.topic("t")
        payloads = [
            (1, '{"URL":"/a","V":3}'),
            (2, '{"URL":"/b","V":4}'),
            (3, '{"URL":"/a","V":null}'),
            (4, None),  # null-value record interleaved
            (5, '{"URL":null,"V":9}'),
            (6, "broken json"),  # per-record decode error path
            (7, '{"URL":"/a","V":7}'),
        ]
        for i, (k, v) in enumerate(payloads):
            t.produce(Record(key=k, value=v, timestamp=i * 10, partition=0))
            e.run_until_quiescent()
        h = list(e.queries.values())[0]
        used = getattr(h.executor, "_native_fields", None) is not None
        return (
            [(r.key, r.value, r.timestamp)
             for r in e.broker.topic("A").all_records()],
            used,
        )
    finally:
        nat._error, nat._lib = saved


def test_engine_parity_native_vs_python():
    out_n, used_n = _run_engine(True)
    out_p, used_p = _run_engine(False)
    assert used_n and not used_p
    assert out_n == out_p
    assert len(out_n) > 0
