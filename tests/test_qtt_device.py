"""QTT files run with the engine's device backend (QTT_BACKEND=device).

Locks in that device-eligible queries executed through `execute_sql` alone
(engine -> DeviceExecutor -> CompiledDeviceQuery) reproduce the reference's
golden outputs, and that ineligible plans fall back to the oracle with
identical results — the device backend must never do WORSE than the oracle
on the same corpus."""

import os

import pytest

QTT_DIR = (
    "/root/reference/ksqldb-functional-tests/src/test/resources/"
    "query-validation-tests"
)

FILES = [
    "suppress.json",
    "tumbling-windows.json",
    "hopping-windows.json",
    "session-windows.json",
    "joins.json",
]


@pytest.mark.skipif(
    not os.path.isdir(QTT_DIR),
    reason="the QTT corpus is external to this repo (ksqlDB's "
    f"query-validation-tests, expected at {QTT_DIR})",
)
@pytest.mark.parametrize("fname", FILES)
def test_device_backend_matches_oracle_on_qtt(fname, monkeypatch):
    from ksql_tpu.tools.qtt import run_file

    path = os.path.join(QTT_DIR, fname)
    monkeypatch.setenv("QTT_BACKEND", "oracle")
    oracle = {r.name: r.status for r in run_file(path)}
    monkeypatch.setenv("QTT_BACKEND", "device")
    device = {r.name: r.status for r in run_file(path)}
    regressions = {
        n: (oracle[n], device.get(n))
        for n in oracle
        if oracle[n] == "PASS" and device.get(n) != "PASS"
    }
    assert not regressions, regressions
    assert sum(1 for s in device.values() if s == "PASS") > 0
