"""Run the full QTT corpus and dump summary + detailed failures.

Usage: python scripts/run_qtt.py [file-substring ...]
Writes qtt_status.json (per-file summary) and qtt_failures.txt (details).
"""
import json
import os
import sys
import concurrent.futures as cf

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

QTT_DIR = "/root/reference/ksqldb-functional-tests/src/test/resources/query-validation-tests"


def run_one(fname):
    if os.environ.get("QTT_BACKEND") == "device":
        # device-mode QTT runs on CPU jax: a chip belongs to one process,
        # and this runs 8 compiling workers (set before jax is imported)
        os.environ["JAX_PLATFORMS"] = "cpu"
    from ksql_tpu.tools.qtt import run_file
    path = os.path.join(QTT_DIR, fname)
    try:
        results = run_file(path)
    except Exception as e:
        return fname, None, f"{type(e).__name__}: {e}"
    return fname, results, None


def main():
    filters = sys.argv[1:]
    files = sorted(f for f in os.listdir(QTT_DIR) if f.endswith(".json"))
    if filters:
        files = [f for f in files if any(s in f for s in filters)]
    status = {}
    failures = []
    totals = {}
    with cf.ProcessPoolExecutor(max_workers=8) as ex:
        for fname, results, harness_err in ex.map(run_one, files):
            if harness_err:
                status[fname] = {"HARNESS_ERROR": harness_err}
                totals["HARNESS_ERROR"] = totals.get("HARNESS_ERROR", 0) + 1
                continue
            summ = {}
            for r in results:
                summ[r.status] = summ.get(r.status, 0) + 1
                totals[r.status] = totals.get(r.status, 0) + 1
                if r.status in ("FAIL", "ERROR"):
                    failures.append(f"{fname} :: {r.name} :: {r.status} :: {r.detail}")
            status[fname] = dict(sorted(summ.items()))
    if not filters and os.environ.get("QTT_BACKEND", "oracle") == "oracle":
        # the committed status/failure files track the oracle corpus;
        # device-mode sweeps report to stdout only
        with open("qtt_status.json", "w") as f:
            json.dump(status, f, indent=1, sort_keys=True)
        with open("qtt_failures.txt", "w") as f:
            f.write("\n".join(failures))
    else:
        print("\n".join(failures))
    npass = (totals.get("PASS", 0) + totals.get("XFAIL_MATCHED", 0)
             + totals.get("XFAIL_LOOSE", 0))
    ntot = sum(v for k, v in totals.items() if k != "SKIP")
    print(json.dumps(totals), f"parity={npass}/{ntot} = {npass/max(ntot,1):.1%}")


if __name__ == "__main__":
    main()
