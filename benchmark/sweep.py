#!/usr/bin/env python3
"""benchmark/sweep.py — find the highest fixed rate a cell's state sustains.

    python3 benchmark/sweep.py --workload pv_count.paced --seed <n> \
        --seconds 15 --rates 10000,12000,14000,16000

One process, one set-up (the cell's own fill), then one paced window per
rate, in the order given, each followed by a full drain.  Per rate it
prints the backlog (rows produced and not yet consumed) over the window's
second half and at its end, and the latency percentiles.  A rate is
sustained when the backlog does not grow: its second-half mean stays under
two served ticks' worth of arrivals.  The cell's traffic file then fixes
0.8 x the highest sustained rate as a number; nothing searches for a rate
at run time.  Needs a TPU, like run.py.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as bench_run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", required=True, help="comma-separated events/s")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    rates = [float(r) for r in a.rates.split(",")]
    args = argparse.Namespace(workload=a.workload, seed=a.seed, seconds=a.seconds,
                              trace=0, rehearse=a.rehearse, control="", keep_trace="")
    code, run = bench_run.open_run(args)
    if run is None:
        return code
    traffic = run.traffic
    rows = []
    try:
        run.setup(window_events_total=int(sum(r * a.seconds + 1 for r in rates)))
        for rate in rates:
            run.obs = {k: v for k, v in run.obs.items() if k.startswith("config.")}
            run.window(dict(traffic, mode="paced", rate_events_per_s=rate,
                            wake_ms=traffic.get("wake_ms", 2.0)), a.seconds)
            o = run.obs
            rows.append({
                "rate": rate, "offered": run.offered,
                "backlog_second_half_mean": o["backlog.rows_second_half_mean"],
                "backlog_end": o["backlog.rows_end"], "backlog_max": o["backlog.rows_max"],
                "latency_p50_ms": o.get("latency.p50_ms"), "latency_p95_ms": o.get("latency.p95_ms"),
                "latency_p99_ms": o.get("latency.p99_ms"),
                "generator_late_ms_p95": o.get("generator.late_ms_p95"),
                "tick_ms_mean": 1e3 * o["window.span_seconds"] / max(o.get("span.poll.n", 0.0), 1.0),
                "rows_per_tick": o.get("span.poll.rows", 0.0) / max(o.get("span.poll.n", 0.0), 1.0),
            })
            print("SWEEP " + json.dumps(rows[-1]), flush=True)
        run.compare()
    finally:
        run.close()
    print(json.dumps({"sweep": rows, "correct": bench_run.within(run.numbers),
                      "device": run.device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
