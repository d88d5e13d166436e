"""Perf-evidence gate — the comparison core behind ``scripts/perfgate.py``.

The bench harness (bench.py, PR 7) emits one JSON line per round with the
headline throughput and, per workload, a per-stage flight-recorder
breakdown (``*_stages``: p50/p99/total ms + transfer/exchange counters).
This module turns those lines into an enforced contract:

* ``extract_run``    one bench JSON line -> per-workload throughput +
                     per-stage p99 observations
* ``summarize``      >=3 runs -> medians (throughput median, per-stage
                     median-of-p99) — medians over repeated runs are the
                     variance control; this container times with ~2x
                     jitter, so single runs must never gate
* ``make_baseline``  summary + environment meta + thresholds -> the
                     committed baseline JSON (PERF_BASELINE.json)
* ``compare``        baseline vs current summary -> per-stage diff rows
                     and the regressions that breach the thresholds,
                     each NAMING the workload + stage that regressed

Everything here is pure (no benches run, no files read) so the gate
logic itself is tier-1-testable with synthetic runs: inflate one stage's
accumulator and the gate must fail naming that stage; add 2x noise on
every number and the variance-aware thresholds must still pass.
"""

from __future__ import annotations

import json
from statistics import median
from typing import Any, Dict, List, Optional, Tuple

#: baseline file schema version (bump on shape changes)
BASELINE_VERSION = 1

#: the pinned workload set (ISSUE 11): metric name in the bench line ->
#: where its throughput and stage block live.  ``None`` throughput key =
#: the headline ``value`` field.
WORKLOADS: Dict[str, Dict[str, Optional[str]]] = {
    "tumbling_count_group_by": {
        "throughput": None,  # the headline "value" field
        "stages": None,  # raw device-step bench: no engine, no recorder
    },
    "hopping_sum_group_by": {
        "throughput": "hopping_sum_group_by_events_s",
        "stages": None,
    },
    "window_family": {
        "throughput": "window_family_events_s",
        "stages": "window_family_stages",
    },
    "mqo_dashboard": {
        "throughput": "mqo_dashboard_events_s",
        "stages": "mqo_dashboard_stages",
    },
    "push_fanout": {
        "throughput": "push_fanout_delivered_rows_s",
        "stages": "push_fanout_stages",
    },
    "engine_e2e_dist": {
        "throughput": "engine_e2e_dist_events_s",
        "stages": "engine_e2e_dist_stages",
    },
    "serde_linerate": {
        "throughput": "serde_linerate_rows_s",
        "stages": "serde_linerate_stages",
    },
}

#: BENCH_ONLY pattern covering exactly the pinned set (substring match in
#: bench.py; "tumbling_count" also turns the headline on)
BENCH_ONLY = (
    "tumbling_count,hopping_sum_group_by,window_family,mqo_dashboard,"
    "push_fanout,engine_e2e_dist,serde_linerate"
)

#: the headline's metric name as bench.py matches BENCH_ONLY against it
HEADLINE_METRIC = "tumbling_count_group_by_events_per_sec"


def selected_workloads(only: str) -> set:
    """The workload subset a BENCH_ONLY-style pattern list selects,
    mirroring bench.py's substring matching (patterns match the metric
    name a config is registered under — the headline included — plus the
    workload name as a friendlier alias).  Drives the zero-evidence
    exemption for --only runs, so it must never be NARROWER than what
    bench.py actually runs."""
    pats = [p for p in (only or "").split(",") if p]
    out = set()
    for name, spec in WORKLOADS.items():
        cands = (name, spec["throughput"] or HEADLINE_METRIC)
        if any(p in c for c in cands for p in pats):
            out.add(name)
    return out

#: stages the gate enforces (the ISSUE-named compile / execute / exchange
#: / sink set plus the push-serving fan-out stages, plus —
#: since the line-rate serde PR made both serde edges batch-optimized
#: hot paths — ``deserialize`` and ``sink.produce``.  Oracle ``stage:*``
#: chains and poll stay informational: corpus-shaped, not
#: regression-shaped.
GATED_STAGES = frozenset({
    "device.compile",
    "device.execute",
    "deserialize",
    "exchange",
    "sink.produce",
    "push.pipeline.step",
    "push.tap.deliver",
    "push.residual.kernel",
})

#: variance-aware defaults, sized for this container's ~2x timing jitter
#: (ROADMAP hazard notes): a stage regresses when its median-of-p99 grows
#: past ``stage_ratio`` x baseline, throughput when it falls below
#: ``throughput_ratio`` x baseline.  Stored IN the baseline file so the
#: operator tunes thresholds where the numbers live.
DEFAULT_THRESHOLDS = {"throughput_ratio": 0.4, "stage_ratio": 2.5}

#: stage times below this floor are never gated: a 0.2ms stage tripling
#: is scheduler noise, not a regression
STAGE_FLOOR_MS = 1.0

#: a gated stage whose BASELINE p99 sits under the floor has no
#: ratio-resolution to gate on (a 0.5ms stage doubling is the same
#: scheduler noise) — it only regresses on an absolute blow-up past this
#: multiple of the floor.  Keeps sub-ms stages (fused tap delivery)
#: honest without failing on container jitter.
SUBFLOOR_ABS_MULT = 10.0


class PerfGateUsageError(Exception):
    """Mis-invocation (missing baseline, too few runs, platform
    mismatch): exit code 2, distinct from a regression (exit 1)."""


def extract_run(line: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """One parsed bench JSON line -> ``{workload: {"throughput": float,
    "stages": {stage: p99_ms}}}``.  Workloads whose slot carries an error
    string (a contained bench failure) are omitted — the summarizer
    requires every gated workload to appear in >=1 run."""
    extra = line.get("extra") or {}
    out: Dict[str, Dict[str, Any]] = {}
    for name, spec in WORKLOADS.items():
        tkey = spec["throughput"]
        raw = line.get("value") if tkey is None else extra.get(tkey)
        if not isinstance(raw, (int, float)) or not raw:
            continue  # error string / missing / the zero-evidence case
        entry: Dict[str, Any] = {"throughput": float(raw), "stages": {}}
        skey = spec["stages"]
        stages = extra.get(skey) if skey else None
        if isinstance(stages, dict):
            for sname, st in stages.items():
                p99 = (st or {}).get("p99Ms")
                if isinstance(p99, (int, float)):
                    entry["stages"][sname] = float(p99)
        out[name] = entry
    return out


def summarize(runs: List[Dict[str, Any]],
              min_runs: int = 3) -> Dict[str, Any]:
    """Fold >=``min_runs`` parsed bench lines into the median summary the
    gate compares: per workload the throughput median and the per-stage
    median of p99s (each stage over the runs that observed it)."""
    if len(runs) < min_runs:
        raise PerfGateUsageError(
            f"need >= {min_runs} runs to gate on medians (got {len(runs)}); "
            "the container's ~2x timing variance makes single runs "
            "meaningless — rerun with --runs or relax via --min-runs"
        )
    extracted = [extract_run(r) for r in runs]
    out: Dict[str, Any] = {}
    for name in WORKLOADS:
        thr = [e[name]["throughput"] for e in extracted if name in e]
        if not thr:
            continue  # absent in every run (narrowed --only / bench error)
        stage_obs: Dict[str, List[float]] = {}
        for e in extracted:
            for sname, p99 in e.get(name, {}).get("stages", {}).items():
                stage_obs.setdefault(sname, []).append(p99)
        out[name] = {
            "throughput": round(median(thr), 1),
            "runs": len(thr),
            "stages": {
                sname: round(median(xs), 3)
                for sname, xs in sorted(stage_obs.items())
            },
        }
    if not out:
        raise PerfGateUsageError(
            "no workload produced a usable number in any run — every slot "
            "was an error/zero (see the bench stderr); nothing to gate"
        )
    return out


def make_baseline(summary: Dict[str, Any], meta: Dict[str, Any],
                  thresholds: Optional[Dict[str, float]] = None,
                  ) -> Dict[str, Any]:
    return {
        "version": BASELINE_VERSION,
        "meta": dict(meta),
        "thresholds": dict(thresholds or DEFAULT_THRESHOLDS),
        "workloads": summary,
    }


def load_baseline(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            data = json.load(f)
    except FileNotFoundError:
        raise PerfGateUsageError(
            f"no baseline at {path}: run with --write-baseline first to "
            "snapshot one, then commit it"
        ) from None
    except ValueError as e:
        raise PerfGateUsageError(f"unparseable baseline {path}: {e}") from e
    if data.get("version") != BASELINE_VERSION:
        raise PerfGateUsageError(
            f"baseline {path} has version {data.get('version')}, expected "
            f"{BASELINE_VERSION}: re-snapshot with --write-baseline"
        )
    return data


def compare(baseline: Dict[str, Any], current: Dict[str, Any],
            thresholds: Optional[Dict[str, float]] = None,
            expected: Optional[Any] = None,
            min_workload_runs: int = 1,
            ) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]]]:
    """Baseline vs current summary -> ``(rows, regressions)``.

    ``rows`` is the full per-workload/per-stage diff table (throughput
    rows first, then stages); ``regressions`` the subset that breached a
    threshold, each carrying workload + stage (the gate's loud,
    stage-NAMING contract).  A baselined workload absent from EVERY
    current run is the zero-evidence regression class and FAILS — unless
    ``expected`` (an iterable of workload names, e.g. derived from the
    CLI's ``--only`` narrowing) says it was deliberately not run, in
    which case it reports informationally.  A workload whose bench
    landed in fewer than ``min_workload_runs`` rounds also FAILS: its
    "median" would really be one or two jittery samples, and this
    module's whole contract is that single runs never gate.  Stages
    missing on one side stay informational: a shape change is visible,
    not auto-failed."""
    th = dict(baseline.get("thresholds") or DEFAULT_THRESHOLDS)
    th.update(thresholds or {})
    thr_ratio = float(th.get("throughput_ratio",
                             DEFAULT_THRESHOLDS["throughput_ratio"]))
    stage_ratio = float(th.get("stage_ratio",
                               DEFAULT_THRESHOLDS["stage_ratio"]))
    rows: List[Dict[str, Any]] = []
    regressions: List[Dict[str, Any]] = []
    base_wl = baseline.get("workloads") or {}
    expected_set = set(expected) if expected is not None else None
    for name in WORKLOADS:
        b, c = base_wl.get(name), current.get(name)
        if b is None and c is None:
            continue
        if b is None or c is None:
            row = {
                "workload": name, "stage": "(throughput)",
                "baseline": (b or {}).get("throughput"),
                "current": (c or {}).get("throughput"),
                "ratio": None,
                "verdict": "missing-current" if c is None
                else "missing-baseline",
            }
            if c is None and (
                expected_set is None or name in expected_set
            ):
                # a baselined workload that produced NO usable number in
                # any current run is the worst regression class there is
                # (the bench crashed/timed out every round — the rounds-
                # 4/5 zero-evidence failure) and must FAIL the gate, not
                # slide through as an info row.  Workloads the caller
                # deliberately narrowed away (--only) are exempt.
                row["verdict"] = (
                    "REGRESSED (no usable runs — the bench errored or "
                    "timed out in every round)"
                )
                regressions.append(row)
            elif c is None:
                row["verdict"] = "not-selected"
            rows.append(row)
            continue
        b_thr, c_thr = float(b["throughput"]), float(c["throughput"])
        ratio = c_thr / b_thr if b_thr else None
        row = {
            "workload": name, "stage": "(throughput)",
            "baseline": b_thr, "current": c_thr,
            "ratio": round(ratio, 3) if ratio is not None else None,
            "verdict": "ok",
        }
        if int(c.get("runs", 0)) < min_workload_runs:
            # the bench erred/timed out in most rounds: a "median" of 1-2
            # jittery samples must not gate — and mostly-failing IS the
            # near-zero-evidence regression class, so fail loudly
            row["verdict"] = (
                f"REGRESSED (only {c.get('runs', 0)} usable runs — "
                f"medians need >= {min_workload_runs})"
            )
            regressions.append(row)
            rows.append(row)
            continue
        if ratio is not None and ratio < thr_ratio:
            row["verdict"] = (
                f"REGRESSED (< {thr_ratio:g}x baseline median over "
                f"{c.get('runs', '?')} runs)"
            )
            regressions.append(row)
        rows.append(row)
        b_stages = b.get("stages") or {}
        c_stages = c.get("stages") or {}
        for sname in sorted(set(b_stages) | set(c_stages)):
            b_p99, c_p99 = b_stages.get(sname), c_stages.get(sname)
            gated = sname in GATED_STAGES
            srow = {
                "workload": name, "stage": sname,
                "baseline": b_p99, "current": c_p99,
                "ratio": (
                    round(c_p99 / b_p99, 3)
                    if b_p99 and c_p99 is not None else None
                ),
                "verdict": "ok" if gated else "info",
            }
            if b_p99 is None or c_p99 is None:
                srow["verdict"] = (
                    "missing-current" if c_p99 is None
                    else "missing-baseline"
                )
            elif gated and c_p99 >= STAGE_FLOOR_MS and b_p99 <= 0:
                # a stage that was instant (counter-only / 0.000 median)
                # at baseline time and now costs real wall time has no
                # finite ratio — it must still fail, not slip through
                # the ratio guard blind
                srow["verdict"] = (
                    "REGRESSED (stage appeared: baseline p99 was 0)"
                )
                regressions.append(srow)
            elif gated and 0 < b_p99 < STAGE_FLOOR_MS:
                # sub-resolution baseline: ratios over a sub-floor p99
                # are scheduler noise (0.5ms -> 1.7ms is jitter, not a
                # regression), so gate only on an absolute blow-up
                if c_p99 >= STAGE_FLOOR_MS * SUBFLOOR_ABS_MULT:
                    srow["verdict"] = (
                        f"REGRESSED (sub-floor baseline grew past "
                        f"{STAGE_FLOOR_MS * SUBFLOOR_ABS_MULT:g}ms)"
                    )
                    regressions.append(srow)
            elif (
                gated
                and max(b_p99, c_p99) >= STAGE_FLOOR_MS
                and b_p99 > 0
                and c_p99 / b_p99 > stage_ratio
            ):
                srow["verdict"] = (
                    f"REGRESSED (p99 > {stage_ratio:g}x baseline "
                    "median-of-p99)"
                )
                regressions.append(srow)
            rows.append(srow)
    return rows, regressions


def diff_table(rows: List[Dict[str, Any]]) -> str:
    """Render the diff rows as the fixed-width table the CLI prints."""
    headers = ("workload", "stage", "baseline", "current", "ratio",
               "verdict")

    def fmt(v: Any) -> str:
        if v is None:
            return "-"
        if isinstance(v, float):
            return f"{v:,.3f}" if v < 1000 else f"{v:,.1f}"
        return str(v)

    table = [headers] + [
        tuple(fmt(r.get(h)) for h in headers) for r in rows
    ]
    widths = [max(len(row[i]) for row in table) for i in range(len(headers))]
    lines = []
    for i, row in enumerate(table):
        lines.append("  ".join(c.ljust(w) for c, w in zip(row, widths)))
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)
