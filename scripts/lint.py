#!/usr/bin/env python
"""graftlint CLI — run the repo's static-analysis rules over source trees.

Usage:
    python scripts/lint.py                  # lint the repo tree (default set)
    python scripts/lint.py path [path ...]  # lint specific files/dirs
    python scripts/lint.py --list-rules     # show rules + one-line docs
    python scripts/lint.py --rules donated-aliasing,jit-retrace ksql_tpu
    python scripts/lint.py --jobs 4         # parallel per-module analysis
    python scripts/lint.py --threads        # dump the concurrency map
    python scripts/lint.py --baseline lint_baseline.json            # diff-only
    python scripts/lint.py --baseline lint_baseline.json --write-baseline

Exit status: 0 when clean, 1 when any finding survives suppression (with
--baseline: when any finding is NEW relative to the audited snapshot).
Suppress a reviewed finding with ``# graftlint: disable=<rule>`` on (or
directly above) the flagged line; always pair it with a justification
comment.  tests/test_analysis.py runs the same default sweep in tier-1,
so a new violation fails the gate before it ships.

--threads prints the shared-state-race rule's per-module entrypoint map
(thread entrypoints, their call-graph reach, and every shared-state key
with its per-mutation guard) so reviewers can see the concurrency
surface at a glance.

--jobs N distributes the whole-program analysis over N processes: a
chunk-local summary pass, a merge, a second pass against the merged
table (the same two global passes the in-process path runs), then
parallel per-module rule checks.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: the tier-1 sweep surface: every tree that feeds the running system
DEFAULT_PATHS = ["ksql_tpu", "scripts"]


def _fingerprint(finding, root: str) -> str:
    """Line numbers drift with every edit; rule + relative path + message
    (which embeds the offending names) is the stable identity an audited
    suppression snapshot can be keyed on."""
    rel = os.path.relpath(finding.path, root)
    return f"{finding.rule}|{rel}|{finding.message}"


def _lint_parallel(files, rule_names, jobs):
    from concurrent.futures import ProcessPoolExecutor
    from itertools import repeat

    from ksql_tpu.analysis.parallel_lint import (
        check_chunk,
        summarize_pass1,
        summarize_pass2,
    )

    if not files:
        return []  # nothing to lint: clean, same as the serial path
    chunks = [files[i::jobs] for i in range(jobs)]
    chunks = [c for c in chunks if c]
    need_summaries = rule_names is None or "donated-aliasing" in rule_names
    meta_all, summaries = {}, {}
    with ProcessPoolExecutor(max_workers=len(chunks)) as ex:
        if need_summaries:
            from ksql_tpu.analysis.rules_aliasing import DonatedAliasingRule

            for meta, summ in ex.map(summarize_pass1, chunks):
                meta_all.update(meta)
                summaries.update(summ)
            # iterate against the merged table to the same bounded
            # fixpoint as the in-process path: a taint chain spanning
            # chunks (leaf in one worker's files, caller in another's)
            # needs one merged pass per hop to propagate
            for _ in range(DonatedAliasingRule.MAX_PASSES - 1):
                before = dict(summaries)
                for summ in ex.map(
                    summarize_pass2, chunks, repeat(meta_all),
                    repeat(summaries),
                ):
                    summaries.update(summ)
                if summaries == before:
                    break
        # (non-aliasing rule sets need no resolution metadata: check_chunk
        # only feeds meta_all to the primed aliasing rule — parsing every
        # file in the parent just to build it would serialize the very
        # work --jobs distributes)
        findings = []
        for chunk_findings in ex.map(
            check_chunk, chunks, repeat(meta_all), repeat(summaries),
            repeat(sorted(rule_names) if rule_names else None),
        ):
            findings.extend(chunk_findings)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def _print_threads_report(files) -> None:
    from ksql_tpu.analysis import RaceAnalysis
    from ksql_tpu.analysis.lint import load_modules

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    any_out = False
    for module in load_modules(files):
        analysis = RaceAnalysis(module)
        rep = analysis.report()
        if not rep["entrypoints"]:
            continue
        any_out = True
        print(f"== {os.path.relpath(module.path, root)}")
        print("  entrypoints:")
        for ep in rep["entrypoints"]:
            print(
                f"    {ep['label']:<18} ({ep['kind']}) root={ep['root']} "
                f"line {ep['line']}, reaches {len(ep['reaches'])} fns"
            )
        if rep["shared"]:
            print("  shared state:")
            for key, info in rep["shared"].items():
                eps = ", ".join(info["entrypoints"])
                print(f"    {key:<34} [{eps}]")
                for mut in info["mutations"]:
                    print(
                        f"      L{mut['line']:<6} {mut['fn']:<28} "
                        f"guard={mut['guard']}"
                    )
        print()
    if not any_out:
        print("no thread entrypoints discovered in the linted tree")


def main(argv=None) -> int:
    from ksql_tpu.analysis import default_rules, expand_lint_paths, lint_paths

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", help="files or directories "
                    f"(default: {' '.join(DEFAULT_PATHS)})")
    ap.add_argument("--rules", help="comma-separated rule names to run "
                    "(default: all)")
    ap.add_argument("--list-rules", action="store_true",
                    help="list rules and exit")
    ap.add_argument("--threads", action="store_true",
                    help="print the per-module thread-entrypoint / "
                    "shared-state map instead of linting")
    ap.add_argument("--jobs", type=int, default=1, metavar="N",
                    help="parallel per-module analysis over N processes")
    ap.add_argument("--baseline", metavar="FILE",
                    help="audited-suppression snapshot: only findings NOT "
                    "in FILE fail the run")
    ap.add_argument("--write-baseline", action="store_true",
                    help="(re)write --baseline FILE from the current "
                    "findings and exit 0")
    args = ap.parse_args(argv)
    if args.write_baseline and not args.baseline:
        ap.error("--write-baseline requires --baseline FILE")

    rules = default_rules()
    if args.list_rules:
        for r in rules:
            print(f"{r.name}: {r.doc}")
        return 0
    wanted = None
    if args.rules:
        wanted = {r.strip() for r in args.rules.split(",") if r.strip()}
        unknown = wanted - {r.name for r in rules}
        if unknown:
            print(f"unknown rule(s): {', '.join(sorted(unknown))}",
                  file=sys.stderr)
            return 2
        rules = [r for r in rules if r.name in wanted]

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if args.paths:
        missing = [p for p in args.paths if not os.path.exists(p)]
        if missing:
            print(f"no such path(s): {', '.join(missing)}", file=sys.stderr)
            return 2
        paths = args.paths
    else:
        paths = [p for p in (os.path.join(root, d) for d in DEFAULT_PATHS)
                 if os.path.exists(p)]
    files = expand_lint_paths(paths)

    if args.threads:
        _print_threads_report(files)
        return 0

    if args.jobs > 1:
        findings = _lint_parallel(files, wanted, args.jobs)
    else:
        findings = lint_paths(files, rules)

    if args.baseline and args.write_baseline:
        counts = {}
        for f in findings:
            fp = _fingerprint(f, root)
            counts[fp] = counts.get(fp, 0) + 1
        with open(args.baseline, "w", encoding="utf-8") as fh:
            json.dump({"fingerprints": counts}, fh, indent=2, sort_keys=True)
        print(f"baseline written: {len(findings)} finding(s) -> "
              f"{args.baseline}")
        return 0

    if args.baseline:
        try:
            with open(args.baseline, encoding="utf-8") as fh:
                budget = dict(json.load(fh).get("fingerprints", {}))
        except FileNotFoundError:
            print(f"baseline file not found: {args.baseline}",
                  file=sys.stderr)
            return 2
        fresh = []
        for f in findings:
            fp = _fingerprint(f, root)
            if budget.get(fp, 0) > 0:
                budget[fp] -= 1  # audited: consumed from the snapshot
            else:
                fresh.append(f)
        for f in fresh:
            print(f.format())
        stale = sum(n for n in budget.values() if n > 0)
        if stale:
            print(f"note: {stale} baseline entr{'y' if stale == 1 else 'ies'}"
                  " no longer fire — consider --write-baseline",
                  file=sys.stderr)
        if fresh:
            print(f"{len(fresh)} NEW finding(s) vs baseline",
                  file=sys.stderr)
            return 1
        return 0

    for f in findings:
        print(f.format())
    if findings:
        print(f"{len(findings)} finding(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
