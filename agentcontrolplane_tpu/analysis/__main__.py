"""``python -m agentcontrolplane_tpu.analysis`` — the acplint runner.

Exit status: 0 when every pass is clean over the target tree AND every
enabled gate holds (suppression-debt budget, timing budget), 1 otherwise
(CI gate; see ``make lint-acp``).

Machine-readable output: ``--json FILE`` (``-`` = stdout) writes the full
findings document — violations, per-rule counts, the live suppression
inventory, and (when enabled) the timing and budget-gate results — so CI
can upload one artifact on failure and downstream tooling never scrapes
the human lines. The shape is documented in docs/debugging-guide.md
("Static analysis & invariant mode").
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Optional, Sequence

from .core import Suppression, Violation, analyze, collect_suppressions
from .passes import RULES

_PACKAGE_ROOT = Path(__file__).resolve().parent.parent


def _findings_doc(
    paths: list[str],
    rules: Sequence[str],
    violations: list[Violation],
    suppressions: list[Suppression],
) -> dict:
    by_rule: dict[str, int] = {}
    for v in violations:
        by_rule[v.rule] = by_rule.get(v.rule, 0) + 1
    return {
        "version": 1,
        "paths": paths,
        "rules": list(rules),
        "violations": [
            {"rule": v.rule, "path": v.path, "line": v.line, "message": v.message}
            for v in violations
        ],
        "counts": {
            "violations": len(violations),
            "by_rule": by_rule,
            "rules_total": len(rules),
            "suppressions_total": len(suppressions),
        },
        "suppressions": [
            {
                "path": s.path,
                "line": s.line,
                "rules": list(s.rules),
                "comment": s.comment,
            }
            for s in suppressions
        ],
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m agentcontrolplane_tpu.analysis",
        description="repo-custom static analysis (acplint)",
    )
    ap.add_argument(
        "paths",
        nargs="*",
        help="files/directories to lint (default: the installed package)",
    )
    ap.add_argument(
        "--rule",
        action="append",
        choices=RULES,
        help="run only this rule (repeatable; default: all)",
    )
    ap.add_argument(
        "-q", "--quiet", action="store_true", help="suppress the summary line"
    )
    ap.add_argument(
        "--json",
        metavar="FILE",
        default=None,
        help="write the machine-readable findings document to FILE "
        "('-' = stdout); CI uploads this as the failure artifact",
    )
    ap.add_argument(
        "--timing",
        action="store_true",
        help="print the per-rule wall-time report",
    )
    ap.add_argument(
        "--timing-budget",
        type=float,
        default=None,
        metavar="SECONDS",
        help="fail when the lint's total wall time exceeds this budget "
        "(pinned in make lint-acp so the pass pack can't silently become "
        "the slow CI step); implies --timing",
    )
    ap.add_argument(
        "--suppression-budget",
        type=int,
        default=None,
        metavar="N",
        help="suppression-debt gate: fail when the live '# acp-lint: "
        "disable=' count over the target tree exceeds N, printing the "
        "full justification list (the in-tree count is pinned in make "
        "lint-acp; growth is a deliberate act, not drift)",
    )
    ap.add_argument(
        "--metrics-docs",
        metavar="DOC",
        default=None,
        help="also check the acp_* metric inventory in this doc against "
        "every Registry call in the package (both drift directions fail)",
    )
    ap.add_argument(
        "--faults-docs",
        action="store_true",
        help="also check the faults.py docstring inventory against every "
        "switchboard consumption site in the package (both drift "
        "directions fail)",
    )
    args = ap.parse_args(argv)
    want_timing = args.timing or args.timing_budget is not None
    rules = tuple(args.rule) if args.rule else RULES
    paths = args.paths or [str(_PACKAGE_ROOT)]
    timings: dict[str, float] = {r: 0.0 for r in rules} if want_timing else {}
    t0 = time.perf_counter()
    violations = analyze(
        paths, rules=args.rule, timings=timings if want_timing else None
    )
    if args.metrics_docs and not args.rule:
        # a run scoped to specific rules (--rule) must not fail on
        # inventory drift the caller didn't ask about
        from .metrics_docs import check_metrics_docs

        violations = sorted(
            violations + check_metrics_docs(_PACKAGE_ROOT, args.metrics_docs),
            key=lambda v: (v.path, v.line, v.rule),
        )
    if args.faults_docs and not args.rule:
        # same scoping contract as --metrics-docs
        from .faults_docs import check_faults_docs

        violations = sorted(
            violations + check_faults_docs(_PACKAGE_ROOT),
            key=lambda v: (v.path, v.line, v.rule),
        )
    total_s = time.perf_counter() - t0
    # the inventory is a second full-tree read+tokenize pass — only pay
    # for it when something consumes it (the debt gate or the JSON doc)
    want_suppressions = args.json or args.suppression_budget is not None
    suppressions = collect_suppressions(paths) if want_suppressions else []
    failed = bool(violations)

    # '--json -' owns stdout: the human lines move to stderr so the
    # payload stays parseable exactly when findings exist
    vio_out = sys.stderr if args.json == "-" else sys.stdout
    for v in violations:
        print(v, file=vio_out)

    doc = _findings_doc(paths, rules, violations, suppressions)

    if want_timing:
        doc["timing"] = {
            "total_s": round(total_s, 4),
            "per_rule_s": {k: round(v, 4) for k, v in sorted(timings.items())},
        }
        print("acplint timing (wall seconds per rule):", file=sys.stderr)
        for name, secs in sorted(timings.items(), key=lambda kv: -kv[1]):
            print(f"  {name:<24} {secs:8.3f}s", file=sys.stderr)
        print(f"  {'total':<24} {total_s:8.3f}s", file=sys.stderr)
    if args.timing_budget is not None:
        ok = total_s <= args.timing_budget
        doc["timing"]["budget_s"] = args.timing_budget
        doc["timing"]["ok"] = ok
        if not ok:
            failed = True
            print(
                f"acplint: TIMING BUDGET EXCEEDED — {total_s:.2f}s > "
                f"{args.timing_budget:.2f}s budget (a rule got slow; see "
                "the per-rule report above)",
                file=sys.stderr,
            )

    if args.suppression_budget is not None:
        count = len(suppressions)
        ok = count <= args.suppression_budget
        doc["suppression_budget"] = {
            "budget": args.suppression_budget,
            "count": count,
            "ok": ok,
        }
        if not ok:
            failed = True
            print(
                f"acplint: SUPPRESSION DEBT OVER BUDGET — {count} live "
                f"'# acp-lint: disable=' pragmas > pinned budget "
                f"{args.suppression_budget}. Every suppression is an "
                "auditable claim; either fix the finding or raise the "
                "budget in the same PR with the justification below:",
                file=sys.stderr,
            )
            for s in suppressions:
                print(f"  {s}", file=sys.stderr)

    if args.json:
        payload = json.dumps(doc, indent=2) + "\n"
        if args.json == "-":
            sys.stdout.write(payload)
        else:
            Path(args.json).write_text(payload, encoding="utf-8")

    if not args.quiet:
        names = ", ".join(args.rule) if args.rule else "all rules"
        print(
            f"acplint: {len(violations)} violation(s) over "
            f"{', '.join(paths)} ({names})",
            file=sys.stderr,
        )
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
