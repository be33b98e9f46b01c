"""CLI: ``python -m kubernetes_tpu.analysis [--json] [--rule R]
[--baseline FILE | --write-baseline FILE] [paths…]``.

Exit status: 0 when clean, 1 when any finding survives suppression (and,
with ``--baseline``, baseline) filtering — CI gates on this; 2 on
usage/internal errors.

With no paths, the shipped tree is analyzed (each checker over its
registered modules).  Explicit paths are handed to ALL checkers — the
fixture-driven mode the tier-1 test uses (a fixture file declares its own
``_KTPU_GUARDED`` / ``pre_filter_spec_pure`` / ``jax.jit`` markers, so
only the relevant checker fires on it).

``--json`` prints a machine-readable report (findings + per-rule counts
and wall times) for tooling instead of the line-per-finding
text form.

Baselines let a BRANCH gate on *new* findings while main stays strict on
zero: ``--write-baseline FILE`` snapshots the current findings;
``--baseline FILE`` reports only findings absent from the snapshot.
Matching is (rule, repo-relative path, message) as a multiset —
line-number churn neither hides nor resurrects a baselined finding, and
fixing one of two identical findings still surfaces the other.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import Counter as _Counter
from typing import List

from kubernetes_tpu.analysis import (
    _REPO_ROOT,
    default_targets,
    last_rule_seconds,
    render_json,
    render_text,
    run_analysis,
)
from kubernetes_tpu.analysis.core import ALL_RULES, Finding


def _finding_key(f: Finding):
    path = f.path
    try:
        rel = os.path.relpath(os.path.abspath(path), _REPO_ROOT)
        if not rel.startswith(".."):
            path = rel
    except ValueError:
        pass
    return (f.rule, path.replace(os.sep, "/"), f.message)


def write_baseline(findings: List[Finding], path: str) -> None:
    doc = {
        "version": 1,
        "findings": [
            {"rule": r, "path": p, "message": m}
            for (r, p, m) in sorted(_finding_key(f) for f in findings)
        ],
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")


def apply_baseline(findings: List[Finding], path: str):
    """(new findings, suppressed count) — multiset subtraction on
    (rule, relpath, message)."""
    with open(path, "r", encoding="utf-8") as fh:
        doc = json.load(fh)
    budget = _Counter(
        (e["rule"], e["path"], e["message"])
        for e in doc.get("findings", ())
    )
    out: List[Finding] = []
    suppressed = 0
    for f in findings:
        key = _finding_key(f)
        if budget.get(key, 0) > 0:
            budget[key] -= 1
            suppressed += 1
        else:
            out.append(f)
    return out, suppressed


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m kubernetes_tpu.analysis",
        description="Static invariant analysis — eleven rule families: "
        "lock-discipline, plugin-purity, jit-boundary, d2h-leak, "
        "donation, slice-clamp, retrace, shape, dtype, shard, breaker.",
    )
    ap.add_argument("paths", nargs="*", help="files to analyze (default: shipped tree)")
    ap.add_argument("--json", action="store_true", help="JSON report on stdout")
    ap.add_argument(
        "--rule",
        action="append",
        choices=sorted(ALL_RULES),
        help="restrict output to RULE (repeatable)",
    )
    ap.add_argument(
        "--baseline",
        metavar="FILE",
        help="report only findings NOT present in this baseline snapshot",
    )
    ap.add_argument(
        "--write-baseline",
        metavar="FILE",
        help="snapshot current findings to FILE and exit 0",
    )
    args = ap.parse_args(argv)
    if args.baseline and args.write_baseline:
        print(
            "kubernetes_tpu.analysis: --baseline and --write-baseline are "
            "mutually exclusive",
            file=sys.stderr,
        )
        return 2

    try:
        if args.paths:
            # every checker key run_analysis knows about — derived, so a
            # new rule family cannot silently miss fixture-mode runs
            targets = {key: args.paths for key in default_targets()}
            findings = run_analysis(targets)
        else:
            findings = run_analysis()
    except (OSError, SyntaxError) as e:
        print(f"kubernetes_tpu.analysis: error: {e}", file=sys.stderr)
        return 2

    if args.write_baseline:
        try:
            write_baseline(findings, args.write_baseline)
        except OSError as e:
            print(f"kubernetes_tpu.analysis: error: {e}", file=sys.stderr)
            return 2
        print(
            f"kubernetes_tpu.analysis: baseline of {len(findings)} "
            f"finding(s) written to {args.write_baseline}"
        )
        return 0

    suppressed = None
    if args.baseline:
        try:
            findings, suppressed = apply_baseline(findings, args.baseline)
        except (OSError, ValueError, KeyError, json.JSONDecodeError) as e:
            print(f"kubernetes_tpu.analysis: error: {e}", file=sys.stderr)
            return 2

    if args.rule:
        findings = [f for f in findings if f.rule in set(args.rule)]

    if args.json:
        print(render_json(findings, rule_seconds=dict(last_rule_seconds),
                          baseline_suppressed=suppressed))
    else:
        print(render_text(findings))
        if suppressed:
            print(
                f"kubernetes_tpu.analysis: {suppressed} baselined "
                "finding(s) suppressed"
            )
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
