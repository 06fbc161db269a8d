"""tpulint CLI.

Exit codes: 0 = clean (or every finding baselined), 1 = new findings,
2 = usage error. ``--update-baseline`` rewrites the checked-in baseline
with the current findings (for grandfathering during adoption; the goal
state is an EMPTY baseline — fix or pragma instead when you can).
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional, Sequence

from deepspeed_tpu.tools.tpulint import rules as _rules  # noqa: F401
from deepspeed_tpu.tools.tpulint.core import (
    BASELINE_NAME,
    all_rules,
    find_root,
    lint_paths,
    load_baseline,
    new_findings,
    save_baseline,
)

DEFAULT_PATHS = ("deepspeed_tpu", "benchmarks", "tests")


def _list_rules() -> str:
    out = []
    for rule_id, rule in sorted(all_rules().items()):
        out.append(f"{rule_id}\n    {rule.doc}")
    return "\n".join(out)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="tpulint",
        description="AST invariant linter for the deepspeed_tpu "
                    "architecture rules (docs/static_analysis.md)")
    parser.add_argument("paths", nargs="*",
                        help="files/directories to lint (default: "
                             f"{' '.join(DEFAULT_PATHS)} under the repo "
                             "root when present)")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalog and exit")
    parser.add_argument("--select", action="append", metavar="RULE",
                        help="run only these rule ids (repeatable)")
    parser.add_argument("--baseline", metavar="PATH", default=None,
                        help="baseline file of grandfathered findings "
                             f"(default: <root>/{BASELINE_NAME} when it "
                             "exists)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore any baseline file")
    parser.add_argument("--update-baseline", action="store_true",
                        help="write the current findings to the baseline "
                             "file and exit 0")
    parser.add_argument("--no-pragmas", action="store_true",
                        help="report findings even on pragma-suppressed "
                             "lines (audit mode)")
    parser.add_argument("--fix", action="store_true",
                        help="apply the mechanical autofixes (import "
                             "routing + warn-once rules), then re-lint")
    parser.add_argument("--update-telemetry-snapshot", action="store_true",
                        help="regenerate docs/telemetry_schema.json from "
                             "docs/telemetry.md (accepts schema additions "
                             "for the telemetry-append-only rule) and exit")
    args = parser.parse_args(argv)

    if args.list_rules:
        print(_list_rules())
        return 0

    if args.update_telemetry_snapshot:
        root = find_root(args.paths or [os.getcwd()])
        path = _rules.save_telemetry_snapshot(root)
        from deepspeed_tpu.tools.tpulint.rules import parse_telemetry_doc
        kinds = parse_telemetry_doc(root)
        print(f"tpulint: wrote {len(kinds)} event kind(s) to {path}")
        return 0

    paths = list(args.paths)
    if not paths:
        root_guess = find_root([os.getcwd()])
        paths = [os.path.join(root_guess, p) for p in DEFAULT_PATHS
                 if os.path.exists(os.path.join(root_guess, p))]
        if not paths:
            print("tpulint: no default paths found; pass paths explicitly",
                  file=sys.stderr)
            return 2
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"tpulint: no such path(s): {missing}", file=sys.stderr)
        return 2

    root = find_root(paths)
    try:
        findings = lint_paths(paths, root=root, rules=args.select,
                              respect_pragmas=not args.no_pragmas)
    except KeyError as e:
        print(f"tpulint: {e.args[0]}", file=sys.stderr)
        return 2

    if args.fix:
        from deepspeed_tpu.tools.tpulint.fixes import apply_fixes
        fixed = apply_fixes(findings, root)
        if fixed:
            for path in sorted(fixed):
                print(f"fixed: {path}")
            findings = lint_paths(paths, root=root, rules=args.select,
                                  respect_pragmas=not args.no_pragmas)

    baseline_path = args.baseline or os.path.join(root, BASELINE_NAME)
    if args.update_baseline:
        save_baseline(baseline_path, findings)
        print(f"tpulint: wrote {len(findings)} finding(s) to "
              f"{baseline_path}")
        return 0

    if not args.no_baseline and os.path.exists(baseline_path):
        baseline = load_baseline(baseline_path)
        reportable = new_findings(findings, baseline)
        grandfathered = len(findings) - len(reportable)
    else:
        reportable, grandfathered = list(findings), 0

    for f in reportable:
        print(f.render())
    tail: List[str] = [f"{len(reportable)} finding(s)"]
    if grandfathered:
        tail.append(f"{grandfathered} baselined")
    print(f"tpulint: {', '.join(tail)} "
          f"({len(all_rules()) if not args.select else len(args.select)} "
          "rule(s))", file=sys.stderr)
    return 1 if reportable else 0


if __name__ == "__main__":
    sys.exit(main())
