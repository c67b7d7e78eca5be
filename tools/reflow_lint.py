#!/usr/bin/env python3
"""reflow-lint: the project's invariant checker.

Usage::

    python tools/reflow_lint.py                  # all fast passes
    python tools/reflow_lint.py --json           # reflow.lint/1 report
    python tools/reflow_lint.py --passes locks,seams
    python tools/reflow_lint.py --rules bare-assert
    python tools/reflow_lint.py --list-rules

Exit codes: 0 clean, 1 findings, 2 usage/internal error. Waive a
finding inline with a reason::

    # reflow-lint: waive <rule> -- <why this is safe>

See docs/guide.md "Static analysis & lockcheck" for the rule catalog.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(
        prog="reflow_lint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--root", default=None,
                    help="repo root to lint (default: the repo this "
                         "script lives in)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the reflow.lint/1 JSON report")
    ap.add_argument("--passes", default=None,
                    help="comma-separated pass subset (default: all)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule filter (default: all)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    args = ap.parse_args()

    from reflow_tpu.analysis import core, run

    if args.list_rules:
        # import the passes so every rule is registered
        from reflow_tpu.analysis import (envknobs, exceptions,  # noqa: F401
                                         locks, metrics_pass, seams)
        for name in sorted(core.RULES):
            print(f"{name:28s} {core.RULES[name]}")
        return 0

    root = args.root or os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    passes = args.passes.split(",") if args.passes else None
    rules = args.rules.split(",") if args.rules else None
    try:
        report = run(root, passes=passes, rules=rules)
    except KeyError as e:
        print(f"reflow_lint: {e}", file=sys.stderr)
        return 2

    if args.as_json:
        print(json.dumps(report, indent=2))
    else:
        print(core.render_report(report))
    return 1 if report["findings"] else 0


if __name__ == "__main__":
    sys.exit(main())
