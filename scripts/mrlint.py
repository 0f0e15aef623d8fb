#!/usr/bin/env python
"""mrlint CLI — domain-aware static analysis (gpu_mapreduce_tpu/lint/).

Pure AST, no jax: the lint package is loaded standalone via importlib
so ``gpu_mapreduce_tpu/__init__`` (and jax behind it) never imports —
the full gate runs in a few seconds with zero side effects.

    scripts/mrlint.py                      # all rules, whole package
    scripts/mrlint.py -r knob-registry     # one rule
    scripts/mrlint.py --changed            # report only changed files
    scripts/mrlint.py --json -             # machine-readable findings
    scripts/mrlint.py --list-rules

Exit codes: 0 clean, 1 unsuppressed findings, 2 usage/internal error.
Wired into scripts/ci.sh (quick: changed-module scope; full: whole
package).  Rule catalog + pragma policy: doc/lint.md.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT_DIR = os.path.join(REPO, "gpu_mapreduce_tpu", "lint")

# scripts the knob-registry and net-timeout rules scan on top of the
# package (mrctl/mrlaunch are the client and the data-plane supervisor
# — both daemon-adjacent enough to hold the timeout line)
EXTRA_FILES = ("scripts/mrctl.py", "scripts/mrlaunch.py")


def _load_lint():
    """Import gpu_mapreduce_tpu.lint WITHOUT executing the package
    __init__ (which imports jax)."""
    if "mrlint_pkg" in sys.modules:
        return sys.modules["mrlint_pkg"]
    spec = importlib.util.spec_from_file_location(
        "mrlint_pkg", os.path.join(LINT_DIR, "__init__.py"),
        submodule_search_locations=[LINT_DIR])
    mod = importlib.util.module_from_spec(spec)
    sys.modules["mrlint_pkg"] = mod
    spec.loader.exec_module(mod)
    return mod


def _changed_paths() -> set:
    """Working-tree + last-commit changes, repo-relative.  Untracked
    files count too — a brand-new module with a violation must not
    slip through the quick gate's changed-file scope."""
    out = set()
    for args in (["git", "diff", "--name-only", "HEAD"],
                 ["git", "diff", "--name-only", "HEAD~1..HEAD"],
                 ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            res = subprocess.run(args, cwd=REPO, capture_output=True,
                                 text=True, timeout=30)
            out.update(p for p in res.stdout.splitlines() if p)
        except Exception:
            pass
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="mrlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--rules", "-r",
                    help="comma-separated checker names (default: all)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--json", metavar="FILE",
                    help="write findings JSON to FILE ('-' = stdout)")
    ap.add_argument("--changed", action="store_true",
                    help="report findings only in files changed vs git "
                         "HEAD/HEAD~1 (analysis still sees everything)")
    ap.add_argument("--baseline", metavar="FILE",
                    help="suppress fingerprints listed in FILE")
    ap.add_argument("--write-baseline", metavar="FILE",
                    help="write current unsuppressed fingerprints to "
                         "FILE and exit 0")
    ap.add_argument("--root", default=REPO, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    try:
        lint = _load_lint()
    except Exception as e:                      # broken analyzer ≠ clean
        print(f"mrlint: failed to load analyzer: {e!r}", file=sys.stderr)
        return 2

    if args.list_rules:
        for name in sorted(lint.RULES):
            print(f"{name:18s} {lint.RULE_DOC.get(name, '')}")
        return 0

    rules = args.rules.split(",") if args.rules else None
    baseline = None
    if args.baseline:
        try:
            baseline = lint.load_baseline(args.baseline)
        except Exception as e:
            print(f"mrlint: bad baseline {args.baseline}: {e!r}",
                  file=sys.stderr)
            return 2
    only = _changed_paths() if args.changed else None

    try:
        project = lint.Project(args.root, extra_files=EXTRA_FILES)
        findings = lint.run(project, rules=rules, baseline=baseline,
                            only_paths=only)
    except KeyError as e:
        print(f"mrlint: {e.args[0]}", file=sys.stderr)
        return 2

    if args.write_baseline:
        lint.write_baseline(args.write_baseline, findings)
        print(f"mrlint: baseline written to {args.write_baseline}")
        return 0

    payload = lint.summary(findings)
    payload["files_scanned"] = len(project.modules) + len(project.extra)
    payload["rules"] = rules or sorted(lint.RULES)
    if args.json == "-":
        json.dump(payload, sys.stdout, indent=2)
        print()
    elif args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")

    live = [f for f in findings if not f.suppressed]
    if args.json != "-":
        for f in live:
            print(f)
    nsupp = payload["suppressed"]
    scope = "changed files" if args.changed else "project"
    if live:
        print(f"mrlint: {len(live)} finding(s) in {scope} "
              f"({nsupp} suppressed by pragma/baseline)",
              file=sys.stderr)
        return 1
    print(f"mrlint OK: 0 findings in {scope} "
          f"({payload['files_scanned']} files, {nsupp} suppressed)",
          file=sys.stderr if args.json == "-" else sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
