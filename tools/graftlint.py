#!/usr/bin/env python3
"""graftlint — run the repo's AST invariant linter (docs/LINT.md).

Usage:
    python tools/graftlint.py                       # full tree, all rules
    python tools/graftlint.py --changed             # fast pre-commit loop
    python tools/graftlint.py --rule HG002 --strict hydragnn_tpu bench.py
    python tools/graftlint.py --json /tmp/findings.json
    python tools/graftlint.py --artifacts           # validate committed artifacts
    python tools/graftlint.py --list-rules

Exit codes: 0 clean, 1 findings, 2 usage/internal error.

The lint package is loaded standalone (importlib, not ``import
hydragnn_tpu``): the package root pulls in jax-adjacent subpackages,
and the linter must run in milliseconds on any container with a bare
CPython — CI calls it before anything heavyweight is proven healthy.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_lint_pkg():
    """Load ``hydragnn_tpu.lint`` as a standalone package named
    ``_graftlint`` so relative imports inside it resolve without ever
    executing ``hydragnn_tpu/__init__.py``."""
    pkg_dir = os.path.join(REPO_ROOT, "hydragnn_tpu", "lint")
    spec = importlib.util.spec_from_file_location(
        "_graftlint",
        os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir],
    )
    pkg = importlib.util.module_from_spec(spec)
    sys.modules["_graftlint"] = pkg
    spec.loader.exec_module(pkg)
    core = importlib.import_module("_graftlint.core")
    rules = importlib.import_module("_graftlint.rules")
    artifacts = importlib.import_module("_graftlint.artifacts")
    return core, rules, artifacts


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="graftlint", description=__doc__.splitlines()[0]
    )
    parser.add_argument(
        "paths",
        nargs="*",
        help="files/directories to lint (default: the whole tree)",
    )
    parser.add_argument(
        "--rule",
        action="append",
        default=None,
        metavar="HGNNN",
        help="run only this rule id (repeatable)",
    )
    parser.add_argument(
        "--strict",
        action="store_true",
        help="exit nonzero on any finding regardless of severity",
    )
    parser.add_argument(
        "--json",
        metavar="PATH",
        default=None,
        help="also write findings as JSON ('-' for stdout)",
    )
    parser.add_argument(
        "--baseline",
        metavar="PATH",
        default=os.path.join("tools", "graftlint_baseline.json"),
        help="baseline file of grandfathered findings "
        "(default: tools/graftlint_baseline.json)",
    )
    parser.add_argument(
        "--no-baseline",
        action="store_true",
        help="ignore the baseline file",
    )
    parser.add_argument(
        "--write-baseline",
        action="store_true",
        help="write current findings to the baseline file and exit 0",
    )
    parser.add_argument(
        "--changed",
        action="store_true",
        help="lint only files git reports as changed vs HEAD",
    )
    parser.add_argument(
        "--artifacts",
        action="store_true",
        help="validate committed machine artifacts (flight JSONLs + "
        "SCALING_*/TUNE_TILES/BENCH_CI_BASELINE/BENCH_FLEET "
        "JSON schemas) instead of linting source",
    )
    parser.add_argument(
        "--list-rules", action="store_true", help="print the rule catalog"
    )
    args = parser.parse_args(argv)

    core, rules_mod, artifacts_mod = _load_lint_pkg()
    all_rules = rules_mod.all_rules(REPO_ROOT)

    if args.list_rules:
        for rule in all_rules:
            print(f"{rule.id}  {rule.name:28s} [{rule.severity}] "
                  f"{rule.description}")
        return 0

    if args.artifacts:
        findings = artifacts_mod.validate_artifacts(
            REPO_ROOT, args.paths or None
        )
        for f in findings:
            print(f.render())
        _emit_json(args.json, findings)
        if findings:
            print(f"graftlint --artifacts: {len(findings)} problem(s)")
            return 1
        print("graftlint --artifacts: all committed artifacts valid")
        return 0

    rules = all_rules
    if args.rule:
        wanted = {r.upper() for r in args.rule}
        rules = [r for r in all_rules if r.id in wanted]
        unknown = wanted - {r.id for r in all_rules}
        if unknown:
            print(f"graftlint: unknown rule id(s): {sorted(unknown)}",
                  file=sys.stderr)
            return 2

    paths = args.paths or None
    if args.changed:
        paths = core.changed_paths(REPO_ROOT)
        if not paths:
            print("graftlint: no changed python files")
            return 0

    baseline = None if (args.no_baseline or args.write_baseline) else (
        args.baseline
        if os.path.isabs(args.baseline)
        else os.path.join(REPO_ROOT, args.baseline)
    )
    findings = core.run_lint(
        REPO_ROOT, rules, paths=paths, baseline=baseline
    )

    if args.write_baseline:
        out = (
            args.baseline
            if os.path.isabs(args.baseline)
            else os.path.join(REPO_ROOT, args.baseline)
        )
        core.write_baseline(out, findings)
        print(f"graftlint: wrote {len(findings)} finding(s) to {out}")
        return 0

    for f in findings:
        print(f.render())
    _emit_json(args.json, findings)
    errors = [f for f in findings if f.severity == "error"]
    if (args.strict and findings) or errors:
        print(
            f"graftlint: {len(findings)} finding(s) "
            f"({len(errors)} error(s))"
        )
        return 1
    if findings:
        print(f"graftlint: {len(findings)} warning(s) (non-strict: ok)")
    else:
        print("graftlint: clean")
    return 0


def _emit_json(dest, findings) -> None:
    if not dest:
        return
    payload = json.dumps(
        {"version": 1, "count": len(findings),
         "findings": [f.to_json() for f in findings]},
        indent=2,
    )
    if dest == "-":
        print(payload)
    else:
        with open(dest, "w") as f:
            f.write(payload + "\n")


if __name__ == "__main__":
    sys.exit(main())
