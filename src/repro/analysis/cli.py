"""Command line driver: ``python -m repro.analysis``.

Default run = flowlint over ``src/repro`` + the kernel auditor + the
capability auditor; exit status 1 if any error-severity finding is not
grandfathered in the baseline.

Examples::

    python -m repro.analysis                  # lint + kernel + capability
    python -m repro.analysis --no-audit       # AST lint only (fast)
    python -m repro.analysis --json           # machine-readable findings
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

from repro.analysis.lint import (
    DEFAULT_BASELINE,
    apply_baseline,
    lint_tree,
    load_baseline,
)

__all__ = ["main"]


def _repo_root() -> pathlib.Path:
    return pathlib.Path(__file__).resolve().parents[3]


def main(argv: list[str] | None = None) -> int:
    """Run the requested analysis layers; return the process exit code."""
    ap = argparse.ArgumentParser(
        prog="repro.analysis",
        description="flowlint + kernel/capability auditors",
    )
    ap.add_argument("--root", type=pathlib.Path, default=None,
                    help="repo root (default: inferred from the package)")
    ap.add_argument("--baseline", type=pathlib.Path, default=DEFAULT_BASELINE,
                    help="grandfathered-findings JSON")
    ap.add_argument("--no-lint", action="store_true",
                    help="skip the AST lint layer")
    ap.add_argument("--no-audit", action="store_true",
                    help="skip the kernel + capability auditors")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as JSON instead of text")
    args = ap.parse_args(argv)

    root = args.root or _repo_root()
    findings = []
    if not args.no_lint:
        findings += lint_tree(root)
    if not args.no_audit:
        from repro.analysis.capability_audit import audit_capabilities
        from repro.analysis.kernel_audit import audit_kernels

        findings += audit_kernels()
        findings += audit_capabilities(root)

    findings = apply_baseline(findings, load_baseline(args.baseline))
    errors = [f for f in findings if f.severity == "error"]

    if args.as_json:
        print(json.dumps([dataclasses.asdict(f) for f in findings], indent=2))
    else:
        for f in findings:
            print(f.render())
        n_warn = len(findings) - len(errors)
        print(f"repro.analysis: {len(errors)} error(s), {n_warn} warning(s)")
    return 1 if errors else 0


if __name__ == "__main__":  # pragma: no cover - exercised via __main__
    sys.exit(main())
