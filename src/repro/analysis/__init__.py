"""Repo-native static analysis: flowlint + kernel/registry auditors.

Two layers keep the conventions PR 1-8 established mechanically true
(see ``docs/analysis.md`` for the rule catalog):

* :mod:`repro.analysis.lint` — stdlib-``ast`` rules FL001-FL004
  (registry bypass, hot-path host sync, deprecated shims, custom_vjp
  residual discipline) with per-line suppressions and a committed
  baseline.
* :mod:`repro.analysis.kernel_audit` — traces every ``pl.pallas_call``
  wrapper over :mod:`repro.analysis.kernel_grid` and statically checks
  alias maps, VMEM footprints, and the fp32-accumulation invariant;
  :mod:`repro.analysis.capability_audit` cross-checks both registries
  and the prose capability tables.

CLI: ``python -m repro.analysis`` (blocking in CI's ``analysis`` job).
"""
from repro.analysis.cli import main
from repro.analysis.lint import Finding, lint_source, lint_tree

__all__ = ["main", "Finding", "lint_source", "lint_tree"]
