"""Kernel auditor: static checks over traced ``pl.pallas_call`` equations.

Every wrapper in the registered shape grid (``kernel_grid.GRID``) is
traced with ``jax.make_jaxpr`` — nothing executes — and each
``pallas_call`` equation found in the jaxpr is checked:

* **aliases** — every ``input_output_aliases`` entry must pair a
  dtype/shape-identical operand and result, or the "in-place" update
  silently copies (this is where quant.py's 11-entry map lives).
* **vmem** — per-program resident bytes (block shapes x dtype bytes,
  double-buffered, plus scratch) against a per-platform budget, so a
  bad chunk config fails in CI instead of OOMing Mosaic on TPU.
* **lowbit** — the fp32-accumulation invariant: no int8/fp8 value may
  reach an arithmetic primitive (``dot_general``/``add``/...) without
  first passing through a dequantizing ``convert_element_type``.
* **residuals** — ``custom_vjp`` forwards (``kernel_grid.VJP_ENTRIES``)
  are ``eval_shape``-d and their residual tuples byte-budgeted: inputs
  may be saved verbatim, aux carries are O(d^2)-small, but the primal
  output or an (N, N) matrix blows the budget (reported as FL004).
* **coverage** — every ``pl.pallas_call`` site under
  ``src/repro/kernels`` must be exercised by some grid entry, so a new
  kernel cannot silently dodge the audit.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import pathlib

import jax
from jax.extend import core as jex_core

from repro.analysis.kernel_grid import GRID, VJP_ENTRIES, GridEntry, VjpEntry
from repro.analysis.lint import Finding
from repro.utils import dtype_bytes

__all__ = [
    "KernelRecord", "trace_entry", "check_alias_map", "check_vmem",
    "check_lowbit", "check_residuals", "check_coverage", "audit_kernels",
    "VMEM_BUDGETS",
]

#: per-platform per-core budget for a program's resident block bytes.
#: TPU VMEM is ~16 MiB/core; the audit charges in/out blocks twice
#: (Mosaic double-buffers the grid pipeline) plus scratch once, and
#: leaves ~25% headroom for Mosaic-internal padding and semaphores.
VMEM_BUDGETS = {"tpu": 12 * 1024 * 1024}

#: low-bit payload dtypes that must be dequantized before arithmetic
_LOW_BIT = {"int8", "uint8", "float8_e4m3fn", "float8_e5m2"}

#: arithmetic primitives a low-bit value must never reach directly
_ARITH = {"dot_general", "add", "sub", "mul", "div", "integer_pow"}


@dataclasses.dataclass
class KernelRecord:
    """One traced ``pallas_call`` equation, unpacked for checking."""

    entry: str                 # grid entry name
    kernel: str                # pallas kernel name (name_and_src_info)
    in_avals: list             # operand avals, call order
    out_avals: list            # result avals, call order
    aliases: dict[int, int]    # input index -> output index
    block_bytes_in: int        # sum of input block footprints
    block_bytes_out: int       # sum of output block footprints
    scratch_bytes: int         # VMEM scratch (kernel jaxpr trailing refs)
    jaxpr: object              # the kernel body jaxpr (low-bit walk)


def _prod(shape) -> int:
    n = 1
    for s in shape:
        s = getattr(s, "block_size", s)  # pl.Blocked(n) -> n
        n *= int(s) if isinstance(s, int) else 1  # squeezed dims occupy 1
    return n


def _iter_eqns(jaxpr):
    """Yield every eqn in ``jaxpr`` and its nested sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from _iter_eqns(sub)


def _sub_jaxprs(val):
    vals = val if isinstance(val, (tuple, list)) else (val,)
    for v in vals:
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v


def _kernel_name(eqn) -> str:
    info = eqn.params.get("name_and_src_info")
    return getattr(info, "name", None) or str(info or "pallas_call")


def _block_bytes(eqn) -> tuple[int, int]:
    """(input, output) block bytes of one ``pallas_call`` equation.

    Block mappings follow the call's blocked operands in order — the
    inputs after any scalar-prefetch operands, then the results — so each
    block's dtype is its operand's.
    """
    gm = eqn.params["grid_mapping"]
    first = gm.num_index_operands
    operands = (list(eqn.invars[first:first + gm.num_inputs])
                + list(eqn.outvars))
    sizes = [_prod(bm.block_shape) * dtype_bytes(v.aval.dtype)
             for bm, v in zip(gm.block_mappings, operands)]
    return sum(sizes[:gm.num_inputs]), sum(sizes[gm.num_inputs:])


def _scratch_bytes(eqn) -> int:
    gm = eqn.params["grid_mapping"]
    n = int(getattr(gm, "num_scratch_operands", 0) or 0)
    if not n:
        return 0
    body = eqn.params["jaxpr"]
    total = 0
    for var in body.invars[len(body.invars) - n:]:
        aval = var.aval
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            inner = getattr(aval, "inner_aval", None)
            shape = getattr(inner, "shape", ())
            dtype = getattr(inner, "dtype", None)
        if dtype is not None:
            total += _prod(shape) * dtype_bytes(dtype)
    return total


def trace_entry(entry: GridEntry) -> list[KernelRecord]:
    """Trace one grid entry and unpack its ``pallas_call`` equations."""
    fn = functools.partial(entry.load(), **entry.kwargs)
    closed = jax.make_jaxpr(fn)(*entry.args())
    records = []
    for eqn in _iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        bin_, bout = _block_bytes(eqn)
        records.append(KernelRecord(
            entry=entry.name,
            kernel=_kernel_name(eqn),
            in_avals=[v.aval for v in eqn.invars],
            out_avals=[v.aval for v in eqn.outvars],
            aliases=dict(eqn.params.get("input_output_aliases") or ()),
            block_bytes_in=bin_,
            block_bytes_out=bout,
            scratch_bytes=_scratch_bytes(eqn),
            jaxpr=eqn.params["jaxpr"],
        ))
    return records


# ---------------------------------------------------------------------------
# Checks (each takes a record so tests can mutate one in-memory)
# ---------------------------------------------------------------------------
def check_alias_map(rec: KernelRecord) -> list[Finding]:
    """Every aliased (operand, result) pair must match shape AND dtype."""
    out = []
    for i, o in sorted(rec.aliases.items()):
        if i >= len(rec.in_avals) or o >= len(rec.out_avals):
            out.append(Finding(
                "KA001", rec.entry, 0,
                f"{rec.kernel}: alias {i}->{o} is out of range "
                f"({len(rec.in_avals)} inputs, {len(rec.out_avals)} outputs)"))
            continue
        a, b = rec.in_avals[i], rec.out_avals[o]
        if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
            out.append(Finding(
                "KA001", rec.entry, 0,
                f"{rec.kernel}: alias {i}->{o} pairs "
                f"{a.dtype}{list(a.shape)} with {b.dtype}{list(b.shape)}; "
                f"in-place update would silently copy or corrupt"))
    return out


def check_vmem(rec: KernelRecord, budgets=None) -> list[Finding]:
    """Resident block bytes (double-buffered) + scratch vs the budget."""
    budgets = budgets or VMEM_BUDGETS
    resident = 2 * (rec.block_bytes_in + rec.block_bytes_out) + rec.scratch_bytes
    out = []
    for platform, budget in budgets.items():
        if resident > budget:
            out.append(Finding(
                "KA002", rec.entry, 0,
                f"{rec.kernel}: ~{resident / 2**20:.1f} MiB resident per "
                f"program (2x{(rec.block_bytes_in + rec.block_bytes_out) / 2**20:.1f}"
                f" blocks + {rec.scratch_bytes / 2**20:.1f} scratch) exceeds "
                f"the {platform} budget of {budget / 2**20:.0f} MiB"))
    return out


def check_lowbit(rec: KernelRecord) -> list[Finding]:
    """No int8/fp8 value may reach arithmetic without a dequantize."""
    out = []
    for eqn in _iter_eqns(rec.jaxpr):
        if eqn.primitive.name not in _ARITH:
            continue
        for var in eqn.invars:
            dtype = getattr(getattr(var, "aval", None), "dtype", None)
            if dtype is not None and str(dtype) in _LOW_BIT:
                out.append(Finding(
                    "KA003", rec.entry, 0,
                    f"{rec.kernel}: {eqn.primitive.name} consumes a "
                    f"{dtype} operand directly; dequantize to fp32 first "
                    f"(payload * scale) — low-bit accumulation drifts"))
    return out


def check_residuals(entry: VjpEntry) -> list[Finding]:
    """Byte-budget a custom_vjp forward's residual tuple (FL004 layer 2)."""
    fwd = entry.load()
    args = entry.args()
    out_res = jax.eval_shape(lambda *a: fwd(*a, *entry.statics), *args)
    _, residuals = out_res
    res_leaves = jax.tree_util.tree_leaves(residuals)
    in_bytes = sum(math.prod(a.shape) * dtype_bytes(a.dtype) for a in args)
    res_bytes = sum(
        math.prod(r.shape) * dtype_bytes(r.dtype) for r in res_leaves)
    findings = []
    for r in res_leaves:
        if sum(1 for s in r.shape if s == entry.seq_len) >= 2:
            findings.append(Finding(
                "FL004", entry.name, 0,
                f"residual leaf {r.dtype}{list(r.shape)} is attention-matrix "
                f"shaped (two N={entry.seq_len} axes); linearization forbids "
                f"O(N^2) residuals"))
    budget = int(in_bytes * 1.25) + 64 * 1024
    if res_bytes > budget:
        findings.append(Finding(
            "FL004", entry.name, 0,
            f"residuals total {res_bytes / 2**20:.2f} MiB vs input "
            f"{in_bytes / 2**20:.2f} MiB (budget 1.25x + 64 KiB); save "
            f"inputs + O(d^2) carries, recompute the rest"))
    return findings


def check_coverage(records: list[KernelRecord],
                   root: pathlib.Path | None = None) -> list[Finding]:
    """Every pallas_call site under src/repro/kernels must be traced."""
    root = root or pathlib.Path(__file__).resolve().parents[1] / "kernels"
    sites = set()
    for path in sorted(root.rglob("*.py")):
        for i, ln in enumerate(path.read_text().splitlines(), start=1):
            if "pl.pallas_call(" in ln:
                sites.add(f"{path.parent.name}/{path.name}")
    traced_files = len(records)
    out = []
    if traced_files < len(sites):
        out.append(Finding(
            "KA004", "kernel_grid", 0,
            f"only {traced_files} pallas_call equations traced but "
            f"{len(sites)} kernel files define one — add the missing "
            f"wrapper to kernel_grid.GRID", severity="warning"))
    return out


def audit_kernels() -> list[Finding]:
    """Trace the whole grid and run every check; returns all findings."""
    findings: list[Finding] = []
    records: list[KernelRecord] = []
    for entry in GRID:
        try:
            recs = trace_entry(entry)
        except Exception as exc:  # pragma: no cover - grid rot is a finding
            findings.append(Finding(
                "KA000", entry.name, 0,
                f"grid entry failed to trace: {type(exc).__name__}: {exc}"))
            continue
        if not recs:
            findings.append(Finding(
                "KA000", entry.name, 0,
                "no pallas_call reached — wrapper took an XLA fallback "
                "branch; pass interpret=True in the grid entry"))
        records.extend(recs)
        for rec in recs:
            findings.extend(check_alias_map(rec))
            findings.extend(check_vmem(rec))
            findings.extend(check_lowbit(rec))
    for ventry in VJP_ENTRIES:
        try:
            findings.extend(check_residuals(ventry))
        except Exception as exc:  # pragma: no cover - grid rot is a finding
            findings.append(Finding(
                "KA000", ventry.name, 0,
                f"vjp entry failed eval_shape: {type(exc).__name__}: {exc}"))
    findings.extend(check_coverage(records))
    return findings
