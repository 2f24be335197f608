"""Tests for repro.analysis: flowlint rules, auditors, baseline, CLI.

Each lint rule gets a bad fixture that must trip it and a good fixture
that must stay quiet; the kernel auditor is exercised both on the live
grid (zero findings) and on deliberately corrupted records (a flipped
alias entry, a tiny VMEM budget, an int8-accumulating kernel); the
capability auditor on doctored docs.  The repo itself must be clean:
the shipped baseline is empty and CI keeps it that way.
"""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import lint
from repro.analysis.lint import Finding, apply_baseline, lint_source

ROOT = pathlib.Path(__file__).resolve().parent.parent

WORKER = "src/repro/serving/worker.py"   # in FL001 + FL002 scope
LAYER = "src/repro/layers/attention.py"  # in FL001 scope only
KERNEL = "src/repro/kernels/flow_chunk/flow_chunk.py"  # FL002 scope


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# FL001 — registry bypass
# ---------------------------------------------------------------------------
def test_fl001_kernel_import_trips():
    src = "from repro.kernels.flow_decode import flow_decode_call\n"
    assert rules_of(lint_source(src, LAYER)) == ["FL001"]


def test_fl001_attention_submodule_trips():
    src = "from repro.attention.plan import ExecutionPlan\n"
    assert rules_of(lint_source(src, WORKER)) == ["FL001"]


def test_fl001_facade_import_passes():
    src = "from repro.attention import ExecutionPlan, resolve\n"
    assert lint_source(src, WORKER) == []


def test_fl001_out_of_scope_passes():
    # kernels may import each other; FL001 scopes to the consumer layers
    src = "from repro.kernels.flow_chunk import flow_chunk_call\n"
    assert "FL001" not in rules_of(lint_source(src, KERNEL))


# ---------------------------------------------------------------------------
# FL002 — hot-path host sync
# ---------------------------------------------------------------------------
def test_fl002_item_trips():
    src = "def step(self, state):\n    return state.tokens.item()\n"
    assert rules_of(lint_source(src, WORKER)) == ["FL002"]


def test_fl002_asarray_computed_trips():
    src = ("import numpy as np\n"
           "def step(self, state):\n"
           "    toks = compute(state)\n"
           "    return np.asarray(toks)\n")
    assert rules_of(lint_source(src, WORKER)) == ["FL002"]


def test_fl002_asarray_on_parameter_passes():
    # converting a host-side function input is not a device sync
    src = ("import numpy as np\n"
           "def admit(self, prompt):\n"
           "    return np.asarray(prompt)\n")
    assert lint_source(src, WORKER) == []


def test_fl002_int_on_traced_in_jit_trips():
    src = ("import jax\n"
           "def step(state):\n"
           "    return int(state.pos)\n"
           "stepper = jax.jit(step)\n")
    assert rules_of(lint_source(src, WORKER)) == ["FL002"]


def test_fl002_out_of_scope_passes():
    src = "def step(self, state):\n    return state.tokens.item()\n"
    assert lint_source(src, "src/repro/launch/train.py") == []


def test_fl002_block_until_ready_trips():
    src = "def run(x):\n    return f(x).block_until_ready()\n"
    assert rules_of(lint_source(src, KERNEL)) == ["FL002"]


def test_fl002_fleet_and_transport_in_scope():
    # the fleet router and the state transport are hot-path modules: an
    # unsanctioned sync there stalls every decode worker behind it
    src = ("import numpy as np\n"
           "def export(self, worker, slot):\n"
           "    leaves = gather(worker.caches, slot)\n"
           "    return np.asarray(leaves)\n")
    for path in ("src/repro/serving/fleet.py",
                 "src/repro/serving/transport.py"):
        assert rules_of(lint_source(src, path)) == ["FL002"]


def test_fl002_fleet_sanctioned_transfer_passes():
    # the transport's export IS the sanctioned migration transfer — it
    # carries the reasoned suppression and must stay silent
    src = ("import numpy as np\n"
           "def export(self, worker, slot):\n"
           "    leaves = gather(worker.caches, slot)\n"
           "    return np.asarray(leaves)  "
           "# flowlint: disable=FL002 -- sanctioned migration transfer\n")
    assert lint_source(src, "src/repro/serving/transport.py") == []


# ---------------------------------------------------------------------------
# FL003 — deprecated shims
# ---------------------------------------------------------------------------
def test_fl003_shim_import_trips():
    src = "from repro.layers.attention import attn_cache_init\n"
    assert rules_of(lint_source(src, "src/repro/launch/train.py")) == ["FL003"]


def test_fl003_shim_call_trips():
    src = "c = attn_cache_init(cfg, 2, 64)\n"
    assert rules_of(lint_source(src, "src/repro/launch/train.py")) == ["FL003"]


def test_fl003_defining_module_passes():
    # the module that DEFINES the shim may reference it
    src = ("def attn_cache_init(cfg, b, n):\n"
           "    return None\n"
           "legacy = attn_cache_init\n")
    assert lint_source(src, "src/repro/layers/attention.py") == []


# ---------------------------------------------------------------------------
# FL004 — custom_vjp residual discipline
# ---------------------------------------------------------------------------
def test_fl004_primal_residual_trips():
    src = ("def _fwd(q, k, v):\n"
           "    out = kernel(q, k, v)\n"
           "    return out, (out, q)\n"
           "flow.defvjp(_fwd, _bwd)\n")
    assert rules_of(lint_source(src, KERNEL)) == ["FL004"]


def test_fl004_inputs_and_aux_pass():
    src = ("def _fwd(q, k, v):\n"
           "    out, sums = kernel(q, k, v)\n"
           "    return out, (q, k, v, sums)\n"
           "flow.defvjp(_fwd, _bwd)\n")
    assert lint_source(src, KERNEL) == []


def test_fl004_trailing_aux_in_primal_is_legitimate():
    # (out, sums) primal where sums is also a residual: only the LEADING
    # element is the sequence-shaped output
    src = ("def _fwd(q, k, v):\n"
           "    out, sums = kernel(q, k, v)\n"
           "    return (out, sums), (q, k, v, sums)\n"
           "flow.defvjp(_fwd, _bwd)\n")
    assert lint_source(src, KERNEL) == []


def test_fl004_inline_expression_trips():
    src = ("def _fwd(q, k):\n"
           "    out = kernel(q, k)\n"
           "    return out, (q * 2, k)\n"
           "flow.defvjp(_fwd, _bwd)\n")
    assert rules_of(lint_source(src, KERNEL)) == ["FL004"]


# ---------------------------------------------------------------------------
# Suppressions + baseline
# ---------------------------------------------------------------------------
def test_trailing_suppression_silences():
    src = ("import numpy as np\n"
           "def step(self, state):\n"
           "    toks = compute(state)\n"
           "    return np.asarray(toks)  # flowlint: disable=FL002 -- ok\n")
    assert lint_source(src, WORKER) == []


def test_preceding_comment_suppression_silences():
    src = ("import numpy as np\n"
           "def step(self, state):\n"
           "    toks = compute(state)\n"
           "    # flowlint: disable=FL002 -- the sanctioned transfer\n"
           "    return np.asarray(toks)\n")
    assert lint_source(src, WORKER) == []


def test_suppression_is_rule_specific():
    src = ("import numpy as np\n"
           "def step(self, state):\n"
           "    toks = compute(state)\n"
           "    return np.asarray(toks)  # flowlint: disable=FL001\n")
    assert rules_of(lint_source(src, WORKER)) == ["FL002"]


def test_baseline_grandfathers_by_key():
    f = Finding("FL002", WORKER, 4, "msg")
    assert apply_baseline([f], {f.key}) == []
    assert apply_baseline([f], {"FL002:other.py:4"}) == [f]


def test_shipped_baseline_is_empty():
    data = json.loads(lint.DEFAULT_BASELINE.read_text())
    assert data["findings"] == []


# ---------------------------------------------------------------------------
# Kernel auditor
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def quant_decode_record():
    from repro.analysis import kernel_audit, kernel_grid

    entry = next(e for e in kernel_grid.GRID
                 if e.name.startswith("flow_decode_q_call"))
    recs = kernel_audit.trace_entry(entry)
    assert recs, "quant decode wrapper must reach a pallas_call"
    rec = recs[0]
    assert len(rec.aliases) == 11  # the full quantized-pool alias map
    return rec


def test_alias_map_clean_on_live_record(quant_decode_record):
    from repro.analysis.kernel_audit import check_alias_map

    assert check_alias_map(quant_decode_record) == []


def test_alias_map_mutation_is_caught(quant_decode_record):
    import copy

    from repro.analysis.kernel_audit import check_alias_map

    rec = copy.copy(quant_decode_record)
    rec.aliases = dict(rec.aliases)
    i = min(rec.aliases)
    rec.aliases[i] = len(rec.out_avals)  # point past the last output
    out_of_range = check_alias_map(rec)
    assert [f.rule for f in out_of_range] == ["KA001"]

    # flip an int8-payload alias onto a dtype/shape-mismatched output
    rec2 = copy.copy(quant_decode_record)
    rec2.aliases = dict(rec2.aliases)
    for j, o in rec2.aliases.items():
        a = rec2.in_avals[j]
        for o2, b in enumerate(rec2.out_avals):
            if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
                rec2.aliases[j] = o2
                break
        else:
            continue
        break
    assert any(f.rule == "KA001" for f in check_alias_map(rec2))


def test_vmem_budget_trips_on_tiny_budget(quant_decode_record):
    from repro.analysis.kernel_audit import check_vmem

    assert check_vmem(quant_decode_record) == []  # real budget: fine
    tight = check_vmem(quant_decode_record, budgets={"tpu": 64})
    assert [f.rule for f in tight] == ["KA002"]


def test_lowbit_accumulation_is_caught():
    from jax.experimental import pallas as pl

    from repro.analysis.kernel_audit import check_lowbit, trace_entry
    from repro.analysis.kernel_grid import GridEntry

    def bad_kernel(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] + y_ref[...]  # int8 + int8, no dequant

    def bad_call(x, y):
        return pl.pallas_call(
            bad_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x, y)

    def good_kernel(x_ref, s_ref, o_ref):
        o_ref[...] = x_ref[...].astype(jnp.float32) * s_ref[...]

    def good_call(x, s):
        return pl.pallas_call(
            good_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
            interpret=True)(x, s)

    def z8():
        return jnp.zeros((8, 8), jnp.int8), jnp.zeros((8, 8), jnp.int8)

    def zf():
        return jnp.zeros((8, 8), jnp.int8), jnp.zeros((8, 8), jnp.float32)

    bad = trace_entry(GridEntry("lowbit_bad", lambda: bad_call, z8))[0]
    assert any(f.rule == "KA003" for f in check_lowbit(bad))

    good = trace_entry(GridEntry("lowbit_good", lambda: good_call, zf))[0]
    assert check_lowbit(good) == []


def test_residual_budget_catches_attention_matrix():
    from repro.analysis.kernel_audit import check_residuals
    from repro.analysis.kernel_grid import VjpEntry

    n, d = 512, 32
    sds = jax.ShapeDtypeStruct

    def bad_fwd(q, k):
        attn = q @ k.T                    # (N, N)
        return attn @ k, (q, k, attn)     # saves the attention matrix

    entry = VjpEntry(
        "fixture_bad_fwd", lambda: bad_fwd,
        lambda: (sds((n, d), jnp.float32), sds((n, d), jnp.float32)),
        statics=(), seq_len=n)
    findings = check_residuals(entry)
    assert any("attention-matrix" in f.message for f in findings)
    assert any("budget" in f.message for f in findings)

    def good_fwd(q, k):
        return q @ k.T @ k, (q, k)        # inputs only

    entry2 = VjpEntry(
        "fixture_good_fwd", lambda: good_fwd,
        lambda: (sds((n, d), jnp.float32), sds((n, d), jnp.float32)),
        statics=(), seq_len=n)
    assert check_residuals(entry2) == []


def test_live_kernel_audit_is_clean():
    from repro.analysis.kernel_audit import audit_kernels

    assert audit_kernels() == []


# ---------------------------------------------------------------------------
# Capability auditor
# ---------------------------------------------------------------------------
def test_live_capability_audit_is_clean():
    from repro.analysis.capability_audit import audit_capabilities

    assert audit_capabilities(ROOT) == []


def test_docs_drift_is_caught(tmp_path):
    from repro.analysis.capability_audit import audit_docs

    # execution.md that documents no predicates and claims a backward
    # pass for a kernel directory that does not exist
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "execution.md").write_text(
        "| kernel | op | backward |\n"
        "|---|---|---|\n"
        "| `no_such_kernel` | forward | yes |\n")
    (tmp_path / "README.md").write_text(
        "| kind | packable | paged | differentiable | verify |\n"
        "|---|---|---|---|---|\n"
        "| `attn` | no | no | no | no |\n")
    findings = audit_docs(tmp_path)
    assert any(f.rule == "CA003" and "undocumented" in f.message
               for f in findings)
    assert any("no_such_kernel" in f.message for f in findings)
    # attn is packable/differentiable in the live registry; the doctored
    # "no" cells must be reported as drift
    assert any("mixer matrix says attn" in f.message for f in findings)


# ---------------------------------------------------------------------------
# Repo-wide + CLI
# ---------------------------------------------------------------------------
def test_repo_lint_is_clean():
    assert lint.lint_tree(ROOT) == []


def test_cli_exits_zero_on_clean_repo():
    from repro.analysis.cli import main

    assert main(["--no-audit"]) == 0


def test_cli_exits_one_on_violation(tmp_path, capsys):
    from repro.analysis.cli import main

    bad = tmp_path / "src" / "repro" / "serving"
    bad.mkdir(parents=True)
    (bad / "worker.py").write_text(
        "def step(self, state):\n    return state.tokens.item()\n")
    rc = main(["--no-audit", "--root", str(tmp_path),
               "--baseline", str(tmp_path / "missing.json")])
    assert rc == 1
    assert "FL002" in capsys.readouterr().out
