"""The split of a train step's device time by the program's named scopes
(``bench/scopes.py``): its classification on hand-written ``op_name``s of
each form, its join on a hand-made trace, and the whole split on a trace
recorded on one TPU v5e (two train steps of ``flowformer-lm-xla`` at 2 x
512 inside a ``bench.window`` span, beside the compiled step's text;
``bench/tests/record_scopes.py fixture`` records both)."""
import gzip
import pathlib

import jax
import pytest

from bench import readers, scopes, trace
from bench.tests.conftest import TINY

DATA = pathlib.Path(__file__).resolve().parent / "data"
NAMES = scopes.program_scopes()
ROOT = "jit(train_step)"


@pytest.mark.parametrize("op_name,want", [
    # forward, through the layer scan
    (f"{ROOT}/jvp()/while/body/closed_call/step.mixer/while/body/dot_general",
     ("mixer", False, False)),
    # backward of the FFN under per-layer remat
    (f"{ROOT}/transpose(jvp())/while/body/closed_call/checkpoint/step.ffn/"
     "...i,io->...o/dot_general", ("ffn", True, False)),
    # recomputation of the mixer's forward for the backward pass
    (f"{ROOT}/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/step.mixer/while/body/closed_call",
     ("mixer", True, True)),
    # a scope wrapped by transforms
    (f"{ROOT}/transpose(jvp(step.head))/jit(log_softmax)/reduce_max",
     ("head", True, False)),
    (f"{ROOT}/jvp(step.embed)/gather", ("embed", False, False)),
    # the innermost scope decides
    (f"{ROOT}/jvp(step.norm)/step.mixer/add", ("mixer", False, False)),
    (f"{ROOT}/step.optimizer/jit(norm)/sqrt", ("optimizer", False, False)),
    # JAX's own names are no scopes
    (f"{ROOT}/jit(norm)/sqrt", ("unscoped", False, False)),
    (f"{ROOT}/transpose(jvp())/while/body/dynamic_update_slice",
     ("unscoped", True, False)),
    ("state.master['scan'][0]['ffn']['w_in']['w']",
     ("unscoped", False, False)),
    # merged metadata goes by its first path
    (f"{ROOT}/step.mixer/lt;{ROOT}/step.ffn/add", ("mixer", False, False)),
    (None, ("no_metadata", False, False)),
    ("", ("no_metadata", False, False)),
])
def test_classify(op_name, want):
    assert scopes.classify(op_name, NAMES) == want


def test_components_unwrap_transforms():
    got = scopes.components(
        "jit(train_step)/transpose(jvp(step.norm))/jit(_var)/"
        "...i,io->...o/x")
    assert got == ["train_step", "step.norm", "_var", "...i,io->...o", "x"]


COMPILED = """\
HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%param_0), metadata={op_name="x/step.ffn/neg"}
}

ENTRY %main.9 (p.1: f32[4], p.2: (f32[], s32[])) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  %fusion.7 = f32[4]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp())/checkpoint/rematted_computation/step.ffn/neg" stack_frame_id=3}, backend_config={"a":{"b":[1,2]},"s":"x}, y"}
  %copy.2 = f32[4]{0:T(4)} copy(%fusion.7)
  %add.3 = f32[4]{0} add(%fusion.7, %copy.2), metadata={op_name="jit(train_step)/step.optimizer/add"}
  %while.4 = (s32[], f32[4]{0}) while(%tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(train_step)/jvp()/while"}
  ROOT %mul.5 = f32[4]{0} multiply(%add.3, /*index=1*/%p.1), metadata={op_name="jit(train_step)/jvp()/step.mixer/mul"}
}
"""

TRACED = {  # the profiler's op text: operand shapes, no metadata
    "%fusion.7": "%fusion.7 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop, "
                 "calls=%fused_computation.1",
    "%copy.2": "%copy.2 = f32[4]{0:T(4)} copy(f32[4]{0} %fusion.7)",
    "%add.3": "%add.3 = f32[4]{0} add(f32[4]{0} %fusion.7, f32[4]{0:T(4)} "
              "%copy.2)",
    "%while.4": "%while.4 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) "
                "%tuple.1), condition=%cond.1, body=%body.1",
    "%mul.5": "%mul.5 = f32[4]{0} multiply(f32[4]{0} %add.3, "
              "/*index=1*/f32[4]{0} %p.1)",
    "%other.6": "%other.6 = f32[4]{0} negate(f32[4]{0} %p.1)",
}


def test_parse_hlo_reads_every_instruction():
    hlo = scopes.parse_hlo(COMPILED)
    assert hlo["%fusion.7"].op_name.endswith("step.ffn/neg")
    assert hlo["%copy.2"].op_name is None
    assert hlo["%neg.1"].op_name == "x/step.ffn/neg"
    for name, text in TRACED.items():
        if name in hlo:
            assert hlo[name].canon == scopes._canon(text.split(" = ", 1)[1])
    # another operand, or another attribute, is another instruction
    assert hlo["%add.3"].canon != scopes._canon(
        "f32[4]{0} add(f32[4]{0} %fusion.7, f32[4]{0} %p.1)")
    assert hlo["%fusion.7"].canon != scopes._canon(
        "f32[4]{0} fusion(f32[4]{0} %p.1), kind=kInput, "
        "calls=%fused_computation.1")


def _trace(ops, window=(0, 1000)):
    """Two executions of the step, [100, 200) and [300, 400), and
    ``ops`` as (name, start, duration)."""
    modules = [(readers.TRAIN, 100, 100), (readers.TRAIN, 300, 100),
               ("jit_other", 500, 50)]
    return trace.Trace(
        modules,
        [trace.Op(n, readers.TRAIN, s, d, TRACED[n]) for n, s, d in ops],
        [], window)


def test_split_partitions_each_step():
    ops = []
    for t0 in (100, 300):
        ops += [("%while.4", t0, 60), ("%fusion.7", t0, 20),
                ("%copy.2", t0 + 20, 10), ("%add.3", t0 + 30, 30),
                ("%mul.5", t0 + 60, 35)]
    ops.append(("%other.6", 510, 5))  # another program's op
    got = scopes.split(_trace(ops), scopes.parse_hlo(COMPILED), NAMES)
    assert got["steps"] == 2 and got["step"] == 100
    assert got["pass"][("ffn", "bwd")] == 20
    assert got["pass"][("optimizer", "fwd")] == 30
    assert got["pass"][("mixer", "fwd")] == 35
    assert got["pass"][("other", "fwd")] == 10  # the copy
    assert got["sub"]["no_metadata"] == 10
    assert got["sub"]["gaps"] == 5 and got["sub"]["unjoined"] == 0
    assert got["recompute"] == {"mixer": 0, "ffn": 20, "head": 0,
                                "optimizer": 0, "other": 0}
    m = scopes.metrics(got)
    assert sum(m[c]["value"] for c in scopes.CLASSES) == pytest.approx(1e-4)
    assert sum(m[c]["share"] for c in scopes.CLASSES) == pytest.approx(100)
    assert m["ffn"] == pytest.approx(
        {"value": 2e-5, "fwd": 0, "bwd": 2e-5, "share": 20})
    assert m["other"]["value"] == pytest.approx(1.5e-5)
    assert m["recompute"] == pytest.approx(
        {"value": 2e-5, "share": 20, "mixer": 0, "ffn": 2e-5, "head": 0,
         "optimizer": 0, "other": 0})


def test_split_counts_unjoined_ops_and_whole_steps_only():
    ops = [("%fusion.7", 40, 10),  # before the window's first step
           ("%add.3", 110, 30), ("%mul.5", 150, 40), ("%other.6", 300, 50)]
    got = scopes.split(_trace(ops, window=(100, 250)),
                       scopes.parse_hlo(COMPILED), NAMES)
    assert got["steps"] == 1  # the second step starts after the window
    assert got["sub"]["unjoined"] == 0
    hlo = scopes.parse_hlo(COMPILED.replace("%mul.5 = f32[4]{0} multiply",
                                            "%mul.5 = f32[4]{0} add"))
    got = scopes.split(_trace(ops), hlo, NAMES)
    # %mul.5 is another instruction there; %other.6 is not in the program
    assert got["sub"]["unjoined"] == (40 + 50) / 2
    assert scopes.split(_trace([], window=(600, 700)), hlo, NAMES) is None


def test_reading_is_silent_where_there_is_nothing_to_read(monkeypatch):
    tr = _trace([("%add.3", 110, 30)])
    assert scopes.reading({"kind": "serve", "trace": tr}, "mixer") is None
    assert scopes.reading({"kind": "train"}, "mixer") is None
    # a program without named scopes: nothing is compiled, nothing read
    monkeypatch.setattr(scopes, "program_scopes", lambda: None)
    monkeypatch.setattr(scopes, "step_hlo", lambda ctx: 1 / 0)
    assert scopes.reading({"kind": "train", "trace": tr}, "mixer") is None


def test_reading_splits_once_a_run(monkeypatch):
    calls = []

    def step_hlo(ctx):
        calls.append(1)
        return COMPILED

    monkeypatch.setattr(scopes, "step_hlo", step_hlo)
    ctx = {"kind": "train", "trace": _trace([("%add.3", 110, 30)])}
    got = {c: scopes.reading(ctx, c) for c in scopes.CLASSES + ("recompute",)}
    assert calls == [1]
    assert got["optimizer"]["value"] == pytest.approx(1.5e-5)
    assert set(got["other"]) >= {"embed", "norm", "unscoped", "no_metadata",
                                 "unjoined", "gaps", "fwd", "bwd", "share"}


def test_step_hlo_is_the_run_loops_step_with_its_scopes():
    mix = {"driver": "train", "batch": 2, "seq": 64, "distinct_batches": 1,
           "optimizer": {"kind": "adamw", "b1": 0.9, "b2": 0.95,
                         "eps": 1e-8, "weight_decay": 0.1, "grad_clip": 1.0,
                         "peak_lr": 3e-4, "warmup": 5, "total_steps": 100}}
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    hlo = scopes.parse_hlo(scopes.step_hlo({"model": TINY, "mix": mix}))
    assert getattr(jax.config, key) == before
    found = {scopes.classify(i.op_name, NAMES)[0] for i in hlo.values()}
    assert found >= {"mixer", "ffn", "head", "optimizer", "embed", "norm"}


@pytest.fixture(scope="module")
def recorded():
    tr = trace.load(DATA / "train_scopes.xplane.pb")
    text = gzip.decompress((DATA / "train_scopes.hlo.gz").read_bytes())
    return tr, scopes.parse_hlo(text.decode())


def test_recorded_ops_all_join_the_compiled_step(recorded):
    tr, hlo = recorded
    ops = [o for o in tr.ops if o.module == readers.TRAIN
           and trace.in_window(tr, o.start, o.dur)
           and not trace._is_container(o)]
    assert len(ops) > 1000
    assert all(o.name in hlo for o in ops)
    got = scopes.split(tr, hlo, NAMES)
    assert got["sub"]["unjoined"] == 0


def test_recorded_split_partitions_the_step(recorded):
    tr, hlo = recorded
    got = scopes.split(tr, hlo, NAMES)
    n, ns = trace.module_time_ns(tr, readers.TRAIN)
    assert got["steps"] == n == 2
    m = scopes.metrics(got)
    step_ms = ns / n * 1e-6
    assert sum(m[c]["value"] for c in scopes.CLASSES) == pytest.approx(
        step_ms, rel=1e-3)
    # the ops themselves cover the step; what they leave is reported
    assert 0 <= got["sub"]["gaps"] < 0.01 * got["step"]
    for c in ("mixer", "ffn", "head", "optimizer"):
        assert m[c]["fwd"] + m[c]["bwd"] == pytest.approx(m[c]["value"])
        assert m[c]["value"] > 0
    for c in ("mixer", "ffn", "head"):
        assert m[c]["fwd"] > 0 and m[c]["bwd"] > 0
    assert m["optimizer"]["bwd"] == 0
    assert m["other"]["embed"] > 0 and m["other"]["norm"] > 0
    assert 0 < m["recompute"]["value"] < step_ms
