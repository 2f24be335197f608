"""The trace reduction on two small traces recorded on one TPU v5e: a few
serving engine steps of flowformer-lm (2 slots) and two of its train
steps at 2 x 512, each inside a ``bench.window`` span."""
import pathlib

import pytest

from bench import counts, readers, trace
from bench.peaks import peaks_of

DATA = pathlib.Path(__file__).resolve().parent / "data"
FLOWFORMER = {"n_layers": 6, "d_model": 512, "n_heads": 8, "n_kv_heads": 8,
              "d_ff": 2048, "vocab_size": 32768, "act": "gelu",
              "attention": {"chunk_size": 128}}


@pytest.fixture(scope="module")
def serve_trace():
    return trace.load(DATA / "serve.xplane.pb")


@pytest.fixture(scope="module")
def train_trace():
    return trace.load(DATA / "train.xplane.pb")


def test_window_busy_and_idle(serve_trace):
    tr = serve_trace
    w, busy = trace.window_ns(tr), trace.busy_ns(tr)
    assert 0 < busy < w
    ivs = trace.busy_intervals(tr)
    assert all(a < b for a, b in ivs)
    assert all(b1 <= a2 for (_, b1), (a2, _) in zip(ivs, ivs[1:]))
    gaps = trace.idle_gaps(tr, 10**6)
    assert sum(s for _, s in gaps) == pytest.approx((w - busy) / 1e9,
                                                    rel=1e-6)
    assert {label for label, _ in gaps} <= {
        "bench.window", "bench.engine_step", "bench.wait_for_arrival",
        "host"}
    idle = readers.idle({"trace": tr})["value"]
    assert idle == pytest.approx(100 * (1 - busy / w))


def test_kernels_are_attributed_to_their_program(serve_trace):
    tr = serve_trace
    dec = trace.kernel_ops(tr, readers.DECODE)
    pre = trace.kernel_ops(tr, readers.PREFILL)
    n_dec, _ = trace.module_time_ns(tr, readers.DECODE)
    assert n_dec >= 1 and len(dec) == 6 * n_dec  # one call per layer
    assert all(o.name.startswith("%flow_decode_step") for o in dec)
    assert all(o.name.startswith("%flow_fused_forward") for o in pre)
    q = trace.operand_shapes(dec[0].text)[1]
    assert q == ("bf16", (16, 1, 64))  # 2 slots x 8 kv heads, G=1, D=64


def test_roofline_from_shapes(serve_trace):
    ctx = {"trace": serve_trace, "model": FLOWFORMER,
           "peaks": peaks_of("TPU v5 lite")}
    got = readers.roofline(ctx, readers.DECODE)
    ops = trace.kernel_ops(serve_trace, readers.DECODE)
    least = len(ops) * counts.least_time(*counts.flow_decode(16, 1, 64, 64),
                                         ctx["peaks"])[0]
    took = sum(o.dur for o in ops) * 1e-9
    assert got["value"] == pytest.approx(100 * least / took)
    assert got["bound"] == "memory" and 0 < got["value"] <= 100


def test_train_trace_has_forward_and_backward_kernels(train_trace):
    ops = trace.kernel_ops(train_trace, readers.TRAIN)
    # the backward reaches the HLO under the forward wrapper's name; it is
    # told apart by its operands (state totals and cotangents besides q/k/v)
    n_operands = [len(trace.operand_shapes(o.text)) for o in ops]
    steps, _ = trace.module_time_ns(train_trace, readers.TRAIN)
    # per step and layer: the forward, its rematerialised copy, the backward
    assert n_operands.count(4) == 2 * 6 * steps
    assert n_operands.count(17) == 6 * steps
    ctx = {"trace": train_trace, "model": FLOWFORMER,
           "peaks": peaks_of("TPU v5 lite")}
    got = readers.roofline(ctx, readers.TRAIN)
    assert 0 < got["value"] <= 100
    top = trace.top_ops(train_trace, 10)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
