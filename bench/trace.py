"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The device plane of a TPU trace has an ``XLA Modules`` line (one event per
program execution, named ``jit_<fn>(<fingerprint>)``) and an ``XLA Ops``
line (one event per top-level HLO instruction, named by its HLO text).
Pallas kernels are the ops whose text calls ``tpu_custom_call``; their
operand shapes are parsed from that text.  Host spans that the harness
opens (``bench.*``) sit on the host plane, on the same clock; the traced
stretch of a window is the ``bench.traced`` span (``bench.window`` where
the whole window was traced).
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


@dataclasses.dataclass
class Op:
    """One device op execution."""

    name: str  # HLO instruction name, e.g. "%flow_decode_step.3"
    module: str  # program base name, e.g. "jit_step_fn"
    start: int  # ns
    dur: int  # ns
    text: str  # the HLO text


@dataclasses.dataclass
class Trace:
    """The parts of one trace the metrics read."""

    modules: list  # (base name, start ns, duration ns)
    ops: list  # Op
    spans: list  # host (name, start ns, duration ns) of bench.* spans
    window: tuple  # (start ns, end ns) of the bench.traced span


def module_base(name: str) -> str:
    """``jit_step_fn(123)`` -> ``jit_step_fn``."""
    return name.split("(", 1)[0]


def load(path) -> Trace:
    """Read the trace file at ``path`` (or the one under a directory)."""
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.is_dir():
        found = sorted(path.rglob("*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(str(path))
    modules, raw_ops, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [(module_base(e.name), int(e.start_ns),
                                int(e.duration_ns)) for e in line.events]
                elif line.name == "XLA Ops":
                    raw_ops = [(e.name, int(e.start_ns), int(e.duration_ns))
                               for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns)))
    modules.sort(key=lambda m: m[1])
    ops = _assign(sorted(raw_ops, key=lambda o: o[1]), modules)
    # the traced stretch of a window; a trace of a whole window has none
    win = ([s for s in spans if s[0] == "bench.traced"]
           or [s for s in spans if s[0] == "bench.window"])
    if win:
        window = (win[0][1], win[0][1] + win[0][2])
    elif ops:
        window = (ops[0].start, max(o.start + o.dur for o in ops))
    else:
        window = (0, 0)
    return Trace(modules, ops, spans, window)


def _assign(raw_ops, modules) -> list:
    """Give each op the module whose execution interval holds its start."""
    out, j = [], 0
    for text, start, dur in raw_ops:
        while j < len(modules) and modules[j][1] + modules[j][2] < start:
            j += 1
        mod = ""
        if j < len(modules) and modules[j][1] <= start:
            mod = modules[j][0]
        name = text.split(" = ", 1)[0].strip()
        out.append(Op(name, mod, start, dur, text))
    return out


def in_window(tr: Trace, start: int, dur: int) -> bool:
    """Does the interval start inside the traced window?"""
    return tr.window[0] <= start < tr.window[1]


def busy_intervals(tr: Trace) -> list:
    """The union of op intervals inside the window, as sorted (start, end)."""
    ivs = sorted((max(o.start, tr.window[0]), min(o.start + o.dur,
                                                  tr.window[1]))
                 for o in tr.ops
                 if o.start < tr.window[1] and o.start + o.dur > tr.window[0])
    out = []
    for a, b in ivs:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def busy_ns(tr: Trace) -> int:
    """Nanoseconds of the window in which some op ran on the device."""
    return sum(b - a for a, b in busy_intervals(tr))


def window_ns(tr: Trace) -> int:
    """Length of the traced window."""
    return tr.window[1] - tr.window[0]


def module_time_ns(tr: Trace, base: str) -> tuple[int, int]:
    """(executions, summed device ns) of the program ``base`` in the window."""
    ms = [m for m in tr.modules if m[0] == base and in_window(tr, m[1], m[2])]
    return len(ms), sum(m[2] for m in ms)


def kernel_ops(tr: Trace, module: str | None = None) -> list:
    """The Pallas kernel executions in the window, optionally of one
    program."""
    return [o for o in tr.ops
            if "tpu_custom_call" in o.text and in_window(tr, o.start, o.dur)
            and (module is None or o.module == module)]


def operand_shapes(text: str) -> list[tuple[str, tuple]]:
    """Operand (dtype, shape) pairs of a custom call's HLO text."""
    body = text.split("custom-call(", 1)[1].split("), custom_call_target", 1)[0]
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in _SHAPE.finditer(body)]


def _short(op: Op) -> str:
    return f"{op.module}:{re.sub(r'[.][0-9]+$', '', op.name.lstrip('%'))}"


def top_ops(tr: Trace, k: int = 10) -> list:
    """The ``k`` device ops (by program and instruction, numbering dropped)
    that took most time in the window, as [name, seconds].  Control-flow
    ops (``while``, ``conditional``, ``call``) are left out: the ops of
    their bodies are listed themselves."""
    acc: dict[str, int] = {}
    for o in tr.ops:
        if in_window(tr, o.start, o.dur) and not _is_container(o):
            acc[_short(o)] = acc.get(_short(o), 0) + o.dur
    return [[n, t / 1e9] for n, t in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def _is_container(op: Op) -> bool:
    rhs = op.text.split(" = ", 1)[-1]
    return bool(re.match(r"^(\(.*?\)|\S+) (while|conditional|call)\(", rhs))


def idle_gaps(tr: Trace, k: int = 10) -> list:
    """The ``k`` longest idle stretches of the device in the window, as
    [what the host was doing, seconds]: the innermost ``bench.*`` span
    around the stretch's middle, ``host`` where none was open."""
    ivs = busy_intervals(tr)
    edges = [tr.window[0]] + [x for iv in ivs for x in iv] + [tr.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (a + b) // 2
        open_ = [s for s in tr.spans if s[1] <= mid < s[1] + s[2]]
        label = min(open_, key=lambda s: s[2])[0] if open_ else "host"
        out.append([label, (b - a) / 1e9])
    return out
