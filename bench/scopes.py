"""Split a traced train step's device time by the program's named scopes.

The program opens one ``jax.named_scope`` where each layer of its train
step is entered (``repro/scopes.py``: ``step.embed``, ``step.norm``,
``step.mixer``, ``step.ffn``, ``step.head``, ``step.optimizer``).  The
scope reaches the ``op_name`` metadata of every instruction of the
compiled step, e.g.
``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/step.ffn/dot_general``.
The device trace names each op by the same instruction name
(``bench/trace.py``).  Joining the two by name gives every op of the
step its layer:

* control-flow containers (``while``, ``conditional``, ``call``) are
  left out, as ``trace.top_ops`` leaves them out: their bodies' ops are
  counted themselves;
* each path component is unwrapped from its transform wrappers
  (``jvp(...)``, ``transpose(...)``); the innermost component that is a
  program scope decides the class, and a merged ``a;b`` metadata goes by
  its first path;
* ``transpose(`` anywhere in the path marks the backward pass, and
  ``rematted_computation`` a recomputation (which also has a class);
* an op is joined only where its traced HLO text, without operand shapes,
  equals the compiled instruction's, without metadata and backend config.

Five classes partition each execution of the step: ``mixer``, ``ffn``,
``head``, ``optimizer`` and ``other``.  ``other`` holds ``embed`` and
``norm``, ops with metadata but no scope (``unscoped``), ops without
metadata (``no_metadata``, which XLA inserted), ops that could not be
joined (``unjoined``), and the time inside the step in which no op ran
(``gaps``).  ``recompute`` overlaps the five.

Beside the classes, the same pass sums the time under every scope by
name (``by_scope``): an op counts toward each path component that begins
with ``step.``, so ``.../step.ffn/step.ffn.router/dot_general`` counts to
both ``step.ffn`` and ``step.ffn.router``.  A part of a layer is named
``step.<layer>.<part>`` and opened inside its layer's scope; its reader
is one line, ``return scopes.scope_reading(ctx, "step.ffn.experts")``,
and reads nothing (a null in the result) once no op carries the name.

The compiled text is the step the run loop compiled and ran
(``drivers/train.run`` puts it in the run's context as ``step_hlo``).
The harness keeps op metadata in the persistent cache's key
(``harness.configure_cache``), so that step is never read from an entry
compiled from code with other scopes (or none).
"""
from __future__ import annotations

import dataclasses
import re
import time

from bench import harness, readers, trace

CLASSES = ("mixer", "ffn", "head", "optimizer", "other")
SCOPE = "step."  # the prefix of every program scope
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+) = (.*)$")
_NAME = re.compile(r"%[\w.\-]+")
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?\bop_name="((?:[^"\\]|\\.)*)"')
# attributes the profiler's op text leaves out
_UNTRACED = ("metadata", "backend_config")


def program_scopes() -> dict | None:
    """Each program scope name -> its class or ``other`` subfield; None
    for a program that names no scopes."""
    try:
        from repro import scopes
    except ImportError:
        return None
    return {scopes.MIXER: "mixer", scopes.FFN: "ffn", scopes.HEAD: "head",
            scopes.OPTIMIZER: "optimizer", scopes.EMBED: "embed",
            scopes.NORM: "norm"}


def _close(s: str, i: int) -> int:
    """Index just past the bracket group or quoted string that opens at
    ``s[i]``; quoted strings inside a group are skipped."""
    pairs = {"(": ")", "{": "}", "[": "]"}
    stack, j = [], i
    while j < len(s):
        c = s[j]
        if c == '"':
            j += 1
            while j < len(s) and s[j] != '"':
                j += 2 if s[j] == "\\" else 1
            if not stack:
                return j + 1
        elif c in pairs:
            stack.append(pairs[c])
        elif stack and c == stack[-1]:
            stack.pop()
            if not stack:
                return j + 1
        j += 1
    return len(s)


def _split_top(s: str, sep: str) -> list[str]:
    """``s`` split at each ``sep`` outside brackets and quotes."""
    out, start, j = [], 0, 0
    while j < len(s):
        if s[j] in "({[\"":
            j = _close(s, j)
            continue
        if s.startswith(sep, j):
            out.append(s[start:j])
            j += len(sep)
            start = j
            continue
        j += 1
    out.append(s[start:])
    return out


def _parts(rhs: str) -> tuple[str, str, str, str]:
    """(result shape, opcode, operand list, attributes) of an
    instruction's right-hand side."""
    j = _close(rhs, 0) if rhs.startswith("(") else rhs.index(" ")
    shape, rest = rhs[:j], rhs[j:].lstrip()
    k = rest.index("(")
    end = _close(rest, k)
    return shape, rest[:k], rest[k + 1:end - 1], rest[end:]


def _canon(rhs: str) -> tuple:
    """An instruction's text as both sides print it: operands by name
    alone, and no attribute the profiler leaves out."""
    shape, opcode, operands, attrs = _parts(rhs)
    kept = tuple(a.strip() for a in _split_top(attrs, ", ")
                 if a.strip() and a.strip().split("=", 1)[0] not in _UNTRACED)
    return shape, opcode, tuple(_NAME.findall(operands)), kept


@dataclasses.dataclass(frozen=True)
class Instr:
    """One instruction of the compiled step."""

    canon: tuple  # ``_canon`` of its text
    op_name: str | None  # its metadata's op_name, None without one


def parse_hlo(text: str) -> dict:
    """Instruction name -> ``Instr``, over every computation of the
    compiled module's text (names are unique in a module)."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        op_name = _OP_NAME.search(m.group(2))
        out[m.group(1)] = Instr(_canon(m.group(2)),
                                op_name.group(1) if op_name else None)
    return out


def components(path: str) -> list[str]:
    """The components of one ``op_name`` path, each unwrapped from its
    transform wrappers: ``transpose(jvp(step.ffn))`` -> ``step.ffn``."""
    out = []
    for c in _split_top(path, "/"):
        while (m := _WRAPPED.match(c)) is not None:
            c = m.group(1)
        out.append(c)
    return out


def classify(op_name: str | None, names: dict) -> tuple[str, bool, bool]:
    """(class or ``other`` subfield, backward, recompute) of an op with
    metadata ``op_name``, ``names`` mapping scope names to classes."""
    if not op_name:
        return "no_metadata", False, False
    path = _split_top(op_name, ";")[0]
    found = "unscoped"
    for c in components(path):
        if c in names:
            found = names[c]
    return found, "transpose(" in path, "rematted_computation" in path


def scopes_of(op_name: str | None) -> tuple[str, ...]:
    """Every program scope on the path of an op with metadata ``op_name``
    (its first path, for a merged ``a;b``), outermost first, each once."""
    if not op_name:
        return ()
    path = _split_top(op_name, ";")[0]
    return tuple(dict.fromkeys(c for c in components(path)
                               if c.startswith(SCOPE)))


def _kind(op: trace.Op, hlo: dict, names: dict):
    """(``pass`` entry or None, ``other`` subfield or None, recompute,
    scopes on its path) of a traced op; None for a control-flow
    container."""
    if trace._is_container(op):
        return None
    ins = hlo.get(op.name)
    if ins is None or ins.canon != _canon(op.text.split(" = ", 1)[1]):
        return None, "unjoined", False, ()
    cls, bwd, remat = classify(ins.op_name, names)
    field = None
    if cls not in CLASSES:
        cls, field = "other", cls
    return ((cls, "bwd" if bwd else "fwd"), field, remat,
            scopes_of(ins.op_name))


def split(tr: trace.Trace, hlo: dict, names: dict) -> dict | None:
    """Nanoseconds per execution of the train step in the traced window,
    by class and pass, and by scope name and pass; None without an
    execution.

    Returns {"steps": n, "step": module ns, "pass": {(class, "fwd"|"bwd"):
    ns}, "sub": {subfield: ns}, "recompute": {class: ns}, "by_scope":
    {scope: {"fwd": ns, "bwd": ns}}}; the ``pass`` entries and
    ``unjoined`` and ``gaps`` (in ``sub``) sum to ``step``, and
    ``by_scope`` holds the scopes that some op of the window carries.
    """
    execs = [m for m in tr.modules
             if m[0] == readers.TRAIN and trace.in_window(tr, m[1], m[2])]
    if not execs:
        return None
    starts = sorted((m[1], m[1] + m[2]) for m in execs)
    acc = {(c, p): 0 for c in CLASSES for p in ("fwd", "bwd")}
    sub = dict.fromkeys(("embed", "norm", "unscoped", "no_metadata",
                         "unjoined", "gaps"), 0)
    recompute = dict.fromkeys(CLASSES, 0)
    by_scope: dict[str, dict] = {}
    op_ns = 0
    kinds: dict[str, tuple | None] = {}  # an instruction runs many times
    j = 0
    for op in tr.ops:  # sorted by start, as the execution intervals
        while j < len(starts) and starts[j][1] <= op.start:
            j += 1
        if j == len(starts) or op.start < starts[j][0]:
            continue
        if op.text not in kinds:
            kinds[op.text] = _kind(op, hlo, names)
        if kinds[op.text] is None:
            continue
        entry, field, remat, scoped = kinds[op.text]
        op_ns += op.dur
        if entry is not None:
            acc[entry] += op.dur
        if field is not None:
            sub[field] += op.dur
        if remat:
            recompute[entry[0] if entry else "other"] += op.dur
        for name in scoped:
            by_scope.setdefault(name, {"fwd": 0, "bwd": 0})[entry[1]] += \
                op.dur
    step_ns = sum(m[2] for m in execs)
    sub["gaps"] = step_ns - op_ns
    n = len(execs)
    return {"steps": n, "step": step_ns / n,
            "pass": {k: v / n for k, v in acc.items()},
            "sub": {k: v / n for k, v in sub.items()},
            "recompute": {k: v / n for k, v in recompute.items()},
            "by_scope": {k: {p: v / n for p, v in d.items()}
                         for k, d in by_scope.items()}}


def metrics(got: dict) -> dict:
    """The six readings, in ms per step, from a ``split``."""
    ms = 1e-6
    step = got["step"]
    out = {}
    for c in CLASSES:
        fwd, bwd = got["pass"][(c, "fwd")], got["pass"][(c, "bwd")]
        total = fwd + bwd
        if c == "other":
            total += got["sub"]["unjoined"] + got["sub"]["gaps"]
        out[c] = {"value": total * ms, "fwd": fwd * ms, "bwd": bwd * ms,
                  "share": 100.0 * total / step}
    out["other"].update({k: v * ms for k, v in got["sub"].items()})
    remat = sum(got["recompute"].values())
    out["recompute"] = {"value": remat * ms, "share": 100.0 * remat / step,
                        **{c: v * ms for c, v in got["recompute"].items()}}
    return out


def _split_of(ctx) -> dict | None:
    """The run's ``split``, made once a run and kept in ``ctx``; None
    where there is nothing to read: not a traced train run, no compiled
    text in the context, a program that names no scopes, or no step in
    the trace."""
    if (ctx.get("kind") != "train" or ctx.get("trace") is None
            or not ctx.get("step_hlo")):
        return None
    if "scope_split" not in ctx:
        names = program_scopes()
        got = None
        if names is not None:
            t0 = time.perf_counter()
            got = split(ctx["trace"], parse_hlo(ctx["step_hlo"]), names)
            harness.log(f"scopes: parsed and split in "
                        f"{time.perf_counter() - t0:.1f}s")
        ctx["scope_split"] = got
    return ctx["scope_split"]


def reading(ctx, cls: str):
    """The metric of class ``cls`` (or ``recompute``) for a traced train
    run; None where there is nothing to read (``_split_of``)."""
    got = _split_of(ctx)
    return None if got is None else metrics(got)[cls]


def scope_reading(ctx, name: str):
    """Device ms per step under the program scope ``name``, with its
    forward and backward parts and its share of the step; None where
    there is nothing to read, or where no op of the step carries
    ``name``."""
    got = _split_of(ctx)
    if got is None or name not in got["by_scope"]:
        return None
    ms = 1e-6
    fwd, bwd = got["by_scope"][name]["fwd"], got["by_scope"][name]["bwd"]
    return {"value": (fwd + bwd) * ms, "fwd": fwd * ms, "bwd": bwd * ms,
            "share": 100.0 * (fwd + bwd) / got["step"]}
