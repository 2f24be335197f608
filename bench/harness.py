"""What every cell's run shares: the chip check, the compile cache, the
configuration and limit files, compile counting, tracing and checks.

Nothing here names a configuration, a traffic mix or a metric; those are
files found by the names in ``BENCHMARK.json``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import sys
import time
import types
import typing

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chips(chips: int):
    """The TPU devices to run on; exits non-zero, before any work, unless
    JAX sees at least ``chips`` of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: no TPU found: JAX runs on {devs[0].platform!r} "
                     f"({devs[0].device_kind}); the benchmark measures the "
                     "chip and has no fallback")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def configure_cache():
    """Keep JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    (a fixed path: it is part of each entry's key), caching every program,
    with no size limit: a limit makes every read and write take one file
    lock, which the warm-up's compile threads then queue on.  Op metadata
    is part of each entry's key, so a program's compiled text always
    carries the named scopes of the code that ran (``bench/scopes.py``),
    never those of an entry compiled from code with other scopes."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def load_module(path: pathlib.Path, name: str | None = None):
    """Import the Python file ``path`` as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        name or f"bench_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    """A JSON file of the benchmark."""
    return json.loads(path.read_text())


def model_config(model: dict):
    """The program's ``ModelConfig`` for a configuration file's ``model``.

    Every nested group (``attention``, ``moe``, ``mla``, ``rglru``,
    ``ssd``) becomes its dataclass, and a list becomes a tuple where the
    field is one; the field types are read from the dataclasses.  A key
    the program does not have fails with its name."""
    from repro.config import ModelConfig

    return _dataclass_of(ModelConfig, model, "model")


def _dataclass_of(cls, values: dict, where: str):
    hints = typing.get_type_hints(cls)
    fields = {f.name for f in dataclasses.fields(cls)}
    for key in values:
        if key not in fields:
            raise ValueError(f"bench: {where}.{key}: {cls.__name__} has no "
                             f"field {key!r}")
    return cls(**{k: _value_of(hints[k], v, f"{where}.{k}")
                  for k, v in values.items()})


def _value_of(hint, value, where: str):
    """``value`` from JSON as the field typed ``hint`` holds it."""
    if typing.get_origin(hint) in (typing.Union, types.UnionType):
        if value is None:
            return None
        hint = next(a for a in typing.get_args(hint) if a is not type(None))
    if dataclasses.is_dataclass(hint) and isinstance(value, dict):
        return _dataclass_of(hint, value, where)
    if typing.get_origin(hint) is tuple and isinstance(value, list):
        return tuple(value)
    return value


def reference(config: dict):
    """The plain reference module a configuration file names under
    ``reference``: ``bench/reference/<name>.py`` (its interface is
    ``bench/reference/__init__.py``'s docstring).  A missing module fails
    with its path."""
    name = config["reference"]
    path = BENCH / "reference" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: the configuration's reference {name!r} "
                         f"has no module: {path} does not exist")
    return importlib.import_module(f"bench.reference.{name}")


class CompileCounter:
    """Counts the programs JAX lowers for compilation (a fresh compile or a
    persistent-cache load alike) while ``counting`` is on."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.counting = False
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.counting and name == self.EVENT:
            self.count += 1


TRACE_TAIL_S = 10.0  # a traced run profiles the last seconds of its window


class TailTrace:
    """Profiles the device over the last ``TRACE_TAIL_S`` seconds of a
    window, when ``trace_dir`` is given; the host's Python tracer stays
    off.  A whole 50 s window holds millions of op events, whose
    collection alone outlasts a run's time limit.  Starting late, rather
    than stopping early, keeps the long collection out of the window.

    The run loop calls ``poll(now)`` between steps (``now``: seconds since
    the window opened) and ``stop()`` once the window has closed.  The
    traced stretch is the ``bench.traced`` span; ``started_at`` is its
    start on the window's clock (None while not tracing).
    """

    def __init__(self, trace_dir: pathlib.Path | None, seconds: float):
        self.trace_dir = trace_dir
        self.start_at = max(0.0, seconds - TRACE_TAIL_S)
        self.started_at = None
        self._span = None

    def poll(self, now: float):
        if (self.trace_dir is None or self.started_at is not None
                or now < self.start_at):
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.trace_dir),
                                 profiler_options=opts)
        self._span = span("bench.traced")
        self._span.__enter__()
        self.started_at = now

    def stop(self):
        if self._span is None:
            return
        import jax

        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()


def span(name: str):
    """A host span in the profiler's trace (inert when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Run:
    """One run of one cell, as the run loop of its traffic kind sees it."""

    cell: dict
    config: dict  # the configuration file
    mix: dict  # the traffic file
    limits: dict  # the cell's limits file (number -> {"limit": ...})
    seed: int
    seconds: float
    trace_dir: pathlib.Path | None
    t0: float  # host clock at process start
    devices: list


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""

    e2e: dict  # end-to-end metric -> value
    checks: dict  # compared number -> (value, limit)
    attempted: int
    failed: int
    setup_s: float
    memory_peak_bytes: int | None
    ctx: dict  # what the per-layer readers read
    complete: bool = True  # every answer the check needed came


def judge(checks: dict, complete: bool = True) -> bool:
    """``correct``: every compared number is finite and within its limit."""
    return complete and bool(checks) and all(
        isinstance(v, (int, float)) and math.isfinite(v) and v <= lim
        for v, lim in checks.values())


def memory_peak(device) -> int | None:
    """Peak bytes on ``device``, where the backend says: the buffers the
    process held (``peak_bytes_in_use``) plus the scratch reserved for the
    programs it ran (``peak_bytes_reserved``).  The TPU runtime keeps a
    running program's temporaries in the reserved part, outside the first
    counter, and they are most of a training step's footprint."""
    stats = device.memory_stats() or {}
    log(f"memory_stats {stats}")
    if "peak_bytes_in_use" not in stats:
        return None
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


@contextlib.contextmanager
def no_gc():
    """Python's collector frozen and off for a measured window: the objects
    made in set-up are never walked again, and no collection pauses the
    loop that feeds the device."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        gc.unfreeze()


def log(msg: str):
    """A line of progress on standard error."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)
