"""Record what the scope split is tested on, and show what the scopes cost.

    python3 bench/tests/record_scopes.py fixture <dir>
        On one TPU: two train steps of the ``flowformer-lm-xla``
        configuration at 2 x 512, traced inside a ``bench.window`` span,
        written as ``<dir>/train_scopes.xplane.pb`` beside the compiled
        step's text, ``<dir>/train_scopes.hlo.gz``.

    JAX_PLATFORMS=cpu python3 bench/tests/record_scopes.py hlo <batch> <seq> [--rename]
        No chip needed: the cell's train step at ``batch`` x ``seq``,
        compiled for a described v5e, printed as optimized HLO with every
        ``metadata={...}`` and the debug tables it points into removed;
        with ``--rename``, each instruction and computation is also named
        by its first appearance.  Run in two checkouts and compare: named
        scopes change metadata, and the numbers XLA gives a few
        instructions, only.
"""
import gzip
import os
import pathlib
import re
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, traffic  # noqa: E402

CONFIG = ROOT / "bench" / "configs" / "flowformer-lm-xla.json"


def _run(batch: int, seq: int, devices) -> harness.Run:
    mix = dict(traffic.load_mix("train-8k"), batch=batch, seq=seq,
               distinct_batches=2)
    return harness.Run(cell={}, config=harness.load_json(CONFIG), mix=mix,
                       limits={}, seed=1, seconds=0.0, trace_dir=None,
                       t0=time.perf_counter(), devices=devices)


def fixture(out: pathlib.Path):
    import jax

    from bench.drivers import train

    devices = harness.require_chips(1)
    _, jit_step, state, state_shape, batches = train.build(
        _run(2, 512, devices))
    compiled = jit_step.lower(state_shape, batches[0]).compile()
    for b in batches:  # warm
        state, _ = compiled(state, b)
    jax.block_until_ready(state)
    tmp = out / "trace"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with harness.span("bench.window"):
        # the device's clock may run a little ahead of the span's: a step
        # that starts before the span is not a step of the window
        time.sleep(0.05)
        for b in batches:
            state, _ = compiled(state, b)
        jax.block_until_ready(state)
    jax.profiler.stop_trace()
    shutil.copy(next(tmp.rglob("*.xplane.pb")),
                out / "train_scopes.xplane.pb")
    shutil.rmtree(tmp)
    (out / "train_scopes.hlo.gz").write_bytes(
        gzip.compress(compiled.as_text().encode(), mtime=0))


def hlo(batch: int, seq: int, rename: bool = False) -> str:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    r = _run(batch, seq, [topo.devices[0]])
    jit_step, state_shape = _step_on(r, Mesh([[topo.devices[0]]],
                                             ("data", "model")))
    sds = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    text = jit_step.lower(state_shape, {"inputs": sds,
                                        "targets": sds}).compile().as_text()
    # the debug tables the metadata's stack_frame_id points into
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r".*?\n\n", "\n", text, flags=re.S)
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    if not rename:
        return text
    # XLA names and numbers some instructions from the lowered module's
    # locations, which carry the scopes: name each instruction and
    # computation by its first appearance
    seen: dict[str, str] = {}

    def name(m):
        return seen.setdefault(m.group(0), f"{m.group(1)}#{len(seen)}")

    return re.sub(r"(%?)\b([A-Za-z_][\w\-]*(?:\.[\w\-]+)+|(?<=%)[\w\-]+)",
                  name, text)


def _step_on(r: harness.Run, mesh):
    """The training run loop's step (``drivers/train.build``) on
    ``mesh``, with no state placed: a described chip holds no arrays."""
    from repro.config import ShapeSpec
    from repro.launch.steps import RunPlan, build_train_step

    cfg = harness.model_config(r.config["model"])
    opt = r.mix["optimizer"]
    shape = ShapeSpec("bench", r.mix["seq"], r.mix["batch"], "train")
    jit_step, state_shape, _, _ = build_train_step(
        cfg, shape, mesh, RunPlan.choose(cfg, shape, mesh),
        train_overrides={"total_steps": opt["total_steps"],
                         "warmup": opt["warmup"], "peak_lr": opt["peak_lr"],
                         "grad_clip": opt["grad_clip"],
                         "weight_decay": opt["weight_decay"],
                         "fused_value_grad": True})
    return jit_step, state_shape


if __name__ == "__main__":
    if sys.argv[1] == "fixture":
        out = pathlib.Path(sys.argv[2])
        out.mkdir(parents=True, exist_ok=True)
        fixture(out)
    else:
        sys.stdout.write(hlo(int(sys.argv[2]), int(sys.argv[3]),
                             "--rename" in sys.argv[4:]))
