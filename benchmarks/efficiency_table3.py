"""Table 3 — efficiency (steps/s) vs sequence length, training + inference.

Flow/linear attention must stay ~flat in sequence length while softmax
degrades quadratically — the paper's core scaling claim, measured here on
CPU with a small model (relative scaling is hardware-independent).

Flow rows can sweep execution strategies by registry name:

    python -m benchmarks.efficiency_table3 --backends auto,fused_causal,xla_cumsum
    python -m benchmarks.efficiency_table3 --backends all

Backends that reject a (shape, config) report ``n/a`` for that cell instead
of aborting the sweep.  The context-parallel backends (``cp_*``) need more
than one device: run under a forced multi-device host

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m benchmarks.efficiency_table3 --backends cp_causal,cp_nc

and their rows bench under a sharded ExecutionPlan (sequence axis over all
devices): ``cp_causal`` through the full LM, ``cp_nc`` through the sharded
non-causal attention op (the LM sweep is causal and the non-causal glue
rightly rejects it).  On a 1-device host they are skipped gracefully (rows
omitted with the reason printed), never an error.
"""
from __future__ import annotations

import dataclasses
import time

import jax

from benchmarks.common import print_table, save_table, with_kind
from repro.attention import ShardSpec
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.layers.attention import plan_of
from repro.models import lm


def _bench(fn, *args, iters: int = 3) -> float:
    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return iters / (time.time() - t0)


def _shard_plan_for(cfg, backend: str, *, causal: bool = True):
    """(plan, skip_reason) for a ``cp_*`` row: a sharded ExecutionPlan over
    every host device, or the reason the row must be skipped (1-device
    host).  The sweep keeps going either way."""
    ndev = len(jax.devices())
    if ndev < 2:
        return None, (
            f"{backend} needs a multi-device host; run with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            f"(found {ndev} device)"
        )
    mesh = make_mesh((ndev,), ("seq",))
    return plan_of(cfg, causal=causal,
                   shard=ShardSpec(axis="seq", mesh=mesh)), None


def _bench_nc_op(cfg, plan, lens: tuple) -> dict:
    """cp_nc row: the LM sweep is causal and the non-causal glue rightly
    rejects it, so bench the sharded attention *op* itself (forward and
    grad steps/s at the same lengths) — the psum glue still gets a real,
    gateable number every night."""
    from repro import attention

    d = cfg.d_model // cfg.n_heads
    ex = attention.resolve(plan)
    row = {}
    for n in lens:
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (2, cfg.n_heads, n, d))
        k = jax.random.normal(ks[1], (2, cfg.n_heads, n, d))
        v = jax.random.normal(ks[2], (2, cfg.n_heads, n, d))
        fwd = jax.jit(ex.forward)
        grad = jax.jit(jax.grad(
            lambda q, k, v: (ex.forward(q, k, v) ** 2).sum(),
            argnums=(0, 1, 2)))
        for col, fn in ((f"infer_{n}", fwd), (f"train_{n}", grad)):
            try:
                row[col] = round(_bench(fn, q, k, v), 2)
            except Exception as err:
                print(f"  [cp_nc @ {col}] n/a: {err}")
                row[col] = "n/a"
    return row


def run(*, quick: bool = True, backends: tuple = ("auto",),
        lens: tuple | None = None, save_as: str = "efficiency_table3") -> dict:
    lens = lens or ((256, 512, 1024) if quick else (1024, 2048, 3072, 4096))
    base = get_config("flowformer_lm")
    base = dataclasses.replace(base, n_layers=2, d_model=128, n_heads=4,
                               n_kv_heads=4, d_ff=256, vocab_size=1024,
                               remat=False)
    variants = [("flow", b) for b in backends]
    # a cp-only sweep (the workflow's forced-8-device leg) omits the
    # softmax/linear baselines: its rows must merge with the main sweep's
    # at the regression gate, and duplicate row names abort the merge
    if not all(b and b.startswith("cp_") for b in backends):
        variants += [("softmax", None), ("linear", None), ("hybrid_ssd", None)]
    rows = {}
    for kind, backend in variants:
        if kind == "hybrid_ssd":
            # mamba2-style (ssd, attn) hybrid stack: the training column
            # exercises the ssd_chunk custom VJP end-to-end
            from repro.config import SSDConfig

            cfg = dataclasses.replace(
                with_kind(base, "flow"), pattern=("ssd", "attn"),
                ssd=SSDConfig(d_state=32, expand=2, head_dim=32,
                              conv_width=4, chunk_size=32))
            name = "hybrid_ssd"
        else:
            over = {"backend": backend} if backend else {}
            cfg = with_kind(base, kind, **over)
            name = kind if backend in (None, "auto") else f"flow[{backend}]"
        plan = None
        if backend and backend.startswith("cp_"):
            nc_only = backend == "cp_nc"
            plan, skip = _shard_plan_for(cfg, backend, causal=not nc_only)
            if skip:
                # graceful: row omitted (so a separate multi-device sweep
                # can merge its own cp rows at the gate), reason printed
                print(f"  [{name}] skipped: {skip}")
                continue
            if nc_only:  # no causal LM exists for the non-causal glue
                print(f"  [{name}] benching the sharded non-causal "
                      "attention op (the LM sweep is causal)")
                rows[name] = _bench_nc_op(cfg, plan, lens)
                continue
        params = lm.init(jax.random.PRNGKey(0), cfg)
        row = {}
        for n in lens:
            toks = jax.random.randint(jax.random.PRNGKey(1), (2, n), 0,
                                      cfg.vocab_size)
            batch = {"inputs": toks, "targets": toks}

            fwd = jax.jit(
                lambda p, b: lm.forward(p, b["inputs"], cfg, plan=plan)[0])
            step = jax.jit(
                jax.grad(lambda p, b: lm.loss_fn(p, b, cfg, plan=plan)[0]))
            # per-op try: a backend can reject a (shape, config) cell — a
            # working infer number should survive a failing train bench
            for col, fn in ((f"infer_{n}", fwd), (f"train_{n}", step)):
                try:
                    row[col] = round(_bench(fn, params, batch), 2)
                except Exception as err:  # rejected shapes/config — keep sweeping
                    # a ResolutionError names EVERY candidate's reason; show
                    # them all so CI logs say why each backend was skipped
                    rejections = getattr(err, "rejections", ())
                    if rejections:
                        print(f"  [{name} @ {col}] n/a:")
                        for bname, why in rejections:
                            print(f"    {bname}: {why}")
                    else:
                        lines = str(err).strip().splitlines()
                        why = lines[0] if lines else type(err).__name__
                        print(f"  [{name} @ {col}] n/a: {why}")
                    row[col] = "n/a"
        rows[name] = row
    cols = [f"{m}_{n}" for m in ("infer", "train") for n in lens]
    print_table("Table 3 (efficiency): steps/s by sequence length", rows, cols)
    # scaling factor: throughput ratio first->last length (1.0 = perfectly linear)
    for name, row in rows.items():
        vals = [row[f"{m}_{n}"] for m in ("infer", "train") for n in lens]
        if any(isinstance(x, str) for x in vals):
            continue
        inf = row[f"infer_{lens[0]}"] / max(row[f"infer_{lens[-1]}"], 1e-9)
        trn = row[f"train_{lens[0]}"] / max(row[f"train_{lens[-1]}"], 1e-9)
        ideal = lens[-1] / lens[0]
        rows[name]["slowdown_vs_linear_ideal"] = round(
            max(inf, trn) / ideal, 2
        )
    save_table(save_as, rows)
    return rows


def _parse_backends(arg: str) -> tuple:
    if arg == "all":
        from repro.attention import get_backend, list_backends

        # only forward-providing strategies: a pinned decode-only backend
        # (pallas_decode) would silently fall back to auto for forward and
        # publish a mislabeled row
        return ("auto",) + tuple(
            n for n in list_backends() if "forward" in get_backend(n).provides
        )
    return tuple(s for s in arg.split(",") if s)


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    backends = ("auto",)
    lens = None
    save_as = "efficiency_table3"
    argv = sys.argv[1:]
    if "--backends" in argv:
        i = argv.index("--backends") + 1
        if i >= len(argv) or argv[i].startswith("--"):
            sys.exit("usage: --backends <name>[,<name>...] | all")
        backends = _parse_backends(argv[i])
    if "--lens" in argv:  # e.g. --lens 256,512 (the CI regression gate)
        i = argv.index("--lens") + 1
        if i >= len(argv) or argv[i].startswith("--"):
            sys.exit("usage: --lens <n>[,<n>...]")
        lens = tuple(int(s) for s in argv[i].split(",") if s)
    if "--save-as" in argv:  # separate sweeps (e.g. the multi-device cp
        i = argv.index("--save-as") + 1  # leg) merge at the regression gate
        if i >= len(argv) or argv[i].startswith("--"):
            sys.exit("usage: --save-as <table-name>")
        save_as = argv[i]
    run(quick="--full" not in argv, backends=backends, lens=lens,
        save_as=save_as)
