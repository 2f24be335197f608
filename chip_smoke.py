#!/usr/bin/env python3
"""Bring-up smoke of flowformer-lm on TPU, through the normal entry points.

    python3 chip_smoke.py             # one chip: serve + train at full width
    python3 chip_smoke.py --chips 4   # four chips: fleet serving + 4-way DP

One chip runs two phases in one process, on the full-width flowformer-lm
configuration (``configs/flowformer_lm.py``) with random weights from
``--seed``:

* serve — an ``Engine`` built as ``launch/serve.py`` builds it (bf16,
  8 slots, ``max_len`` 512) answers 16 requests with prompts of 64–384
  tokens and 64 new tokens each.  Prefill must resolve to ``pallas_fused``
  and decode to ``pallas_decode``.  The served prefill logits at each
  prompt's last position, and every served token, are compared with
  ``lm.forward`` pinned to ``xla_cumsum`` in fp32 at the highest matmul
  precision.
* train — five steps of ``launch.train.train`` at batch 8, sequence 512;
  training must bind ``pallas_fused`` and every loss and gradient norm
  must be finite.

``--chips 4`` runs only what spans chips: a ``FleetEngine`` with one
prefill and three decode workers on disjoint devices whose greedy tokens
must equal a one-chip ``Engine``'s, and the train step on a (4, 1) data
mesh whose losses must match one device's.

The script needs the chip: without a TPU it exits non-zero before doing
any work (there is no CPU fallback).  Any failed check exits non-zero.
The last line of standard output is one JSON object naming the device.
Times printed are set-up and smoke times, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "flowformer-lm"
SLOTS, MAX_LEN = 8, 512
N_REQUESTS, PROMPT_RANGE, NEW_TOKENS = 16, (64, 384), 64
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 5, 8, 512
# Served logits vs the fp32 reference, as a fraction of the reference's
# largest |logit| at that position.  The served path keeps activations and
# weights in bf16 (8 significant bits, 2^-9 relative rounding per op); six
# residual layers and the 512-wide vocabulary projection compound that to a
# few 2^-9, so 2^-4 leaves ~8x headroom, while a wrong boundary state, row or
# position moves logits by O(1) of their scale.
LOGIT_TOL = 2.0 ** -4
# 4-way data parallel vs one device, per step, on the loss and (relative)
# on the gradient norm: each device rounds its partial gradients to bf16
# (2^-9 relative) before they are summed, which moves a norm over 52M
# elements far less than 1e-3, while a lost or doubled shard moves it by
# a quarter.
DP_TOL = 1e-3


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def check(ok: bool, msg: str):
    if not ok:
        raise SmokeFailure(msg)


def require_tpu(chips: int):
    """The devices to run on; exits non-zero unless JAX sees ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU chip found: JAX runs on "
                 f"{devs[0].platform!r} ({devs[0].device_kind}); this smoke "
                 "measures the chip and has no CPU fallback")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU chips, "
                 f"JAX sees {len(devs)}")
    return devs


def make_requests(cfg, seed: int, n: int = N_REQUESTS,
                  prompt_range=PROMPT_RANGE, new_tokens: int = NEW_TOKENS):
    """Seeded greedy requests with prompt lengths spread over the range."""
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    lo, hi = prompt_range
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(lo, hi + 1))
                                        ).astype(np.int32),
                    max_new_tokens=new_tokens)
            for i in range(n)]


def resolved_backends(plan, cfg, slots: int, prompt_len: int) -> dict:
    """The backend each serving op resolves to under the worker's plan."""
    from repro import attention

    ex = attention.resolve(plan)
    d = cfg.dim_head

    def shapes(n):
        return attention.ShapeInfo(b=slots, hq=cfg.n_heads, hkv=cfg.kv_heads,
                                   n=n, m=n, d=d, dv=d)

    return {"prefill_packed": ex.backend("prefill_packed",
                                         shapes(prompt_len)).name,
            "decode": ex.backend("decode", shapes(1)).name}


def served_prefill_logits(worker, prompts):
    """Last-prompt-position logits of the worker's packed prefill.

    Packs ``prompts`` into admission batches exactly as ``Worker.prefill``
    does (``slots`` at a time, right-padded to the length bucket) and runs
    the same ``lm.prefill`` under the worker's plan and activation dtype.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm
    from repro.serving.worker import _bucket_len

    @jax.jit
    def prefill(params, toks, lens):
        logits, _ = lm.prefill(params, toks, worker.cfg, toks.shape[1],
                               lengths=lens, plan=worker.plan,
                               dtype=worker.dtype)
        return logits[:, 0]

    out = []
    for i in range(0, len(prompts), worker.slots):
        batch = prompts[i:i + worker.slots]
        lens = [len(p) for p in batch]
        toks = np.zeros((len(batch), _bucket_len(max(lens), worker.max_len)),
                        np.int32)
        for row, p in enumerate(batch):
            toks[row, :len(p)] = p
        out.extend(np.asarray(prefill(worker.params, jnp.asarray(toks),
                                      jnp.asarray(lens, jnp.int32))))
    return out


def reference_logits(params, cfg, reqs, batch: int = 8):
    """fp32 teacher-forced logits of prompt + generated tokens per request,
    from ``lm.forward`` pinned to the ``xla_cumsum`` reference strategy at
    the highest matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm

    ref_cfg = dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention,
                                           backend="xla_cumsum"))
    seqs = [np.concatenate([r.prompt, np.asarray(r.generated[:-1], np.int32)])
            for r in reqs]
    n = max(len(s) for s in seqs)
    out = []
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(functools.partial(lm.forward, cfg=ref_cfg,
                                        dtype=jnp.float32))
        for i in range(0, len(seqs), batch):
            chunk = seqs[i:i + batch]
            toks = np.zeros((len(chunk), n), np.int32)
            for row, s in enumerate(chunk):
                toks[row, :len(s)] = s
            logits, _ = fwd(params, jnp.asarray(toks))
            logits = np.asarray(logits, np.float32)
            out.extend(logits[row, :len(s)] for row, s in enumerate(chunk))
    return out


def serve_phase(cfg, *, seed: int, n_requests: int = N_REQUESTS,
                prompt_range=PROMPT_RANGE, new_tokens: int = NEW_TOKENS,
                slots: int = SLOTS, max_len: int = MAX_LEN) -> dict:
    """Serve seeded requests through ``Engine`` and check them against the
    fp32 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.layers.attention import plan_of
    from repro.models import lm
    from repro.serving.engine import Engine

    t0 = time.perf_counter()
    params = lm.init(jax.random.PRNGKey(seed), cfg)
    # built as launch/serve.py builds it: one plan for the serving lifetime
    engine = Engine(params, cfg, slots=slots, max_len=max_len,
                    plan=plan_of(cfg, packed=True), dtype=jnp.bfloat16)
    worker = engine.worker
    backends = resolved_backends(worker.plan, cfg, slots, prompt_range[1])
    for op, name in backends.items():
        print(f"[serve] {op} -> {name}")
    check(backends == {"prefill_packed": "pallas_fused",
                       "decode": "pallas_decode"},
          f"serving resolved {backends}, expected prefill_packed -> "
          "pallas_fused and decode -> pallas_decode")

    reqs = make_requests(cfg, seed, n_requests, prompt_range, new_tokens)
    for r in reqs:
        engine.submit(r)
    t1 = time.perf_counter()
    finished = engine.run()
    t2 = time.perf_counter()
    check(len(finished) == len(reqs)
          and all(len(r.generated) == new_tokens for r in reqs),
          "not every request finished with its token budget")
    tokens = sum(len(r.generated) for r in reqs)
    print(f"[serve] {len(reqs)} requests (prompts "
          f"{min(len(r.prompt) for r in reqs)}-"
          f"{max(len(r.prompt) for r in reqs)} tokens), {tokens} tokens "
          f"generated; set-up {t1 - t0:.1f}s, serving incl. compiles "
          f"{t2 - t1:.1f}s (smoke times, not a benchmark)")

    served = served_prefill_logits(worker, [r.prompt for r in reqs])
    ref = reference_logits(params, cfg, reqs)
    prefill_ratio = decode_ratio = 0.0
    for r, s, rl in zip(reqs, served, ref):
        last = len(r.prompt) - 1
        check(bool(np.isfinite(s).all()), f"request {r.uid}: non-finite "
              "served logits")
        gap = float(np.abs(s - rl[last]).max())
        prefill_ratio = max(prefill_ratio,
                            gap / (LOGIT_TOL * float(np.abs(rl[last]).max())))
        # every served token (the prefill sample, then one per decode
        # step) must be a near-argmax of the reference at its position
        rows = rl[last:last + new_tokens]
        picked = rows[np.arange(new_tokens), np.asarray(r.generated)]
        deficit = rows.max(axis=1) - picked
        decode_ratio = max(decode_ratio, float(
            (deficit / (LOGIT_TOL * np.abs(rows).max(axis=1))).max()))
    print(f"[serve] prefill logit gap vs fp32 reference: worst "
          f"{prefill_ratio:.4f} of the tolerance ({LOGIT_TOL} x max|logit|)")
    print(f"[serve] served-token logit deficit vs fp32 reference argmax: "
          f"worst {decode_ratio:.4f} of the same tolerance")
    check(prefill_ratio <= 1.0, "served prefill logits exceed the tolerance")
    check(decode_ratio <= 1.0, "a served token is not a near-argmax of the "
          "reference")
    return {"backends": backends, "prefill_gap_of_tol": prefill_ratio,
            "token_deficit_of_tol": decode_ratio}


def train_phase(cfg, *, seed: int, steps: int = TRAIN_STEPS,
                batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ,
                mesh=None) -> dict:
    """Take ``steps`` steps of ``launch.train.train``; check they are sane."""
    import math

    from repro.launch.train import train

    out = train(cfg, steps=steps, batch=batch, seq=seq, seed=seed,
                mesh=mesh, log_every=1)
    print(f"[train] losses {out['history']} grad norms {out['grad_norms']}; "
          f"{out['wall_s']:.1f}s incl. compiles (smoke time, not a "
          "benchmark)")
    check(out["attention_backend"] == "pallas_fused",
          f"training bound {out['attention_backend']}, expected pallas_fused")
    check(len(out["history"]) == steps
          and all(math.isfinite(x) for x in out["history"] + out["grad_norms"]),
          "a train loss or grad norm is not finite")
    return out


def fleet_phase(cfg, *, seed: int, devices, n_requests: int = N_REQUESTS,
                prompt_range=PROMPT_RANGE, new_tokens: int = NEW_TOKENS,
                slots: int = SLOTS, max_len: int = MAX_LEN) -> dict:
    """FleetEngine prefill:1,decode:3 on disjoint devices vs one Engine."""
    import jax
    import jax.numpy as jnp

    from repro.layers.attention import plan_of
    from repro.models import lm
    from repro.serving.engine import Engine
    from repro.serving.fleet import FleetEngine

    params = lm.init(jax.random.PRNGKey(seed), cfg)
    plan = plan_of(cfg, packed=True)
    # fp32 activations on both sides: the parity is exact only where both
    # run the same arithmetic, and fp32 keeps a reassociation from flipping
    # a near-tied greedy argmax
    kw = dict(slots=slots, max_len=max_len, plan=plan, dtype=jnp.float32)
    fleet = FleetEngine(params, cfg, prefill=1, decode=3, devices=devices,
                        **kw)
    pdevs = [w.device.id for w in fleet.prefills]
    ddevs = [w.device.id for w in fleet.workers]
    print(f"[fleet] prefill worker devices {pdevs}, decode worker devices "
          f"{ddevs}")
    check(len(set(pdevs + ddevs)) == len(pdevs) + len(ddevs),
          "fleet workers share a device")

    def serve(engine):
        reqs = make_requests(cfg, seed, n_requests, prompt_range, new_tokens)
        for r in reqs:
            engine.submit(r)
        engine.run()
        return {r.uid: list(r.generated) for r in reqs}

    t0 = time.perf_counter()
    fleet_tokens = serve(fleet)
    t1 = time.perf_counter()
    engine_tokens = serve(Engine(params, cfg, **kw))
    t2 = time.perf_counter()
    same = sum(fleet_tokens[u] == engine_tokens[u] for u in fleet_tokens)
    print(f"[fleet] loads {fleet.loads()}, {fleet.migrations} migrations; "
          f"{same}/{len(fleet_tokens)} requests token-identical to the "
          f"one-chip Engine; fleet {t1 - t0:.1f}s, engine {t2 - t1:.1f}s "
          "incl. compiles (smoke times, not a benchmark)")
    check(same == len(fleet_tokens),
          "fleet greedy tokens differ from the one-chip Engine")
    return {"prefill_devices": pdevs, "decode_devices": ddevs,
            "identical_requests": same}


def dp_train_phase(cfg, *, seed: int, devices, steps: int = 2,
                   batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ) -> dict:
    """The train step on a (4, 1) data mesh vs the same steps on one device."""
    from repro.launch.mesh import make_mesh

    one = train_phase(cfg, seed=seed, steps=steps, batch=batch, seq=seq,
                      mesh=make_mesh((1, 1), ("data", "model"),
                                     devices=devices[:1]))
    four = train_phase(cfg, seed=seed, steps=steps, batch=batch, seq=seq,
                       mesh=make_mesh((4, 1), ("data", "model"),
                                      devices=devices[:4]))
    dloss = max(abs(a - b) for a, b in zip(one["history"], four["history"]))
    dgnorm = max(abs(a - b) / abs(a)
                 for a, b in zip(one["grad_norms"], four["grad_norms"]))
    print(f"[dp] 4-way data parallel vs one device over {steps} steps: "
          f"max |loss diff| {dloss:.6f}, max relative grad-norm diff "
          f"{dgnorm:.6f} (tolerance {DP_TOL})")
    check(dloss <= DP_TOL and dgnorm <= DP_TOL,
          "4-way data-parallel training diverges from one device")
    return {"max_loss_diff": dloss, "max_gnorm_rel_diff": dgnorm}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve + train on one chip; 4: only the fleet "
                    "and data-parallel paths that span four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)

    from repro.configs import get_config
    from repro.launch.compile_cache import configure_compile_cache
    from repro.utils import device_summary

    print(f"[smoke] {device_summary()}; compile cache "
          f"{configure_compile_cache()}")
    cfg = get_config(ARCH)
    try:
        if args.chips == 4:
            fleet_phase(cfg, seed=args.seed, devices=devs[:4])
            dp_train_phase(cfg, seed=args.seed, devices=devs)
        else:
            serve_phase(cfg, seed=args.seed)
            train_phase(cfg, seed=args.seed)
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
