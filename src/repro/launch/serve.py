"""Serve driver: continuous batching with constant-memory flow states.

    python -m repro.launch.serve --arch flowformer-lm --smoke \
        --requests 16 --max-new 32

Softmax-mode baselines can serve from the paged KV pool instead of dense
``max_len`` caches:

    python -m repro.launch.serve --arch flowformer-lm --smoke \
        --attn softmax --paged --page-size 64

Speculative decoding (greedy output is token-for-token identical to plain
decode; see docs/serving.md):

    python -m repro.launch.serve --arch flowformer-lm --smoke \
        --draft self --speculate-k 4

Disaggregated fleet serving (prefill/decode worker groups with bundle
hand-off, rebalancing and failover; see docs/serving.md):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m repro.launch.serve --arch flowformer-lm --smoke \
        --requests 16 --fleet prefill:1,decode:3
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import configure_compile_cache
from repro.layers.attention import plan_of
from repro.models import lm
from repro.serving.engine import Engine, PagedSpec, Request
from repro.serving.fleet import FleetEngine
from repro.utils import device_summary


def _parse_fleet(spec: str) -> tuple[int, int]:
    """``prefill:N,decode:M`` -> (N, M), with loud errors."""
    sizes = {"prefill": 1, "decode": 2}
    for part in spec.split(","):
        name, _, num = part.partition(":")
        if name not in sizes or not num.isdigit() or int(num) < 1:
            raise SystemExit(
                f"--fleet expects 'prefill:N,decode:M' (got {spec!r})")
        sizes[name] = int(num)
    return sizes["prefill"], sizes["decode"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="flowformer-lm")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--attn", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy); "
                    "sampling is one batched draw per step either way")
    ap.add_argument("--paged", action="store_true",
                    help="serve softmax KV caches from the paged pool "
                    "instead of dense max_len caches")
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="paged pool size (0 = dense-equivalent worst case)")
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "fp32"],
                    help="serving activation dtype")
    ap.add_argument("--state-dtype", default=None,
                    choices=["bf16", "fp32", "int8", "fp8"],
                    help="state-pool storage dtype, independent of the "
                    "activation dtype; int8/fp8 store quantized pools "
                    "(low-bit payload + fp32 per-(slot, head) scales) and "
                    "route decode through the quant-capable kernels")
    ap.add_argument("--draft", default=None, choices=["self", "tiny"],
                    help="speculative decoding draft source: 'self' "
                    "(self-speculation over the target's own caches) or "
                    "'tiny' (a smoke-sized flowformer_lm drafter)")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="drafted tokens per verify window (0 = plain "
                    "decode; implies --draft self when unset)")
    ap.add_argument("--fleet", default=None, metavar="prefill:N,decode:M",
                    help="serve through FleetEngine: disaggregated "
                    "prefill/decode worker groups with StateBundle "
                    "hand-off (run with XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8 to place "
                    "the groups on disjoint simulated devices)")
    args = ap.parse_args()
    if args.fleet and args.speculate_k:
        raise SystemExit("--fleet serves plain decode only (speculative "
                         "windows stay a single-engine feature)")
    configure_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.attn:
        cfg = dataclasses.replace(
            cfg, attention=dataclasses.replace(cfg.attention, kind=args.attn)
        )
    params = lm.init(jax.random.PRNGKey(0), cfg)
    paged = (PagedSpec(page_size=args.page_size, num_pages=args.num_pages)
             if args.paged else None)
    # one ExecutionPlan for the whole serving lifetime: the paged-cache
    # option, packed admission and the speculative window ride it instead
    # of per-call kwargs
    plan = plan_of(cfg, paged=paged, packed=True,
                   speculate_k=args.speculate_k,
                   state_dtype=args.state_dtype)
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[args.dtype]
    max_len = args.prompt_len + args.max_new + 8
    if args.fleet:
        n_pre, n_dec = _parse_fleet(args.fleet)
        engine = FleetEngine(params, cfg, prefill=n_pre, decode=n_dec,
                             slots=args.slots, max_len=max_len, plan=plan,
                             dtype=dtype, paged=paged,
                             state_dtype=args.state_dtype)
        worker0 = engine.workers[0]
        print(f"[serve] fleet: {n_pre} prefill + {n_dec} decode workers, "
              f"{len(jax.devices())} host devices "
              f"(decode group: {[d.id for d in engine.dmesh.devices.flat]})")
    else:
        engine = Engine(params, cfg, slots=args.slots, max_len=max_len,
                        plan=plan, dtype=dtype, draft=args.draft,
                        speculate_k=args.speculate_k)
        worker0 = engine.worker
    print(f"[serve] attention plan: {worker0.plan.describe()}, "
          f"{device_summary()}")
    print(f"[serve] dtypes: activations={args.dtype} "
          f"state_pools={args.state_dtype or args.dtype}")
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        r = Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, args.prompt_len
                                        ).astype(np.int32),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature)
        reqs.append(r)
        engine.submit(r)

    t0 = time.time()
    steps = 0
    while any(not r.done for r in reqs):
        if engine.step() == 0 and not engine.queue:
            break
        steps += 1
    dt = time.time() - t0
    total_tokens = sum(len(r.generated) for r in reqs)
    print(f"[serve] {args.requests} requests, {total_tokens} tokens in "
          f"{dt:.2f}s ({total_tokens/max(dt,1e-9):.1f} tok/s, {steps} steps)")
    if args.fleet:
        kb_moved = engine.bytes_migrated / 1024.0
        kb_req = np.mean(list(engine.kb_by_uid.values()) or [0.0])
        print(f"[serve] fleet: loads={engine.loads()}, "
              f"{engine.migrations} migrations ({kb_moved:.1f} KiB moved), "
              f"{engine.recoveries} recoveries, "
              f"~{kb_req:.1f} KiB of state moved per request")
    elif engine.draft is not None:
        print(f"[serve] speculative: k={engine.speculate_k}, "
              f"~{total_tokens/max(steps,1):.2f} tokens committed per step")
    alloc = worker0.allocator
    if alloc is not None:
        print(f"[serve] paged KV: page_size={alloc.page_size} "
              f"pool={alloc.num_pages} pages, {alloc.free_pages} free after "
              "drain")
    print(f"[serve] sample generation: {reqs[0].generated[:16]}")


if __name__ == "__main__":
    main()
