"""Serving throughput: tokens/s vs slots x context length, flow vs softmax.

Drives the real ``serving.Engine`` (scheduler/worker split, packed prefill,
fused batched sampling) end-to-end on a small model and measures steady-
state decode throughput per (variant, slots, context) cell:

  * ``flow``   — O(d^2) recurrent states; the decode cost must stay ~flat
    in context length (the paper's serving claim).
  * ``softmax`` — dense max_len KV caches (the unfair-at-long-context
    baseline Tab. 3 used to compare against).
  * ``paged``  — softmax served from the paged KV pool
    (``serving/paged.py``), the PagedAttention-style fair baseline.
  * ``hybrid_rg`` — RecurrentGemma-style (rglru, rglru, attn) pattern and
  * ``hybrid_m2`` — Mamba2-style pure-ssd pattern: hybrid stacks riding
    the SequenceMixer registry through the SAME engine (packed admission
    included); their decode must stay as context-flat as flow's.

  * ``flow_q8`` / ``paged_q8`` / ``hybrid_rg_q8`` — the same engines with
    int8-quantized state pools (``state_dtype="int8"``): low-bit payload
    plus fp32 per-(slot, head) scales, decode through the quant-capable
    kernel variants.

  * ``fleet_flow`` / ``fleet_paged`` — the disaggregated ``FleetEngine``
    (1 prefill + 2 decode workers, ``serving/fleet.py``) at 4x/8x the
    longest context.  Beyond tokens/s these rows measure the migration
    path itself: ``kb_migrated`` (mean StateBundle KiB per request
    moved) and ``migs_s`` (mid-stream migrations per second, full
    export->install round trips).  The printed comparison is the
    paper's portability claim: a flow request's bundle is O(d^2)
    constant, >=10x smaller than the equivalent paged-KV transfer at
    these context lengths.

Cells are named ``serve_<ctx>`` so ``regression_gate.py`` sweeps them with
the same tolerance machinery as the training/inference cells, and every
row gets a ``trend_vs_ctx`` column — throughput ratio shortest/longest
context (1.0 = perfectly flat), printed as the per-length trend summary.
Every row also reports its pool footprint: ``kb_slot`` (state KiB per
slot at the longest context) and ``tps_per_gb`` (tokens/s per GiB of
state pool — slots x throughput per HBM byte, the capacity-density
figure the quantized rows triple).

    python -m benchmarks.serving_bench
    python -m benchmarks.serving_bench --slots 2,4 --ctxs 64,128 --steps 24
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from benchmarks.common import print_table, save_table, with_kind
from repro.configs import get_config
from repro.layers.attention import plan_of
from repro.models import lm
from repro.serving.engine import Engine, PagedSpec, Request
from repro.serving.fleet import FleetEngine


def pool_slot_kb(caches, slots: int) -> float:
    """HBM KiB of serving state per slot, summed over every layer pool.

    Quantized pools count payload + scales (the scales are the per-(slot,
    head) fp32 columns, a rounding error next to the panel/KV payload).
    """
    from repro.serving.quant import pool_bytes

    return pool_bytes(caches) / slots / 1024.0


def _bench_cell(params, cfg, *, slots: int, ctx: int, steps: int,
                paged: PagedSpec | None, speculate_k: int = 0,
                state_dtype: str | None = None):
    """Steady-state decode tokens/s with every slot live at context ctx.

    Counts *committed* tokens (identical to steps x slots for plain
    decode; each slot's accepted prefix + bonus token under speculation),
    so speculative rows report accepted tokens/s.  Returns (tokens/s,
    mean committed tokens per slot-step, state-pool KiB per slot) — the
    second is ``accept_len``, 1.0 for plain decode and up to
    ``speculate_k + 1`` for speculation."""
    # the serving ExecutionPlan, built once per engine like launch/serve.py
    plan = plan_of(cfg, paged=paged, packed=True, speculate_k=speculate_k,
                   state_dtype=state_dtype)
    budget = (steps + 2) * (speculate_k + 1)
    engine = Engine(params, cfg, slots=slots, max_len=ctx + budget + 8,
                    plan=plan, speculate_k=speculate_k)
    kb_slot = pool_slot_kb(engine.worker.caches, slots)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(slots):
        reqs.append(Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab_size, ctx).astype(np.int32),
            max_new_tokens=budget,
        ))
        engine.submit(reqs[-1])
    engine.step()  # admission (prefill+install) + decode compile/warm
    count0 = sum(len(r.generated) for r in reqs)
    t0 = time.time()
    for _ in range(steps):
        engine.step()
    dt = time.time() - t0
    tokens = sum(len(r.generated) for r in reqs) - count0
    return tokens / dt, tokens / (steps * slots), kb_slot


def _bench_fleet_cell(params, cfg, *, slots: int, ctx: int, steps: int,
                      paged: PagedSpec | None):
    """Fleet decode tokens/s plus the migration-path figures.

    Fills a 1-prefill + 2-decode fleet (``2 x (slots - 1)`` live
    requests at context ``ctx`` — one slot per worker stays free so the
    post-loop migrations have somewhere to land), times ``steps`` fleet
    iterations, then migrates every live request once between the
    decode workers and times the full export->install round trips.
    Returns (tokens/s, mean KiB per migrated bundle, migrations/s)."""
    plan = plan_of(cfg, paged=paged, packed=True)
    budget = steps + 8  # headroom: requests must outlive the timed loop
    fleet = FleetEngine(params, cfg, prefill=1, decode=2, slots=slots,
                        max_len=ctx + budget + 8, plan=plan)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(2 * (slots - 1)):
        reqs.append(Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab_size, ctx).astype(np.int32),
            max_new_tokens=budget,
        ))
        fleet.submit(reqs[-1])
    fleet.step()  # admission (packed prefill + bundle install) + warm
    count0 = sum(len(r.generated) for r in reqs)
    t0 = time.time()
    for _ in range(steps):
        fleet.step()
    dt = time.time() - t0
    tokens = sum(len(r.generated) for r in reqs) - count0
    # migration microbench: bounce every live request to the other worker
    live = [r.uid for r in reqs if fleet.locate(r.uid) is not None]
    assert live, "migration bench needs live requests after the timed loop"
    before = fleet.bytes_migrated
    t0 = time.time()
    for uid in live:
        fleet.migrate(uid)
    mig_dt = time.time() - t0
    kb = (fleet.bytes_migrated - before) / max(len(live), 1) / 1024.0
    return tokens / dt, kb, len(live) / max(mig_dt, 1e-9)


def run(*, slots: tuple = (2, 4), ctxs: tuple = (64, 128),
        steps: int = 24) -> dict:
    from repro.config import RGLRUConfig, SSDConfig

    base = get_config("flowformer_lm")
    base = dataclasses.replace(base, n_layers=2, d_model=128, n_heads=4,
                               n_kv_heads=4, d_ff=256, vocab_size=1024,
                               remat=False)
    page = PagedSpec(page_size=32)
    hybrid_rg = dataclasses.replace(  # recurrentgemma-style 2:1 pattern
        with_kind(base, "flow"), n_layers=3,
        pattern=("rglru", "rglru", "attn"),
        rglru=RGLRUConfig(conv_width=4, lru_width=0, n_blocks=4),
    )
    hybrid_m2 = dataclasses.replace(  # mamba2-style attention-free stack
        with_kind(base, "flow"), pattern=("ssd",),
        ssd=SSDConfig(d_state=32, expand=2, head_dim=32, conv_width=4,
                      chunk_size=32),
    )
    variants = [("flow", with_kind(base, "flow"), None, 0, None),
                ("softmax", with_kind(base, "softmax"), None, 0, None),
                ("paged", with_kind(base, "softmax"), page, 0, None),
                ("hybrid_rg", hybrid_rg, None, 0, None),
                ("hybrid_m2", hybrid_m2, None, 0, None),
                # quantized state pools: int8 payload + fp32 per-(slot,
                # head) scales — same engines, ~1/4 the pool HBM; the
                # density column (tokens/s per pool GiB) is the serving
                # capacity claim these rows exist for
                ("flow_q8", with_kind(base, "flow"), None, 0, "int8"),
                ("paged_q8", with_kind(base, "softmax"), page, 0, "int8"),
                ("hybrid_rg_q8", hybrid_rg, None, 0, "int8"),
                # speculative variants: self-speculation drafts are the
                # target's own greedy continuation, so every window
                # accepts all k drafts — these rows measure the pure
                # dispatch/sampling amortization win of committing k+1
                # tokens per engine iteration (accepted tokens/s)
                ("spec_flow", with_kind(base, "flow"), None, 4, None),
                ("spec_hybrid_rg", hybrid_rg, None, 4, None)]
    rows = {}
    for name, cfg, paged, spec_k, sdt in variants:
        params = lm.init(jax.random.PRNGKey(0), cfg)
        for s in slots:
            row = {}
            for ctx in ctxs:
                tps, alen, kb_slot = _bench_cell(
                    params, cfg, slots=s, ctx=ctx, steps=steps, paged=paged,
                    speculate_k=spec_k, state_dtype=sdt)
                row[f"serve_{ctx}"] = round(tps, 2)
            # pool accounting from the largest-context cell (dense KV
            # pools grow with max_len; flow/hybrid pools don't care):
            # KiB of state per slot, and the density figure — tokens/s
            # per GiB of state pool, i.e. slots x throughput per HBM byte
            row["kb_slot"] = round(kb_slot, 1)
            row["tps_per_gb"] = round(tps / (kb_slot * s / 2**20), 1)
            row["trend_vs_ctx"] = round(
                row[f"serve_{ctxs[0]}"] / max(row[f"serve_{ctxs[-1]}"], 1e-9),
                2)
            if spec_k:
                row["accept_len"] = round(alen, 2)
            rows[f"{name}[s{s}]"] = row
    # fleet rows at 4x/8x the longest context: the KV-vs-flow migration
    # gap grows linearly with context (the flow bundle doesn't), so
    # bench the migration path where portability actually matters
    fleet_ctxs = (4 * ctxs[-1], 8 * ctxs[-1])
    fleet_len = fleet_ctxs[-1] + steps + 32
    fleet_variants = [("fleet_flow", with_kind(base, "flow"), None),
                      ("fleet_paged", with_kind(base, "softmax"), page)]
    s = slots[-1]
    for name, cfg, paged in fleet_variants:
        if cfg.max_seq_len < fleet_len:
            cfg = dataclasses.replace(cfg, max_seq_len=fleet_len)
        params = lm.init(jax.random.PRNGKey(0), cfg)
        row = {}
        for ctx in fleet_ctxs:
            tps, kb, migs = _bench_fleet_cell(
                params, cfg, slots=s, ctx=ctx, steps=steps, paged=paged)
            row[f"serve_{ctx}"] = round(tps, 2)
        row["kb_migrated"] = round(kb, 1)
        row["migs_s"] = round(migs, 1)
        row["trend_vs_ctx"] = round(
            row[f"serve_{fleet_ctxs[0]}"]
            / max(row[f"serve_{fleet_ctxs[-1]}"], 1e-9), 2)
        rows[f"{name}[s{s}]"] = row
    cols = [f"serve_{c}" for c in ctxs] + \
        [f"serve_{c}" for c in fleet_ctxs if c not in ctxs] + \
        ["kb_slot", "tps_per_gb", "kb_migrated", "migs_s",
         "trend_vs_ctx", "accept_len"]
    print_table("Serving: decode tokens/s by slots x context", rows, cols)
    for name in rows:
        if name.startswith(("flow_q8", "paged_q8", "hybrid_rg_q8")):
            full = rows.get(name.replace("_q8", ""), {})
            q8 = rows[name]
            if full:
                print(f"[quant]   {name:18s} pool x"
                      f"{full['kb_slot'] / max(q8['kb_slot'], 1e-9):.2f} "
                      "smaller, density x"
                      f"{q8['tps_per_gb'] / max(full['tps_per_gb'], 1e-9):.2f}"
                      " vs full precision")
    ff, fp = rows.get(f"fleet_flow[s{s}]"), rows.get(f"fleet_paged[s{s}]")
    if ff and fp:
        ratio = fp["kb_migrated"] / max(ff["kb_migrated"], 1e-9)
        print(f"\n[fleet] migration bundle at ctx {fleet_ctxs[-1]}: "
              f"flow {ff['kb_migrated']} KiB vs paged KV "
              f"{fp['kb_migrated']} KiB -> x{ratio:.1f} smaller "
              f"({ff['migs_s']:.0f} vs {fp['migs_s']:.0f} migrations/s)")
    print("\n[trend] decode throughput ratio ctx "
          f"{ctxs[0]} -> {ctxs[-1]} (1.0 = flat in context length):")
    for name, row in rows.items():
        print(f"[trend]   {name:14s} x{row['trend_vs_ctx']}")
    for name, row in rows.items():
        if "accept_len" in row:
            plain = rows.get(name.replace("spec_", ""), {})
            base_t = plain.get(f"serve_{ctxs[0]}", 0)
            spec_t = row[f"serve_{ctxs[0]}"]
            print(f"[spec]    {name:18s} accept_len={row['accept_len']} "
                  f"accepted tok/s x{spec_t / max(base_t, 1e-9):.2f} vs plain")
    save_table("serving_bench", rows)
    return rows


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    kw = {}
    argv = sys.argv[1:]
    if "--slots" in argv:
        kw["slots"] = tuple(
            int(s) for s in argv[argv.index("--slots") + 1].split(","))
    if "--ctxs" in argv:
        kw["ctxs"] = tuple(
            int(s) for s in argv[argv.index("--ctxs") + 1].split(","))
    if "--steps" in argv:
        kw["steps"] = int(argv[argv.index("--steps") + 1])
    run(**kw)
