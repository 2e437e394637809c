"""Serving decode benchmark: split-KV paged flash decoding vs the PR-5
flash kernel vs dense decode, across batch x cache-depth cells.

One decode step of single-layer GQA attention per cell — the serving hot
loop's attention cost, isolated from the model around it.  Three
executors per (batch, cache) cell:

* ``split_kv``  — ``fused.paged_flash_decode`` over the paged pool, page
  table bucketed to the LIVE pages (the engine's column bucketing), PWL
  exp in the split-wise online softmax and the cross-split merge;
* ``pr5_flash`` — ``fused.fused_flash_attention`` over the dense
  capacity-wide cache with ragged ``kv_valid_len`` (the pre-serving
  decode path: grid sized by CAPACITY, compute skipped past valid);
* ``dense``     — materialized-scores exact softmax over the capacity
  cache (the toy-loop baseline).

The headline cell is ``long`` (capacity >> valid): split-KV's table is
bucketed to ceil(valid/page_size) columns, so its work tracks the LIVE
cache while both dense paths drag the full capacity through memory.  The
JSON summary makes that check machine-readable:
``long_cell_work_ratio`` = t(split_kv @ capacity C, valid V) /
t(split_kv @ capacity V, valid V) — ~1.0 means work proportional to
valid pages, independent of capacity.  Also per cell: output MSE vs the
exact-softmax oracle, and a 2-request continuous-batching engine session
(tokens/sec end to end, fused-fallback count must be 0).  The
``preemption_overhead`` summary cell runs the same engine at an
oversubscribed page budget under both admission policies: reserved
(serialized by worst-case reservation) vs optimistic (parallel but paying
recompute preemptions), reporting tok/s, preemption count, and
replayed-prefill tokens for each.

    PYTHONPATH=src python benchmarks/bench_serving.py [--quick] [--out PATH]

Note: on CPU the Pallas paths run in interpret mode — latency numbers are
only meaningful on TPU; --quick exists for CI smoke coverage.
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import pathlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np

import repro  # noqa: F401
from repro import sfu
from repro.kernels import fused
from repro.serving.kv_cache import PageAllocator, gather_pages

DEFAULT_OUT = pathlib.Path(__file__).resolve().parent.parent / "BENCH_serving.json"

try:  # package-style (python -m benchmarks.run) or script-style invocation
    from .common import emit, provenance, time_fn, write_bench_json
except ImportError:
    from common import emit, provenance, time_fn, write_bench_json

# full-size grid (TPU): ISSUE 6 cells
FULL = {
    "batches": (1, 8, 64),
    "caches": (4096, 65536, 524288),
    "long": (524288, 2048),   # (capacity, valid) — the 500k/2k cell
    "page_size": 128,
    "hkv": 4, "g": 2, "dh": 64,
}
# CI smoke (CPU interpret mode): same structure, shapes scaled down
QUICK = {
    "batches": (1, 4),
    "caches": (256, 512, 1024),
    "long": (1024, 128),
    "page_size": 16,
    "hkv": 2, "g": 2, "dh": 16,
}


def _exact_ref(q, k, v, kv_len):
    B, _, H, dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qr = np.asarray(q, np.float64).reshape(B, Hkv, G, dh)
    kr = np.asarray(k, np.float64).transpose(0, 2, 1, 3)
    vr = np.asarray(v, np.float64).transpose(0, 2, 1, 3)
    sc = np.einsum("bhgd,bhtd->bhgt", qr, kr) / math.sqrt(dh)
    mask = np.arange(k.shape[1])[None, :] < np.asarray(kv_len)[:, None]
    sc = np.where(mask[:, None, None, :], sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.einsum("bhgt,bhtd->bhgd", p, vr)
    return out.reshape(B, 1, H, dh).astype(np.float32)


def _mse(out, ref):
    return float(np.mean((np.asarray(out, np.float64) - ref) ** 2))


def _make_cell(key, B, capacity, valid, ps, hkv, g, dh):
    """Paged pool + fragmented table holding `valid` tokens per request,
    plus the dense capacity-wide view the flash/dense executors see."""
    npg_live = -(-valid // ps)
    pool = B * npg_live + 1
    alloc = PageAllocator(pool)
    rows = [[] for _ in range(B)]
    for _ in range(npg_live):          # interleaved -> fragmented
        for r in range(B):
            rows[r].extend(alloc.alloc(1))
    pt_live = jnp.asarray(np.asarray(rows, np.int32))
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(key), 3)
    kp = jax.random.normal(k1, (1, hkv, pool, ps, dh), jnp.float32)
    vp = jax.random.normal(k2, (1, hkv, pool, ps, dh), jnp.float32)
    q = jax.random.normal(k3, (B, 1, hkv * g, dh), jnp.float32)
    kv_len = jnp.full((B,), valid, jnp.int32)
    # dense capacity view: live tokens then zeros out to capacity
    k_dense = np.zeros((B, capacity, hkv, dh), np.float32)
    v_dense = np.zeros((B, capacity, hkv, dh), np.float32)
    k_dense[:, :npg_live * ps] = np.asarray(gather_pages(kp[0], pt_live))
    v_dense[:, :npg_live * ps] = np.asarray(gather_pages(vp[0], pt_live))
    return q, kp, vp, pt_live, kv_len, jnp.asarray(k_dense), jnp.asarray(v_dense)


def _dense_decode(q, k, v, kv_len):
    from repro.models import layers

    valid = jnp.arange(k.shape[1])[None, :] < kv_len[:, None]
    return layers.decode_attention(q, k, v, valid)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="tiny shapes (CI smoke)")
    ap.add_argument("--breakpoints", type=int, default=32)
    ap.add_argument("--out", default=str(DEFAULT_OUT),
                    help="machine-readable results JSON path")
    # parse_known_args: tolerate the runner's own flags (benchmarks/run.py
    # calls main() with run.py's sys.argv still in place)
    args, _ = ap.parse_known_args(argv)
    if jax.default_backend() == "cpu" and not args.quick:
        print("# cpu backend: forcing --quick shapes (interpret mode)")
        args.quick = True
    cfgd = QUICK if args.quick else FULL
    iters = 2 if args.quick else 10
    warmup = 1 if args.quick else 2
    ps, hkv, g, dh = cfgd["page_size"], cfgd["hkv"], cfgd["g"], cfgd["dh"]
    table = sfu.get_store().get(fn="exp", n_breakpoints=args.breakpoints)

    split_fn = lambda q, kp, vp, pt, kvl: fused.paged_flash_decode(  # noqa: E731
        q, kp, vp, pt, kvl, 0, table=table)
    flash_fn = jax.jit(lambda q, k, v, kvl: fused.fused_flash_attention(
        q, k, v, table=table, causal=False, kv_valid_len=kvl))
    dense_fn = jax.jit(_dense_decode)

    print("cell,impl,us_per_step,tok_per_s,mse_vs_exact")
    cells = []
    grid = [(B, C, C) for B in cfgd["batches"] for C in cfgd["caches"]]
    grid.append((cfgd["batches"][-1],) + cfgd["long"])
    split_times = {}
    for seed, (B, capacity, valid) in enumerate(grid):
        name = f"b{B}_cache{capacity}" + ("" if valid == capacity
                                          else f"_valid{valid}")
        q, kp, vp, pt, kvl, kd, vd = _make_cell(
            seed, B, capacity, valid, ps, hkv, g, dh)
        ref = _exact_ref(q, kd, vd, kvl)
        row = {"batch": B, "cache_capacity": capacity, "valid": valid,
               "live_pages": int(pt.shape[1]),
               "capacity_pages": -(-capacity // ps), "modes": {}}
        runs = {
            "split_kv": (split_fn, (q, kp, vp, pt, kvl)),
            "pr5_flash": (flash_fn, (q, kd, vd, kvl)),
            "dense": (dense_fn, (q, kd, vd, kvl)),
        }
        for impl, (fn, a) in runs.items():
            us = time_fn(fn, *a, warmup=warmup, iters=iters)
            mse = _mse(fn(*a), ref)
            tok_s = B / (us * 1e-6)
            row["modes"][impl] = {"us_per_step": round(us, 2),
                                  "tok_per_s": round(tok_s, 1),
                                  "mse_vs_exact": mse}
            emit(f"{name}_{impl}", us, f"{tok_s:.0f}tok/s")
        split_times[(B, capacity, valid)] = row["modes"]["split_kv"]["us_per_step"]
        cells.append(row)

    # work ∝ valid pages: the long cell (capacity >> valid) vs a cache whose
    # CAPACITY equals the long cell's valid length — identical live pages,
    # so split-KV should cost the same despite the capacity gap
    B_long, C_long, V_long = (cfgd["batches"][-1],) + cfgd["long"]
    q, kp, vp, pt, kvl, _, _ = _make_cell(
        1234, B_long, V_long, V_long, ps, hkv, g, dh)
    us_small = time_fn(split_fn, q, kp, vp, pt, kvl,
                       warmup=warmup, iters=iters)
    ratio = split_times[(B_long, C_long, V_long)] / us_small
    emit("long_cell_work_ratio", ratio,
         f"capacity{C_long}_vs_{V_long}_same_valid")

    # end-to-end: 2-request continuous-batching session on repro-100m
    # (reduced), fused plan — tokens/sec and the zero-fallback guarantee
    from repro.configs import get_reduced_config
    from repro.models import Model
    from repro.serving import GenRequest, PagedServingEngine

    cfg = get_reduced_config("repro-100m", act_impl="fused")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [GenRequest(f"r{i}", rng.integers(1, 500, size=n).tolist(), m)
            for i, (n, m) in enumerate([(24, 8), (9, 6)])]
    sfu.reset_fused_fallback_warnings()
    fallbacks = []
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        engine = PagedServingEngine(model, params, max_slots=2, page_size=ps,
                                    max_context=8 * ps)
        import time as _time
        t0 = _time.perf_counter()
        engine.run(reqs)
        session_s = _time.perf_counter() - t0
        fallbacks = [str(w.message) for w in caught
                     if "fused" in str(w.message).lower()]
    session_tok_s = engine.generated / session_s
    emit("engine_session_2req", session_s * 1e6, f"{session_tok_s:.1f}tok/s")

    # preemption overhead: reserved vs optimistic at an OVERSUBSCRIBED page
    # budget.  3 requests of worst-case 3 pages each on 2 slots with only 5
    # usable pages: reserved serializes admissions (worst-case reservation
    # can't cover two), optimistic runs two at once and pays for it with
    # recompute preemptions — the tok/s gap against the replayed-prefill
    # token count is the cost of the optimism (ISSUE 10).
    prompt_len = 2 * ps - 2          # 2 pages, grows to 3 mid-decode
    preempt_reqs = [
        GenRequest(f"p{i}", rng.integers(1, 500, size=prompt_len).tolist(), 8)
        for i in range(3)
    ]
    preemption_cell = {}
    for policy in ("reserved", "optimistic"):
        eng = PagedServingEngine(
            model, params, max_slots=2, page_size=ps,
            max_context=prompt_len + 8 + ps, num_pages=6,
            policy=policy, max_preemptions=32)
        t0 = _time.perf_counter()
        eng.run([dataclasses.replace(r) for r in preempt_reqs])
        dt = _time.perf_counter() - t0
        h = eng.health_summary()
        preemption_cell[policy] = {
            "tok_per_s": round(eng.generated / dt, 1),
            "preemptions": h["preemptions"],
            "replayed_prefill_tokens": h["replayed_prefill_tokens"],
        }
        emit(f"preemption_{policy}", dt * 1e6,
             f"{preemption_cell[policy]['tok_per_s']}tok/s_"
             f"{h['preemptions']}preempt")

    payload = {
        "benchmark": "serving",
        **provenance(args.quick),
        "shape": {"page_size": ps, "kv_heads": hkv, "group": g, "head_dim": dh},
        "breakpoints": args.breakpoints,
        "cells": cells,
        "summary": {
            "long_cell": {"batch": B_long, "cache_capacity": C_long,
                          "valid": V_long},
            "long_cell_work_ratio": round(ratio, 3),
            "work_proportional_to_valid_pages": ratio < 2.0,
            "engine_session": {
                "requests": len(reqs),
                "tokens": engine.generated,
                "tok_per_s": round(session_tok_s, 1),
                "fused_fallbacks": len(fallbacks),
            },
            "preemption_overhead": preemption_cell,
        },
    }
    write_bench_json(args.out, payload)
    if fallbacks:
        raise SystemExit(f"fused fallbacks during serving session: {fallbacks}")


if __name__ == "__main__":
    main()
