"""Fused-vs-unfused MoE expert FFN latency: exact / jnp / fused.

The MoE sibling of ``bench_fused_mlp.py``: the rows routed to the experts,
grouped by expert (``--capacity`` rows each), go through each expert's GLU —

    h_r = act(x_r @ Wg[e]) * (x_r @ Wu[e]);   y_r = h_r @ Wd[e]

Unfused (``jax.lax.ragged_dot``), the two (rows, F) pre-activations and the
activation output each round-trip HBM; ``fused`` evaluates the non-uniform
PWL decode as an epilogue of the grouped gemms (kernels/fused/moe.py) so the
activation and gating cost zero extra traffic.  Emits CSV rows via benchmarks/common.py
AND a machine-readable ``BENCH_fused_moe.json`` (per-mode latency + output
MSE vs the exact mode) at the repo root.

    PYTHONPATH=src python benchmarks/bench_fused_moe.py [--quick] [--out PATH]

Note: on CPU the Pallas path runs in interpret mode — latency numbers are
only meaningful on TPU; --quick exists for CI smoke coverage.
"""
from __future__ import annotations

import argparse
import pathlib

import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro import sfu
from repro.core import pwl
from repro.kernels import fused

DEFAULT_OUT = pathlib.Path(__file__).resolve().parent.parent / "BENCH_fused_moe.json"

try:  # package-style (python -m benchmarks.run) or script-style invocation
    from .common import emit, provenance, time_fn, write_bench_json
except ImportError:
    from common import emit, provenance, time_fn, write_bench_json


def make_expert_ffn(mode: str, table, sizes, row_tile: int):
    if mode == "exact":
        from repro.core import functions as F

        act = F.get(table.name).fn
    elif mode == "jnp":
        def act(x):
            return pwl.eval_coeff(x, table)

    if mode == "fused":
        @jax.jit
        def ffn(x, wg, wu, wd):
            h = fused.fused_moe_glu(x, wg, wu, sizes, row_tile=row_tile,
                                    table=table)
            return fused.moe.moe_down(h, wd, sizes, row_tile=row_tile)
    else:
        @jax.jit
        def ffn(x, wg, wu, wd):
            g = jax.lax.ragged_dot(x, wg, sizes)
            u = jax.lax.ragged_dot(x, wu, sizes)
            return jax.lax.ragged_dot(act(g) * u, wd, sizes)

    return ffn


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="tiny shapes (CI smoke)")
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--capacity", type=int, default=512)
    ap.add_argument("--d-model", type=int, default=2048)
    ap.add_argument("--d-ff", type=int, default=1024)
    ap.add_argument("--activation", default="silu")
    ap.add_argument("--breakpoints", type=int, default=32)
    ap.add_argument("--out", default=str(DEFAULT_OUT),
                    help="machine-readable results JSON path")
    # parse_known_args: tolerate the runner's own flags (benchmarks/run.py)
    args, _ = ap.parse_known_args(argv)

    if jax.default_backend() == "cpu" and not args.quick:
        print("# cpu backend: forcing --quick shapes (interpret mode)")
        args.quick = True
    if args.quick:
        args.experts, args.capacity, args.d_model, args.d_ff = 8, 32, 128, 256
    iters = 3 if args.quick else 10

    table = sfu.get_store().get(
        fn=args.activation, n_breakpoints=args.breakpoints
    )
    kx, kg, ku, kd = jax.random.split(jax.random.PRNGKey(0), 4)
    dtype = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16
    E, D, F = args.experts, args.d_model, args.d_ff
    row_tile = fused.moe.row_tile(args.capacity)
    C = -(-args.capacity // row_tile) * row_tile
    sizes = jnp.full((E,), C, jnp.int32)
    x = jax.random.normal(kx, (E * C, D), dtype)
    wg = jax.random.normal(kg, (E, D, F), dtype) * 0.02
    wu = jax.random.normal(ku, (E, D, F), dtype) * 0.02
    wd = jax.random.normal(kd, (E, F, D), dtype) * 0.02

    print(f"# backend={jax.default_backend()} experts={E} capacity={C} "
          f"d_model={D} d_ff={F} act={args.activation}")
    base = None
    y_exact = None
    results = {}
    for mode in ("exact", "jnp", "fused"):
        fn = make_expert_ffn(mode, table, sizes, row_tile)
        us = time_fn(fn, x, wg, wu, wd,
                     warmup=1 if args.quick else 2, iters=iters)
        y = fn(x, wg, wu, wd).astype(jnp.float32)
        if base is None:
            base = us
            y_exact = y
        mse = float(jnp.mean((y - y_exact) ** 2))
        results[mode] = {
            "us_per_call": round(us, 2),
            "speedup_vs_exact": round(base / us, 4),
            "mse_vs_exact": mse,
        }
        emit(f"moe_expert_ffn_{mode}", us, f"{base / us:.2f}x_vs_exact")

    payload = {
        "benchmark": "fused_moe",
        **provenance(args.quick),
        "shape": {"experts": E, "capacity": C, "d_model": D, "d_ff": F,
                  "dtype": str(jnp.dtype(dtype))},
        "activation": args.activation,
        "breakpoints": args.breakpoints,
        "modes": results,
    }
    write_bench_json(args.out, payload)


if __name__ == "__main__":
    main()
