import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the sharding config is coherent at production scale
(no mismatched shardings, no unsupported collectives, fits per-device memory)
and extracts the roofline terms:

  * ``compiled.cost_analysis()``  -> HLO FLOPs / bytes   (per device)
  * ``compiled.memory_analysis()``-> peak per-device bytes
  * HLO text                      -> collective bytes (roofline/hlo_parse.py)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod-only|--singlepod-only]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import functools
import json
import pathlib
import sys
import time
import traceback

import jax

from repro import sfu
from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step
from repro.models import SHAPE_CELLS
from repro.roofline import hlo_parse
from repro.roofline.model import (
    RooflineReport,
    active_params,
    analytic_memory_traffic,
    analytic_peak_memory,
    model_flops_decode,
    model_flops_train,
)

# long_500k requires sub-quadratic attention: skip pure full-attention archs
# (DESIGN.md Sec. 5) — recorded as explicit SKIP rows, not silently dropped.
LONG_OK = {"mamba2-2.7b", "jamba-v0.1-52b", "gemma3-1b"}


def cell_is_skipped(arch: str, shape: str) -> bool:
    return shape == "long_500k" and arch not in LONG_OK


def _compile_cell(cfg, mesh, cell, microbatches=None):
    t0 = time.time()
    fn, in_sh, out_sh, structs, extra = build_step(cfg, mesh, cell, microbatches=microbatches)
    jitted = jax.jit(
        fn, in_shardings=in_sh, out_shardings=out_sh,
        donate_argnums=extra.get("donate_argnums", ()),
    )
    lowered = jitted.lower(*structs)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    return compiled, t_lower, t_compile


def _metrics(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = hlo_parse.collective_bytes(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "coll": coll,
    }


def probe_metrics(arch: str, cfg, mesh, cell, microbatches=None) -> dict:
    """cost_analysis counts scan bodies ONCE (not x trip count), so derive the
    true per-step cost from two UNROLLED shallow probes: total(metric) =
    m(1 period) + (n_periods-1) * (m(2 periods) - m(1 period)).

    unroll_scans=True also unrolls the flash-attention q/kv scans inside each
    layer — without it the attention cost would be counted once per scan, not
    once per block (discovered via the refuted H2 measurement, Sec. Perf)."""
    import dataclasses

    if cfg.is_encoder_decoder:
        L1 = 1
    else:
        period = cfg.period
        # long-period patterns (gemma3: period 26) probe a pattern-consistent
        # prefix instead (global_every keeps kinds[:L1] == kinds of n_layers=L1)
        L1 = period if period <= 8 else (cfg.global_every or 8)
    L2 = 2 * L1
    L_total = cfg.n_layers

    def shallow(L):
        if cfg.is_encoder_decoder:
            c = dataclasses.replace(
                cfg, n_layers=L, n_encoder_layers=L, scan_layers=False,
                unroll_scans=True,
            )
        else:
            c = dataclasses.replace(
                cfg, n_layers=L, scan_layers=False, unroll_scans=True
            )
        # probes always run microbatches=1: the grad-accumulation scan body
        # would otherwise be counted once instead of K times (totals are
        # K-invariant: same math, K x smaller microbatch)
        compiled, _, _ = _compile_cell(
            c, mesh, cell, microbatches=1 if cell.kind == "train" else None
        )
        return _metrics(compiled)

    m1 = shallow(L1)
    if L_total == L1:
        return m1
    m2 = shallow(L2)

    def extrap(a, b):
        return a + (L_total - L1) * (b - a) / (L2 - L1)

    out = {
        "flops": extrap(m1["flops"], m2["flops"]),
        "bytes": extrap(m1["bytes"], m2["bytes"]),
        "transcendentals": extrap(m1["transcendentals"], m2["transcendentals"]),
        "coll": {
            k: extrap(m1["coll"].get(k, 0), m2["coll"].get(k, 0))
            for k in set(m1["coll"]) | set(m2["coll"])
        },
    }
    return out


@functools.lru_cache(maxsize=None)
def _plan_missing_cached(arch: str, plan) -> tuple[str, ...]:
    return tuple(sfu.plan_missing_sites(get_config(arch), plan))


def plan_missing_sites(arch: str, plan) -> list[str]:
    """Arch-id wrapper over :func:`sfu.plan_missing_sites` (see there).
    Cached on (arch, plan) — plans are frozen/hashable — so the sweep's
    per-arch precheck and run_cell's API-level guard share one evaluation
    instead of recomputing get_config + model_sites per cell."""
    return list(_plan_missing_cached(arch, plan))


def run_cell(arch: str, shape: str, multi_pod: bool, act_impl: str = "jnp",
             plan=None, overrides: dict | None = None) -> dict:
    cell = SHAPE_CELLS[shape]
    over = dict(overrides or {})
    if plan is not None:
        missing = plan_missing_sites(arch, plan)
        if missing:
            raise ValueError(
                f"plan {plan.fingerprint} has no spec for activation sites "
                f"{missing} that arch '{arch}' instantiates (plan sites: "
                f"{[k for k in plan]}) — dump a plan from this arch's "
                "config instead"
            )
        over["act_plan"] = plan
        act_impl = f"plan:{plan.fingerprint}"  # provenance tag for the row
        cfg = get_config(arch, **over)
    else:
        cfg = get_config(arch, act_impl=act_impl, **over)
    if cfg.force_dp_only is None:
        import dataclasses as _dc

        from repro.roofline.model import total_params as _tp

        # pin H3 eligibility from the FULL config so shallow probes match
        cfg = _dc.replace(
            cfg, force_dp_only=bool(_tp(cfg) < 2.5e9 and cfg.n_experts == 0)
        )
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    # 1) full-depth compile: proves the sharding config + gives peak memory
    from repro.launch.steps import auto_microbatches

    mb = auto_microbatches(cfg, cell, mesh) if cell.kind == "train" else None
    compiled, t_lower, t_compile = _compile_cell(cfg, mesh, cell, microbatches=mb)
    try:
        mem = compiled.memory_analysis()
        # XLA-CPU upper bound: args + temps + outputs - aliased(donated)
        peak = (
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0)
        )
        mem_repr = (
            f"peak={getattr(mem, 'peak_memory_in_bytes', 0)} "
            f"temp={getattr(mem, 'temp_size_in_bytes', 0)} "
            f"args={getattr(mem, 'argument_size_in_bytes', 0)} "
            f"out={getattr(mem, 'output_size_in_bytes', 0)} "
            f"alias={getattr(mem, 'alias_size_in_bytes', 0)}"
        )
    except Exception as e:  # CPU backend may not support it
        peak, mem_repr = 0, f"unavailable: {e}"

    raw = _metrics(compiled)
    # 2) shallow unrolled probes: true per-step FLOPs/bytes/collectives
    probed = probe_metrics(arch, cfg, mesh, cell, microbatches=mb)
    cost = {"flops": probed["flops"], "bytes accessed": probed["bytes"],
            "transcendentals": probed["transcendentals"]}
    coll = probed["coll"]

    tokens = cell.global_batch * cell.seq_len
    if cell.kind == "train":
        mflops = model_flops_train(cfg, tokens)        # 6*N*D (fwd+bwd)
    elif cell.kind == "prefill":
        mflops = model_flops_train(cfg, tokens) / 3.0  # forward only = 2ND
    else:
        mflops = model_flops_decode(cfg, cell.global_batch, cell.seq_len)

    mem_bytes = analytic_memory_traffic(cfg, cell, dict(mesh.shape))
    # per-device link traffic: the compiled module is already the per-device
    # (SPMD-partitioned) program.  ring estimates: all-gather/all-to-all/
    # permute ~ output bytes; all-reduce ~ 2x (RS+AG phases); reduce-scatter's
    # *output* is the scattered shard, so scale by the typical (data) axis.
    dp_axis = dict(mesh.shape).get("data", 1)
    coll_dev = (
        coll.get("all-gather", 0)
        + coll.get("all-to-all", 0)
        + coll.get("collective-permute", 0)
        + 2 * coll.get("all-reduce", 0)
        + dp_axis * coll.get("reduce-scatter", 0)
    )
    report = RooflineReport(
        name=f"{arch}__{shape}__{'multi' if multi_pod else 'single'}",
        chips=chips,
        hlo_flops=float(cost.get("flops", 0.0)),
        hlo_bytes=mem_bytes,
        coll_bytes=float(coll_dev),
        model_flops=mflops,
        peak_mem_bytes=float(peak or 0),
    )
    row = report.row()
    row.update(
        arch=arch,
        shape=shape,
        mesh="2x16x16" if multi_pod else "16x16",
        act_impl=act_impl,
        # exact per-site approximation plan this cell compiled with — a later
        # run can reproduce it via ActivationPlan.from_json (repro.sfu)
        act_plan=sfu.plan_for(cfg).to_json(),
        act_plan_fingerprint=sfu.plan_for(cfg).fingerprint,
        status="ok",
        t_lower_s=round(t_lower, 1),
        t_compile_s=round(t_compile, 1),
        transcendentals=float(cost.get("transcendentals", 0.0)),
        collectives=coll,
        active_params=active_params(cfg),
        memory_analysis=mem_repr[:500],
        peak_analytic_gb=analytic_peak_memory(cfg, cell, dict(mesh.shape), mb or 1) / 2**30,
        hlo_bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        raw_scan_once=raw,  # un-extrapolated full-graph numbers for reference
    )
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPE_CELLS))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod-only", action="store_true")
    ap.add_argument("--singlepod-only", action="store_true")
    ap.add_argument(
        "--plan", default=None, metavar="PATH",
        help="compile every cell against this ActivationPlan JSON "
        "(repro.sfu); default: the jnp PWL plan from each arch config",
    )
    ap.add_argument("--out", default="experiments/dryrun")
    # removed flag, kept one release as a hard error with a pointer
    ap.add_argument("--act-impl", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.act_impl is not None:
        ap.error(
            "--act-impl was removed: pass --plan <plan.json> instead "
            "(see docs/plans.md)"
        )
    plan = sfu.load_plan(args.plan) if args.plan else None

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPE_CELLS) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True]
    if args.multipod_only:
        meshes = [True]
    if args.singlepod_only:
        meshes = [False]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cells.append((arch, shape, mp))

    failures = 0
    for arch, shape, mp in cells:
        tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
        path = outdir / f"{tag}.json"
        if cell_is_skipped(arch, shape):
            row = {
                "arch": arch, "shape": shape,
                "mesh": "2x16x16" if mp else "16x16",
                "status": "SKIP (full attention at 500k — DESIGN.md Sec. 5)",
            }
            path.write_text(json.dumps(row, indent=2))
            print(f"[skip] {tag}", flush=True)
            continue
        if plan is not None and plan_missing_sites(arch, plan):
            # one plan JSON cannot cover heterogeneous archs: record an
            # explicit skip instead of failing the sweep on a KeyError
            # (plan_missing_sites is cached, so this costs one dict hit)
            row = {
                "arch": arch, "shape": shape,
                "mesh": "2x16x16" if mp else "16x16",
                "status": f"SKIP (plan {plan.fingerprint} lacks sites "
                          f"{plan_missing_sites(arch, plan)} for this arch)",
            }
            path.write_text(json.dumps(row, indent=2))
            print(f"[skip] {tag} (plan/arch site mismatch)", flush=True)
            continue
        try:
            row = run_cell(arch, shape, mp, plan=plan)
            path.write_text(json.dumps(row, indent=2, default=str))
            print(
                f"[ok]   {tag}  compile={row['t_compile_s']}s  "
                f"bottleneck={row['bottleneck']}  "
                f"t=(c {row['t_compute_ms']:.1f} | m {row['t_memory_ms']:.1f} "
                f"| x {row['t_collective_ms']:.2f}) ms  peak={row['peak_mem_gb']:.2f} GiB",
                flush=True,
            )
        except Exception as e:
            failures += 1
            row = {
                "arch": arch, "shape": shape,
                "mesh": "2x16x16" if mp else "16x16",
                "status": f"FAIL: {e}",
                "traceback": traceback.format_exc()[-3000:],
            }
            path.write_text(json.dumps(row, indent=2))
            print(f"[FAIL] {tag}: {str(e)[:300]}", flush=True)
    print(f"done: {len(cells)} cells, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
