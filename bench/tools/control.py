#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` for the program and for its
control, on the chip at the cell's own size, over several seeds in one
process.

    python3 bench/tools/control.py --workload <cell> --seeds 1,2,3 \
        [--seconds 10]

The control is the plain reference computed in the precision below the
configuration's bfloat16: every matmul operand rounded to float8 e4m3.

* Serving cells: each seed runs the cell's driver with a short window at
  the cell's own load; over the same sample of finished requests it reads
  the widest gap of the served tokens (the program) and of the tokens the
  control puts first, both against the float32 reference.
* Training cells: the control follows the first steps from the seed's
  weights and batches, and is read against the float32 reference by the
  same three numbers as the program; so is the fault "half of the batch
  left out", planted in the reference.  No window is needed.

Prints one JSON line per seed and writes them all to
``chiprun_out/control/<cell>.json``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import types

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import core, serving  # noqa: E402


def serve_seed(ctx, drv) -> dict:
    """One run of a serving driver; the check also reads the control."""
    import numpy as np

    seen = {}
    plain = serving.check_served

    def both(ctx, params, done):
        checks = plain(ctx, params, done)
        ref = core.load_module(core.BENCH / "references" /
                               f"{ctx.config['reference']}.py")
        lim = ctx.traffic["check"]
        sample = serving.sample_finished(done, lim["served_tokens"],
                                         ctx.args.seed, lim["min_requests"])
        prog = serving.served_gaps(ref, ctx.config, params, sample)
        ctrl = serving.served_gaps(ref, ctx.config, params, sample, "fp8",
                                   pick="control")
        q = lambda g: [float(np.percentile(g, p)) for p in (50, 90, 99, 100)]  # noqa: E731
        seen.update(tokens=int(prog.size), program_max=float(prog.max()),
                    control_max=float(ctrl.max()), program_q=q(prog),
                    control_q=q(ctrl),
                    control_flips=int(np.sum(ctrl > 0)))
        return checks

    serving.check_served = both
    try:
        out = drv.run(ctx)
    finally:
        serving.check_served = plain
    seen["correct"] = out.correct
    return seen


def train_seed(ctx, drv) -> dict:
    from repro.data.pipeline import DataConfig, SyntheticLMData

    job = ctx.traffic
    data = SyntheticLMData(DataConfig(
        vocab_size=ctx.config["vocab_size"], seq_len=job["seq"],
        global_batch=job["batch"], seed=ctx.args.seed))
    batches = [data.batch_at(k) for k in range(drv.FIRST)]
    ref = drv.reference_readings(ctx, batches, "f32")
    ctrl = drv.reference_readings(ctx, batches, "fp8")
    # the fault "half of the batch left out, the mean over the rest",
    # planted in the reference put in the program's place
    b = job["batch"] // 2
    half = drv.reference_readings(
        ctx, [{k: v[:b] for k, v in x.items()} for x in batches], "f32")
    return {"control": drv.gaps(ctrl, ref), "half_batch": drv.gaps(half, ref),
            "ref_losses": ref.losses, "control_losses": ctrl.losses}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    bench = core.load_json(BENCH.parent / "BENCHMARK.json")
    cell = core.find_cell(bench, args.workload)
    config = core.load_json(BENCH / "configs" / f"{cell['config']}.json")
    tr = core.load_json(BENCH / "traffic" / f"{cell['name']}.json")
    devices = core.require_device(cell["chips"])
    core.enable_compile_cache()
    drv = core.load_module(BENCH / "drivers" / f"{tr['driver']}.py")
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        a = types.SimpleNamespace(workload=cell["name"], seed=seed,
                                  seconds=args.seconds, trace=0)
        ctx = core.Context(args=a, bench=bench, cell=cell, config=config,
                           traffic=tr, devices=devices,
                           meter=core.CompileMeter(), spans=core.Spans(),
                           t_start=time.perf_counter())
        one = train_seed if tr["driver"] == "train" else serve_seed
        row = {"seed": seed, **one(ctx, drv)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = core.CHECKOUT / "chiprun_out" / "control"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cell['name']}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
