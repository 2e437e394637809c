#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell on the chip: the highest
Poisson rate at which the queue does not grow over a window.

    python3 bench/tools/knee.py --workload <cell> --rates 1.5,2,2.5 \
        [--seconds 51] [--seed 1]

One process, one engine and one warm-up; each rate runs the cell's own
driver loop over a fresh schedule from the same seed (prelude included)
and drains before the next.  For each rate it prints the queue length over
the window (its least-squares slope in requests per second and its value
at the close), TTFT p90 and ITL p95.  A rate whose queue grows through the
window is past the knee.
"""
from __future__ import annotations

import argparse
import copy
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import core, serving, traffic as gen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import numpy as np

    bench = core.load_json(BENCH.parent / "BENCHMARK.json")
    cell = core.find_cell(bench, args.workload)
    config = core.load_json(BENCH / "configs" / f"{cell['config']}.json")
    tr = core.load_json(BENCH / "traffic" / f"{cell['name']}.json")
    devices = core.require_device(cell["chips"])
    core.enable_compile_cache()
    drv = core.load_module(BENCH / "drivers" / f"{tr['driver']}.py")
    ctx = core.Context(args=args, bench=bench, cell=cell, config=config,
                       traffic=tr, devices=devices, meter=core.CompileMeter(),
                       spans=core.Spans(), t_start=time.perf_counter())
    cfg, _, engine = drv.setup(ctx)
    rates = [float(r) for r in args.rates.split(",")]
    shapes = ([], [])
    schedules = {}
    for rate in rates:
        t = copy.deepcopy(tr)
        t["rate_per_s"] = rate
        schedules[rate] = gen.poisson_schedule(t, args.seed, args.seconds,
                                               cfg.vocab_size)
        b, w = serving.shapes(engine, schedules[rate])
        shapes = (sorted(set(shapes[0]) | set(b)), sorted(set(shapes[1]) | set(w)))
    serving.warm_up(engine, *shapes)
    rows = []
    for rate in rates:
        times, results, in_window, qlen, _ = drv.serve(
            ctx, engine, schedules[rate], args.seconds)
        done = [r for r in in_window if r in results]
        ttft = [(times[r].tokens[0] - times[r].due) * 1e3 for r in done]
        gaps = [g * 1e3 for r in done for g in np.diff(times[r].tokens)]
        t = np.array([q[0] for q in qlen])
        q = np.array([q[1] for q in qlen], np.float64)
        slope = float(np.polyfit(t, q, 1)[0]) if len(t) > 2 else float("nan")
        row = {"rate_per_s": rate, "due": len(in_window),
               "finished": len(done), "queue_slope_per_s": slope,
               "queue_at_close": int(q[-1]) if len(q) else 0,
               "queue_max": int(q.max()) if len(q) else 0,
               "ttft_p90_ms": serving.percentile(ttft, 90) if ttft else None,
               "itl_p95_ms": serving.percentile(gaps, 95) if gaps else None,
               "decode_steps": engine.decode_steps}
        rows.append(row)
        print(json.dumps(row), flush=True)
        # drain whatever is left before the next rate
        while engine.sched.has_work():
            for adm in engine.sched.admit():
                engine._prefill(adm)
            if engine.sched.active_slots():
                engine.decode_step()
    out = core.CHECKOUT / "chiprun_out" / "knee"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cell['name']}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
