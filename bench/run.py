#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine, once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell's entry in ``BENCHMARK.json`` names
its configuration (``bench/configs/<config>.json``) and its traffic
(``bench/traffic/<cell>.json``), and the traffic file names the driver
(``bench/drivers/<driver>.py``) that builds the program, warms up the
cell's shapes, measures for ``--seconds`` and checks what the timed path
produced against the plain reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries the
cell's per-layer metrics, each read by ``bench/metrics/<metric>.py``.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), with
``checks`` last: each number compared beside its limit.  The checks are
also the last lines on stderr.  With no TPU, fewer chips than the cell
asks for, or Pallas in interpret mode, it prints no result and exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import core  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(ctx, outcome, spec: list[dict]) -> tuple[dict, dict, dict]:
    """Reduce the trace and read each per-layer metric.  Returns the
    metrics, the device's busy and window seconds, and the breakdown."""
    from harness import trace as tr

    red = tr.reduce_dir(ctx.trace_dir, ctx.spans.records, ctx.window,
                        n_devices=len(ctx.devices))
    reading = tr.Reading(trace=red, counts=outcome.counts, config=ctx.config,
                         traffic=ctx.traffic,
                         peaks=core.peaks_for(ctx.devices[0].device_kind),
                         window=ctx.window, chips=len(ctx.devices))
    metrics = {}
    for m in spec:
        mod = core.load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = mod.read(reading)
        if value is None:
            core.log(f"{m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"busy_s": red.busy_s, "window_s": red.window_s}
    return metrics, device, red.breakdown()


def main(argv=None) -> int:
    args = parse(argv)
    bench_file = BENCH.parent / "BENCHMARK.json"
    try:
        if not (BENCH.parent / "src" / "repro").is_dir():
            raise core.Refused("no program under src/repro; run from the "
                               "root of a checkout")
        bench = core.load_json(bench_file)
        cell = core.find_cell(bench, args.workload)
        core.config_entry(bench, cell["config"])
        config = core.load_json(BENCH / "configs" / f"{cell['config']}.json")
        traffic = core.load_json(BENCH / "traffic" / f"{cell['name']}.json")
        driver = core.load_module(BENCH / "drivers" /
                                  f"{traffic['driver']}.py")
        devices = core.require_device(cell["chips"])
        cache = core.enable_compile_cache()
        trace_dir = None
        if args.trace:
            trace_dir = core.OUT / "trace" / cell["name"]
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True)
        ctx = core.Context(args=args, bench=bench, cell=cell, config=config,
                           traffic=traffic, devices=devices,
                           meter=core.CompileMeter(), spans=core.Spans(),
                           t_start=T_START, trace_dir=trace_dir)
    except (core.Refused, FileNotFoundError, json.JSONDecodeError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    core.log(f"{cell['name']} on {core.device_info(devices)}, seed "
             f"{args.seed}, {args.seconds} s, trace {args.trace}, compile "
             f"cache {cache}; devices ready at "
             f"{time.perf_counter() - T_START:.3f} s")

    outcome = driver.run(ctx)
    core.log(f"set-up {ctx.setup_s:.3f} s, compile {ctx.meter.seconds:.3f} s "
             f"({ctx.meter.compiles} compiles, {ctx.meter.cache_hits} cache "
             f"hits), compiles inside the window {ctx.window_compiles}")

    device = core.device_info(devices)
    device["memory_peak_bytes"] = ctx.memory_peak
    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed}
    if args.trace:
        spec = core.metrics_for(bench, cell["name"], "per_layer")
        metrics, busy, breakdown = per_layer(ctx, outcome, spec)
        device.update(busy)
        result.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        metrics = {}
        for m in core.metrics_for(bench, cell["name"], "end_to_end"):
            if m["name"] == "setup_s":
                value = ctx.setup_s
            else:
                value = outcome.end_to_end.get(m["name"])
            if value is None:
                core.log(f"{m['name']}: not measured")
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result.update(metrics=metrics, device=device)
    result["checks"] = {c.name: c.as_json() for c in outcome.checks}
    for c in outcome.checks:
        print(f"check {c.name}: {c.value!r} {c.op} {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
