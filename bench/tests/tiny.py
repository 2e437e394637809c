"""A run of a cell at a size the CPU can hold: the configuration shrunk in
width and depth, the traffic in lengths and rate, the device check skipped.
Everything else is the run as on the chip: the driver, the program's engine
or train step, the reference and the comparison."""
from __future__ import annotations

import copy
import json
import pathlib
import sys
import time
import types

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import core  # noqa: E402

SMALL = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "intermediate_size": 128,
         "vocab_size": 512}
_PROGRAM = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
            "num_attention_heads": "n_heads",
            "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
            "vocab_size": "vocab_size"}


def config(name: str, **extra) -> dict:
    c = core.load_json(BENCH / "configs" / f"{name}.json")
    c = copy.deepcopy(c)
    c.update(SMALL)
    c.update(extra)
    settings = c["program"].setdefault("settings", {})
    for k, f in _PROGRAM.items():
        settings[f] = c[k]
    return c


def traffic(cell: str, **extra) -> dict:
    t = copy.deepcopy(core.load_json(BENCH / "traffic" / f"{cell}.json"))
    for k, v in extra.items():
        if isinstance(v, dict):
            t[k].update(v)
        else:
            t[k] = v
    return t


def context(cell: str, cfg: dict, tr: dict, seed: int, seconds: float,
            chips: int = 1):
    """A run's context; ``cell`` need not be in ``BENCHMARK.json``."""
    import jax

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    args = types.SimpleNamespace(workload=cell, seed=seed, seconds=seconds,
                                 trace=0)
    entry = {"name": cell, "chips": chips}
    return core.Context(args=args, bench=bench, cell=entry, config=cfg,
                        traffic=tr, devices=jax.devices()[:chips],
                        meter=core.CompileMeter(), spans=core.Spans(),
                        t_start=time.perf_counter())


def driver(name: str):
    return core.load_module(BENCH / "drivers" / f"{name}.py")
