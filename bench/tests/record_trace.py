#!/usr/bin/env python3
"""Record the small TPU trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py      # on a TPU v5e

olmo-1b at its published widths cut to 2 layers, through the paged engine
with the fused plan: one prefill of 200 tokens and four decode steps at 4
slots, each inside the driver's span (``prefill``, ``decode``), traced with
the harness's profiler options.  Writes ``bench/tests/data/serve.xplane.pb``
and, beside it, what the recording saw (``serve.json``): the spans and the
kernels the test expects.
"""
from __future__ import annotations

import glob
import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))


def main() -> int:
    import jax
    import numpy as np

    from harness import core, serving
    from repro.configs import get_config
    from repro.serving import GenRequest

    core.require_device(1)
    cfg = get_config("olmo-1b", act_impl="fused", pwl_softmax=True,
                     n_layers=2)
    params = serving.make_params(cfg, 1)
    engine = serving.make_engine(cfg, params, {"engine": {
        "max_slots": 4, "page_size": 128, "max_context": 512,
        "num_pages": 17, "policy": "reserved"}})
    rng = np.random.default_rng(0)
    for i in range(4):
        engine.sched.submit(GenRequest(
            f"r{i}", rng.integers(1, 50000, 200).tolist(), max_new_tokens=16))
    adms = engine.sched.admit()
    for adm in adms[1:]:
        engine._prefill(adm)
    engine.decode_step()          # compiles
    spans = core.Spans()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = core.OUT / "record_trace"
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with spans("prefill"):
        engine._prefill(adms[0])
    for _ in range(4):
        with spans("decode"):
            engine.decode_step()
    jax.profiler.stop_trace()
    src = sorted(glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True))[-1]
    data = BENCH / "tests" / "data"
    data.mkdir(exist_ok=True)
    shutil.copy(src, data / "serve.xplane.pb")
    shutil.rmtree(tmp)
    (data / "serve.json").write_text(json.dumps({
        "spans": {"prefill": 1, "decode": 4},
        "decode_kernels": ["_paged_decode", "_fused_glu_2d"],
        "prefill_kernels": ["_fused_softmax_2d", "_fused_glu_2d"],
        "window_s": spans.records[-1][2] - spans.records[0][1],
        "device": core.device_info(jax.devices()[:1])}, indent=1) + "\n")
    print((data / "serve.xplane.pb").stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
