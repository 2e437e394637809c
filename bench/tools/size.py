#!/usr/bin/env python3
"""Size the cells by compiling their programs for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 bench/tools/size.py serve <config> <cell>
    JAX_PLATFORMS=cpu python3 bench/tools/size.py train <config> <batch>...

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` that is
described, not attached, and ``memory_analysis()`` gives each program's
argument, output and temporary bytes on one chip.  A refusal by the compiler
(too much VMEM, a program that does not fit) shows here too.

``serve``: the paged decode step at the widest page table and the prefill
step at the largest bucket the cell's traffic reaches, at two pool sizes;
the bytes grow linearly in pages, so the largest ``num_pages`` that fits
follows.  The steps do not donate the pool, so a step holds the old pool
and the new one: the pool counts twice.  ``train``: the train step at each
batch.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

# the chip's HBM less what the runtime keeps for itself: jax reports
# ``bytes_limit`` 16909336064 on a v5e (measured on the chip)
HBM_LIMIT = 16_909_336_064
MARGIN = 512 * 2**20


def _topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def _mosaic():
    """Kernels lower through Mosaic, not the interpreter (the CPU backend
    would otherwise pick interpret mode)."""
    from repro.kernels import _backend

    original = _backend.should_interpret
    for mod in list(sys.modules.values()):
        if not getattr(mod, "__name__", "").startswith("repro."):
            continue
        for name in ("should_interpret", "_should_interpret"):
            if getattr(mod, name, None) is original:
                setattr(mod, name, lambda: False)


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, f"{k}_size_in_bytes")) for k in
           ("argument", "output", "temp", "alias", "generated_code")}
    out["total"] = (out["argument"] + out["output"] + out["temp"]
                    - out["alias"] + out["generated_code"])
    return out


def _placed(defs, sharding):
    """ShapeDtypeStructs for a ParamDef tree, placed on ``sharding``."""
    import jax

    from repro.models.common import ParamDef

    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype, sharding=sharding),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


def _serve_programs(cfg, sharding, slots, page_size, pages, width, bucket):
    import jax
    import jax.numpy as jnp

    from repro.models import Model, transformer

    model = Model(cfg)
    params = _placed(model.param_defs(), sharding)
    cache = _placed(transformer.paged_cache_defs(cfg, pages, page_size),
                    sharding)
    i32 = jnp.int32
    s = lambda shape: jax.ShapeDtypeStruct(shape, i32, sharding=sharding)  # noqa: E731
    dec = jax.jit(model.decode_step_paged).lower(
        params, s((slots, 1)), cache, s((slots, width)), s((slots,))).compile()
    pre = jax.jit(model.prefill_paged).lower(
        params, s((1, bucket)), cache, s((1, bucket // page_size)),
        s((1,))).compile()
    return _mem(dec), _mem(pre)


def refusal(err: Exception) -> str:
    """The compiler's reason, first lines only."""
    return " ".join(str(err).split("\n")[:1])[:600]


def serve(config_name: str, cell: str) -> dict:
    from jax.sharding import SingleDeviceSharding

    from harness import serving, traffic as gen

    topo = _topology()
    _mosaic()
    config = json.loads((BENCH / "configs" / f"{config_name}.json").read_text())
    tr = json.loads((BENCH / "traffic" / f"{cell}.json").read_text())
    cfg = serving.program_config(config)
    e = tr["engine"]
    reqs = gen.request_set(tr, 0, 4096, cfg.vocab_size)
    ps = e["page_size"]
    bucket = max(max(ps, serving._pow2(len(r["prompt"]))) for r in reqs)
    deepest = max(len(r["prompt"]) + r["max_new_tokens"] for r in reqs)
    width = min(serving._pow2(-(-deepest // ps)), -(-e["max_context"] // ps))
    sharding = SingleDeviceSharding(topo.devices[0])
    rows = {}
    for pages in (64, 128):
        dec, pre = _serve_programs(cfg, sharding, e["max_slots"], ps, pages,
                                   width, bucket)
        rows[pages] = {"decode": dec, "prefill": pre}
    # a step's total grows by two pools (input and output) per page
    grow = {k: (rows[128][k]["total"] - rows[64][k]["total"]) / 64
            for k in ("decode", "prefill")}
    fixed = {k: rows[64][k]["total"] - 64 * grow[k]
             for k in ("decode", "prefill")}
    fit = min(int((HBM_LIMIT - MARGIN - fixed[k]) // grow[k])
              for k in ("decode", "prefill"))
    return {"cell": cell, "bucket": bucket, "width": width,
            "slots": e["max_slots"], "programs": rows,
            "bytes_per_page_in_step": grow["decode"], "fixed_bytes": fixed,
            "num_pages_that_fit": fit}


def train(config_name: str, batches: list[int]) -> dict:
    import jax
    from jax.sharding import Mesh
    import numpy as np

    from harness import serving
    from repro.launch.steps import build_train_step
    from repro.models import ShapeCell

    topo = _topology()
    _mosaic()
    config = json.loads((BENCH / "configs" / f"{config_name}.json").read_text())
    cfg = dataclasses.replace(serving.program_config(config),
                              act_impl_bwd="fused")
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    out = {}
    for b in batches:
        cell = ShapeCell("size", config["max_position_embeddings"], b, "train")
        fn, ins, outs, structs, kw = build_train_step(cfg, mesh, cell,
                                                      microbatches=1)
        try:
            compiled = jax.jit(fn, in_shardings=ins, out_shardings=outs,
                               donate_argnums=kw["donate_argnums"]).lower(
                *structs).compile()
            out[b] = _mem(compiled)
            out[b]["fits"] = out[b]["total"] <= HBM_LIMIT - MARGIN
        except Exception as err:  # the compiler's refusal is the finding
            out[b] = {"refused": refusal(err)}
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[0] == "serve":
        res = serve(argv[1], argv[2])
    elif argv[0] == "train":
        res = train(argv[1], [int(b) for b in argv[2:]])
    else:
        print(__doc__, file=sys.stderr)
        return 2
    print(json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
