"""Closed-loop serving of a standing backlog on a mesh: the load of offline
batch generation, where every slot stays busy.

The program is served whole over the cell's chips as a (data=1, model=n)
mesh: parameters are drawn from the seed straight into the layout the
program's sharding rules give them, and the engine runs sharded.  A stream
of requests drawn from the seed keeps ``backlog`` requests waiting above
the busy slots: every finished request is replaced at once by the next of
the stream.  The loop is the body of ``PagedServingEngine.run``: admit,
prefill each admission, one decode step.  A prelude fills the slots and the
backlog before the window opens (set-up, since the traffic needs it).

The end-to-end metric is ``itl_p95_ms``: every gap between consecutive
tokens of a request whose later token the host held inside the window.  A
backlog's time to first token is its queue, so the cell reports none.

A traced run (``--trace 1``) reports only per-layer metrics, and its window
is the traffic's ``trace_window_s`` when that is shorter than ``--seconds``.
The harness labels each idle gap of chip 0 by a search over every host
event of the trace, so its reduction grows with the product of the two:
four chips' trace of a 51 s window of this cell (~1,100 decode steps of
~1,300 device ops each) did not finish reducing within five minutes on a
v5e host, while a 5 s window (~80 decode steps and ~20 admissions,
enough for the per-layer readings) reduced in ~6 s.

Importing this driver refuses the run (``core.Refused``, exit 3) where the
program's ``ModelConfig`` lacks QK-norm or the top-k rule, or still drops
tokens by capacity: such a program is not the published model, and would
run out of memory or hang at this size.
"""
from __future__ import annotations

import dataclasses
import json
import math
import time
import warnings

import numpy as np

from harness import core, serving, traffic as gen, weights

from repro.models.common import ModelConfig  # noqa: E402

_FIELDS = {f.name for f in dataclasses.fields(ModelConfig)}
if not {"qk_norm", "norm_topk"} <= _FIELDS or "capacity_factor" in _FIELDS:
    raise core.Refused("the program's ModelConfig has no qk_norm/norm_topk "
                       "or still has capacity_factor: it cannot serve the "
                       "published OLMoE")

# configuration keys of an expert model -> fields of the program's config
_MOE = {"num_experts": "n_experts", "num_experts_per_tok": "n_active_experts",
        "intermediate_size": "moe_d_ff", "qk_norm": "qk_norm",
        "norm_eps": "norm_eps"}


def program_config(config: dict):
    """The program's ModelConfig, held to every size and rule the file
    states, the experts' too."""
    cfg = serving.program_config(config)
    mism = {k: (config[k], getattr(cfg, f)) for k, f in _MOE.items()
            if config[k] != getattr(cfg, f)}
    if cfg.norm_topk != config["norm_topk_prob"]:
        mism["norm_topk_prob"] = (config["norm_topk_prob"], cfg.norm_topk)
    if mism:
        raise core.Refused(f"configuration file and program differ: {mism}")
    return cfg


def _draw(name: str, struct, key, stacked: bool):
    """The benchmark's rule for each leaf: ``weights``'s, and for the
    router (D, E) std 1/sqrt(D), so that its logits have unit variance
    over the normalised input."""
    import jax

    if name == "router":
        std = 1.0 / math.sqrt(struct.shape[-2])
        return (jax.random.normal(key, struct.shape) * std).astype(
            struct.dtype)
    return weights._draw(name, struct, key, stacked)


def make_params(model, rules, seed: int):
    """Weights drawn from the seed in one jitted call, straight into the
    layout the sharding rules give each leaf (no leaf is ever whole on one
    chip)."""
    import jax
    from jax.sharding import NamedSharding

    from repro.distributed.sharding import sanitize_spec
    from repro.models.common import ParamDef

    defs = model.param_defs()
    mesh = rules.mesh
    shardings = jax.tree_util.tree_map(
        lambda d: NamedSharding(mesh, sanitize_spec(
            mesh, rules.spec(*d.logical_axes), d.shape)),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        model.param_structs())

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(treedef, [
            _draw(weights._leaf_name(path), st, k, weights.stacked(path))
            for k, (path, st) in zip(keys, flat)])

    return jax.jit(build, out_shardings=shardings)(weights.seed_key(seed))


def setup(ctx):
    """Configuration, mesh, weights and engine."""
    from repro.distributed.sharding import make_rules
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.serving import PagedServingEngine

    cfg = program_config(ctx.config)
    shape = ctx.traffic["mesh"]
    mesh = make_mesh((shape["data"], shape["model"]), ("data", "model"),
                     devices=ctx.devices)
    rules = make_rules(cfg, mesh)
    model = Model(cfg)
    params = make_params(model, rules, ctx.args.seed)
    e = ctx.traffic["engine"]
    engine = PagedServingEngine(
        model, params, max_slots=e["max_slots"], page_size=e["page_size"],
        max_context=e["max_context"], num_pages=e["num_pages"],
        policy=e["policy"], rules=rules)
    return cfg, params, engine


def request_stream(traffic: dict, seed: int, vocab: int) -> list[dict]:
    """The seed's stream: ``stream`` requests as consecutive sets of
    ``stream_set`` (the generator's quantile lengths, each set in the
    seed's order).  Every stretch of one set's length admitted holds the
    whole distribution of lengths, so the share of long prompts a window
    admits, on which the tail of the gaps rests, barely moves between
    seeds."""
    n, k = int(traffic["stream"]), int(traffic["stream_set"])
    return [r for i in range(-(-n // k))
            for r in gen.request_set(traffic, seed, k, vocab, stream=i)][:n]


def serve(ctx, engine, stream: list[dict], prelude: float, seconds: float):
    """Keep ``backlog`` requests of ``stream`` waiting above the busy slots
    for ``prelude`` seconds, then for a window of ``seconds``.  Returns each
    request's token times (host clock), the finished results by id, the
    number of requests submitted, and the window's decode steps as the
    depth of each active slot (the counts the paged-decode reader takes)."""
    from repro import sfu
    from repro.serving import GenRequest

    sched, spans = engine.sched, ctx.spans
    sfu.reset_all_warnings()
    record: dict = {}
    outstanding = engine.max_slots + int(ctx.traffic["backlog"])
    times: dict[str, list[float]] = {}
    results: dict = {}
    nxt, opened = 0, False
    t_open = time.perf_counter() + prelude
    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            t_open = ctx.open_window()
            opened = True
        if opened and now >= t_open + seconds:
            ctx.close_window()
            break
        while (len(sched.queue) + len(sched.active_slots()) < outstanding
               and nxt < len(stream)):
            r = stream[nxt]
            sched.submit(GenRequest(f"r{nxt}", r["prompt"],
                                    max_new_tokens=r["max_new_tokens"]))
            times[f"r{nxt}"] = []
            nxt += 1
        for adm in sched.admit():
            with spans("prefill"):
                engine._prefill(adm)
            times[adm.request.request_id].append(time.perf_counter())
        active = sched.active_slots()
        if active:
            ids = [sched.slot(i).request.request_id for i in active]
            depth = [int(engine.kv_len[i]) + 1 for i in active]
            with spans("decode"):
                engine.decode_step()
            t = time.perf_counter()
            for rid in ids:
                times[rid].append(t)
            if opened:
                record.setdefault("decode", []).append(depth)
        for r in sched.results()[len(results):]:
            results[r.request_id] = r
    return times, results, nxt, record


def routing_check(since: float, until: float) -> tuple[int, int]:
    """Steps whose ranks computed fewer or more rows than were routed, and
    the steps read, among the program's records of the range."""
    from repro import tracing

    recs = [x.attrs for x in tracing.records(since=since, until=until)
            if x.name in ("serving.decode", "serving.prefill")
            and "routed_rows" in x.attrs]
    bad = sum(sum(a["rank_rows"]) != a["routed_rows"] for a in recs)
    return bad, len(recs)


def run(ctx) -> core.Outcome:
    tr = ctx.traffic
    cfg, params, engine = setup(ctx)
    stream = request_stream(tr, ctx.args.seed, cfg.vocab_size)
    buckets, widths = serving.shapes(engine, stream)
    core.log(f"mesh {tr['mesh']}, {engine.max_slots} slots, backlog "
             f"{tr['backlog']}, prefill buckets {buckets}, decode widths "
             f"{widths}")
    serving.warm_up(engine, buckets, widths)
    seconds = ctx.args.seconds
    if ctx.args.trace:
        seconds = min(seconds, float(tr["trace_window_s"]))
    t_begin = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        times, results, n_sub, record = serve(
            ctx, engine, stream, float(tr["prelude_s"]), seconds)
    for msg in serving.bad_warnings(caught):
        core.log(f"warning: {msg}")
    if n_sub >= len(stream):
        core.log("warning: the stream ran out; raise the traffic's stream")

    lo, hi = ctx.window
    gaps, served = [], set()
    for rid, ts in times.items():
        for a, b in zip(ts, ts[1:]):
            if lo <= b < hi:
                gaps.append((b - a) * 1e3)
        if any(lo <= t < hi for t in ts):
            served.add(rid)
    failed = sum(1 for rid in served if rid in results
                 and results[rid].finish_reason != "length")
    tokens = sum(1 for ts in times.values() for t in ts if lo <= t < hi)
    qs = (50, 90, 95, 99)
    core.log(f"window: {len(served)} requests served, {tokens} tokens "
             f"({tokens / (hi - lo):.1f}/s), {len(gaps)} gaps, ms " +
             json.dumps({f"itl_p{q}": serving.percentile(gaps, q)
                         for q in qs}))
    bad, steps = routing_check(t_begin, hi)
    core.log(f"routing: {steps} steps recorded, {bad} with the ranks' rows "
             f"apart from the rows routed")
    e2e = {"itl_p95_ms": serving.percentile(gaps, 95)}
    ctx.memory_peak = core.memory_peak_bytes(ctx.devices)
    del engine

    done = {rid: r for rid, r in results.items()
            if r.finish_reason == "length"
            and len(r.tokens) == len(times[rid])}
    checks = serving.check_served(ctx, params, done)
    checks.append(core.Check("routed_rows_dropped", float(bad), 0.0, "=="))
    return core.Outcome(attempted=len(served), failed=failed,
                        end_to_end=e2e, checks=checks,
                        counts={"window_tokens": tokens, **record})
