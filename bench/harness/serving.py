"""What the serving drivers share: the program's configuration and engine,
the warm-up of the cell's own shapes, per-request timing, and the check of
served tokens against the plain reference."""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from . import core, weights

# configuration keys of the file -> fields of the program's ModelConfig
_SIZES = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta"}


def program_config(config: dict):
    """The program's ModelConfig for a configuration file: its ``program``
    block names the program's architecture and settings, and every size the
    file states must be the program's."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = get_config(prog["arch"], **prog.get("settings", {}))
    mism = {k: (config[k], getattr(cfg, f)) for k, f in _SIZES.items()
            if k in config and config[k] != getattr(cfg, f)}
    if cfg.d_ff != config["intermediate_size"]:
        mism["intermediate_size"] = (config["intermediate_size"], cfg.d_ff)
    if cfg.tie_embeddings != config["tie_word_embeddings"]:
        mism["tie_word_embeddings"] = (config["tie_word_embeddings"],
                                       cfg.tie_embeddings)
    if mism:
        raise core.Refused(f"configuration file and program differ: {mism}")
    return cfg


def make_params(cfg, seed: int):
    """The benchmark's weights in the program's layout."""
    from repro.models import Model

    return weights.make(Model(cfg).param_structs(), seed)


def make_engine(cfg, params, traffic: dict):
    from repro.models import Model
    from repro.serving import PagedServingEngine

    e = traffic["engine"]
    return PagedServingEngine(
        Model(cfg), params, max_slots=e["max_slots"],
        page_size=e["page_size"], max_context=e["max_context"],
        num_pages=e["num_pages"], policy=e["policy"])


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def shapes(engine, requests: list[dict]) -> tuple[list[int], list[int]]:
    """The prefill buckets and decode page-table widths these requests can
    reach, by the engine's bucketing rules."""
    ps = engine.page_size
    buckets = sorted({max(ps, _pow2(len(r["prompt"]))) for r in requests})
    deepest = max(len(r["prompt"]) + r["max_new_tokens"] for r in requests)
    top = min(_pow2(-(-deepest // ps)), engine.max_cols)
    widths = sorted({min(1 << i, top) for i in range(top.bit_length() + 1)})
    return buckets, widths


def warm_up(engine, buckets: list[int], widths: list[int]) -> None:
    """Compile (or fetch from the cache) exactly the programs these shapes
    use, through the engine's own jitted steps; their outputs are dropped."""
    import jax
    import jax.numpy as jnp

    ps, slots = engine.page_size, engine.max_slots
    for b in buckets:
        toks = jnp.zeros((1, b), jnp.int32)
        row = jnp.asarray(np.arange(1, b // ps + 1, dtype=np.int32)[None])
        out = engine._fns["prefill"](engine.params, toks, engine.cache, row,
                                     jnp.asarray([b], jnp.int32))
        jax.block_until_ready(out)
        del out
    for w in widths:
        out = engine._fns["decode"](
            engine.params, jnp.zeros((slots, 1), jnp.int32), engine.cache,
            jnp.zeros((slots, w), jnp.int32), jnp.zeros((slots,), jnp.int32))
        jax.block_until_ready(out)
        del out


@dataclasses.dataclass
class Timing:
    """Host clock times of one request: when it was due, submitted,
    admitted, and when the host held each of its tokens."""

    due: float
    submitted: Optional[float] = None
    admitted: Optional[float] = None
    tokens: list = dataclasses.field(default_factory=list)


def percentile(values, q: float) -> float:
    """The q-th percentile (linear between order statistics)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def bad_warnings(caught) -> list[str]:
    """Warnings that mean the program left the fused path it was planned on."""
    keys = ("falling back", "uniform-breakpoint", "non-finite")
    return [str(w.message) for w in caught
            if any(k in str(w.message) for k in keys)]


# ---------------------------------------------------------------------------
# correctness: served tokens against the plain reference


def sample_finished(results: dict, want_tokens: int, seed: int,
                    min_requests: int = 1) -> list:
    """Finished requests to compare, drawn from the seed: the one with the
    most positions first, then others until ``want_tokens`` served tokens
    and ``min_requests`` requests are in the sample."""
    from .traffic import rng_for

    done = sorted(results.values(), key=lambda r: r.request_id)
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    order = rng_for(seed, 7).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= want_tokens and len(out) >= min_requests:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def served_gaps(reference, config: dict, params, results: list,
                precision: str = "f32", pick: str = "served") -> np.ndarray:
    """For each served token of each result: how far the reference's logit
    of that token lies below the reference's best at that position, in
    units of the standard deviation of the reference's logits there (so the
    number reads alike at any width or vocabulary).

    ``pick="control"`` reads instead the token that the reference computed
    at ``precision`` puts first (the control: it need not decode).  Returns
    all the gaps, in order."""
    gaps = []
    for r in results:
        seq = list(r.prompt) + list(r.tokens[:-1])
        p, n = len(r.prompt), len(seq)
        # pad to a multiple of 512 so that few lengths compile; causal
        # attention keeps the padding out of the positions read
        seq = seq + [0] * (-n % 512)
        ref = np.asarray(reference.logits(config, params, seq, "f32"))
        ref = ref[p - 1:n]
        if pick == "served":
            tok = np.asarray(r.tokens)
        else:
            low = np.asarray(reference.logits(config, params, seq, precision))
            tok = np.argmax(low[p - 1:n], axis=-1)
        best = ref.max(axis=-1)
        sd = ref.std(axis=-1)
        gaps.append((best - ref[np.arange(len(tok)), tok]) / sd)
    return np.concatenate(gaps) if gaps else np.zeros(0)


def check_served(ctx, params, done: dict) -> list[core.Check]:
    """Served tokens of a sample of the finished requests against the plain
    reference: the widest gap by which a served token's reference logit
    lies below the reference's best, in standard deviations of the
    reference's logits at that position."""
    ref = core.load_module(core.BENCH / "references" /
                           f"{ctx.config['reference']}.py")
    lim = ctx.traffic["check"]
    sample = sample_finished(done, lim["served_tokens"], ctx.args.seed,
                             lim["min_requests"])
    gaps = served_gaps(ref, ctx.config, params, sample)
    n = int(gaps.size)
    core.log(f"check: {len(sample)} requests, {n} served tokens compared")
    worst = float(gaps.max()) if n else float("nan")
    return [core.Check("served_gap_sd_max", worst, lim["gap_limit"]),
            core.Check("served_tokens_compared", float(n), lim["min_tokens"],
                       ">=")]
