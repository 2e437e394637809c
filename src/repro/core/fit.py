"""SGD-based non-uniform PWL fitting (paper Sec. IV).

Pipeline (faithful to the paper):
  1. init breakpoints uniformly over [a, b], values = exact f(p_i);
  2. Adam (lr=0.1, betas=(0.9, 0.999)) on the continuous MSE
     L_[a,b] = 1/(b-a) ∫ (f̂-f)² dx   (trapezoid quadrature on a dense grid),
     with a reduce-on-plateau LR schedule;
  3. heuristic escape from local minima: remove the breakpoint with minimal
     *removal loss*, re-insert at the midpoint of the segment with maximal
     *insertion loss* ℓ_i = (p_{i+1}-p_i)·L_[p_i,p_{i+1}], retrain at lower LR;
  4. iterate until the remove/insert pair stops changing (or max rounds).

Boundary condition: v_0 and v_{n-1} are *derived* from the asymptotes
(v_0 = m_l p_0 + c_l, v_{n-1} = m_r p_{n-1} + c_r) so the outer segments lie on
the asymptote lines; p_0 and p_{n-1} themselves stay learnable (paper Sec. IV).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import functions as F
from . import pwl


@dataclasses.dataclass
class FitConfig:
    lr: float = 0.1
    betas: tuple[float, float] = (0.9, 0.999)
    eps: float = 1e-8
    max_steps: int = 4000
    eval_every: int = 50
    plateau_patience: int = 4      # evals without improvement before LR cut
    plateau_factor: float = 0.5
    min_lr: float = 1e-4
    rel_tol: float = 1e-5          # stop train() when improvement < rel_tol
    n_grid: int = 8192
    max_rounds: int = 8            # outer remove/insert rounds
    round_lr_decay: float = 0.5    # LR shrink per outer round
    init: str = "uniform"          # "uniform" (paper) | "curvature" (beyond-paper)
    curvature_gamma: float = 0.5   # breakpoint density ∝ |f''|^gamma
    seed: int = 0


def _effective_values(spec: F.FunctionSpec, p, v):
    """Apply the boundary condition: v0/vn derived from asymptotes (or edges)."""
    v0 = spec.fn(p[0]) if spec.left_is_edge else spec.m_left * p[0] + spec.c_left
    vn = spec.fn(p[-1]) if spec.right_is_edge else spec.m_right * p[-1] + spec.c_right
    return v.at[0].set(v0).at[-1].set(vn)


def _loss_fn(spec: F.FunctionSpec, x, fx, w, p, v, m_l, m_r):
    """Trapezoid MSE on grid x with weights w (∑w = 1 after /(b-a)).

    PRECONDITION: p is sorted.  The trainer re-sorts (p, v, Adam state) after
    every update *outside* the differentiated region, so no gradient flows
    through the sort."""
    vs = _effective_values(spec, p, v)
    y = pwl.eval_interp(x, p, vs, m_l, m_r)
    return jnp.sum(w * (y - fx) ** 2)


def _trapezoid_weights(x):
    dx = x[1:] - x[:-1]
    w = jnp.zeros_like(x)
    w = w.at[:-1].add(dx / 2).at[1:].add(dx / 2)
    return w / (x[-1] - x[0])


@functools.partial(jax.jit, static_argnames=("spec_name", "steps"))
def _adam_chunk(spec_name, steps, p, v, m_state, lr, x, fx, w, m_l, m_r):
    """Run `steps` Adam updates; jit'd once per (function, n)."""
    spec = F.get(spec_name)
    loss = functools.partial(_loss_fn, spec, x, fx, w)

    def body(carry, _):
        p, v, (mp, vp, mv, vv, t) = carry
        l, (gp, gv) = jax.value_and_grad(
            lambda p, v: loss(p, v, m_l, m_r), argnums=(0, 1)
        )(p, v)
        t = t + 1
        b1, b2 = 0.9, 0.999
        mp = b1 * mp + (1 - b1) * gp
        vp = b2 * vp + (1 - b2) * gp**2
        mv = b1 * mv + (1 - b1) * gv
        vv = b2 * vv + (1 - b2) * gv**2
        bc1 = 1 - b1**t
        bc2 = 1 - b2**t
        p = p - lr * (mp / bc1) / (jnp.sqrt(vp / bc2) + 1e-8)
        v = v - lr * (mv / bc1) / (jnp.sqrt(vv / bc2) + 1e-8)
        # keep breakpoints sorted (re-sort params + Adam state consistently)
        order = jnp.argsort(p)
        p, v = p[order], v[order]
        mp, vp = mp[order], vp[order]
        mv, vv = mv[order], vv[order]
        return (p, v, (mp, vp, mv, vv, t)), l

    (p, v, m_state), losses = jax.lax.scan(body, (p, v, m_state), None, length=steps)
    return p, v, m_state, losses[-1]


def _train(spec, p, v, lr, cfg: FitConfig, x, fx, w, m_l, m_r):
    """Adam until plateau; reduce-on-plateau LR schedule."""
    n = p.shape[0]
    m_state = (
        jnp.zeros(n), jnp.zeros(n), jnp.zeros(n), jnp.zeros(n), jnp.int32(0)
    )
    best = float("inf")
    best_pv = (p, v)
    stale = 0
    steps_done = 0
    cur_lr = lr
    while steps_done < cfg.max_steps and cur_lr >= cfg.min_lr:
        p, v, m_state, last = _adam_chunk(
            spec.name, cfg.eval_every, p, v, m_state, jnp.float32(cur_lr), x, fx, w, m_l, m_r
        )
        steps_done += cfg.eval_every
        last = float(last)
        if last < best * (1 - cfg.rel_tol):
            best, best_pv, stale = last, (p, v), 0
        else:
            stale += 1
            if stale >= cfg.plateau_patience:
                cur_lr *= cfg.plateau_factor
                stale = 0
    return best_pv[0], best_pv[1], best


def _removal_losses(spec, p, v, cfg, x, fx, w, m_l, m_r):
    """Loss after deleting breakpoint i, for each interior i (1..n-2)."""
    loss = functools.partial(_loss_fn, spec, x, fx, w)
    pn, vn = np.asarray(p), np.asarray(v)
    out = {}
    reduced_p, reduced_v = [], []
    idxs = list(range(1, len(pn) - 1))
    for i in idxs:
        reduced_p.append(np.delete(pn, i))
        reduced_v.append(np.delete(vn, i))
    if not idxs:
        return {}
    rp = jnp.asarray(np.stack(reduced_p))
    rv = jnp.asarray(np.stack(reduced_v))
    # lax.map (scan-based), not vmap: batched-operand gathers trip the broken
    # GatherDimensionNumbers in this jaxlib.
    losses = jax.lax.map(lambda pv: loss(pv[0], pv[1], m_l, m_r), (rp, rv))
    for k, i in enumerate(idxs):
        out[i] = float(losses[k])
    return out


def _insertion_losses(spec, p, v, cfg, x, fx, w, m_l, m_r):
    """ℓ_i^ins = ∫_{p_i}^{p_{i+1}} (f̂-f)² dx for each inner segment i."""
    order = jnp.argsort(p)
    ps = p[order]
    vs = _effective_values(spec, ps, v[order])
    y = pwl.eval_interp(x, ps, vs, m_l, m_r)
    err2 = (y - fx) ** 2 * w * (x[-1] - x[0])  # un-normalized integrand
    seg = jnp.clip(jnp.searchsorted(ps, x, side="right") - 1, 0, ps.shape[0] - 2)
    inside = (x >= ps[0]) & (x <= ps[-1])
    seg_loss = jax.ops.segment_sum(jnp.where(inside, err2, 0.0), seg, num_segments=ps.shape[0] - 1)
    return np.asarray(seg_loss)


def curvature_init(spec, n_breakpoints, lo, hi, gamma=0.5, n_grid=4096):
    """Beyond-paper init: equidistribute breakpoints w.r.t. |f''|^gamma.

    For PWL interpolation the per-segment L2 error scales ~ f''(x)^2 h^5, so
    the asymptotically optimal segment width is h ∝ |f''|^(-1/2), i.e. the
    breakpoint *density* ∝ |f''|^(1/2).  Starting from this layout (instead of
    uniform) typically lands within a few percent of the final MSE before any
    Adam step, cutting fit time and avoiding remove/insert rounds.
    """
    x = jnp.linspace(lo, hi, n_grid, dtype=jnp.float32)
    d2 = jax.vmap(jax.grad(jax.grad(lambda t: spec.fn(t).sum())))(x)
    dens = jnp.abs(d2) ** gamma + 1e-3 * jnp.max(jnp.abs(d2) ** gamma)
    cdf = jnp.cumsum(dens)
    cdf = (cdf - cdf[0]) / (cdf[-1] - cdf[0])
    targets = jnp.linspace(0.0, 1.0, n_breakpoints)
    p = jnp.interp(targets, cdf, x)
    # guarantee strict monotonicity (flat-CDF regions can collide breakpoints)
    p = jnp.maximum(p, p[0] + jnp.arange(n_breakpoints) * 1e-6)
    return p


@dataclasses.dataclass
class FitResult:
    table: pwl.PWLTable
    mse: float
    mae: float
    history: list
    n_breakpoints: int
    range: tuple[float, float]


def fit(
    spec_or_name,
    n_breakpoints: int,
    lo: Optional[float] = None,
    hi: Optional[float] = None,
    cfg: Optional[FitConfig] = None,
) -> FitResult:
    """Fit a non-uniform PWL table to `spec` on [lo, hi] (paper Sec. IV)."""
    spec = F.get(spec_or_name) if isinstance(spec_or_name, str) else spec_or_name
    cfg = cfg or FitConfig()
    if lo is None or hi is None:
        lo, hi = spec.default_range
    x = jnp.linspace(lo, hi, cfg.n_grid, dtype=jnp.float32)
    fx = spec.fn(x)
    w = _trapezoid_weights(x)

    if cfg.init == "curvature":
        p = curvature_init(spec, n_breakpoints, lo, hi, cfg.curvature_gamma)
    else:
        p = jnp.linspace(lo, hi, n_breakpoints, dtype=jnp.float32)
    v = spec.fn(p)
    m_l, m_r = pwl.boundary_slopes(spec, p)

    history = []
    p, v, best = _train(spec, p, v, cfg.lr, cfg, x, fx, w, m_l, m_r)
    history.append(("init_train", best))

    lr = cfg.lr * cfg.round_lr_decay
    last_move = None
    for rnd in range(cfg.max_rounds):
        rm = _removal_losses(spec, p, v, cfg, x, fx, w, m_l, m_r)
        if not rm:
            break
        i_rm = min(rm, key=rm.get)
        pn, vn = np.delete(np.asarray(p), i_rm), np.delete(np.asarray(v), i_rm)
        ins = _insertion_losses(spec, jnp.asarray(pn), jnp.asarray(vn), cfg, x, fx, w, m_l, m_r)
        i_ins = int(np.argmax(ins))
        move = (i_rm, i_ins)
        p_new = np.insert(pn, i_ins + 1, (pn[i_ins] + pn[i_ins + 1]) / 2)
        v_new = np.insert(vn, i_ins + 1, (vn[i_ins] + vn[i_ins + 1]) / 2)
        p2, v2, best2 = _train(
            spec, jnp.asarray(p_new), jnp.asarray(v_new), lr, cfg, x, fx, w, m_l, m_r
        )
        history.append((f"round{rnd}_rm{i_rm}_ins{i_ins}", best2))
        if best2 < best:
            p, v, best = p2, v2, best2
        if move == last_move:
            break
        last_move = move
        lr = max(lr * cfg.round_lr_decay, cfg.min_lr)

    # recompute boundary slopes at final boundary breakpoints (edge tangents move)
    m_l, m_r = pwl.boundary_slopes(spec, p)
    v_eff = _effective_values(spec, p, v)
    table = pwl.params_to_coeffs(p, v_eff, m_l, m_r, name=spec.name)
    return FitResult(
        table=table,
        mse=pwl.mse(table, spec, lo, hi, cfg.n_grid),
        mae=pwl.mae(table, spec, lo, hi, cfg.n_grid),
        history=history,
        n_breakpoints=n_breakpoints,
        range=(lo, hi),
    )
