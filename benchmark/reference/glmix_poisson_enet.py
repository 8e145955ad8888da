"""Plain reference of a Poisson GLMix fit under an elastic net: block
coordinate descent, every block solved to ITS optimum by a method that is
not the program's.

Same semantics as the configuration states: coordinates in the configured
order, each trained against the summed scores of the others, ``cd_passes``
passes from zero coefficients. A coordinate's objective is Photon's, a sum
and not a mean:

    Σ rows (exp(z) − y·z)  +  λ·(α·‖w‖₁ + (1 − α)/2·‖w‖²),   z = x·w + offset

with ``reg_weight`` λ and ``alpha`` α from the coordinate, so l1 = α·λ and
l2 = (1 − α)·λ, neither on the intercept.

- Fixed effect: **proximal Newton** (glmnet's). At the current w the
  weighted Gram matrix Xᵀ·diag(exp(z))·X and the gradient come from one pass
  over X in row blocks; the quadratic model plus the elastic net is
  minimised by cyclic coordinate descent with soft-thresholding on that
  d-wide system until it no longer moves; the step is halved until the true
  objective does not rise; the solve stops when the KKT residual
  (|g_j + l2·w_j| − l1 where w_j = 0 and that is positive,
  g_j + l2·w_j + l1·sign(w_j) elsewhere) is under ``KKT_TOL`` of its value at
  the start. A zero is exact because the threshold set it, not rounding.
  The non-smooth block has no Newton step that is the optimum's: with l1 = 0
  the same code is plain damped Newton.
- Random effects: damped Newton per entity with step halving, on the padded
  (entities, n_max) grouping of ``reference/glmix.py`` (the users here are
  even), to a gradient norm under ``GRAD_TOL`` of the entity's first.

float32 throughout, every matrix product at ``Precision.HIGHEST``. An
overflowing trial (``exp`` of a large margin) reads inf and is a rejected
trial. ``control=True`` is the comparison's control: every matrix product's
operands cut to bfloat16 (float32 accumulation). Imports nothing of the
program and nothing of the other references.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 1 << 17
SLAB_ROUND = 128
KKT_TOL = 1e-6
GRAD_TOL = 1e-6
CD_SWEEPS = 500


def _mm(spec: str, a, b, control: bool):
    if control:
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _poisson(z, y):
    return jnp.exp(z) - y * z


def penalties(coordinate: dict):
    """``(l1, l2)`` of a coordinate: α·λ and (1 − α)·λ."""
    weight, alpha = float(coordinate["reg_weight"]), float(coordinate["alpha"])
    return alpha * weight, (1.0 - alpha) * weight


def _masked(d: int, value: float, intercept):
    v = jnp.full((d,), value, jnp.float32)
    return v if intercept is None else v.at[intercept].set(0.0)


# ---- fixed effect: proximal Newton -------------------------------------------


@functools.partial(jax.jit, static_argnames=("control",))
def _fe_system(w, x, y, offset, control: bool):
    """Σ loss, its gradient and its Hessian at ``w``, accumulated over row
    blocks (the penalties are the caller's)."""
    n, d = x.shape
    rb = math.gcd(n, ROW_BLOCK)
    nb = n // rb

    def block(carry, xs):
        f, g, h = carry
        xb, yb, ob = xs
        z = _mm("nd,d->n", xb, w, control) + ob
        mu = jnp.exp(z)
        f = f + jnp.sum(mu - yb * z)
        g = g + _mm("nd,n->d", xb, mu - yb, control)
        h = h + _mm("nd,ne->de", xb * mu[:, None], xb, control)
        return (f, g, h), None

    init = (jnp.float32(0.0), jnp.zeros((d,), jnp.float32),
            jnp.zeros((d, d), jnp.float32))
    (f, g, h), _ = jax.lax.scan(
        block, init, (x.reshape(nb, rb, d), y.reshape(nb, rb),
                      offset.reshape(nb, rb)))
    return f, g, h


@functools.partial(jax.jit, static_argnames=("control",))
def _fe_value(w, x, y, offset, l1, l2, control: bool):
    z = _mm("nd,d->n", x, w, control) + offset
    return (jnp.sum(_poisson(z, y)) + 0.5 * jnp.sum(l2 * w * w)
            + jnp.sum(l1 * jnp.abs(w)))


def _soft(v, t):
    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)


@functools.partial(jax.jit, static_argnames=("control",))
def _prox_cd(w0, g, h, l1, l2, control: bool):
    """argmin_w g·(w − w0) + ½(w − w0)ᵀH(w − w0) + ½Σ l2 w² + Σ l1 |w| by
    cyclic coordinate descent: coordinate j's minimiser in closed form is a
    soft threshold. ``r`` is the model's smooth gradient g + H(w − w0), kept
    up to date coordinate by coordinate and taken afresh every sweep."""
    d = w0.shape[0]
    diag = jnp.diagonal(h)

    def coordinate(j, carry):
        w, r = carry
        u = _soft(diag[j] * w[j] - r[j], l1[j]) / (diag[j] + l2[j])
        return w.at[j].set(u), r + h[:, j] * (u - w[j])

    def sweep(state):
        w, _, k = state
        r = g + _mm("de,e->d", h, w - w0, control)
        w_new, _ = jax.lax.fori_loop(0, d, coordinate, (w, r))
        return w_new, jnp.max(jnp.abs(w_new - w)), k + 1

    def moving(state):
        w, moved, k = state
        return (moved > 2e-7 * jnp.max(jnp.abs(w))) & (k < CD_SWEEPS)

    w, _, sweeps = jax.lax.while_loop(
        moving, sweep, (w0, jnp.float32(jnp.inf), jnp.int32(0)))
    return w, sweeps


def kkt_residual(w, g, l1, l2):
    """Per coefficient, how far the optimality conditions of the smooth
    gradient ``g`` plus the elastic net are from holding at ``w``."""
    s = g + l2 * w
    return jnp.where(w != 0, s + l1 * jnp.sign(w),
                     jnp.maximum(jnp.abs(s) - l1, 0.0))


def solve_fixed(x, y, offset, l1: float, l2: float, intercept,
                control: bool = False, max_iter: int = 40, log=None):
    """argmin_w Σ poisson(x·w + offset, y) + ½ l2 ‖w‖² + l1 ‖w‖₁ (neither on
    the intercept) by proximal Newton."""
    d = x.shape[1]
    l1v, l2v = _masked(d, l1, intercept), _masked(d, l2, intercept)
    w = jnp.zeros((d,), jnp.float32)
    kkt0 = None
    for it in range(max_iter):
        _, g, h = _fe_system(w, x, y, offset, control)
        kkt = float(jnp.max(jnp.abs(kkt_residual(w, g, l1v, l2v))))
        kkt0 = kkt if kkt0 is None else kkt0
        if kkt <= KKT_TOL * kkt0:
            break
        target, sweeps = _prox_cd(w, g, h, l1v, l2v, control)
        f = float(_fe_value(w, x, y, offset, l1v, l2v, control))
        t = 1.0
        while True:  # step halving: an overflowing trial reads inf and is halved
            # At t = 1 the trial is the model's minimiser itself, zeros and all.
            w_try = target if t == 1.0 else w + t * (target - w)
            if float(_fe_value(w_try, x, y, offset, l1v, l2v, control)) <= f \
                    or t < 1e-3:
                break
            t *= 0.5
        moved = float(jnp.max(jnp.abs(w_try - w)))
        w = w_try
        if log:
            log(f"reference fixed effect it {it}: F={f:.6f} kkt={kkt:.3g} "
                f"(start {kkt0:.3g}) sweeps={int(sweeps)} t={t:g} moved={moved:.3g} "
                f"zeros={int(jnp.sum(w == 0))}")
        if moved <= 5e-7:
            break
    return w


# ---- random effects: damped Newton per entity ----------------------------------


def entity_rows(ids, entities: int):
    """(entities, n_max) row indices and a 0/1 mask grouping rows by entity
    (padding points at row 0 with mask 0)."""
    n = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    counts = jnp.bincount(ids, length=entities)
    # Rounded up so that seeds share a compiled shape; padding has mask 0.
    n_max = -(-max(int(jnp.max(counts)), 1) // SLAB_ROUND) * SLAB_ROUND
    starts = jnp.cumsum(counts) - counts
    sorted_ids = ids[order]
    slot = jnp.arange(n, dtype=jnp.int32) - starts[sorted_ids].astype(jnp.int32)
    rows = jnp.zeros((entities, n_max), jnp.int32).at[sorted_ids, slot].set(
        order.astype(jnp.int32))
    mask = jnp.zeros((entities, n_max), jnp.float32).at[sorted_ids, slot].set(1.0)
    return rows, mask


@functools.partial(jax.jit, static_argnames=("control",))
def _re_newton(w, xs, ys, offs, mask, lam, control: bool):
    """One damped Newton step for every entity of a slab at once; also the
    gradient norm it started from."""

    def value(wt):
        z = _mm("end,ed->en", xs, wt, control) + offs
        return (jnp.sum(_poisson(z, ys) * mask, axis=1)
                + 0.5 * jnp.sum(lam * wt * wt, axis=1))

    z = _mm("end,ed->en", xs, w, control) + offs
    mu = jnp.exp(z) * mask
    g = _mm("end,en->ed", xs, mu - ys * mask, control) + lam * w
    h = _mm("end,enf->edf", xs * mu[..., None], xs, control)
    h = h + jnp.diag(lam + 1e-6)
    step = jnp.linalg.solve(h, g[..., None])[..., 0]
    f0 = value(w)
    t = jnp.ones((w.shape[0],), jnp.float32)
    for _ in range(8):  # per-entity step halving; inf or NaN is "worse"
        worse = ~(value(w - t[:, None] * step) <= f0)
        t = jnp.where(worse, 0.5 * t, t)
    better = value(w - t[:, None] * step) <= f0
    w_new = jnp.where(better[:, None], w - t[:, None] * step, w)
    return w_new, jnp.max(jnp.abs(w_new - w)), jnp.linalg.norm(g, axis=1)


def solve_random(x, y, offset, ids, entities: int, l2: float, intercept,
                 control: bool = False, max_iter: int = 40,
                 entity_block: int = 4096, log=None):
    """Per-entity argmin of Σ poisson + ½ l2 ‖w‖² over each entity's rows, in
    blocks of entities. Entities without rows keep zero coefficients."""
    d = x.shape[1]
    lam = _masked(d, l2, intercept)
    rows, mask = entity_rows(ids, entities)
    out = []
    for lo in range(0, entities, entity_block):
        r, m = rows[lo:lo + entity_block], mask[lo:lo + entity_block]
        xs = x[r] * m[..., None]
        ys, offs = y[r] * m, offset[r] * m
        w = jnp.zeros((r.shape[0], d), jnp.float32)
        g0 = None
        for it in range(max_iter):
            w, moved, gnorm = _re_newton(w, xs, ys, offs, m, lam, control)
            g0 = gnorm if g0 is None else g0
            if bool(jnp.all(gnorm <= GRAD_TOL * g0)) or float(moved) <= 1e-6:
                break
        if log:
            log(f"reference random effect entities {lo}..{lo + r.shape[0]}: "
                f"{it + 1} Newton iterations, last move {float(moved):.3g}")
        out.append(w)
    return jnp.concatenate(out, axis=0)


def _rowdot(x, w_rows):
    return jnp.sum(x * w_rows, axis=-1)


# ---- coordinate descent -----------------------------------------------------


def fit(config: dict, xf, shards: Dict, ids: Dict, y, entities: Dict[str, int],
        control: bool = False, log=None) -> Dict[str, jax.Array]:
    """Coefficients by coordinate id after ``cd_passes`` passes from zero."""
    coords: List[dict] = config["coordinates"]
    n = y.shape[0]
    scores = {c["id"]: jnp.zeros((n,), jnp.float32) for c in coords}
    model = {}
    for _ in range(int(config["cd_passes"])):
        for c in coords:
            cid = c["id"]
            others = sum(s for k, s in scores.items() if k != cid)
            l1, l2 = penalties(c)
            if c["type"] == "fixed":
                w = solve_fixed(xf, y, others, l1, l2, c.get("intercept"),
                                control, log=log)
                scores[cid] = _mm("nd,d->n", xf, w, control)
            else:
                if l1:
                    raise ValueError(f"{cid}: the reference's random effects "
                                     "take L2 alone")
                w = solve_random(shards[cid], y, others, ids[cid],
                                 entities[cid], l2, c.get("intercept"),
                                 control, log=log)
                scores[cid] = _rowdot(shards[cid], w[ids[cid]])
            model[cid] = w
    return model
