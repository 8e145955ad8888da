"""Plain reference of a GLMix fit on a squared loss: block coordinate
descent, every block solved to its optimum by the normal equations.

Same semantics as the configuration states: coordinates in the configured
order, each trained against the summed scores of the others, ``cd_passes``
passes from zero coefficients. A coordinate's objective is Photon's, a sum
and not a mean:

    ½ Σ rows (x·w + offset − y)²  +  ½ λ ‖w‖²   (the intercept unpenalised)

with ``l2`` λ from the coordinate. Its minimiser solves
(XᵀX + λM) w = Xᵀ(y − offset), M the diagonal 0/1 mask of the penalised
columns.

- Fixed effect: XᵀX + λM and Xᵀ(y − offset) accumulated over row blocks,
  one Cholesky solve of the d × d system, then Newton refinement steps
  (w ← w − (XᵀX + λM)⁻¹·g with g the gradient taken afresh over every row)
  for as long as ‖g‖ falls: the answer is the optimum to the float32
  accuracy of the gradient, not of the Gram matrix's factor.
- Random effects: the same per entity, every entity at once. The rows are
  sorted by entity once, and each entity's Gram matrix and right-hand side
  are ``jax.ops.segment_sum``s over its rows (outer products a row block at
  a time), as in ``reference/glmix_ragged.py``: no padded grouping, every
  row of every entity in its entity's sums. Entities without rows keep zero
  coefficients.

Departures from Photon: the program's blocks are solved by TRON (fixed
effect) and by batched Newton (random effects) to their stopping rules;
here each block is solved exactly, so what separates the two is the
program's stopping rule and its arithmetic. No kernels, no solve cache, no
padding, no down-sampling.

float32 throughout. The fixed effect's matrix products run at
``Precision.HIGHEST``; the per-entity sums are elementwise products and
float32 additions, which no matrix unit touches. ``control=True`` is the
comparison's control: every product's operands cut to bfloat16 first
(float32 accumulation). Imports nothing of the program and nothing of the
other references.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 1 << 17      # fixed effect: rows a Gram block
OUTER_BLOCK = 1 << 15    # random effects: rows whose (d, d) outer products live at once
REFINE_STEPS = 10        # at most; refinement stops once |g| no longer falls
RIDGE_FLOOR = 1e-6       # in the factor alone: an entity without rows stays solvable


def _mm(spec: str, a, b, control: bool):
    if control:
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _cut(a, control: bool):
    """An operand of an elementwise product as the control's matrix unit
    would read it."""
    return a.astype(jnp.bfloat16).astype(jnp.float32) if control else a


def _lam(d: int, l2: float, intercept):
    lam = jnp.full((d,), l2, jnp.float32)
    return lam if intercept is None else lam.at[intercept].set(0.0)


# ---- fixed effect ---------------------------------------------------------------


def _row_blocks(n: int) -> int:
    return math.gcd(n, ROW_BLOCK)


@functools.partial(jax.jit, static_argnames=("control",))
def _fe_system(x, target, lam, control: bool):
    """XᵀX + λM and Xᵀ·target, accumulated over row blocks."""
    n, d = x.shape
    rb = _row_blocks(n)

    def block(carry, xs):
        h, b = carry
        xb, tb = xs
        return (h + _mm("nd,ne->de", xb, xb, control),
                b + _mm("nd,n->d", xb, tb, control)), None

    (h, b), _ = jax.lax.scan(
        block, (jnp.zeros((d, d), jnp.float32), jnp.zeros((d,), jnp.float32)),
        (x.reshape(n // rb, rb, d), target.reshape(n // rb, rb)))
    return h + jnp.diag(lam), b


@functools.partial(jax.jit, static_argnames=("control",))
def _fe_grad(w, x, target, lam, control: bool):
    """Xᵀ(X·w − target) + λMw, over row blocks."""
    n, d = x.shape
    rb = _row_blocks(n)

    def block(g, xs):
        xb, tb = xs
        r = _mm("nd,d->n", xb, w, control) - tb
        return g + _mm("nd,n->d", xb, r, control), None

    g, _ = jax.lax.scan(block, jnp.zeros((d,), jnp.float32),
                        (x.reshape(n // rb, rb, d), target.reshape(n // rb, rb)))
    return g + lam * w


def solve_fixed(x, y, offset, l2: float, intercept, control: bool = False,
                log=None):
    """argmin_w ½‖x·w + offset − y‖² + ½ Σ l2 w² (not on the intercept)."""
    d = x.shape[1]
    lam = _lam(d, l2, intercept)
    target = y - offset
    h, b = _fe_system(x, target, lam, control)
    factor = jax.scipy.linalg.cho_factor(h)
    w = jax.scipy.linalg.cho_solve(factor, b)
    g = _fe_grad(w, x, target, lam, control)
    gnorm = float(jnp.linalg.norm(g))
    norms = [gnorm]
    for _ in range(REFINE_STEPS):
        w_try = w - jax.scipy.linalg.cho_solve(factor, g)
        g_try = _fe_grad(w_try, x, target, lam, control)
        norm_try = float(jnp.linalg.norm(g_try))
        if not norm_try < gnorm:
            break
        w, g, gnorm = w_try, g_try, norm_try
        norms.append(gnorm)
    if log:
        log(f"reference fixed effect: |g| {' -> '.join(f'{v:.3g}' for v in norms)} "
            f"({len(norms) - 1} refinement steps)")
    return w


# ---- random effects: segment sums over rows sorted by entity ----------------


def sort_by_entity(x, ids):
    """``(order, xs, sid)``: the rows' order by entity (stable), the features
    in it, and each sorted row's entity. Done once a coordinate."""
    order = jnp.argsort(ids, stable=True)
    return order, x[order], ids[order]


@functools.partial(jax.jit, static_argnames=("entities", "control"))
def _re_gram(xs, sid, lam, entities: int, control: bool):
    """Every entity's Xᵀ_eX_e + λM (+ a floor), its outer products a row
    block at a time; rows padded up to whole blocks point past the last
    entity and are dropped."""
    n, d = xs.shape
    xc = _cut(xs, control)
    nb = -(-n // OUTER_BLOCK)
    pad = nb * OUTER_BLOCK - n
    xb = jnp.pad(xc, ((0, pad), (0, 0))).reshape(nb, OUTER_BLOCK, d)
    sb = jnp.pad(sid, (0, pad), constant_values=entities).reshape(nb, OUTER_BLOCK)

    def block(h, xs_):
        x_, s_ = xs_
        return h + jax.ops.segment_sum(x_[:, :, None] * x_[:, None, :], s_,
                                       entities, indices_are_sorted=True), None

    h, _ = jax.lax.scan(block, jnp.zeros((entities, d, d), jnp.float32), (xb, sb))
    return h + jnp.diag(lam + RIDGE_FLOOR)


@functools.partial(jax.jit, static_argnames=("entities", "control"))
def _re_grad(w, xs, sid, target, lam, entities: int, control: bool):
    """Every entity's Xᵀ_e(X_e·w_e − target_e) + λM w_e."""
    xc = _cut(xs, control)
    r = jnp.sum(xc * _cut(w, control)[sid], axis=-1) - target
    return (jax.ops.segment_sum(xc * _cut(r, control)[:, None], sid, entities,
                                indices_are_sorted=True)
            + lam * w)


@jax.jit
def _re_solve(factor, g):
    return jax.scipy.linalg.cho_solve((factor, True), g[..., None])[..., 0]


def solve_random(grouped, y, offset, entities: int, l2: float, intercept,
                 control: bool = False, log=None):
    """Per-entity argmin of the same objective over ALL of each entity's
    rows, by the normal equations and the same refinement, all entities at
    once."""
    order, xs, sid = grouped
    d = xs.shape[1]
    lam = _lam(d, l2, intercept)
    target = (y - offset)[order]
    factor = jnp.linalg.cholesky(_re_gram(xs, sid, lam, entities, control))
    # The right-hand side Xᵀ_e·target_e, as the gradient at zero of the
    # objective whose target is −target.
    w = _re_solve(factor, _re_grad(jnp.zeros((entities, d), jnp.float32), xs, sid,
                                   -target, lam, entities, control))
    g = _re_grad(w, xs, sid, target, lam, entities, control)
    gnorm = float(jnp.linalg.norm(g))
    norms = [gnorm]
    for _ in range(REFINE_STEPS):
        w_try = w - _re_solve(factor, g)
        g_try = _re_grad(w_try, xs, sid, target, lam, entities, control)
        norm_try = float(jnp.linalg.norm(g_try))
        if not norm_try < gnorm:
            break
        w, g, gnorm = w_try, g_try, norm_try
        norms.append(gnorm)
    if log:
        log(f"reference random effect, {entities} entities over {xs.shape[0]} rows: "
            f"|g| {' -> '.join(f'{v:.3g}' for v in norms)}")
    return w


def _rowdot(x, w_rows):
    return jnp.sum(x * w_rows, axis=-1)


# ---- coordinate descent -----------------------------------------------------


def fit(config: dict, xf, shards: Dict, ids: Dict, y, entities: Dict[str, int],
        control: bool = False, log=None) -> Dict[str, jax.Array]:
    """Coefficients by coordinate id after ``cd_passes`` passes from zero."""
    coords: List[dict] = config["coordinates"]
    n = y.shape[0]
    scores = {c["id"]: jnp.zeros((n,), jnp.float32) for c in coords}
    grouped = {c["id"]: sort_by_entity(shards[c["id"]], ids[c["id"]])
               for c in coords if c["type"] == "random"}
    model = {}
    for _ in range(int(config["cd_passes"])):
        for c in coords:
            cid = c["id"]
            others = sum(s for k, s in scores.items() if k != cid)
            if c["type"] == "fixed":
                w = solve_fixed(xf, y, others, c["l2"], c.get("intercept"),
                                control, log=log)
                scores[cid] = _mm("nd,d->n", xf, w, control)
            else:
                w = solve_random(grouped[cid], y, others, entities[cid],
                                 c["l2"], c.get("intercept"), control, log=log)
                scores[cid] = _rowdot(shards[cid], w[ids[cid]])
            model[cid] = w
    return model
