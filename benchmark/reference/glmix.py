"""Plain reference of a GLMix fit: block coordinate descent, every block
solved to its optimum by exact (damped) Newton.

Same semantics as the configuration states: coordinates in the configured
order, each trained against the summed scores of the others, ``cd_passes``
passes from zero coefficients, L2 on every column but the intercept. Where
the program stops L-BFGS at a tolerance, the reference goes on to the
optimum; the gap that leaves is part of the measured lower reading.

float32 throughout, every matrix product at ``Precision.HIGHEST`` (on a TPU a
float32 product is otherwise one bfloat16 pass), the fixed effect's Hessian
accumulated over row blocks so that it fits beside the data.

``control=True`` is the comparison's control: the same mathematics with every
matrix product's operands cut to bfloat16 (float32 accumulation) — what
default matmul precision gives on the chip, and the step a later PR would be
tempted by. It imports nothing of the program either.
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


def _mm(spec: str, a, b, control: bool):
    if control:
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _logloss(z, y):
    return jnp.logaddexp(0.0, z) - y * z


def _lam(d: int, l2: float, intercept):
    lam = jnp.full((d,), l2, jnp.float32)
    return lam if intercept is None else lam.at[intercept].set(0.0)


# ---- fixed effect -----------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("control",))
def _fe_system(w, x, y, offset, lam, control: bool):
    """Objective, gradient and Hessian at ``w``, accumulated over row blocks."""
    n, d = x.shape
    rb = math.gcd(n, ROW_BLOCK)  # the largest power of two ≤ ROW_BLOCK dividing n
    nb = n // rb

    def block(carry, xs):
        f, g, h = carry
        xb, yb, ob = xs
        z = _mm("nd,d->n", xb, w, control) + ob
        p = jax.nn.sigmoid(z)
        f = f + jnp.sum(_logloss(z, yb))
        g = g + _mm("nd,n->d", xb, p - yb, control)
        h = h + _mm("nd,ne->de", xb * (p * (1.0 - p))[:, None], xb, control)
        return (f, g, h), None

    init = (jnp.float32(0.0), jnp.zeros((d,), jnp.float32),
            jnp.zeros((d, d), jnp.float32))
    (f, g, h), _ = jax.lax.scan(
        block, init, (x.reshape(nb, rb, d), y.reshape(nb, rb),
                      offset.reshape(nb, rb)))
    f = f + 0.5 * jnp.sum(lam * w * w)
    return f, g + lam * w, h + jnp.diag(lam)


@functools.partial(jax.jit, static_argnames=("control",))
def _fe_value(w, x, y, offset, lam, control: bool):
    z = _mm("nd,d->n", x, w, control) + offset
    return jnp.sum(_logloss(z, y)) + 0.5 * jnp.sum(lam * w * w)


def solve_fixed(x, y, offset, l2: float, intercept, control: bool = False,
                max_iter: int = 25, log=None):
    """argmin_w Σ logloss(x·w + offset, y) + ½ Σ l2 w² by damped Newton."""
    d = x.shape[1]
    lam = _lam(d, l2, intercept)
    w = jnp.zeros((d,), jnp.float32)
    for it in range(max_iter):
        f, g, h = _fe_system(w, x, y, offset, lam, control)
        step = jnp.linalg.solve(h, g)
        t = 1.0
        while True:  # step halving: Newton from zero can overshoot
            w_try = w - t * step
            if float(_fe_value(w_try, x, y, offset, lam, control)) <= float(f) \
                    or t < 1e-3:
                break
            t *= 0.5
        moved = float(jnp.max(jnp.abs(w_try - w)))
        w = w_try
        if log:
            log(f"reference fixed effect it {it}: f={float(f):.6f} "
                f"|g|max={float(jnp.max(jnp.abs(g))):.3g} moved={moved:.3g}")
        if moved <= 5e-7:
            break
    return w


# ---- random effects ---------------------------------------------------------


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
    """One damped Newton step for every entity of a slab at once."""

    def value(wt):
        z = _mm("end,ed->en", xs, wt, control) + offs
        return (jnp.sum(_logloss(z, ys) * mask, axis=1)
                + 0.5 * jnp.sum(lam * wt * wt, axis=1))

    z = _mm("end,ed->en", xs, w, control) + offs
    p = jax.nn.sigmoid(z)
    g = _mm("end,en->ed", xs, (p - ys) * mask, control) + lam * w
    h = _mm("end,enf->edf", xs * (p * (1.0 - p) * mask)[..., None], xs, control)
    h = h + jnp.diag(lam + 1e-6)
    step = jnp.linalg.solve(h, g[..., None])[..., 0]
    f0 = value(w)
    t = jnp.ones((w.shape[0],), jnp.float32)
    for _ in range(4):  # per-entity step halving
        worse = value(w - t[:, None] * step) > f0
        t = jnp.where(worse, 0.5 * t, t)
    w_new = w - t[:, None] * step
    return w_new, jnp.max(jnp.abs(w_new - w))


def solve_random(x, y, offset, ids, entities: int, l2: float, intercept,
                 control: bool = False, max_iter: int = 25,
                 entity_block: int = 4096, log=None):
    """Per-entity argmin of the same objective over each entity's rows, in
    blocks of entities. Entities without rows keep zero coefficients."""
    d = x.shape[1]
    lam = _lam(d, l2, intercept)
    rows, mask = entity_rows(ids, entities)
    out = []
    for lo in range(0, entities, entity_block):
        r, m = rows[lo:lo + entity_block], mask[lo:lo + entity_block]
        xs = x[r] * m[..., None]
        ys, offs = y[r] * m, offset[r] * m
        w = jnp.zeros((r.shape[0], d), jnp.float32)
        for it in range(max_iter):
            w, moved = _re_newton(w, xs, ys, offs, m, lam, control)
            if float(moved) <= 1e-6:
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
            if c["type"] == "fixed":
                w = solve_fixed(xf, y, others, c["l2"], c.get("intercept"),
                                control, log=log)
                scores[cid] = _mm("nd,d->n", xf, w, control)
            else:
                w = solve_random(shards[cid], y, others, ids[cid],
                                 entities[cid], c["l2"], c.get("intercept"),
                                 control, log=log)
                scores[cid] = _rowdot(shards[cid], w[ids[cid]])
            model[cid] = w
    return model
