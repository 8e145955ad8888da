"""Plain reference of a GLMix fit on uneven entities: the block coordinate
descent of ``reference/glmix.py`` with no padded grouping at all.

``glmix.py`` gathers every entity's rows into an (entities, n_max) grid,
which is the layout question the program answers with its block plan; on a
heavy-tailed population that grid is entities x the largest user's rows (14
GB of indices at 8192 users, one of them with 438,000 rows). Here the rows
are sorted by entity once and every per-entity sum is a
``jax.ops.segment_sum`` over them: the gradient and the Hessian of every
entity's objective at once, the Hessian's (rows, d, d) outer products taken
a row block at a time so they fit beside the data. Nothing here knows a
layout, so nothing here can share a layout's fault.

Same semantics as the configuration states: coordinates in the configured
order, each trained against the summed scores of the others, ``cd_passes``
passes from zero, L2 on every column but the intercept, every row of every
entity in its entity's sums, entities without rows left at zero. Each block
is solved to its optimum by damped Newton with per-entity step halving.

float32 throughout. The fixed effect's matrix products run at
``Precision.HIGHEST``; the per-entity sums are elementwise products and
float32 additions, which no matrix unit touches. ``control=True`` is the
comparison's control: every product's operands cut to bfloat16 first
(float32 accumulation), as in ``glmix.py``. Imports nothing of the program
and nothing of ``glmix.py``; the fixed-effect solve is a copy of its.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 1 << 17      # fixed effect: rows a Hessian block
OUTER_BLOCK = 1 << 15    # random effects: rows whose (d, d) outer products live at once


def _mm(spec: str, a, b, control: bool):
    if control:
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _cut(a, control: bool):
    """An operand of an elementwise product as the control's matrix unit
    would read it."""
    return a.astype(jnp.bfloat16).astype(jnp.float32) if control else a


def _logloss(z, y):
    return jnp.logaddexp(0.0, z) - y * z


def _lam(d: int, l2: float, intercept):
    lam = jnp.full((d,), l2, jnp.float32)
    return lam if intercept is None else lam.at[intercept].set(0.0)


# ---- fixed effect (as in glmix.py) -------------------------------------------


@functools.partial(jax.jit, static_argnames=("control",))
def _fe_system(w, x, y, offset, lam, control: bool):
    """Objective, gradient and Hessian at ``w``, accumulated over row blocks."""
    n, d = x.shape
    rb = math.gcd(n, ROW_BLOCK)
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


# ---- random effects: segment sums over rows sorted by entity ----------------


def _margins(w, xs, sid, offs, control: bool):
    return jnp.sum(_cut(xs, control) * _cut(w, control)[sid], axis=-1) + offs


def _values(w, xs, ys, offs, sid, lam, entities: int, control: bool):
    """Every entity's objective at its row of ``w``."""
    loss = _logloss(_margins(w, xs, sid, offs, control), ys)
    return (jax.ops.segment_sum(loss, sid, entities, indices_are_sorted=True)
            + 0.5 * jnp.sum(lam * w * w, axis=1))


@functools.partial(jax.jit, static_argnames=("entities", "control"))
def _re_newton(w, xs, ys, offs, sid, lam, entities: int, control: bool):
    """One damped Newton step for every entity at once. ``xs``, ``ys``,
    ``offs`` are the rows sorted by entity, ``sid`` their entity."""
    n, d = xs.shape
    p = jax.nn.sigmoid(_margins(w, xs, sid, offs, control))
    xc = _cut(xs, control)
    g = jax.ops.segment_sum(xc * _cut(p - ys, control)[:, None], sid, entities,
                            indices_are_sorted=True) + lam * w
    curve = p * (1.0 - p)
    # Rows padded up to whole blocks point past the last entity and are dropped.
    nb = -(-n // OUTER_BLOCK)
    pad = nb * OUTER_BLOCK - n
    xb = jnp.pad(xc, ((0, pad), (0, 0))).reshape(nb, OUTER_BLOCK, d)
    cb = jnp.pad(curve, (0, pad)).reshape(nb, OUTER_BLOCK)
    sb = jnp.pad(sid, (0, pad), constant_values=entities).reshape(nb, OUTER_BLOCK)

    def block(h, xs_):
        x_, c_, s_ = xs_
        outer = _cut(x_ * c_[:, None], control)[:, :, None] * x_[:, None, :]
        return h + jax.ops.segment_sum(outer, s_, entities,
                                       indices_are_sorted=True), None

    h, _ = jax.lax.scan(block, jnp.zeros((entities, d, d), jnp.float32),
                        (xb, cb, sb))
    h = h + jnp.diag(lam + 1e-6)
    step = jnp.linalg.solve(h, g[..., None])[..., 0]
    f0 = _values(w, xs, ys, offs, sid, lam, entities, control)
    # "Worse" by more than float32 can tell: a user of 400,000 rows has an
    # objective near 2e5, which float32 resolves to 0.016, and at its optimum
    # a full Newton step lowers it by less. Without the margin the halving
    # there is decided by rounding and the loop never settles.
    slack = 1e-6 * jnp.abs(f0)
    t = jnp.ones((entities,), jnp.float32)
    for _ in range(4):  # per-entity step halving
        worse = _values(w - t[:, None] * step, xs, ys, offs, sid, lam, entities,
                        control) > f0 + slack
        t = jnp.where(worse, 0.5 * t, t)
    w_new = w - t[:, None] * step
    return w_new, jnp.max(jnp.abs(w_new - w))


def sort_by_entity(x, ids):
    """``(order, xs, sid)``: the rows' order by entity (stable), the features
    in it, and each sorted row's entity. Done once a coordinate."""
    order = jnp.argsort(ids, stable=True)
    return order, x[order], ids[order]


def solve_random(grouped, y, offset, entities: int, l2: float, intercept,
                 control: bool = False, max_iter: int = 25, log=None):
    """Per-entity argmin of the same objective over ALL of each entity's
    rows. Entities without rows keep zero coefficients (their gradient is
    zero at zero)."""
    order, xs, sid = grouped
    d = xs.shape[1]
    lam = _lam(d, l2, intercept)
    ys, offs = y[order], offset[order]
    w = jnp.zeros((entities, d), jnp.float32)
    for it in range(max_iter):
        w, moved = _re_newton(w, xs, ys, offs, sid, lam, entities, control)
        if float(moved) <= 1e-6:
            break
    if log:
        log(f"reference random effect, {entities} entities over {xs.shape[0]} "
            f"rows: {it + 1} Newton iterations, last move {float(moved):.3g}")
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
