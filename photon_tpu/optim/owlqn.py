"""OWL-QN: Orthant-Wise Limited-memory Quasi-Newton for L1/elastic-net.

Parity target: reference photon-lib optimization/OWLQN.scala:39-70 (which
wraps breeze.optimize.OWLQN; supports mutable l1RegularizationWeight for
regularization sweeps — here ``GLMObjective.with_l1``).

Algorithm (Andrew & Gao 2007, public): minimize f(w) + λ‖w‖₁ by
  1. pseudo-gradient: subgradient choosing the orthant of steepest descent,
  2. L-BFGS two-loop direction from the smooth-curvature history,
  3. sign-align the direction with the negative pseudo-gradient,
  4. backtracking (Armijo on the regularized objective) with orthant
     projection: trial points are clipped to the orthant of the search point.
     A non-finite trial is a rejected trial; a search without an accepted
     trial keeps the iterate and ends with OBJECTIVE_NOT_IMPROVING.

Fully jittable: one ``lax.while_loop`` per optimize call, inner backtracking
as a nested while_loop. The intercept is excluded from the L1 term via the
``l1_mask`` argument (reference interceptOpt convention).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from photon_tpu.optim.common import (
    OptimizeResult,
    OptimizerConfig,
    REASON_MAX_ITERATIONS,
    REASON_NOT_CONVERGED,
    REASON_OBJECTIVE_NOT_IMPROVING,
    check_convergence,
)
from photon_tpu.optim.lbfgs import two_loop_direction

Array = jax.Array
ValueAndGrad = Callable[[Array], Tuple[Array, Array]]


def _pseudo_gradient(w: Array, g: Array, l1: Array) -> Array:
    """Steepest-descent subgradient of f + λ‖·‖₁ (λ per-coordinate)."""
    right = g + l1  # derivative approaching from the right (w→0⁺)
    left = g - l1  # from the left
    pg_zero = jnp.where(left > 0, left, jnp.where(right < 0, right, 0.0))
    return jnp.where(w > 0, g + l1, jnp.where(w < 0, g - l1, pg_zero))


def minimize_owlqn(
    value_and_grad: ValueAndGrad,
    w0: Array,
    l1_weight: float,
    config: OptimizerConfig = OptimizerConfig(),
    l1_mask: Optional[Array] = None,
) -> OptimizeResult:
    """Minimize f(w) + λ·‖mask∘w‖₁ where f is smooth (loss + L2 for
    elastic net, reference RegularizationContext L1/L2 split).

    Args:
      value_and_grad: smooth part only.
      l1_mask: optional 0/1 vector; 0 entries (e.g. intercept) are unpenalized.
    """
    m, max_iter, tol = config.memory, config.max_iter, config.tol
    d = w0.shape[0]
    dtype = w0.dtype
    l1 = jnp.full((d,), l1_weight, dtype)
    if l1_mask is not None:
        l1 = l1 * l1_mask

    def full_value(w):
        f, g = value_and_grad(w)
        return f + jnp.sum(l1 * jnp.abs(w)), f, g

    F0, f0, g0 = full_value(w0)
    pg0 = _pseudo_gradient(w0, g0, l1)
    pg0_norm = jnp.linalg.norm(pg0)

    hist_len = config.history_len
    state0 = dict(
        w=w0, F=F0, g=g0, it=jnp.int32(0),
        reason=jnp.int32(REASON_NOT_CONVERGED),
        s_hist=jnp.zeros((m, d), dtype),
        y_hist=jnp.zeros((m, d), dtype),
        rho_hist=jnp.zeros((m,), dtype),
        num_stored=jnp.int32(0),
        head=jnp.int32(0),
        evals=jnp.int32(1),
        loss_hist=jnp.full((hist_len,), F0, dtype),
        gnorm_hist=jnp.full((hist_len,), pg0_norm, dtype),
    )

    def cond(st):
        return (st["reason"] == REASON_NOT_CONVERGED) & (st["it"] < max_iter)

    def body(st):
        w, F, g = st["w"], st["F"], st["g"]
        pg = _pseudo_gradient(w, g, l1)
        p = two_loop_direction(
            pg, st["s_hist"], st["y_hist"], st["rho_hist"], st["num_stored"], st["head"]
        )
        # Sign alignment: zero out components that disagree with -pg.
        p = jnp.where(p * -pg > 0, p, 0.0)
        fallback = jnp.dot(p, pg) >= 0
        p = jnp.where(fallback, -pg, p)

        # Orthant: sign(w), or sign(-pg) where w == 0.
        xi = jnp.where(w != 0, jnp.sign(w), jnp.sign(-pg))

        dirderiv = jnp.dot(pg, p)
        init_step = jnp.where(
            st["num_stored"] == 0,
            1.0 / jnp.maximum(jnp.linalg.norm(p), 1e-12),
            1.0,
        ).astype(dtype)

        # Backtracking Armijo on the regularized objective with orthant projection.
        def armijo(alpha, Ft):
            return Ft <= F + 1e-4 * alpha * dirderiv

        def bt_cond(bs):
            alpha, Ft, _wt, _gt, evals = bs
            return (~armijo(alpha, Ft)) & (evals < config.max_line_search_evals)

        def bt_body(bs):
            alpha, _Ft, _wt, _gt, evals = bs
            alpha = alpha * 0.5
            wt = _orthant_project(w + alpha * p, xi)
            Ft, _ft, gt = full_value(wt)
            return alpha, Ft, wt, gt, evals + 1

        with jax.named_scope("line_search"):
            w1 = _orthant_project(w + init_step * p, xi)
            F1, _f1, g1 = full_value(w1)
            alpha, F_new, w_new, g_new, bt_evals = jax.lax.while_loop(
                bt_cond, bt_body, (init_step, F1, w1, g1, jnp.int32(1))
            )
        # A trial that overflowed (``exp`` of a Poisson margin in float32)
        # reads inf or NaN, never meets Armijo and is halved like any other
        # rejected trial. A search that spent its budget without an accepted
        # trial keeps the iterate and ends the solve: the last trial is not
        # a step.
        found = (
            armijo(alpha, F_new)
            & jnp.all(jnp.isfinite(w_new))
            & jnp.all(jnp.isfinite(g_new))
        )
        w_new = jnp.where(found, w_new, w)
        F_new = jnp.where(found, F_new, F)
        g_new = jnp.where(found, g_new, g)

        s = w_new - w
        y = g_new - g  # curvature pairs from the SMOOTH gradient (per OWL-QN)
        sy = jnp.dot(s, y)
        store = sy > 1e-12
        slot = (st["head"] + 1) % m
        s_hist = jnp.where(store, st["s_hist"].at[slot].set(s), st["s_hist"])
        y_hist = jnp.where(store, st["y_hist"].at[slot].set(y), st["y_hist"])
        rho_hist = jnp.where(
            store, st["rho_hist"].at[slot].set(1.0 / jnp.maximum(sy, 1e-30)), st["rho_hist"]
        )
        head = jnp.where(store, slot, st["head"])
        num_stored = jnp.where(store, jnp.minimum(st["num_stored"] + 1, m), st["num_stored"])

        it = st["it"] + 1
        pg_new = _pseudo_gradient(w_new, g_new, l1)
        pgn = jnp.linalg.norm(pg_new)
        reason = check_convergence(F_new, F, pgn, pg0_norm, tol, it, max_iter)
        reason = jnp.where(found, reason, REASON_OBJECTIVE_NOT_IMPROVING)
        return dict(
            w=w_new, F=F_new, g=g_new, it=it, reason=reason,
            s_hist=s_hist, y_hist=y_hist, rho_hist=rho_hist,
            num_stored=num_stored, head=head,
            evals=st["evals"] + bt_evals,
            loss_hist=st["loss_hist"].at[jnp.minimum(it, config.history_len - 1)].set(F_new),
            gnorm_hist=st["gnorm_hist"].at[jnp.minimum(it, config.history_len - 1)].set(pgn),
        )

    st = jax.lax.while_loop(cond, body, state0)
    idx = jnp.arange(config.history_len)
    pg_final = _pseudo_gradient(st["w"], st["g"], l1)
    loss_hist = jnp.where(idx <= st["it"], st["loss_hist"], st["F"])
    gnorm_hist = jnp.where(idx <= st["it"], st["gnorm_hist"], jnp.linalg.norm(pg_final))
    reason = jnp.where(
        st["reason"] == REASON_NOT_CONVERGED, REASON_MAX_ITERATIONS, st["reason"]
    )
    return OptimizeResult(
        w=st["w"], value=st["F"], grad_norm=jnp.linalg.norm(pg_final),
        iterations=st["it"], reason_code=reason,
        loss_history=loss_hist, grad_norm_history=gnorm_hist,
        evals=st["evals"],
    )


def _orthant_project(w: Array, xi: Array) -> Array:
    """Clip w to the orthant defined by xi (zero where signs disagree)."""
    return jnp.where(w * xi > 0, w, 0.0)
