"""TRON: trust-region Newton with truncated conjugate-gradient inner solves.

Parity target: reference photon-lib optimization/TRON.scala (a LIBLINEAR
port; notice TRON.scala:16-51): outer trust-region loop with (η, σ) update
constants (TRON.scala:93-94), inner truncated CG solving the TR subproblem
with Hessian-vector products (truncatedConjugateGradientMethod:272-329);
defaults maxIter=15, tol=1e-5, ≤20 CG iterations (TRON.scala:251-256).

TPU-first design: the Hessian-vector product comes from a factory built once
an outer iteration (``GLMObjective.linearized_hvp``), no Hessian ever
materialized. The whole outer/inner loop nest is ``lax.while_loop``s inside a
single jitted program, so the ≤20 H·v products per outer iteration that cost
the reference ≤20 treeAggregate rounds (TRON.scala:287-326) cost zero host
round-trips here.

Trust-region constants: acceptance/band thresholds eta0=1e-4, eta1=0.25,
eta2=0.75 and shrink/grow factors sigma1=0.25, sigma3=4 (standard published
values). Unlike LIBLINEAR's exact radius schedule, the middle band
(eta1 <= rho < eta2) keeps the radius unchanged — the textbook TR update —
which avoids the geometric shrink that stalls runs whose rho hovers there.

Work is counted in passes over X (``eval_unit="x_passes"``): 2 for the
starting value and gradient; per outer iteration 1 for the margins the H·v
factory linearizes at, ``hvp_passes`` a product (each CG step's and ρ's:
2, a forward and a transpose pass, on the two-pass form; 1 on the one-read
kernel the fixed effect's route takes where the batch qualifies), and 2 for
the trial's value and gradient. A compiler may serve independent passes
with one read of X, and drop a pass nothing reads: compiled for a TPU
v5e, the squared loss's margins feed no curvature and go, and the
transposes of the trial's gradient and of ρ's two-pass product share one
read, so outside CG the two-pass form's 5 passes an iteration cost 3
reads, and so do the one-read form's 4. ``cg_steps`` and
``rejected_steps`` come out of the loop beside it. Inside the program the
CG loop runs under the scope ``cg`` and the trial's evaluation (its value,
gradient and ρ's product) under ``trial``.
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
    check_convergence,
    project_to_box,
)

Array = jax.Array
ValueAndGrad = Callable[[Array], Tuple[Array, Array]]
Hvp = Callable[[Array, Array], Array]

ETA0, ETA1, ETA2 = 1e-4, 0.25, 0.75
SIGMA1, SIGMA3 = 0.25, 4.0

# Passes over X: a value and gradient, a Hessian-vector product of the
# two-pass form, and the margins an H·v factory linearizes at.
VG_PASSES, HVP_PASSES, BUILD_PASSES = 2, 2, 1

TRON_DEFAULT_CONFIG = OptimizerConfig(max_iter=15, tol=1e-5)


def _truncated_cg(
    hvp: Callable[[Array], Array],
    g: Array,
    delta: Array,
    max_cg_iter: int,
    cg_tol: Array,
) -> Tuple[Array, Array, Array]:
    """Solve min_s g·s + ½ sᵀHs  s.t. ‖s‖ ≤ delta by truncated CG
    (Steihaug). Returns (step s, whether boundary was hit, #iterations —
    each iteration costs one H·v product, counted by the caller)."""
    d = g.shape[0]
    s0 = jnp.zeros((d,), g.dtype)
    r0 = -g
    p0 = r0

    def cond(carry):
        s, r, p, it, done = carry
        return (~done) & (it < max_cg_iter) & (jnp.linalg.norm(r) > cg_tol)

    def body(carry):
        s, r, p, it, _done = carry
        Hp = hvp(p)
        pHp = jnp.dot(p, Hp)
        rr = jnp.dot(r, r)
        # Negative curvature: follow p to the boundary.
        alpha = jnp.where(pHp > 0, rr / jnp.maximum(pHp, 1e-30), jnp.inf)
        s_next = s + alpha * p

        def to_boundary(s, p):
            # tau ≥ 0 with ‖s + tau p‖ = delta
            ss, sp, pp = jnp.dot(s, s), jnp.dot(s, p), jnp.dot(p, p)
            disc = jnp.sqrt(jnp.maximum(sp * sp + pp * (delta * delta - ss), 0.0))
            return (disc - sp) / jnp.maximum(pp, 1e-30)

        outside = (jnp.linalg.norm(s_next) >= delta) | (pHp <= 0)
        tau = to_boundary(s, p)
        s_bound = s + tau * p
        s_new = jnp.where(outside, s_bound, s_next)
        r_new = jnp.where(outside, r, r - alpha * Hp)
        beta = jnp.dot(r_new, r_new) / jnp.maximum(rr, 1e-30)
        p_new = r_new + beta * p
        return s_new, r_new, p_new, it + 1, outside

    s, r, _p, it, hit = jax.lax.while_loop(
        cond, body, (s0, r0, p0, jnp.int32(0), jnp.bool_(False))
    )
    return s, hit, it


def minimize_tron(
    value_and_grad: ValueAndGrad,
    hvp: Optional[Hvp],
    w0: Array,
    config: OptimizerConfig = TRON_DEFAULT_CONFIG,
    max_cg_iter: int = 20,
    box: Optional[Tuple[Array, Array]] = None,
    hvp_factory: Optional[Callable[[Array], Callable[[Array], Array]]] = None,
    hvp_passes: int = HVP_PASSES,
) -> OptimizeResult:
    """Trust-region Newton minimization.

    Args:
      value_and_grad: w -> (f, ∇f).
      hvp: (w, v) -> H(w)·v. May be None when ``hvp_factory`` is given.
      box: optional coefficient box, applied by projection per accepted step
        (reference applies OptimizationUtils projection each iteration).
      hvp_factory: w -> (v -> H(w)·v). Preferred over ``hvp``: built ONCE
        per outer iteration, so w-dependent state (margins, curvature
        multipliers) is shared across all ≤max_cg_iter CG products of that
        iteration instead of recomputed inside each one
        (GLMObjective.linearized_hvp).

    ``hvp_passes`` is what one product of ``hvp_factory``'s form reads of
    X: 2 for ``GLMObjective.linearized_hvp``'s two-pass form, 1 for its
    one-read kernel. ``result.evals`` counts the passes over a dense X
    with it (the module's docstring); a jvp-of-grad ``hvp`` pays another
    number of passes for the same work, which ``iterations`` and
    ``cg_steps`` state whatever the form.
    """
    if hvp_factory is None:
        if hvp is None:
            raise ValueError("minimize_tron needs hvp or hvp_factory")
        hvp_factory = lambda w: (lambda v: hvp(w, v))  # noqa: E731
    max_iter, tol = config.max_iter, config.tol
    dtype = w0.dtype

    w0 = project_to_box(w0, box)
    f0, g0 = value_and_grad(w0)
    g0_norm = jnp.linalg.norm(g0)
    delta0 = g0_norm

    hist_len = config.history_len
    state0 = dict(
        w=w0, f=f0, g=g0, delta=delta0,
        it=jnp.int32(0), reason=jnp.int32(REASON_NOT_CONVERGED),
        evals=jnp.int32(VG_PASSES),
        cg_steps=jnp.int32(0),
        rejected=jnp.int32(0),
        loss_hist=jnp.full((hist_len,), f0, dtype),
        gnorm_hist=jnp.full((hist_len,), g0_norm, dtype),
    )

    def cond(st):
        return (st["reason"] == REASON_NOT_CONVERGED) & (st["it"] < max_iter)

    def body(st):
        w, f, g, delta = st["w"], st["f"], st["g"], st["delta"]
        gnorm = jnp.linalg.norm(g)
        cg_tol = 0.1 * gnorm
        hv = hvp_factory(w)  # one build per outer iteration
        with jax.named_scope("cg"):
            s, _hit, cg_iters = _truncated_cg(hv, g, delta, max_cg_iter, cg_tol)

        w_trial = project_to_box(w + s, box)
        s_eff = w_trial - w
        with jax.named_scope("trial"):
            f_trial, g_trial = value_and_grad(w_trial)
            # Predicted reduction from the quadratic model (on the effective
            # step).
            Hs = hv(s_eff)
        pred = -(jnp.dot(g, s_eff) + 0.5 * jnp.dot(s_eff, Hs))
        actual = f - f_trial
        rho = actual / jnp.maximum(pred, 1e-30)

        snorm = jnp.linalg.norm(s_eff)
        accept = (rho > ETA0) & (pred > 0)

        # Standard trust-region radius update: shrink on poor agreement,
        # keep on moderate agreement, grow on strong agreement.
        delta_new = jnp.where(
            rho < ETA1,
            jnp.maximum(SIGMA1 * jnp.minimum(snorm, delta), 1e-12),
            jnp.where(
                rho < ETA2,
                delta,
                jnp.clip(SIGMA3 * snorm, delta, SIGMA3 * delta),
            ),
        )

        w_new = jnp.where(accept, w_trial, w)
        f_new = jnp.where(accept, f_trial, f)
        g_new = jnp.where(accept, g_trial, g)

        it = st["it"] + 1
        gn = jnp.linalg.norm(g_new)
        reason = jnp.where(
            accept,
            check_convergence(f_new, f, gn, g0_norm, tol, it, max_iter),
            # Rejected step: keep going unless the radius collapsed.
            jnp.where(
                delta_new <= 1e-10,
                jnp.int32(REASON_MAX_ITERATIONS),
                jnp.int32(REASON_NOT_CONVERGED),
            ),
        )
        # Passes over X: the margins the factory linearized at, one H·v a
        # CG step and ρ's, each ``hvp_passes``, and the trial's value and
        # gradient (TRON.scala:287-326: each product was a treeAggregate
        # round).
        passes = BUILD_PASSES + hvp_passes * (cg_iters + 1) + VG_PASSES
        return dict(
            w=w_new, f=f_new, g=g_new, delta=delta_new, it=it, reason=reason,
            evals=st["evals"] + passes,
            cg_steps=st["cg_steps"] + cg_iters,
            rejected=st["rejected"] + (~accept).astype(jnp.int32),
            loss_hist=st["loss_hist"].at[jnp.minimum(it, config.history_len - 1)].set(f_new),
            gnorm_hist=st["gnorm_hist"].at[jnp.minimum(it, config.history_len - 1)].set(gn),
        )

    st = jax.lax.while_loop(cond, body, state0)
    idx = jnp.arange(config.history_len)
    loss_hist = jnp.where(idx <= st["it"], st["loss_hist"], st["f"])
    gnorm_hist = jnp.where(idx <= st["it"], st["gnorm_hist"], jnp.linalg.norm(st["g"]))
    reason = jnp.where(
        st["reason"] == REASON_NOT_CONVERGED, REASON_MAX_ITERATIONS, st["reason"]
    )
    return OptimizeResult(
        w=st["w"], value=st["f"], grad_norm=jnp.linalg.norm(st["g"]),
        iterations=st["it"], reason_code=reason,
        loss_history=loss_hist, grad_norm_history=gnorm_hist,
        evals=st["evals"], eval_unit="x_passes",
        cg_steps=st["cg_steps"], rejected_steps=st["rejected"],
        hvp_passes=hvp_passes,
    )
