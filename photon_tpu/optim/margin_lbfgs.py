"""Margin-space L-BFGS: GLM-structured solver with O(n) line-search trials.

The reference (Breeze LBFGS via photon-lib optimization/LBFGS.scala:38-79)
treats the objective as a black box: every line-search trial re-evaluates
value+gradient with a full pass over the data — the dominant cost
(ValueAndGradientAggregator broadcast+treeAggregate per trial, SURVEY.md
§3.1 hot loop).

A GLM objective is not a black box: the margin is affine in the step along a
fixed direction,

    z(w + α·p) = z0 + α·u,        u = X·p   (one pass, independent of α)

so an entire strong-Wolfe line search costs ONE feature-matrix pass (u),
with every trial an O(n) elementwise evaluation on (z0, u):

    φ(α)  = Σᵢ wtᵢ·loss(z0ᵢ + α·uᵢ, yᵢ) + L2(α)      (L2 analytic in α)
    φ'(α) = Σᵢ uᵢ·wtᵢ·loss'(z0ᵢ + α·uᵢ, yᵢ) + L2'(α)

and the accepted point updates the carried margins incrementally
(z0 += α·u — float32 drift over ≤100 iterations is ~1e-5 relative, well
under optimizer tolerances). One L-BFGS iteration therefore costs exactly
TWO X passes (u = X·p and the new gradient Xᵀ·dz) instead of the black-box
2·(1 + #trials). Normalization stays folded: with factors f and shifts s,
u = X·(f∘p) − (s·(f∘p)) is still affine in α (photon_tpu.data.normalization
algebra), and the gradient chain-rules back through f. The margin algebra
is ``optim/margins.py``'s, shared with margin-space OWL-QN.

Smooth objectives only: a box routes through optim.lbfgs, an L1 term through
optim.margin_owlqn (whose orthant projection its search answers with clip
columns).
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from photon_tpu.data.batch import LabeledBatch
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.common import (
    OptimizeResult,
    OptimizerConfig,
    REASON_MAX_ITERATIONS,
    REASON_NOT_CONVERGED,
    check_convergence,
)
from photon_tpu.optim.lbfgs import two_loop_direction
from photon_tpu.optim.linesearch import strong_wolfe
from photon_tpu.optim.margins import GLMMargins

Array = jax.Array


def minimize_lbfgs_margin(
    objective: GLMObjective,
    batch: LabeledBatch,
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    l2_override: Optional[Array] = None,
    start_score: Optional[Array] = None,
    return_score: bool = False,
) -> Union[OptimizeResult, Tuple[OptimizeResult, Array]]:
    """L-BFGS over a GLMObjective exploiting margin affinity.

    Semantically equivalent to ``minimize_lbfgs(objective.value_and_grad...)``
    on smooth GLMs, at ~2 X-passes per iteration. ``result.evals`` counts
    X passes (the full-data cost unit); O(n) margin-only line-search trials
    are not counted.

    ``l2_override`` replaces the objective's static L2 weight with a TRACED
    scalar — the hook that lets ``sweep_l2_lbfgs_margin`` vmap one program
    over a whole λ grid.

    Margins cross the boundary both ways, for a caller (coordinate descent)
    that keeps x·w beside the model. ``start_score`` is x·w0 for every sample
    (the margins at ``w0`` less the batch's offset) where the caller holds it:
    the solve then starts without its pass over X, and ``evals`` counts one
    pass fewer. With ``return_score`` the result comes as ``(result, score)``,
    ``score`` being x·w at ``result.w``: the margins the loop carried, less
    the offset, O(n) and no pass over X. (On the fused Pallas path every
    gradient pass yields fresh margins, the first one too, so ``start_score``
    saves nothing there and is not read.)
    """
    if objective.l1_weight > 0.0:
        raise ValueError("margin L-BFGS is for smooth objectives; use OWL-QN for L1")

    mg = GLMMargins(objective, batch, l2_override)

    m, max_iter, tol = config.memory, config.max_iter, config.tol
    d = w0.shape[0]
    dtype = w0.dtype

    f0, g0, z0, init_evals = mg.start(w0, start_score)
    g0_norm = jnp.linalg.norm(g0)

    hist_len = config.history_len
    state0 = dict(
        w=w0,
        z=z0,
        f=f0,
        g=g0,
        it=jnp.int32(0),
        reason=jnp.int32(REASON_NOT_CONVERGED),
        s_hist=jnp.zeros((m, d), dtype),
        y_hist=jnp.zeros((m, d), dtype),
        rho_hist=jnp.zeros((m,), dtype),
        num_stored=jnp.int32(0),
        head=jnp.int32(0),
        evals=jnp.int32(init_evals),
        loss_hist=jnp.full((hist_len,), f0, dtype),
        gnorm_hist=jnp.full((hist_len,), g0_norm, dtype),
    )

    def cond(st):
        return (st["reason"] == REASON_NOT_CONVERGED) & (st["it"] < max_iter)

    def body(st):
        w, z, f, g = st["w"], st["z"], st["f"], st["g"]
        p = two_loop_direction(
            g, st["s_hist"], st["y_hist"], st["rho_hist"], st["num_stored"], st["head"]
        )
        dg0 = jnp.dot(p, g)
        bad_dir = dg0 >= 0
        p = jnp.where(bad_dir, -g, p)
        dg0 = jnp.where(bad_dir, -jnp.dot(g, g), dg0)

        u = mg.matvec(p)  # the ONE X pass for this whole line search
        # L2 along the path: quadratic with analytic coefficients.
        if mg.has_l2:
            wm, pm = mg.l2_mask(w), mg.l2_mask(p)
            l2_a = mg.l2 * jnp.dot(wm, pm)
            l2_b = mg.l2 * jnp.dot(pm, pm)
        else:
            l2_a = l2_b = jnp.zeros((), dtype)
        f_l2 = mg.l2_value(w)

        def ls_fg(a):
            za = z + a * u
            dza = mg.weight * mg.loss.dz(za, mg.label)
            val = mg.data_value(za) + f_l2 + a * l2_a + 0.5 * a * a * l2_b
            deriv = jnp.dot(u, dza) + l2_a + a * l2_b
            return val, deriv

        init_alpha = jnp.where(
            st["num_stored"] == 0,
            jnp.minimum(1.0, 1.0 / jnp.maximum(jnp.linalg.norm(g), 1e-12)),
            1.0,
        ).astype(dtype)
        ls = strong_wolfe(
            ls_fg, f, dg0, init_alpha, max_evals=config.max_line_search_evals
        )

        w_new = w + ls.alpha * p
        if mg.use_fused:
            # Second X pass: fused value+grad+margins at w_new — carried
            # margins refreshed exactly, no incremental drift.
            f_new, g_new, z_new = mg.fused_value_grad_margins(w_new)
        else:
            z_new = z + ls.alpha * u  # incremental margin update — no X pass
            f_new = mg.data_value(z_new) + mg.l2_value(w_new)
            g_new = mg.grad_from_margins(z_new, w_new)  # second X pass

        s = w_new - w
        y = g_new - g
        sy = jnp.dot(s, y)
        store = sy > 1e-12
        slot = (st["head"] + 1) % m
        s_hist = jnp.where(store, st["s_hist"].at[slot].set(s), st["s_hist"])
        y_hist = jnp.where(store, st["y_hist"].at[slot].set(y), st["y_hist"])
        rho_hist = jnp.where(
            store,
            st["rho_hist"].at[slot].set(1.0 / jnp.maximum(sy, 1e-30)),
            st["rho_hist"],
        )
        head = jnp.where(store, slot, st["head"])
        num_stored = jnp.where(store, jnp.minimum(st["num_stored"] + 1, m), st["num_stored"])

        it = st["it"] + 1
        gn = jnp.linalg.norm(g_new)
        reason = check_convergence(f_new, f, gn, jnp.linalg.norm(g0), tol, it, max_iter)
        return dict(
            w=w_new,
            z=z_new,
            f=f_new,
            g=g_new,
            it=it,
            reason=reason,
            s_hist=s_hist,
            y_hist=y_hist,
            rho_hist=rho_hist,
            num_stored=num_stored,
            head=head,
            evals=st["evals"] + 2,
            loss_hist=st["loss_hist"].at[jnp.minimum(it, hist_len - 1)].set(f_new),
            gnorm_hist=st["gnorm_hist"].at[jnp.minimum(it, hist_len - 1)].set(gn),
        )

    st = jax.lax.while_loop(cond, body, state0)
    final_gnorm = jnp.linalg.norm(st["g"])
    idx = jnp.arange(hist_len)
    loss_hist = jnp.where(idx <= st["it"], st["loss_hist"], st["f"])
    gnorm_hist = jnp.where(idx <= st["it"], st["gnorm_hist"], final_gnorm)
    reason = jnp.where(
        st["reason"] == REASON_NOT_CONVERGED, REASON_MAX_ITERATIONS, st["reason"]
    )
    result = OptimizeResult(
        w=st["w"],
        value=st["f"],
        grad_norm=final_gnorm,
        iterations=st["it"],
        reason_code=reason,
        loss_history=loss_hist,
        grad_norm_history=gnorm_hist,
        evals=st["evals"],
        eval_unit="x_passes",
    )
    if return_score:
        return result, st["z"] - batch.offset
    return result


def sweep_l2_lbfgs_margin(
    objective: GLMObjective,
    batch: LabeledBatch,
    w0s: Array,  # (k, d) initial points, one per λ
    l2_weights: Array,  # (k,)
    config: OptimizerConfig = OptimizerConfig(),
) -> OptimizeResult:
    """Solve the SAME data against k regularization weights as ONE vmapped
    program — the TPU replacement for the reference's sequential warm-started
    λ sweep (ModelTraining.scala:162-200) and the parallel-candidate hook for
    Bayesian tuning (SURVEY.md §2.7.5: hyperparameter parallelism, absent in
    the reference).

    Every lane streams the shared X through its own margin-space L-BFGS via
    ``l2_override`` (a traced per-lane scalar), so the k solves cost one
    X-bandwidth budget per iteration instead of k. Returns a batched
    OptimizeResult whose leaves carry a leading (k,) axis.
    """
    import dataclasses

    # The fused Pallas kernel doesn't batch under vmap the way the XLA path
    # does (a batched pallas_call adds a grid axis instead of widening the
    # matmul); the XLA path turns the k lane matvecs into ONE X·P matmul,
    # which is exactly the bandwidth sharing this sweep exists for.
    objective = dataclasses.replace(objective, use_pallas=False)

    def solve(w0, l2):
        return minimize_lbfgs_margin(objective, batch, w0, config, l2_override=l2)

    return jax.vmap(solve)(w0s, l2_weights)
