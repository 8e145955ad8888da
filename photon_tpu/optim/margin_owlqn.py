"""Margin-space OWL-QN: L1 / elastic net over a GLMObjective with one pass
over X a line search and one gradient pass an iteration.

The black-box OWL-QN (``optim/owlqn.py::minimize_owlqn``) pays a value and a
gradient, two passes over X, for every trial of its backtracking. The
trials of one search are w_k = π(w + α_k·p; ξ) with α_k = α_0·2^-k, all
known before the first. Their margins are

    z_k = z + α_k·u − X·c_k,    u = X·p,    c_k = (w + α_k·p) − w_k,

where c_k is non-zero only on the coordinates trial k clips to zero (w_j ≠ 0
and the step crosses zero; a coordinate at zero cannot clip, p being
sign-aligned with −pg). So ONE product reads X for u and the clip columns
X·c_k of the first ``TRIAL_COLUMNS`` trials together, and each of those
trials is O(n): its value is Σ wt·loss(z_k, y) + L2(w_k) + λ‖w_k‖₁. A later
trial that clips reads X once more, for its own column and those of the T
trials after it (a deep backtrack from a large first step clips on many
trials); one that clips nothing is O(n) too. The smooth gradient is taken
once, at the accepted trial, from its margins. Pseudo-gradient, direction, orthant, projection and the Armijo
test are the black-box solver's own (``run_owlqn``), so the two accept the
same trials up to the rounding of the margins.
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from photon_tpu.data.batch import LabeledBatch
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.common import OptimizeResult, OptimizerConfig
from photon_tpu.optim.margins import GLMMargins
from photon_tpu.optim.owlqn import (
    LineSearch,
    _armijo,
    _orthant_project,
    l1_weights,
    run_owlqn,
)

Array = jax.Array

# Trials whose clip columns ride on the search's one product over X (T; a
# product past them holds T + 1). On a TPU v5e (2^22 × 256 float32)
# X·[p | c_0 … c_3] costs 1.07 × X·p as one multiply-and-reduce fusion,
# X·[p | c_0 … c_7] 1.74 ×.
TRIAL_COLUMNS = 4


def minimize_owlqn_margin(
    objective: GLMObjective,
    batch: LabeledBatch,
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    start_score: Optional[Array] = None,
    return_score: bool = False,
) -> Union[OptimizeResult, Tuple[OptimizeResult, Array]]:
    """Minimize ``objective`` + its L1 term (intercept unpenalised) over
    ``batch``: ``minimize_owlqn`` on ``objective.value_and_grad`` in result,
    at 2 passes over X an iteration. ``evals`` counts passes over X: the
    start (the margins unless ``start_score`` holds them, and the gradient),
    each search's product, each product past its columns and each gradient
    pass; ``margin_trials`` counts the trials that read no X of their own. ``start_score`` / ``return_score`` are ``minimize_lbfgs_margin``'s:
    x·w0 held by the caller, and x·w at the result handed back from the
    carried margins."""
    mg = GLMMargins(objective, batch)
    l1 = l1_weights(w0, objective.l1_weight, objective.l1_mask(w0))
    T = TRIAL_COLUMNS

    def l1_value(w):
        return jnp.sum(l1 * jnp.abs(w))

    def search(w, F, g, z, p, xi, init_step, dirderiv):
        def clip_rows(alpha, count):
            """c_k of ``count`` trials from step ``alpha`` on, halved in the
            black box's order: (count, d)."""
            alphas = [alpha]
            for _ in range(count - 1):
                alphas.append(alphas[-1] * 0.5)
            reach = w[None, :] + jnp.stack(alphas)[:, None] * p[None, :]
            return reach - _orthant_project(reach, xi[None, :])

        # ONE read of X: u and the clip columns of trials 0 … T−1. Row r of
        # ``cols`` is trial ``first + r``'s column (row 0 is u here).
        cols0 = mg.matvecs(jnp.concatenate([p[None, :], clip_rows(init_step, T)]))
        u = cols0[0]

        def trial(k, alpha, cols, first):
            """Trial k: its point, margins and value; past the columns, a
            trial that clips reads X once for its own column and the next
            T (a clip set only shrinks as the step does, so once a trial
            clips nothing no later one does)."""
            reach = w + alpha * p
            wt = _orthant_project(reach, xi)
            clipped = jnp.any(wt != reach)
            refill = clipped & (k - first > T)
            cols, first = jax.lax.cond(
                refill,
                lambda: (mg.matvecs(clip_rows(alpha, T + 1)), k),
                lambda: (cols, first),
            )
            column = cols[jnp.clip(k - first, 0, T)]
            zt = z + alpha * u - jnp.where(clipped, column, 0.0)
            Ft = mg.data_value(zt) + mg.l2_value(wt) + l1_value(wt)
            return wt, zt, Ft, cols, first, refill.astype(jnp.int32)

        def bt_cond(bs):
            trials, alpha, Ft = bs[:3]
            return (~_armijo(F, dirderiv, alpha, Ft)) & (
                trials < config.max_line_search_evals
            )

        def bt_body(bs):
            trials, alpha, _Ft, _wt, _zt, cols, first, refills = bs
            alpha = alpha * 0.5
            wt, zt, Ft, cols, first, refill = trial(trials, alpha, cols, first)
            return trials + 1, alpha, Ft, wt, zt, cols, first, refills + refill

        w1, z1, F1, cols, first, refill = trial(
            jnp.int32(0), init_step, cols0, jnp.int32(-1)
        )
        trials, alpha, F_new, w_new, z_new, _cols, _first, refills = jax.lax.while_loop(
            bt_cond, bt_body, (jnp.int32(1), init_step, F1, w1, z1, cols, first, refill)
        )
        accepted = _armijo(F, dirderiv, alpha, F_new) & jnp.all(jnp.isfinite(w_new))
        # The one gradient pass of the iteration, at the last trial.
        if mg.use_fused:
            # Fresh margins and value from the same pass: no drift.
            f_new, g_new, z_new = mg.fused_value_grad_margins(w_new)
            F_new = f_new + l1_value(w_new)
        else:
            g_new = mg.grad_from_margins(z_new, w_new)
        found = accepted & jnp.all(jnp.isfinite(g_new))
        return LineSearch(found, w_new, F_new, g_new, z_new, refills + 2,
                          trials, trials - refills)

    f0, g0, z0, evals0 = mg.start(w0, start_score)
    result, z = run_owlqn(
        w0, f0 + l1_value(w0), g0, z0, evals0, l1, search, config, "x_passes"
    )
    if return_score:
        return result, z - batch.offset
    return result
