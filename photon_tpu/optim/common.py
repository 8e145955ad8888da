"""Shared optimizer plumbing: result/state containers and convergence logic.

Parity targets: ``Optimizer.optimize`` template loop + convergence reasons
(reference photon-lib optimization/Optimizer.scala:126-187) and
``OptimizationStatesTracker`` (OptimizationStatesTracker.scala:31-113).

TPU-first design: the optimize loop is a single ``lax.while_loop`` inside one
jitted program — per-iteration state (loss, gradient norm) is recorded into
fixed-size history arrays (the tracker), so observability survives jit without
host round-trips. Convergence reasons are int codes resolved to the
``ConvergenceReason`` enum on the host.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from photon_tpu.types import ConvergenceReason

Array = jax.Array

# Reason codes used inside jit (host maps them back to the enum).
REASON_NOT_CONVERGED = 0
REASON_MAX_ITERATIONS = 1
REASON_FUNCTION_VALUES_CONVERGED = 2
REASON_GRADIENT_CONVERGED = 3
REASON_OBJECTIVE_NOT_IMPROVING = 4
# The solve produced a non-finite iterate and was rolled back to the last
# finite point (in-trace divergence guard). Not a convergence state: callers
# treating DIVERGED results should keep the previous/warm-start coefficients.
REASON_DIVERGED = 5

_REASONS = {
    REASON_NOT_CONVERGED: ConvergenceReason.NOT_CONVERGED,
    REASON_MAX_ITERATIONS: ConvergenceReason.MAX_ITERATIONS,
    REASON_FUNCTION_VALUES_CONVERGED: ConvergenceReason.FUNCTION_VALUES_CONVERGED,
    REASON_GRADIENT_CONVERGED: ConvergenceReason.GRADIENT_CONVERGED,
    REASON_OBJECTIVE_NOT_IMPROVING: ConvergenceReason.OBJECTIVE_NOT_IMPROVING,
    REASON_DIVERGED: ConvergenceReason.DIVERGED,
}


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Static solver configuration. Defaults mirror the reference:
    L-BFGS maxIter=100, m=10, tol=1e-7 (LBFGS.scala:148-154);
    TRON overrides maxIter=15, tol=1e-5 (TRON.scala:251-256)."""

    max_iter: int = dataclasses.field(default=100, metadata=dict(static=True))
    tol: float = dataclasses.field(default=1e-7, metadata=dict(static=True))
    memory: int = dataclasses.field(default=10, metadata=dict(static=True))
    # Line-search evaluation budget per iteration.
    max_line_search_evals: int = dataclasses.field(default=20, metadata=dict(static=True))
    # Record per-iteration (loss, |grad|) histories. Disable for vmapped
    # per-entity solves where (E, max_iter) tracker arrays would dominate HBM
    # (the reference's RandomEffectOptimizationTracker keeps only aggregate
    # stats for the same reason).
    track_history: bool = dataclasses.field(default=True, metadata=dict(static=True))

    @property
    def history_len(self) -> int:
        return self.max_iter + 1 if self.track_history else 1


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class OptimizeResult:
    """Solution + tracker (OptimizationStatesTracker role).

    ``loss_history[i]`` / ``grad_norm_history[i]`` hold the state after i
    iterations; entries past ``iterations`` are padded with the final values.
    """

    w: Array
    value: Array
    grad_norm: Array
    iterations: Array
    reason_code: Array
    loss_history: Array
    grad_norm_history: Array
    # Work counter for throughput accounting. Its unit is ``eval_unit``:
    # black-box solvers (LBFGS/OWL-QN/LBFGS-B) count objective evaluations
    # including line-search trials ("objective_evals", each = 2
    # feature-matrix passes); margin-space L-BFGS and OWL-QN, Newton and
    # TRON count feature-matrix passes directly ("x_passes"). Consumers
    # aggregating across solvers must check the unit (bench.py normalizes
    # to passes).
    evals: Array = dataclasses.field(default_factory=lambda: jnp.zeros((), jnp.int32))
    eval_unit: str = dataclasses.field(
        default="objective_evals", metadata=dict(static=True)
    )
    # Coefficients of ``w`` that are not exactly zero (what an L1 term
    # leaves), counted inside the solve's own program where the solve cache
    # builds it; -1 where nobody counted.
    nonzeros: Array = dataclasses.field(
        default_factory=lambda: jnp.full((), -1, jnp.int32)
    )
    # Line-search trials of an OWL-QN solve, summed over its iterations: all
    # of them, and those answered from carried margins without a read of X
    # (``optim/margin_owlqn.py``). None where the solver counts no trials.
    trials: Optional[Array] = None
    margin_trials: Optional[Array] = None
    # A TRON solve's conjugate-gradient steps summed over its outer
    # iterations, and the outer iterations whose step the trust region
    # refused (``optim/tron.py``). None where the solver has neither.
    cg_steps: Optional[Array] = None
    rejected_steps: Optional[Array] = None
    # Reads of X one of a TRON solve's Hessian-vector products took: 1 on
    # the one-read kernel, 2 for a forward and a transpose pass; 0 where
    # the solver makes no products.
    hvp_passes: int = dataclasses.field(default=0, metadata=dict(static=True))
    # Which solver the factory routed to ("owlqn_margin", "lbfgs_margin", ...) and
    # which coordinate it solved: the labels this result is published under
    # when it is read. Empty where no factory or coordinate made it.
    optimizer: str = dataclasses.field(default="", metadata=dict(static=True))
    coordinate: str = dataclasses.field(default="", metadata=dict(static=True))

    @property
    def x_passes(self) -> Array:
        """``evals`` normalized to feature-matrix passes (the bench unit)."""
        return self.evals * (2 if self.eval_unit == "objective_evals" else 1)

    @property
    def converged(self) -> bool:
        return int(self.reason_code) in (
            REASON_FUNCTION_VALUES_CONVERGED,
            REASON_GRADIENT_CONVERGED,
        )

    @property
    def convergence_reason(self) -> ConvergenceReason:
        return _REASONS[int(self.reason_code)]

    def _on_host(self) -> "OptimizeResult":
        """The tracker's fields (all but ``w``) as numpy, in ONE
        ``jax.device_get``: the two readers below format from this copy and
        apply no ``jnp`` operation, so reading a result is one transfer
        whatever the number of iterations. The copy is kept, so a result
        read twice (the log's summary, then the run report) is transferred
        and published once."""
        host = self.__dict__.get("_host")
        if host is None:
            host = jax.device_get(dataclasses.replace(self, w=None))
            object.__setattr__(self, "_host", host)
            if self.optimizer:
                _publish(host)
        return host

    def diagnostics_dict(self) -> dict:
        """Report-ready host scalars: one device→host transfer, then host
        arithmetic. Still a read the dispatch loop must not make — call it
        at run-report finalize."""
        h = self._on_host()
        out = dict(
            type="fixed_effect",
            iterations=int(h.iterations),
            value=float(h.value),
            grad_norm=float(h.grad_norm),
            reason=h.convergence_reason.value,
            converged=bool(h.converged),
            evals=int(h.evals),
            eval_unit=self.eval_unit,
        )
        if h.cg_steps is not None:
            out.update(cg_steps=int(h.cg_steps),
                       rejected_steps=int(h.rejected_steps))
        return out

    def summary(self) -> str:
        """Human-readable per-iteration table (tracker toSummaryString):
        one device→host transfer, formatted from numpy."""
        h = self._on_host()
        n = int(h.iterations)
        if h.loss_history.shape[0] < n + 1:
            # track_history=False run: only aggregates are available.
            return (
                f"iterations={n} value={float(h.value):.6e} "
                f"|grad|={float(h.grad_norm):.6e} "
                f"reason: {h.convergence_reason.value} (history not tracked)"
            )
        lines = ["iter    loss           |grad|"]
        for i in range(n + 1):
            lines.append(
                f"{i:4d}    {float(h.loss_history[i]):.6e}   "
                f"{float(h.grad_norm_history[i]):.6e}"
            )
        lines.append(f"reason: {h.convergence_reason.value}")
        return "\n".join(lines)


def _publish(host: OptimizeResult) -> None:
    """One solve's work into the registry, from the host copy the tracker's
    readers already made: iterations, evaluations, (OWL-QN) line-search
    trials and (TRON) CG and rejected steps and Hessian-vector products,
    all and those on the one-read kernel, as counters, the count of
    non-zero coefficients as a gauge (the last solve's)."""
    from photon_tpu.obs.metrics import registry

    labels = dict(coordinate=host.coordinate, optimizer=host.optimizer)
    registry().counter("fe_solver_iterations_total", **labels).inc(
        int(host.iterations)
    )
    registry().counter(
        "fe_solver_evals_total", unit=host.eval_unit, **labels
    ).inc(int(host.evals))
    if host.trials is not None:
        registry().counter("fe_line_search_trials_total", **labels).inc(
            int(host.trials)
        )
        registry().counter("fe_line_search_margin_trials_total", **labels).inc(
            int(host.margin_trials)
        )
    if host.cg_steps is not None:
        registry().counter(
            "fe_tron_cg_steps_total", coordinate=host.coordinate
        ).inc(int(host.cg_steps))
        registry().counter(
            "fe_tron_rejected_steps_total", coordinate=host.coordinate
        ).inc(int(host.rejected_steps))
        # A product a CG step and one for each outer iteration's ρ, all on
        # one lowering.
        products = int(host.cg_steps) + int(host.iterations)
        registry().counter(
            "fe_tron_products_total", coordinate=host.coordinate
        ).inc(products)
        registry().counter(
            "fe_tron_one_read_products_total", coordinate=host.coordinate
        ).inc(products if host.hvp_passes == 1 else 0)
    if int(host.nonzeros) >= 0:
        registry().gauge(
            "fe_nonzero_coefficients", coordinate=host.coordinate
        ).set(int(host.nonzeros))


def check_convergence(
    value: Array,
    prev_value: Array,
    grad_norm: Array,
    init_grad_norm: Array,
    tol: float,
    iteration: Array,
    max_iter: int,
) -> Array:
    """Reason code for the current state (Optimizer.scala:126-139 semantics):
    gradient converged relative to the initial gradient norm; function values
    converged on relative improvement; max iterations."""
    rel_impr = jnp.abs(value - prev_value) / jnp.maximum(jnp.abs(prev_value), 1e-12)
    code = jnp.where(
        grad_norm <= tol * jnp.maximum(init_grad_norm, 1e-12),
        REASON_GRADIENT_CONVERGED,
        jnp.where(
            rel_impr <= tol,
            REASON_FUNCTION_VALUES_CONVERGED,
            jnp.where(iteration >= max_iter, REASON_MAX_ITERATIONS, REASON_NOT_CONVERGED),
        ),
    )
    return code.astype(jnp.int32)


def project_to_box(
    w: Array, box: Optional[Tuple[Array, Array]]
) -> Array:
    """Coefficient box projection (reference
    OptimizationUtils.projectCoefficientsToSubspace, OptimizationUtils.scala:56)."""
    if box is None:
        return w
    lower, upper = box
    return jnp.clip(w, lower, upper)
