"""Optimizer factory: config → solver closure over a GLMObjective.

Parity target: reference photon-api optimization/OptimizerFactory +
OptimizerConfig case classes; selection semantics from
ObjectiveFunctionHelper/GeneralizedLinearOptimizationProblem: OWL-QN when an
L1 weight is present, otherwise the configured solver.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax

from photon_tpu.data.batch import LabeledBatch
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.common import OptimizeResult, OptimizerConfig
from photon_tpu.optim.lbfgs import minimize_lbfgs, minimize_lbfgsb
from photon_tpu.optim.margin_lbfgs import minimize_lbfgs_margin
from photon_tpu.optim.owlqn import minimize_owlqn
from photon_tpu.optim.tron import TRON_DEFAULT_CONFIG, minimize_tron
from photon_tpu.types import OptimizerType

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class OptimizerSpec:
    """User-facing optimizer configuration (reference
    CoordinateOptimizationConfiguration optimizer fields)."""

    optimizer: OptimizerType = OptimizerType.LBFGS
    max_iter: Optional[int] = None
    tol: Optional[float] = None
    memory: int = 10
    max_cg_iter: int = 20
    box: Optional[Tuple[Array, Array]] = None
    # OPTIMIZATION_STATE_TRACKER_OPTION (PhotonMLCmdLineParser.scala:136-139)
    track_history: bool = True

    def config(self) -> OptimizerConfig:
        base = TRON_DEFAULT_CONFIG if self.optimizer == OptimizerType.TRON else OptimizerConfig()
        return OptimizerConfig(
            max_iter=self.max_iter if self.max_iter is not None else base.max_iter,
            tol=self.tol if self.tol is not None else base.tol,
            memory=self.memory,
            track_history=self.track_history,
        )


def make_optimizer(
    objective: GLMObjective, spec: OptimizerSpec
) -> Callable[[Array, object], OptimizeResult]:
    """Return solve(w0, batch) -> OptimizeResult for the given objective.

    OWL-QN is auto-selected when the objective carries an L1 weight
    (reference RegularizationContext L1/elastic-net routing via OWLQN.scala).
    """
    config = spec.config()

    def run(name: str, minimize: Callable[..., OptimizeResult], *args, **kwargs):
        # The solver's name is the scope of its operations in a profile
        # (``fe_solve/owlqn`` under the solve cache's scope) and the label
        # its result is published under when the tracker is read.
        with jax.named_scope(name):
            return dataclasses.replace(minimize(*args, **kwargs), optimizer=name)

    def solve(w0: Array, batch) -> OptimizeResult:
        vg = lambda w: objective.value_and_grad(w, batch)
        # OWL-QN whenever an L1 term exists (auto-selected or explicit) —
        # with l1_weight == 0 OWL-QN degenerates below plain L-BFGS (orthant
        # projection still pins sign-crossing coordinates), so a smooth
        # objective always routes to L-BFGS regardless of the spec.
        if objective.l1_weight > 0.0:
            return run(
                "owlqn", minimize_owlqn, vg, w0, objective.l1_weight, config,
                objective.l1_mask(w0),
            )
        if spec.optimizer == OptimizerType.TRON:
            # Factory form: margins/curvature built once per outer iteration,
            # shared across that iteration's CG products (2 X passes each).
            return run(
                "tron", minimize_tron, vg, None, w0, config, spec.max_cg_iter,
                spec.box,
                hvp_factory=lambda w: objective.linearized_hvp(w, batch),
            )
        if spec.optimizer == OptimizerType.LBFGSB:
            assert spec.box is not None, "LBFGSB requires a box"
            return run(
                "lbfgsb", minimize_lbfgsb, vg, w0, spec.box[0], spec.box[1], config
            )
        # Smooth unconstrained GLM over a LabeledBatch: margin-space L-BFGS
        # (photon_tpu.optim.margin_lbfgs) — ~2 X passes/iteration instead of
        # the black-box 2·(1+trials); measured ~3× per-solve on TPU.
        if spec.box is None and isinstance(batch, LabeledBatch):
            return run(
                "lbfgs_margin", minimize_lbfgs_margin, objective, batch, w0, config
            )
        return run("lbfgs", minimize_lbfgs, vg, w0, config, spec.box)

    return solve
