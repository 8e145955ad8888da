"""Optimizer factory: config → solver closure over a GLMObjective.

Parity target: reference photon-api optimization/OptimizerFactory +
OptimizerConfig case classes; selection semantics from
ObjectiveFunctionHelper/GeneralizedLinearOptimizationProblem: OWL-QN when an
L1 weight is present, otherwise the configured solver.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple, Union

import jax

from photon_tpu.data.batch import LabeledBatch
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.common import OptimizeResult, OptimizerConfig
from photon_tpu.optim.lbfgs import minimize_lbfgs, minimize_lbfgsb
from photon_tpu.optim.margin_lbfgs import minimize_lbfgs_margin
from photon_tpu.optim.margin_owlqn import minimize_owlqn_margin
from photon_tpu.optim.owlqn import minimize_owlqn
from photon_tpu.optim.tron import TRON_DEFAULT_CONFIG, minimize_tron
from photon_tpu.types import OptimizerType

Array = jax.Array

# The solvers that carry margins over a ``LabeledBatch``, and the black-box
# form each gives way to over any other batch.
_MARGIN_SOLVERS = {
    "lbfgs_margin": (minimize_lbfgs_margin, "lbfgs"),
    "owlqn_margin": (minimize_owlqn_margin, "owlqn"),
}


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


def routed_solver(objective: GLMObjective, spec: OptimizerSpec) -> str:
    """The solver ``make_optimizer`` routes (objective, spec) to over a
    ``LabeledBatch``, from static facts alone: margin-space OWL-QN whenever
    an L1 term exists (auto-selected or explicit; with l1_weight == 0 OWL-QN
    degenerates below plain L-BFGS, so a smooth objective never routes
    there; photon_tpu.optim.margin_owlqn: one X pass a line search and one
    gradient pass an iteration), the configured TRON or L-BFGS-B, else
    margin-space L-BFGS (photon_tpu.optim.margin_lbfgs: ~2 X passes an
    iteration instead of the black-box 2·(1+trials); measured ~3× per solve
    on a TPU), which is for smooth unconstrained problems: a box routes to
    the black-box L-BFGS. Over any other batch the two margin solvers give
    way to their black-box forms, ``owlqn`` and ``lbfgs``."""
    if objective.l1_weight > 0.0:
        return "owlqn_margin"
    if spec.optimizer == OptimizerType.TRON:
        return "tron"
    if spec.optimizer == OptimizerType.LBFGSB:
        return "lbfgsb"
    return "lbfgs_margin" if spec.box is None else "lbfgs"


def carries_margins(objective: GLMObjective, spec: OptimizerSpec) -> bool:
    """Whether the routed solver holds x·w through its loop, and so can start
    from a score the caller holds and hand the new one back without a pass
    over X. The others are black boxes over ``value_and_grad``."""
    return routed_solver(objective, spec) in _MARGIN_SOLVERS


def make_optimizer(
    objective: GLMObjective, spec: OptimizerSpec, with_score: bool = False
) -> Callable[..., Union[OptimizeResult, Tuple[OptimizeResult, Array]]]:
    """Return solve(w0, batch) -> OptimizeResult for the given objective.

    OWL-QN is auto-selected when the objective carries an L1 weight
    (reference RegularizationContext L1/elastic-net routing via OWLQN.scala),
    in margin space over a ``LabeledBatch``.

    ``with_score`` makes it solve(w0, batch, start_score=None) -> (result,
    score), ``score`` being x·w at ``result.w`` (``GLMObjective.scores``): a
    solver that carries margins takes ``start_score`` (x·w0) in and hands its
    own back; any other ignores it and pays ONE pass over X at the end.
    """
    config = spec.config()
    routed = routed_solver(objective, spec)

    def solve(w0: Array, batch, start_score: Optional[Array] = None):
        vg = lambda w: objective.value_and_grad(w, batch)
        name = routed
        if name in _MARGIN_SOLVERS and not isinstance(batch, LabeledBatch):
            name = _MARGIN_SOLVERS[name][1]
        score = None
        # The solver's name is the scope of its operations in a profile
        # (``fe_solve/owlqn_margin`` under the solve cache's scope) and the label
        # its result is published under when the tracker is read.
        with jax.named_scope(name):
            if name == "owlqn":
                res = minimize_owlqn(
                    vg, w0, objective.l1_weight, config, objective.l1_mask(w0)
                )
            elif name == "tron":
                # Factory form: margins/curvature built once per outer
                # iteration, shared across that iteration's CG products (2 X
                # passes each).
                res = minimize_tron(
                    vg, None, w0, config, spec.max_cg_iter, spec.box,
                    hvp_factory=lambda w: objective.linearized_hvp(w, batch),
                )
            elif name == "lbfgsb":
                assert spec.box is not None, "LBFGSB requires a box"
                res = minimize_lbfgsb(vg, w0, spec.box[0], spec.box[1], config)
            elif name in _MARGIN_SOLVERS:
                res = _MARGIN_SOLVERS[name][0](
                    objective, batch, w0, config,
                    start_score=start_score, return_score=with_score,
                )
                if with_score:
                    res, score = res
            else:
                res = minimize_lbfgs(vg, w0, config, spec.box)
        res = dataclasses.replace(res, optimizer=name)
        if not with_score:
            return res
        if score is None:
            with jax.named_scope("score"):
                score = objective.scores(res.w, batch)
        return res, score

    return solve
