"""Fixed-effect coordinate: one global GLM over the whole (sharded) batch.

Parity target: reference ``FixedEffectCoordinate`` (photon-api
algorithm/FixedEffectCoordinate.scala:31-152: train via
DistributedOptimizationProblem.runWithSampling + broadcast model; score =
map-side dot with broadcast coefficients) and ``DistributedOptimizationProblem``
(optimization/DistributedOptimizationProblem.scala:140: optional down-sampling,
variance computation).

TPU-first: the batch lives sharded over the mesh's data axis; the whole
optimizer run is one jitted program (w replicated by sharding rule — no
broadcast step exists). Down-sampling is a weight mask (shapes stay static).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from photon_tpu.data.batch import features_dot
from photon_tpu.data.game_data import GameBatch
from photon_tpu.algorithm.coordinate import Coordinate
from photon_tpu.models.coefficients import Coefficients
from photon_tpu.models.game import FixedEffectModel
from photon_tpu.models.glm import GeneralizedLinearModel
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.ops.variance import coefficient_variances, normalize_variance_type
from photon_tpu.optim.common import OptimizeResult
from photon_tpu.optim.factory import OptimizerSpec, carries_margins
from photon_tpu.algorithm.solve_cache import SolveCache, default_cache
from photon_tpu.obs.metrics import registry
from photon_tpu.obs.trace import span
from photon_tpu.sampling.down_sampler import DownSampler
from photon_tpu.types import TaskType, VarianceComputationType

Array = jax.Array

# x·w as one fused pass over X: the score of a model nobody has solved here
# (a warm start, a resume), and what the solve's own program runs at its end
# for a solver that carries no margins.
_fused_score = jax.jit(features_dot)


@dataclasses.dataclass
class FixedEffectCoordinate(Coordinate):
    coordinate_id: str
    feature_shard: str
    task: TaskType
    objective: GLMObjective
    optimizer_spec: OptimizerSpec = dataclasses.field(default_factory=OptimizerSpec)
    down_sampler: Optional[DownSampler] = None
    # SIMPLE (diag-inverse) or FULL (Cholesky inverse diagonal); bool accepted
    # for compatibility (True → SIMPLE).
    compute_variance: object = VarianceComputationType.NONE
    dim: Optional[int] = None  # inferred from the batch if None
    # Shared compiled-executable cache (algorithm/solve_cache.py): the full
    # optimizer run is one jitted program per (objective, spec), reused
    # across CD passes and across coordinates with identical configs.
    solve_cache: Optional[SolveCache] = None

    def __post_init__(self):
        self.compute_variance = normalize_variance_type(self.compute_variance)
        if self.solve_cache is None:
            self.solve_cache = default_cache()

    def train(
        self,
        batch: GameBatch,
        residual_scores: Optional[Array] = None,
        initial_model: Optional[FixedEffectModel] = None,
    ) -> Tuple[FixedEffectModel, OptimizeResult]:
        """:meth:`update` without the scores (the program computes them all
        the same: free where the solver carries margins, one pass over X
        where it does not)."""
        model, result, _scores = self.update(batch, residual_scores, initial_model)
        return model, result

    def update(
        self,
        batch: GameBatch,
        residual_scores: Optional[Array] = None,
        initial_model: Optional[FixedEffectModel] = None,
        initial_scores: Optional[Array] = None,
    ) -> Tuple[FixedEffectModel, OptimizeResult, Array]:
        """The solve program's launch and nothing else that reads X: the new
        model's scores come back from the program (the solver's own margins,
        or one fused pass at its end), and a margin-carrying solver starts
        from the scores the caller holds: zero for a model that starts at
        zero, ``initial_scores`` for ``initial_model``."""
        lb = batch.labeled_batch(self.feature_shard, residual_scores)
        if self.down_sampler is not None:
            # Down-sampling as reweighting mask — static shapes
            # (DistributedOptimizationProblem.runWithSampling:140-166 role).
            lb = self.down_sampler.apply(lb)
        d = lb.dim
        w0 = (
            initial_model.model.coefficients.means
            if initial_model is not None
            else jnp.zeros((d,), lb.label.dtype)
        )
        # Models live in MODEL space; solves run in the normalization-folded
        # transformed space (reference Optimizer.scala:167 converts the warm
        # start in, DistributedOptimizationProblem.scala:127 converts the
        # result out). Scores are the same in both.
        norm = self.objective.normalization
        folded = norm is not None and not norm.is_identity
        if folded:
            w0 = norm.model_to_transformed_space(w0)
        # Where the solve's starting margins and the new scores come from:
        # static facts of the routed solver and of what the caller passed,
        # counted at dispatch (nothing is read back).
        margins = carries_margins(self.objective, self.optimizer_spec)
        source = "solver_margins" if margins else "fused_pass"
        if not margins or (initial_model is not None and initial_scores is None):
            start, start_score = "recomputed", None
        elif initial_model is not None:
            start, start_score = "prior_score", initial_scores
        elif initial_scores is not None:
            start, start_score = "zero", initial_scores
        else:
            start, start_score = "zero", jnp.zeros((lb.n,), lb.label.dtype)
        registry().counter(
            "fe_start_margins_total", coordinate=self.coordinate_id, source=start
        ).inc()
        registry().counter(
            "fe_score_source_total", coordinate=self.coordinate_id, source=source
        ).inc()
        solve = self.solve_cache.fe_solver(
            self.objective, self.optimizer_spec, lb)
        # Host-wall span of the dispatch (the solve itself runs async on
        # device; nothing here blocks).
        with span("fe_solve"):
            result, scores = solve(w0, lb, start_score)
        # The label the tracker's read publishes this solve under (a static
        # field: no device work).
        result = dataclasses.replace(result, coordinate=self.coordinate_id)
        # SIMPLE/FULL variance computation
        # (DistributedOptimizationProblem.scala:83-103 role). Evaluated at
        # the transformed-space optimum (self-consistent with the folded
        # objective — the reference instead feeds model-space coefficients
        # to the folded Hessian) and mapped to model space via factors².
        variances = coefficient_variances(
            self.objective, result.w, lb, self.compute_variance
        )
        w_model = norm.transformed_to_model_space(result.w) if folded else result.w
        if folded and variances is not None and norm.factors is not None:
            variances = variances * norm.factors**2
        model = FixedEffectModel(
            GeneralizedLinearModel(Coefficients(w_model, variances), self.task),
            self.feature_shard,
        )
        return model, result, scores

    def train_from_stream(
        self,
        chunks,
        residual_scores: Optional[Array] = None,
        initial_model: Optional[FixedEffectModel] = None,
    ) -> Tuple[FixedEffectModel, OptimizeResult]:
        """Train from a pipelined chunk stream (io/pipeline.py
        ``BatchChunk`` iterator — e.g. ``stream_device_batches`` or a
        ``ChunkReplayCache`` replay routed through ``device_chunks_from``).

        Chunks concatenate ON DEVICE as they arrive, so each chunk's
        decode/assembly/H2D overlaps earlier chunks' placement via async
        dispatch; the solve then runs exactly as :meth:`train` — same
        compiled executable, same result. Feed unpadded chunks
        (``pad_rows_to=None``): the optimizer is one whole-batch jitted
        program, so row padding would embed inert rows in the objective.
        """
        from photon_tpu.io.pipeline import materialize_game_batch

        return self.train(
            materialize_game_batch(chunks), residual_scores, initial_model
        )

    def score(self, model: FixedEffectModel, batch: GameBatch) -> Array:
        return _fused_score(
            batch.features[self.feature_shard], model.model.coefficients.means
        )

    def zero_model(self) -> FixedEffectModel:
        assert self.dim is not None, "dim required for zero_model"
        return FixedEffectModel(
            GeneralizedLinearModel.zeros(self.dim, self.task), self.feature_shard
        )
