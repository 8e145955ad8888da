"""Batch-parallel hyperparameter evaluation: q GAME candidates as ONE
vmapped program.

The reference evaluates tuning candidates strictly sequentially — each
Bayesian round trains one full GAME model (GameEstimator.scala:364-382,
AtlasTuner loop). On a TPU the fixed-effect solves are HBM-bandwidth bound,
so q candidate trainings that differ only in regularization weights can
share every X pass: vmap the GLMix train step over traced per-lane λs
(``l2_override`` in margin-LBFGS / Newton) and evaluate all q validation
metrics inside the same program. SURVEY.md §2.7 item 5 names this the
natural TPU win over the reference.

Eligibility (falls back to sequential fits otherwise): one fixed-effect +
one random-effect coordinate (the GLMix shape), pure-L2 tuning dimensions,
unprojected entity blocks, no down-sampling/boxes/feature masks, and a
jittable primary metric. Normalization-folded shards ARE eligible (r4): the
per-shard fold is static per lane, and models convert between transformed
and model space exactly as the production coordinates do. Every fallback is
logged (VERDICT r3: a silent fallback makes "8 candidates per program"
quietly mean 1).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Callable, List, Optional

import numpy as np

logger = logging.getLogger(__name__)


def _decline(reason: str) -> None:
    logger.warning(
        "batched hyperparameter evaluation declined (%s); candidates will "
        "be trained sequentially", reason,
    )
    return None

from photon_tpu.estimators.config import (
    FixedEffectCoordinateConfig,
    GameOptimizationConfig,
    RandomEffectCoordinateConfig,
)

# Jittable primary metrics (evaluation/evaluators.py): name → fn(scores,
# labels, weight) -> scalar.
_JITTABLE_METRICS = ("AUC", "AUPR", "RMSE", "LOGISTIC_LOSS", "SQUARED_LOSS",
                    "POISSON_LOSS")


def _metric_fn(name: str):
    from photon_tpu.evaluation import evaluators as ev

    return {
        "AUC": ev.auc_roc,
        "AUPR": ev.auc_pr,
        "RMSE": ev.rmse,
        "LOGISTIC_LOSS": ev.logistic_loss_metric,
        "SQUARED_LOSS": ev.squared_loss_metric,
        "POISSON_LOSS": ev.poisson_loss_metric,
    }[name]


def build_batched_evaluator(
    estimator,
    base_config: GameOptimizationConfig,
    slots,  # GameEstimatorEvaluationFunction._slots (coordinate_id, kind)
    batch,
    validation_batch,
    evaluation_suite,
) -> Optional[Callable[[np.ndarray], List[float]]]:
    """Return fn(X: (q, dim) candidate vectors) -> list of q primary-metric
    values, or None when the setup is not batchable."""
    cfgs = estimator.coordinate_configs
    if len(cfgs) != 2:
        return _decline(
            f"{len(cfgs)} coordinates; only the 2-coordinate GLMix shape "
            "is batchable"
        )
    fe_cfgs = [c for c in cfgs if isinstance(c, FixedEffectCoordinateConfig)]
    re_cfgs = [c for c in cfgs if isinstance(c, RandomEffectCoordinateConfig)]
    if len(fe_cfgs) != 1 or len(re_cfgs) != 1:
        return _decline("need exactly one fixed + one random effect")
    fe_cfg, re_cfg = fe_cfgs[0], re_cfgs[0]
    if estimator.update_sequence[0] != fe_cfg.coordinate_id:
        return _decline("update sequence does not train the fixed effect first")
    # Tuning dims must be pure-L2 weights (l2_override hook).
    if any(kind != "weight" for _, kind in ((s.coordinate_id, s.kind) for s in slots)):
        return _decline("non-L2-weight tuning dimension")
    if any(base_config.reg[c.coordinate_id].alpha != 0.0 for c in cfgs):
        return _decline("elastic-net alpha != 0")
    if (
        fe_cfg.down_sampling_rate is not None
        or getattr(fe_cfg, "box", None) is not None
        or re_cfg.features_to_samples_ratio is not None
    ):
        return _decline("down-sampling / box constraints / Pearson masks")
    if estimator.locked_coordinates:
        return _decline("locked coordinates")
    primary = evaluation_suite.primary
    if primary.etype.name not in _JITTABLE_METRICS or primary.group_by is not None:
        return _decline(f"primary metric {primary.name} is not jittable")
    from photon_tpu.types import OptimizerType

    if fe_cfg.optimizer != OptimizerType.LBFGS:
        return _decline("fixed-effect optimizer is not LBFGS")
    if re_cfg.optimizer not in (OptimizerType.LBFGS, OptimizerType.NEWTON):
        return _decline("random-effect optimizer is not LBFGS/NEWTON")

    # Datasets: unprojected RE dataset (any block count), whose blocks index
    # the batch the estimator trains on (laid out in runs of one
    # coordinate's entities, where the device holds the copy).
    batch = estimator._prepare_datasets(batch)
    ds = estimator._re_datasets.get(re_cfg.coordinate_id)
    if ds is None or ds.projected:
        return _decline("projected random-effect dataset")

    import jax
    import jax.numpy as jnp

    from photon_tpu.algorithm.random_effect import newton_eligible
    from photon_tpu.data.batch import LabeledBatch
    from photon_tpu.ops.losses import loss_for_task
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optim.margin_lbfgs import minimize_lbfgs_margin
    from photon_tpu.optim.newton import minimize_newton

    loss = loss_for_task(estimator.task)
    fe_shard, re_shard = fe_cfg.feature_shard, re_cfg.feature_shard
    fe_icpt = estimator.intercept_indices.get(fe_shard)
    re_icpt = estimator.intercept_indices.get(re_shard)
    # Base λs: lanes override via l2_override, so the static weight only
    # matters for coordinates without a tuning slot. Normalization folds
    # exactly as in the production coordinates (_build_coordinates).
    fe_norm = estimator.normalization.get(fe_shard)
    re_norm = estimator.normalization.get(re_shard)
    fe_obj = GLMObjective(
        loss=loss, l2_weight=base_config.reg[fe_cfg.coordinate_id].l2,
        intercept_index=fe_icpt, normalization=fe_norm,
    )
    re_obj = GLMObjective(
        loss=loss, l2_weight=base_config.reg[re_cfg.coordinate_id].l2,
        intercept_index=re_icpt, normalization=re_norm,
    )
    fe_folded = fe_norm is not None and not fe_norm.is_identity
    re_folded = re_norm is not None and not re_norm.is_identity
    fe_spec_cfg = dataclasses.replace(
        fe_cfg.optimizer_spec().config(), track_history=False
    )
    re_spec_cfg = dataclasses.replace(
        re_cfg.optimizer_spec().config(), track_history=False
    )

    re_type = re_cfg.re_type
    train_lb = batch.labeled_batch(fe_shard)
    train_re_feats = batch.features[re_shard]
    train_eids = batch.entity_ids[re_type]
    valid_lb = validation_batch.labeled_batch(fe_shard)
    valid_re_feats = validation_batch.features[re_shard]
    valid_eids = validation_batch.entity_ids[re_type]
    E, d_fix = ds.num_entities, train_lb.dim
    d_re = ds.dim
    num_iterations = estimator.num_iterations
    metric = _metric_fn(primary.etype.name)

    # Slot → lane-λ extraction (log10-weight space).
    slot_for = {s.coordinate_id: i for i, s in enumerate(slots)}
    fe_slot = slot_for.get(fe_cfg.coordinate_id)
    re_slot = slot_for.get(re_cfg.coordinate_id)
    fe_base = base_config.reg[fe_cfg.coordinate_id].l2
    re_base = base_config.reg[re_cfg.coordinate_id].l2

    @jax.jit
    def eval_lanes(fe_lams, re_lams):  # (q,), (q,) traced λs
        def re_scores_of(coefs, feats, eids):
            ok = eids >= 0
            return jnp.where(
                ok, jnp.sum(feats * coefs[jnp.maximum(eids, 0)], -1), 0.0
            )

        def one(lf, lr):
            # The mini coordinate-descent loop of the production path
            # (CoordinateDescent → FE margin-LBFGS → per-block batched
            # Newton), parameterized by this lane's traced λs. Carries live
            # in MODEL space; solves convert in/out exactly like the
            # production coordinates.
            w = jnp.zeros((d_fix,), jnp.float32)
            coefs = jnp.zeros((E, d_re), jnp.float32)
            for _ in range(num_iterations):
                re_sc = re_scores_of(coefs, train_re_feats, train_eids)
                w_start = (
                    fe_norm.model_to_transformed_space(w) if fe_folded else w
                )
                fe_res = minimize_lbfgs_margin(
                    fe_obj, train_lb.add_scores_to_offsets(re_sc), w_start,
                    fe_spec_cfg, l2_override=lf,
                )
                w = (
                    fe_norm.transformed_to_model_space(fe_res.w)
                    if fe_folded else fe_res.w
                )
                fe_scores = train_lb.margins(w)  # includes base offsets
                for block in ds.blocks:
                    offs = block.gather_offsets(fe_scores)
                    w0 = coefs[block.entity_idx]
                    # Same static routing predicate as the production
                    # _solve_block (ADVICE r3: an explicit NEWTON spec past
                    # the auto-dim cap must not score with a different
                    # solver than the final refit).
                    use_newton = newton_eligible(
                        re_obj, re_cfg.optimizer_spec(), block.dim,
                        has_mask=False,
                    )

                    def solve_one(feat, lab, wt, off, wi):
                        lb = LabeledBatch(lab, feat, off, wt)
                        wi_t = (
                            re_norm.model_to_transformed_space(wi)
                            if re_folded else wi
                        )
                        if use_newton:
                            res = minimize_newton(
                                re_obj, lb, wi_t, re_spec_cfg, l2_override=lr
                            )
                        else:
                            res = minimize_lbfgs_margin(
                                re_obj, lb, wi_t, re_spec_cfg, l2_override=lr
                            )
                        return (
                            re_norm.transformed_to_model_space(res.w)
                            if re_folded else res.w
                        )

                    w_new = jax.vmap(solve_one)(
                        block.features, block.label, block.weight, offs, w0
                    )
                    w_new = jnp.where(block.train_mask[:, None], w_new, w0)
                    coefs = coefs.at[block.entity_idx].set(w_new)
            re_scores = re_scores_of(coefs, valid_re_feats, valid_eids)
            val_scores = valid_lb.margins(w) + re_scores
            return metric(val_scores, valid_lb.label, valid_lb.weight)

        return jax.vmap(one)(fe_lams, re_lams)

    def evaluate(X: np.ndarray) -> List[float]:
        X = np.asarray(X, float)
        q = X.shape[0]
        fe_lams = (
            10.0 ** X[:, fe_slot] if fe_slot is not None
            else np.full(q, fe_base)
        )
        re_lams = (
            10.0 ** X[:, re_slot] if re_slot is not None
            else np.full(q, re_base)
        )
        vals = eval_lanes(
            jnp.asarray(fe_lams, jnp.float32), jnp.asarray(re_lams, jnp.float32)
        )
        return [float(v) for v in np.asarray(vals)]

    return evaluate
