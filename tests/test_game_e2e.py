"""End-to-end GLMix: coordinate descent with fixed + random effects.

Mirrors the reference's GAME integration tests (GameEstimatorIntegTest /
GameTrainingDriverIntegTest property checks): random effects must add
measurable lift over the fixed effect alone; trackers must report
convergence; cold-start entities must score 0 from RE coordinates.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.algorithm import (
    CoordinateDescent,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.random_effect import (
    RandomEffectDataConfig,
    build_random_effect_dataset,
)
from photon_tpu.evaluation import EvaluationSuite
from photon_tpu.evaluation.suite import EvaluatorSpec
from photon_tpu.models.game import GameModel
from photon_tpu.ops import GLMObjective, LogisticLoss
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.types import TaskType

rng = np.random.default_rng(7)
N, D_FIX, D_RE, E = 2048, 12, 4, 30


@pytest.fixture(scope="module")
def glmix_data():
    Xf = rng.normal(size=(N, D_FIX)).astype(np.float32)
    Xf[:, 0] = 1.0
    Xr = rng.normal(size=(N, D_RE)).astype(np.float32)
    Xr[:, 0] = 1.0
    users = rng.integers(0, E, size=N).astype(np.int32)
    w_fix = rng.normal(size=D_FIX).astype(np.float32)
    w_users = rng.normal(scale=2.0, size=(E, D_RE)).astype(np.float32)
    logits = Xf @ w_fix + np.sum(Xr * w_users[users], axis=1)
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    batch = GameBatch(
        label=jnp.asarray(y),
        offset=jnp.zeros(N, jnp.float32),
        weight=jnp.ones(N, jnp.float32),
        features={"global": jnp.asarray(Xf), "per_user": jnp.asarray(Xr)},
        entity_ids={"userId": jnp.asarray(users)},
    )
    return batch, Xr, users, y


def make_coordinates(batch, Xr, users, y, **re_cfg):
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)
    fixed = FixedEffectCoordinate(
        "global", "global", TaskType.LOGISTIC_REGRESSION, obj, OptimizerSpec()
    )
    ds = build_random_effect_dataset(
        np.asarray(users), np.asarray(Xr), np.asarray(y), np.ones(N, np.float32), E,
        RandomEffectDataConfig(re_type="userId", feature_shard="per_user", **re_cfg),
    )
    re_obj = GLMObjective(loss=LogisticLoss, l2_weight=0.5, intercept_index=0)
    rand = RandomEffectCoordinate(
        "per_user", ds, TaskType.LOGISTIC_REGRESSION, re_obj
    )
    return fixed, rand


def test_glmix_beats_fixed_only(glmix_data):
    batch, Xr, users, y = glmix_data
    fixed, rand = make_coordinates(batch, Xr, users, y)
    suite = EvaluationSuite(
        [EvaluatorSpec.parse("AUC"), EvaluatorSpec.parse("AUC:userId")],
        num_entities={"userId": E},
    )
    cd = CoordinateDescent(
        {"global": fixed, "per_user": rand}, ["global", "per_user"], num_iterations=2
    )
    result = cd.run(
        batch, validation_batch=batch, validation_fn=suite.validation_fn(),
        better=suite.primary.better(),
    )
    fe_model, _ = fixed.train(batch)
    fe_auc = suite.evaluate_model(GameModel({"global": fe_model}), batch)["AUC"]
    glmix_auc = result.metric_history[-1]["AUC"]
    assert glmix_auc > fe_auc + 0.03
    assert glmix_auc > 0.85
    # Metric must not degrade across CD iterations.
    aucs = [m["AUC"] for m in result.metric_history]
    assert aucs[-1] >= aucs[0] - 1e-3
    # Tracker: all entities converge on this well-conditioned problem.
    stats = result.tracker["per_user"][-1]
    assert stats.num_entities == E
    assert stats.num_converged == E


def test_cold_start_entities_score_zero(glmix_data):
    batch, Xr, users, y = glmix_data
    fixed, rand = make_coordinates(batch, Xr, users, y)
    cd = CoordinateDescent(
        {"global": fixed, "per_user": rand}, ["global", "per_user"], num_iterations=1
    )
    model = cd.run(batch).model
    cold = GameBatch(
        label=batch.label, offset=batch.offset, weight=batch.weight,
        features=batch.features,
        entity_ids={"userId": jnp.full((N,), -1, jnp.int32)},
    )
    re_scores = model.models["per_user"].score(cold)
    assert float(jnp.max(jnp.abs(re_scores))) == 0.0


def test_warm_start_initial_model(glmix_data):
    batch, Xr, users, y = glmix_data
    fixed, rand = make_coordinates(batch, Xr, users, y)
    cd = CoordinateDescent(
        {"global": fixed, "per_user": rand}, ["global", "per_user"], num_iterations=1
    )
    first = cd.run(batch)
    # Warm start from the previous model (GameEstimator partial-retrain role).
    second = cd.run(batch, initial_model=first.model)
    suite = EvaluationSuite([EvaluatorSpec.parse("AUC")])
    auc1 = suite.evaluate_model(first.model, batch)["AUC"]
    auc2 = suite.evaluate_model(second.model, batch)["AUC"]
    assert auc2 >= auc1 - 1e-3


def test_locked_coordinates(glmix_data):
    batch, Xr, users, y = glmix_data
    fixed, rand = make_coordinates(batch, Xr, users, y)
    cd0 = CoordinateDescent({"global": fixed}, ["global"])
    pretrained = cd0.run(batch).model
    cd = CoordinateDescent(
        {"global": fixed, "per_user": rand},
        ["global", "per_user"],
        num_iterations=1,
        locked_coordinates=["global"],
    )
    result = cd.run(batch, initial_model=pretrained)
    # Locked coordinate unchanged.
    np.testing.assert_array_equal(
        np.asarray(result.model.models["global"].model.coefficients.means),
        np.asarray(pretrained.models["global"].model.coefficients.means),
    )
    # Locked without a model → error.
    with pytest.raises(ValueError):
        CoordinateDescent(
            {"global": fixed, "per_user": rand}, ["global", "per_user"],
            locked_coordinates=["global"],
        ).run(batch)


def test_reservoir_sampling_bounds_active_data(glmix_data):
    batch, Xr, users, y = glmix_data
    ds = build_random_effect_dataset(
        np.asarray(users), np.asarray(Xr), np.asarray(y), np.ones(N, np.float32), E,
        RandomEffectDataConfig(
            re_type="userId", feature_shard="per_user", active_upper_bound=20
        ),
    )
    for b in ds.blocks:
        counts = np.asarray(jnp.sum(b.weight > 0, axis=1))
        assert counts.max() <= 20
    # Deterministic: same config → identical sampling.
    ds2 = build_random_effect_dataset(
        np.asarray(users), np.asarray(Xr), np.asarray(y), np.ones(N, np.float32), E,
        RandomEffectDataConfig(
            re_type="userId", feature_shard="per_user", active_upper_bound=20
        ),
    )
    for b1, b2 in zip(ds.blocks, ds2.blocks):
        np.testing.assert_array_equal(np.asarray(b1.sample_index), np.asarray(b2.sample_index))


def test_pearson_feature_selection_keeps_informative(glmix_data):
    """With a feature cap, the informative features survive and dead columns
    are dropped (regression: constant columns used to crowd out real ones)."""
    batch, Xr, users, y = glmix_data
    # Add 4 dead columns the entities never touch.
    Xr_wide = np.concatenate(
        [np.asarray(Xr), np.zeros((N, 4), np.float32)], axis=1
    )
    fixed, rand = make_coordinates(
        batch, Xr_wide, users, y, features_to_samples_ratio=0.05
    )
    from photon_tpu.data.random_effect import pearson_feature_mask

    block = rand.dataset.blocks[0]
    counts = jnp.sum(block.weight > 0, axis=1)
    k_e = jnp.clip((counts * 0.05).astype(jnp.int32), 1, 8)
    mask = pearson_feature_mask(block, k_e, always_keep=0)
    m = np.asarray(mask)
    # Intercept always kept; dead columns never kept.
    assert np.all(m[:, 0] == 1.0)
    assert np.all(m[:, 4:] == 0.0)


def test_tracker_wall_times_and_summary(glmix_data):
    """Wall-times per solve + summary table (OptimizationStatesTracker
    toSummaryString role) + event-bus emission (VERDICT r2 #9)."""
    from photon_tpu.utils.events import EventEmitter

    batch, Xr, users, y = glmix_data
    fixed, rand = make_coordinates(batch, Xr, users, y)
    events = []
    emitter = EventEmitter()
    emitter.register(events.append)
    cd = CoordinateDescent(
        {"global": fixed, "per_user": rand}, ["global", "per_user"], num_iterations=2
    )
    result = cd.run(batch, emitter=emitter)

    # Wall times: one entry per (coordinate, CD pass).
    assert len(result.wall_times["global"]) == 2
    assert len(result.wall_times["per_user"]) == 2
    assert all(t > 0 for t in result.wall_times["global"])

    # Summary table: per-pass header with wall time + per-iteration rows
    # (loss, |grad|) for the fixed effect, aggregate stats for RE.
    s = result.summary()
    assert "coordinate 'global', CD pass 0 (wall" in s
    assert "iter    loss           |grad|" in s
    assert "entities=" in s  # RandomEffectTrackerStats line

    # Event bus: one PhotonOptimizationLogEvent per solve with the summary.
    logs = [e for e in events if e.name == "PhotonOptimizationLogEvent"]
    assert len(logs) == 4
    assert {e.payload["coordinate"] for e in logs} == {"global", "per_user"}
    assert all(e.payload["wall_s"] > 0 for e in logs)
    assert any("loss" in e.payload["summary"] for e in logs)


def test_normalization_folded_matches_explicit_pretransform():
    """GAME fit with a folded NormalizationContext on RAW features must match
    the same fit run WITHOUT normalization on explicitly standardized
    features — models in both runs live in their feature space's model
    coordinates, so validation scores coincide. Guards the reference's
    convert-in/convert-out contract (Optimizer.scala:167,
    DistributedOptimizationProblem.scala:127): before round 4 the estimator
    stored transformed-space coefficients and scored raw features with them.
    """
    from photon_tpu.data.normalization import NormalizationContext
    from photon_tpu.estimators.config import (
        FixedEffectCoordinateConfig,
        GameOptimizationConfig,
        RandomEffectCoordinateConfig,
        RegularizationConfig,
    )
    from photon_tpu.estimators.game_estimator import GameEstimator

    rng2 = np.random.default_rng(42)
    n, d_fix, d_re, e = 1024, 6, 3, 12
    scales = np.array([1.0, 50.0, 0.02, 7.0, 300.0, 0.5], np.float32)
    Xf = (rng2.normal(size=(n, d_fix)) * scales + 2.0 * scales).astype(np.float32)
    Xf[:, 0] = 1.0
    Xr = (rng2.normal(size=(n, d_re)) * np.array([1.0, 20.0, 0.1], np.float32)
          ).astype(np.float32)
    Xr[:, 0] = 1.0
    users = rng2.integers(0, e, size=n).astype(np.int32)
    logits = (Xf / (scales + 1.0)) @ rng2.normal(size=d_fix).astype(np.float32)
    y = (rng2.uniform(size=n) < 1 / (1 + np.exp(-logits))).astype(np.float32)

    def mk_batch(Xf_, Xr_):
        return GameBatch(
            label=jnp.asarray(y),
            offset=jnp.zeros(n, jnp.float32),
            weight=jnp.ones(n, jnp.float32),
            features={"global": jnp.asarray(Xf_), "per_user": jnp.asarray(Xr_)},
            entity_ids={"userId": jnp.asarray(users)},
        )

    def std_ctx(X):
        mean = X.mean(0)
        std = X.std(0)
        mean[0], std[0] = 0.0, 1.0
        return NormalizationContext(
            factors=jnp.asarray(1.0 / std), shifts=jnp.asarray(mean),
            intercept_index=0,
        ), (X - mean) / std

    ctx_f, Xf_explicit = std_ctx(Xf.copy())
    ctx_r, Xr_explicit = std_ctx(Xr.copy())

    def fit(batch, normalization):
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs=[
                FixedEffectCoordinateConfig("global", "global"),
                RandomEffectCoordinateConfig("per_user", "userId", "per_user"),
            ],
            num_iterations=2,
            intercept_indices={"global": 0, "per_user": 0},
            num_entities={"userId": e},
            normalization=normalization,
        )
        cfg = GameOptimizationConfig(reg={
            "global": RegularizationConfig(weight=1.0),
            "per_user": RegularizationConfig(weight=1.0),
        })
        (res,) = est.fit(batch, optimization_configs=[cfg])
        return res.model

    folded = fit(mk_batch(Xf, Xr),
                 {"global": ctx_f, "per_user": ctx_r})
    explicit = fit(mk_batch(Xf_explicit.astype(np.float32),
                            Xr_explicit.astype(np.float32)), None)

    s_folded = np.asarray(folded.score(mk_batch(Xf, Xr)))
    s_explicit = np.asarray(
        explicit.score(mk_batch(Xf_explicit.astype(np.float32),
                                Xr_explicit.astype(np.float32)))
    )
    np.testing.assert_allclose(s_folded, s_explicit, rtol=2e-3, atol=2e-3)


def test_active_lower_bound_and_ignore_threshold_for_new_models():
    """Reference ignoreThresholdForNewModels (GameTrainingDriver.scala:
    169-172 + RandomEffectDataset.filterActiveData:550-570): with a
    warm-start model, entities WITHOUT an existing model bypass the
    active-data lower bound; entities WITH one must still meet it."""
    from photon_tpu.data.random_effect import (
        RandomEffectDataConfig, build_random_effect_dataset,
    )

    n_e, d = 4, 3
    # entity 0: 5 samples, 1: 2 samples, 2: 2 samples, 3: 5 samples
    counts = [5, 2, 2, 5]
    eids = np.concatenate([np.full(c, e, np.int32) for e, c in enumerate(counts)])
    n = eids.size
    feats = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    w = np.ones(n, np.float32)
    cfg = RandomEffectDataConfig(
        re_type="userId", feature_shard="re", active_lower_bound=3
    )

    def trainable(ds):
        out = {}
        for b in ds.blocks:
            for eid, m in zip(np.asarray(b.entity_idx), np.asarray(b.train_mask)):
                out[int(eid)] = bool(m)
        return out

    # No warm start: the bound applies to everyone.
    t = trainable(build_random_effect_dataset(eids, feats, y, w, n_e, cfg))
    assert t == {0: True, 1: False, 2: False, 3: True}

    # Warm start where entity 1 HAS a model and entity 2 does NOT:
    # 1 must still meet the bound (fails), 2 is exempt (trains).
    existing = np.array([True, True, False, True])
    t = trainable(build_random_effect_dataset(
        eids, feats, y, w, n_e, cfg, existing_model_mask=existing
    ))
    assert t == {0: True, 1: False, 2: True, 3: True}


def test_ignore_threshold_requires_warm_start_model():
    """GameTrainingDriver.scala:250-252 require parity."""
    from photon_tpu.estimators.game_estimator import GameEstimator
    from photon_tpu.estimators.config import (
        FixedEffectCoordinateConfig,
    )

    with pytest.raises(ValueError, match="warm-start"):
        GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs=[
                FixedEffectCoordinateConfig(
                    coordinate_id="global", feature_shard="global"
                )
            ],
            ignore_threshold_for_new_models=True,
        )


def test_existing_entity_mask_model_types():
    """Warm-start presence semantics (reference key-presence,
    RandomEffectDataset.scala:550-570): projected models report presence by
    entity_block >= 0 (no AttributeError), dense models without a loader
    mask treat every row as existing (an all-zero L1-sparsified row is NOT
    'new'), present_entities wins when set, and unknown model types raise
    a descriptive TypeError."""
    from photon_tpu.estimators.game_estimator import _existing_entity_mask
    from photon_tpu.models.game import (
        ProjectedRandomEffectModel, RandomEffectModel,
    )

    proj = ProjectedRandomEffectModel(
        block_coefs=[jnp.zeros((2, 3), jnp.float32)],
        col_maps=[jnp.arange(3, dtype=jnp.int32)],
        inv_maps=[jnp.arange(3, dtype=jnp.int32)],
        entity_block=jnp.asarray([0, -1, 0], jnp.int32),
        entity_row=jnp.asarray([0, 0, 1], jnp.int32),
        d_full=3, re_type="userId", feature_shard="re",
        task=TaskType.LOGISTIC_REGRESSION,
    )
    np.testing.assert_array_equal(
        _existing_entity_mask(proj), [True, False, True]
    )

    dense = RandomEffectModel(
        jnp.asarray([[0.0, 0.0], [1.0, 0.0]], jnp.float32),  # row 0 L1-zeroed
        "userId", "re", TaskType.LOGISTIC_REGRESSION,
    )
    np.testing.assert_array_equal(_existing_entity_mask(dense), [True, True])

    with_mask = RandomEffectModel(
        jnp.zeros((3, 2), jnp.float32), "userId", "re",
        TaskType.LOGISTIC_REGRESSION,
        present_entities=jnp.asarray([True, False, True]),
    )
    np.testing.assert_array_equal(
        _existing_entity_mask(with_mask), [True, False, True]
    )

    with pytest.raises(TypeError, match="RandomEffectModel"):
        _existing_entity_mask(object())
