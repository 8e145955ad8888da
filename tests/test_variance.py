"""SIMPLE vs FULL coefficient-variance computation (reference
DistributedOptimizationProblem.scala:83-103, Linalg.scala:33-100)."""

import numpy as np
import jax.numpy as jnp
import pytest

from photon_tpu.data.batch import LabeledBatch
from photon_tpu.ops import GLMObjective, LogisticLoss, SquaredLoss
from photon_tpu.ops.variance import (
    coefficient_variances,
    full_hessian_variances,
    normalize_variance_type,
)
from photon_tpu.types import TaskType, VarianceComputationType


def _linear_problem(n=256, d=6, seed=4):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32)
    y = (X @ w + 0.1 * rng.normal(size=n)).astype(np.float32)
    return X, y


def test_full_matches_closed_form_ols():
    """Linear regression, no penalty: FULL variances == diag((XᵀX)⁻¹), the
    textbook OLS covariance diagonal (σ² = 1)."""
    X, y = _linear_problem()
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X))
    obj = GLMObjective(loss=SquaredLoss)
    w = jnp.zeros(X.shape[1], jnp.float32)  # H is w-independent for OLS
    v_full = coefficient_variances(obj, w, batch, VarianceComputationType.FULL)
    expected = np.diag(np.linalg.inv(X.T @ X))
    np.testing.assert_allclose(np.asarray(v_full), expected, rtol=1e-3)
    # SIMPLE is the diagonal-inverse — different whenever X has correlated
    # columns, and an underestimate of the marginal variance.
    v_simple = coefficient_variances(obj, w, batch, VarianceComputationType.SIMPLE)
    np.testing.assert_allclose(np.asarray(v_simple), 1.0 / np.diag(X.T @ X), rtol=1e-4)
    assert np.all(np.asarray(v_full) >= np.asarray(v_simple) * 0.999)


def test_full_logistic_with_l2():
    X, y = _linear_problem()
    y = (y > 0).astype(np.float32)
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X))
    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.5)
    w = jnp.full(X.shape[1], 0.1, jnp.float32)
    v = coefficient_variances(obj, w, batch, VarianceComputationType.FULL)
    H = np.asarray(obj.hessian_matrix(w, batch))
    np.testing.assert_allclose(np.asarray(v), np.diag(np.linalg.inv(H)), rtol=1e-3)


def test_full_hessian_variances_degenerate_fallback():
    """A singular H (dead unpenalized column) must not poison the vector:
    degenerate coordinates fall back to the SIMPLE estimate."""
    H = jnp.asarray([[2.0, 0.0], [0.0, 0.0]], jnp.float32)
    v = np.asarray(full_hessian_variances(H))
    assert np.isfinite(v).all()
    np.testing.assert_allclose(v[0], 0.5, rtol=1e-5)


def test_normalize_variance_type():
    assert normalize_variance_type(None) == VarianceComputationType.NONE
    assert normalize_variance_type(False) == VarianceComputationType.NONE
    assert normalize_variance_type(True) == VarianceComputationType.SIMPLE
    assert normalize_variance_type("full") == VarianceComputationType.FULL
    assert (
        normalize_variance_type(VarianceComputationType.FULL)
        == VarianceComputationType.FULL
    )
    with pytest.raises(ValueError):
        normalize_variance_type("bogus")


def test_fixed_effect_full_variances_end_to_end():
    from photon_tpu.algorithm import FixedEffectCoordinate
    from photon_tpu.data.game_data import GameBatch
    from photon_tpu.optim.factory import OptimizerSpec

    X, y = _linear_problem(n=512, d=5, seed=7)
    batch = GameBatch(
        label=jnp.asarray(y),
        offset=jnp.zeros(len(y), jnp.float32),
        weight=jnp.ones(len(y), jnp.float32),
        features={"global": jnp.asarray(X)},
        entity_ids={},
    )
    obj = GLMObjective(loss=SquaredLoss)
    coord = FixedEffectCoordinate(
        "global", "global", TaskType.LINEAR_REGRESSION, obj, OptimizerSpec(),
        compute_variance="FULL",  # string shorthand accepted
    )
    model, _ = coord.train(batch)
    v = np.asarray(model.model.coefficients.variances)
    expected = np.diag(np.linalg.inv(X.T @ X))
    np.testing.assert_allclose(v, expected, rtol=1e-3)


def test_random_effect_full_variances_vmapped():
    from photon_tpu.algorithm import RandomEffectCoordinate
    from photon_tpu.data.game_data import GameBatch
    from photon_tpu.data.random_effect import (
        RandomEffectDataConfig,
        build_random_effect_dataset,
    )

    rng = np.random.default_rng(11)
    N, E, d = 512, 8, 3
    Xr = rng.normal(size=(N, d)).astype(np.float32)
    users = rng.integers(0, E, size=N).astype(np.int32)
    y = (rng.uniform(size=N) < 0.5).astype(np.float32)
    ds = build_random_effect_dataset(
        users, Xr, y, np.ones(N, np.float32), E,
        RandomEffectDataConfig(re_type="u", feature_shard="re"),
    )
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    coord = RandomEffectCoordinate(
        "re", ds, TaskType.LOGISTIC_REGRESSION, obj,
        compute_variance=VarianceComputationType.FULL,
    )
    batch = GameBatch(
        label=jnp.asarray(y), offset=jnp.zeros(N, jnp.float32),
        weight=jnp.ones(N, jnp.float32), features={"re": jnp.asarray(Xr)},
        entity_ids={"u": jnp.asarray(users)},
    )
    model, _ = coord.train(batch)
    v = np.asarray(model.variances)
    assert v.shape == (E, d)
    assert np.isfinite(v).all() and (v > 0).all()
    # Cross-check one entity against the dense closed form.
    e = 0
    rows = users == e
    lb = LabeledBatch(jnp.asarray(y[rows]), jnp.asarray(Xr[rows]))
    w_e = jnp.asarray(np.asarray(model.coefficients)[e])
    H = np.asarray(obj.hessian_matrix(w_e, lb))
    np.testing.assert_allclose(v[e], np.diag(np.linalg.inv(H)), rtol=2e-3)
