"""The fixed effect's update reads X only inside its solver's iterations.

The solve program hands back the new model's scores (the margins the
margin-space L-BFGS carried, less the offset; one fused pass at the program's
end for a solver that carries none) and starts from the scores coordinate
descent already holds. Counted and compared on the CPU at small shapes: the
scores are those of ``model.score(batch)``, a start from held scores ends on
the same coefficients with one pass over X fewer, nothing drifts over many
passes, the diverged backstop takes the score back with the coefficients, a
resumed descent ends where the uninterrupted one does, and nothing of the
batch's length rides on a tracker.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.algorithm import (
    CoordinateDescent,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_tpu.algorithm.solve_cache import SolveCache
from photon_tpu.data.batch import LabeledBatch, SparseFeatures
from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.normalization import NormalizationContext
from photon_tpu.data.random_effect import (
    RandomEffectDataConfig,
    build_random_effect_dataset,
)
from photon_tpu.obs.metrics import registry
from photon_tpu.ops import GLMObjective, LogisticLoss
from photon_tpu.optim.common import OptimizerConfig
from photon_tpu.optim.factory import OptimizerSpec, carries_margins, routed_solver
from photon_tpu.optim.margin_lbfgs import minimize_lbfgs_margin
from photon_tpu.sampling.down_sampler import BinaryClassificationDownSampler
from photon_tpu.types import ConvergenceReason, OptimizerType, TaskType
from photon_tpu.utils.checkpoint import latest_step, load_checkpoint

N, D, D_RE, E = 768, 7, 3, 12


# --- the data ----------------------------------------------------------------


def design(seed=37):
    """Half of the entries zero (so the padded-sparse form is not the dense
    one), an intercept column of ones, columns of unequal scale and mean."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, D)) * rng.uniform(0.5, 3.0, size=D) + rng.normal(size=D)
    X = np.where(rng.uniform(size=(N, D)) < 0.5, X, 0.0).astype(np.float32)
    X[:, 0] = 1.0
    w_true = (rng.normal(size=D) / np.sqrt(D)).astype(np.float32)
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-(X @ w_true)))).astype(np.float32)
    residual = rng.normal(scale=0.5, size=N).astype(np.float32)
    return X, y, residual


def as_features(X, storage):
    if storage == "dense":
        return jnp.asarray(X)
    rows = [(np.flatnonzero(r), r[np.flatnonzero(r)]) for r in X]
    return SparseFeatures.from_rows(rows, X.shape[1])


def game_batch(X, y, storage="dense"):
    return GameBatch(
        label=jnp.asarray(y),
        offset=jnp.zeros(N, jnp.float32),
        weight=jnp.ones(N, jnp.float32),
        features={"global": as_features(X, storage)},
        entity_ids={},
    )


def normalization(kind, X):
    if kind == "identity":
        return None
    std, mean = X.std(axis=0), X.mean(axis=0)
    factors = np.where(std > 0, 1.0 / np.where(std > 0, std, 1.0), 1.0)
    factors[0] = 1.0
    shifts = None
    if kind == "factors_shifts":
        shifts = mean.copy()
        shifts[0] = 0.0
    return NormalizationContext(
        jnp.asarray(factors, jnp.float32),
        None if shifts is None else jnp.asarray(shifts, jnp.float32),
        0,
    )


def fixed_coordinate(objective, spec=None, down_sampler=None):
    return FixedEffectCoordinate(
        "global", "global", TaskType.LOGISTIC_REGRESSION, objective,
        spec or OptimizerSpec(), down_sampler=down_sampler,
        solve_cache=SolveCache(),
    )


def assert_scores_of(model, batch, scores, rel=1e-5):
    want = np.asarray(model.score(batch))
    got = np.asarray(scores)
    assert got.shape == want.shape == (N,)
    assert np.max(np.abs(got - want)) <= rel * np.max(np.abs(want))


def counted(name, source):
    found = registry().find(name, coordinate="global", source=source)
    return 0 if found is None else found.value


# --- (1) the score that comes back is the model's -----------------------------


@pytest.mark.parametrize("sampled", [False, True], ids=["all_rows", "down_sampled"])
@pytest.mark.parametrize("storage", ["dense", "sparse"])
@pytest.mark.parametrize("norm", ["identity", "factors", "factors_shifts"])
def test_margin_solver_hands_back_the_models_score(norm, storage, sampled):
    X, y, residual = design()
    batch = game_batch(X, y, storage)
    objective = GLMObjective(
        loss=LogisticLoss, l2_weight=1.0, intercept_index=0,
        normalization=normalization(norm, X),
    )
    assert carries_margins(objective, OptimizerSpec())
    coord = fixed_coordinate(
        objective,
        down_sampler=BinaryClassificationDownSampler(0.5, seed=3) if sampled else None,
    )
    registry().reset()
    # From zero, then from the model and the scores that came back.
    model, diag, scores = coord.update(batch, jnp.asarray(residual))
    assert_scores_of(model, batch, scores)
    again, diag2, scores2 = coord.update(
        batch, jnp.asarray(-residual), model, scores
    )
    assert_scores_of(again, batch, scores2)
    assert int(diag.iterations) > 1 and int(diag2.iterations) > 1
    # ... and train() is the same solve without the scores: it recomputes
    # the starting margins, whose last bits differ from the held ones, so the
    # two stop on the same objective (the coefficients agree only to what a
    # relative function change of 1e-7 resolves of them, ~1e-3 here).
    trained, diag3 = coord.train(batch, jnp.asarray(-residual), model)
    np.testing.assert_allclose(float(diag3.value), float(diag2.value), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(trained.model.coefficients.means),
        np.asarray(again.model.coefficients.means), rtol=0, atol=1e-2,
    )
    assert counted("fe_score_source_total", "solver_margins") == 3
    assert counted("fe_score_source_total", "fused_pass") == 0
    assert counted("fe_start_margins_total", "zero") == 1
    assert counted("fe_start_margins_total", "prior_score") == 1
    assert counted("fe_start_margins_total", "recomputed") == 1
    assert coord.solve_cache.stats.traces == 2  # with and without a start


def _box():
    return (jnp.full((D,), -5.0, jnp.float32), jnp.full((D,), 5.0, jnp.float32))


ROUTES = {
    "tron": ({}, lambda: OptimizerSpec(optimizer=OptimizerType.TRON)),
    "lbfgsb": ({}, lambda: OptimizerSpec(optimizer=OptimizerType.LBFGSB, box=_box())),
    "lbfgs": ({}, lambda: OptimizerSpec(box=_box())),
}


@pytest.mark.parametrize("norm", ["identity", "factors_shifts"])
def test_an_l1_solver_hands_back_its_margins(norm):
    """An L1 term routes to margin-space OWL-QN, which starts from the held
    scores and hands its margins back like margin-space L-BFGS."""
    X, y, residual = design()
    batch = game_batch(X, y)
    objective = GLMObjective(
        loss=LogisticLoss, l2_weight=1.0, l1_weight=2.0, intercept_index=0,
        normalization=normalization(norm, X),
    )
    spec = OptimizerSpec()
    assert routed_solver(objective, spec) == "owlqn_margin"
    assert carries_margins(objective, spec)
    coord = fixed_coordinate(objective, spec)
    registry().reset()
    model, diag, scores = coord.update(batch, jnp.asarray(residual))
    assert diag.optimizer == "owlqn_margin"
    assert_scores_of(model, batch, scores)
    again, _diag, scores2 = coord.update(batch, jnp.asarray(-residual), model, scores)
    assert_scores_of(again, batch, scores2)
    assert int(diag.iterations) > 1
    assert counted("fe_score_source_total", "solver_margins") == 2
    assert counted("fe_score_source_total", "fused_pass") == 0
    assert counted("fe_start_margins_total", "zero") == 1
    assert counted("fe_start_margins_total", "prior_score") == 1
    assert coord.solve_cache.stats.traces == 1  # held scores from zero, then the model's


@pytest.mark.parametrize("norm", ["identity", "factors_shifts"])
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_a_black_box_solver_scores_in_its_own_program(route, norm):
    X, y, residual = design()
    batch = game_batch(X, y)
    extra, spec = ROUTES[route]
    objective = GLMObjective(
        loss=LogisticLoss, l2_weight=1.0, intercept_index=0,
        normalization=normalization(norm, X), **extra,
    )
    spec = spec()
    assert routed_solver(objective, spec) == route
    assert not carries_margins(objective, spec)
    coord = fixed_coordinate(objective, spec)
    registry().reset()
    model, diag, scores = coord.update(batch, jnp.asarray(residual))
    assert diag.optimizer == route
    assert_scores_of(model, batch, scores)
    again, _diag, scores2 = coord.update(batch, jnp.asarray(-residual), model, scores)
    assert_scores_of(again, batch, scores2)
    assert counted("fe_score_source_total", "fused_pass") == 2
    assert counted("fe_score_source_total", "solver_margins") == 0
    assert counted("fe_start_margins_total", "recomputed") == 2
    assert coord.solve_cache.stats.traces == 1  # the held scores are not passed


def test_the_fused_pallas_path_hands_back_its_fresh_margins():
    X, y, residual = design()
    batch = game_batch(X, y)
    objective = GLMObjective(
        loss=LogisticLoss, l2_weight=1.0, intercept_index=0, use_pallas=True
    )
    assert objective._can_fuse(batch.labeled_batch("global"))
    coord = fixed_coordinate(objective)
    model, _diag, scores = coord.update(batch, jnp.asarray(residual))
    assert_scores_of(model, batch, scores)
    again, _diag, scores2 = coord.update(batch, jnp.asarray(-residual), model, scores)
    assert_scores_of(again, batch, scores2)


def test_a_data_sharded_batch_gets_its_score_from_the_solver():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from photon_tpu.parallel.mesh import make_mesh

    X, y, residual = design()
    mesh = make_mesh(n_data=8)
    rows, mat = NamedSharding(mesh, P("data")), NamedSharding(mesh, P("data", None))
    batch = GameBatch(
        label=jax.device_put(jnp.asarray(y), rows),
        offset=jax.device_put(jnp.zeros(N, jnp.float32), rows),
        weight=jax.device_put(jnp.ones(N, jnp.float32), rows),
        features={"global": jax.device_put(jnp.asarray(X), mat)},
        entity_ids={},
    )
    coord = fixed_coordinate(
        GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)
    )
    model, _diag, scores = coord.update(batch, jax.device_put(jnp.asarray(residual), rows))
    assert_scores_of(model, batch, scores)
    again, _diag, scores2 = coord.update(batch, None, model, scores)
    assert_scores_of(again, batch, scores2)
    # the unsharded solve's coefficients, to the reductions' reordering
    plain, _ = fixed_coordinate(coord.objective).train(game_batch(X, y), None, model)
    np.testing.assert_allclose(
        np.asarray(again.model.coefficients.means),
        np.asarray(plain.model.coefficients.means), rtol=1e-4, atol=1e-5,
    )


def test_score_of_a_model_nobody_solved_here_is_the_fused_pass():
    X, y, _ = design()
    coord = fixed_coordinate(
        GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)
    )
    for storage in ("dense", "sparse"):
        batch = game_batch(X, y, storage)
        model, _ = coord.train(batch)
        assert_scores_of(model, batch, coord.score(model, batch), rel=1e-6)


# --- (2) a start from held scores: the same answer, one pass fewer --------------


@pytest.mark.parametrize("norm", ["identity", "factors_shifts"])
@pytest.mark.parametrize("start", ["zero", "warm"])
def test_start_score_saves_one_pass_and_moves_nothing(start, norm):
    X, y, residual = design()
    objective = GLMObjective(
        loss=LogisticLoss, l2_weight=1.0, intercept_index=0,
        normalization=normalization(norm, X),
    )
    lb = LabeledBatch(jnp.asarray(y), jnp.asarray(X), jnp.asarray(residual))
    config = OptimizerConfig()
    w0 = jnp.zeros((D,), jnp.float32)
    if start == "warm":
        w0 = minimize_lbfgs_margin(
            objective, lb, w0, dataclasses.replace(config, max_iter=2)
        ).w
    held = objective.scores(w0, lb)
    solve = jax.jit(
        lambda w, s: minimize_lbfgs_margin(
            objective, lb, w, config, start_score=s, return_score=True
        )
    )
    recomputed = jax.jit(lambda w: minimize_lbfgs_margin(objective, lb, w, config))(w0)
    (started, score) = solve(w0, held)
    np.testing.assert_allclose(
        np.asarray(started.w), np.asarray(recomputed.w), rtol=0, atol=1e-6
    )
    assert int(started.iterations) == int(recomputed.iterations) > 1
    assert int(started.evals) == int(recomputed.evals) - 1
    assert started.eval_unit == "x_passes"
    want = np.asarray(objective.scores(started.w, lb))
    assert np.max(np.abs(np.asarray(score) - want)) <= 1e-5 * np.max(np.abs(want))
    # nothing new comes out unless asked
    assert not isinstance(recomputed, tuple)


# --- (3)-(6) inside a descent --------------------------------------------------


def glmix(seed=5):
    rng = np.random.default_rng(seed)
    X, _y, _ = design(seed)
    Xr = rng.normal(size=(N, D_RE)).astype(np.float32)
    Xr[:, 0] = 1.0
    users = (np.arange(N) % E).astype(np.int32)
    w_fix = (rng.normal(size=D) / np.sqrt(D)).astype(np.float32)
    w_users = rng.normal(size=(E, D_RE)).astype(np.float32)
    logits = X @ w_fix + np.sum(Xr * w_users[users], axis=1)
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    batch = GameBatch(
        label=jnp.asarray(y),
        offset=jnp.zeros(N, jnp.float32),
        weight=jnp.ones(N, jnp.float32),
        features={"global": jnp.asarray(X), "per_user": jnp.asarray(Xr)},
        entity_ids={"userId": jnp.asarray(users)},
    )
    fixed = fixed_coordinate(
        GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)
    )
    ds = build_random_effect_dataset(
        users, Xr, y, np.ones(N, np.float32), E,
        RandomEffectDataConfig(re_type="userId", feature_shard="per_user"),
    )
    rand = RandomEffectCoordinate(
        "per_user", ds, TaskType.LOGISTIC_REGRESSION,
        GLMObjective(loss=LogisticLoss, l2_weight=0.5, intercept_index=0),
    )
    return batch, X, {"global": fixed, "per_user": rand}


SEQUENCE = ["global", "per_user"]


def test_held_score_does_not_drift_over_ten_passes(tmp_path):
    batch, X, coords = glmix()
    ck = str(tmp_path / "ck")
    registry().reset()
    result = CoordinateDescent(coords, SEQUENCE, num_iterations=10).run(
        batch, checkpoint_dir=ck
    )
    state, step = load_checkpoint(ck)
    assert step == 9
    held = np.asarray(state["scores"]["global"])
    w = np.asarray(result.model.models["global"].model.coefficients.means)
    fresh = X.astype(np.float64) @ w.astype(np.float64)
    assert np.max(np.abs(held - fresh)) <= 1e-5 * np.max(np.abs(fresh))
    total = np.asarray(state["total_scores"])
    both = fresh + np.asarray(result.model.models["per_user"].score(batch))
    assert np.max(np.abs(total - both)) <= 1e-5 * np.max(np.abs(both))
    # every pass after the first started from the score the loop held
    assert counted("fe_start_margins_total", "zero") == 1
    assert counted("fe_start_margins_total", "prior_score") == 9
    assert counted("fe_start_margins_total", "recomputed") == 0
    assert counted("fe_score_source_total", "solver_margins") == 10
    # (6) nothing of the batch's length rides on a tracker
    assert E != N and D != N
    for cid in SEQUENCE:
        assert len(result.tracker[cid]) == 10
        for diag in result.tracker[cid]:
            for leaf in jax.tree_util.tree_leaves(diag):
                assert N not in np.shape(leaf), (cid, np.shape(leaf))


def test_resume_mid_descent_ends_on_the_uninterrupted_model(tmp_path):
    batch, _X, coords = glmix()
    full = CoordinateDescent(dict(coords), SEQUENCE, num_iterations=4).run(batch)
    ck = str(tmp_path / "ck")
    CoordinateDescent(dict(coords), SEQUENCE, num_iterations=2).run(
        batch, checkpoint_dir=ck
    )
    assert latest_step(ck) == 1
    registry().reset()
    resumed = CoordinateDescent(dict(coords), SEQUENCE, num_iterations=4).run(
        batch, checkpoint_dir=ck
    )
    # The resumed passes start from the checkpoint's scores, which are the
    # ones the uninterrupted run held: the same programs on the same bits.
    assert counted("fe_start_margins_total", "prior_score") == 2
    assert counted("fe_start_margins_total", "zero") == 0
    for a, b in zip(jax.tree_util.tree_leaves(full.model),
                    jax.tree_util.tree_leaves(resumed.model), strict=True):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6, atol=1e-7)


def test_warm_start_scores_its_model_once_and_starts_from_that():
    batch, X, coords = glmix()
    first = CoordinateDescent(dict(coords), SEQUENCE, num_iterations=1).run(batch)
    registry().reset()
    CoordinateDescent(dict(coords), SEQUENCE, num_iterations=2).run(
        batch, initial_model=first.model
    )
    assert counted("fe_start_margins_total", "prior_score") == 2
    assert counted("fe_start_margins_total", "zero") == 0
    assert counted("fe_start_margins_total", "recomputed") == 0


# --- (4) the diverged backstop ---------------------------------------------------


@pytest.mark.parametrize("held", [False, True], ids=["recomputed", "held_scores"])
@pytest.mark.parametrize("route", ["lbfgs_margin", "lbfgs"])
def test_diverged_backstop_returns_the_warm_starts_score(route, held):
    """Margin-space L-BFGS ends on a non-finite point and the solve cache's
    backstop takes coefficients and score back; the black-box L-BFGS rolls
    back inside its own loop and the program's closing pass scores that."""
    X, y, _ = design()
    y_bad = y.copy()
    y_bad[3] = np.nan  # every evaluation goes non-finite; X·w0 stays finite
    batch = game_batch(X, y_bad)
    objective = GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)
    spec = OptimizerSpec(box=_box() if route == "lbfgs" else None)
    assert routed_solver(objective, spec) == route
    coord = fixed_coordinate(objective, spec)
    start, _ = fixed_coordinate(objective, spec).train(game_batch(X, y))
    w0 = np.asarray(start.model.coefficients.means)
    assert np.all(np.isfinite(w0)) and np.any(w0 != 0.0)
    model, diag, scores = coord.update(
        batch, None, start, start.score(batch) if held else None
    )
    assert diag.convergence_reason == ConvergenceReason.DIVERGED
    np.testing.assert_array_equal(np.asarray(model.model.coefficients.means), w0)
    assert np.all(np.isfinite(np.asarray(scores)))
    assert_scores_of(start, batch, scores)
