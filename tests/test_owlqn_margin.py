"""Margin-space OWL-QN against the black-box OWL-QN.

``optim/margin_owlqn.py`` answers every line-search trial from carried
margins: one pass over X for the search's direction and the clip columns of
its first trials, one gradient pass at the accepted point. Held here on the
CPU at small shapes, on a Poisson elastic net and a logistic lasso, from
zero and from a warm start whose every coefficient is non-zero (so trials
clip): the same iterations, trials and accepted values as
``minimize_owlqn``, the same coefficients and the same exact zeros, under
normalization and on sparse storage too; the passes over X counted as the
program runs them; the score that crosses the solver's boundary; the trial
counters a read tracker publishes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.batch import LabeledBatch, SparseFeatures
from photon_tpu.data.normalization import NormalizationContext
from photon_tpu.obs.metrics import registry
from photon_tpu.ops import GLMObjective, LogisticLoss
from photon_tpu.ops.losses import PoissonLoss
from photon_tpu.optim import margin_owlqn
from photon_tpu.optim.common import OptimizerConfig
from photon_tpu.optim.factory import OptimizerSpec, make_optimizer
from photon_tpu.optim.margin_owlqn import minimize_owlqn_margin
from photon_tpu.optim.margins import GLMMargins
from photon_tpu.optim.owlqn import minimize_owlqn

N, D = 1024, 24
# The default tol (1e-7 relative change of F) stops at float32's resolution,
# where the last iteration of a solve flips with the last bits of the
# margins, black box against black box too; at 1e-5 every solve here ends
# on the same iteration either way.
CONFIG = OptimizerConfig(tol=1e-5)


def design(kind, seed=5):
    """An intercept column, half the other entries zero, 6 true features;
    Poisson counts or binary labels."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, D)) * rng.uniform(0.5, 2.0, size=D) + 0.3 * rng.normal(size=D)
    X = np.where(rng.uniform(size=(N, D)) < 0.5, X, 0.0).astype(np.float32)
    X[:, 0] = 1.0
    w_true = np.zeros(D, np.float32)
    w_true[1:7] = rng.choice([-1.0, 1.0], 6) * 0.25
    z = X @ w_true
    if kind == "poisson":
        y = rng.poisson(np.exp(z - 0.5)).astype(np.float32)
    else:
        y = (rng.uniform(size=N) < 1 / (1 + np.exp(-z))).astype(np.float32)
    return X, y


PROBLEMS = {
    # loss, l2, l1: penalties that leave most null features at exactly zero
    "poisson_enet": (PoissonLoss, 8.0, 8.0),
    "logistic_l1": (LogisticLoss, 0.0, 6.0),
}


def problem(name, storage="dense", norm=False, pallas=False):
    loss, l2, l1 = PROBLEMS[name]
    X, y = design("poisson" if loss is PoissonLoss else "logistic")
    if storage == "dense":
        feats = jnp.asarray(X)
    else:
        rows = [(np.flatnonzero(r), r[np.flatnonzero(r)]) for r in X]
        feats = SparseFeatures.from_rows(rows, D)
    offset = np.random.default_rng(9).normal(scale=0.1, size=N).astype(np.float32)
    batch = LabeledBatch(jnp.asarray(y), feats, jnp.asarray(offset),
                         jnp.ones(N, jnp.float32))
    normalization = None
    if norm:
        std, mean = X.std(axis=0), X.mean(axis=0)
        factors = 1.0 / np.where(std > 0, std, 1.0)
        factors[0], mean[0] = 1.0, 0.0
        normalization = NormalizationContext(
            jnp.asarray(factors, jnp.float32), jnp.asarray(mean, jnp.float32), 0)
    objective = GLMObjective(loss=loss, l2_weight=l2, l1_weight=l1,
                             intercept_index=0, normalization=normalization,
                             use_pallas=pallas)
    return objective, batch


def warm_start(scale=0.2, seed=13):
    """Every coefficient non-zero, most of them on the wrong side of the
    optimum's zero: the first searches clip."""
    w = np.random.default_rng(seed).normal(scale=scale, size=D).astype(np.float32)
    w[w == 0] = 0.1
    return jnp.asarray(w)


def black_box(objective, batch, w0, config=CONFIG):
    return jax.jit(lambda w: minimize_owlqn(
        lambda v: objective.value_and_grad(v, batch), w, objective.l1_weight,
        config, objective.l1_mask(w)))(w0)


def margin(objective, batch, w0, config=CONFIG):
    return jax.jit(lambda w: minimize_owlqn_margin(objective, batch, w, config))(w0)


def assert_same_solve(got, want):
    """The same iterations and trials (so the same accepted steps: trial k
    of a search is the step α_0·2^-k), the same value after every
    iteration, w within 1e-5 relative and the same exact zeros."""
    assert int(got.iterations) == int(want.iterations) > 1
    assert int(got.trials) == int(want.trials)
    assert int(got.reason_code) == int(want.reason_code)
    it = int(got.iterations)
    np.testing.assert_allclose(np.asarray(got.loss_history[: it + 1]),
                               np.asarray(want.loss_history[: it + 1]), rtol=1e-5)
    w, w_bb = np.asarray(got.w), np.asarray(want.w)
    assert np.linalg.norm(w - w_bb) <= 1e-5 * np.linalg.norm(w_bb)
    np.testing.assert_array_equal(w == 0, w_bb == 0)
    assert 0 < np.count_nonzero(w == 0) < D - 1


@pytest.mark.parametrize("start", ["zero", "warm"])
@pytest.mark.parametrize("name", sorted(PROBLEMS))
def test_margin_path_takes_the_black_box_steps(name, start):
    objective, batch = problem(name)
    w0 = jnp.zeros(D, jnp.float32) if start == "zero" else warm_start()
    got = margin(objective, batch, w0)
    want = black_box(objective, batch, w0)
    assert_same_solve(got, want)
    assert got.eval_unit == "x_passes" and want.eval_unit == "objective_evals"
    assert int(got.margin_trials) == int(got.trials)  # T columns covered them
    assert int(want.margin_trials) == 0
    if start == "warm":
        # exact zeros where w0 had none: some accepted trials clipped
        assert np.all(np.asarray(w0) != 0) and np.any(np.asarray(got.w) == 0)


@pytest.mark.parametrize("storage,norm,pallas", [
    ("dense", True, False), ("sparse", False, False), ("sparse", True, False),
    ("dense", False, True)])
def test_margin_path_under_normalization_and_sparse_storage(storage, norm, pallas):
    """The fused Pallas pass (interpret mode here) takes the gradient pass's
    place and refreshes the margins; the black box fuses every trial."""
    objective, batch = problem("poisson_enet", storage, norm, pallas)
    assert GLMMargins(objective, batch).use_fused == pallas
    for w0 in (jnp.zeros(D, jnp.float32), warm_start()):
        assert_same_solve(margin(objective, batch, w0),
                          black_box(objective, batch, w0))


@pytest.fixture
def passes(monkeypatch):
    """Passes over X as the program runs them: each of ``GLMMargins``'s
    products reports itself from inside the compiled program, so a branch
    not taken counts nothing."""
    seen = []

    def counted(method, kind):
        def run(self, *args):
            out = method(self, *args)
            jax.debug.callback(lambda: seen.append(kind))
            return out
        return run

    for attr, kind in [("matvecs", "search"), ("margins", "margins"),
                       ("grad_from_margins", "gradient"), ("matvec", "matvec")]:
        monkeypatch.setattr(GLMMargins, attr, counted(getattr(GLMMargins, attr), kind))
    return seen


@pytest.mark.parametrize("columns", [margin_owlqn.TRIAL_COLUMNS, 1],
                         ids=["columns", "refills"])
@pytest.mark.parametrize("held", [False, True], ids=["margins_pass", "held_score"])
def test_x_passes_are_two_an_iteration_and_the_start(passes, monkeypatch, held, columns):
    monkeypatch.setattr(margin_owlqn, "TRIAL_COLUMNS", columns)
    # far from the optimum: searches backtrack past their first trial and
    # clip there too
    objective, batch = problem("logistic_l1")
    w0 = warm_start(scale=2.0)
    score0 = objective.scores(w0, batch) if held else None
    res = jax.jit(lambda w, s: minimize_owlqn_margin(
        objective, batch, w, CONFIG, start_score=s))(w0, score0)
    jax.effects_barrier()
    its = int(res.iterations)
    refills = int(res.trials) - int(res.margin_trials)
    assert passes.count("search") == its + refills
    assert passes.count("gradient") == its + 1
    assert passes.count("margins") == (0 if held else 1)
    assert "matvec" not in passes
    assert int(res.evals) == len(passes) == 2 * its + (1 if held else 2) + refills
    if columns == 1:
        assert refills > 0  # later trials clipped and read X
        assert_same_solve(res, black_box(objective, batch, w0))
    else:
        assert refills == 0


@pytest.mark.parametrize("norm", [False, True], ids=["identity", "factors_shifts"])
def test_score_crosses_the_solvers_boundary(norm):
    objective, batch = problem("poisson_enet", norm=norm)
    solve = jax.jit(make_optimizer(objective, OptimizerSpec(tol=CONFIG.tol),
                                   with_score=True))
    w0 = warm_start()
    res, score = solve(w0, batch, objective.scores(w0, batch))
    assert res.optimizer == "owlqn_margin"
    want = np.asarray(objective.scores(res.w, batch))
    assert np.max(np.abs(np.asarray(score) - want)) <= 1e-5 * np.max(np.abs(want))
    # held and recomputed starts take the same steps
    again, _ = solve(w0, batch, None)
    np.testing.assert_allclose(np.asarray(again.w), np.asarray(res.w), rtol=0,
                               atol=1e-6)
    assert int(again.evals) == int(res.evals) + 1


def test_trial_counters_are_published_when_the_tracker_is_read():
    objective, batch = problem("logistic_l1")
    res = jax.jit(make_optimizer(objective, OptimizerSpec()))(warm_start(), batch)
    res = dataclasses.replace(res, coordinate="global")
    labels = dict(coordinate="global", optimizer="owlqn_margin")
    registry().reset()
    assert registry().find("fe_line_search_trials_total", **labels) is None
    res.diagnostics_dict()
    res.summary()  # a second read publishes nothing more
    trials = registry().find("fe_line_search_trials_total", **labels).value
    assert trials == int(res.trials) >= int(res.iterations)
    assert registry().find("fe_line_search_margin_trials_total",
                           **labels).value == int(res.margin_trials)
