"""A count response under an elastic net through the estimator's normal path:
``TaskType.POISSON_REGRESSION`` with ``RegularizationConfig(weight, alpha)``.

What is held here, on the CPU at a small size: ``GameEstimator.fit`` against
the benchmark's proximal-Newton reference (which imports nothing of the
program); that reference's fixed-effect block against a brute-force search
round its answer; a trial that overflows ``exp`` in float32 is a rejected
trial in OWL-QN's backtracking and in the Newton sweep; the tracker's unit
of work follows the solver; the solver's counters and the gauge of non-zero
coefficients appear when a tracker is read.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare_poisson, data_poisson, program, program_enet
from benchmark.reference import glmix_poisson_enet as reference
from photon_tpu.data.batch import LabeledBatch
from photon_tpu.obs.metrics import registry
from photon_tpu.ops import GLMObjective
from photon_tpu.ops.losses import PoissonLoss
from photon_tpu.optim.common import (
    REASON_DIVERGED,
    REASON_OBJECTIVE_NOT_IMPROVING,
    OptimizerConfig,
)
from photon_tpu.optim.factory import OptimizerSpec, make_optimizer
from photon_tpu.optim.newton import minimize_newton
from photon_tpu.optim.owlqn import minimize_owlqn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, D_FIX, D_RE, USERS = 4096, 32, 4, 64
TRUTH = dict(intercept=-1.0, support=8, norm=0.5, re_scale=0.125)


def small_config() -> dict:
    """benchmark/configs/glmix2-poisson-enet.json at the small widths; its
    reg_weight scaled with the root of the rows, as a null feature's
    gradient is, so that l1 zeroes the null features here as it does there."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glmix2-poisson-enet.json")) as f:
        config = json.load(f)
    for c in config["coordinates"]:
        c["dim"] = D_FIX if c["type"] == "fixed" else D_RE
        if c["type"] == "fixed":
            c["reg_weight"] *= (ROWS / (1 << 22)) ** 0.5
    return config


@pytest.fixture(scope="module")
def fitted():
    config = small_config()
    entities = {"per_user": USERS}
    data = data_poisson.make_glmix(11, ROWS, D_FIX, {"per_user": (D_RE, USERS)},
                                   TRUTH)
    estimator, batch, opt = program_enet.build_fit(config, *data, entities)
    registry().reset()
    model, tracker = program.fit_once(estimator, batch, opt)
    ref = reference.fit(config, *data, entities)
    return config, data, model, tracker, ref


# ---- the estimator against the reference ---------------------------------------


def test_fit_agrees_with_the_proximal_newton_reference(fitted):
    config, _data, model, _tracker, ref = fitted
    gaps = compare_poisson.model_gaps(config, model, ref)
    assert gaps["fixed_gap"] <= 5e-4, gaps
    assert gaps["random_gap"] <= 1e-3, gaps
    assert gaps["random_row_gap"] <= 5e-3, gaps
    assert gaps["support_diff"] == 0
    share = compare_poisson.zero_share(config, ref)
    assert 0.25 <= share <= 0.80, share   # the penalty is at work, and not alone
    # the program's zeros are exact zeros, in the reference's places
    got, want = np.asarray(model["global"]), np.asarray(ref["global"])
    assert np.array_equal(got == 0, want == 0)


def test_no_solve_ended_diverged_and_every_user_moved(fitted):
    config, _data, model, tracker, _ref = fitted
    assert program_enet.quarantined(config, tracker) == 0
    assert np.all(np.linalg.norm(np.asarray(model["per_user"]), axis=1) > 0)


def test_reference_fixed_effect_block_against_a_brute_force_search(fitted):
    """The reference's fixed-effect block, solved against the other
    coordinate's scores: no coefficient can be moved, one at a time over a
    grid round it (zero included), to a lower objective, and the optimality
    conditions hold at the zeros and off them. Float64 on the host."""
    config, (xf, shards, ids, y), _model, _tracker, ref = fitted
    (fixed,) = program.coordinates(config, "fixed")
    l1, l2 = reference.penalties(fixed)
    others = jnp.sum(shards["per_user"] * ref["per_user"][ids["per_user"]], axis=1)
    w = np.asarray(reference.solve_fixed(xf, y, others, l1, l2, fixed["intercept"]),
                   np.float64)
    x, yy, offset = (np.asarray(a, np.float64) for a in (xf, y, others))
    pen = np.ones_like(w)
    pen[fixed["intercept"]] = 0.0

    def objective(v):
        z = x @ v + offset
        return (np.sum(np.exp(z) - yy * z) + 0.5 * l2 * np.sum(pen * v * v)
                + l1 * np.sum(pen * np.abs(v)))

    best = objective(w)
    zeros = np.flatnonzero(w == 0)
    assert 0 < zeros.size < w.size - 1
    for j in range(w.size):
        for step in (-1e-2, -1e-3, -1e-4, 1e-4, 1e-3, 1e-2):
            trial = w.copy()
            trial[j] += step
            # float32's optimum sits within 1e-6 of float64's: a step of 1e-4
            # gains at most curvature × 1e-6 × 1e-4
            assert objective(trial) >= best - 1e-9 * abs(best), (j, step)
        if w[j] != 0:
            trial = w.copy()
            trial[j] = 0.0
            assert objective(trial) > best, j
    g = x.T @ (np.exp(x @ w + offset) - yy)
    assert np.all(np.abs(g[zeros]) <= l1)
    moving = np.flatnonzero((w != 0) & (pen > 0))
    np.testing.assert_allclose(g[moving] + l2 * w[moving],
                               -l1 * np.sign(w[moving]), rtol=0, atol=1e-4 * l1)
    assert abs(g[fixed["intercept"]]) <= 1e-4 * l1


# ---- an overflowing trial is a rejected trial ------------------------------------


def wide_poisson(n=512, scale=200.0, seed=3):
    """One feature of scale 200 beside the intercept: from zero, OWL-QN's
    first trial (a unit step) puts margins near ±200·3 and ``exp`` at inf."""
    rng = np.random.default_rng(seed)
    x = np.stack([np.ones(n), scale * rng.normal(size=n)], axis=1).astype(np.float32)
    y = rng.poisson(np.exp(-0.5 + 0.002 * x[:, 1])).astype(np.float32)
    return LabeledBatch(jnp.asarray(y), jnp.asarray(x), jnp.zeros(n, jnp.float32),
                        jnp.ones(n, jnp.float32))


def test_owlqn_first_trial_overflows_and_the_solve_still_converges():
    batch = wide_poisson()
    objective = GLMObjective(loss=PoissonLoss, l2_weight=0.5, l1_weight=0.5,
                             intercept_index=0)
    w0 = jnp.zeros((2,), jnp.float32)
    first = objective.full_value(jnp.asarray([0.0, 1.0], jnp.float32), batch)
    assert not np.isfinite(float(first))   # the unit step does overflow
    res = jax.jit(make_optimizer(objective, OptimizerSpec()))(w0, batch)
    assert res.optimizer == "owlqn"
    assert np.all(np.isfinite(np.asarray(res.w))) and np.isfinite(float(res.value))
    assert int(res.reason_code) != REASON_DIVERGED and res.converged
    assert int(res.evals) > int(res.iterations) + 1   # trials were rejected
    want = reference.solve_fixed(batch.features, batch.label, batch.offset, 0.5, 0.5, 0)
    # the feature's scale of 200 leaves the stopping rule 2e-4 from the optimum
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(want), atol=1e-3)


def test_owlqn_search_without_an_accepted_trial_keeps_the_iterate():
    """Two trials, both at inf: the parent took the last one for a step."""
    batch = wide_poisson()
    objective = GLMObjective(loss=PoissonLoss, l1_weight=0.5, intercept_index=0)
    w0 = jnp.asarray([-0.5, 0.0], jnp.float32)
    config = OptimizerConfig(max_line_search_evals=2)
    res = minimize_owlqn(lambda w: objective.value_and_grad(w, batch), w0, 0.5,
                         config, objective.l1_mask(w0))
    np.testing.assert_array_equal(np.asarray(res.w), np.asarray(w0))
    assert np.isfinite(float(res.value)) and np.isfinite(float(res.grad_norm))
    assert int(res.reason_code) == REASON_OBJECTIVE_NOT_IMPROVING
    assert int(res.iterations) == 1


def test_newton_sweep_whose_every_trial_overflows_rejects_and_recovers():
    """Counts near 1e4 on an intercept: the Newton step from zero is 1e4, and
    its smallest trial (1/64 of it) still puts ``exp`` at inf."""
    n = 64
    rng = np.random.default_rng(5)
    x = np.stack([np.ones(n), rng.normal(size=n)], axis=1).astype(np.float32)
    y = rng.poisson(1e4, size=n).astype(np.float32)
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(x), jnp.zeros(n, jnp.float32),
                         jnp.ones(n, jnp.float32))
    objective = GLMObjective(loss=PoissonLoss, l2_weight=1.0, intercept_index=0)
    assert not np.isfinite(float(jnp.exp(jnp.float32(1e4 / 64))))
    res = jax.jit(lambda w: minimize_newton(objective, batch, w))(
        jnp.zeros((2,), jnp.float32))
    assert np.all(np.isfinite(np.asarray(res.w)))
    assert int(res.reason_code) != REASON_DIVERGED and res.converged
    assert float(res.w[0]) == pytest.approx(np.log(y.mean()), abs=1e-3)
    assert int(res.iterations) > 7   # the rejected steps each cost an iteration


# ---- what the tracker reports ------------------------------------------------------


@pytest.mark.parametrize("l1,unit,optimizer", [
    (4.0, "objective_evals", "owlqn"), (0.0, "x_passes", "lbfgs_margin")])
def test_fixed_effect_tracker_unit_follows_the_solver(l1, unit, optimizer):
    batch = wide_poisson(scale=1.0)
    objective = GLMObjective(loss=PoissonLoss, l2_weight=1.0, l1_weight=l1,
                             intercept_index=0)
    res = jax.jit(make_optimizer(objective, OptimizerSpec()))(
        jnp.zeros((2,), jnp.float32), batch)
    assert res.eval_unit == unit and res.optimizer == optimizer
    assert res.diagnostics_dict()["eval_unit"] == unit


def test_counters_and_gauge_are_published_when_the_trackers_are_read(fitted):
    config, _data, model, tracker, _ref = fitted
    registry().reset()
    # the fixture's trackers may have been read by an earlier test: read copies
    fresh = [dataclasses.replace(d) for d in tracker["global"]]
    labels = dict(coordinate="global", optimizer="owlqn")
    assert registry().find("fe_solver_iterations_total", **labels) is None
    diags = [d.diagnostics_dict() for d in fresh]
    for d in fresh:
        d.summary()   # a second read publishes nothing more
    assert registry().find("fe_solver_iterations_total", **labels).value == sum(
        d["iterations"] for d in diags)
    assert registry().find("fe_solver_evals_total", unit="objective_evals",
                           **labels).value == sum(d["evals"] for d in diags)
    gauge = registry().find("fe_nonzero_coefficients", coordinate="global")
    assert gauge.value == np.count_nonzero(np.asarray(model["global"]))
    assert 1 < gauge.value < D_FIX
