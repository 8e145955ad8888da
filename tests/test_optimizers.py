"""Optimizer correctness against closed-form test functions and scipy.

Mirrors the reference's optimizer test strategy (SURVEY.md §4): fake
objectives with known minima (TestObjective / IntegTestObjective) instead of
fake backends, plus convergence + tracker invariants (OptimizerIntegTest).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.optimize

from photon_tpu.data.batch import LabeledBatch
from photon_tpu.ops.losses import LogisticLoss, PoissonLoss, SquaredLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.common import OptimizerConfig
from photon_tpu.optim.lbfgs import minimize_lbfgs, minimize_lbfgsb
from photon_tpu.optim.owlqn import minimize_owlqn
from photon_tpu.optim.tron import minimize_tron
from photon_tpu.types import ConvergenceReason

rng = np.random.default_rng(42)


def quad_vg(A, b):
    """f(w) = 0.5 wᵀAw - bᵀw, minimum at A⁻¹ b."""
    A, b = jnp.asarray(A), jnp.asarray(b)
    return lambda w: (0.5 * w @ A @ w - b @ w, A @ w - b)


def rosenbrock_vg():
    def f(w):
        return jnp.sum(100.0 * (w[1:] - w[:-1] ** 2) ** 2 + (1.0 - w[:-1]) ** 2)

    return lambda w: (f(w), jax.grad(f)(w))


def test_lbfgs_quadratic_exact():
    d = 12
    M = rng.normal(size=(d, d))
    A = (M @ M.T + d * np.eye(d)).astype(np.float32)
    b = rng.normal(size=d).astype(np.float32)
    res = minimize_lbfgs(quad_vg(A, b), jnp.zeros(d, jnp.float32))
    np.testing.assert_allclose(res.w, np.linalg.solve(A, b), rtol=1e-3, atol=1e-3)
    assert res.converged
    assert res.convergence_reason in (
        ConvergenceReason.GRADIENT_CONVERGED,
        ConvergenceReason.FUNCTION_VALUES_CONVERGED,
    )


def test_lbfgs_rosenbrock():
    res = minimize_lbfgs(
        rosenbrock_vg(), jnp.zeros(4, jnp.float32), OptimizerConfig(max_iter=200, tol=1e-9)
    )
    np.testing.assert_allclose(res.w, np.ones(4), rtol=1e-2, atol=1e-2)


def test_lbfgs_tracker_monotone_and_padded():
    res = minimize_lbfgs(rosenbrock_vg(), jnp.zeros(4, jnp.float32), OptimizerConfig(max_iter=50))
    hist = np.asarray(res.loss_history)
    n = int(res.iterations)
    # Line-searched L-BFGS must be monotonically non-increasing in f.
    assert np.all(np.diff(hist[: n + 1]) <= 1e-5)
    # Padding equals final value.
    np.testing.assert_allclose(hist[n:], hist[n], rtol=0)


def make_logistic_problem(n=256, d=10, l2=0.1):
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 0] = 1.0
    w_true = rng.normal(size=d).astype(np.float32)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-X @ w_true))).astype(np.float32)
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X))
    obj = GLMObjective(loss=LogisticLoss, l2_weight=l2)
    return X, y, batch, obj


def scipy_logistic_opt(X, y, l2):
    def f(w):
        z = X @ w
        return np.sum(np.logaddexp(0, z) - y * z) + 0.5 * l2 * np.dot(w, w)

    def g(w):
        z = X @ w
        return X.T @ (1.0 / (1.0 + np.exp(-z)) - y) + l2 * w

    r = scipy.optimize.minimize(f, np.zeros(X.shape[1]), jac=g, method="L-BFGS-B",
                                options=dict(maxiter=500, ftol=1e-12, gtol=1e-10))
    return r.x, r.fun


def test_lbfgs_logistic_matches_scipy():
    X, y, batch, obj = make_logistic_problem()
    vg = lambda w: obj.value_and_grad(w, batch)
    res = minimize_lbfgs(vg, jnp.zeros(X.shape[1], jnp.float32), OptimizerConfig(max_iter=200))
    w_ref, f_ref = scipy_logistic_opt(X, y, 0.1)
    assert float(res.value) <= f_ref + 1e-2
    np.testing.assert_allclose(res.w, w_ref, rtol=5e-2, atol=5e-2)


def test_tron_logistic_matches_lbfgs():
    X, y, batch, obj = make_logistic_problem()
    vg = lambda w: obj.value_and_grad(w, batch)
    hvp = lambda w, v: obj.hvp(w, v, batch)
    res = minimize_tron(vg, hvp, jnp.zeros(X.shape[1], jnp.float32))
    w_ref, f_ref = scipy_logistic_opt(X, y, 0.1)
    assert float(res.value) <= f_ref + 1e-2
    assert res.eval_unit == "x_passes"
    assert int(res.evals) == 2 + 5 * int(res.iterations) + 2 * int(res.cg_steps)


def test_tron_poisson():
    n, d = 128, 6
    X = rng.normal(scale=0.3, size=(n, d)).astype(np.float32)
    w_true = rng.normal(scale=0.5, size=d).astype(np.float32)
    y = rng.poisson(np.exp(X @ w_true)).astype(np.float32)
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X))
    obj = GLMObjective(loss=PoissonLoss, l2_weight=0.01)
    res = minimize_tron(
        lambda w: obj.value_and_grad(w, batch),
        lambda w, v: obj.hvp(w, v, batch),
        jnp.zeros(d, jnp.float32),
    )
    g = np.asarray(obj.grad(res.w, batch))
    assert np.linalg.norm(g) < 1e-2 * max(1.0, np.linalg.norm(np.asarray(obj.grad(jnp.zeros(d), batch))))
    assert res.eval_unit == "x_passes" and int(res.cg_steps) >= int(res.iterations)


def _counting_tron(problem, one_read=False):
    """A TRON solve whose value-and-gradient, H·v factory and products each
    add the passes over X they make to a host count as they run, through
    the factory form ``GLMObjective.linearized_hvp`` (its one-read kernel
    with ``one_read``: one pass a product)."""
    n, d = 256, 8
    local = np.random.default_rng(7)
    if problem == "poisson_steep":
        # Features of scale 3 from a start at zero: exp's curvature grows
        # faster than the quadratic model says, and the first steps are refused.
        X = local.normal(scale=3.0, size=(n, d)).astype(np.float32)
        y = local.poisson(np.exp(X @ local.normal(scale=0.3, size=d))).astype(np.float32)
        obj = GLMObjective(loss=PoissonLoss, l2_weight=1.0)
    else:
        X = local.normal(size=(n, d)).astype(np.float32)
        y = (X @ local.normal(size=d) + local.normal(size=n)).astype(np.float32)
        obj = GLMObjective(loss=SquaredLoss, l2_weight=1.0)
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X))
    passes = []

    def count(k):
        jax.debug.callback(lambda: passes.append(k))

    def vg(w):
        count(2)
        return obj.value_and_grad(w, batch)

    def factory(w):
        count(1)
        hv = obj.linearized_hvp(w, batch, one_read=one_read)

        def product(v):
            count(1 if one_read else 2)
            return hv(v)

        return product

    res = jax.jit(lambda w0: minimize_tron(
        vg, None, w0, hvp_factory=factory, hvp_passes=1 if one_read else 2))(
        jnp.zeros(d, jnp.float32))
    jax.effects_barrier()
    return res, sum(passes)


@pytest.mark.parametrize("one_read", [False, True], ids=["two_pass", "one_read"])
@pytest.mark.parametrize("problem", ["squared", "poisson_steep"])
def test_tron_passes_are_its_iterations_and_cg_steps(problem, one_read):
    """The reported passes are the passes the solve made: 2 for the start,
    then per outer iteration the linearized margins, 2 for the trial, and
    a CG product and ρ's product at 2 passes each on the two-pass form, 1
    on the one-read kernel."""
    res, made = _counting_tron(problem, one_read)
    its, cg = int(res.iterations), int(res.cg_steps)
    assert res.eval_unit == "x_passes" and its >= 1 and cg >= its
    per_product = 1 if one_read else 2
    assert int(res.evals) == made == 2 + 3 * its + per_product * (cg + its)
    assert res.hvp_passes == per_product
    rejected = int(res.rejected_steps)
    assert 0 <= rejected < its
    if problem == "poisson_steep":
        assert rejected >= 1
    diag = res.diagnostics_dict()
    assert (diag["cg_steps"], diag["rejected_steps"]) == (cg, rejected)


def test_lbfgsb_respects_box():
    d = 8
    M = rng.normal(size=(d, d))
    A = (M @ M.T + d * np.eye(d)).astype(np.float32)
    b = (10 * rng.normal(size=d)).astype(np.float32)
    lower = jnp.full((d,), -0.5, jnp.float32)
    upper = jnp.full((d,), 0.5, jnp.float32)
    res = minimize_lbfgsb(quad_vg(A, b), jnp.zeros(d, jnp.float32), lower, upper)
    w = np.asarray(res.w)
    assert np.all(w >= -0.5 - 1e-6) and np.all(w <= 0.5 + 1e-6)
    ref = scipy.optimize.minimize(
        lambda w: 0.5 * w @ A @ w - b @ w,
        np.zeros(d),
        jac=lambda w: A @ w - b,
        bounds=[(-0.5, 0.5)] * d,
        method="L-BFGS-B",
    )
    assert float(res.value) <= ref.fun + 1e-2 * abs(ref.fun)


def test_owlqn_lasso_sparsity_and_optimum():
    """OWL-QN on least squares + L1 vs scipy coordinate-descent-quality optimum."""
    n, d = 128, 20
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = np.zeros(d, np.float32)
    w_true[:3] = [2.0, -3.0, 1.5]
    y = (X @ w_true + 0.01 * rng.normal(size=n)).astype(np.float32)
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X))
    obj = GLMObjective(loss=SquaredLoss)
    lam = 5.0
    vg = lambda w: obj.value_and_grad(w, batch)
    res = minimize_owlqn(vg, jnp.zeros(d, jnp.float32), lam, OptimizerConfig(max_iter=300))
    w = np.asarray(res.w)
    # True zeros should be (near-)zero — orthant projection gives exact zeros.
    assert np.sum(np.abs(w[3:]) < 1e-3) >= d - 5
    # Objective value sanity vs subgradient-informed scipy solution.
    def f_full(w):
        r = X @ w - y
        return 0.5 * np.dot(r, r) + lam * np.sum(np.abs(w))
    ref = scipy.optimize.minimize(f_full, np.zeros(d), method="Powell",
                                  options=dict(maxiter=20000, xtol=1e-8))
    assert float(res.value) <= f_full(ref.x) + 1e-1


def test_owlqn_with_l2_elastic_net():
    X, y, batch, obj = make_logistic_problem(l2=0.5)
    res = minimize_owlqn(
        lambda w: obj.value_and_grad(w, batch),
        jnp.zeros(X.shape[1], jnp.float32),
        l1_weight=1.0,
        config=OptimizerConfig(max_iter=200),
    )
    assert np.isfinite(float(res.value))
    assert int(res.iterations) > 0


def test_optimizers_jittable():
    """Whole optimize calls must compile: wrap in jit and check identical result."""
    d = 6
    M = rng.normal(size=(d, d))
    A = (M @ M.T + d * np.eye(d)).astype(np.float32)
    b = rng.normal(size=d).astype(np.float32)
    vg = quad_vg(A, b)
    run = jax.jit(lambda w0: minimize_lbfgs(vg, w0).w)
    np.testing.assert_allclose(
        run(jnp.zeros(d, jnp.float32)),
        minimize_lbfgs(vg, jnp.zeros(d, jnp.float32)).w,
        rtol=1e-5, atol=1e-5,
    )
