"""Batched Newton solver tests (optim/newton.py): the damped loop and the
SPD solve inside it, unrolled over the entity axis up to
SPD_UNROLL_MAX_DIM and the library's Cholesky above."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_tpu.data.batch import LabeledBatch, SparseFeatures
from photon_tpu.data.normalization import NormalizationContext
from photon_tpu.ops.losses import LogisticLoss, PoissonLoss, SquaredLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.common import OptimizerConfig
from photon_tpu.optim.lbfgs import minimize_lbfgs
from photon_tpu.optim.newton import (
    ROWS_ON_MXU_MIN,
    SPD_LANE_CHUNK,
    SPD_UNROLL_MAX_DIM,
    SPD_UNROLL_MIN_LANES,
    minimize_newton,
    spd_solve,
    spd_solve_lowering,
)


def _problem(n, d, seed=0, poisson=False):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 0] = 1.0
    w = (rng.normal(size=d) / np.sqrt(d)).astype(np.float32)
    z = X @ w
    if poisson:
        y = rng.poisson(np.exp(np.clip(z, None, 3))).astype(np.float32)
    else:
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    weight = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    offset = (rng.normal(size=n) * 0.2).astype(np.float32)
    return X, y, weight, offset


def test_newton_linear_closed_form():
    """Weighted ridge regression: Newton lands on the normal-equations
    solution in one accepted step."""
    n, d = 300, 8
    X, y, weight, offset = _problem(n, d, seed=1)
    lam = 0.7
    obj = GLMObjective(loss=SquaredLoss, l2_weight=lam)
    batch = LabeledBatch(
        jnp.asarray(y), jnp.asarray(X), jnp.asarray(offset), jnp.asarray(weight)
    )
    res = jax.jit(
        lambda w: minimize_newton(obj, batch, w, OptimizerConfig(max_iter=5))
    )(jnp.zeros(d, jnp.float32))
    # Closed form: (XᵀWX + λI) w = XᵀW(y - offset)
    W = np.diag(weight)
    H = X.T @ W @ X + lam * np.eye(d)
    w_star = np.linalg.solve(H, X.T @ (weight * (y - offset)))
    np.testing.assert_allclose(np.asarray(res.w), w_star, rtol=2e-4, atol=2e-4)
    assert int(res.iterations) <= 3


@pytest.mark.parametrize(
    "loss,poisson", [(LogisticLoss, False), (PoissonLoss, True)]
)
def test_newton_matches_lbfgs(loss, poisson):
    n, d = 256, 12
    X, y, weight, offset = _problem(n, d, seed=2, poisson=poisson)
    batch = LabeledBatch(
        jnp.asarray(y), jnp.asarray(X), jnp.asarray(offset), jnp.asarray(weight)
    )
    obj = GLMObjective(loss=loss, l2_weight=1.0, intercept_index=0)
    res_n = jax.jit(
        lambda w: minimize_newton(obj, batch, w, OptimizerConfig(max_iter=25, tol=1e-9))
    )(jnp.zeros(d, jnp.float32))
    res_b = jax.jit(
        lambda w: minimize_lbfgs(
            lambda v: obj.value_and_grad(v, batch),
            w,
            OptimizerConfig(max_iter=100, tol=1e-9),
        )
    )(jnp.zeros(d, jnp.float32))
    np.testing.assert_allclose(
        np.asarray(res_n.w), np.asarray(res_b.w), rtol=2e-3, atol=2e-4
    )
    assert float(res_n.value) <= float(res_b.value) + 1e-4 * abs(float(res_b.value))
    # Second-order convergence: far fewer iterations than L-BFGS.
    assert int(res_n.iterations) < int(res_b.iterations)


def test_newton_vmapped_entities():
    """The RE use case: one program solving many entities at once matches
    per-entity solves."""
    E, n, d = 16, 40, 4
    rng = np.random.default_rng(3)
    X = rng.normal(size=(E, n, d)).astype(np.float32)
    X[:, :, 0] = 1.0
    w_true = rng.normal(size=(E, d)).astype(np.float32)
    z = np.einsum("end,ed->en", X, w_true)
    y = (rng.uniform(size=(E, n)) < 1 / (1 + np.exp(-z))).astype(np.float32)
    wt = np.ones((E, n), np.float32)
    # Mask a ragged tail on some entities via zero weights.
    wt[::3, n // 2 :] = 0.0

    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.5, intercept_index=0)
    cfg = OptimizerConfig(max_iter=20, tol=1e-8, track_history=False)

    def solve_one(Xe, ye, we):
        return minimize_newton(
            obj, LabeledBatch(ye, Xe, None, we), jnp.zeros(d, jnp.float32), cfg
        ).w

    w_batch = jax.jit(jax.vmap(solve_one))(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(wt)
    )
    for e in range(0, E, 5):
        w_ref = solve_one(jnp.asarray(X[e]), jnp.asarray(y[e]), jnp.asarray(wt[e]))
        np.testing.assert_allclose(
            np.asarray(w_batch[e]), np.asarray(w_ref), rtol=1e-4, atol=1e-5
        )


def _glm_systems(lanes, d, seed):
    """Well-conditioned logistic-GLM Hessians XᵀDX + I and gradients, one a
    lane, with the float64 solutions."""
    rng = np.random.default_rng(seed)
    n = 4 * d + 8
    X = rng.normal(size=(lanes, n, d)).astype(np.float32)
    D = rng.uniform(0.05, 0.25, size=(lanes, n)).astype(np.float32)
    H = (np.einsum("end,en,enf->edf", X, D, X) + np.eye(d)).astype(np.float32)
    g = rng.normal(size=(lanes, d)).astype(np.float32)
    x = np.linalg.solve(H.astype(np.float64), g.astype(np.float64)[..., None])
    return H, g, x[..., 0]


def _rel_err(x, ref):
    x = np.asarray(x, np.float64)
    return np.max(np.linalg.norm(x - ref, axis=-1) / np.linalg.norm(ref, axis=-1))


@pytest.mark.parametrize("lanes", [1, 2, 3072])
@pytest.mark.parametrize(
    "d", [1, 2, 16, SPD_UNROLL_MAX_DIM, SPD_UNROLL_MAX_DIM + 1]
)
def test_spd_solve_matches_float64(d, lanes):
    """Both lowerings of the solve (the unrolled one at 3072 lanes up to
    the bound), under the entity ``vmap`` of ``_solve_block``, against
    float64 ``numpy.linalg.solve``."""
    H, g, ref = _glm_systems(lanes, d, seed=100 * d + lanes)
    x = jax.jit(jax.vmap(spd_solve))(jnp.asarray(H), jnp.asarray(g))
    assert x.dtype == jnp.float32 and x.shape == g.shape
    assert _rel_err(x, ref) <= 2e-6


@pytest.mark.parametrize("d", [16, SPD_UNROLL_MAX_DIM + 1])
def test_spd_solve_under_a_second_vmap(d):
    """``batched_tuning`` maps λ outside the entity map: H + λI a lane of
    the outer axis, one gradient for all of them (an unbatched operand of
    the inner map's rule)."""
    H, g, _ = _glm_systems(SPD_UNROLL_MIN_LANES + 2, d, seed=5)
    lams = np.asarray([0.0, 0.5, 4.0], np.float32)

    def per_lambda(lam):
        return jax.vmap(
            lambda He, ge: spd_solve(He + lam * jnp.eye(d, dtype=He.dtype), ge)
        )(jnp.asarray(H), jnp.asarray(g))

    x = jax.jit(jax.vmap(per_lambda))(jnp.asarray(lams))
    x_shared_g = jax.jit(
        jax.vmap(lambda He: spd_solve(He, jnp.asarray(g[0])))
    )(jnp.asarray(H))
    for i, lam in enumerate(lams):
        Hl = H.astype(np.float64) + float(lam) * np.eye(d)
        ref = np.linalg.solve(Hl, g.astype(np.float64)[..., None])[..., 0]
        assert _rel_err(x[i], ref) <= 2e-6
    ref0 = np.linalg.solve(H.astype(np.float64), g[0].astype(np.float64))
    assert _rel_err(x_shared_g, ref0) <= 2e-6


@pytest.mark.parametrize("lanes", [5, SPD_UNROLL_MIN_LANES + 2])
@pytest.mark.parametrize("d", [16, SPD_UNROLL_MAX_DIM + 1])
def test_spd_solve_not_positive_definite_is_nan_in_that_lane_alone(d, lanes):
    """What the Levenberg reject branch rests on: a failed factorisation is
    NaN in its own lane's step and leaves every other lane finite."""
    H, g, ref = _glm_systems(lanes, d, seed=6)
    H[3] = H[3] - 2.0 * np.linalg.eigvalsh(H[3].astype(np.float64))[-1] * np.eye(
        d, dtype=np.float32
    )  # negative definite
    x = np.asarray(jax.jit(jax.vmap(spd_solve))(jnp.asarray(H), jnp.asarray(g)))
    assert np.isnan(x[3]).all()
    keep = [i for i in range(lanes) if i != 3]
    assert np.isfinite(x[keep]).all()
    assert _rel_err(x[keep], ref[keep]) <= 2e-6


def test_spd_solve_lowering_by_static_sizes():
    """The mechanism engages by the block's width and lanes alone: the
    lowered default block solver holds no ``cholesky`` operation at d = 16
    from 128 lanes, and holds one just above the width's bound or under
    the lanes'."""
    from photon_tpu.algorithm.random_effect import _solve_block
    from photon_tpu.data.random_effect import EntityBlock
    from photon_tpu.optim.factory import OptimizerSpec

    E = SPD_UNROLL_MIN_LANES
    assert spd_solve_lowering(16, E) == "unrolled"
    assert spd_solve_lowering(SPD_UNROLL_MAX_DIM, 3072) == "unrolled"
    assert spd_solve_lowering(SPD_UNROLL_MAX_DIM + 1, 3072) == "library"
    assert spd_solve_lowering(16, E - 1) == "library"
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    spec = OptimizerSpec(max_iter=5, tol=1e-6)  # the default: L-BFGS → Newton

    def lowered(d, E, n=8):
        f32 = jnp.float32
        block = EntityBlock(
            features=jnp.zeros((E, n, d), f32), label=jnp.zeros((E, n), f32),
            weight=jnp.ones((E, n), f32),
            sample_index=jnp.zeros((E, n), jnp.int32),
            entity_idx=jnp.arange(E, dtype=jnp.int32),
            train_mask=jnp.ones((E,), bool),
        )
        return jax.jit(
            lambda b, off, w0: _solve_block(b, off, w0, obj, spec, spec.config())
        ).lower(block, jnp.zeros((E, n), f32), jnp.zeros((E, d), f32)).as_text()

    assert "cholesky" not in lowered(16, E).lower()
    assert "cholesky" in lowered(SPD_UNROLL_MAX_DIM + 1, E).lower()
    assert "cholesky" in lowered(16, E - 1).lower()


def test_newton_vmapped_entities_lands_on_the_float64_optimum():
    """``test_newton_vmapped_entities``'s data: the float32 loop with the
    unrolled solve against a float64 Newton iteration in numpy. The loop
    stops when float32 objective values stop changing, which leaves w about
    sqrt(eps) from the optimum whatever solves the system: 2.3e-4 here,
    2.1e-4 with the library's Cholesky in its place."""
    E, n, d = 16, 40, 4
    rng = np.random.default_rng(3)
    X = rng.normal(size=(E, n, d)).astype(np.float32)
    X[:, :, 0] = 1.0
    w_true = rng.normal(size=(E, d)).astype(np.float32)
    z = np.einsum("end,ed->en", X, w_true)
    y = (rng.uniform(size=(E, n)) < 1 / (1 + np.exp(-z))).astype(np.float32)
    wt = np.ones((E, n), np.float32)
    wt[::3, n // 2 :] = 0.0
    lam = 0.5
    obj = GLMObjective(loss=LogisticLoss, l2_weight=lam, intercept_index=0)
    cfg = OptimizerConfig(max_iter=30, tol=1e-9, track_history=False)

    w_batch = jax.jit(jax.vmap(
        lambda Xe, ye, we: minimize_newton(
            obj, LabeledBatch(ye, Xe, None, we), jnp.zeros(d, jnp.float32), cfg
        ).w
    ))(jnp.asarray(X), jnp.asarray(y), jnp.asarray(wt))

    reg = np.full(d, lam)
    reg[0] = 0.0
    for e in range(E):
        Xe, ye, we = (a[e].astype(np.float64) for a in (X, y, wt))
        w = np.zeros(d)
        for _ in range(50):  # undamped Newton converges on this data
            p = 1 / (1 + np.exp(-Xe @ w))
            grad = Xe.T @ (we * (p - ye)) + reg * w
            hess = Xe.T @ (Xe * (we * p * (1 - p))[:, None]) + np.diag(reg)
            w = w - np.linalg.solve(hess, grad)
        assert np.linalg.norm(grad) < 1e-10
        got = np.asarray(w_batch[e], np.float64)
        assert np.linalg.norm(got - w) <= 5e-4 * np.linalg.norm(w)


def test_spd_solve_in_lane_chunks_is_the_unchunked_solve_bit_for_bit():
    """Over ``SPD_LANE_CHUNK`` lanes the column steps run a chunk of lanes
    at a time (each chunk's intermediates fit the chip's fast memory): the
    same operations on every lane, so the same bits; a lane count the chunk
    does not divide stays whole."""
    d = 8
    rng = np.random.default_rng(11)
    for lanes in (2 * SPD_LANE_CHUNK, 2 * SPD_LANE_CHUNK + 128):
        M = rng.normal(size=(lanes, d, d)).astype(np.float32)
        A = jnp.asarray(M @ M.transpose(0, 2, 1) + 0.5 * np.eye(d, dtype=np.float32))
        b = jnp.asarray(rng.normal(size=(lanes, d)).astype(np.float32))
        got = jax.jit(jax.vmap(spd_solve))(A, b)
        halves = [jax.jit(jax.vmap(spd_solve))(A[a:a + SPD_LANE_CHUNK],
                                                b[a:a + SPD_LANE_CHUNK])
                  for a in (0, SPD_LANE_CHUNK)]
        assert jnp.array_equal(got[:2 * SPD_LANE_CHUNK], jnp.concatenate(halves))
        resid = jnp.einsum("lde,le->ld", A, got) - b
        assert float(jnp.max(jnp.abs(resid))) < 1e-3


@pytest.mark.parametrize("rows", [1, 3, ROWS_ON_MXU_MIN - 1, ROWS_ON_MXU_MIN])
def test_newton_on_either_side_of_the_row_count_that_chooses_the_products(rows):
    """Under ``ROWS_ON_MXU_MIN`` rows the three products over the rows are
    multiply-and-reduce, from it matrix products: the same optimum, to what
    float32 leaves, for users with fewer rows than coefficients too."""
    d = 16
    X, y, _wt, off = _problem(rows, d, seed=rows)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    cfg = OptimizerConfig(max_iter=50, tol=1e-9, track_history=False)
    res = jax.jit(lambda X, y, off: minimize_newton(
        obj, LabeledBatch(y, X, off, jnp.ones_like(y)), jnp.zeros(d, jnp.float32), cfg
    ))(jnp.asarray(X), jnp.asarray(y), jnp.asarray(off))
    w = np.zeros(d)
    X64, y64, off64 = (a.astype(np.float64) for a in (X, y, off))
    for _ in range(60):   # a damped float64 Newton iteration
        p = 1 / (1 + np.exp(-(X64 @ w + off64)))
        grad = X64.T @ (p - y64) + w
        hess = X64.T @ (X64 * (p * (1 - p))[:, None]) + np.eye(d)
        w = w - 0.5 * np.linalg.solve(hess, grad)
    assert np.linalg.norm(grad) < 1e-8
    assert np.linalg.norm(np.asarray(res.w, np.float64) - w) <= 1e-3 * max(np.linalg.norm(w), 1e-2)


def test_a_reject_within_an_ulp_of_the_objective_ends_the_loop():
    """A 16-row user of the few-rows population (``newton_stall_user.npz``:
    its rows, labels and the fixed effect's scores as the block solver got
    them in a 4,096-user rehearsal of ``fit.glmix2-fewrows``). At iteration
    5 its objective is 4.4569006; the next trial reads one ulp above it, a
    relative 1.07e-7 and so over ``tol`` 1e-7, and every later trial does
    too (fresh margins against the carried objective): the loop ran to
    ``max_iter`` and, in lockstep, held its whole block there. It ends at the
    reject now, on the same iterate."""
    import os

    from photon_tpu.optim.common import (
        REASON_FUNCTION_VALUES_CONVERGED,
        REASON_GRADIENT_CONVERGED,
    )

    user = np.load(os.path.join(os.path.dirname(__file__), "newton_stall_user.npz"))
    lanes = SPD_UNROLL_MIN_LANES    # the unrolled solve's rounding, as a large block has it
    stack = lambda a: jnp.broadcast_to(jnp.asarray(a), (lanes,) + a.shape)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    cfg = OptimizerConfig(max_iter=100, tol=1e-7, track_history=False)
    res = jax.jit(jax.vmap(lambda X, y, off: minimize_newton(
        obj, LabeledBatch(y, X, off, jnp.ones_like(y)), jnp.zeros(16, jnp.float32), cfg
    )))(stack(user["features"]), stack(user["label"]), stack(user["offset"]))
    assert int(jnp.max(res.iterations)) <= 8
    assert set(np.asarray(res.reason_code).tolist()) <= {
        REASON_FUNCTION_VALUES_CONVERGED, REASON_GRADIENT_CONVERGED}
    assert float(res.value[0]) == pytest.approx(4.4569006, rel=1e-6)
    assert float(res.grad_norm[0]) < 1e-3


def test_newton_scale_normalization():
    n, d = 200, 6
    X, y, weight, offset = _problem(n, d, seed=5)
    factors = np.linspace(0.5, 2.0, d).astype(np.float32)
    norm = NormalizationContext(factors=jnp.asarray(factors), shifts=None)
    obj = GLMObjective(
        loss=LogisticLoss, l2_weight=1.0, intercept_index=0, normalization=norm
    )
    batch = LabeledBatch(
        jnp.asarray(y), jnp.asarray(X), jnp.asarray(offset), jnp.asarray(weight)
    )
    cfg = OptimizerConfig(max_iter=30, tol=1e-9)
    res_n = jax.jit(lambda w: minimize_newton(obj, batch, w, cfg))(
        jnp.zeros(d, jnp.float32)
    )
    res_b = jax.jit(
        lambda w: minimize_lbfgs(
            lambda v: obj.value_and_grad(v, batch),
            w,
            OptimizerConfig(max_iter=100, tol=1e-9),
        )
    )(jnp.zeros(d, jnp.float32))
    np.testing.assert_allclose(
        np.asarray(res_n.w), np.asarray(res_b.w), rtol=2e-3, atol=3e-4
    )


def test_newton_rejects_sparse_and_l1():
    sp = SparseFeatures(
        jnp.zeros((4, 1), jnp.int32), jnp.ones((4, 1), jnp.float32), 3
    )
    batch = LabeledBatch(jnp.zeros(4, jnp.float32), sp)
    with pytest.raises(ValueError):
        minimize_newton(
            GLMObjective(loss=LogisticLoss), batch, jnp.zeros(3, jnp.float32)
        )
    dense = LabeledBatch(jnp.zeros(4, jnp.float32), jnp.ones((4, 3), jnp.float32))
    with pytest.raises(ValueError):
        minimize_newton(
            GLMObjective(loss=LogisticLoss, l1_weight=0.1),
            dense,
            jnp.zeros(3, jnp.float32),
        )


def test_solve_block_routes_to_newton_and_matches_lbfgs():
    """Default-spec RE block solves run batched Newton (the bench's solver —
    VERDICT r2 #3: production path == benched path) and agree with the
    margin-LBFGS fallback on the optimum."""
    from photon_tpu.algorithm import random_effect as re_mod
    from photon_tpu.data.random_effect import (
        RandomEffectDataConfig,
        build_random_effect_dataset,
    )
    from photon_tpu.optim.factory import OptimizerSpec
    from photon_tpu.optim.margin_lbfgs import minimize_lbfgs_margin
    from photon_tpu.types import OptimizerType

    rng = np.random.default_rng(33)
    N, E, d = 512, 16, 4
    Xr = rng.normal(size=(N, d)).astype(np.float32)
    Xr[:, 0] = 1.0
    # N / E rows each: one grid level, so the plan is one block.
    users = rng.permutation(np.repeat(np.arange(E, dtype=np.int32), N // E))
    y = (rng.uniform(size=N) < 0.5).astype(np.float32)
    ds = build_random_effect_dataset(
        users, Xr, y, np.ones(N, np.float32), E,
        RandomEffectDataConfig(re_type="u", feature_shard="re"),
    )
    (block,) = ds.blocks
    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.5, intercept_index=0)
    cfg = OptimizerConfig(max_iter=30, tol=1e-7, track_history=False)
    offs = block.gather_offsets(jnp.zeros(N, jnp.float32))
    w0 = jnp.zeros((block.num_entities, d), jnp.float32)

    # Routing decision is static: default spec at d=4 must pick Newton.
    assert d <= re_mod.NEWTON_AUTO_MAX_DIM
    w_auto, _iters_auto, _ = re_mod._solve_block(
        block, offs, w0, obj, OptimizerSpec(), cfg
    )
    w_newt, _, _ = re_mod._solve_block(
        block, offs, w0, obj, OptimizerSpec(optimizer=OptimizerType.NEWTON), cfg
    )
    # Auto and explicit NEWTON produce bitwise-identical programs.
    np.testing.assert_array_equal(np.asarray(w_auto), np.asarray(w_newt))

    # And the optimum agrees with the margin-LBFGS fallback path.
    def solve_margin(feat, lab, wt, off, w_init):
        return minimize_lbfgs_margin(
            obj, LabeledBatch(lab, feat, off, wt), w_init, cfg
        ).w

    w_lbfgs = jax.vmap(solve_margin)(
        block.features, block.label, block.weight, offs, w0
    )
    np.testing.assert_allclose(
        np.asarray(w_auto), np.asarray(w_lbfgs), rtol=2e-3, atol=2e-3
    )


def test_newton_routing_predicate():
    """newton_eligible covers every gate: default-spec width cutoff, explicit
    NEWTON override, and the L1 / mask / shift-normalization exclusions."""
    import dataclasses as dc

    from photon_tpu.algorithm.random_effect import (
        NEWTON_AUTO_MAX_DIM,
        newton_eligible,
    )
    from photon_tpu.optim.factory import OptimizerSpec
    from photon_tpu.types import OptimizerType

    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    default, newton = OptimizerSpec(), OptimizerSpec(optimizer=OptimizerType.NEWTON)
    assert newton_eligible(obj, default, NEWTON_AUTO_MAX_DIM, has_mask=False)
    # Wide-d: auto falls back, explicit NEWTON still wins.
    assert not newton_eligible(obj, default, NEWTON_AUTO_MAX_DIM + 1, has_mask=False)
    assert newton_eligible(obj, newton, NEWTON_AUTO_MAX_DIM + 1, has_mask=False)
    # Exclusions: L1, Pearson mask, shift normalization, explicit TRON.
    assert not newton_eligible(dc.replace(obj, l1_weight=0.1), default, 4, has_mask=False)
    assert not newton_eligible(obj, default, 4, has_mask=True)
    shifted = dc.replace(
        obj,
        normalization=NormalizationContext(
            factors=jnp.ones(4), shifts=jnp.ones(4), intercept_index=None
        ),
    )
    assert not newton_eligible(shifted, default, 4, has_mask=False)
    assert not newton_eligible(
        obj, OptimizerSpec(optimizer=OptimizerType.TRON), 4, has_mask=False
    )


def test_newton_dead_column_no_l2():
    """l2=0 with a feature column no sample activates: the damping floor must
    keep Cholesky PD so the live subspace still converges (code-review r3)."""
    rng = np.random.default_rng(9)
    n, d = 64, 4
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 2] = 0.0  # dead column: H[2,2] = 0, g[2] = 0
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X))
    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.0)
    cfg = OptimizerConfig(max_iter=30, tol=1e-7, track_history=False)
    res = minimize_newton(obj, batch, jnp.zeros(d, jnp.float32), cfg)
    ref = minimize_lbfgs(
        lambda w: obj.value_and_grad(w, batch), jnp.zeros(d, jnp.float32), cfg
    )
    w = np.asarray(res.w)
    assert np.isfinite(w).all()
    assert w[2] == 0.0  # dead direction untouched
    np.testing.assert_allclose(w, np.asarray(ref.w), rtol=2e-3, atol=2e-3)


def test_solve_block_tron_masked_and_unmasked():
    """The RE TRON branch (linearized hvp_factory) must match explicit
    per-entity TRON with the (w, v) jvp-of-grad hvp — masked (Pearson M·H·M
    sandwich) and unmasked. Guards the factory rewrite of _solve_block."""
    from photon_tpu.algorithm import random_effect as re_mod
    from photon_tpu.data.random_effect import (
        RandomEffectDataConfig,
        build_random_effect_dataset,
    )
    from photon_tpu.optim.factory import OptimizerSpec
    from photon_tpu.optim.tron import minimize_tron
    from photon_tpu.types import OptimizerType

    rng = np.random.default_rng(41)
    N, E, d = 600, 12, 5
    Xr = rng.normal(size=(N, d)).astype(np.float32)
    Xr[:, 0] = 1.0
    # N / E rows each: one grid level, so the plan is one block.
    users = rng.permutation(np.repeat(np.arange(E, dtype=np.int32), N // E))
    y = (rng.uniform(size=N) < 0.5).astype(np.float32)
    ds = build_random_effect_dataset(
        users, Xr, y, np.ones(N, np.float32), E,
        RandomEffectDataConfig(re_type="u", feature_shard="re"),
    )
    (block,) = ds.blocks
    d_b = block.dim  # may exceed d under shape bucketing (padded zero cols)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.8, intercept_index=0)
    cfg = OptimizerConfig(max_iter=25, tol=1e-8, track_history=False)
    offs = block.gather_offsets(jnp.zeros(N, jnp.float32))
    w0 = jnp.zeros((block.num_entities, d_b), jnp.float32)
    spec = OptimizerSpec(optimizer=OptimizerType.TRON)

    # Pearson-style mask: knock out a different column per entity (never
    # the intercept), plus some entities fully unmasked.
    mask = np.ones((block.num_entities, d_b), np.float32)
    for e in range(block.num_entities // 2):
        mask[e, 1 + (e % (d - 1))] = 0.0
    mask_j = jnp.asarray(mask)

    for fmask_arg in (None, mask_j):
        w_block, _, _ = re_mod._solve_block(
            block, offs, w0, obj, spec, cfg, feature_mask=fmask_arg
        )

        def solve_ref(feat, lab, wt, off, w_init, fm):
            lb = LabeledBatch(lab, feat, off, wt)

            def vg(w):
                v, g = obj.value_and_grad(w * fm, lb)
                return v, g * fm

            hvp = lambda w, v: fm * obj.hvp(w * fm, fm * v, lb)  # noqa: E731
            res = minimize_tron(vg, hvp, w_init, cfg, spec.max_cg_iter)
            return res.w * fm

        fm_all = (
            jnp.ones((block.num_entities, d_b), jnp.float32)
            if fmask_arg is None
            else fmask_arg
        )
        w_ref = jax.vmap(solve_ref)(
            block.features, block.label, block.weight, offs, w0, fm_all
        )
        # Cross-form tolerance: the two hvp forms round differently in f32,
        # so CG trajectories drift slightly (same bar as the other
        # cross-solver comparisons in this file).
        np.testing.assert_allclose(
            np.asarray(w_block), np.asarray(w_ref), rtol=2e-3, atol=5e-4
        )
        if fmask_arg is not None:
            # Masked coordinates must be exactly zero in the output.
            assert np.all(np.asarray(w_block)[mask == 0.0] == 0.0)
