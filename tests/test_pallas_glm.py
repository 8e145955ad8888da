"""Fused Pallas GLM kernel vs autodiff objective (interpret mode on CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_tpu.data.batch import LabeledBatch
from photon_tpu.data.normalization import NormalizationContext
from photon_tpu.ops.losses import LogisticLoss, PoissonLoss, SquaredLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.ops import pallas_glm
from photon_tpu.ops.pallas_glm import fused_data_value_and_grad
from photon_tpu.optim.common import OptimizerConfig
from photon_tpu.optim.lbfgs import minimize_lbfgs


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
    return X, y, weight, offset, w


@pytest.mark.parametrize(
    "loss,poisson", [(LogisticLoss, False), (PoissonLoss, True), (SquaredLoss, False)]
)
def test_fused_matches_autodiff(loss, poisson, monkeypatch):
    n, d = 293, 13  # deliberately not tile/lane aligned
    monkeypatch.setattr(pallas_glm, "DEFAULT_TILE_N", 128)  # multi-tile grid
    X, y, weight, offset, w = _problem(n, d, poisson=poisson)
    val, grad = fused_data_value_and_grad(
        loss, jnp.asarray(w), jnp.asarray(X), jnp.asarray(y),
        jnp.asarray(offset), jnp.asarray(weight),
    )
    obj = GLMObjective(loss=loss)
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X), jnp.asarray(offset), jnp.asarray(weight))
    val_ref, grad_ref = jax.value_and_grad(obj.value)(jnp.asarray(w), batch)
    np.testing.assert_allclose(float(val), float(val_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(grad_ref), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("tile_n", [8, 128, 4096])
def test_fused_tile_height_invariance(tile_n, monkeypatch):
    """Identical results at any tile height, including a request below one
    lane row (clamped up to 128), tile_n > n (the n-cap clamps it) and the
    big default (grid-step amortization). The height is a module constant
    since the round-4 A/B deleted the per-call override — geometry varies
    via monkeypatch only."""
    monkeypatch.setattr(pallas_glm, "DEFAULT_TILE_N", tile_n)
    n, d = 300, 24
    X, y, weight, offset, w = _problem(n, d, seed=7)
    val, grad = fused_data_value_and_grad(
        LogisticLoss, jnp.asarray(w), jnp.asarray(X), jnp.asarray(y),
        jnp.asarray(offset), jnp.asarray(weight),
    )
    obj = GLMObjective(loss=LogisticLoss)
    batch = LabeledBatch(
        jnp.asarray(y), jnp.asarray(X), jnp.asarray(offset), jnp.asarray(weight)
    )
    val_ref, grad_ref = jax.value_and_grad(obj.value)(jnp.asarray(w), batch)
    np.testing.assert_allclose(float(val), float(val_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(grad_ref), rtol=1e-4, atol=1e-5)


def test_tile_geometry(monkeypatch):
    """The tall default must never cost real padding: tile height clamps
    to the data, prefers an exact divisor of n, otherwise rebalances across
    the grid, and keeps EVERY block the call holds inside the VMEM budget
    at the width VMEM gives it."""
    from photon_tpu.ops.pallas_glm import (
        _VMEM_BUDGET,
        _VMEM_FIXED,
        COL_VEC_BYTES,
        DEFAULT_TILE_N,
        ROW_VEC_BYTES,
        _tile_geometry,
        x_row_bytes,
    )

    assert DEFAULT_TILE_N >= 4096  # the default really is tall
    # A (tile_n, 1) column costs a full 128-lane row per sample — the
    # accounting error that made the v5e compiler refuse d=256 (PR 21).
    assert COL_VEC_BYTES == 128 * ROW_VEC_BYTES

    def row_bytes(d_pad, dtype, n_vec=4):
        return x_row_bytes(d_pad, dtype) + n_vec * ROW_VEC_BYTES

    # Small batch: one lane-padded tile, NOT one 8192-row tile.
    t, npad = _tile_geometry(100, DEFAULT_TILE_N, row_bytes(128, jnp.float32))
    assert t == 128 and npad == 128

    # n just past a tile multiple: rebalanced, padding < one lane row per
    # tile (the un-rebalanced geometry would pad 8200 → 16384).
    t, npad = _tile_geometry(8200, DEFAULT_TILE_N, row_bytes(128, jnp.float32))
    n_tiles = npad // t
    assert npad - 8200 < n_tiles * 128, (t, npad)
    assert npad < 8200 + 2 * 8192 - 8192, npad

    # The budget binds at wide d and counts the per-sample vectors too; a
    # power-of-two n is tiled exactly (no padded copy of X in HBM).
    for dtype in (jnp.float32, jnp.bfloat16):
        for d_pad in [128, 256, 2048, 4096]:
            rb = row_bytes(d_pad, dtype)
            t, npad = _tile_geometry(1 << 21, DEFAULT_TILE_N, rb)
            assert t * rb + _VMEM_FIXED <= _VMEM_BUDGET
            assert t % 128 == 0 and npad == 1 << 21

    # Column blocks (the RE Newton kernel) shorten the tile accordingly.
    rb = x_row_bytes(128, jnp.float32) + 2 * COL_VEC_BYTES
    t, npad = _tile_geometry(1 << 16, 1 << 16, rb, align=8)
    assert t * rb + _VMEM_FIXED <= _VMEM_BUDGET and t % 8 == 0

    # Numerical parity at a rebalanced odd size spanning several tiles.
    monkeypatch.setattr(pallas_glm, "DEFAULT_TILE_N", 512)
    n, d = 1030, 8  # three 384-row tiles
    X, y, weight, offset, w = _problem(n, d, seed=11)
    val, grad = fused_data_value_and_grad(
        LogisticLoss, jnp.asarray(w), jnp.asarray(X), jnp.asarray(y),
        jnp.asarray(offset), jnp.asarray(weight),
    )
    obj = GLMObjective(loss=LogisticLoss)
    batch = LabeledBatch(
        jnp.asarray(y), jnp.asarray(X), jnp.asarray(offset), jnp.asarray(weight)
    )
    val_ref, grad_ref = jax.value_and_grad(obj.value)(jnp.asarray(w), batch)
    np.testing.assert_allclose(float(val), float(val_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(grad_ref), rtol=1e-4, atol=1e-5)


def test_objective_dispatch_parity():
    """use_pallas=True objective == plain objective (L2 + scale norm folded)."""
    n, d = 64, 10
    X, y, weight, offset, w = _problem(n, d, seed=2)
    factors = np.linspace(0.5, 1.5, d).astype(np.float32)
    norm = NormalizationContext(factors=jnp.asarray(factors))
    kw = dict(loss=LogisticLoss, l2_weight=0.8, intercept_index=0, normalization=norm)
    obj_p = GLMObjective(use_pallas=True, **kw)
    obj_r = GLMObjective(**kw)
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X), jnp.asarray(offset), jnp.asarray(weight))
    vp, gp = obj_p.value_and_grad(jnp.asarray(w), batch)
    vr, gr = obj_r.value_and_grad(jnp.asarray(w), batch)
    np.testing.assert_allclose(float(vp), float(vr), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr), rtol=1e-4, atol=1e-5)


def test_dispatch_falls_back_on_shifts():
    norm = NormalizationContext(
        factors=jnp.ones(4), shifts=jnp.ones(4) * 0.5, intercept_index=0
    )
    obj = GLMObjective(loss=LogisticLoss, normalization=norm, use_pallas=True)
    X, y, weight, offset, w = _problem(16, 4, seed=3)
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X), jnp.asarray(offset), jnp.asarray(weight))
    assert not obj._can_fuse(batch)
    # Still correct through the fallback.
    v, g = obj.value_and_grad(jnp.asarray(w), batch)
    v_ref, g_ref = jax.value_and_grad(obj.value)(jnp.asarray(w), batch)
    np.testing.assert_allclose(float(v), float(v_ref), rtol=1e-6)


def test_lbfgs_over_fused_objective():
    """Full L-BFGS solve through the Pallas path reaches the same optimum."""
    n, d = 256, 12
    X, y, weight, offset, _ = _problem(n, d, seed=5)
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X), jnp.asarray(offset), jnp.asarray(weight))
    cfg = OptimizerConfig(max_iter=50, tol=1e-8, track_history=False)
    res_p = minimize_lbfgs(
        lambda w: GLMObjective(loss=LogisticLoss, l2_weight=1.0, use_pallas=True)
        .value_and_grad(w, batch),
        jnp.zeros(d, jnp.float32), cfg,
    )
    res_r = minimize_lbfgs(
        lambda w: GLMObjective(loss=LogisticLoss, l2_weight=1.0)
        .value_and_grad(w, batch),
        jnp.zeros(d, jnp.float32), cfg,
    )
    np.testing.assert_allclose(np.asarray(res_p.w), np.asarray(res_r.w), rtol=1e-3, atol=1e-4)


def test_fused_return_margins():
    import numpy as np
    import jax.numpy as jnp
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.pallas_glm import fused_data_value_and_grad

    rng = np.random.default_rng(21)
    n, d = 300, 24  # non-tile-aligned on purpose
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    off = rng.normal(size=n).astype(np.float32) * 0.1
    wt = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    val, grad, z = fused_data_value_and_grad(
        LogisticLoss, jnp.asarray(w), jnp.asarray(X), jnp.asarray(y),
        jnp.asarray(off), jnp.asarray(wt), return_margins=True,
    )
    np.testing.assert_allclose(np.asarray(z), X @ w + off, rtol=1e-5, atol=1e-5)
    val2, grad2 = fused_data_value_and_grad(
        LogisticLoss, jnp.asarray(w), jnp.asarray(X), jnp.asarray(y),
        jnp.asarray(off), jnp.asarray(wt),
    )
    np.testing.assert_allclose(float(val), float(val2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(grad2), rtol=1e-6)


@pytest.mark.parametrize("tile_n", [8, 128, 4096])
def test_fused_hvp_matches_dense_hessian(tile_n, monkeypatch):
    """fused_data_hvp == Xᵀ·diag(d2)·X·v at any tile height, non-aligned
    shapes included."""
    from photon_tpu.ops.pallas_glm import fused_data_hvp

    monkeypatch.setattr(pallas_glm, "DEFAULT_TILE_N", tile_n)
    rng = np.random.default_rng(13)
    n, d = 311, 19
    X = rng.normal(size=(n, d)).astype(np.float32)
    v = rng.normal(size=d).astype(np.float32)
    d2 = rng.uniform(0.05, 1.0, size=n).astype(np.float32)
    got = fused_data_hvp(jnp.asarray(v), jnp.asarray(X), jnp.asarray(d2))
    ref = X.T @ (d2 * (X @ v))
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4, atol=1e-4)


def test_losing_lowerings_deleted():
    """The round-4 FE A/B left exactly ONE lowering: no per-call tile-height
    override survives on either public entry point (the losing short-tile
    variants were deleted, not gated)."""
    import inspect

    from photon_tpu.ops.pallas_glm import fused_data_hvp

    for fn in (fused_data_value_and_grad, fused_data_hvp):
        assert "tile_n" not in inspect.signature(fn).parameters


def test_interpret_mode_is_cpu_only_and_explicit():
    """Pallas is part of the installed JAX: no import gate, no silent return
    to XLA. Off-TPU ``pallas_available()`` is False and the kernels
    interpret only because ``interpret`` resolves from the backend."""
    assert not pallas_glm.pallas_available()  # no TPU backend here
    assert not hasattr(pallas_glm, "pallas_usable")
    assert not hasattr(pallas_glm, "_require_pallas")

    n, d = 32, 6
    X, y, weight, offset, w = _problem(n, d, seed=23)
    batch = LabeledBatch(
        jnp.asarray(y), jnp.asarray(X), jnp.asarray(offset), jnp.asarray(weight)
    )
    val, grad = fused_data_value_and_grad(
        LogisticLoss, jnp.asarray(w), jnp.asarray(X), jnp.asarray(y),
        jnp.asarray(offset), jnp.asarray(weight), interpret=True,
    )
    obj = GLMObjective(loss=LogisticLoss)
    val_ref, grad_ref = jax.value_and_grad(obj.value)(jnp.asarray(w), batch)
    np.testing.assert_allclose(float(val), float(val_ref), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(grad), np.asarray(grad_ref), rtol=1e-4, atol=1e-5
    )
    # A use_pallas objective on a fusible batch always takes the kernel.
    assert GLMObjective(loss=LogisticLoss, use_pallas=True)._can_fuse(batch)


def test_linearized_hvp_fused_route_matches_fallback():
    """linearized_hvp's one-read route (the fused kernel) == the
    linearize/transpose two-pass form, with L2, intercept, and factor
    normalization folded."""
    from photon_tpu.data.normalization import NormalizationContext

    rng = np.random.default_rng(17)
    n, d = 160, 11
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    wt = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    off = rng.normal(size=n).astype(np.float32) * 0.1
    w = rng.normal(size=d).astype(np.float32) * 0.4
    v = rng.normal(size=d).astype(np.float32)
    batch = LabeledBatch(jnp.asarray(y), jnp.asarray(X), jnp.asarray(off), jnp.asarray(wt))
    norm = NormalizationContext(
        factors=jnp.asarray(np.linspace(0.6, 1.4, d).astype(np.float32)),
        intercept_index=0,
    )
    for kw in [
        dict(loss=LogisticLoss, l2_weight=0.9, intercept_index=0),
        dict(loss=LogisticLoss, l2_weight=0.3, intercept_index=0, normalization=norm),
        dict(loss=SquaredLoss),
    ]:
        obj_f = GLMObjective(use_pallas=True, **kw)
        obj_r = GLMObjective(**kw)
        assert obj_f._can_fuse(batch)
        got = obj_f.linearized_hvp(jnp.asarray(w), batch, one_read=True)(
            jnp.asarray(v))
        ref = obj_r.linearized_hvp(jnp.asarray(w), batch)(jnp.asarray(v))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)
        # And against the jvp-of-grad operator for good measure.
        ref2 = obj_r.hvp(jnp.asarray(w), jnp.asarray(v), batch)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref2), rtol=1e-4, atol=1e-4)
