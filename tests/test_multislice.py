"""Multi-slice (DCN) mesh tests on the virtual 8-device CPU mesh.

A (slice=2, data=2, feature=2) mesh exercises hierarchical dp reductions
(psum over ('slice','data')) together with feature sharding — the layout a
multi-slice pod would run (SURVEY.md §2.8 DCN obligations).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_tpu.data.batch import LabeledBatch, SparseFeatures
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.common import OptimizerConfig
from photon_tpu.optim.lbfgs import minimize_lbfgs
from photon_tpu.parallel.feature_sharded import (
    place_feature_sharded,
    train_fixed_effect_feature_sharded,
)
from photon_tpu.parallel.mesh import (
    DATA_AXIS,
    FEATURE_AXIS,
    SLICE_AXIS,
    dp_axes,
    make_mesh,
    make_multislice_mesh,
)
from photon_tpu.parallel.distributed import shard_batch
from photon_tpu.parallel.train_step import glmix_sharded_train_step


def test_multislice_mesh_axes():
    mesh = make_multislice_mesh(n_slices=2, n_feature=2)
    assert mesh.axis_names == (SLICE_AXIS, DATA_AXIS, FEATURE_AXIS)
    assert mesh.shape[SLICE_AXIS] == 2
    assert mesh.shape[DATA_AXIS] == 2
    assert mesh.shape[FEATURE_AXIS] == 2
    assert dp_axes(mesh) == (SLICE_AXIS, DATA_AXIS)
    assert dp_axes(make_mesh(n_data=8)) == (DATA_AXIS,)


def test_feature_sharded_on_multislice_mesh():
    """Sparse TP fit over (2 slices × 2 data × 2 feature) == replicated fit."""
    mesh = make_multislice_mesh(n_slices=2, n_feature=2)
    n, d, k = 64, 32, 5
    rng = np.random.default_rng(0)
    indices = rng.integers(0, d, size=(n, k)).astype(np.int32)
    values = rng.normal(size=(n, k)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    Xd = np.zeros((n, d), np.float32)
    for i in range(n):
        for j in range(k):
            Xd[i, indices[i, j]] += values[i, j]

    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)
    cfg = OptimizerConfig(max_iter=40, tol=1e-8, track_history=False)
    fit = train_fixed_effect_feature_sharded(mesh, obj, cfg, d)
    batch = LabeledBatch(
        jnp.asarray(y), SparseFeatures(jnp.asarray(indices), jnp.asarray(values), d)
    )
    w0, b = place_feature_sharded(mesh, jnp.zeros(d, jnp.float32), batch)
    res = fit(w0, b)

    ref = minimize_lbfgs(
        lambda w: obj.value_and_grad(w, LabeledBatch(jnp.asarray(y), jnp.asarray(Xd))),
        jnp.zeros(d, jnp.float32),
        cfg,
    )
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(ref.w), rtol=5e-3, atol=5e-4)


def test_glmix_step_on_multislice_mesh():
    """The full GLMix sharded train step compiles and runs on a slice mesh."""
    from photon_tpu.data.random_effect import (
        RandomEffectDataConfig,
        build_random_effect_dataset,
    )

    mesh = make_multislice_mesh(n_slices=2, n_feature=1)  # (2, 4, 1)
    n_dp = 8
    E, n, d_fix, d_re = 4 * n_dp, 16 * n_dp, 12, 4
    rng = np.random.default_rng(1)
    Xf = rng.normal(size=(n, d_fix)).astype(np.float32)
    Xr = rng.normal(size=(n, d_re)).astype(np.float32)
    users = (np.arange(n) % E).astype(np.int32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)

    ds = build_random_effect_dataset(
        users, Xr, y, np.ones(n, np.float32), E,
        RandomEffectDataConfig(re_type="userId", feature_shard="re"),
    )
    (block,) = ds.blocks

    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)
    cfg = OptimizerConfig(max_iter=3, track_history=False)
    step, place = glmix_sharded_train_step(mesh, obj, obj, cfg, cfg)
    args = place(
        jnp.zeros((d_fix,), jnp.float32),
        jnp.zeros((E, d_re), jnp.float32),
        LabeledBatch(jnp.asarray(y), jnp.asarray(Xf)),
        block,
        jnp.asarray(Xr),
        jnp.asarray(users),
    )
    w, coefs, scores, _, _ = step(*args)
    assert w.shape == (d_fix,)
    assert coefs.shape == (E, d_re)
    assert bool(jnp.all(jnp.isfinite(scores)))


def test_shard_batch_multislice_padding():
    mesh = make_multislice_mesh(n_slices=2, n_feature=1)  # dp size 8
    batch = LabeledBatch(jnp.ones(13), jnp.ones((13, 3)))
    sb = shard_batch(batch, mesh)
    assert sb.n == 16  # padded to the dp-axis product
    assert float(sb.total_weight) == 13.0  # padding rows weight 0


def test_evaluators_exact_on_sharded_scores():
    """SURVEY §7 hard part 2 (exact distributed AUC): every evaluator must
    produce the SAME value when scores/labels/weights live sharded across
    the 8-device mesh as when they are replicated on one device — XLA's
    global sort/segment collectives, not an approximation."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from photon_tpu.evaluation import evaluators as ev
    from photon_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(77)
    n = 8 * 250
    scores = rng.normal(size=n).astype(np.float32)
    labels = (rng.uniform(size=n) < 0.4).astype(np.float32)
    weight = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    # Inject exact ties so tie handling rides through the sharded sort.
    scores[::7] = 0.5

    mesh = make_mesh(n_data=8)
    rows = NamedSharding(mesh, P("data"))
    sh = lambda x: jax.device_put(jnp.asarray(x), rows)

    metrics = {
        "auc_roc": ev.auc_roc,
        "auc_pr": ev.auc_pr,
        "rmse": ev.rmse,
        "logistic_loss": ev.logistic_loss_metric,
        "squared_loss": ev.squared_loss_metric,
    }
    for name, fn in metrics.items():
        plain = float(jax.jit(fn)(jnp.asarray(scores), jnp.asarray(labels),
                                  jnp.asarray(weight)))
        sharded = float(jax.jit(fn)(sh(scores), sh(labels), sh(weight)))
        np.testing.assert_allclose(sharded, plain, rtol=1e-5, atol=1e-6,
                                   err_msg=name)

    # Grouped (per-entity) AUC: the global lexicographic sort + segment ops
    # must be exact over sharded inputs too.
    gids = rng.integers(0, 16, size=n).astype(np.int32)
    g = jax.jit(ev.grouped_auc, static_argnames="num_groups")
    plain = float(g(jnp.asarray(scores), jnp.asarray(labels),
                    jnp.asarray(gids), num_groups=16, weight=jnp.asarray(weight)))
    sharded = float(g(sh(scores), sh(labels), sh(gids), num_groups=16,
                      weight=sh(weight)))
    np.testing.assert_allclose(sharded, plain, rtol=1e-5, atol=1e-6,
                               err_msg="grouped_auc")
