"""Per-block subspace projection: wide sparse shards train in compact
block feature spaces and project back losslessly (reference
LinearSubspaceProjector.scala:36-88, RandomEffectDataset.scala:383-432,
ModelProjection.scala)."""

import numpy as np
import jax.numpy as jnp
import pytest

from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
from photon_tpu.data.batch import SparseFeatures
from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.index_map import IndexMap
from photon_tpu.data.random_effect import (
    RandomEffectDataConfig,
    build_random_effect_dataset,
)
from photon_tpu.models.game import ProjectedRandomEffectModel
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.types import TaskType

D_FULL = 500  # wide shard
K = 4  # nnz per row
E = 24
N = 360


def _wide_problem(seed=0):
    """Each entity touches a small random set of columns — the reference's
    normal case (wide shared shard, tiny per-entity slice)."""
    rng = np.random.default_rng(seed)
    eids = (np.arange(N) % E).astype(np.int32)
    # Entity e draws its columns from a 12-wide window → block unions ≪ D_FULL.
    base = rng.integers(0, D_FULL - 12, size=E)
    indices = np.zeros((N, K), np.int32)
    values = np.zeros((N, K), np.float32)
    for i in range(N):
        cols = base[eids[i]] + rng.choice(12, size=K - 1, replace=False)
        indices[i, : K - 1] = cols
        values[i, : K - 1] = rng.normal(size=K - 1)
        indices[i, K - 1] = 0  # intercept column
        values[i, K - 1] = 1.0
    logits = rng.normal(size=E)[eids] * 1.5
    y = (rng.uniform(size=N) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    wt = np.ones(N, np.float32)
    return eids, indices, values, y, wt


def _dense_of(indices, values):
    Xd = np.zeros((N, D_FULL), np.float32)
    np.add.at(Xd, (np.arange(N)[:, None].repeat(K, 1), indices), values)
    return Xd


def _config(**kw):
    return RandomEffectDataConfig(
        re_type="userId", feature_shard="wide", **kw
    )


def test_sparse_build_compacts_blocks():
    eids, indices, values, y, wt = _wide_problem()
    ds = build_random_effect_dataset(
        eids, (indices, values, D_FULL), y, wt, E, _config()
    )
    assert ds.projected
    assert ds.dim == D_FULL
    for b in ds.blocks:
        assert b.col_map is not None
        assert b.dim <= D_FULL // 2  # block dim ≪ shard dim
        # col_map covers exactly the nonzero columns of the block.
        dense = _dense_of(indices, values)
        rows = np.asarray(b.sample_index)[np.asarray(b.sample_index) >= 0]
        active = np.flatnonzero(np.any(dense[rows] != 0, axis=0))
        np.testing.assert_array_equal(np.sort(np.asarray(b.col_map)), active)
        # Block features reproduce the dense rows under the column map.
        dense_block = np.asarray(b.project_backward(
            jnp.asarray(np.asarray(b.features).reshape(-1, b.dim)), D_FULL
        )).reshape(b.num_entities, b.n_max, D_FULL)
        si = np.asarray(b.sample_index)
        for e in range(b.num_entities):
            for t in range(b.n_max):
                if si[e, t] >= 0:
                    np.testing.assert_allclose(dense_block[e, t], dense[si[e, t]])


def test_projected_training_matches_dense():
    eids, indices, values, y, wt = _wide_problem(seed=1)
    dense = _dense_of(indices, values)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)

    ds_sp = build_random_effect_dataset(
        eids, (indices, values, D_FULL), y, wt, E, _config()
    )
    ds_dn = build_random_effect_dataset(eids, dense, y, wt, E, _config())
    assert ds_sp.projected and not ds_dn.projected

    coord_sp = RandomEffectCoordinate(
        coordinate_id="perUser", dataset=ds_sp,
        task=TaskType.LOGISTIC_REGRESSION, objective=obj,
    )
    coord_dn = RandomEffectCoordinate(
        coordinate_id="perUser", dataset=ds_dn,
        task=TaskType.LOGISTIC_REGRESSION, objective=obj,
    )
    batch_sp = GameBatch(
        label=jnp.asarray(y),
        offset=jnp.zeros(N, jnp.float32),
        weight=jnp.asarray(wt),
        features={"wide": SparseFeatures(jnp.asarray(indices), jnp.asarray(values), D_FULL)},
        entity_ids={"userId": jnp.asarray(eids)},
    )
    batch_dn = GameBatch(
        label=jnp.asarray(y),
        offset=jnp.zeros(N, jnp.float32),
        weight=jnp.asarray(wt),
        features={"wide": jnp.asarray(dense)},
        entity_ids={"userId": jnp.asarray(eids)},
    )
    model_sp, stats_sp = coord_sp.train(batch_sp)
    model_dn, stats_dn = coord_dn.train(batch_dn)
    assert isinstance(model_sp, ProjectedRandomEffectModel)

    # Same optima, projected back to the global space.
    np.testing.assert_allclose(
        np.asarray(model_sp.to_dense().coefficients),
        np.asarray(model_dn.coefficients),
        rtol=2e-3, atol=2e-4,
    )
    # Same scores, through both feature representations.
    np.testing.assert_allclose(
        np.asarray(model_sp.score(batch_sp)),
        np.asarray(model_dn.score(batch_dn)),
        rtol=2e-3, atol=2e-4,
    )
    assert stats_sp.num_entities == stats_dn.num_entities == E


def test_projected_warm_start_and_zero_model():
    eids, indices, values, y, wt = _wide_problem(seed=2)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)
    ds = build_random_effect_dataset(
        eids, (indices, values, D_FULL), y, wt, E, _config()
    )
    coord = RandomEffectCoordinate(
        coordinate_id="perUser", dataset=ds,
        task=TaskType.LOGISTIC_REGRESSION, objective=obj,
    )
    batch = GameBatch(
        label=jnp.asarray(y),
        offset=jnp.zeros(N, jnp.float32),
        weight=jnp.asarray(wt),
        features={"wide": SparseFeatures(jnp.asarray(indices), jnp.asarray(values), D_FULL)},
        entity_ids={"userId": jnp.asarray(eids)},
    )
    zero = coord.zero_model()
    assert float(jnp.sum(jnp.abs(zero.score(batch)))) == 0.0
    m1, _ = coord.train(batch)
    # Projected warm start (same dataset) and dense warm start both accepted.
    m2, _ = coord.train(batch, initial_model=m1)
    m3, _ = coord.train(batch, initial_model=m1.to_dense())
    np.testing.assert_allclose(
        np.asarray(m2.to_dense().coefficients),
        np.asarray(m3.to_dense().coefficients),
        rtol=1e-3, atol=1e-4,
    )


def test_projected_model_io(tmp_path):
    from photon_tpu.io.model_io import load_game_model, save_game_model
    from photon_tpu.models.game import GameModel

    eids, indices, values, y, wt = _wide_problem(seed=3)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)
    ds = build_random_effect_dataset(
        eids, (indices, values, D_FULL), y, wt, E, _config()
    )
    coord = RandomEffectCoordinate(
        coordinate_id="perUser", dataset=ds,
        task=TaskType.LOGISTIC_REGRESSION, objective=obj, compute_variance=True,
    )
    batch = GameBatch(
        label=jnp.asarray(y),
        offset=jnp.zeros(N, jnp.float32),
        weight=jnp.asarray(wt),
        features={"wide": SparseFeatures(jnp.asarray(indices), jnp.asarray(values), D_FULL)},
        entity_ids={"userId": jnp.asarray(eids)},
    )
    model, _ = coord.train(batch)
    imap = IndexMap.build([f"f{j}" for j in range(D_FULL)])
    # Feature j ↔ name f{j}: build ensures insertion order = index order.
    game = GameModel({"perUser": model})
    out = tmp_path / "model"
    save_game_model(game, str(out), {"wide": imap})
    loaded = load_game_model(str(out), {"wide": imap})
    dense = model.to_dense()
    np.testing.assert_allclose(
        np.asarray(loaded.models["perUser"].coefficients),
        np.asarray(dense.coefficients),
        atol=2e-4,  # save applies the sparsity threshold
    )
