"""The batch laid out in runs of the first random coordinate's entities.

Three contracts, on the CPU: a block whose lanes are runs reads its residual
offsets as windows, bit for bit what the scalar gather reads
(``LaneRuns.offsets`` against ``EntityBlock.gather_offsets``);
``GameEstimator.fit`` on the laid-out batch returns the model of the row
order (the batch's rows are summed in another order, so to the last bits);
and where the device's memory leaves no room for the copy nothing is laid
out. The device's memory is what ``game_estimator._device_memory`` reports,
which the tests set: the CPU reports none.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.batch import SparseFeatures
from photon_tpu.data.game_data import GameBatch, RowLayout, take_rows
from photon_tpu.data.random_effect import (
    RUN_WINDOW_MIN_ROWS,
    EntityBlock,
    LaneRuns,
    group_entity_rows,
    lane_runs,
    slab_budget_of,
)
from photon_tpu.estimators import game_estimator
from photon_tpu.estimators.config import (
    FixedEffectCoordinateConfig,
    GameOptimizationConfig,
    RandomEffectCoordinateConfig,
    RegularizationConfig,
)
from photon_tpu.estimators.game_estimator import GameEstimator
from photon_tpu.models.coefficients import Coefficients
from photon_tpu.models.game import FixedEffectModel, GameModel
from photon_tpu.models.glm import GeneralizedLinearModel
from photon_tpu.obs.metrics import registry
from photon_tpu.types import TaskType

LIMIT = 16 << 30


def _device_with(monkeypatch, room: bool) -> None:
    """A device of 16 GiB that has the room for the copy, or none of it."""
    _device_with_free(monkeypatch, LIMIT if room else 0)


def _device_with_free(monkeypatch, free: int) -> None:
    monkeypatch.setattr(
        game_estimator, "_device_memory",
        lambda: {"bytes_limit": LIMIT, "bytes_in_use": LIMIT - free},
    )


# --- (a) the run form against the scalar gather ------------------------------


def _run_block(counts, n_max, first_row, n):
    """A block of consecutive runs from ``first_row`` (``counts`` a lane, 0
    on padding lanes) over a batch of ``n`` rows."""
    counts = np.asarray(counts, np.int32)
    start = first_row + np.concatenate([[0], np.cumsum(counts)[:-1]])
    start = np.where(counts > 0, start, 0).astype(np.int32)
    slot = np.arange(n_max)
    sidx = np.where(slot < counts[:, None], start[:, None] + slot, -1)
    assert sidx.max() < n
    lanes = counts.size
    block = EntityBlock(
        entity_idx=jnp.asarray(np.where(counts > 0, np.arange(lanes), -1)),
        features=jnp.zeros((lanes, n_max, 1), jnp.float32),
        label=jnp.zeros((lanes, n_max), jnp.float32),
        weight=jnp.asarray((sidx >= 0).astype(np.float32)),
        sample_index=jnp.asarray(sidx.astype(np.int32)),
        train_mask=jnp.asarray(counts > 0),
    )
    return block, LaneRuns(jnp.asarray(start), jnp.asarray(counts))


RUN_CASES = {
    # name: (counts a lane, n_max, first row, n)
    "n_max_4_padding_lanes": ([4, 1, 3, 2, 0, 0], 4, 10, 64),
    "n_max_24_ends_on_last_row": ([24, 17, 9], 24, 64 - 50, 64),
    "n_max_96": ([96, 65, 80, 0], 96, 7, 400),
    "n_max_128_ends_on_last_row": ([128, 100, 1], 128, 1000 - 229, 1000),
    "n_max_512_padding_lanes": ([512, 385, 400, 0, 0, 0], 512, 3, 2000),
    "one_lane_4096_ends_on_last_row": ([3001], 4096, 5000 - 3001, 5000),
    "one_lane_4096_full": ([4096], 4096, 0, 4096),
}


@pytest.mark.parametrize("case", sorted(RUN_CASES))
def test_run_form_equals_the_scalar_gather_bit_for_bit(case):
    counts, n_max, first, n = RUN_CASES[case]
    block, runs = _run_block(counts, n_max, first, n)
    offsets = jnp.asarray(
        np.random.default_rng(n_max).standard_normal(n).astype(np.float32)
    )
    want = np.asarray(jax.jit(lambda b, o: b.gather_offsets(o))(block, offsets))
    got = np.asarray(
        jax.jit(lambda r, o: r.offsets(o, n_max))(runs, offsets)
    )
    assert got.dtype == want.dtype
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    assert not got[np.asarray(block.sample_index) < 0].any()  # zeros on padding
    # The fill keeps the runs where the block is deep enough to read them.
    kept = lane_runs(np.asarray(block.sample_index), np.asarray(counts, np.int32))
    assert (kept is not None) == (n_max >= RUN_WINDOW_MIN_ROWS)


def test_lanes_that_are_not_runs_keep_the_scalar_gather():
    counts = np.array([200, 150], np.int32)
    block, _runs = _run_block(counts, 256, 0, 400)
    sidx = np.asarray(block.sample_index).copy()
    sidx[1, [3, 4]] = sidx[1, [4, 3]]  # one lane's rows out of order
    assert lane_runs(sidx, counts) is None
    sidx = np.asarray(block.sample_index).copy()
    sidx[1, :150] += 5  # a gap between the lanes: each is still one run
    runs = lane_runs(sidx, counts)
    assert runs is not None and list(np.asarray(runs.start)) == [0, 205]


def test_runs_are_not_a_leaf_of_the_block():
    """A solver traced on a block with runs serves one without them, and a
    block rebuilt from its leaves reads by ``sample_index``."""
    block, runs = _run_block([200, 150], 256, 0, 400)
    object.__setattr__(block, "runs", runs)
    bare = dataclasses.replace(block)
    assert bare.runs is None
    assert jax.tree_util.tree_structure(block) == jax.tree_util.tree_structure(bare)
    assert jax.device_put(block).runs is None


# --- (b)-(d) fits on the laid-out batch --------------------------------------


def _counts(kind: str, rng) -> np.ndarray:
    if kind == "zipf":
        # Levels from 12 to 600 rows: blocks under RUN_WINDOW_MIN_ROWS slots
        # keep the scalar gather, the deeper ones read runs.
        return np.array([600, 300, 220, 160, 140, 90, 60, 40, 30, 24, 20, 12])
    if kind == "shallow":
        # Every user under RUN_WINDOW_MIN_ROWS rows: no block reads windows.
        return rng.integers(4, 40, size=48)
    return rng.integers(150, 190, size=16)


def _sparse(X: np.ndarray, plan: bool = False) -> SparseFeatures:
    """``X`` as a padded-sparse shard of every column; with ``plan``, the
    transpose plan a wide shard carries on a TPU."""
    n, d = X.shape
    k = np.tile(np.arange(d, dtype=np.int32), (n, 1))
    f = SparseFeatures(jnp.asarray(k), jnp.asarray(X), d)
    return f.with_transpose_plan() if plan else f


def _batch(kind: str, seed: int = 3, sparse_users: bool = False,
           sparse_fixed: bool = False):
    rng = np.random.default_rng(seed)
    counts = _counts(kind, rng)
    users = np.repeat(np.arange(counts.size, dtype=np.int32), counts)
    unknown = 37
    users = np.concatenate([users, np.full(unknown, -1, np.int32)])
    n = users.size
    perm = rng.permutation(n)
    users = users[perm]  # ids i.i.d. over the rows
    items = rng.integers(0, 5, size=n).astype(np.int32)
    Xf = rng.normal(size=(n, 6)).astype(np.float32)
    Xf[:, 0] = 1.0
    Xu = rng.normal(size=(n, 3)).astype(np.float32)
    Xu[:, 0] = 1.0
    Xi = rng.normal(size=(n, 3)).astype(np.float32)
    Xi[:, 0] = 1.0
    wu = rng.normal(size=(counts.size, 3)).astype(np.float32)
    logits = Xf @ rng.normal(size=6).astype(np.float32) + np.where(
        users >= 0, np.sum(Xu * wu[np.maximum(users, 0)], axis=1), 0.0
    )
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    batch = GameBatch(
        label=jnp.asarray(y),
        offset=jnp.asarray(rng.normal(scale=0.1, size=n).astype(np.float32)),
        weight=jnp.asarray(rng.uniform(0.5, 1.5, size=n).astype(np.float32)),
        features={
            "g": _sparse(Xf, plan=True) if sparse_fixed else jnp.asarray(Xf),
            "u": _sparse(Xu) if sparse_users else jnp.asarray(Xu),
            "i": jnp.asarray(Xi),
        },
        entity_ids={"user": jnp.asarray(users), "item": jnp.asarray(items)},
        uid=jnp.asarray(np.arange(n, dtype=np.int64) * 7919),
    )
    return batch, counts.size


FIT_CASES = {
    # name: (counts, items coordinate, sparse shard, per-user config,
    #        estimator options, passes)
    "uniform": ("uniform", False, None, {}, {}, 2),
    "zipf": ("zipf", False, None, {}, {}, 2),
    "two_random": ("uniform", True, None, {}, {}, 2),
    "capped": ("zipf", False, None, dict(active_upper_bound=200), {}, 2),
    "active_set": ("zipf", False, None, {}, dict(re_active_set=True), 3),
    "projected_users": ("uniform", True, "users", {}, {}, 2),
    # A wide fixed-effect shard carries a transpose plan over its entries.
    "sparse_fixed_with_plan": ("zipf", False, "fixed", {}, {}, 2),
}


def _estimator(case: str, users: int):
    _kind, items, _sparse, per_user, options, passes = FIT_CASES[case]
    # Solved to float32's resolution: the two orders of the rows then differ
    # by rounding, not by where a stopping rule happened to stop.
    tight = dict(max_iter=200, tol=1e-12)
    cfgs = [
        FixedEffectCoordinateConfig("fixed", "g", **tight),
        RandomEffectCoordinateConfig("per_user", "user", "u", **tight, **per_user),
    ]
    if items:
        cfgs.append(RandomEffectCoordinateConfig("per_item", "item", "i", **tight))
    estimator = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs=cfgs,
        num_iterations=passes,
        intercept_indices={"g": 0, "u": 0, "i": 0},
        num_entities={"user": users, "item": 5},
        **options,
    )
    opt = GameOptimizationConfig(
        reg={c.coordinate_id: RegularizationConfig(weight=1.0) for c in cfgs}
    )
    return estimator, opt


def _fit(monkeypatch, case: str, room: bool, fixed=None):
    """One fit of ``case``; with ``fixed``, a fixed-effect model the fit is
    given and keeps (a locked coordinate)."""
    kind, _items, sparse, *_rest = FIT_CASES[case]
    _device_with(monkeypatch, room)
    batch, users = _batch(
        kind, sparse_users=sparse == "users", sparse_fixed=sparse == "fixed"
    )
    estimator, opt = _estimator(case, users)
    initial = None
    if fixed is not None:
        estimator.locked_coordinates = ["fixed"]
        initial = GameModel({"fixed": FixedEffectModel(
            GeneralizedLinearModel(Coefficients(jnp.asarray(fixed)),
                                   TaskType.LOGISTIC_REGRESSION), "g")})
    (result,) = estimator.fit(
        batch, optimization_configs=[opt], initial_model=initial
    )
    return estimator, batch, result


def _tables(result) -> dict:
    out = {}
    for cid, m in result.model.models.items():
        if hasattr(m, "to_dense"):
            m = m.to_dense()
        out[cid] = np.asarray(
            m.coefficients if hasattr(m, "coefficients")
            else m.model.coefficients.means
        )
    return out


def _gauge(name: str, coordinate: str) -> float:
    return registry().gauge(name, coordinate=coordinate).value


@pytest.mark.parametrize("fixed", ["locked", "trained"])
@pytest.mark.parametrize("case", sorted(FIT_CASES))
def test_fit_on_the_laid_out_batch_gives_the_row_order_model(
    monkeypatch, case, fixed
):
    """With the fixed effect locked every coordinate sees the same residuals
    in both orders, and the random-effect tables come out bit for bit. A
    trained fixed effect sums the rows in another order, and its stopping
    rule stops within float32's resolution of the objective: at a few
    thousand rows that moves both effects by up to 6.2e-4 of their norm."""
    w = None if fixed == "trained" else np.linspace(-1, 1, 6).astype(np.float32)
    _est, _b, rows = _fit(monkeypatch, case, room=False, fixed=w)
    estimator, batch, laid = _fit(monkeypatch, case, room=True, fixed=w)
    layout = "per_item" if case == "projected_users" else "per_user"
    assert _gauge("batch_laid_out", layout) == 1
    run_batch = estimator._prepare_datasets(batch)
    assert run_batch is not batch
    order = np.asarray(estimator._layout.order)
    assert np.array_equal(np.sort(order), np.arange(batch.n))
    assert np.array_equal(
        np.asarray(run_batch.entity_ids["user"]),
        np.asarray(batch.entity_ids["user"])[order],
    )
    blocks = estimator._re_datasets[layout].blocks
    assert any(b.runs is not None for b in blocks)
    assert _gauge("re_row_gather_blocks", layout) == sum(
        b.runs is None for b in blocks
    )
    # Every lane of the layout coordinate's blocks is one run; the rows that
    # no block holds (unknown ids, a cap's leftovers) come last.
    for b in blocks:
        sidx = np.asarray(b.sample_index)
        slot = np.arange(b.n_max)
        count = (sidx >= 0).sum(axis=1)
        runs = np.where(slot < count[:, None], sidx[:, :1] + slot, -1)
        assert np.array_equal(sidx, runs)
    held = np.concatenate([np.asarray(b.sample_index).ravel() for b in blocks])
    held = held[held >= 0]
    assert np.array_equal(np.sort(held), np.arange(held.size))

    want, got = _tables(rows), _tables(laid)
    for cid in want:
        if fixed == "locked":
            assert np.array_equal(got[cid], want[cid]), cid
        else:
            gap = np.linalg.norm(got[cid] - want[cid]) / np.linalg.norm(want[cid])
            assert gap <= 2e-3, (cid, gap)


def test_the_layout_does_not_depend_on_what_the_entities_are_called(monkeypatch):
    """Renaming the entities leaves the rows' order, and so every sum over
    them, as it was."""
    _device_with(monkeypatch, room=True)
    orders = []
    for rename in (False, True):
        batch, users = _batch("zipf")
        if rename:
            ids = np.asarray(batch.entity_ids["user"])
            new = np.random.default_rng(9).permutation(users).astype(np.int32)
            batch = dataclasses.replace(batch, entity_ids=dict(
                batch.entity_ids, user=jnp.asarray(np.where(ids >= 0, new[ids], -1))
            ))
        estimator, _opt = _estimator("zipf", users)
        estimator._prepare_datasets(batch)
        orders.append(np.asarray(estimator._layout.order))
    assert np.array_equal(orders[0], orders[1])


def test_no_room_for_the_copy_lays_nothing_out(monkeypatch):
    estimator, batch, _result = _fit(monkeypatch, "zipf", room=False)
    assert estimator._prepare_datasets(batch) is batch
    assert estimator._layout.order is None
    assert _gauge("batch_laid_out", "per_user") == 0
    blocks = estimator._re_datasets["per_user"].blocks
    assert all(b.runs is None for b in blocks)
    assert _gauge("re_row_gather_blocks", "per_user") == len(blocks)


def test_a_backend_that_reports_no_memory_lays_nothing_out(monkeypatch):
    monkeypatch.setattr(game_estimator, "_device_memory", lambda: {})
    batch, users = _batch("uniform")
    estimator, opt = _estimator("uniform", users)
    assert estimator._prepare_datasets(batch) is batch


@pytest.mark.parametrize("plan", [False, True])
def test_take_rows_moves_every_row_of_the_batch(plan):
    """Every array indexed by row moves with its rows; a sparse shard's
    transpose plan indexes its entries, and its product with the laid-out
    rows is the product of the rows as given, bit for bit."""
    batch, _users = _batch("uniform", sparse_users=True)
    batch = dataclasses.replace(batch, features=dict(
        batch.features, g=_sparse(np.asarray(batch.features["g"]), plan)
    ))
    order = np.random.default_rng(0).permutation(batch.n).astype(np.int32)
    moved = take_rows(batch, jnp.asarray(order))

    def by_row(b):
        feats = [
            a for _s, f in sorted(b.features.items())
            for a in ((f.indices, f.values) if isinstance(f, SparseFeatures) else (f,))
        ]
        eids = [e for _t, e in sorted(b.entity_ids.items())]
        return [b.label, b.offset, b.weight, b.uid, *feats, *eids]

    for a, b in zip(by_row(batch), by_row(moved)):
        assert np.array_equal(np.asarray(b), np.asarray(a)[order])
    r = np.random.default_rng(2).standard_normal(batch.n).astype(np.float32)
    want = np.asarray(batch.features["g"].rmatvec(jnp.asarray(r)))
    got = np.asarray(moved.features["g"].rmatvec(jnp.asarray(r[order])))
    assert (moved.features["g"].csc_order is not None) == plan
    if plan:
        assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_blocks_too_shallow_for_windows_lay_nothing_out(monkeypatch):
    """A coordinate whose blocks would all keep the scalar gather is not
    worth the copy."""
    _device_with(monkeypatch, room=True)
    batch, users = _batch("shallow")
    estimator, _opt = _estimator("uniform", users)
    assert estimator._prepare_datasets(batch) is batch
    assert _gauge("batch_laid_out", "per_user") == 0


@pytest.mark.parametrize("short", [0, 1, "fit"])
def test_the_room_check_reserves_the_fit_its_working_set(monkeypatch, short):
    """The device must leave free the batch once more, the blocks, a slab
    budget and what the fit itself holds: a byte short of that, or short by
    the fit's working set alone, nothing is laid out."""
    batch, users = _batch("uniform")
    estimator, _opt = _estimator("uniform", users)
    cfg = estimator.coordinate_configs[1]
    grouping = group_entity_rows(
        np.asarray(batch.entity_ids["user"]), np.asarray(batch.features["u"]),
        GameEstimator._data_config(cfg), np.asarray(batch.uid),
        slab_budget_of(LIMIT),
    )
    need = game_estimator._layout_bytes(batch, {"per_user": grouping}, LIMIT)
    copy = (
        sum(a.nbytes for a in jax.tree_util.tree_leaves(batch))
        + grouping.block_bytes() + slab_budget_of(LIMIT)
    )
    assert need - copy >= 4 * batch.n * (
        game_estimator.FIT_FLOATS_A_ROW
        + game_estimator.FIT_FLOATS_A_ROW_AND_COLUMN * grouping.d
    )
    _device_with_free(monkeypatch, copy if short == "fit" else need - short)
    laid = estimator._prepare_datasets(batch) is not batch
    assert laid == (short == 0)
    assert _gauge("batch_laid_out", "per_user") == int(laid)


def test_down_sampling_keeps_the_rows_of_the_row_order(monkeypatch):
    """A seed draws the same rows whatever order the batch is laid out in."""
    from photon_tpu.sampling.down_sampler import DefaultDownSampler

    batch, _users = _batch("uniform")
    order = np.random.default_rng(1).permutation(batch.n).astype(np.int32)
    lb = batch.labeled_batch("g")
    laid_lb = take_rows(batch, jnp.asarray(order)).labeled_batch("g")
    plain = DefaultDownSampler(0.5, seed=4).apply(lb)
    laid = DefaultDownSampler(
        0.5, seed=4, layout=RowLayout(jnp.asarray(order))
    ).apply(laid_lb)
    assert np.array_equal(np.asarray(laid.weight), np.asarray(plain.weight)[order])


def test_a_checkpoint_resumes_across_layouts(monkeypatch, tmp_path):
    """Score vectors are checkpointed in the rows' original order: a pass
    checkpointed laid out holds the scores of its own model on the batch as
    given, and a descent that resumes it on the row order ends where one
    that never stopped ends."""
    from photon_tpu.utils.checkpoint import load_checkpoint

    _device_with(monkeypatch, room=True)
    batch, users = _batch("uniform")
    estimator, opt = _estimator("uniform", users)
    estimator.num_iterations = 1
    (one,) = estimator.fit(
        batch, optimization_configs=[opt], checkpoint_dir=str(tmp_path)
    )
    assert estimator._layout.order is not None
    state, _step = load_checkpoint(f"{tmp_path}/cfg_0")
    np.testing.assert_allclose(
        np.asarray(state["total_scores"]),
        np.asarray(sum(m.score(batch) for m in one.model.models.values())),
        rtol=1e-5, atol=1e-5,
    )
    _device_with(monkeypatch, room=False)
    resumed, _opt = _estimator("uniform", users)
    (res,) = resumed.fit(
        batch, optimization_configs=[opt], checkpoint_dir=str(tmp_path)
    )
    assert resumed._layout.order is None
    (whole,) = _estimator("uniform", users)[0].fit(
        batch, optimization_configs=[opt]
    )
    a, b = _tables(res), _tables(whole)
    for cid in a:
        gap = np.linalg.norm(a[cid] - b[cid]) / np.linalg.norm(b[cid])
        assert gap <= 2e-3, (cid, gap)


def test_batched_tuning_reads_the_laid_out_batch(monkeypatch):
    from photon_tpu.estimators.evaluation_function import (
        GameEstimatorEvaluationFunction,
    )
    from photon_tpu.evaluation import EvaluationSuite
    from photon_tpu.evaluation.suite import EvaluatorSpec

    X = np.array([[0.0, 0.0], [1.0, -1.0], [-1.0, 1.0]])
    values = {}
    for room in (False, True):
        _device_with(monkeypatch, room)
        train, users = _batch("zipf", seed=5)
        valid, _ = _batch("zipf", seed=6)
        estimator, _opt = _estimator("uniform", users)
        base = GameOptimizationConfig(reg={
            "fixed": RegularizationConfig(weight=1.0),
            "per_user": RegularizationConfig(weight=1.0),
        })
        fn = GameEstimatorEvaluationFunction(
            estimator, base, train, valid,
            EvaluationSuite([EvaluatorSpec.parse("AUC")]), is_opt_max=True,
        )
        assert fn._batched_evaluator() is not None
        assert (estimator._layout.order is not None) == room
        values[room] = fn.evaluate_batch(X)
    # The fixed effect's sums in another order move an AUC by ~1e-5; rows
    # gathered against the wrong batch would move it by far more.
    np.testing.assert_allclose(values[True], values[False], rtol=0, atol=1e-4)
