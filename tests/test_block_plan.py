"""The random-effect block plan (data/random_effect.py: ``plan_blocks``):
geometry from the entities' row counts alone, under its stated bounds, and a
``GameEstimator.fit`` on heavy-tailed users against the layout-free
reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import photon_tpu.data.random_effect as re_data
from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
from photon_tpu.algorithm.solve_cache import SolveCache
from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.random_effect import (
    PLAN_MERGE_PAD_BOUND,
    PLAN_PAD_CEILING,
    BlockPlan,
    RandomEffectDataConfig,
    bucket_dim,
    build_random_effect_dataset,
    lane_dim,
    plan_blocks,
    slab_budget_of,
)
from photon_tpu.estimators.config import (
    FixedEffectCoordinateConfig,
    GameOptimizationConfig,
    RandomEffectCoordinateConfig,
    RegularizationConfig,
)
from photon_tpu.estimators.game_estimator import GameEstimator
from photon_tpu.obs.metrics import registry
from photon_tpu.obs.trace import get_spans, reset_tracer
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.types import OptimizerType, TaskType

ROW_BYTES = 16 * 4          # the benchmark's random-effect shard: d_re 16, float32
BUDGET = slab_budget_of(16 << 30)  # what GameEstimator gives on a 16 GiB chip
MAX_GEOMETRIES = 24         # stated: the Zipf cell's plan stays under two dozen programs


def zipf_counts(entities=8192, rows=1 << 22, exponent=1.0, seed=0):
    p = np.arange(1, entities + 1, dtype=np.float64) ** -exponent
    ids = np.random.default_rng(seed).choice(entities, size=rows, p=p / p.sum())
    return np.bincount(ids, minlength=entities)


def uniform_counts(entities, rows=1 << 22, seed=1):
    ids = np.random.default_rng(seed).integers(0, entities, size=rows)
    return np.bincount(ids, minlength=entities)


COUNT_VECTORS = {
    "zipf_8192": zipf_counts(),
    # Off the exponent the benchmark's cell draws: a flatter and a steeper tail.
    "zipf_8192_s0.8": zipf_counts(exponent=0.8),
    "zipf_8192_s1.2": zipf_counts(exponent=1.2),
    "uniform_users": uniform_counts(8192),
    "uniform_items": uniform_counts(512),
    "all_equal": np.full((1000,), 33),
    "one_giant": np.concatenate([[3_000_000], np.full((4095,), 40)]),
    "few_entities": np.array([5, 9, 17, 900]),
}


@pytest.mark.parametrize("name", sorted(COUNT_VECTORS))
def test_plan_covers_every_entity_once_within_its_bounds(name):
    counts = COUNT_VECTORS[name]
    plans = plan_blocks(counts, ROW_BYTES, slab_budget=BUDGET)
    members = np.concatenate([p.members for p in plans])
    assert np.array_equal(np.sort(members), np.arange(counts.size))
    for p in plans:
        assert p.lanes >= p.members.size
        assert p.n_max >= counts[p.members].max()       # every row has a slot
        assert p.n_max == bucket_dim(p.n_max)            # on the grid
        slab = p.lanes * p.n_max * ROW_BYTES
        # One entity is never cut: only a one-lane block may pass the budget.
        assert slab <= BUDGET or p.members.size == 1, (p.lanes, p.n_max)
    allocated = sum(p.lanes * p.n_max for p in plans)
    assert allocated / counts.sum() <= PLAN_PAD_CEILING
    assert len({(p.lanes, p.n_max) for p in plans}) <= MAX_GEOMETRIES


@pytest.mark.parametrize("name", ["zipf_8192", "zipf_8192_s0.8", "zipf_8192_s1.2"])
def test_plan_on_zipf_users_stays_under_the_merge_bound_and_35_percent(name):
    counts = COUNT_VECTORS[name]
    plans = plan_blocks(counts, ROW_BYTES, slab_budget=BUDGET)
    allocated = sum(p.lanes * p.n_max for p in plans)
    assert allocated / counts.sum() <= PLAN_MERGE_PAD_BOUND
    assert 1.0 - counts.sum() / allocated <= 0.35      # the cell's re_pad_rows_share
    # The giant user pads to its own level, never a bucket of 2048 lanes.
    top = max(plans, key=lambda p: p.n_max)
    assert top.members.size == 1 and top.lanes == 1
    assert top.n_max == bucket_dim(int(counts.max()))
    # Fewer programs than one a grid level: the thin levels were joined.
    assert len(plans) < len({bucket_dim(int(c)) for c in counts})


@pytest.mark.parametrize("name,n_maxes,max_lanes", [
    ("uniform_users", {512, 768}, 3072),
    ("uniform_items", {8192, 12288}, 3072),
])
def test_plan_on_even_counts_gives_the_shapes_of_the_quantile_layout(
        name, n_maxes, max_lanes):
    plans = plan_blocks(COUNT_VECTORS[name], ROW_BYTES, slab_budget=BUDGET)
    assert {p.n_max for p in plans} == n_maxes
    assert max(p.lanes for p in plans) <= max_lanes
    assert len(plans) <= 4                              # as many dispatches a pass, or fewer


def test_lane_dim_is_exact_to_16_and_within_an_eighth_above():
    assert [lane_dim(e) for e in (0, 1, 2, 7, 16)] == [1, 1, 2, 7, 16]
    for e in (17, 100, 2003, 2093, 4186, 8192, 100_000):
        assert e <= lane_dim(e) <= e * 1.125


def test_slab_budget_is_the_callers_and_none_cuts_nothing():
    assert slab_budget_of(16 << 30) == 1 << 27           # 1/128 of the device
    counts = COUNT_VECTORS["uniform_users"]
    uncut = plan_blocks(counts, ROW_BYTES)
    assert [(p.lanes, p.n_max) for p in uncut] == [(4608, 512), (4096, 768)]
    cut = plan_blocks(counts, ROW_BYTES, slab_budget=BUDGET)
    assert [(p.lanes, p.n_max) for p in cut] == [
        (2304, 512), (2304, 512), (2048, 768), (2048, 768)]
    # The builder asks no device: without a caller's budget it cuts nothing.
    ids = np.repeat(np.arange(8, dtype=np.int32), 40)
    x = np.ones((ids.size, 4), np.float32)
    assert len(_dataset(ids, x, x[:, 0], 8).blocks) == 1
    assert len(_dataset(ids, x, x[:, 0], 8, slab_budget=2 * 48 * 4 * 4).blocks) == 4


def test_plan_without_bucketing_allocates_exact_shapes():
    counts = COUNT_VECTORS["few_entities"]
    plans = plan_blocks(counts, ROW_BYTES, bucketed=False, slab_budget=BUDGET)
    for p in plans:
        assert p.lanes == p.members.size
        assert p.n_max == counts[p.members].max()


# ---- the dataset the plan builds ---------------------------------------------


def _zipf_data(entities=64, rows=8192, d_fix=8, d_re=4, seed=3, empty=2):
    """A small heavy-tailed GLMix data set; the last ``empty`` users own no
    row at all."""
    rng = np.random.default_rng(seed)
    live = entities - empty
    p = np.arange(1, live + 1, dtype=np.float64) ** -1.0
    ids = rng.permutation(entities)[rng.choice(live, size=rows, p=p / p.sum())]
    xf = rng.normal(size=(rows, d_fix)).astype(np.float32)
    xr = rng.normal(size=(rows, d_re)).astype(np.float32)
    xf[:, 0] = xr[:, 0] = 1.0
    w_fix = rng.normal(size=d_fix) / np.sqrt(d_fix)
    w_re = 0.5 * rng.normal(size=(entities, d_re))
    logits = xf @ w_fix + np.sum(xr * w_re[ids], axis=1)
    y = (rng.uniform(size=rows) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    return xf, xr, ids.astype(np.int32), y


def _dataset(ids, xr, y, entities, slab_budget=None, **kw):
    return build_random_effect_dataset(
        ids, xr, y, np.ones_like(y), entities,
        RandomEffectDataConfig(re_type="userId", feature_shard="per_user", **kw),
        slab_budget=slab_budget)


def test_every_row_lands_in_exactly_one_slot_and_padding_is_inert():
    _xf, xr, ids, y = _zipf_data()
    ds = _dataset(ids, xr, y, 64)
    seen = np.concatenate([np.asarray(b.sample_index).ravel() for b in ds.blocks])
    assert np.array_equal(np.sort(seen[seen >= 0]), np.arange(ids.size))
    lanes = np.concatenate([np.asarray(b.entity_idx) for b in ds.blocks])
    assert np.array_equal(np.sort(lanes[lanes >= 0]), np.unique(ids))
    samples = np.asarray(ds.lane_samples)
    assert samples.sum() == ids.size and np.all(samples[lanes < 0] == 0)
    offsets = jnp.arange(1.0, ids.size + 1.0)
    for b in ds.blocks:
        pad = np.asarray(b.sample_index) < 0
        assert np.all(np.asarray(b.weight)[pad] == 0)
        assert np.all(np.asarray(b.features)[pad] == 0)
        # The residual exchange: a padding slot gathers 0, a real one its row's.
        got = np.asarray(b.gather_offsets(offsets))
        assert np.all(got[pad] == 0)
        assert np.array_equal(got[~pad], np.asarray(b.sample_index)[~pad] + 1.0)
        assert not np.any(np.asarray(b.train_mask)[np.asarray(b.entity_idx) < 0])


def _fit(xf, xr, ids, y, entities):
    batch = GameBatch(
        label=jnp.asarray(y), offset=jnp.zeros(y.shape, jnp.float32),
        weight=jnp.ones(y.shape, jnp.float32),
        features={"global": jnp.asarray(xf), "per_user": jnp.asarray(xr)},
        entity_ids={"userId": jnp.asarray(ids)})
    estimator = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs=[
            FixedEffectCoordinateConfig("global", "global"),
            RandomEffectCoordinateConfig("per_user", "userId", "per_user")],
        num_iterations=2, intercept_indices={"global": 0, "per_user": 0},
        num_entities={"userId": entities})
    opt = GameOptimizationConfig(reg={
        "global": RegularizationConfig(weight=1.0),
        "per_user": RegularizationConfig(weight=1.0)})
    (result,) = estimator.fit(batch, optimization_configs=[opt])
    return result, batch


REFERENCE_CONFIG = dict(cd_passes=2, coordinates=[
    dict(id="global", type="fixed", intercept=0, l2=1.0),
    dict(id="per_user", type="random", intercept=0, l2=1.0)])


def test_fit_on_zipf_users_matches_the_ragged_reference():
    from benchmark.reference import glmix_ragged

    xf, xr, ids, y = _zipf_data()
    reset_tracer()
    def solves():  # 64 users: every block is under the lanes' bound
        return registry().counter(
            "re_block_solves_total", spd_solve="library",
            coordinate="per_user").value

    solves_before = solves()
    result, batch = _fit(xf, xr, ids, y, 64)
    ref = glmix_ragged.fit(
        REFERENCE_CONFIG, jnp.asarray(xf), {"per_user": jnp.asarray(xr)},
        {"per_user": jnp.asarray(ids)}, jnp.asarray(y), {"per_user": 64})
    w_fix = np.asarray(result.model.models["global"].model.coefficients.means)
    w_re = np.asarray(result.model.models["per_user"].coefficients)
    want_fix, want_re = np.asarray(ref["global"]), np.asarray(ref["per_user"])
    # Stated tolerances: the L-BFGS stopping rule leaves the fixed effect
    # ~1e-4 off the optimum, and the users' optima follow it.
    assert np.linalg.norm(w_fix - want_fix) <= 5e-4 * np.linalg.norm(want_fix)
    assert np.linalg.norm(w_re - want_re) <= 1e-3 * np.linalg.norm(want_re)
    # Users without rows keep zero, in the program and in the reference.
    empty = np.setdiff1d(np.arange(64), ids)
    assert empty.size == 2
    assert np.all(w_re[empty] == 0) and np.all(want_re[empty] == 0)
    # A user without rows scores nothing; every other row scores x·w.
    scores = np.asarray(result.model.models["per_user"].score(batch))
    assert np.allclose(scores, np.sum(xr * w_re[ids], axis=1), atol=1e-5)

    # What the build published: the spans and the plan's gauges.
    names = [s.name for s in get_spans()]
    for leaf in ("prepare/group/per_user", "prepare/group/per_user/plan",
                 "prepare/group/per_user/fill"):
        assert any(n.endswith(leaf) for n in names), leaf
    gauges = {r["metric"]: r["value"] for r in registry().snapshot()
              if r["labels"].get("coordinate") == "per_user"}
    ds = _dataset(ids, xr, y, 64)
    assert gauges["re_blocks"] == len(ds.blocks)
    # one dispatch a block and pass: the counter over the passes is the gauge
    assert solves() - solves_before == 2 * len(ds.blocks)
    assert gauges["re_block_geometries"] == len(
        {b.features.shape for b in ds.blocks})
    # The tracker weighs iterations by rows where it knows them.
    diag = result.tracker["per_user"][-1].diagnostics_dict()
    assert 1.0 <= diag["row_weighted_iterations"] <= diag["max_iterations"]


def quantile_blocks(counts, n_buckets, bucketed=True):
    """The layout before the planner, kept here as the parity reference:
    ``n_buckets`` quantile buckets of the row counts, each with the n_max of
    its largest member (one heavy user drags a whole bucket to its n_max)."""
    counts = np.asarray(counts, np.int64)
    n_buckets = max(1, min(int(n_buckets), len(np.unique(counts))))
    qs = np.quantile(counts, np.linspace(0, 1, n_buckets + 1)[1:], method="higher")
    qs = np.unique(qs.astype(np.int64))
    assigned = np.digitize(counts, qs, right=True)
    plans = []
    for b, n_max in enumerate(qs):
        members = np.flatnonzero(assigned == b)
        if members.size == 0:
            continue
        shape = (int(max(n_max, 1)), members.size)
        if bucketed:
            shape = tuple(bucket_dim(x) for x in shape)
        plans.append(BlockPlan(members, *shape))
    return plans


def test_planned_and_four_quantile_geometries_agree_to_solver_tolerance(monkeypatch):
    xf, xr, ids, y = _zipf_data(seed=4)
    planned, _ = _fit(xf, xr, ids, y, 64)

    built = []

    def four_quantiles(counts, row_bytes, bucketed=True, slab_budget=None):
        built.append(quantile_blocks(counts, 4, bucketed))
        return built[-1]

    monkeypatch.setattr(re_data, "plan_blocks", four_quantiles)
    quantile, _ = _fit(xf, xr, ids, y, 64)
    assert len(built) == 1 and len(built[0]) == 4

    def tables(result):
        return (np.asarray(result.model.models["global"].model.coefficients.means),
                np.asarray(result.model.models["per_user"].coefficients))

    (f_a, r_a), (f_b, r_b) = tables(planned), tables(quantile)
    assert np.linalg.norm(f_a - f_b) <= 5e-4 * np.linalg.norm(f_b)
    assert np.linalg.norm(r_a - r_b) <= 1e-3 * np.linalg.norm(r_b)


def _one_level_users(seed, entities=96):
    """Users of 37 to 46 rows, d = 6: one grid level (n_max 48), so only a
    ``slab_budget`` makes more than one block of them."""
    rng = np.random.default_rng(seed)
    ids = np.repeat(np.arange(entities, dtype=np.int32),
                    rng.integers(37, 47, size=entities))
    x = rng.normal(size=(ids.size, 6)).astype(np.float32)
    y = (rng.uniform(size=ids.size) < 0.5).astype(np.float32)
    return ids, x, y


def _per_user_batch(ids, x, y):
    return GameBatch(
        label=jnp.asarray(y), offset=jnp.zeros(y.shape, jnp.float32),
        weight=jnp.ones(y.shape, jnp.float32), features={"per_user": jnp.asarray(x)},
        entity_ids={"userId": jnp.asarray(ids)})


@pytest.mark.parametrize("parts", [2, 4])
def test_a_level_cut_by_the_slab_budget_solves_every_entity_as_uncut(parts):
    """A ``slab_budget`` cuts one grid level into ``parts`` blocks of one
    geometry: a pass then counts ``parts`` block solves, and every entity's
    coefficients are the uncut plan's to solver tolerance (entities are
    lanes, and no arithmetic crosses them)."""
    entities = 96
    ids, x, y = _one_level_users(seed=11, entities=entities)
    batch = _per_user_batch(ids, x, y)

    def train(coordinate_id, slab_budget):
        ds = _dataset(ids, x, y, entities, slab_budget=slab_budget)
        coord = RandomEffectCoordinate(
            coordinate_id=coordinate_id, dataset=ds,
            task=TaskType.LOGISTIC_REGRESSION,
            objective=GLMObjective(loss=LogisticLoss, l2_weight=0.5),
            optimizer_spec=OptimizerSpec(
                optimizer=OptimizerType.NEWTON, max_iter=25, tol=1e-9),
            solve_cache=SolveCache(donate=False))
        model, _ = coord.train(batch, None, None)
        solves = registry().counter(
            "re_block_solves_total", spd_solve="library",
            coordinate=coordinate_id).value
        return ds, np.asarray(model.coefficients), solves

    uncut_ds, want, uncut_solves = train(f"uncut_for_{parts}", None)
    assert len(uncut_ds.blocks) == 1 and uncut_solves == 1
    lanes = entities // parts
    cut_ds, got, cut_solves = train(
        f"cut_in_{parts}", lanes * 48 * 6 * 4)
    assert [b.features.shape for b in cut_ds.blocks] == [(lanes, 48, 6)] * parts
    assert cut_solves == parts
    assert np.all(np.any(want != 0, axis=1))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_a_gated_pass_compiles_no_scatter_or_solver_of_its_own():
    """With the active set on every pass scatters block by block, so the full
    first pass compiles what a gated pass, which dispatches another NUMBER of
    blocks, runs: all a gated pass may still compile is the tracker's two
    concatenations, once a block count it has not seen."""
    compiles = []

    def on_duration(event, _seconds, **_kw):
        if event.endswith("backend_compile_duration"):
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    entities = 96
    ids, x, y = _one_level_users(seed=7, entities=entities)
    x[ids % 3 != 0] = 0.0          # a cold cohort: retires after the first pass
    ds = _dataset(ids, x, y, entities, slab_budget=24 * 48 * 6 * 4)
    assert len(ds.blocks) == 4
    batch = _per_user_batch(ids, x, y)
    coord = RandomEffectCoordinate(
        coordinate_id="per_user", dataset=ds, task=TaskType.LOGISTIC_REGRESSION,
        objective=GLMObjective(loss=LogisticLoss, l2_weight=0.5),
        optimizer_spec=OptimizerSpec(
            optimizer=OptimizerType.NEWTON, max_iter=25, tol=1e-9),
        solve_cache=SolveCache(donate=True), active_set=True, convergence_tol=1e-4)
    model, in_pass, dispatched = None, [], []
    try:
        for it in range(4):
            coord.begin_cd_pass(it)
            before = len(compiles)
            model, _ = coord.train(batch, None, model)
            jax.block_until_ready(model.coefficients)
            in_pass.append(len(compiles) - before)
            dispatched.append(coord.last_active_set_stats["dispatched_blocks"])
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert dispatched[0] == 4 and min(dispatched[1:]) < 4     # it did compact
    assert sum(in_pass[1:]) <= 2 * len(set(dispatched[1:]) - {dispatched[0]})
