"""Convergence-gated active-set random-effect passes (ISSUE 4): repack-plan
and block-compaction correctness, gated-vs-full objective parity (dense and
projected), zero-retrace reuse of cached executables under compaction, and
per-pass active-set accounting/reset behavior."""

import numpy as np
import jax.numpy as jnp
import pytest

from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
from photon_tpu.algorithm.solve_cache import SolveCache
from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.random_effect import (
    RandomEffectDataConfig,
    build_random_effect_dataset,
    compact_entity_blocks,
    pack_into_sizes,
)
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.types import OptimizerType, TaskType

E = 96


def _cold_cohort_problem(frac_cold=3, d=6, seed=7, cold_level=False):
    """Logistic problem where every entity whose id is NOT a multiple of
    ``frac_cold`` has ALL-ZERO random-effect features: the ridge solve
    returns exactly w=0 for those entities every pass, so their coefficient
    delta is exactly 0 and they retire from the active set deterministically
    at the first gated pass.

    Sample counts sit in ONE bucket window (37..46 → n_max bucket 48), so
    the block plan under ``_dataset``'s slab budget yields several
    SAME-geometry blocks — the regime where the active-set repack actually
    compacts (a geometry group with a single block can only fall back to
    identity dispatch, never shrink). ``cold_level`` gives the cold entities
    20..23 rows (n_max bucket 24): an all-cold block of their own without any
    budget, which a projected dataset needs because the budget holds dense
    blocks alone."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(37, 47, size=E)
    if cold_level:
        cold = np.arange(E) % frac_cold != 0
        counts[cold] = rng.integers(20, 24, size=int(cold.sum()))
    eids = np.repeat(np.arange(E, dtype=np.int32), counts)
    n = eids.size
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[eids % frac_cold != 0] = 0.0
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    w = np.ones(n, np.float32)
    return eids, X, y, w


def _dataset(eids, X, y, w, projected=False):
    # One (E / 4, 48, d) slab as the budget: a dense data set's one grid level
    # of 48 rows is cut into four same-geometry blocks.
    return build_random_effect_dataset(
        eids, X, y, w, E,
        RandomEffectDataConfig(
            re_type="userId", feature_shard="re",
            shape_bucketing=True, subspace_projection=projected,
        ),
        slab_budget=(E // 4) * 48 * X.shape[1] * X.dtype.itemsize,
    )


def _batch(eids, X, y, w):
    return GameBatch(
        label=jnp.asarray(y),
        offset=jnp.zeros(y.shape[0], jnp.float32),
        weight=jnp.asarray(w),
        features={"re": jnp.asarray(X)},
        entity_ids={"userId": jnp.asarray(eids)},
    )


def _coordinate(ds, cache, active_set=False, tol=1e-4, **spec_kw):
    spec_kw.setdefault("max_iter", 25)
    spec_kw.setdefault("tol", 1e-9)
    spec = OptimizerSpec(optimizer=OptimizerType.NEWTON, **spec_kw)
    return RandomEffectCoordinate(
        coordinate_id="per_user",
        dataset=ds,
        task=TaskType.LOGISTIC_REGRESSION,
        objective=GLMObjective(loss=LogisticLoss, l2_weight=0.5),
        optimizer_spec=spec,
        solve_cache=cache,
        active_set=active_set,
        convergence_tol=tol,
    )


def _run_passes(coord, batch, passes):
    """CD-style pass loop over a single coordinate (zero residual), driving
    the same begin_cd_pass/train protocol CoordinateDescent uses."""
    model, stats = None, []
    for it in range(passes):
        coord.begin_cd_pass(it)
        model, _ = coord.train(batch, None, model)
        stats.append(coord.last_active_set_stats)
    return model, stats


def _objective(model, batch, y, w):
    total = np.asarray(model.score(batch))
    return float(np.mean(w * np.logaddexp(0.0, -(2.0 * y - 1.0) * total)))


# ---------------------------------------------------------------- pack plan


def test_pack_into_sizes_plans_from_allowed_set_only():
    assert pack_into_sizes(10, [12, 24]) == [12]
    assert pack_into_sizes(13, [12, 24]) == [24]
    assert pack_into_sizes(25, [12, 24]) == [24, 12]  # 24 first, 1 left
    assert pack_into_sizes(60, [12, 24]) == [24, 24, 12]
    # Exhausts via the largest size when nothing single fits.
    plan = pack_into_sizes(100, [12])
    assert plan == [12] * 9 and sum(plan) >= 100
    with pytest.raises(ValueError):
        pack_into_sizes(5, [])


# ----------------------------------------------------------- block repack


def test_compact_entity_blocks_src_maps_and_padding():
    """The compacted block carries exactly the kept rows (in block, row
    order), its padding tail is inert (entity_idx −1, weight 0,
    sample_index −1), and the src maps point each compacted row back at
    its source (block, row) — −1 on padding."""
    eids, X, y, w = _cold_cohort_problem()
    ds = _dataset(eids, X, y, w)
    blocks = [b for b in ds.blocks if b.n_max == ds.blocks[0].n_max]
    assert blocks, "need at least one geometry group"
    valid = [np.asarray(b.entity_idx) >= 0 for b in blocks]
    # Keep every third valid row; bucket-padding rows stay excluded.
    keep = [v & (np.arange(v.size) % 3 == 0) for v in valid]
    total = int(sum(k.sum() for k in keep))
    assert total > 0

    out = compact_entity_blocks(
        blocks, keep, allowed_sizes=[b.num_entities for b in blocks]
    )
    assert out, "non-empty keep must produce compacted blocks"
    rows_seen = 0
    for block_c, sb, sr in out:
        assert block_c.num_entities == len(sb) == len(sr)
        real = sb >= 0
        # Padding tail: −1 src maps and inert rows.
        np.testing.assert_array_equal(sb[~real], -1)
        np.testing.assert_array_equal(sr[~real], -1)
        eidx_c = np.asarray(block_c.entity_idx)
        np.testing.assert_array_equal(eidx_c[~real], -1)
        assert not np.asarray(block_c.train_mask)[~real].any()
        assert float(np.asarray(block_c.weight)[~real].sum()) == 0.0
        np.testing.assert_array_equal(
            np.asarray(block_c.sample_index)[~real], -1
        )
        # Real rows: every field equals the (src_block, src_row) source.
        for j in np.flatnonzero(real):
            src = blocks[sb[j]]
            assert keep[sb[j]][sr[j]], "src map points at a non-kept row"
            assert eidx_c[j] == int(np.asarray(src.entity_idx)[sr[j]])
            np.testing.assert_array_equal(
                np.asarray(block_c.features)[j],
                np.asarray(src.features)[sr[j]],
            )
            np.testing.assert_array_equal(
                np.asarray(block_c.sample_index)[j],
                np.asarray(src.sample_index)[sr[j]],
            )
        rows_seen += int(real.sum())
    assert rows_seen == total
    # Compacted sizes come from the allowed set only (zero-retrace shapes).
    allowed = {b.num_entities for b in blocks}
    assert {o[0].num_entities for o in out} <= allowed
    # Bucket-padding source rows (entity_idx −1) can never be in a keep mask
    # produced by the coordinate: asserting here that none leaked through.
    for block_c, sb, _sr in out:
        assert (np.asarray(block_c.entity_idx)[sb >= 0] >= 0).all()


def test_compact_entity_blocks_rejects_mixed_geometry():
    # Bimodal counts (5..6 vs 37..46) land in different n_max buckets.
    rng = np.random.default_rng(3)
    counts = np.where(
        np.arange(E) % 4 != 0,  # 3/4 small: grid levels 6 and 48
        rng.integers(5, 7, size=E),
        rng.integers(37, 47, size=E),
    )
    eids = np.repeat(np.arange(E, dtype=np.int32), counts)
    n = eids.size
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    w = np.ones(n, np.float32)
    ds = _dataset(eids, X, y, w)
    geoms = {(b.n_max, b.dim) for b in ds.blocks}
    assert len(geoms) >= 2, f"expected mixed geometries, got {geoms}"
    keep = [np.asarray(b.entity_idx) >= 0 for b in ds.blocks]
    with pytest.raises(ValueError, match="same-geometry"):
        compact_entity_blocks(ds.blocks, keep)


def test_compact_entity_blocks_empty_keep_is_empty():
    eids, X, y, w = _cold_cohort_problem()
    ds = _dataset(eids, X, y, w)
    blocks = [b for b in ds.blocks if b.n_max == ds.blocks[0].n_max]
    keep = [np.zeros(b.num_entities, bool) for b in blocks]
    assert compact_entity_blocks(blocks, keep) == []


# ------------------------------------------------- gated-vs-full parity


def test_dense_gated_vs_full_parity_and_skips():
    """3 CD passes gated vs full: final objective parity at rtol 1e-5, the
    cold cohort is skipped from pass 2 on, and cold entities keep exactly
    zero coefficients."""
    eids, X, y, w = _cold_cohort_problem()
    batch = _batch(eids, X, y, w)
    ds = _dataset(eids, X, y, w)

    m_full, _ = _run_passes(
        _coordinate(ds, SolveCache(donate=True), active_set=False),
        batch, 3,
    )
    m_gated, stats = _run_passes(
        _coordinate(ds, SolveCache(donate=True), active_set=True),
        batch, 3,
    )

    of = _objective(m_full, batch, y, w)
    og = _objective(m_gated, batch, y, w)
    assert abs(og - of) / max(abs(of), 1e-30) <= 1e-5

    # Pass 1 dispatches everything; every later pass skips the cold cohort.
    n_cold = int(np.sum(np.arange(E) % 3 != 0))
    assert stats[0]["entities_skipped"] == 0
    for s in stats[1:]:
        assert s["entities_skipped"] >= n_cold > 0
        assert s["entities_active"] + s["entities_skipped"] == E
        assert s["dispatched_entity_alloc"] < s["full_entity_alloc"]
    # Cold entities' models are exactly zero in both variants.
    cold = np.arange(E) % 3 != 0
    np.testing.assert_array_equal(
        np.asarray(m_gated.coefficients)[cold], 0.0
    )


def test_projected_whole_block_skip_parity():
    """Projected blocks gate whole-block (content-defined col_map widths
    cannot merge without a retrace): an all-cold geometry converges its
    blocks entirely, later passes skip them, and the final objective still
    matches the full run at rtol 1e-5."""
    eids, X, y, w = _cold_cohort_problem(cold_level=True)
    batch = _batch(eids, X, y, w)
    ds = _dataset(eids, X, y, w, projected=True)
    assert ds.projected and len(ds.blocks) == 2

    m_full, _ = _run_passes(
        _coordinate(ds, SolveCache(donate=True), active_set=False),
        batch, 3,
    )
    m_gated, stats = _run_passes(
        _coordinate(ds, SolveCache(donate=True), active_set=True),
        batch, 3,
    )
    of = _objective(m_full, batch, y, w)
    og = _objective(m_gated, batch, y, w)
    assert abs(og - of) / max(abs(of), 1e-30) <= 1e-5
    # From pass 2 on the warm solves converge in place → whole blocks drop
    # out of the dispatch list.
    assert stats[-1]["entities_skipped"] > 0
    assert stats[-1]["dispatched_blocks"] < stats[0]["dispatched_blocks"]


# ------------------------------------------------ zero-retrace compaction


def test_compacted_blocks_reuse_cached_executables():
    """Compaction across 3 CD passes lands exclusively on executables
    compiled during the full first pass: the trace counter stays at one per
    (bucket, config) key and equals the non-gated run's. (The dispatch path
    itself asserts via SolveCache.expect_cached — a retrace inside a gated
    pass raises.) The cold cohort interleaves with warm entities in every
    block, so the pass-2 masks are PARTIAL per block and the repack merges
    survivors across blocks."""
    eids, X, y, w = _cold_cohort_problem()
    batch = _batch(eids, X, y, w)
    ds = _dataset(eids, X, y, w)
    assert len({(b.n_max, b.dim) for b in ds.blocks}) == 1
    assert len(ds.blocks) >= 3

    cache_full = SolveCache(donate=True)
    _run_passes(
        _coordinate(ds, cache_full, active_set=False), batch, 3
    )
    cache = SolveCache(donate=True)
    _, stats = _run_passes(
        _coordinate(ds, cache, active_set=True), batch, 3
    )
    assert cache.stats.traces == cache_full.stats.traces
    # Pass 2 actually compacted: fewer rows dispatched than allocated, onto
    # fewer blocks, all of allowed (already-compiled) sizes.
    s2 = stats[1]
    assert s2["entities_skipped"] > 0
    assert s2["dispatched_entity_alloc"] < s2["full_entity_alloc"]
    assert s2["dispatched_blocks"] < len(ds.blocks)
    # Every gated dispatch beyond the traces was a cache hit.
    assert cache.stats.hits == cache.stats.calls - cache.stats.traces


# ------------------------------------------------------- state & reset


def test_begin_cd_pass_resets_active_set_state():
    eids, X, y, w = _cold_cohort_problem()
    batch = _batch(eids, X, y, w)
    ds = _dataset(eids, X, y, w)
    coord = _coordinate(ds, SolveCache(donate=True), active_set=True)

    model, _ = _run_passes(coord, batch, 2)
    assert coord._pending_masks is not None
    assert coord.last_active_set_stats["cd_pass"] == 1

    # A NEW CD run (pass index 0) must forget the previous run's masks —
    # pass 1 of the new run dispatches everything again.
    coord.begin_cd_pass(0)
    assert coord._pending_masks is None
    model2, _ = coord.train(batch, None, model)
    assert coord.last_active_set_stats["entities_skipped"] == 0
    # Mid-run boundaries (non-zero pass index) keep the pending masks.
    coord.begin_cd_pass(1)
    assert coord._pending_masks is not None
