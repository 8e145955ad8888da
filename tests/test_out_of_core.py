"""Out-of-core random-effect training (algorithm/re_store.py + the shared
residency core in data/residency.py).

The headline contract is BIT parity: a budget-constrained run uploads
blocks through the ingest pipeline, evicts under LRU pressure, and still
produces coefficients that are ``np.array_equal`` to the fully-resident
run's — because warm starts gather from the frozen previous-pass host
table and f32 device→host round-trips are lossless. Everything else here
guards the operational envelope: deterministic eviction sequences, zero
post-warmup retraces, the resident-bytes gauge staying under the
(effective) budget, memmap spill, and the config combinations the store
refuses.
"""

import logging

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
from photon_tpu.algorithm.re_store import (
    ReDeviceStore,
    block_device_cost,
    host_entity_block,
)
from photon_tpu.algorithm.solve_cache import SolveCache
from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.random_effect import (
    RandomEffectDataConfig,
    build_random_effect_dataset,
)
from photon_tpu.data.residency import ByteBudgetLru
from photon_tpu.obs.metrics import registry
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.types import (
    OptimizerType,
    TaskType,
    VarianceComputationType,
)

E, D = 96, 6
PASSES = 4

_rng = np.random.default_rng(7)
_counts = _rng.integers(37, 47, size=E)
EIDS = np.repeat(np.arange(E, dtype=np.int32), _counts)
N = EIDS.size
X = _rng.normal(size=(N, D)).astype(np.float32)
# A cold cohort (two thirds of entities see all-zero features) converges in
# one pass — the active-set variant then retires those blocks early.
X[EIDS % 3 != 0] = 0.0
Y = (_rng.uniform(size=N) < 0.5).astype(np.float32)
W = np.ones(N, np.float32)

CFG = RandomEffectDataConfig(
    re_type="userId", feature_shard="re", shape_bucketing=True
)
BATCH = GameBatch(
    label=jnp.asarray(Y), offset=jnp.zeros(N, jnp.float32),
    weight=jnp.asarray(W), features={"re": jnp.asarray(X)},
    entity_ids={"userId": jnp.asarray(EIDS)},
)
SPEC = OptimizerSpec(optimizer=OptimizerType.NEWTON, max_iter=25, tol=1e-9)


# One (24, 48, D) slab: cuts the one grid level the counts share into four
# same-geometry blocks, so a device budget has blocks to evict.
SLAB_BUDGET = 24 * 48 * D * 4


def _dataset():
    return build_random_effect_dataset(
        EIDS, X, Y, W, E, CFG, slab_budget=SLAB_BUDGET
    )


def _footprint():
    return sum(block_device_cost(b) for b in _dataset().blocks)


def _run(budget, active_set=False, spill_dir=None, passes=PASSES):
    cache = SolveCache()
    coord = RandomEffectCoordinate(
        coordinate_id="per_user", dataset=_dataset(),
        task=TaskType.LOGISTIC_REGRESSION,
        objective=GLMObjective(loss=LogisticLoss, l2_weight=0.5),
        optimizer_spec=SPEC, solve_cache=cache,
        active_set=active_set, convergence_tol=1e-4,
        device_budget_bytes=budget, device_spill_dir=spill_dir,
    )
    model = None
    warm_mark = None
    for it in range(passes):
        coord.begin_cd_pass(it)
        model, _stats = coord.train(BATCH, None, model)
        if it == 0:
            warm_mark = cache.trace_mark()
    return model, coord, cache.traces_since(warm_mark)


@pytest.fixture(scope="module")
def ref_run():
    return _run(None)


@pytest.fixture(scope="module")
def ooc_run():
    return _run(_footprint() // 4)


# ---------------------------------------------------------------------------
# Residency core (shared with serve/store.py — see data/residency.py)
# ---------------------------------------------------------------------------


def test_byte_budget_lru_semantics():
    evicted = []
    lru = ByteBudgetLru(100, on_evict=evicted.append)
    assert lru.admit("a", 40) == [] and lru.admit("b", 40) == []
    assert lru.resident_bytes == 80 and lru.peak_bytes == 80
    # LRU order decides the victim; touch refreshes recency.
    assert lru.touch("a")
    assert lru.admit("c", 40) == ["b"]
    assert evicted == ["b"] and lru.eviction_log == ["b"]
    assert lru.resident == ["a", "c"] and lru.evictions == 1
    # Protected keys are skipped over for eviction.
    assert lru.admit("d", 40, protected={"a", "c"}) == []
    assert lru.resident_bytes == 120  # floor admission ran over budget
    # would_fit: only protected bytes in the way → wait; nothing protected
    # resident → floor admission applies and it always "fits".
    assert not lru.would_fit(50, protected={"a", "c", "d"})
    assert lru.would_fit(50, protected=())
    # discard is an uncounted release; evict counts and logs.
    assert lru.discard("d") and lru.evictions == 1
    assert lru.evict("c") and lru.eviction_log == ["b", "c"]
    assert not lru.evict("c") and not lru.discard("zzz")
    # Re-admitting a resident key refreshes recency, evicts nothing.
    assert lru.admit("a", 40) == [] and lru.resident == ["a"]


def test_host_entity_block_memmaps_under_spill_dir(tmp_path):
    block = _dataset().blocks[0]
    hb = host_entity_block(block, str(tmp_path), 0)
    assert isinstance(hb.features, np.memmap)
    np.testing.assert_array_equal(
        np.asarray(hb.features), np.asarray(block.features)
    )
    assert any(tmp_path.iterdir())  # the .npy spill files exist


# ---------------------------------------------------------------------------
# Bit parity + operational envelope
# ---------------------------------------------------------------------------


def test_ooc_bit_parity_with_fully_resident(ref_run, ooc_run):
    ref_model, _, ref_post = ref_run
    ooc_model, coord, ooc_post = ooc_run
    st = coord.last_residency_stats
    # The keystone: not "close" — EQUAL, bit for bit.
    np.testing.assert_array_equal(
        np.asarray(ref_model.coefficients), np.asarray(ooc_model.coefficients)
    )
    np.testing.assert_array_equal(
        np.asarray(ref_model.score(BATCH)), np.asarray(ooc_model.score(BATCH))
    )
    # The budget actually constrained the run (quarter footprint ⇒ waves of
    # evictions), and the working set never exceeded the effective budget.
    assert st["evictions"] > 0
    assert st["footprint_bytes"] >= 4 * st["budget_bytes"]
    assert st["peak_bytes"] <= st["effective_budget_bytes"]
    # Zero retraces after warm-up: the solve cache never compiled a new
    # executable past pass 0, upload churn notwithstanding.
    assert ref_post == 0 and ooc_post == 0


def test_ooc_gauges_published(ooc_run):
    _, coord, _ = ooc_run
    st = coord.last_residency_stats
    g = registry().find("re_device_resident_bytes", coordinate="per_user")
    assert g is not None
    peak = registry().find(
        "re_device_resident_bytes_peak", coordinate="per_user"
    )
    assert peak is not None and peak.value <= st["effective_budget_bytes"]
    budget = registry().find("re_device_budget_bytes", coordinate="per_user")
    assert budget is not None and budget.value == st["effective_budget_bytes"]
    # Pipeline telemetry rode along: the upload/download stages were timed.
    assert {"h2d", "d2h"} <= set(st["pipeline"]["stages"])


def test_ooc_eviction_sequence_deterministic(ooc_run):
    _, coord_a, _ = ooc_run
    _, coord_b, _ = _run(_footprint() // 4)
    a, b = coord_a.last_residency_stats, coord_b.last_residency_stats
    assert a["eviction_log"] == b["eviction_log"] and a["evictions"] > 0
    assert a["uploads"] == b["uploads"]
    assert a["pass_evictions"] == b["pass_evictions"]


def test_ooc_active_set_retires_converged_blocks(ref_run):
    ref_gated, _, _ = _run(None, active_set=True)
    ooc_gated, coord, post = _run(_footprint() // 4, active_set=True)
    st = coord.last_residency_stats
    np.testing.assert_array_equal(
        np.asarray(ref_gated.coefficients), np.asarray(ooc_gated.coefficients)
    )
    assert post == 0
    # The cold cohort converges in pass 1; retiring those blocks shrinks the
    # later passes' working set, so eviction pressure collapses after the
    # first gated pass (the residency policy composes with the active set).
    assert st["evictions"] > 0
    assert sum(st["pass_evictions"][2:]) <= st["pass_evictions"][0]
    # Gating also cuts upload traffic: converged blocks stop riding the
    # pipeline entirely, so the gated run uploads less than the ungated one.
    ungated = _run(_footprint() // 4)[1].last_residency_stats
    assert st["uploads"] < ungated["uploads"]


def test_ooc_store_retire_evicts_unprotected_resident_blocks():
    blocks = _dataset().blocks
    store = ReDeviceStore(blocks, sum(block_device_cost(b) for b in blocks),
                          "retire_test")
    w0 = np.zeros((blocks[0].num_entities, blocks[0].dim), np.float32)
    store.begin_pass(0)
    store.acquire(0, blocks[0], w0, cacheable=True)
    store.release(0, cacheable=True)
    # Not resident → no-op; resident-but-protected → kept; resident → drop.
    assert store.retire([99]) == 0
    store.acquire(0, blocks[0], w0, cacheable=True)  # re-protects key 0
    assert store.retire([0]) == 0
    store.release(0, cacheable=True)
    assert store.retire([0]) == 1
    retired = registry().find("re_store_retired_total",
                              coordinate="retire_test")
    assert retired is not None and retired.value == 1
    assert store.lru.eviction_log == [0]
    store.end_pass()


def test_ooc_memmap_spill_parity(ref_run, tmp_path):
    ref_model, _, _ = ref_run
    ooc_model, coord, post = _run(
        _footprint() // 4, spill_dir=str(tmp_path)
    )
    np.testing.assert_array_equal(
        np.asarray(ref_model.coefficients), np.asarray(ooc_model.coefficients)
    )
    assert post == 0 and coord.last_residency_stats["evictions"] > 0
    assert any(tmp_path.iterdir())  # block data really lives on disk


def test_ooc_budget_floors_at_largest_block():
    blocks = _dataset().blocks
    store = ReDeviceStore(blocks, 1, "floor_test")
    assert store.effective_budget == max(block_device_cost(b) for b in blocks)
    assert store.budget == 1


# ---------------------------------------------------------------------------
# Config guards
# ---------------------------------------------------------------------------


def _coord_kwargs(**over):
    kw = dict(
        coordinate_id="per_user", dataset=_dataset(),
        task=TaskType.LOGISTIC_REGRESSION,
        objective=GLMObjective(loss=LogisticLoss, l2_weight=0.5),
        optimizer_spec=SPEC, solve_cache=SolveCache(),
        device_budget_bytes=1 << 20,
    )
    kw.update(over)
    return kw


def test_ooc_projected_dataset_falls_back_fully_resident(caplog):
    cfg = RandomEffectDataConfig(
        re_type="userId", feature_shard="re",
        shape_bucketing=True, subspace_projection=True,
    )
    ds = build_random_effect_dataset(EIDS, X, Y, W, E, cfg)
    assert ds.projected
    with caplog.at_level(logging.WARNING, logger="photon_tpu"):
        coord = RandomEffectCoordinate(**_coord_kwargs(dataset=ds))
    assert coord._store is None  # fully resident: the budget was ignored
    assert any("fully resident" in r.message for r in caplog.records)


def test_ooc_rejects_pearson_ratio():
    cfg = RandomEffectDataConfig(
        re_type="userId", feature_shard="re",
        shape_bucketing=True, features_to_samples_ratio=0.5,
    )
    ds = build_random_effect_dataset(EIDS, X, Y, W, E, cfg)
    with pytest.raises(ValueError, match="features_to_samples_ratio"):
        RandomEffectCoordinate(**_coord_kwargs(dataset=ds))


def test_ooc_rejects_variance_computation():
    with pytest.raises(ValueError, match="variance"):
        RandomEffectCoordinate(
            **_coord_kwargs(compute_variance=VarianceComputationType.SIMPLE)
        )
