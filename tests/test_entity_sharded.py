"""Device-sharded GAME tests: bit-parity of the entity-sharded RE
coordinate across device counts, solve-cache zero-retrace under sharding,
train/serve shard-assignment identity through the consistent-hash ring, the
sharded serving hot store, and the fused whole-program pjit step.

conftest.py forces an 8-virtual-CPU-device backend, so every test here has
a real (if virtual) mesh to shard over. Parity across device counts is
asserted with ``np.array_equal`` (atol=0): the shard layout is FIXED at
S=8 regardless of device count, so every rung dispatches identical
programs on identical block geometry — only placement varies — and any
drift is a real bug, not float noise. Only the fused step's cross-mesh
comparison is allclose-level (its FE data-parallel gradient psum reorders
reductions with mesh size).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
from photon_tpu.algorithm.sharded_random_effect import (
    ShardedRandomEffectCoordinate,
)
from photon_tpu.algorithm.solve_cache import SolveCache
from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.index_map import EntityIndex
from photon_tpu.data.random_effect import (
    RandomEffectDataConfig,
    build_random_effect_dataset,
)
from photon_tpu.models.coefficients import Coefficients
from photon_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_tpu.models.glm import GeneralizedLinearModel
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.parallel.entity_shard import (
    DEFAULT_N_SHARDS,
    build_shard_plan,
    merge_shard_coefficients,
    shard_members,
)
from photon_tpu.serve import (
    HotColdEntityStore,
    ScoreRequest,
    ServeConfig,
    ServingEngine,
)
from photon_tpu.serve.routing import HashRing
from photon_tpu.types import OptimizerType, TaskType

E, D_RE = 96, 4


def make_workload(seed=7):
    """Ragged per-entity row counts — the general case."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(3, 24, size=E)
    eids = np.repeat(np.arange(E, dtype=np.int32), counts)
    n = eids.size
    Xr = rng.normal(size=(n, D_RE)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    w = np.ones(n, np.float32)
    offsets = (0.25 * np.sin(np.arange(n, dtype=np.float32))).astype(
        np.float32
    )
    return eids, Xr, y, w, offsets


def make_batch(eids, Xr, y, w):
    n = eids.size
    return GameBatch(
        label=jnp.asarray(y), offset=jnp.zeros(n, jnp.float32),
        weight=jnp.asarray(w), features={"re": jnp.asarray(Xr)},
        entity_ids={"userId": jnp.asarray(eids)},
    )


RE_CFG = RandomEffectDataConfig(
    re_type="userId", feature_shard="re",
    shape_bucketing=True, subspace_projection=False,
)
OBJ = GLMObjective(loss=LogisticLoss, l2_weight=0.5)
SPEC = OptimizerSpec(optimizer=OptimizerType.NEWTON, max_iter=3, tol=1e-9)


def run_sharded(devices, passes=3, cache=None, workload=None, **kw):
    eids, Xr, y, w, offsets = workload or make_workload()
    batch = make_batch(eids, Xr, y, w)
    cache = cache if cache is not None else SolveCache(donate=True)
    coord = ShardedRandomEffectCoordinate.build(
        coordinate_id="per_user",
        entity_ids=eids, features=Xr, label=y, weight=w,
        num_entities=E, config=RE_CFG,
        task=TaskType.LOGISTIC_REGRESSION, objective=OBJ,
        optimizer_spec=SPEC, devices=devices, solve_cache=cache, **kw,
    )
    model, marks = None, []
    off = jnp.asarray(offsets)
    for it in range(passes):
        coord.begin_cd_pass(it)
        m = cache.trace_mark()
        model, _ = coord.train(batch, off, model)
        marks.append(cache.traces_since(m))
    return coord, model, marks


# ---------------------------------------------------------------------------
# Bit-parity across device counts (the multichip contract)
# ---------------------------------------------------------------------------


def test_bit_parity_across_device_counts():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest should have forced 8 virtual devices"
    _, m1, marks1 = run_sharded(devs[:1])
    _, m2, marks2 = run_sharded(devs[:2])
    _, m8, marks8 = run_sharded(devs[:8])
    c1 = np.asarray(m1.coefficients)
    np.testing.assert_array_equal(c1, np.asarray(m2.coefficients))
    np.testing.assert_array_equal(c1, np.asarray(m8.coefficients))
    # Zero post-warmup retraces at every device count.
    assert marks1[1:] == [0, 0] and marks2[1:] == [0, 0] \
        and marks8[1:] == [0, 0]


def test_gated_bit_parity_across_device_counts():
    devs = jax.devices()
    _, m1, marks1 = run_sharded(devs[:1], active_set=True,
                                convergence_tol=1e-7)
    _, m8, marks8 = run_sharded(devs[:8], active_set=True,
                                convergence_tol=1e-7)
    np.testing.assert_array_equal(
        np.asarray(m1.coefficients), np.asarray(m8.coefficients)
    )
    assert marks1[-1] == 0 and marks8[-1] == 0


def test_out_of_core_bit_parity_across_device_counts():
    # budget=1 floors at each shard's largest block: every pass churns the
    # per-shard residency layer, and the coefficients must not notice.
    devs = jax.devices()
    c1, m1, _ = run_sharded(devs[:1], device_budget_bytes=1)
    c8, m8, marks8 = run_sharded(devs[:8], device_budget_bytes=1)
    np.testing.assert_array_equal(
        np.asarray(m1.coefficients), np.asarray(m8.coefficients)
    )
    assert marks8[-1] == 0
    assert all(st is not None for st in c8.residency_stats())


def test_blocks_actually_placed_across_devices():
    devs = jax.devices()
    c8, _, _ = run_sharded(devs[:8])
    placements = {
        list(b.entity_idx.devices())[0]
        for c in c8.shards for b in c.dataset.blocks
    }
    assert len(placements) == 8
    # Per-device busy accounting folds shard walls through the device map.
    busy = c8.device_busy_seconds(8)
    assert len(busy) == 8 and all(b > 0 for b in busy)
    assert sum(c8.last_shard_samples) == make_workload()[0].size


def test_sharded_matches_unsharded_coordinate():
    """The sharded coordinate solves the SAME per-entity problems as the
    plain single-table coordinate — allclose-level (per-shard bucket
    geometry differs from the global bucketing, which reorders padded-row
    reductions)."""
    eids, Xr, y, w, offsets = make_workload()
    batch = make_batch(eids, Xr, y, w)
    ds = build_random_effect_dataset(eids, Xr, y, w, E, RE_CFG)
    plain = RandomEffectCoordinate(
        coordinate_id="per_user", dataset=ds,
        task=TaskType.LOGISTIC_REGRESSION, objective=OBJ,
        optimizer_spec=SPEC, solve_cache=SolveCache(donate=True),
    )
    model_p = None
    off = jnp.asarray(offsets)
    for it in range(3):
        plain.begin_cd_pass(it)
        model_p, _ = plain.train(batch, off, model_p)
    _, model_s, _ = run_sharded(jax.devices()[:8])
    # Per-shard bucketing pads entities to different n_max than the global
    # bucketing, so per-entity reductions sum in a different order — a few
    # 1e-4-level ULP walks on converged Newton solves are expected.
    np.testing.assert_allclose(
        np.asarray(model_p.coefficients), np.asarray(model_s.coefficients),
        atol=1e-3, rtol=1e-3,
    )


def test_solve_cache_shared_across_device_counts_no_new_traces():
    """One jitted trace serves every device of a backend: after the
    1-device run warms the shared cache, the 8-device run over the same
    shard geometry compiles NOTHING new — the property that keeps the
    multichip ladder retrace-free without per-device cache keying."""
    cache = SolveCache(donate=True)
    _, _, marks1 = run_sharded(jax.devices()[:1], cache=cache)
    assert marks1[0] > 0  # cold cache did compile
    _, _, marks8 = run_sharded(jax.devices()[:8], cache=cache)
    assert marks8 == [0, 0, 0], marks8


# ---------------------------------------------------------------------------
# Shard plan: ring identity, merge exactness
# ---------------------------------------------------------------------------


def test_plan_ring_matches_explicit_ring():
    eidx = EntityIndex()
    for e in range(E):
        eidx.intern(f"user{e}")
    ring = HashRing(shard_members(8), vnodes=64, seed=0)
    p_default = build_shard_plan(E, 8, entity_index=eidx)
    p_ring = build_shard_plan(E, 8, entity_index=eidx, ring=ring)
    assert p_default.snapshot() == p_ring.snapshot()
    # Local index spaces are dense and disjoint.
    seen = set()
    for s in range(8):
        ents = p_default.entities_of(s)
        assert np.array_equal(
            p_default.local_of[ents], np.arange(ents.size)
        )
        seen.update(ents.tolist())
    assert seen == set(range(E))


def test_merge_shard_coefficients_is_exact():
    plan = build_shard_plan(E, DEFAULT_N_SHARDS)
    rng = np.random.default_rng(3)
    table = rng.normal(size=(E, D_RE)).astype(np.float32)
    shards = [table[plan.entities_of(s)] for s in range(plan.n_shards)]
    merged = merge_shard_coefficients(plan, shards, D_RE)
    np.testing.assert_array_equal(merged, table)


def test_device_of_is_contiguous_and_total():
    plan = build_shard_plan(E, 8)
    for n_dev in (1, 2, 4, 8):
        devs = [plan.device_of(s, n_dev) for s in range(8)]
        assert devs == sorted(devs)  # contiguous blocks
        assert set(devs) == set(range(n_dev))  # every device owns shards


# ---------------------------------------------------------------------------
# Sharded serving store + engine
# ---------------------------------------------------------------------------

D_FIX = 6


def make_model(seed=41):
    rng = np.random.default_rng(seed)
    w_fix = np.linspace(-1, 1, D_FIX).astype(np.float32)
    w_re = rng.normal(size=(E, D_RE)).astype(np.float32)
    return GameModel({
        "global": FixedEffectModel(
            GeneralizedLinearModel(
                Coefficients(np.asarray(w_fix)), TaskType.LOGISTIC_REGRESSION
            ),
            "shardA",
        ),
        "per_user": RandomEffectModel(
            np.asarray(w_re), "userId", "shardB", TaskType.LOGISTIC_REGRESSION
        ),
    }), w_re


def make_entity_index():
    eidx = EntityIndex()
    for e in range(E):
        eidx.intern(f"user{e}")
    return eidx


def score_via(store, users, xa, xb):
    from photon_tpu.estimators.game_transformer import GameTransformer

    n = len(users)
    slots = store.resolve("userId", [f"user{u}" for u in users])
    b = GameBatch(
        label=jnp.zeros(n, jnp.float32),
        offset=jnp.zeros(n, jnp.float32),
        weight=jnp.ones(n, jnp.float32),
        features={"shardA": jnp.asarray(xa), "shardB": jnp.asarray(xb)},
        entity_ids={"userId": jnp.asarray(slots, jnp.int32)},
    )
    b = jax.device_put(b, store.batch_sharding)
    return np.asarray(
        GameTransformer(store.scoring_model()).transform(b), np.float32
    )


def serving_inputs(seed=5, n=48):
    rng = np.random.default_rng(seed)
    users = rng.integers(0, E, size=n)
    xa = rng.normal(size=(n, D_FIX)).astype(np.float32)
    xb = rng.normal(size=(n, D_RE)).astype(np.float32)
    return users, xa, xb


def test_store_sharded_pinned_parity_and_layout():
    model, _ = make_model()
    eidx = make_entity_index()
    users, xa, xb = serving_inputs()
    ref = HotColdEntityStore(model, {"userId": eidx}, hot_bytes=1 << 30)
    sh = HotColdEntityStore(
        model, {"userId": eidx}, hot_bytes=1 << 30, device_shards=8
    )
    assert ref.group("userId").pinned and sh.group("userId").pinned
    np.testing.assert_array_equal(
        score_via(ref, users, xa, xb), score_via(sh, users, xa, xb)
    )
    # The hot table really is one sharded array over the 8-device mesh.
    tab = sh.group("userId").tables["per_user"]
    assert len(tab.sharding.device_set) == 8
    assert tab.shape[0] % 8 == 0
    st = sh.stats()["userId"]
    assert st["device_shards"] == 8 and st["shard_rows"] * 8 == tab.shape[0]


def test_store_sharded_unpinned_parity_and_demotion():
    model, w_re = make_model()
    eidx = make_entity_index()
    users, xa, xb = serving_inputs()
    ref = HotColdEntityStore(
        model, {"userId": eidx}, hot_bytes=1, min_hot_rows=64
    )
    sh = HotColdEntityStore(
        model, {"userId": eidx}, hot_bytes=1, min_hot_rows=64,
        device_shards=8,
    )
    assert not sh.group("userId").pinned
    sh.warm_uploads(64)
    np.testing.assert_array_equal(
        score_via(ref, users, xa, xb), score_via(sh, users, xa, xb)
    )
    # Churn the per-shard LRUs, then verify resident rows byte-exactly.
    rng = np.random.default_rng(9)
    users2 = rng.integers(0, E, size=48)
    slots = sh.resolve("userId", [f"user{u}" for u in users2])
    tab = np.asarray(sh.group("userId").tables["per_user"])
    for u, s in zip(users2, slots):
        np.testing.assert_array_equal(tab[s], w_re[u])


def test_store_shard_snapshot_matches_training_plan():
    model, _ = make_model()
    eidx = make_entity_index()
    sh = HotColdEntityStore(
        model, {"userId": eidx}, hot_bytes=1 << 30, device_shards=8
    )
    plan = build_shard_plan(E, 8, entity_index=eidx)
    assert plan.snapshot() == sh.shard_snapshot("userId")


def test_store_sharded_clone_with_delta():
    model, _ = make_model()
    eidx = make_entity_index()
    rng = np.random.default_rng(13)
    idx = np.array([3, 17], np.int64)
    rows = rng.normal(size=(2, D_RE)).astype(np.float32)
    # Pinned: the delta scatter goes through the shard permutation.
    sh = HotColdEntityStore(
        model, {"userId": eidx}, hot_bytes=1 << 30, device_shards=8
    )
    c1 = sh.clone_with_delta({"per_user": (idx, rows)})
    tab = np.asarray(c1.group("userId").tables["per_user"])
    perm = c1.group("userId").perm
    np.testing.assert_array_equal(tab[perm[3]], rows[0])
    np.testing.assert_array_equal(tab[perm[17]], rows[1])
    # Unpinned: the clone rebuilds per-shard LRUs and re-resolves.
    sh2 = HotColdEntityStore(
        model, {"userId": eidx}, hot_bytes=1, min_hot_rows=64,
        device_shards=8,
    )
    c2 = sh2.clone_with_delta({"per_user": (idx, rows)})
    slots = c2.resolve("userId", ["user3", "user17"])
    tab2 = np.asarray(c2.group("userId").tables["per_user"])
    np.testing.assert_array_equal(tab2[slots[0]], rows[0])
    np.testing.assert_array_equal(tab2[slots[1]], rows[1])


def test_engine_device_shards_end_to_end():
    model, _ = make_model()
    users, xa, xb = serving_inputs(n=32)
    eng = ServingEngine(
        model,
        entity_indexes={"userId": make_entity_index()},
        config=ServeConfig(
            max_batch_size=8, max_delay_ms=1.0, device_shards=8
        ),
    )
    try:
        reqs = [
            ScoreRequest(
                {"shardA": xa[i], "shardB": xb[i]},
                {"userId": f"user{users[i]}"},
            )
            for i in range(len(users))
        ]
        got = np.asarray(
            [np.float32(eng.submit(r).result(timeout=30)) for r in reqs],
            np.float32,
        )
        # Reference: the plain (unsharded) engine on the same requests.
        ref_eng = ServingEngine(
            model,
            entity_indexes={"userId": make_entity_index()},
            config=ServeConfig(max_batch_size=8, max_delay_ms=1.0),
        )
        try:
            want = np.asarray(
                [np.float32(ref_eng.submit(r).result(timeout=30))
                 for r in reqs],
                np.float32,
            )
        finally:
            ref_eng.close()
        np.testing.assert_array_equal(got, want)
        assert eng.retraces_since_warmup == 0, eng.stats()
        assert eng._state.store.device_shards == 8
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Fused whole-program step (pjit over the mesh)
# ---------------------------------------------------------------------------


def _fused_run(n_dev, S=8):
    from photon_tpu.data.batch import LabeledBatch
    from photon_tpu.optim.common import OptimizerConfig
    from photon_tpu.parallel.mesh import make_mesh
    from photon_tpu.parallel.train_step import (
        game_entity_sharded_train_step,
        stack_shard_blocks,
    )

    rng = np.random.default_rng(3)
    E_f, d_re, d_fe, rows_per = 64, 4, 8, 8
    n = E_f * rows_per
    eids = np.repeat(np.arange(E_f, dtype=np.int32), rows_per)[
        rng.permutation(n)
    ]
    Xf = rng.normal(size=(n, d_fe)).astype(np.float32)
    Xr = rng.normal(size=(n, d_re)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    w = np.ones(n, np.float32)

    plan = build_shard_plan(E_f, n_shards=S, seed=0)
    cfg = RandomEffectDataConfig(
        re_type="userId", feature_shard="re",
        shape_bucketing=True, subspace_projection=False,
    )
    blocks = []
    for s, se in enumerate(plan.shard_sample_entities(eids)):
        ds = build_random_effect_dataset(se, Xr, y, w, int(plan.counts[s]),
                                         cfg)
        blocks.append(ds.blocks[0])
    stacked = stack_shard_blocks(blocks)
    E_s = stacked.entity_idx.shape[1]
    assert stacked.features.shape[0] == S

    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    mesh = make_mesh(n_data=n_dev, devices=jax.devices()[:n_dev])
    step, place = game_entity_sharded_train_step(
        mesh, obj, obj,
        OptimizerConfig(max_iter=6, tol=1e-8),
        OptimizerConfig(max_iter=3, tol=1e-9),
    )
    fe = LabeledBatch(
        label=jnp.asarray(y), features=jnp.asarray(Xf),
        offset=jnp.zeros(n, jnp.float32), weight=jnp.asarray(w),
    )
    args = place(
        np.zeros(d_fe, np.float32), np.zeros((S, E_s, d_re), np.float32),
        fe, stacked, Xr,
        plan.shard_of[eids].astype(np.int32),
        plan.local_of[eids].astype(np.int32),
    )
    wf, rc = args[0], args[1]
    for _ in range(2):
        wf, rc, scores, fe_evals, visits = step(wf, rc, *args[2:])
    jax.block_until_ready(rc)
    return (np.asarray(wf), np.asarray(rc), np.asarray(scores),
            int(np.asarray(visits)))


def test_fused_step_runs_sharded_and_consistent():
    w1, rc1, sc1, v1 = _fused_run(1)
    w8, rc8, sc8, v8 = _fused_run(8)
    # Visit counts track FE L-BFGS evals, which can differ by a line-search
    # step across mesh sizes (psum reduction reorder) — both must be live.
    assert v1 > 0 and v8 > 0
    # Cross-mesh consistency is allclose-level: the FE gradient psum
    # reorders reductions with mesh size (documented in train_step.py).
    np.testing.assert_allclose(w1, w8, atol=1e-4)
    np.testing.assert_allclose(rc1, rc8, atol=1e-3)
    np.testing.assert_allclose(sc1, sc8, atol=1e-3)


def test_stack_shard_blocks_rejects_mismatched_geometry():
    from photon_tpu.parallel.train_step import stack_shard_blocks

    rng = np.random.default_rng(1)
    eids = np.repeat(np.arange(8, dtype=np.int32), 4)
    Xr = rng.normal(size=(32, D_RE)).astype(np.float32)
    y = np.zeros(32, np.float32)
    w = np.ones(32, np.float32)
    cfg = RandomEffectDataConfig(
        re_type="userId", feature_shard="re",
        shape_bucketing=True, subspace_projection=False,
    )
    a = build_random_effect_dataset(eids, Xr, y, w, 8, cfg).blocks[0]
    # 6 rows/entity → different n_max than a's 4 rows/entity.
    eids_b = np.repeat(np.arange(4, dtype=np.int32), 6)
    Xr_b = rng.normal(size=(24, D_RE)).astype(np.float32)
    b = build_random_effect_dataset(
        eids_b, Xr_b, np.zeros(24, np.float32), np.ones(24, np.float32),
        4, cfg,
    ).blocks[0]
    with pytest.raises(ValueError):
        stack_shard_blocks([a, b])
