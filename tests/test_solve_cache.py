"""Compiled-solver cache (algorithm/solve_cache.py): retrace-count
regression, shape bucketing, bucketed-vs-exact parity, warm-start donation
safety, and the sync-free CoordinateDescent.run(profile=...) contract."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_tpu.algorithm.random_effect import (
    RandomEffectCoordinate,
    _solve_block,
)
from photon_tpu.algorithm.solve_cache import SolveCache
from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.random_effect import (
    RandomEffectDataConfig,
    build_random_effect_dataset,
    bucket_dim,
)
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.common import OptimizerConfig
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.types import OptimizerType, TaskType

E, D = 48, 5
rng = np.random.default_rng(11)


def _clustered_problem(dtype=np.float32):
    """Entity sample counts in one bucket window, sized so the block plan
    under ``_dataset``'s slab budget yields THREE 12-entity blocks whose EXACT
    (E, n_max) differ —
    (12,40,·), (12,43,·), (12,46,·) — but whose bucketed shapes coincide at
    (12, 48, ·). The last 12 of the E entities carry no data (their rows
    stay zero in every trained model)."""
    counts = np.concatenate([
        np.repeat([37, 40], 6), np.repeat([43, 46], 12), np.zeros(12, int)
    ])
    eids = np.repeat(np.arange(E, dtype=np.int32), counts)
    n = eids.size
    X = rng.normal(size=(n, D)).astype(dtype)
    X[:, 0] = 1.0
    y = (rng.uniform(size=n) < 0.5).astype(dtype)
    w = np.ones(n, dtype)
    return eids, X, y, w


def _dataset(eids, X, y, w, bucketed=True):
    # The budget of one (12, 48, bucket_dim(D)) slab cuts the one grid level
    # the counts share into three blocks.
    return build_random_effect_dataset(
        eids, X, y, w, E,
        RandomEffectDataConfig(
            re_type="userId", feature_shard="re",
            shape_bucketing=bucketed, subspace_projection=False,
        ),
        slab_budget=12 * 48 * bucket_dim(D) * X.dtype.itemsize,
    )


def _batch(eids, X, y, w):
    return GameBatch(
        label=jnp.asarray(y),
        offset=jnp.zeros(y.shape[0], jnp.asarray(y).dtype),
        weight=jnp.asarray(w),
        features={"re": jnp.asarray(X)},
        entity_ids={"userId": jnp.asarray(eids)},
    )


def _coordinate(ds, cache, **spec_kw):
    spec = OptimizerSpec(
        optimizer=OptimizerType.NEWTON, max_iter=25, tol=1e-9, **spec_kw
    )
    return RandomEffectCoordinate(
        coordinate_id="per_user",
        dataset=ds,
        task=TaskType.LOGISTIC_REGRESSION,
        objective=GLMObjective(loss=LogisticLoss, l2_weight=0.5,
                               intercept_index=0),
        optimizer_spec=spec,
        solve_cache=cache,
    )


def test_bucket_dim_grid():
    # Powers of two ∪ 1.5× powers of two, ratio ≤ 4/3, identity below 3.
    assert [bucket_dim(x) for x in [1, 2, 3, 4, 5, 6, 7, 8, 9]] == \
        [1, 2, 3, 4, 6, 6, 8, 8, 12]
    # Worst-case rounding waste is the 2^k → 1.5·2^k step (ratio 1.5).
    for x in [17, 33, 49, 97, 1000]:
        b = bucket_dim(x)
        assert b >= x and b / x <= 1.5 + 1e-9


def test_retrace_once_per_bucket_across_passes():
    """≥3 same-bucket blocks over ≥3 CD passes: the solver traces exactly
    once per (bucket, objective-config) key; every other dispatch is a
    cache hit (the ISSUE acceptance criterion)."""
    eids, X, y, w = _clustered_problem()
    ds = _dataset(eids, X, y, w, bucketed=True)
    assert len(ds.blocks) >= 3  # ≥3 same-bucket blocks (the criterion)
    shapes = {tuple(b.features.shape) for b in ds.blocks}
    assert len(shapes) == 1, "clustered counts must collapse to one bucket"

    cache = SolveCache(donate=True)
    coord = _coordinate(ds, cache)
    batch = _batch(eids, X, y, w)
    model = None
    passes = 3
    for _ in range(passes):
        model, _stats = coord.train(batch, None, model)

    n_calls = passes * len(ds.blocks)
    assert cache.stats.calls == n_calls
    # One executable for the whole run: one bucket shape × one config.
    assert cache.stats.traces == 1
    assert cache.stats.hits == n_calls - 1
    assert len(set(cache.stats.trace_keys)) == 1


def test_coordinates_of_equal_static_configuration_share_one_entry():
    """Two coordinates over one cache, each with an objective and a spec of
    its own that compare equal: one ``block_solver`` entry, one trace, and
    every dispatch of the second coordinate a hit."""
    eids, X, y, w = _clustered_problem()
    ds = _dataset(eids, X, y, w, bucketed=True)
    cache = SolveCache(donate=True)
    first, second = _coordinate(ds, cache), _coordinate(ds, cache)
    assert first.objective is not second.objective
    batch = _batch(eids, X, y, w)
    model, _stats = first.train(batch, None, None)
    assert (cache.num_entries, cache.stats.traces) == (1, 1)
    with cache.expect_cached("the second coordinate"):
        again, _stats = second.train(batch, None, None)
    assert (cache.num_entries, cache.stats.traces) == (1, 1)
    assert cache.stats.hits == 2 * len(ds.blocks) - 1
    assert np.array_equal(
        np.asarray(model.coefficients), np.asarray(again.coefficients)
    )
    # Another static configuration (here the spec's memory) is another entry.
    _coordinate(ds, cache, memory=7).train(batch, None, None)
    assert cache.num_entries == 2


def test_exact_shapes_trace_per_block():
    """Without bucketing the same data costs one trace per distinct block
    shape — the regression the cache+bucketing pair exists to prevent."""
    eids, X, y, w = _clustered_problem()
    ds = _dataset(eids, X, y, w, bucketed=False)
    shapes = {tuple(b.features.shape) for b in ds.blocks}
    cache = SolveCache(donate=True)
    coord = _coordinate(ds, cache)
    batch = _batch(eids, X, y, w)
    model = None
    for _ in range(2):
        model, _stats = coord.train(batch, None, model)
    assert cache.stats.traces == len(shapes)
    assert cache.stats.hits == cache.stats.calls - len(shapes)


def test_bucketed_vs_exact_parity_f64():
    """Bucketed solves match exact-shape solves at rtol ≤ 1e-6. Run in f64:
    padding changes XLA reduction trees, so f32 carries trajectory-rounding
    noise that is not a property of bucketing itself."""
    jax.config.update("jax_enable_x64", True)
    try:
        eids, X, y, w = _clustered_problem(dtype=np.float64)
        batch = _batch(eids, X, y, w)
        models = {}
        for bucketed in (True, False):
            ds = _dataset(eids, X, y, w, bucketed=bucketed)
            coord = _coordinate(ds, SolveCache(donate=True))
            model = None
            for _ in range(2):
                model, _stats = coord.train(batch, None, model)
            models[bucketed] = np.asarray(model.coefficients)[:E, :D]
        np.testing.assert_allclose(
            models[True], models[False], rtol=1e-6, atol=1e-12
        )
    finally:
        jax.config.update("jax_enable_x64", False)


def test_donation_safety():
    """The warm-start buffer is donated to the cached executable: it must be
    consumed (deleted) after the call, the result must match the eager
    un-donated solve, and a later dispatch must not disturb the first
    result (nothing reads w0 after donation)."""
    eids, X, y, w = _clustered_problem()
    ds = _dataset(eids, X, y, w, bucketed=True)
    block = ds.blocks[0]
    obj = GLMObjective(loss=LogisticLoss, l2_weight=0.5, intercept_index=0)
    spec = OptimizerSpec(optimizer=OptimizerType.NEWTON, max_iter=25, tol=1e-9)
    cfg = dataclasses.replace(spec.config(), track_history=False)
    offs = block.gather_offsets(jnp.zeros(y.shape[0], jnp.float32))

    cache = SolveCache(donate=True)
    solve = cache.block_solver(obj, spec, cfg, has_mask=False)
    w0 = jnp.zeros((block.num_entities, block.dim), jnp.float32)
    w_cached, _it, _rs = solve(block, offs, w0)
    assert w0.is_deleted(), "donated warm start must be consumed"

    w0_eager = jnp.zeros((block.num_entities, block.dim), jnp.float32)
    w_eager, _, _ = _solve_block(block, offs, w0_eager, obj, spec, cfg)
    np.testing.assert_allclose(
        np.asarray(w_cached), np.asarray(w_eager), rtol=1e-5, atol=1e-6
    )

    # Second dispatch through the same executable: first result unchanged.
    before = np.asarray(w_cached).copy()
    solve(block, offs, jnp.ones((block.num_entities, block.dim), jnp.float32))
    np.testing.assert_array_equal(before, np.asarray(w_cached))

    # donate=False leaves the caller's buffer alive.
    cache_nd = SolveCache(donate=False)
    solve_nd = cache_nd.block_solver(obj, spec, cfg, has_mask=False)
    w0_kept = jnp.zeros((block.num_entities, block.dim), jnp.float32)
    solve_nd(block, offs, w0_kept)
    assert not w0_kept.is_deleted()


def test_warm_start_survives_donation_end_to_end():
    """Training twice with a warm-start model must not invalidate the
    model passed in (the coordinate gathers a fresh w0 buffer; the model's
    own coefficients are never donated)."""
    eids, X, y, w = _clustered_problem()
    ds = _dataset(eids, X, y, w, bucketed=True)
    coord = _coordinate(ds, SolveCache(donate=True))
    batch = _batch(eids, X, y, w)
    m1, _ = coord.train(batch)
    keep = np.asarray(m1.coefficients).copy()
    coord.train(batch, None, m1)
    assert not m1.coefficients.is_deleted()
    np.testing.assert_array_equal(keep, np.asarray(m1.coefficients))


def test_profile_flag_controls_sync(monkeypatch):
    """run(profile=False) performs ZERO block_until_ready calls between
    coordinate updates; profile=True keeps the timing sync (the default)."""
    from photon_tpu.algorithm.coordinate_descent import CoordinateDescent

    eids, X, y, w = _clustered_problem()
    ds = _dataset(eids, X, y, w, bucketed=True)
    batch = _batch(eids, X, y, w)

    calls = {"n": 0}
    real = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)

    def run(profile):
        coord = _coordinate(ds, SolveCache(donate=True))
        cd = CoordinateDescent(
            coordinates={"per_user": coord},
            update_sequence=["per_user"],
            num_iterations=2,
        )
        calls["n"] = 0
        return cd.run(batch, profile=profile)

    res = run(profile=False)
    assert calls["n"] == 0
    # Wall times still recorded (dispatch-only) and the model trains.
    assert all(t >= 0 for t in res.wall_times["per_user"])

    res = run(profile=True)
    assert calls["n"] >= 2  # one sync per coordinate update
    assert all(t > 0 for t in res.wall_times["per_user"])


def test_full_telemetry_stays_sync_free(monkeypatch, tmp_path):
    """The telemetry tentpole must not reintroduce host syncs: with spans,
    metrics, AND a registered event listener all active, run(profile=False)
    still performs ZERO block_until_ready calls. Device-resident diagnostics
    are read exactly once, at report finalize."""
    from photon_tpu.algorithm.coordinate_descent import CoordinateDescent
    from photon_tpu.obs import begin_run, finalize_run_report, get_spans
    from photon_tpu.utils.events import EventEmitter

    eids, X, y, w = _clustered_problem()
    ds = _dataset(eids, X, y, w, bucketed=True)
    batch = _batch(eids, X, y, w)

    calls = {"n": 0}
    real = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)

    begin_run()
    events = []
    emitter = EventEmitter()
    emitter.register(events.append)
    coord = _coordinate(ds, SolveCache(donate=True))
    cd = CoordinateDescent(
        coordinates={"per_user": coord},
        update_sequence=["per_user"],
        num_iterations=2,
    )
    calls["n"] = 0
    res = cd.run(batch, profile=False, emitter=emitter)
    assert calls["n"] == 0  # full telemetry, zero syncs in the loop

    # Spans were recorded for every coordinate update without syncing.
    names = {s.name for s in get_spans()}
    assert {"cd/iter0/per_user", "cd/iter1/per_user"} <= names
    assert sum(1 for n in names if n.endswith("/solve")) == 2
    assert sum(1 for n in names if n.endswith("/score")) == 2

    # Per-update events were emitted, but sync-free: no device-read summary.
    logs = [e for e in events if e.name == "PhotonOptimizationLogEvent"]
    assert len(logs) == 2
    assert all(e.payload["summary"] is None for e in logs)

    # Finalize reads device-resident diagnostics — syncs are allowed HERE,
    # once, outside the dispatch loop.
    out = tmp_path / "run.jsonl"
    finalize_run_report(
        "test", path=str(out), emitter=emitter,
        trackers=[{"label": "cd", "tracker": res.tracker,
                   "wall_times": res.wall_times}],
    )
    assert out.exists()
    begin_run()


def test_lru_eviction_bounded_cache():
    """PHOTON_TPU_SOLVE_CACHE_MAX_ENTRIES-style bounded cache: a λ-sweep
    (one entry per l2_weight) stays under the cap, evictions count, the two
    LIVE entries keep serving hits, and a solver handle whose entry was
    evicted transparently rebuilds (a legitimate retrace, not an error)."""
    from photon_tpu.obs.metrics import registry

    eids, X, y, w = _clustered_problem()
    ds = _dataset(eids, X, y, w, bucketed=True)
    block = ds.blocks[0]
    spec = OptimizerSpec(optimizer=OptimizerType.NEWTON, max_iter=10, tol=1e-9)
    cfg = dataclasses.replace(spec.config(), track_history=False)
    offs = block.gather_offsets(jnp.zeros(y.shape[0], jnp.float32))

    def w0():
        return jnp.zeros((block.num_entities, block.dim), jnp.float32)

    cache = SolveCache(donate=False, max_entries=2)
    counter_before = registry().counter("solve_cache_evictions_total").value
    lams = [0.1, 0.5, 1.0, 2.0]
    solvers, results = {}, {}
    for lam in lams:
        obj = GLMObjective(loss=LogisticLoss, l2_weight=lam, intercept_index=0)
        solvers[lam] = cache.block_solver(obj, spec, cfg, has_mask=False)
        out, *_ = solvers[lam](block, offs, w0())
        results[lam] = np.asarray(out).copy()
        assert cache.num_entries <= 2  # the cap holds throughout the sweep
    assert cache.stats.traces == len(lams)
    assert cache.stats.evictions == len(lams) - 2
    evicted = registry().counter("solve_cache_evictions_total").value
    assert evicted - counter_before == len(lams) - 2

    # The two most-recent entries are live: re-dispatching them is a HIT.
    hits0 = cache.stats.hits
    for lam in lams[-2:]:
        out, *_ = solvers[lam](block, offs, w0())
        np.testing.assert_allclose(
            np.asarray(out), results[lam], rtol=1e-5, atol=1e-6
        )
    assert cache.stats.hits == hits0 + 2
    assert cache.stats.traces == len(lams)

    # An evicted entry's HANDLE still works without a retrace: handles pin
    # their executable, so eviction reclaims the cache slot without
    # invalidating live callers (memory frees once no handle remains).
    out, *_ = solvers[lams[0]](block, offs, w0())
    np.testing.assert_allclose(
        np.asarray(out), results[lams[0]], rtol=1e-5, atol=1e-6
    )
    assert cache.stats.traces == len(lams)

    # A NEW handle for the evicted λ rebuilds — the entry really is gone.
    obj0 = GLMObjective(
        loss=LogisticLoss, l2_weight=lams[0], intercept_index=0
    )
    fresh = cache.block_solver(obj0, spec, cfg, has_mask=False)
    out, *_ = fresh(block, offs, w0())
    np.testing.assert_allclose(
        np.asarray(out), results[lams[0]], rtol=1e-5, atol=1e-6
    )
    assert cache.stats.traces == len(lams) + 1
    assert cache.num_entries <= 2


def test_max_entries_env_and_validation(monkeypatch):
    from photon_tpu.algorithm.solve_cache import MAX_ENTRIES_ENV

    monkeypatch.setenv(MAX_ENTRIES_ENV, "3")
    assert SolveCache().max_entries == 3
    monkeypatch.delenv(MAX_ENTRIES_ENV)
    assert SolveCache().max_entries is None
    with pytest.raises(ValueError):
        SolveCache(max_entries=0)
