"""Batched small-GLM Pallas Newton kernel: parity, routing, and layout.

Everything here runs in interpret mode on CPU (the compiled lowering is
checked by tests/test_tpu_aot_compile.py and on the chip by chip_smoke.py).
The load-bearing claims:

* ``re_kernel="pallas"`` is BIT-EXACT against the XLA ``_solve_block`` on
  an identical block layout — the fused kernel replaces only the two
  X-reductions whose per-entity values are reduction-order-identical to
  the vmapped XLA formulations, everything else (while_loop, damping,
  trial sweep, Cholesky) is shared code.
* ``re_kernel="pallas_bf16x"`` matches at a pinned tolerance (bf16 X
  read, f32 accumulate).
* Padding rows, quarantine, the active-set mask, and the solve-cache
  zero-retrace discipline behave identically through the fused path.
* ``merge_same_geometry_blocks`` collapses same-(n_max, d) dense blocks
  into single dispatches without touching per-entity data.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_tpu.algorithm.random_effect import _solve_block
from photon_tpu.algorithm.solve_cache import SolveCache
from photon_tpu.data.random_effect import (
    RandomEffectDataConfig,
    build_random_effect_dataset,
    merge_same_geometry_blocks,
)
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.ops.pallas_newton import (
    RE_KERNELS,
    fused_newton_system,
    resolve_re_kernel,
)
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.types import OptimizerType

# Pinned parity bar for the bf16-X kernel on these workloads (observed
# ≤ 5e-3 coefficient drift; the f32 kernel is bit-exact).
BF16X_TOL = 5e-3


# One (8, 48, 6) float32 slab: as the block plan's budget it cuts the 32-row
# grid level of ``_workload``'s counts into several same-geometry blocks.
SLAB_BUDGET = 8 * 48 * 6 * 4


def _workload(seed=0, n=1800, d=6, E=48, slab_budget=None):
    """Clustered-count workload whose bucketed blocks cover several
    geometries (the mixed-bucket case of the acceptance criteria)."""
    rng = np.random.default_rng(seed)
    counts = np.where(
        rng.uniform(size=E) < 0.5,
        rng.integers(4, 8, size=E),
        rng.integers(20, 34, size=E),
    ).astype(int)
    eids = np.repeat(np.arange(E, dtype=np.int32), counts)
    n = eids.size
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 0] = 1.0
    w_true = rng.normal(size=(E, d)).astype(np.float32) * 0.5
    z = np.einsum("nd,nd->n", X, w_true[eids])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    wt = np.ones(n, np.float32)
    ds = build_random_effect_dataset(
        eids, X, y, wt, E,
        RandomEffectDataConfig(
            re_type="m", feature_shard="s",
            subspace_projection=False,
        ),
        slab_budget=slab_budget,
    )
    return ds, n


def _solve_all(ds, re_kernel, spec=None, jit=False):
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    spec = spec or OptimizerSpec(
        optimizer=OptimizerType.NEWTON, max_iter=20, tol=1e-7
    )
    config = spec.config()
    out = []
    for b in ds.blocks:
        offs = jnp.zeros(b.label.shape, jnp.float32)
        w0 = jnp.zeros((b.num_entities, b.dim), jnp.float32)
        if jit:
            fn = jax.jit(
                lambda bl, o, w, rk=re_kernel: _solve_block(
                    bl, o, w, obj, spec, config, re_kernel=rk
                )
            )
            out.append(fn(b, offs, w0))
        else:
            out.append(
                _solve_block(b, offs, w0, obj, spec, config, re_kernel=re_kernel)
            )
    return out


@pytest.mark.parametrize("on_tpu", [False, True])
def test_resolve_re_kernel(on_tpu, monkeypatch):
    """``auto`` is the XLA lowering on every backend (on the v5e it is the
    fastest and the most exact of the three: PERF.md §6, PR 28); the kernel
    runs only where it is asked for by name."""
    from photon_tpu.ops import pallas_newton

    monkeypatch.setattr(pallas_newton, "pallas_available", lambda: on_tpu)
    assert set(RE_KERNELS) == {"auto", "xla", "pallas", "pallas_bf16x"}
    for k in ("xla", "pallas", "pallas_bf16x"):
        assert resolve_re_kernel(k) == k
    assert resolve_re_kernel("auto") == "xla"
    with pytest.raises(ValueError, match="re_kernel"):
        resolve_re_kernel("mosaic")


def test_default_coordinate_counts_block_solves_by_kernel():
    """A coordinate built with the default ``re_kernel`` runs the XLA
    lowering, and ``re_block_solves_total`` says so: one count a dispatched
    block, under the kernel that assembled its Newton system and the
    lowering that solved it (by the block's width and lanes)."""
    from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
    from photon_tpu.data.game_data import GameBatch
    from photon_tpu.obs.metrics import registry
    from photon_tpu.types import TaskType

    ds, n = _workload(seed=8, E=400)
    batch = GameBatch(
        label=jnp.zeros(n, jnp.float32), offset=jnp.zeros(n, jnp.float32),
        weight=jnp.ones(n, jnp.float32), features={}, entity_ids={},
    )

    def coordinate(cid, optimizer=OptimizerType.NEWTON, **kw):
        return RandomEffectCoordinate(
            coordinate_id=cid, dataset=ds, task=TaskType.LOGISTIC_REGRESSION,
            objective=GLMObjective(loss=LogisticLoss, l2_weight=1.0),
            optimizer_spec=OptimizerSpec(
                optimizer=optimizer, max_iter=5, tol=1e-6
            ),
            solve_cache=SolveCache(donate=False), **kw,
        )

    def solves(cid):
        return {
            (s["labels"]["kernel"], s["labels"]["spd_solve"]): s["value"]
            for s in registry().snapshot()
            if s["metric"] == "re_block_solves_total"
            and s["labels"]["coordinate"] == cid
        }

    from collections import Counter

    from photon_tpu.optim.newton import spd_solve_lowering

    # by the block's width and lanes: 400 users in two clusters of counts
    by_solve = Counter(
        spd_solve_lowering(b.dim, b.num_entities) for b in ds.blocks
    )
    assert by_solve["unrolled"] >= 1

    default = coordinate("counted_default")
    assert default.re_kernel == "auto"
    model = None
    for _ in range(2):
        model, _stats = default.train(batch, None, model)
    assert solves("counted_default") == {
        ("xla", how): 2 * n for how, n in by_solve.items()
    }

    coordinate("counted_pallas", re_kernel="pallas").train(batch, None, None)
    assert solves("counted_pallas") == {
        ("pallas", how): n for how, n in by_solve.items()
    }

    # Off the Newton route no SPD system is solved, whatever the sizes.
    coordinate("counted_tron", OptimizerType.TRON).train(batch, None, None)
    assert solves("counted_tron") == {("xla", "none"): len(ds.blocks)}


def test_fused_newton_system_bitexact_unbatched_and_vmapped():
    """The kernel's (H, g) equal the XLA formulations bit-for-bit, alone
    and under vmap (the per-block-row batching used by _solve_block)."""
    rng = np.random.default_rng(3)
    n, d, E = 40, 6, 5
    X = jnp.asarray(rng.normal(size=(E, n, d)).astype(np.float32))
    d2 = jnp.asarray(rng.uniform(0.01, 1.0, size=(E, n)).astype(np.float32))
    dz = jnp.asarray(rng.normal(size=(E, n)).astype(np.float32))

    h1, g1 = fused_newton_system(X[0], d2[0], dz[0])
    # Jitted references: the interpret-mode kernel is itself a traced
    # computation, and eager dispatch lowers the transpose matvec through
    # a different (non-bit-identical) matmul path.
    h_ref1 = jax.jit(lambda x, c: jnp.einsum("nd,n,ne->de", x, c, x))(X[0], d2[0])
    g_ref1 = jax.jit(lambda x, r: x.T @ r)(X[0], dz[0])
    assert np.array_equal(np.asarray(h1), np.asarray(h_ref1))
    assert np.array_equal(np.asarray(g1), np.asarray(g_ref1))

    hv, gv = jax.vmap(fused_newton_system)(X, d2, dz)
    h_ref = jax.jit(
        jax.vmap(lambda x, c: jnp.einsum("nd,n,ne->de", x, c, x))
    )(X, d2)
    g_ref = jax.jit(jax.vmap(lambda x, r: x.T @ r))(X, dz)
    assert np.array_equal(np.asarray(hv), np.asarray(h_ref))
    assert np.array_equal(np.asarray(gv), np.asarray(g_ref))


def test_padded_tiled_lowering_tolerance():
    """The TPU-shaped padded/tiled lowering (forced in interpret mode)
    agrees with the exact kernel at f32 tolerance — tiling re-associates
    the n-reduction, so this path is pinned-tolerance, not bit-exact."""
    rng = np.random.default_rng(5)
    n, d = 333, 6  # not sublane/lane aligned
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    d2 = jnp.asarray(rng.uniform(0.01, 1.0, size=n).astype(np.float32))
    dz = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h_e, g_e = fused_newton_system(X, d2, dz, interpret=True, padded=False)
    h_t, g_t = fused_newton_system(X, d2, dz, interpret=True, padded=True)
    np.testing.assert_allclose(np.asarray(h_t), np.asarray(h_e), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g_t), np.asarray(g_e), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("jit", [False, True])
def test_solve_block_pallas_bitexact_mixed_geometries(jit):
    """The acceptance criterion: pallas vs xla on IDENTICAL block layouts
    is bit-for-bit across every bucket geometry, eager and jitted —
    coefficients AND iteration counts AND reason codes."""
    ds, _ = _workload()
    assert len(ds.blocks) > 1  # really mixed geometries
    for rx, rp in zip(_solve_all(ds, "xla", jit=jit),
                      _solve_all(ds, "pallas", jit=jit)):
        for ax, ap in zip(rx, rp):
            assert np.array_equal(np.asarray(ax), np.asarray(ap))


def test_solve_block_bf16x_pinned_tolerance():
    ds, _ = _workload(seed=1)
    for rx, rp in zip(_solve_all(ds, "xla"), _solve_all(ds, "pallas_bf16x")):
        diff = np.max(np.abs(np.asarray(rx[0]) - np.asarray(rp[0])))
        assert diff < BF16X_TOL, diff


def test_padding_rows_inert():
    """Shape-bucket padding rows (entity_idx=-1, weight 0) through the
    fused kernel: real entities' coefficients are unchanged by the
    padding's presence, and the padded rows produce the same (finite)
    output as the XLA path."""
    ds, _ = _workload(seed=2)  # 23 entities of one level fill 24 lanes
    padded_blocks = [
        b for b in ds.blocks if np.any(np.asarray(b.entity_idx) < 0)
    ]
    assert padded_blocks, "bucketing should have produced padding rows"
    for rx, rp in zip(_solve_all(ds, "xla"), _solve_all(ds, "pallas")):
        assert np.array_equal(np.asarray(rx[0]), np.asarray(rp[0]))
        assert np.all(np.isfinite(np.asarray(rp[0])))


def test_solve_cache_masks_and_quarantine_parity():
    """Through SolveCache.block_solver with the active-set gate: the
    active and quarantined masks from the pallas executable are bitwise
    the ones the XLA executable computes, including a corrupted block
    whose non-finite offsets force divergence quarantine."""
    ds, _ = _workload(seed=4)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    spec = OptimizerSpec(optimizer=OptimizerType.NEWTON, max_iter=20, tol=1e-7)
    config = spec.config()

    def run(re_kernel, poison):
        cache = SolveCache(donate=False)
        solver = cache.block_solver(
            obj, spec, config, has_mask=False, convergence_tol=1e-4,
            re_kernel=re_kernel,
        )
        outs = []
        for i, b in enumerate(ds.blocks):
            offs = jnp.zeros(b.label.shape, jnp.float32)
            if poison and i == 0:
                offs = offs.at[0, 0].set(jnp.nan)  # diverge entity row 0
            w0 = jnp.zeros((b.num_entities, b.dim), jnp.float32)
            outs.append(solver(b, offs, w0))
        return outs

    for poison in (False, True):
        for rx, rp in zip(run("xla", poison), run("pallas", poison)):
            w_x, _, reasons_x, active_x, quar_x = rx
            w_p, _, reasons_p, active_p, quar_p = rp
            assert np.array_equal(np.asarray(w_x), np.asarray(w_p))
            assert np.array_equal(np.asarray(reasons_x), np.asarray(reasons_p))
            assert np.array_equal(np.asarray(active_x), np.asarray(active_p))
            assert np.array_equal(np.asarray(quar_x), np.asarray(quar_p))
    # The poisoned row really exercised quarantine (not vacuous parity).
    assert bool(run("pallas", True)[0][4][0])


def test_zero_post_warmup_retraces():
    """Each re_kernel gets its own cache entry (part of the key), and a
    second dispatch of the same geometry is a hit — asserted with
    expect_cached, the active-set path's zero-retrace discipline."""
    ds, _ = _workload(seed=6)
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    spec = OptimizerSpec(optimizer=OptimizerType.NEWTON, max_iter=10, tol=1e-6)
    config = spec.config()
    cache = SolveCache(donate=False)

    def dispatch_all(re_kernel):
        solver = cache.block_solver(
            obj, spec, config, has_mask=False, re_kernel=re_kernel
        )
        for b in ds.blocks:
            solver(
                b, jnp.zeros(b.label.shape, jnp.float32),
                jnp.zeros((b.num_entities, b.dim), jnp.float32),
            )

    dispatch_all("pallas")
    traces_warm = cache.stats.traces
    dispatch_all("xla")  # separate key: may trace, must not evict pallas
    with cache.expect_cached("pallas re-dispatch"):
        dispatch_all("pallas")
    with cache.expect_cached("xla re-dispatch"):
        dispatch_all("xla")
    assert cache.stats.traces >= traces_warm
    assert cache.num_entries == 2  # one executable per kernel routing


def test_merge_same_geometry_blocks():
    ds, _ = _workload(seed=7, E=64, slab_budget=SLAB_BUDGET)
    geoms = [(b.n_max, b.dim) for b in ds.blocks]
    assert len(set(geoms)) < len(geoms), "need colliding geometries"
    merged = merge_same_geometry_blocks(ds)
    assert len(merged.blocks) == len(set(geoms))
    assert len(merged.blocks) < len(ds.blocks)

    # Every real entity's rows survive exactly once, bit-identical.
    def rows_by_entity(blocks):
        out = {}
        for b in blocks:
            eidx = np.asarray(b.entity_idx)
            feats = np.asarray(b.features)
            labs = np.asarray(b.label)
            wts = np.asarray(b.weight)
            for j, e in enumerate(eidx):
                if e >= 0:
                    assert e not in out
                    out[int(e)] = (feats[j], labs[j], wts[j])
        return out

    before, after = rows_by_entity(ds.blocks), rows_by_entity(merged.blocks)
    assert before.keys() == after.keys()
    for e in before:
        for a, b_ in zip(before[e], after[e]):
            assert np.array_equal(a, b_)
    # Padding rows stay inert.
    for b in merged.blocks:
        pad = np.asarray(b.entity_idx) < 0
        assert not np.any(np.asarray(b.weight)[pad])
        assert not np.any(np.asarray(b.train_mask)[pad])
        assert np.all(np.asarray(b.sample_index)[pad] == -1)

def test_config_flag_builds_merged_dataset():
    rng = np.random.default_rng(9)
    E = 64
    counts = np.where(
        rng.uniform(size=E) < 0.5,
        rng.integers(4, 8, size=E),
        rng.integers(20, 34, size=E),
    ).astype(int)
    eids = np.repeat(np.arange(E, dtype=np.int32), counts)
    n = eids.size
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    wt = np.ones(n, np.float32)

    def build(merge):
        return build_random_effect_dataset(
            eids, X, y, wt, E,
            RandomEffectDataConfig(
                re_type="m", feature_shard="s",
                subspace_projection=False, merge_same_geometry=merge,
            ),
            slab_budget=SLAB_BUDGET,
        )

    plain, merged = build(False), build(True)
    assert len(merged.blocks) < len(plain.blocks)
    geoms = [(b.n_max, b.dim) for b in merged.blocks]
    assert len(set(geoms)) == len(geoms)


def test_minimize_newton_rejects_unresolved_kernel():
    from photon_tpu.data.batch import LabeledBatch
    from photon_tpu.optim.newton import minimize_newton

    X = jnp.ones((4, 2), jnp.float32)
    lb = LabeledBatch(jnp.ones(4), X, jnp.zeros(4), jnp.ones(4))
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    with pytest.raises(ValueError, match="resolve"):
        minimize_newton(obj, lb, jnp.zeros(2, jnp.float32), kernel="auto")
