"""The random-effect block solve: ``_solve_block`` on the Newton route, alone
and through ``SolveCache.block_solver``.

One lowering assembles the Newton system (optim/newton.py); two solve it,
chosen by a block's lanes (``spd_solve_lowering``), so what a block's lanes
must not change is checked on a block under and a block from
``SPD_UNROLL_MIN_LANES`` lanes:

* Shape-bucket padding lanes are inert: a real entity's coefficients are
  bit-equal with and without them.
* A poisoned lane is quarantined in the trace, keeps its warm start, and
  moves no other lane.
* One cache entry a static configuration, one trace a geometry, and every
  later dispatch a hit.
* ``re_block_solves_total`` counts a pass's dispatched blocks by the
  lowering that solved them.

Last, the seam ``benchmark/program.py::re_kernel_forced`` holds on to
(photon_tpu/ops/pallas_newton.py) still opens for it, and the program itself
never imports it.
"""

import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_tpu.algorithm.random_effect import _solve_block
from photon_tpu.algorithm.solve_cache import SolveCache
from photon_tpu.data.random_effect import (
    RandomEffectDataConfig,
    build_random_effect_dataset,
)
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.common import REASON_DIVERGED
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.optim.newton import SPD_UNROLL_MIN_LANES, spd_solve_lowering
from photon_tpu.types import OptimizerType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OBJECTIVE = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
SPEC = OptimizerSpec(optimizer=OptimizerType.NEWTON, max_iter=20, tol=1e-7)

# Users of the clustered workload whose blocks all stay under
# SPD_UNROLL_MIN_LANES lanes, and whose two small-count levels reach it.
USERS = {"library": 48, "unrolled": 600}


def _workload(seed=0, d=6, E=48):
    """Clustered-count workload whose bucketed blocks cover several
    geometries."""
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
    )
    return ds, n


def _padded_blocks(lowering, seed):
    """The blocks of the workload that carry padding lanes and whose Newton
    systems ``lowering`` solves, with and without those lanes."""
    ds, _ = _workload(seed=seed, E=USERS[lowering])
    out = []
    for b in ds.blocks:
        real = int(np.sum(np.asarray(b.entity_idx) >= 0))
        if real < b.num_entities and {
            spd_solve_lowering(b.dim, b.num_entities),
            spd_solve_lowering(b.dim, real),
        } == {lowering}:
            out.append((b, real))
    assert out, "bucketing should have produced padding lanes"
    return out


def _inputs(block, seed=0):
    rng = np.random.default_rng(seed)
    w0 = 0.1 * rng.normal(size=(block.num_entities, block.dim))
    return jnp.zeros(block.label.shape, jnp.float32), jnp.asarray(w0, jnp.float32)


_solve = jax.jit(
    lambda block, offs, w0: _solve_block(
        block, offs, w0, OBJECTIVE, SPEC, SPEC.config()
    )
)


@pytest.mark.parametrize("lowering", ["library", "unrolled"])
def test_padding_rows_inert(lowering):
    """Shape-bucket padding lanes (entity_idx -1, weight 0): the real
    entities' coefficients, iterations and reasons are bit-equal with the
    padding lanes and with the block cut to its real lanes, and the padding
    lanes come out finite."""
    for block, real in _padded_blocks(lowering, seed=2):
        assert np.all(np.asarray(block.entity_idx)[real:] == -1)
        offs, w0 = _inputs(block)
        cut = jax.tree.map(lambda a: a[:real], block)
        padded = _solve(block, offs, w0)
        alone = _solve(cut, offs[:real], w0[:real])
        for with_pad, without in zip(padded, alone):
            assert np.array_equal(np.asarray(with_pad)[:real], np.asarray(without))
        assert np.all(np.isfinite(np.asarray(padded[0])))


@pytest.mark.parametrize("poisoned", [False, True])
@pytest.mark.parametrize("lowering", ["library", "unrolled"])
def test_solve_cache_masks_and_quarantine(lowering, poisoned):
    """Through ``SolveCache.block_solver`` with the active-set gate. Clean:
    the coefficients are the bare ``_solve_block``'s, no lane is quarantined
    and no padding lane is active. Poisoned (a non-finite offset in lane 0):
    that lane is quarantined, keeps its warm start, reads DIVERGED and
    retires; every other lane's outputs are bit-equal to the clean run's."""
    block, real = _padded_blocks(lowering, seed=4)[0]
    offs, w0 = _inputs(block, seed=1)

    def run(offsets):
        solver = SolveCache(donate=False).block_solver(
            OBJECTIVE, SPEC, SPEC.config(), has_mask=False, convergence_tol=1e-4
        )
        return [np.asarray(a) for a in solver(block, offsets, w0)]

    clean = run(offs)
    w, _iters, reasons, active, quarantined = clean
    if not poisoned:
        assert np.array_equal(w, np.asarray(_solve(block, offs, w0)[0]))
        assert not quarantined.any() and not np.any(reasons == REASON_DIVERGED)
        assert active[:real].any() and not active[real:].any()
        return
    w, _iters, reasons, active, quarantined = got = run(offs.at[0, 0].set(jnp.nan))
    assert quarantined[0] and not quarantined[1:].any()
    assert np.array_equal(w[0], np.asarray(w0)[0])
    assert reasons[0] == REASON_DIVERGED and not active[0]
    for out, want in zip(got, clean):
        assert np.array_equal(out[1:], want[1:])


def test_zero_post_warmup_retraces():
    """One cache entry a static configuration, one trace a geometry, and a
    second dispatch of each geometry is a hit: asserted with
    ``expect_cached``, the active-set path's zero-retrace discipline."""
    ds, _ = _workload(seed=6)
    cache = SolveCache(donate=False)

    def dispatch_all():
        solver = cache.block_solver(OBJECTIVE, SPEC, SPEC.config(), has_mask=False)
        for b in ds.blocks:
            solver(b, *_inputs(b))

    dispatch_all()
    geometries = {tuple(b.features.shape) for b in ds.blocks}
    assert cache.stats.traces == len(geometries) > 1
    with cache.expect_cached("re-dispatch"):
        dispatch_all()
    assert cache.stats.hits == cache.stats.calls - len(geometries)
    assert cache.num_entries == 1
    # The active-set gate is another static configuration: an entry of its own.
    cache.block_solver(
        OBJECTIVE, SPEC, SPEC.config(), has_mask=False, convergence_tol=1e-4
    )
    assert cache.num_entries == 2


def test_coordinate_counts_block_solves_by_spd_solve():
    """``re_block_solves_total``: one count a dispatched block, under the
    lowering that solved its Newton systems (by the block's width and
    lanes), and ``none`` off the Newton route."""
    from collections import Counter

    from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
    from photon_tpu.data.game_data import GameBatch
    from photon_tpu.obs.metrics import registry
    from photon_tpu.types import TaskType

    ds, n = _workload(seed=8, E=400)
    batch = GameBatch(
        label=jnp.zeros(n, jnp.float32), offset=jnp.zeros(n, jnp.float32),
        weight=jnp.ones(n, jnp.float32), features={}, entity_ids={},
    )

    def coordinate(cid, optimizer=OptimizerType.NEWTON):
        return RandomEffectCoordinate(
            coordinate_id=cid, dataset=ds, task=TaskType.LOGISTIC_REGRESSION,
            objective=OBJECTIVE,
            optimizer_spec=OptimizerSpec(
                optimizer=optimizer, max_iter=5, tol=1e-6
            ),
            solve_cache=SolveCache(donate=False),
        )

    def solves(cid):
        found = [
            s for s in registry().snapshot()
            if s["metric"] == "re_block_solves_total"
            and s["labels"]["coordinate"] == cid
        ]
        assert all(set(s["labels"]) == {"coordinate", "spd_solve"} for s in found)
        return {s["labels"]["spd_solve"]: s["value"] for s in found}

    # by the block's width and lanes: 400 users in two clusters of counts
    by_solve = Counter(
        spd_solve_lowering(b.dim, b.num_entities) for b in ds.blocks
    )
    assert by_solve["unrolled"] >= 1 and by_solve["library"] >= 1
    assert any(b.num_entities >= SPD_UNROLL_MIN_LANES for b in ds.blocks)

    newton = coordinate("counted_newton")
    model = None
    for _ in range(2):
        model, _stats = newton.train(batch, None, model)
    assert solves("counted_newton") == {
        how: 2 * blocks for how, blocks in by_solve.items()
    }

    # Off the Newton route no SPD system is solved, whatever the sizes.
    coordinate("counted_tron", OptimizerType.TRON).train(batch, None, None)
    assert solves("counted_tron") == {"none": len(ds.blocks)}


@pytest.mark.parametrize("kernel", ["xla", "pallas", "auto"])
def test_benchmark_witness_seam(kernel):
    """``benchmark/program.py::re_kernel_forced`` (the witness of
    ``benchmark/control.py``) swaps ``resolve_re_kernel`` of
    ``photon_tpu.ops.pallas_newton`` while it is open: ``xla`` opens and
    closes and puts the resolver back, and no other name is a lowering."""
    from benchmark.program import re_kernel_forced
    from photon_tpu.ops import pallas_newton

    real = pallas_newton.resolve_re_kernel
    if kernel == "xla":
        with re_kernel_forced(kernel):
            assert pallas_newton.resolve_re_kernel("auto") == "xla"
    else:
        with pytest.raises(ValueError, match="no concrete RE kernel"):
            with re_kernel_forced(kernel):
                pass
    assert pallas_newton.resolve_re_kernel is real
    assert real("auto") == real("xla") == "xla"
    with pytest.raises(ValueError, match="re_kernel"):
        real("pallas")


def test_the_program_never_imports_the_seam():
    code = (
        "import sys\n"
        "import photon_tpu.algorithm.random_effect\n"
        "import photon_tpu.algorithm.sharded_random_effect\n"
        "import photon_tpu.estimators.game_estimator\n"
        "assert 'photon_tpu.ops.pallas_newton' not in sys.modules\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    done = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=120,
    )
    assert done.returncode == 0, done.stderr[-2000:]
