"""TRON's Hessian-vector products on the one-read kernel.

Where the batch qualifies (a dense ``LabeledBatch`` on one device, no
wider than ``MAX_FUSED_DIM``, normalization without shifts, a TPU), the
fixed effect's ``tron`` route runs every product on
``ops.pallas_glm.data_hvp_operator``: one read of X where XLA's forward and
transpose passes make two. Off the chip the kernel interprets; the backend
test is patched where a test needs the route taken (``objective``'s
binding of ``pallas_available`` alone, so the kernel still interprets).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from photon_tpu.algorithm.solve_cache import SolveCache
from photon_tpu.data.batch import LabeledBatch, SparseFeatures
from photon_tpu.data.normalization import NormalizationContext
from photon_tpu.obs.metrics import registry
from photon_tpu.ops import objective as objective_module
from photon_tpu.ops import pallas_glm
from photon_tpu.ops.losses import LogisticLoss, SquaredLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.ops.pallas_glm import MAX_FUSED_DIM, fused_data_hvp
from photon_tpu.optim.factory import OptimizerSpec, make_optimizer
from photon_tpu.types import OptimizerType

TRON = OptimizerSpec(OptimizerType.TRON, max_iter=15, tol=1e-4)


@pytest.fixture
def on_tpu(monkeypatch):
    """The route's backend test answers as on a TPU; the kernel still runs
    in interpret mode (its own backend test is untouched)."""
    monkeypatch.setattr(objective_module, "pallas_available", lambda: True)


def _batch(loss, n=2500, d=24, seed=3):
    rng = np.random.default_rng(seed)
    # Correlated columns (AR(1) 0.8) so CG takes several steps.
    Z = rng.normal(size=(n, d))
    X = np.empty_like(Z)
    X[:, 0] = Z[:, 0]
    for j in range(1, d):
        X[:, j] = 0.8 * X[:, j - 1] + 0.6 * Z[:, j]
    X[:, 0] = 1.0
    z = X @ (rng.normal(size=d) / np.sqrt(d))
    if loss is SquaredLoss:
        y = z + rng.normal(size=n)
    else:
        y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-z))).astype(float)
    wt = rng.uniform(0.5, 2.0, size=n)
    off = 0.1 * rng.normal(size=n)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    return LabeledBatch(f32(y), f32(X), f32(off), f32(wt))


@pytest.mark.parametrize("loss", [SquaredLoss, LogisticLoss], ids=["squared", "logistic"])
def test_one_read_solve_matches_the_two_pass_solve(loss, monkeypatch):
    """The same model, iterations and CG steps on either lowering, to
    float32 rounding; only the passes it reports differ. At a tol near
    float32's resolution of the objective (1e-6 here) the last iterations
    are refused steps in rounding noise, and where they stop moves with
    any change of the products' arithmetic, either way: TRON's stopping
    rule, not the lowering, so the comparison runs at 1e-4."""
    monkeypatch.setattr(pallas_glm, "DEFAULT_TILE_N", 1024)  # three tiles, the last partial
    obj = GLMObjective(loss=loss, l2_weight=1.0, intercept_index=0)
    batch = _batch(loss)
    w0 = jnp.zeros((batch.dim,), jnp.float32)
    runs = {}
    for one_read in (False, True):
        solve = jax.jit(make_optimizer(obj, TRON, with_score=True, one_read_hvp=one_read))
        runs[one_read] = solve(w0, batch)
    (two, s2), (one, s1) = runs[False], runs[True]
    assert int(one.iterations) == int(two.iterations) >= 2
    assert int(one.cg_steps) == int(two.cg_steps) >= 2 * int(one.iterations)
    assert int(one.rejected_steps) == int(two.rejected_steps)
    rel = lambda a, b: np.linalg.norm(np.asarray(a) - b) / np.linalg.norm(b)  # noqa: E731
    assert rel(one.w, np.asarray(two.w)) <= 2e-5
    assert rel(s1, np.asarray(s2)) <= 2e-5
    its, cg = int(one.iterations), int(one.cg_steps)
    # 2 to start; an iteration: the linearized margins, the trial's value
    # and gradient (2), then one read (or two) a CG step and ρ's product;
    # 1 for the closing score pass.
    assert int(two.evals) == 2 + its * (1 + 2) + 2 * (cg + its) + 1
    assert int(one.evals) == 2 + its * (1 + 2) + (cg + its) + 1
    assert (one.hvp_passes, two.hvp_passes) == (1, 2)


@pytest.mark.parametrize("tile_n", [1024, 8192])
def test_fused_data_hvp_is_exact_where_no_tile_divides_n(tile_n, monkeypatch):
    """n = 2^16 + 37 (no tile height divides it): float32-exact against a
    float64 product, and X is never padded: the last tile masks the rows
    past the end."""
    monkeypatch.setattr(pallas_glm, "DEFAULT_TILE_N", tile_n)
    n, d = (1 << 16) + 37, 40
    rng = np.random.default_rng(5)
    X = rng.normal(size=(n, d)).astype(np.float32)
    v = rng.normal(size=d).astype(np.float32)
    d2 = rng.uniform(0.05, 1.0, size=n).astype(np.float32)
    got = jax.jit(fused_data_hvp)(jnp.asarray(v), jnp.asarray(X), jnp.asarray(d2))
    X64 = X.astype(np.float64)
    ref = X64.T @ (d2 * (X64 @ v))
    assert np.linalg.norm(np.asarray(got) - ref) / np.linalg.norm(ref) <= 1e-6
    jaxpr = jax.make_jaxpr(fused_data_hvp)(jnp.asarray(v), jnp.asarray(X), jnp.asarray(d2))
    eqns = list(_eqns(jaxpr.jaxpr))
    assert "pallas_call" in [e.primitive.name for e in eqns]
    for e in eqns:
        if e.primitive.name in ("pad", "concatenate", "dynamic_update_slice", "copy"):
            assert all(getattr(x.aval, "ndim", 0) < 2 for x in e.invars), e


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it (``jnp``
    functions trace as calls of their own), the kernel's body left out."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call":
            continue
        for p in e.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_the_route_reads_once_only_where_the_batch_qualifies(on_tpu):
    obj = GLMObjective(loss=SquaredLoss, l2_weight=1.0)
    batch = _batch(SquaredLoss, n=512, d=8)
    assert obj.one_read_hvp(batch)
    # ... and never off the chip.
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(objective_module, "pallas_available", lambda: False)
        assert not obj.one_read_hvp(batch)
    # Sparse features.
    sparse = SparseFeatures(
        indices=jnp.zeros((512, 2), jnp.int32),
        values=jnp.ones((512, 2), jnp.float32), dim=8)
    assert not obj.one_read_hvp(LabeledBatch(batch.label, sparse))
    # Wider than the kernel's limit.
    wide = jnp.ones((8, MAX_FUSED_DIM + 1), jnp.float32)
    assert not obj.one_read_hvp(LabeledBatch(jnp.zeros(8), wide))
    # A batch on more than one device: never gathered to one.
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    sharded = jax.device_put(batch.features, NamedSharding(mesh, PartitionSpec("data")))
    assert len(sharded.sharding.device_set) == 2
    assert not obj.one_read_hvp(LabeledBatch(batch.label, sharded))
    # Shift normalization.
    shifted = GLMObjective(loss=SquaredLoss, normalization=NormalizationContext(
        factors=jnp.ones(8), shifts=jnp.zeros(8), intercept_index=0))
    assert not shifted.one_read_hvp(batch)
    # use_pallas decides nothing here.
    assert GLMObjective(loss=SquaredLoss, use_pallas=True).one_read_hvp(batch)


def test_a_traced_batch_keeps_the_two_pass_form(on_tpu):
    """Inside a trace the placement is hidden: the vmapped per-entity TRON
    of the random effect (one small solve a lane) and any jitted caller
    get the two-pass product."""
    obj = GLMObjective(loss=SquaredLoss, l2_weight=1.0)
    batch = _batch(SquaredLoss, n=64, d=4)
    seen = []

    def lane(X, y):
        seen.append(obj.one_read_hvp(LabeledBatch(y, X)))
        return jnp.sum(X)

    jax.vmap(lane)(jnp.stack([batch.features] * 3), jnp.stack([batch.label] * 3))
    jax.jit(lambda b: seen.append(obj.one_read_hvp(b)) or 0.0)(batch)
    assert seen == [False, False]


def test_the_random_effect_tron_never_reaches_the_kernel(on_tpu):
    """The per-entity TRON solve lowers without a ``pallas_call``."""
    from photon_tpu.algorithm.random_effect import _solve_block
    from photon_tpu.data.random_effect import EntityBlock
    from photon_tpu.optim.common import OptimizerConfig

    lanes, rows, d = 4, 16, 3
    block = EntityBlock(
        entity_idx=jnp.arange(lanes, dtype=jnp.int32),
        features=jnp.ones((lanes, rows, d), jnp.float32),
        label=jnp.zeros((lanes, rows), jnp.float32),
        weight=jnp.ones((lanes, rows), jnp.float32),
        sample_index=jnp.zeros((lanes, rows), jnp.int32),
        train_mask=jnp.ones((lanes,), bool),
    )
    obj = GLMObjective(loss=SquaredLoss, l2_weight=1.0)
    spec = OptimizerSpec(OptimizerType.TRON)
    text = jax.jit(lambda b, o, w: _solve_block(
        b, o, w, obj, spec, OptimizerConfig(max_iter=3), None)).lower(
        block, jnp.zeros((lanes, rows), jnp.float32),
        jnp.zeros((lanes, d), jnp.float32)).as_text()
    assert "tpu_custom_call" not in text and "pallas" not in text


def test_the_solve_cache_keys_the_route_from_the_concrete_batch(on_tpu):
    """The decision is made before the trace and is part of the key: a
    batch on one device and the same batch sharded get two programs, and
    only the first reads X once a product."""
    obj = GLMObjective(loss=SquaredLoss, l2_weight=1.0, intercept_index=0)
    batch = _batch(SquaredLoss, n=512, d=8)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    put = lambda a: jax.device_put(a, NamedSharding(mesh, PartitionSpec("data")))  # noqa: E731
    sharded = LabeledBatch(put(batch.label), put(batch.features),
                           put(batch.offset), put(batch.weight))
    cache = SolveCache()
    w0 = jnp.zeros((8,), jnp.float32)
    one, _ = cache.fe_solver(obj, TRON, batch)(w0, batch)
    two, _ = cache.fe_solver(obj, TRON, sharded)(w0, sharded)
    assert cache.num_entries == 2 and cache.stats.traces == 2
    assert (one.hvp_passes, two.hvp_passes) == (1, 2)
    # A solver that is not TRON never asks.
    lbfgs = OptimizerSpec(OptimizerType.LBFGS)
    cache.fe_solver(obj, lbfgs, batch)
    cache.fe_solver(obj, lbfgs, sharded)
    assert cache.num_entries == 3


def test_product_counters_are_published_when_a_tracker_is_read(on_tpu):
    """``fe_tron_products_total`` and ``fe_tron_one_read_products_total``
    come with the tracker's read (a CG step's product and ρ's, one an
    outer iteration), and not before: a warm solve publishes nothing."""
    import dataclasses

    obj = GLMObjective(loss=SquaredLoss, l2_weight=1.0, intercept_index=0)
    batch = _batch(SquaredLoss, n=512, d=8)
    w0 = jnp.zeros((8,), jnp.float32)
    registry().reset()
    solve = SolveCache().fe_solver(obj, TRON, batch)
    for label, b in (("one_read", batch), ("two_pass", None)):
        if b is None:  # the same solve on the two-pass program
            solve = SolveCache().fe_solver(obj, TRON)
            b = batch
        res, _ = solve(w0, b)
        res = dataclasses.replace(res, coordinate=label)
        jax.block_until_ready(res.w)
        assert registry().find("fe_tron_products_total", coordinate=label) is None
        d = res.diagnostics_dict()
        res.summary()  # a second read publishes nothing more
        products = registry().find("fe_tron_products_total", coordinate=label)
        one_read = registry().find("fe_tron_one_read_products_total", coordinate=label)
        assert products.value == d["cg_steps"] + d["iterations"] > 0
        assert one_read.value == (products.value if label == "one_read" else 0)


def test_a_warm_fit_on_the_one_read_route_publishes_only_when_read(on_tpu, monkeypatch):
    """Through ``GameEstimator.fit``: the fixed effect's TRON runs every
    product on the kernel, a warm fit reads nothing back and publishes
    nothing, and the tracker's read publishes both product counters."""
    from test_fit_round_trips import estimator_of, host_reads
    from photon_tpu.data.game_data import GameBatch

    rng = np.random.default_rng(8)
    n = 1024
    Xf = rng.normal(size=(n, 6)).astype(np.float32)
    Xf[:, 0] = 1.0
    Xr = rng.normal(size=(n, 3)).astype(np.float32)
    Xr[:, 0] = 1.0
    users = rng.integers(0, 12, size=n).astype(np.int32)
    y = (Xf @ rng.normal(size=6) + rng.normal(size=n)).astype(np.float32)
    batch = GameBatch(
        label=jnp.asarray(y), offset=jnp.zeros(n, jnp.float32),
        weight=jnp.ones(n, jnp.float32),
        features={"global": jnp.asarray(Xf), "per_user": jnp.asarray(Xr)},
        entity_ids={"userId": jnp.asarray(users)},
    )
    estimator, opt, optimizer = estimator_of("linear_tron")
    assert optimizer == "tron"
    registry().reset()
    estimator.fit(batch, optimization_configs=[opt])
    with host_reads(monkeypatch) as read:
        (second,) = estimator.fit(batch, optimization_configs=[opt])
        jax.block_until_ready(jax.tree_util.tree_leaves(second.model))
    assert read == []
    assert registry().find("fe_tron_products_total", coordinate="global") is None
    diags = [d.diagnostics_dict() for d in second.tracker["global"]]
    assert all(t.hvp_passes == 1 for t in second.tracker["global"])
    products = registry().find("fe_tron_products_total", coordinate="global")
    one_read = registry().find("fe_tron_one_read_products_total", coordinate="global")
    assert products.value == sum(d["cg_steps"] + d["iterations"] for d in diags) > 0
    assert one_read.value == products.value
