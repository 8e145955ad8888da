"""Feature-dimension-sharded fixed-effect training (the TP analogue).

Parity target: the reference's answer to coefficient vectors too large for
one machine — sparse Breeze vectors plus the off-heap PalDB feature index
(photon-api index/PalDBIndexMap.scala:43-240) so "hundreds of billions of
coefficients" (README.md:56) never materialize on the driver. The TPU
analogue (SURVEY.md §2.7/§5): shard ``w`` and its gradient over the mesh's
``feature`` axis so a single fixed-effect coordinate can exceed one chip's
HBM.

Design (shard_map over a (data, feature) mesh):

- Each device along ``feature`` owns a contiguous coefficient range
  ``[lo, lo + d/F)`` of the global dimension; ``w`` lives sharded
  ``P('feature')`` and is never gathered.
- Sparse batches keep GLOBAL feature indices, rows sharded ``P('data')`` and
  replicated along ``feature``. Each device resolves only the indices that
  land in its range (mask + local gather); partial margins are psummed over
  ``feature`` — a (n_local,) all-reduce on ICI instead of an all-gather of a
  10B-coefficient vector.
- The gradient is scatter-added into the LOCAL coefficient range (each device
  owns its features outright) and psummed over ``data`` only — the same
  reduction Spark's treeAggregate performs, minus the driver round-trip.

L-BFGS runs unchanged on top: its two-loop recursion is built from dots and
axpys over (m, d) history arrays which XLA partitions along ``feature``
automatically once ``w`` is sharded (history inherits the sharding; the dots
become psums on ICI).

Normalization: scale ``factors`` fold in (a local gather, like values);
``shifts`` densify sparse rows (reference hits the same wall —
HessianMatrixAggregator.scala:27-28) and are rejected.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_tpu.data.batch import LabeledBatch, SparseFeatures
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.common import OptimizeResult, OptimizerConfig
from photon_tpu.optim.lbfgs import minimize_lbfgs
from photon_tpu.parallel.mesh import FEATURE_AXIS, dp_axes

Array = jax.Array


def padded_dim(dim: int, n_feature_shards: int) -> int:
    """Global coefficient dim padded so every feature shard is equal-sized.
    Padded coefficients start at 0, receive zero data gradient and zero L2
    gradient, and therefore stay exactly 0 through any quasi-Newton run."""
    f = n_feature_shards
    return int(np.ceil(dim / f) * f)


def _check_objective(objective: GLMObjective) -> None:
    norm = objective.normalization
    if norm is not None and norm.shifts is not None:
        raise ValueError(
            "feature-sharded training supports scale normalization only: "
            "shift normalization densifies sparse rows (same limitation the "
            "reference documents in HessianMatrixAggregator.scala:27-28); "
            "standardize to scale-only or use the replicated path"
        )


def _local_window(indices, values, shard, factors_loc):
    """Per-device view of globally-indexed sparse rows: indices mapped into
    this shard's coefficient range, values factor-folded, validity mask
    applied. The ONE place the sharding-critical window math lives — the
    gradient and Hessian paths must stay byte-for-byte consistent."""
    lo = jax.lax.axis_index(FEATURE_AXIS) * shard
    local_idx = indices - lo
    valid = (local_idx >= 0) & (local_idx < shard)
    local_idx = jnp.clip(local_idx, 0, shard - 1)
    vals = values
    if factors_loc is not None:
        vals = vals * jnp.where(valid, factors_loc[local_idx], 0.0)
    return local_idx, valid, vals


def _acc_dtype(w_dtype):
    """Accumulation dtype: at LEAST float32 (bf16 values would degrade the
    margins, the gradient, and through them the curvature pairs — same
    preferred_element_type discipline as ops/pallas_glm), but float64 is
    preserved when the coefficients are f64 (the dryrun's tight
    x64-on-CPU parity certification runs the same program at f64)."""
    return w_dtype if w_dtype == jnp.float64 else jnp.float32


def _l2_masked_local(x_loc, shard, intercept):
    """Local shard of x with the (globally-indexed) intercept zeroed."""
    xm = x_loc.astype(_acc_dtype(x_loc.dtype))
    if intercept is not None:
        lo = jax.lax.axis_index(FEATURE_AXIS) * shard
        pos = jnp.arange(shard) + lo
        xm = jnp.where(pos == intercept, 0.0, xm)
    return xm


def sparse_value_and_grad_feature_sharded(
    objective: GLMObjective, mesh: Mesh, dim: int
):
    """Build ``(w, batch) -> (value, grad)`` for a sparse LabeledBatch with
    ``w`` sharded over FEATURE_AXIS and rows sharded over DATA_AXIS.

    ``dim`` is the PADDED global dimension (a multiple of the feature-axis
    size). The returned function is jittable; ``batch.features`` must be
    SparseFeatures carrying global indices.
    """
    _check_objective(objective)
    n_feat = mesh.shape[FEATURE_AXIS]
    dp = dp_axes(mesh)
    assert dim % n_feat == 0, f"dim {dim} not divisible by feature axis {n_feat}"
    shard = dim // n_feat
    loss = objective.loss
    l2 = objective.l2_weight
    intercept = objective.intercept_index
    factors = None if objective.normalization is None else objective.normalization.factors

    def local_fn(w_loc, indices, values, label, offset, weight, factors_loc):
        """Runs per device: w_loc (shard,), rows local along data."""
        local_idx, valid, vals = _local_window(indices, values, shard, factors_loc)

        # Accumulation in _acc_dtype (≥ f32; f64 preserved for the x64
        # parity certification) regardless of the feature-value dtype.
        acc = _acc_dtype(w_loc.dtype)
        gathered = jnp.where(valid, w_loc[local_idx], 0.0)
        z_partial = jnp.sum(
            (vals * gathered).astype(acc), axis=-1
        )
        z = jax.lax.psum(z_partial, FEATURE_AXIS) + offset

        lv = loss.value(z, label)
        dz = weight * loss.dz(z, label)
        loss_local = jnp.sum(weight * lv).astype(acc)

        # Scatter-add into the local coefficient range only.
        contrib = jnp.where(valid, vals * dz[:, None], 0.0).astype(acc)
        grad_loc = jnp.zeros((shard,), acc).at[
            local_idx.reshape(-1)
        ].add(contrib.reshape(-1))
        grad_loc = jax.lax.psum(grad_loc, dp)

        # L2 on the local shard; the (global) intercept is exempt.
        if l2 != 0.0:
            wm = _l2_masked_local(w_loc, shard, intercept)
            grad_loc = grad_loc + l2 * wm
            l2_local = 0.5 * l2 * jnp.sum(wm * wm)
        else:
            l2_local = jnp.zeros((), acc)

        value = jax.lax.pmean(
            jax.lax.psum(loss_local, dp), FEATURE_AXIS
        ) + jax.lax.pmean(jax.lax.psum(l2_local, FEATURE_AXIS), dp)
        return value, grad_loc

    in_specs = (
        P(FEATURE_AXIS),          # w
        P(dp, None),              # indices
        P(dp, None),              # values
        P(dp),                    # label
        P(dp),                    # offset
        P(dp),                    # weight
    )
    factor_spec = (P(FEATURE_AXIS),) if factors is not None else ()
    shmapped = jax.shard_map(
        (lambda w, i, v, y, o, wt, f: local_fn(w, i, v, y, o, wt, f))
        if factors is not None
        else (lambda w, i, v, y, o, wt: local_fn(w, i, v, y, o, wt, None)),
        mesh=mesh,
        in_specs=in_specs + factor_spec,
        out_specs=(P(), P(FEATURE_AXIS)),
    )

    def value_and_grad(w: Array, batch: LabeledBatch) -> Tuple[Array, Array]:
        feats = batch.features
        assert isinstance(feats, SparseFeatures)
        args = (w, feats.indices, feats.values, batch.label, batch.offset, batch.weight)
        if factors is not None:
            args = args + (factors,)
        return shmapped(*args)

    return value_and_grad


def sparse_linearized_hvp_feature_sharded(
    objective: GLMObjective, mesh: Mesh, dim: int
):
    """Build ``make_hvp(w, batch) -> (v -> H(w)·v)`` with ``w``/``v``
    feature-sharded and rows data-sharded — the distributed counterpart of
    GLMObjective.linearized_hvp (reference: the distributed objective's
    hessianVector treeAggregate, HessianVectorAggregator.scala, one round
    per CG product). Curvature d2 = weight·loss''(z,y) is computed ONCE per
    outer iterate (one sharded margins pass, psum over ``feature``); each
    product is then one forward + one scatter-add transpose pass with a
    psum over ``feature`` (for u) and one over ``data`` (for the result) —
    both on ICI.
    """
    _check_objective(objective)
    n_feat = mesh.shape[FEATURE_AXIS]
    dp = dp_axes(mesh)
    assert dim % n_feat == 0, f"dim {dim} not divisible by feature axis {n_feat}"
    shard = dim // n_feat
    loss = objective.loss
    l2 = objective.l2_weight
    intercept = objective.intercept_index
    factors = None if objective.normalization is None else objective.normalization.factors

    def local_d2(w_loc, indices, values, label, offset, weight, factors_loc):
        local_idx, valid, vals = _local_window(indices, values, shard, factors_loc)
        gathered = jnp.where(valid, w_loc[local_idx], 0.0)
        z_partial = jnp.sum(
            (vals * gathered).astype(_acc_dtype(w_loc.dtype)), axis=-1
        )
        z = jax.lax.psum(z_partial, FEATURE_AXIS) + offset
        return weight * loss.dzz(z, label)

    def local_hv(v_loc, indices, values, d2, factors_loc):
        local_idx, valid, vals = _local_window(indices, values, shard, factors_loc)
        acc = _acc_dtype(v_loc.dtype)
        v_gather = jnp.where(valid, v_loc[local_idx], 0.0)
        u_partial = jnp.sum((vals * v_gather).astype(acc), axis=-1)
        u = jax.lax.psum(u_partial, FEATURE_AXIS)  # (A·v) on each data shard
        t = d2 * u
        contrib = jnp.where(valid, vals * t[:, None], 0.0).astype(acc)
        hv_loc = jnp.zeros((shard,), acc).at[
            local_idx.reshape(-1)
        ].add(contrib.reshape(-1))
        hv_loc = jax.lax.psum(hv_loc, dp)
        if l2 != 0.0:
            hv_loc = hv_loc + l2 * _l2_masked_local(v_loc, shard, intercept)
        return hv_loc

    row_specs = (P(dp, None), P(dp, None))  # indices, values
    factor_spec = (P(FEATURE_AXIS),) if factors is not None else ()
    d2_shmapped = jax.shard_map(
        (lambda w, i, v, y, o, wt, f: local_d2(w, i, v, y, o, wt, f))
        if factors is not None
        else (lambda w, i, v, y, o, wt: local_d2(w, i, v, y, o, wt, None)),
        mesh=mesh,
        in_specs=(P(FEATURE_AXIS),) + row_specs + (P(dp), P(dp), P(dp)) + factor_spec,
        out_specs=P(dp),
    )
    hv_shmapped = jax.shard_map(
        (lambda v, i, vl, d2, f: local_hv(v, i, vl, d2, f))
        if factors is not None
        else (lambda v, i, vl, d2: local_hv(v, i, vl, d2, None)),
        mesh=mesh,
        in_specs=(P(FEATURE_AXIS),) + row_specs + (P(dp),) + factor_spec,
        out_specs=P(FEATURE_AXIS),
    )

    def make_hvp(w: Array, batch: LabeledBatch):
        feats = batch.features
        assert isinstance(feats, SparseFeatures)
        args = (w, feats.indices, feats.values, batch.label, batch.offset, batch.weight)
        if factors is not None:
            args = args + (factors,)
        d2 = d2_shmapped(*args)

        def hv(v: Array) -> Array:
            hv_args = (v, feats.indices, feats.values, d2)
            if factors is not None:
                hv_args = hv_args + (factors,)
            return hv_shmapped(*hv_args)

        return hv

    return make_hvp


def place_feature_sharded(
    mesh: Mesh, w: Array, batch: LabeledBatch
) -> Tuple[Array, LabeledBatch]:
    """device_put ``w`` P('feature') and the sparse batch rows P('data')."""
    dp = dp_axes(mesh)
    wsh = NamedSharding(mesh, P(FEATURE_AXIS))
    rows = NamedSharding(mesh, P(dp))
    rows2d = NamedSharding(mesh, P(dp, None))
    feats = batch.features
    assert isinstance(feats, SparseFeatures)
    put = jax.device_put
    feats = SparseFeatures(put(feats.indices, rows2d), put(feats.values, rows2d), feats.dim)
    placed = LabeledBatch(
        label=put(batch.label, rows),
        features=feats,
        offset=put(batch.offset, rows),
        weight=put(batch.weight, rows),
        uid=None if batch.uid is None else put(batch.uid, rows),
    )
    return put(w, wsh), placed


def train_fixed_effect_feature_sharded(
    mesh: Mesh,
    objective: GLMObjective,
    config: OptimizerConfig,
    dim: int,
    box: Optional[Tuple[Array, Array]] = None,
    solver: str = "lbfgs",
    max_cg_iter: int = 20,
):
    """Jitted fit of a sparse fixed-effect coordinate with ``w``
    feature-sharded over the mesh (reference FixedEffectCoordinate.trainModel
    role, FixedEffectCoordinate.scala:115-129, for coordinates whose ``w``
    exceeds one chip's HBM).

    ``solver``: ``"lbfgs"`` (default) or ``"tron"`` — TRON rides the
    sharded linearized HVP (one psum pair per CG product, the reference's
    distributed hessianVector).

    Returns ``fit(w0, batch) -> OptimizeResult`` with ``result.w`` sharded
    P('feature'). ``dim`` must be pre-padded (see ``padded_dim``).
    """
    if solver not in ("lbfgs", "tron"):
        raise ValueError(f"unknown feature-sharded solver {solver!r}")
    vg = sparse_value_and_grad_feature_sharded(objective, mesh, dim)
    make_hvp = (
        sparse_linearized_hvp_feature_sharded(objective, mesh, dim)
        if solver == "tron"
        else None
    )

    @functools.partial(
        jax.jit,
        in_shardings=(
            NamedSharding(mesh, P(FEATURE_AXIS)),
            NamedSharding(mesh, P(dp_axes(mesh))),
            NamedSharding(mesh, P(dp_axes(mesh), None)),
            NamedSharding(mesh, P(dp_axes(mesh), None)),
            NamedSharding(mesh, P(dp_axes(mesh))),
            NamedSharding(mesh, P(dp_axes(mesh))),
        ),
    )
    def fit(w0, label, indices, values, offset, weight) -> OptimizeResult:
        batch = LabeledBatch(
            label, SparseFeatures(indices, values, dim), offset, weight
        )
        if solver == "tron":
            from photon_tpu.optim.tron import minimize_tron

            return minimize_tron(
                lambda w: vg(w, batch), None, w0, config, max_cg_iter, box,
                hvp_factory=lambda w: make_hvp(w, batch),
            )
        return minimize_lbfgs(lambda w: vg(w, batch), w0, config, box=box)

    def fit_batch(w0: Array, batch: LabeledBatch) -> OptimizeResult:
        feats = batch.features
        assert isinstance(feats, SparseFeatures)
        return fit(
            w0, batch.label, feats.indices, feats.values, batch.offset, batch.weight
        )

    return fit_batch
