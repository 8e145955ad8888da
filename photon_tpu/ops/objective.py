"""GLM objective functions: value / gradient / Hessian-vector products.

Parity target: the reference's ObjectiveFunction hierarchy —
``ObjectiveFunction → DiffFunction → TwiceDiffFunction`` (photon-lib
function/ObjectiveFunction.scala:26, DiffFunction.scala:49,
TwiceDiffFunction.scala:34-60), the L2Regularization mixins
(L2Regularization.scala:26-255), and the four aggregators that compute
Σloss/Σgrad/H·v/diag(H)/H over distributed data
(photon-lib aggregators/*.scala).

TPU-first design: there is no aggregator layer at all. The objective is a pure
function ``w → Σ_i weight_i · loss(x_i·w + offset_i, y_i) + reg``; the gradient
is ``jax.grad``, the Hessian-vector product is a forward-over-reverse
``jax.jvp(jax.grad(f))``. Under ``jit`` with the batch sharded over a mesh's
sample axis, XLA inserts the cross-device reductions (the role of Spark
``treeAggregate``, reference ValueAndGradientAggregator.scala:300-321)
automatically; under ``shard_map`` the caller psums the outputs
(photon_tpu.parallel.distributed). Normalization is folded algebraically in
front of the margin matmul (see photon_tpu.data.normalization), exactly the
fold the reference derives by hand in ValueAndGradientAggregator.scala:41-148.

The **sum is weighted, not averaged**, matching the reference's aggregator
semantics (regularization weights are comparable across frameworks).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from photon_tpu.data.batch import LabeledBatch, SparseFeatures, features_dot
from photon_tpu.data.normalization import NormalizationContext
from photon_tpu.ops.losses import PointwiseLoss
from photon_tpu.ops.pallas_glm import (
    MAX_FUSED_DIM,
    data_hvp_operator,
    fused_data_value_and_grad,
    pallas_available,
)

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GLMObjective:
    """Smooth part of a GLM objective (loss + L2). The L1 weight is carried
    here for OWL-QN (reference OWLQN.scala:39-70) but is NOT part of the
    smooth value/gradient, matching the reference split where Breeze's OWLQN
    owns the L1 term.

    ``intercept_index`` is excluded from both L1 and L2 regularization
    (reference L2Regularization.scala interceptOpt).
    """

    loss: PointwiseLoss = dataclasses.field(metadata=dict(static=True))
    l2_weight: float = 0.0
    l1_weight: float = 0.0
    intercept_index: Optional[int] = dataclasses.field(
        default=None, metadata=dict(static=True)
    )
    normalization: Optional[NormalizationContext] = None
    # Route dense value_and_grad through the fused Pallas kernel (one HBM
    # pass over X instead of XLA's two; photon_tpu.ops.pallas_glm). Falls
    # back automatically where the kernel doesn't apply (sparse features,
    # shift normalization, very wide dims). Hessian-vector products do not
    # read it: their lowering is ``linearized_hvp``'s ``one_read``.
    use_pallas: bool = dataclasses.field(default=False, metadata=dict(static=True))

    # ----- margins -----

    def margins(self, w: Array, batch: LabeledBatch) -> Array:
        if self.normalization is not None and not self.normalization.is_identity:
            ew, es = self.normalization.effective(w)
            return batch.margins(ew) + es
        return batch.margins(w)

    def scores(self, w: Array, batch: LabeledBatch) -> Array:
        """x·w for every sample, normalization folded: the margins less the
        batch's offset, and what the model made of ``w`` scores in model
        space. One pass over X."""
        if self.normalization is not None and not self.normalization.is_identity:
            ew, es = self.normalization.effective(w)
            return features_dot(batch.features, ew) + es
        return features_dot(batch.features, w)

    # ----- regularization -----

    def _l2_mask(self, w: Array) -> Array:
        if self.intercept_index is None:
            return w
        return w.at[self.intercept_index].set(0.0)

    def l2_term(self, w: Array) -> Array:
        if self.l2_weight == 0.0:
            return jnp.zeros((), w.dtype)
        wm = self._l2_mask(w)
        return 0.5 * self.l2_weight * jnp.dot(wm, wm)

    def l1_term(self, w: Array) -> Array:
        """Nonsmooth term, for reporting/OWL-QN only."""
        if self.l1_weight == 0.0:
            return jnp.zeros((), w.dtype)
        return self.l1_weight * jnp.sum(jnp.abs(self._l2_mask(w)))

    def l1_mask(self, w: Array) -> Optional[Array]:
        """The 0/1 vector OWL-QN multiplies its L1 weight by: 0 at the
        intercept, which is unpenalised; None where there is no intercept."""
        if self.intercept_index is None:
            return None
        return jnp.ones_like(w).at[self.intercept_index].set(0.0)

    # ----- ObjectiveFunction.value -----

    def value(self, w: Array, batch: LabeledBatch) -> Array:
        z = self.margins(w, batch)
        return jnp.sum(batch.weight * self.loss.value(z, batch.label)) + self.l2_term(w)

    # ----- DiffFunction.calculate -----

    def value_and_grad(self, w: Array, batch: LabeledBatch) -> Tuple[Array, Array]:
        if self._can_fuse(batch):
            return self._pallas_value_and_grad(w, batch)
        return jax.value_and_grad(self.value)(w, batch)

    def _can_fuse(self, batch: LabeledBatch) -> bool:
        return self.use_pallas and self._fusible(batch)

    def _fusible(self, batch: LabeledBatch) -> bool:
        """What the fused kernels need of a batch: dense features no wider
        than ``MAX_FUSED_DIM``, normalization without shifts, and data on
        one device where the placement is visible (concrete arrays)."""
        feats = batch.features
        if isinstance(feats, SparseFeatures) or feats.shape[1] > MAX_FUSED_DIM:
            return False
        # A pallas_call on a batch sharded over the mesh's data axis would
        # gather X to one device, silently defeating the data-parallel path
        # — require single-device data where the placement is visible
        # (concrete arrays). Sharded entry points must strip use_pallas
        # (glmix_sharded_train_step does) or shard_map around the solver.
        if isinstance(feats, jax.Array) and not isinstance(
            feats, jax.core.Tracer
        ):
            try:
                if len(feats.sharding.device_set) > 1:
                    return False
            except Exception:  # pragma: no cover - sharding introspection
                return False
        norm = self.normalization
        return norm is None or norm.shifts is None

    def one_read_hvp(self, batch) -> bool:
        """Whether Hessian-vector products over ``batch`` take the one-read
        kernel (``ops.pallas_glm.data_hvp_operator``), from static facts
        alone: a ``LabeledBatch`` of dense features no wider than
        ``MAX_FUSED_DIM``, on ONE device, normalization without shifts, and
        a TPU backend. Ask it of a CONCRETE batch: a traced one (inside
        ``jit`` or ``vmap``) hides where it lives, and a batch sharded over
        devices must never be gathered to one, so a tracer answers False."""
        if not (pallas_available() and isinstance(batch, LabeledBatch)):
            return False
        feats = batch.features
        concrete = isinstance(feats, jax.Array) and not isinstance(
            feats, jax.core.Tracer)
        return concrete and self._fusible(batch)

    def _pallas_value_and_grad(self, w: Array, batch: LabeledBatch) -> Tuple[Array, Array]:
        f = None if self.normalization is None else self.normalization.factors
        ew = w if f is None else w * f
        val, g = fused_data_value_and_grad(
            self.loss, ew, batch.features, batch.label, batch.offset, batch.weight
        )
        if f is not None:
            g = g * f
        if self.l2_weight != 0.0:
            val = val + self.l2_term(w)
            g = g + self.l2_weight * self._l2_mask(w)
        return val.astype(w.dtype), g.astype(w.dtype)

    def grad(self, w: Array, batch: LabeledBatch) -> Array:
        return jax.grad(self.value)(w, batch)

    # ----- TwiceDiffFunction.hessianVector (HessianVectorAggregator role) -----

    def hvp(self, w: Array, v: Array, batch: LabeledBatch) -> Array:
        """Forward-over-reverse Hessian-vector product: one extra fused pass,
        no Hessian materialization (reference HessianVectorAggregator.scala)."""
        return jax.jvp(lambda u: self.grad(u, batch), (w,), (v,))[1]

    def linearized_hvp(self, w: Array, batch: LabeledBatch, one_read: bool = False):
        """Build ``v -> H(w)·v`` with all w-dependent state computed ONCE.

        The GLM Hessian at fixed ``w`` is H = Aᵀ·diag(d2)·A + λ·mask, where
        A = ∂margins/∂w is CONSTANT (margins is affine in w, normalization
        folding included) and d2 = weight·loss''(z, y) depends on w only
        through the margins z. The jvp-of-grad form recomputes z and the
        gradient inside every product (~4 X passes); here z/d2 are cached
        so each product is one forward and one transpose pass, two reads
        of X — the same per-outer-iteration caching the reference's
        HessianVectorAggregator gets from broadcasting the fixed
        coefficients once per CG solve (HessianVectorAggregator.scala).
        Inner solvers (TRON's truncated CG) should prefer this via
        ``minimize_tron(hvp_factory=...)``.

        ``one_read`` (a caller that asked ``one_read_hvp`` of the concrete
        batch: the fixed effect's TRON route of ``optim/factory.py``) runs
        each product on ``ops.pallas_glm.data_hvp_operator`` instead: the
        forward and transpose products share ONE read of each X tile.
        Every other caller (the vmapped per-entity TRON, sparse, wide or
        sharded batches) keeps the two-pass form.
        """
        if one_read:
            z = self.margins(w, batch)
            d2 = batch.weight * self.loss.dzz(z, batch.label)
            data_hvp = data_hvp_operator(batch.features, d2)
            f = None if self.normalization is None else self.normalization.factors

            def hv_one_read(v: Array) -> Array:
                out = data_hvp(v if f is None else v * f)
                if f is not None:
                    out = out * f
                if self.l2_weight != 0.0:
                    out = out + self.l2_weight * self._l2_mask(v)
                return out.astype(v.dtype)

            return hv_one_read

        mfun = lambda ww: self.margins(ww, batch)  # noqa: E731
        z, lin = jax.linearize(mfun, w)
        # Transpose of the (already-linear) tangent map — no second forward
        # evaluation of the margins, unlike jax.vjp(mfun, w).
        lin_t = jax.linear_transpose(lin, w)
        d2 = batch.weight * self.loss.dzz(z, batch.label)

        def hv(v: Array) -> Array:
            out = lin_t(d2 * lin(v))[0]
            if self.l2_weight != 0.0:
                out = out + self.l2_weight * self._l2_mask(v)
            return out

        return hv

    # ----- TwiceDiffFunction.hessianDiagonal -----

    def hessian_diagonal(self, w: Array, batch: LabeledBatch) -> Array:
        """diag(H) = Σ_i weight_i · dzz_i · x_ij² (+λ), with normalization
        folded into effective features (HessianDiagonalAggregator.scala)."""
        z = self.margins(w, batch)
        d2 = batch.weight * self.loss.dzz(z, batch.label)
        feats = batch.features
        if self.normalization is not None and self.normalization.factors is not None:
            f = self.normalization.factors
        else:
            f = None
        if isinstance(feats, SparseFeatures):
            vals = feats.values
            if f is not None:
                vals = vals * f[feats.indices]
            if self.normalization is not None and self.normalization.shifts is not None:
                # Shifted sparse features densify; fall back to dense math.
                return self._hessian_diag_dense(feats.to_dense(), d2)
            contrib = (vals * vals) * d2[:, None]
            diag = jnp.zeros((feats.dim,), vals.dtype).at[feats.indices].add(contrib)
        else:
            diag = self._hessian_diag_dense(feats, d2)
        if self.l2_weight != 0.0:
            lam = jnp.full_like(diag, self.l2_weight)
            if self.intercept_index is not None:
                lam = lam.at[self.intercept_index].set(0.0)
            diag = diag + lam
        return diag

    def _hessian_diag_dense(self, X: Array, d2: Array) -> Array:
        if self.normalization is not None and not self.normalization.is_identity:
            f = self.normalization.factors
            s = self.normalization.shifts
            if f is not None:
                X = X * f[None, :]
            if s is not None:
                fs = s if f is None else s * f
                X = X - fs[None, :]
                if self.normalization.intercept_index is not None:
                    X = X.at[:, self.normalization.intercept_index].set(1.0)
        return jnp.einsum("n,nd->d", d2, X * X)

    # ----- TwiceDiffFunction.hessianMatrix (HessianMatrixAggregator role) -----

    def hessian_matrix(self, w: Array, batch: LabeledBatch) -> Array:
        """Full H = Xᵀ D X + λI — for variance computation on small problems
        (reference HessianMatrixAggregator.scala:34-157, no-normalization note
        :27-28 — here normalization IS supported via densified features)."""
        z = self.margins(w, batch)
        d2 = batch.weight * self.loss.dzz(z, batch.label)
        feats = batch.features
        X = feats.to_dense() if isinstance(feats, SparseFeatures) else feats
        if self.normalization is not None and not self.normalization.is_identity:
            f = self.normalization.factors
            s = self.normalization.shifts
            if f is not None:
                X = X * f[None, :]
            if s is not None:
                fs = s if f is None else s * f
                X = X - fs[None, :]
                if self.normalization.intercept_index is not None:
                    X = X.at[:, self.normalization.intercept_index].set(1.0)
        H = jnp.einsum("nd,n,ne->de", X, d2, X)
        if self.l2_weight != 0.0:
            lam = jnp.full((X.shape[1],), self.l2_weight, X.dtype)
            if self.intercept_index is not None:
                lam = lam.at[self.intercept_index].set(0.0)
            H = H + jnp.diag(lam)
        return H

    # ----- convenience -----

    def full_value(self, w: Array, batch: LabeledBatch) -> Array:
        """Smooth value + L1 term (the quantity OWL-QN minimizes)."""
        return self.value(w, batch) + self.l1_term(w)

    def with_l2(self, l2_weight: float) -> "GLMObjective":
        """Mutable-regularization-weight analogue for λ sweeps
        (reference DistributedOptimizationProblem.scala:63-74)."""
        return dataclasses.replace(self, l2_weight=l2_weight)

    def with_l1(self, l1_weight: float) -> "GLMObjective":
        return dataclasses.replace(self, l1_weight=l1_weight)
