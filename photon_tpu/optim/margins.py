"""A GLM objective over one ``LabeledBatch`` in margin space: the algebra the
margin-space solvers (``optim/margin_lbfgs.py``, ``optim/margin_owlqn.py``)
share.

The margins z = X·(f∘w) − s·(f∘w) + offset are linear in w (normalization
factors f and shifts s folded, photon_tpu.data.normalization), so a solver
that carries z answers the value anywhere along a direction from products
it already holds and reads X only for new directions and gradients.
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from photon_tpu.data.batch import LabeledBatch, SparseFeatures
from photon_tpu.ops.objective import GLMObjective

Array = jax.Array


class GLMMargins:
    """``objective`` over ``batch``, in terms of margins. ``l2_override``
    replaces the objective's static L2 weight with a traced scalar (the
    vmapped λ sweep's hook)."""

    def __init__(
        self,
        objective: GLMObjective,
        batch: LabeledBatch,
        l2_override: Optional[Union[Array, float]] = None,
    ):
        self.objective = objective
        self.loss = objective.loss
        self.l2 = objective.l2_weight if l2_override is None else l2_override
        self.has_l2 = l2_override is not None or objective.l2_weight != 0.0
        norm = objective.normalization
        self.factors = None if norm is None or norm.is_identity else norm.factors
        self.shifts = None if norm is None or norm.is_identity else norm.shifts
        self.label, self.weight, self.offset = batch.label, batch.weight, batch.offset
        self.feats = batch.features
        self.batch = batch
        # Fused Pallas gradient pass: one X read yields value + gradient +
        # FRESH margins (ops/pallas_glm), replacing the separate Xᵀ·dz pass
        # and the incremental margin update. The carried margins are exact
        # every iteration — which is what makes bfloat16 X safe (no
        # accumulated drift), halving the bandwidth-bound HBM traffic.
        # Caller contract (ops/objective._can_fuse): dense unsharded
        # features, no shift normalization, d within the VMEM tile budget.
        self.use_fused = objective.use_pallas and objective._can_fuse(batch)

    def matvec(self, p: Array) -> Array:
        """u = d(margins)/dα along direction p (normalization folded)."""
        ep = p if self.factors is None else p * self.factors
        feats = self.feats
        if isinstance(feats, SparseFeatures):
            u = feats.matvec(ep)
        elif feats.dtype == jnp.bfloat16:
            # bf16 X stream with f32 accumulation on the MXU; the bf16
            # rounding of the direction only perturbs the line-search
            # parametrization (the accepted w stays f32, and the fused
            # gradient pass refreshes margins exactly from it).
            u = jnp.dot(feats, ep.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        else:
            u = feats @ ep
        if self.shifts is not None:
            u = u - jnp.dot(self.shifts, ep)
        return u

    def matvecs(self, P: Array) -> Array:
        """``matvec`` of every row of P (k, d) as ONE read of X: (k, n).
        Dense float32 X is multiplied and reduced in float32, which XLA on a
        TPU lowers to one multiply-and-reduce fusion that reads X once (an
        MXU dot at default precision would round X to bfloat16)."""
        EP = P if self.factors is None else P * self.factors
        feats = self.feats
        if isinstance(feats, SparseFeatures):
            U = jax.vmap(feats.matvec)(EP)
        elif feats.dtype == jnp.bfloat16:
            U = jnp.dot(EP.astype(jnp.bfloat16), feats.T,
                        preferred_element_type=jnp.float32)
        else:
            U = jnp.sum(feats[None, :, :] * EP[:, None, :], axis=2)
        if self.shifts is not None:
            U = U - (EP @ self.shifts)[:, None]
        return U

    def margins(self, w: Array) -> Array:
        """z at w, offset included: one pass over X."""
        return self.objective.margins(w, self.batch)

    def fused_value_grad_margins(self, w: Array) -> Tuple[Array, Array, Array]:
        """One X pass: value, gradient, and fresh margins at w."""
        from photon_tpu.ops.pallas_glm import fused_data_value_and_grad

        factors = self.factors
        ew = w if factors is None else w * factors
        val, g, z = fused_data_value_and_grad(
            self.loss, ew, self.feats, self.label, self.offset, self.weight,
            return_margins=True,
        )
        if factors is not None:
            g = g * factors
        if self.has_l2:
            g = g + self.l2 * self.l2_mask(w)
        return val + self.l2_value(w), g, z

    def grad_from_margins(self, z: Array, w: Array) -> Array:
        """The smooth gradient at w from its margins z: one Xᵀ pass."""
        dz = self.weight * self.loss.dz(z, self.label)
        feats = self.feats
        g = feats.rmatvec(dz) if isinstance(feats, SparseFeatures) else feats.T @ dz
        if self.shifts is not None:
            g = g - jnp.sum(dz) * self.shifts
        if self.factors is not None:
            g = g * self.factors
        if self.has_l2:
            g = g + self.l2 * self.l2_mask(w)
        return g

    def l2_mask(self, w: Array) -> Array:
        if self.objective.intercept_index is None:
            return w
        return w.at[self.objective.intercept_index].set(0.0)

    def data_value(self, z: Array) -> Array:
        return jnp.sum(self.weight * self.loss.value(z, self.label))

    def l2_value(self, w: Array) -> Array:
        if not self.has_l2:
            return jnp.zeros((), w.dtype)
        wm = self.l2_mask(w)
        return 0.5 * self.l2 * jnp.dot(wm, wm)

    def start(
        self, w0: Array, start_score: Optional[Array]
    ) -> Tuple[Array, Array, Array, int]:
        """``(f0, g0, z0, passes)``: smooth value, gradient and margins at w0,
        and the passes over X they took: one fused pass, else the gradient's
        after the margins' unless the caller holds x·w0 (``start_score``,
        which the fused pass has no use for)."""
        if self.use_fused:
            f0, g0, z0 = self.fused_value_grad_margins(w0)
            return f0, g0, z0, 1
        if start_score is None:
            z0, passes = self.margins(w0), 2
        else:
            z0, passes = start_score + self.offset, 1
        f0 = self.data_value(z0) + self.l2_value(w0)
        return f0, self.grad_from_margins(z0, w0), z0, passes
