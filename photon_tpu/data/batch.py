"""Core data containers.

Parity target: the reference's ``LabeledPoint(label, features, offset, weight)``
with ``computeMargin = x·w + offset`` (photon-lib data/LabeledPoint.scala:30-62)
and ``RDD[LabeledPoint]`` datasets.

TPU-first design: instead of a distributed collection of per-sample records,
a ``LabeledBatch`` is a struct-of-arrays pytree — one fixed-shape batch that
jit/pjit shards across the device mesh on the sample axis. Features are either
a dense ``(n, d)`` matrix (margins are MXU matmuls) or a padded sparse
``SparseFeatures`` (fixed nnz-per-row gather form, so shapes stay static under
jit). Sample weights of 0 mark padding rows, which makes ragged data a
non-problem: every reduction is already weighted.
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# Per-backend defaults for the padded-sparse rmatvec lowering at the ingest
# boundary (FeatureShardConfig.transpose_plan=None resolves through
# ``default_transpose_plan()``). CPU: measured head-to-head on this image's
# CPU mesh (bench.py --rmatvec-cpu-ab, BENCH_FULL.md) — the duplicate-index
# scatter-add beat the column-sorted segment_sum, so no plan is attached.
# Re-confirmed on the SHARDED path (bench.py --rmatvec-sharded-ab, batch
# rows over the 8-virtual-device mesh, 2026-08-06): scatter 0.384 s vs
# segsum 0.439 s — the scatter partitions trivially on the sample axis
# (per-device partial + psum) while the flat column-sorted (n·k,) plan
# arrays cut across the row partition and cost SPMD collectives.
# TPU: segment-sum is the native lowering (XLA:TPU serializes colliding
# scatter updates, so the scatter path degenerates under index collisions);
# pinned True pending the on-chip re-run of the A/B at full run_sparse_wide
# scale — the CPU number does not transfer, and per-device row partitions
# shrink the collision profile, so the sharded on-chip A/B may narrow the
# gap but is not expected to flip it.
_TRANSPOSE_PLAN_CPU = False
_TRANSPOSE_PLAN_TPU = True


def default_transpose_plan() -> bool:
    """Backend-aware rmatvec-plan default, resolved LAZILY at dataset build
    / read time (a module-level constant would bake in whichever backend
    imported first and silently ship the CPU-measured winner to TPU)."""
    return _TRANSPOSE_PLAN_TPU if jax.default_backend() == "tpu" \
        else _TRANSPOSE_PLAN_CPU


@jax.tree_util.register_pytree_node_class
class SparseFeatures:
    """Row-padded sparse feature matrix: each row holds up to k (index, value)
    pairs; unused slots have value 0 (index arbitrary, conventionally 0).

    This is the TPU replacement for Breeze SparseVector rows: static shapes
    (n, k) so the margin is a gather + rowwise dot and the gradient is a
    scatter-add, both of which XLA compiles to efficient TPU programs.
    """

    def __init__(
        self,
        indices: Array,
        values: Array,
        dim: int,
        csc_order: Optional[Array] = None,
        csc_segments: Optional[Array] = None,
    ):
        self.indices = indices  # (n, k) int32
        self.values = values  # (n, k) float
        self.dim = int(dim)
        # Optional precomputed transpose plan (see with_transpose_plan):
        # csc_order sorts the flattened nnz entries by column, csc_segments
        # are the sorted column ids. When present, rmatvec uses a gather +
        # segment_sum instead of a duplicate-index scatter-add — the sorted
        # form is the TPU-friendly lowering (XLA serializes colliding
        # scatter updates).
        self.csc_order = csc_order  # (n*k,) int32 or None
        self.csc_segments = csc_segments  # (n*k,) int32 or None

    @property
    def shape(self):
        return (self.values.shape[0], self.dim)

    def matvec(self, w: Array) -> Array:
        """X @ w for the padded-sparse layout: (n,)."""
        return jnp.sum(self.values * w[self.indices], axis=-1)

    def rmatvec(self, r: Array) -> Array:
        """X.T @ r: segment-sum over the precomputed column-sorted plan when
        available, duplicate-index scatter-add otherwise."""
        d = self.dim
        contrib = self.values * r[:, None]  # promotes bf16 values to r.dtype
        if self.csc_order is not None:
            sorted_contrib = contrib.reshape(-1)[self.csc_order]
            return jax.ops.segment_sum(
                sorted_contrib, self.csc_segments, num_segments=d,
                indices_are_sorted=True,
            )
        # Accumulate at the PROMOTED dtype — a bf16-storage matrix must not
        # sum its gradient in bf16.
        return jnp.zeros((d,), dtype=contrib.dtype).at[self.indices].add(contrib)

    def with_transpose_plan(self) -> "SparseFeatures":
        """Return a copy carrying the column-sorted transpose plan (one host
        argsort over the static index pattern; ~2 extra int32 nnz-sized
        arrays in device memory). Host (numpy) matrices get a host plan —
        the pipeline's h2d stage places all leaves together."""
        flat = np.asarray(self.indices).reshape(-1)
        order = np.argsort(flat, kind="stable")
        as_arr = (
            np.asarray if isinstance(self.indices, np.ndarray) else jnp.asarray
        )
        return SparseFeatures(
            self.indices, self.values, self.dim,
            csc_order=as_arr(order.astype(np.int32)),
            csc_segments=as_arr(flat[order].astype(np.int32)),
        )

    def to_dense(self) -> Array:
        n, k = self.values.shape
        out = jnp.zeros((n, self.dim), dtype=self.values.dtype)
        return out.at[jnp.arange(n)[:, None], self.indices].add(self.values)

    def tree_flatten(self):
        return (
            (self.indices, self.values, self.csc_order, self.csc_segments),
            (self.dim,),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        indices, values, csc_order, csc_segments = children
        return cls(indices, values, aux[0], csc_order, csc_segments)

    @staticmethod
    def from_rows(rows, dim: int, dtype=np.float32) -> "SparseFeatures":
        """Build from a list of (indices, values) per-row pairs, padding to the
        max row nnz. Host-side (numpy) construction for ingest."""
        k = max((len(ix) for ix, _ in rows), default=1)
        k = max(k, 1)
        n = len(rows)
        indices = np.zeros((n, k), dtype=np.int32)
        values = np.zeros((n, k), dtype=dtype)
        for i, (ix, vs) in enumerate(rows):
            m = len(ix)
            indices[i, :m] = ix
            values[i, :m] = vs
        return SparseFeatures(jnp.asarray(indices), jnp.asarray(values), dim)


Features = Union[Array, SparseFeatures]


def features_dot(features: Features, w: Array) -> Array:
    """x·w for every sample as ONE pass over the features, whatever their
    storage: a matvec, where ``Coefficients.compute_score`` multiplies and
    reduces (bit-stable across batch sizes, which serving needs and training
    does not; on a TPU it is two launches around an (n, d) temporary)."""
    if isinstance(features, SparseFeatures):
        return features.matvec(w)
    return features @ w


@jax.tree_util.register_pytree_node_class
class LabeledBatch:
    """A batch of labeled samples (struct-of-arrays LabeledPoint).

    Fields mirror LabeledPoint.scala:30: label, features, offset, weight.
    ``uid`` carries the reference's UniqueSampleId for score alignment
    (GameDatum.scala:37); padding rows have weight 0.
    """

    def __init__(
        self,
        label: Array,
        features: Features,
        offset: Optional[Array] = None,
        weight: Optional[Array] = None,
        uid: Optional[Array] = None,
    ):
        n = label.shape[0]
        self.label = label
        self.features = features
        self.offset = jnp.zeros((n,), label.dtype) if offset is None else offset
        self.weight = jnp.ones((n,), label.dtype) if weight is None else weight
        self.uid = uid

    @property
    def n(self) -> int:
        return self.label.shape[0]

    @property
    def dim(self) -> int:
        return self.features.shape[1]

    def margins(self, w: Array) -> Array:
        """x·w + offset for every sample (LabeledPoint.computeMargin)."""
        return features_dot(self.features, w) + self.offset

    def with_offset(self, offset: Array) -> "LabeledBatch":
        return LabeledBatch(self.label, self.features, offset, self.weight, self.uid)

    def add_scores_to_offsets(self, scores: Array) -> "LabeledBatch":
        """Residual application (Dataset.addScoresToOffsets, reference
        data/Dataset.scala:23-31) — alignment by construction, no join."""
        return self.with_offset(self.offset + scores)

    def tree_flatten(self):
        return (self.label, self.features, self.offset, self.weight, self.uid), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        label, features, offset, weight, uid = children
        return cls(label, features, offset, weight, uid)

    @property
    def total_weight(self) -> Array:
        return jnp.sum(self.weight)
