"""GAME data containers: multi-shard batches with entity ids.

Parity target: reference ``GameDatum`` (response/offset/weight + per-shard
feature vectors + id-tag map, photon-api data/GameDatum.scala:37-68) and the
``RDD[(UniqueSampleId, GameDatum)]`` game dataset.

TPU-first design: one struct-of-arrays ``GameBatch`` holds every sample's
label/offset/weight, a feature matrix per feature shard, and a dense int32
entity index per random-effect type. Entity ids are interned to [0, E) at
ingest (see photon_tpu.data.index_map.EntityIndex); -1 marks entities unseen
at training time (cold start → that coordinate contributes score 0, matching
the reference's behavior of missing random-effect models). Residual exchange
between coordinates is pure array arithmetic on aligned score vectors — the
reference's outer-join score algebra (DataScores.scala:33-157) disappears.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from photon_tpu.data.batch import Features, LabeledBatch, SparseFeatures

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GameBatch:
    """All samples for training/scoring, aligned on a single sample axis."""

    label: Array
    offset: Array
    weight: Array
    features: Dict[str, Features]  # feature-shard name -> (n, d_shard)
    entity_ids: Dict[str, Array]  # RE type name -> (n,) int32 dense entity idx
    uid: Optional[Array] = None

    @property
    def n(self) -> int:
        return self.label.shape[0]

    def labeled_batch(self, shard: str, extra_offset: Optional[Array] = None) -> LabeledBatch:
        """Project to a single-shard LabeledBatch
        (GameDatum.generateLabeledPointWithFeatureShardId role)."""
        offset = self.offset if extra_offset is None else self.offset + extra_offset
        return LabeledBatch(self.label, self.features[shard], offset, self.weight, self.uid)

    def with_offset(self, offset: Array) -> "GameBatch":
        return dataclasses.replace(self, offset=offset)


def _take_feature_rows(f: Features, order: Array, row_of: Array) -> Features:
    """A feature shard's rows reordered. A sparse shard's transpose plan
    indexes its flattened (n·k) entries, not its rows: each entry moves to
    where its row went, and the column ids it is sorted by stay as they
    are, so ``rmatvec`` sums every column in the order it did before."""
    if not isinstance(f, SparseFeatures):
        return jnp.take(f, order, axis=0)
    plan = {}
    if f.csc_order is not None:
        k = f.indices.shape[1]
        entry = f.csc_order
        plan = dict(
            csc_order=row_of[entry // k] * k + entry % k,
            csc_segments=f.csc_segments,
        )
    return SparseFeatures(
        jnp.take(f.indices, order, axis=0), jnp.take(f.values, order, axis=0),
        f.dim, **plan,
    )


@jax.jit
def take_rows(batch: GameBatch, order: Array) -> GameBatch:
    """The batch with its rows reordered: row ``i`` of the result is row
    ``order[i]`` of ``batch``. One gather an array, on the device that holds
    them."""
    row_of = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.size, dtype=order.dtype)
    )

    def rows(a: Array) -> Array:
        return jnp.take(a, order, axis=0)

    return GameBatch(
        label=rows(batch.label),
        offset=rows(batch.offset),
        weight=rows(batch.weight),
        features={
            s: _take_feature_rows(f, order, row_of)
            for s, f in batch.features.items()
        },
        entity_ids={t: rows(e) for t, e in batch.entity_ids.items()},
        uid=None if batch.uid is None else rows(batch.uid),
    )


@dataclasses.dataclass(frozen=True)
class RowLayout:
    """The order a batch's rows are trained in: row ``i`` of the laid-out
    batch is row ``order[i]`` of the batch as given; None keeps them as
    given. What outlives a fit or is drawn by row (a checkpoint's score
    vectors, a down-sampling draw) stays in the order given, so a run laid
    out otherwise, or not at all, reads the same rows."""

    order: Optional[Array] = None

    def apply(self, batch: GameBatch) -> GameBatch:
        return batch if self.order is None else take_rows(batch, self.order)

    def from_original(self, a: Array) -> Array:
        """A per-row vector in the order given, laid out."""
        return a if self.order is None else jnp.asarray(a)[self.order]

    def to_original(self, a: Array) -> Array:
        """A per-row vector of the laid-out batch, in the order given."""
        if self.order is None:
            return a
        return jnp.zeros_like(a).at[self.order].set(a)
