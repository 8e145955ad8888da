"""Down-sampling for fixed-effect training.

Parity target: reference ``DownSampler`` trait (photon-lib
sampling/DownSampler.scala:28-67), ``BinaryClassificationDownSampler``
(negatives only, reweighted; BinaryClassificationDownSampler.scala:32) and
``DefaultDownSampler`` (DefaultDownSampler.scala:28), selected per task by
``DownSamplerHelper`` (photon-api sampling/DownSamplerHelper.scala).

TPU-first: sampling is a deterministic-by-seed weight mask — dropped samples
get weight 0, kept samples are reweighted by 1/rate, and shapes never change
(no filter/shuffle). Weighted objectives make this exact.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from photon_tpu.data.batch import LabeledBatch
from photon_tpu.data.game_data import RowLayout
from photon_tpu.types import TaskType

Array = jax.Array


@dataclasses.dataclass
class DownSampler:
    """Uniform down-sampling of all samples (DefaultDownSampler role)."""

    rate: float
    seed: int = 0
    # The order of the batch it samples (``GameEstimator`` lays a batch out
    # in entity runs): the draw is made in the order given, so a seed keeps
    # the same rows.
    layout: RowLayout = RowLayout()

    def _keep(self, n: int, salt: int) -> Array:
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), salt)
        return self.layout.from_original(jax.random.uniform(key, (n,))) < self.rate

    def apply(self, batch: LabeledBatch) -> LabeledBatch:
        keep = self._keep(batch.n, 0)
        new_w = jnp.where(keep, batch.weight / self.rate, 0.0)
        return LabeledBatch(batch.label, batch.features, batch.offset, new_w, batch.uid)


@dataclasses.dataclass
class DefaultDownSampler(DownSampler):
    pass


@dataclasses.dataclass
class BinaryClassificationDownSampler(DownSampler):
    """Down-samples only the negative class, reweighting kept negatives by
    1/rate so the implied class prior is unchanged."""

    def apply(self, batch: LabeledBatch) -> LabeledBatch:
        keep = self._keep(batch.n, 1)
        is_neg = batch.label <= 0
        new_w = jnp.where(
            is_neg, jnp.where(keep, batch.weight / self.rate, 0.0), batch.weight
        )
        return LabeledBatch(batch.label, batch.features, batch.offset, new_w, batch.uid)


def down_sampler_for_task(task: TaskType, rate: float, seed: int = 0) -> DownSampler:
    """Task → sampler dispatch (DownSamplerHelper role)."""
    if task in (TaskType.LOGISTIC_REGRESSION, TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM):
        return BinaryClassificationDownSampler(rate, seed)
    return DefaultDownSampler(rate, seed)
