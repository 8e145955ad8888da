"""Coordinate protocol: one block of the GAME coordinate-descent problem.

Parity target: reference ``Coordinate`` / ``ModelCoordinate`` (photon-lib
algorithm/Coordinate.scala:28-84, ModelCoordinate.scala:28-63) — trainModel
(± initial model, ± residual scores) and score(model).

TPU-first: residuals are a flat (n,) score array aligned with the GameBatch
sample axis (``addScoresToOffsets`` is addition, not a join).
"""

from __future__ import annotations

import abc
from typing import Any, Optional, Tuple

import jax

from photon_tpu.data.game_data import GameBatch

Array = jax.Array


class Coordinate(abc.ABC):
    """One coordinate: owns its view of the data + optimization problem.

    Coordinate descent asks for one :meth:`update` a pass: the new model, its
    diagnostics and its scores on the batch. The default is :meth:`train`
    then :meth:`score`; a coordinate whose solve already holds the scores
    (the fixed effect's margins) overrides it and reads the data once."""

    coordinate_id: str

    @abc.abstractmethod
    def train(
        self,
        batch: GameBatch,
        residual_scores: Optional[Array] = None,
        initial_model: Optional[Any] = None,
    ) -> Tuple[Any, Any]:
        """Train against residuals of all other coordinates; returns
        (model, tracker-like diagnostics). The four trainModel overloads of
        the reference collapse into the two optional arguments."""

    @abc.abstractmethod
    def score(self, model: Any, batch: GameBatch) -> Array:
        """Per-sample raw scores of this coordinate's model (no offsets)."""

    def update(
        self,
        batch: GameBatch,
        residual_scores: Optional[Array] = None,
        initial_model: Optional[Any] = None,
        initial_scores: Optional[Array] = None,
    ) -> Tuple[Any, Any, Array]:
        """One coordinate-descent update: ``(model, diagnostics, scores)``,
        the scores being :meth:`score` of the new model on ``batch``.
        ``initial_scores`` are this coordinate's scores of ``initial_model``
        on ``batch`` where the caller holds them (zeros for no model): a
        coordinate may start from them, and may not rely on getting them."""
        model, diag = self.train(batch, residual_scores, initial_model)
        return model, diag, self.score(model, batch)

    @abc.abstractmethod
    def zero_model(self) -> Any:
        """Initial all-zeros model (initializeZeroModel role, reference
        GeneralizedLinearOptimizationProblem.scala:35-91)."""


class ModelCoordinate(Coordinate):
    """Score-only coordinate for locked (partial-retrain) blocks
    (reference FixedEffectModelCoordinate / RandomEffectModelCoordinate)."""

    def __init__(self, coordinate_id: str, inner: Coordinate, model: Any):
        self.coordinate_id = coordinate_id
        self._inner = inner
        self._model = model

    def train(self, batch, residual_scores=None, initial_model=None):
        return self._model, None

    def score(self, model, batch):
        return self._inner.score(self._model if model is None else model, batch)

    def zero_model(self):
        return self._model
