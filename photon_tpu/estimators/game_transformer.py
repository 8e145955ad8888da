"""GameTransformer: batch scoring with a trained GameModel.

Parity target: reference ``GameTransformer`` (photon-api
transformers/GameTransformer.scala:39-318): load model → score a dataset →
optional evaluation; logValue of metrics.
"""

from __future__ import annotations

import logging
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from photon_tpu.data.game_data import GameBatch
from photon_tpu.evaluation.suite import EvaluationSuite
from photon_tpu.models.game import GameModel

Array = jax.Array
logger = logging.getLogger(__name__)


class GameTransformer:
    def __init__(self, model: GameModel, evaluation_suite: Optional[EvaluationSuite] = None):
        self.model = model
        self.evaluation_suite = evaluation_suite
        # Model passed as an argument so repeated transforms (same batch
        # shapes) reuse one compiled program instead of retracing against a
        # fresh model-closure every call. trace_count increments inside the
        # traced body, so it counts REAL XLA traces (the retrace-contract
        # observable for streamed scoring: at most one per bucket shape),
        # not Python calls — the solve_cache.py counter pattern.
        self.trace_count = 0

        def _score(model, batch):
            self.trace_count += 1
            with jax.named_scope("compute_score"):
                return model.score_with_offset(batch)

        self._score = jax.jit(_score)

    def transform(self, batch: GameBatch, model: Optional[GameModel] = None) -> Array:
        """Per-sample total scores (model + offsets), jitted.

        ``model`` overrides the init-time model for this call — the serving
        engine passes its store's current ``scoring_model()`` so hot-table
        promotions take effect. Same pytree STRUCTURE as ``self.model`` →
        same compiled program (value-only swap, no retrace)."""
        scores = self._score(self.model if model is None else model, batch)
        if self.evaluation_suite is not None:
            metrics = self.evaluation_suite.evaluate_scores(scores, batch)
            logger.info("scoring evaluation: %s", metrics)
            self.last_metrics: Optional[Dict[str, float]] = metrics
        return scores

    def warm_up(self, template: GameBatch, row_buckets, sharding=None) -> int:
        """Compile the scorer for every row-count bucket an online caller
        will dispatch on, up front — the serving engine's startup step that
        turns "at most one trace per bucket" into "ZERO traces after
        warm-up" (compiles happen before traffic, never under a request).

        ``template`` is a 1-row batch with the production feature/entity
        layout; each bucket size pads it with inert rows (weight 0, entity
        -1 — data/padding.py) and scores it to completion. Tracing is
        shape-driven, so the dummy values never matter. Returns the number
        of fresh traces (== number of previously-unseen bucket shapes).

        ``sharding`` places each padded batch exactly as the caller will
        place live ones. The jit cache keys on placement as well as shape: a
        batch committed to a mesh (the serving engine replicates requests
        over the device-sharded store's mesh) re-traces a scorer that was
        warmed on an uncommitted single-device template."""
        from photon_tpu.data.padding import pad_game_batch

        before = self.trace_count
        for n in sorted(set(int(b) for b in row_buckets)):
            padded = pad_game_batch(template, n, xp=jnp)
            if sharding is not None:
                padded = jax.device_put(padded, sharding)
            jax.block_until_ready(self._score(self.model, padded))
        return self.trace_count - before
