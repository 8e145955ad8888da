"""Entity-sharded random-effect coordinate: one GAME coordinate, S device
shards.

The multi-device training tentpole for the coordinate-descent path: the RE
coefficient store is sharded by ENTITY across devices using the serving
fleet's consistent-hash ring (parallel/entity_shard.py — the PR-13 disjoint
ownership trick applied to devices instead of replicas). Each shard is a
full :class:`~photon_tpu.algorithm.random_effect.RandomEffectCoordinate`
over ONLY its entities' samples, with its blocks, warm starts, and solves
committed to the owning device; solve caching, drop-mode scatter
discipline, convergence-gated active-set passes, and out-of-core residency
all run unchanged inside each shard. The score/residual merge is the one
cross-device exchange per pass: per-shard coefficient tables gather to a
host master (disjoint rows — exact, order-independent) that scores the flat
batch exactly like a single-device model.

Bit-parity by construction: the shard layout is FIXED (default 8 shards)
independent of device count — shard ``s`` runs on device ``(s*n)//S`` — so
every device count dispatches the identical programs on identical block
geometry and differs only in placement. ``n=1`` IS the single-device run;
``np.array_equal`` holds against any other ``n`` (asserted by
``bench.py --multichip`` and tests/test_entity_sharded.py).

Zero retraces: shards share one :class:`SolveCache`; a shard's block
shapes are stable across passes and across device counts, and the cache
needs no per-device keying (one jitted executable serves every device of a
backend), so after the first full pass no shard ever retraces — including
gated and out-of-core passes, whose compaction plans draw only on
already-compiled allocations.
"""

from __future__ import annotations

import time
from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np

from photon_tpu.algorithm.coordinate import Coordinate
from photon_tpu.algorithm.random_effect import (
    RandomEffectCoordinate,
    RandomEffectTrackerStats,
)
from photon_tpu.algorithm.solve_cache import SolveCache, default_cache
from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.random_effect import (
    RandomEffectDataConfig,
    build_random_effect_dataset,
)
from photon_tpu.models.game import RandomEffectModel
from photon_tpu.obs.trace import span
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.parallel.entity_shard import (
    DEFAULT_N_SHARDS,
    EntityShardPlan,
    build_shard_plan,
    merge_shard_coefficients,
)
from photon_tpu.types import TaskType

Array = jax.Array


class ShardedRandomEffectCoordinate(Coordinate):
    """S per-device sub-coordinates behind the single-coordinate protocol.

    Build with :meth:`build` (it owns the per-shard dataset construction).
    ``train`` returns a merged host-master :class:`RandomEffectModel` whose
    rows are each entity's coefficients from its owning shard; warm starts
    stay per-shard on-device across passes (the merged model is for
    scoring/residuals — passing it back as ``initial_model`` re-slices it
    only when it is not this coordinate's own previous output).

    ``last_shard_walls`` holds the previous pass's per-shard
    (dispatch + sync) wall seconds: shards are timed one at a time, so on a
    mesh of real devices each entry is that device's busy time for its own
    work — the per-chip throughput measurement ``bench.py --multichip``
    aggregates.
    """

    def __init__(
        self,
        coordinate_id: str,
        plan: EntityShardPlan,
        shards: Sequence[RandomEffectCoordinate],
        devices: Sequence,
        re_type: str,
        feature_shard: str,
        task: TaskType,
        dim: int,
    ):
        self.coordinate_id = coordinate_id
        self.plan = plan
        self.shards = list(shards)
        self.devices = list(devices)
        self.re_type = re_type
        self.feature_shard = feature_shard
        self.task = task
        self.dim = int(dim)
        self.num_entities = plan.num_entities
        # Per-shard previous-pass models (device-resident warm starts).
        self._shard_models: List[Optional[RandomEffectModel]] = [
            None for _ in self.shards
        ]
        self._last_merged: Optional[RandomEffectModel] = None
        self.last_shard_walls: Optional[List[float]] = None
        self.last_shard_samples: List[int] = [
            sum(
                int(np.sum(np.asarray(b.weight) > 0))
                for b in c.dataset.blocks
            )
            for c in self.shards
        ]

    # -- construction ------------------------------------------------------

    @classmethod
    def build(
        cls,
        coordinate_id: str,
        entity_ids: np.ndarray,
        features: np.ndarray,
        label: np.ndarray,
        weight: np.ndarray,
        num_entities: int,
        config: RandomEffectDataConfig,
        task: TaskType,
        objective: GLMObjective,
        optimizer_spec: Optional[OptimizerSpec] = None,
        plan: Optional[EntityShardPlan] = None,
        n_shards: int = DEFAULT_N_SHARDS,
        seed: int = 0,
        entity_index=None,
        devices: Optional[Sequence] = None,
        solve_cache: Optional[SolveCache] = None,
        active_set: bool = False,
        convergence_tol: float = 1e-4,
        device_budget_bytes: Optional[int] = None,  # PER SHARD
        device_spill_dir: Optional[str] = None,
    ) -> "ShardedRandomEffectCoordinate":
        """Shard the flat sample arrays by entity owner and build one
        per-device sub-coordinate per shard.

        Each shard's dataset is built from the SAME flat arrays with
        non-owned samples' entity ids masked to -1 (the builder drops
        them), so ``sample_index`` keeps addressing the GLOBAL batch rows —
        residual gathers need no per-shard batch slicing. Entity indices
        are LOCAL to the shard (ascending-global order), which is what
        makes the per-device coefficient table (E_s, d) instead of (E, d):
        the store is genuinely sharded, not replicated.

        ``device_budget_bytes`` (out-of-core residency) applies PER SHARD —
        the fixed per-device budget of the capacity-scaling story.
        """
        if plan is None:
            plan = build_shard_plan(
                num_entities, n_shards=n_shards, seed=seed,
                entity_index=entity_index,
            )
        if devices is None:
            devices = jax.devices()
        cache = solve_cache if solve_cache is not None else default_cache()
        spec = optimizer_spec or OptimizerSpec()
        per_shard_eids = plan.shard_sample_entities(np.asarray(entity_ids))
        shards: List[RandomEffectCoordinate] = []
        shard_devices = []
        for s in range(plan.n_shards):
            dev = devices[plan.device_of(s, len(devices))]
            shard_devices.append(dev)
            dataset = build_random_effect_dataset(
                per_shard_eids[s],
                features,
                label,
                weight,
                int(plan.counts[s]),
                config,
            )
            shards.append(
                RandomEffectCoordinate(
                    coordinate_id=f"{coordinate_id}/shard{s}",
                    dataset=dataset,
                    task=task,
                    objective=objective,
                    optimizer_spec=spec,
                    solve_cache=cache,
                    active_set=active_set,
                    convergence_tol=convergence_tol,
                    device_budget_bytes=device_budget_bytes,
                    # Host-owned spill layout: shard s's master lives under
                    # ``<spill>/host-<s>/`` so a shard-count rebalance is a
                    # file move (re_store.rebalance_spill_layout).
                    device_spill_dir=device_spill_dir,
                    device_spill_member=(
                        s if device_spill_dir is not None else None
                    ),
                    device=dev,
                )
            )
        return cls(
            coordinate_id=coordinate_id,
            plan=plan,
            shards=shards,
            devices=shard_devices,
            re_type=config.re_type,
            feature_shard=config.feature_shard,
            task=task,
            dim=int(features.shape[1]),
        )

    # -- coordinate protocol -----------------------------------------------

    def begin_cd_pass(self, cd_iteration: int) -> None:
        for c in self.shards:
            c.begin_cd_pass(cd_iteration)

    def train(
        self,
        batch: GameBatch,
        residual_scores: Optional[Array] = None,
        initial_model: Optional[Any] = None,
    ) -> Tuple[RandomEffectModel, RandomEffectTrackerStats]:
        shard_inits = self._shard_initials(initial_model)
        walls: List[float] = []
        shard_models: List[Optional[RandomEffectModel]] = []
        shard_stats = []
        with span("re_sharded_train"):
            for s, coord in enumerate(self.shards):
                # One shard at a time, synced at the end: the wall below is
                # this device's busy time for its own work (per-chip
                # accounting), and shards stay deterministic regardless of
                # host thread scheduling.
                t0 = time.perf_counter()
                model_s, stats_s = coord.train(
                    batch, residual_scores, shard_inits[s]
                )
                jax.block_until_ready(model_s.coefficients)
                walls.append(time.perf_counter() - t0)
                shard_models.append(model_s)
                shard_stats.append(stats_s)
        self._shard_models = shard_models
        self.last_shard_walls = walls

        # Score/residual merge: the one cross-device exchange of the pass.
        # Shards own disjoint entity rows, so the gather into the host
        # master is exact (x + 0 = x; no reduction order to vary).
        with span("re_sharded_merge"):
            merged = RandomEffectModel(
                merge_shard_coefficients(
                    self.plan,
                    [np.asarray(m.coefficients) for m in shard_models],
                    self.dim,
                ),
                self.re_type,
                self.feature_shard,
                self.task,
            )
        self._last_merged = merged
        return merged, self._merge_stats(shard_stats)

    def _shard_initials(
        self, initial_model: Optional[Any]
    ) -> List[Optional[RandomEffectModel]]:
        """Warm starts per shard. Our own previous output reuses the
        device-resident per-shard models (no re-slicing, no h2d); a foreign
        dense model is sliced through the plan onto each shard's local
        entity space."""
        if initial_model is None:
            return [None for _ in self.shards]
        if initial_model is self._last_merged and self._last_merged is not None:
            return list(self._shard_models)
        coefs = np.asarray(initial_model.coefficients, np.float32)
        inits: List[Optional[RandomEffectModel]] = []
        for s in range(self.plan.n_shards):
            ents = self.plan.entities_of(s)
            inits.append(
                RandomEffectModel(
                    jax.device_put(
                        np.ascontiguousarray(coefs[ents, : self.dim]),
                        self.devices[s],
                    ),
                    self.re_type,
                    self.feature_shard,
                    self.task,
                )
            )
        return inits

    @staticmethod
    def _merge_stats(shard_stats: Sequence) -> RandomEffectTrackerStats:
        parts = [st for st in shard_stats if st is not None]
        if not parts:
            return RandomEffectTrackerStats.empty()
        import jax.numpy as jnp

        # Per-shard tracker arrays live on different devices; concatenate
        # host-side (tiny int arrays — this is diagnostics, not hot path).
        return RandomEffectTrackerStats(
            iterations=jnp.asarray(
                np.concatenate([np.asarray(st.iterations) for st in parts])
            ),
            reasons=jnp.asarray(
                np.concatenate([np.asarray(st.reasons) for st in parts])
            ),
            valid=jnp.asarray(
                np.concatenate([np.asarray(st.valid) for st in parts])
            ),
        )

    def score(self, model, batch: GameBatch) -> Array:
        return model.score(batch)

    def zero_model(self) -> RandomEffectModel:
        return RandomEffectModel(
            np.zeros((self.num_entities, self.dim), np.float32),
            self.re_type,
            self.feature_shard,
            self.task,
        )

    # -- diagnostics -------------------------------------------------------

    def device_busy_seconds(self, n_devices: Optional[int] = None) -> List[float]:
        """Previous pass's busy seconds per DEVICE (shard walls folded
        through the shard→device map)."""
        if self.last_shard_walls is None:
            return []
        n = n_devices if n_devices is not None else len(set(map(id, self.devices)))
        busy = [0.0] * n
        for s, w in enumerate(self.last_shard_walls):
            busy[self.plan.device_of(s, n)] += w
        return busy

    def residency_stats(self) -> List[Optional[dict]]:
        return [c.last_residency_stats for c in self.shards]
