"""GameEstimator: the fit() orchestrator.

Parity target: reference ``GameEstimator`` (photon-api
estimators/GameEstimator.scala:53-713): prepare per-coordinate datasets
(prepareTrainingDatasets:470-530), validation evaluators
(prepareValidationEvaluators:573-611), build coordinates via a factory
(CoordinateFactory role), loop over optimization configurations with warm
start (fit:310-404), run coordinate descent per configuration, return
(model, config, evaluation) triples for model selection.

TPU-first: datasets are built once (host-side grouping for random effects),
and the λ sweep re-uses them — only the objectives change; every training is
jit-compiled against the same shapes so the sweep hits the compile cache.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.algorithm.coordinate import Coordinate
from photon_tpu.algorithm.coordinate_descent import CoordinateDescent
from photon_tpu.algorithm.fixed_effect import FixedEffectCoordinate
from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
from photon_tpu.data.batch import SparseFeatures
from photon_tpu.data.game_data import GameBatch, RowLayout
from photon_tpu.data.normalization import NormalizationContext
from photon_tpu.data.random_effect import (
    EntityGrouping,
    RandomEffectDataConfig,
    fill_entity_blocks,
    group_entity_rows,
    slab_budget_of,
)
from photon_tpu.estimators.config import (
    FixedEffectCoordinateConfig,
    GameOptimizationConfig,
    RandomEffectCoordinateConfig,
    expand_optimization_configs,
)
from photon_tpu.evaluation.suite import EvaluationSuite
from photon_tpu.models.game import (
    GameModel,
    ProjectedRandomEffectModel,
    RandomEffectModel,
)
from photon_tpu.obs.host import start_sentinel
from photon_tpu.obs.metrics import registry
from photon_tpu.obs.trace import span
from photon_tpu.ops.losses import loss_for_task
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.ops.variance import normalize_variance_type
from photon_tpu.sampling.down_sampler import down_sampler_for_task
from photon_tpu.types import TaskType, VarianceComputationType
from photon_tpu.utils.timed import Timed

logger = logging.getLogger(__name__)

CoordinateConfig = Union[FixedEffectCoordinateConfig, RandomEffectCoordinateConfig]


def _device_memory() -> dict:
    """``memory_stats()`` of the device the blocks are placed on; empty where
    the backend reports none, as the CPU does."""
    return jax.local_devices()[0].memory_stats() or {}


def _device_slab_budget() -> Optional[int]:
    """The block plan's byte budget (data/random_effect.py, rule 4) for the
    device the blocks are placed on; None, so no level is cut, where the
    backend reports no memory limit, as the CPU does."""
    limit = _device_memory().get("bytes_limit")
    return slab_budget_of(limit) if limit else None


# What a fit holds on the device besides its batch and its blocks, in
# float32s. A row and column of the widest random coordinate: its update's
# eager score (the coefficients gathered to (n, d) and their product) and
# its residual and warm-start gathers, read at 1.06 GB over resident for
# n = 2^22, d = 16 on a TPU v5e (3.95 a row and column); twice that is
# reserved. A row: the descent's score vectors and the fixed-effect solve's.
# A sparse shard's transpose product also holds its (n, k) entries twice.
FIT_FLOATS_A_ROW_AND_COLUMN = 8
FIT_FLOATS_A_ROW = 16

# The batch is laid out only where the layout coordinate's blocks deep
# enough to read windows hold at least this share of its slots. Below it the
# copy costs the batch's bytes for little: with 6 % of the slots in such
# blocks the block inputs fell 98.8 -> 94.5 ms of a 634 ms fit, for 4.9 GB.
LAYOUT_MIN_WINDOW_SHARE = 0.5


def _layout_bytes(batch: GameBatch, groupings: Dict[str, EntityGrouping],
                  limit: int) -> int:
    """Device bytes a laid-out fit needs on top of what is in use before it:
    the batch once more, the blocks still to be filled, one block's slab
    budget and the fit's own working set."""
    widest = max((g.d for g in groupings.values()), default=0)
    sparse = max(
        (2 * f.values.shape[1] for f in batch.features.values()
         if isinstance(f, SparseFeatures)),
        default=0,
    )
    floats = FIT_FLOATS_A_ROW + max(FIT_FLOATS_A_ROW_AND_COLUMN * widest, sparse)
    return (
        sum(a.nbytes for a in jax.tree_util.tree_leaves(batch))
        + sum(g.block_bytes() for g in groupings.values())
        + slab_budget_of(limit)
        + 4 * batch.n * floats
    )


def _row_layout(batch: GameBatch, groupings: Dict[str, EntityGrouping],
                 coordinate: str) -> Optional[np.ndarray]:
    """The order to lay ``batch``'s rows out in so that every lane of
    ``coordinate``'s blocks is one run of rows: its entities' rows
    (``EntityGrouping.run_rows``), then the rows no block holds (unknown
    ids, rows a cap left out) in their order.
    None where the layout would not pay (``LAYOUT_MIN_WINDOW_SHARE``) or the
    device cannot hold the copy: every array of the batch must sit on one
    device whose memory limit leaves ``_layout_bytes`` free."""
    grouping = groupings[coordinate]
    if grouping.window_share() < LAYOUT_MIN_WINDOW_SHARE:
        return None
    leaves = jax.tree_util.tree_leaves(batch)
    if not all(isinstance(a, jax.Array) and len(a.devices()) == 1 for a in leaves):
        return None
    memory = _device_memory()
    limit = memory.get("bytes_limit")
    if not limit:
        return None
    if limit - memory.get("bytes_in_use", 0) < _layout_bytes(batch, groupings, limit):
        return None
    held = grouping.run_rows()
    rest = np.ones((batch.n,), bool)
    rest[held] = False
    return np.concatenate([held, np.flatnonzero(rest)])


def _existing_entity_mask(prev_model) -> np.ndarray:
    """(E,) bool — which entities the warm-start model has a record for.

    Presence means record membership (reference
    RandomEffectDataset.scala:550-570), never coefficient values: an
    all-zero L1-sparsified row is still an EXISTING model and must keep the
    active-data bound. The loader's ``present_entities`` mask is
    authoritative when set; a projected model's presence is entity_block ≥ 0
    (entities with no block never had data or a model); a dense in-memory
    model without the mask treats every row as existing.
    """
    pm = getattr(prev_model, "present_entities", None)
    if pm is not None:
        return np.asarray(pm, bool)
    if isinstance(prev_model, ProjectedRandomEffectModel):
        return np.asarray(prev_model.entity_block) >= 0
    if isinstance(prev_model, RandomEffectModel):
        return np.ones((prev_model.num_entities,), bool)
    raise TypeError(
        "warm-start model for a random-effect coordinate must be a "
        "RandomEffectModel or ProjectedRandomEffectModel, got "
        f"{type(prev_model).__name__}"
    )


@dataclasses.dataclass
class GameResult:
    """(model, config, evaluations) triple (reference fit() return)."""

    model: GameModel
    config: GameOptimizationConfig
    metrics: Optional[Dict[str, float]]
    tracker: Dict[str, list]
    # Host wall seconds per (coordinate, CD pass) — carried from
    # CoordinateDescentResult so the run report joins diagnostics with
    # timing without re-running anything.
    wall_times: Dict[str, List[float]] = dataclasses.field(default_factory=dict)


class GameEstimator:
    """Trains GAME models over a list of optimization configurations.

    Args:
      task: GLM task for every coordinate (reference trainingTask param).
      coordinate_configs: data+optimizer config per coordinate, in update-
        sequence order.
      num_iterations: coordinate-descent passes per configuration.
      intercept_indices: feature-shard -> intercept column (excluded from
        regularization).
      normalization: feature-shard -> NormalizationContext.
      num_entities: RE type -> entity count (for dataset building).
    """

    def __init__(
        self,
        task: TaskType,
        coordinate_configs: Sequence[CoordinateConfig],
        num_iterations: int = 1,
        intercept_indices: Optional[Dict[str, int]] = None,
        normalization: Optional[Dict[str, NormalizationContext]] = None,
        num_entities: Optional[Dict[str, int]] = None,
        locked_coordinates: Sequence[str] = (),
        variance_computation: object = None,  # VarianceComputationType/bool/str
        ignore_threshold_for_new_models: bool = False,
        warm_start_model=None,  # GameModel the flag reads existing ids from
        re_active_set: bool = False,
        re_convergence_tol: float = 1e-4,
        re_device_budget_mb: Optional[float] = None,
        re_spill_dir: Optional[str] = None,
        re_spill_member: Optional[str] = None,
    ):
        self.task = task
        self.coordinate_configs = list(coordinate_configs)
        self.num_iterations = num_iterations
        self.intercept_indices = intercept_indices or {}
        self.normalization = normalization or {}
        self.num_entities = num_entities or {}
        self.locked_coordinates = list(locked_coordinates)
        self.variance_computation = normalize_variance_type(variance_computation)
        # ignoreThresholdForNewModels (GameTrainingDriver.scala:169-172):
        # during warm start, entities WITHOUT an existing model bypass the
        # RE active-data lower bound. The reference validates this pairing
        # at driver start (validateParams, :250-252) — mirrored here at
        # construction so a mid-sweep tuning fit can never trip it.
        self.ignore_threshold_for_new_models = bool(ignore_threshold_for_new_models)
        self.warm_start_model = warm_start_model
        # Estimator-level active-set default (per-coordinate config wins,
        # same precedence shape as variance): convergence-gated random-
        # effect passes for every RE coordinate of this estimator.
        self.re_active_set = bool(re_active_set)
        self.re_convergence_tol = float(re_convergence_tol)
        # Out-of-core residency: device byte budget for every RE
        # coordinate's block data + in-flight coefficients (None → fully
        # resident). See algorithm/re_store.ReDeviceStore.
        self.re_device_budget_bytes = (
            int(re_device_budget_mb * (1 << 20))
            if re_device_budget_mb
            else None
        )
        self.re_spill_dir = re_spill_dir
        # Host-owned spill layout: when set, spill files land under
        # ``<re_spill_dir>/host-<k>/`` so a fleet rebalance moves files
        # instead of re-streaming rows (re_store.rebalance_spill_layout).
        self.re_spill_member = re_spill_member
        if self.ignore_threshold_for_new_models and warm_start_model is None:
            raise ValueError(
                "'Ignore threshold for new models' flag set but no initial "
                "model provided for warm-start"
            )
        self.update_sequence = [c.coordinate_id for c in self.coordinate_configs]

    def _variance_type(self, cfg):
        """Per-coordinate setting wins; estimator-level is the fallback
        (reference variance flag precedence)."""
        per = normalize_variance_type(cfg.compute_variance)
        return per if per != VarianceComputationType.NONE else self.variance_computation

    # --- prepareTrainingDatasets role ---

    def _build_coordinates(
        self, batch: GameBatch, opt_config: GameOptimizationConfig
    ) -> Dict[str, Coordinate]:
        coords: Dict[str, Coordinate] = {}
        loss = loss_for_task(self.task)
        for cfg in self.coordinate_configs:
            reg = opt_config.reg[cfg.coordinate_id]
            if isinstance(cfg, FixedEffectCoordinateConfig):
                objective = GLMObjective(
                    loss=loss,
                    l2_weight=reg.l2,
                    l1_weight=reg.l1,
                    intercept_index=self.intercept_indices.get(cfg.feature_shard),
                    normalization=self.normalization.get(cfg.feature_shard),
                )
                sampler = (
                    dataclasses.replace(
                        down_sampler_for_task(self.task, cfg.down_sampling_rate),
                        layout=self._layout,
                    )
                    if cfg.down_sampling_rate is not None and cfg.down_sampling_rate < 1.0
                    else None
                )
                coords[cfg.coordinate_id] = FixedEffectCoordinate(
                    coordinate_id=cfg.coordinate_id,
                    feature_shard=cfg.feature_shard,
                    task=self.task,
                    objective=objective,
                    optimizer_spec=cfg.optimizer_spec(),
                    down_sampler=sampler,
                    compute_variance=self._variance_type(cfg),
                    dim=batch.features[cfg.feature_shard].shape[1],
                )
            elif isinstance(cfg, RandomEffectCoordinateConfig):
                ds = self._re_datasets[cfg.coordinate_id]
                objective = GLMObjective(
                    loss=loss,
                    l2_weight=reg.l2,
                    l1_weight=reg.l1,
                    intercept_index=self.intercept_indices.get(cfg.feature_shard),
                    # Same per-shard fold as the fixed effect (the reference
                    # passes NormalizationContexts per shard to every
                    # coordinate via CoordinateFactory).
                    normalization=self.normalization.get(cfg.feature_shard),
                )
                coords[cfg.coordinate_id] = RandomEffectCoordinate(
                    coordinate_id=cfg.coordinate_id,
                    dataset=ds,
                    task=self.task,
                    objective=objective,
                    optimizer_spec=cfg.optimizer_spec(),
                    compute_variance=self._variance_type(cfg),
                    active_set=bool(cfg.active_set or self.re_active_set),
                    convergence_tol=(
                        cfg.convergence_tol
                        if cfg.convergence_tol is not None
                        else self.re_convergence_tol
                    ),
                    device_budget_bytes=self.re_device_budget_bytes,
                    device_spill_dir=self.re_spill_dir,
                    device_spill_member=self.re_spill_member,
                )
            else:
                raise TypeError(f"unknown coordinate config {type(cfg)}")
        return coords

    def _prepare_datasets(self, batch: GameBatch) -> GameBatch:
        """Random-effect grouping happens once per fit() — the λ sweep
        reuses the blocks (the reference rebuilds per config; we don't).
        Repeated fits on the SAME batch (hyperparameter tuning calls fit
        once per candidate) reuse the previous grouping.

        Returns the batch the datasets index, which every coordinate trains
        on: where the device holds a second copy, ``batch`` laid out so that
        each entity of the first random coordinate whose blocks are dense has
        its rows together (``_row_layout``), and that coordinate's blocks
        read their residuals as runs (``self._layout``); else ``batch``
        itself. Nothing a fit returns is indexed by row."""
        if getattr(self, "_prepared_for", None) is batch:
            return self._prepared_batch
        self._re_datasets = {}
        self._prepared_for = self._prepared_batch = None
        self._layout = RowLayout()
        re_cfgs = [
            c for c in self.coordinate_configs
            if isinstance(c, RandomEffectCoordinateConfig)
        ]
        with span("prepare"):
            with span("host_copy"):
                # Sparse (wide) shards pass through as host triples — the
                # builder compacts each block to its active-column subspace
                # instead of densifying the full shard width.
                feats_np = {
                    k: (
                        (np.asarray(v.indices), np.asarray(v.values), v.dim)
                        if isinstance(v, SparseFeatures)
                        else np.asarray(v)
                    )
                    for k, v in batch.features.items()
                }
                label_np = np.asarray(batch.label)
                weight_np = np.asarray(batch.weight)
                uid_np = None if batch.uid is None else np.asarray(batch.uid)
                eids_np = {
                    cfg.re_type: np.asarray(batch.entity_ids[cfg.re_type])
                    for cfg in re_cfgs
                }
            with span("group"):
                # Children ``plan`` and ``fill`` open in the builder.
                groupings: Dict[str, EntityGrouping] = {}
                for cfg in re_cfgs:
                    with span(cfg.coordinate_id):
                        groupings[cfg.coordinate_id] = group_entity_rows(
                            eids_np[cfg.re_type], feats_np[cfg.feature_shard],
                            self._data_config(cfg), uid_np,
                            _device_slab_budget(),
                        )
                runs_of = next(
                    (cid for cid, g in groupings.items()
                     if g.entities and not g.project),
                    None,
                )
                order = (
                    None if runs_of is None
                    else _row_layout(batch, groupings, runs_of)
                )
                row_of = None
                if order is not None:
                    row_of = np.empty_like(order)
                    row_of[order] = np.arange(order.size)
                for cfg in re_cfgs:
                    with span(cfg.coordinate_id):
                        ds = fill_entity_blocks(
                            groupings[cfg.coordinate_id],
                            feats_np[cfg.feature_shard], label_np, weight_np,
                            self._entity_count(cfg, eids_np[cfg.re_type]),
                            self._data_config(cfg),
                            self._existing_mask(cfg, eids_np[cfg.re_type]),
                            row_of,
                        )
                    self._re_datasets[cfg.coordinate_id] = ds
                    self._publish_plan(
                        cfg.coordinate_id, ds,
                        laid_out=order is not None and cfg.coordinate_id == runs_of,
                    )
            if order is not None:
                with span("layout"):
                    self._layout = RowLayout(jnp.asarray(order.astype(np.int32)))
                    run_batch = self._layout.apply(batch)
            else:
                run_batch = batch
        self._prepared_for = batch
        self._prepared_batch = run_batch
        return run_batch

    @staticmethod
    def _publish_plan(coordinate: str, ds, laid_out: bool) -> None:
        """What the plan costs a pass and what the population is, into the
        registry at dataset build."""
        labels = dict(coordinate=coordinate)
        # One dispatch a block, one solver program a distinct (lanes, n_max,
        # d); of the blocks, those that gather their residuals one by one
        # (the rest read them as runs); and whether the batch is laid out in
        # runs of this coordinate's entities.
        registry().gauge("re_blocks", **labels).set(len(ds.blocks))
        registry().gauge("re_block_geometries", **labels).set(
            len({b.features.shape for b in ds.blocks})
        )
        registry().gauge("re_row_gather_blocks", **labels).set(
            sum(b.runs is None for b in ds.blocks)
        )
        registry().gauge("batch_laid_out", **labels).set(int(laid_out))
        # Entities that hold rows, those of them with at least as many rows
        # as coefficients (the rest are under-determined: only the penalty
        # bounds them), and the widest block's lanes.
        rows = np.asarray(
            ds.lane_samples if ds.blocks else np.zeros((0,), np.int32)
        )
        registry().gauge("re_entities", **labels).set(int(np.sum(rows > 0)))
        registry().gauge("re_entities_rows_ge_dim", **labels).set(
            int(np.sum(rows >= ds.dim))
        )
        registry().gauge("re_lanes_max", **labels).set(
            max((b.num_entities for b in ds.blocks), default=0)
        )

    @staticmethod
    def _data_config(cfg) -> RandomEffectDataConfig:
        return RandomEffectDataConfig(
            re_type=cfg.re_type,
            feature_shard=cfg.feature_shard,
            active_upper_bound=cfg.active_upper_bound,
            active_lower_bound=cfg.active_lower_bound,
            features_to_samples_ratio=cfg.features_to_samples_ratio,
        )

    def _entity_count(self, cfg, eids: np.ndarray) -> int:
        return self.num_entities.get(
            cfg.re_type, int(eids.max()) + 1 if eids.size else 0
        )

    def _existing_mask(self, cfg, eids: np.ndarray) -> Optional[np.ndarray]:
        """Entities with an existing model in the warm-start GameModel, where
        new ones bypass the lower bound; None otherwise. Presence comes from
        the loader's record-membership mask when available (L1-zeroed models
        still count as existing, matching the reference's key-presence
        semantics); nonzero rows are the fallback for in-memory models."""
        if not self.ignore_threshold_for_new_models:
            return None
        E = self._entity_count(cfg, eids)
        existing = np.zeros((E,), bool)
        prev_model = self.warm_start_model.get(cfg.coordinate_id)
        if prev_model is not None:
            existing_src = _existing_entity_mask(prev_model)
            k = min(E, existing_src.shape[0])
            existing[:k] = existing_src[:k]
        return existing

    # --- fit ---

    def fit(
        self,
        batch: GameBatch,
        validation_batch: Optional[GameBatch] = None,
        evaluation_suite: Optional[EvaluationSuite] = None,
        optimization_configs: Optional[Sequence[GameOptimizationConfig]] = None,
        initial_model: Optional[GameModel] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 1,
        checkpoint_keep_last: Optional[int] = None,
        emitter=None,  # utils.events.EventEmitter for optimization-log events
    ) -> List[GameResult]:
        """Train one GameModel per optimization configuration, warm-starting
        each config from the previous result (fit:364-382 role).

        With ``checkpoint_dir``, each config's coordinate descent checkpoints
        under ``<dir>/cfg_<i>`` and resumes from its latest state — an
        already-finished config replays from its final checkpoint without
        recomputation, so a preempted λ-sweep continues where it stopped."""
        start_sentinel()  # host pauses (collections, stalls) on the span clock
        with Timed("game-estimator/prepare-datasets"):
            batch = self._prepare_datasets(batch)

        configs = (
            list(optimization_configs)
            if optimization_configs is not None
            else expand_optimization_configs(self.coordinate_configs)
        )
        validation_fn = better = None
        if evaluation_suite is not None and validation_batch is not None:
            validation_fn = evaluation_suite.validation_fn()
            better = evaluation_suite.primary.better()

        results: List[GameResult] = []
        warm = initial_model
        for cfg_idx, opt_config in enumerate(configs):
            with Timed(f"game-estimator/train[{opt_config.describe()}]"):
                coords = self._build_coordinates(batch, opt_config)
                cd = CoordinateDescent(
                    coords,
                    self.update_sequence,
                    num_iterations=self.num_iterations,
                    locked_coordinates=self.locked_coordinates,
                )
                cd_result = cd.run(
                    batch,
                    initial_model=warm,
                    validation_batch=validation_batch,
                    validation_fn=validation_fn,
                    better=better if better is not None else (lambda a, b: a < b),
                    checkpoint_dir=(
                        None
                        if checkpoint_dir is None
                        else f"{checkpoint_dir}/cfg_{cfg_idx}"
                    ),
                    checkpoint_every=checkpoint_every,
                    checkpoint_keep_last=checkpoint_keep_last,
                    layout=self._layout,
                    # Fingerprint the λ-sweep point: resuming against a
                    # changed grid/sequence fails loudly instead of serving a
                    # stale model from the same cfg index.
                    checkpoint_tag=f"{opt_config.describe()}|{','.join(self.update_sequence)}",
                    emitter=emitter,
                )
            metrics = cd_result.metric_history[-1] if cd_result.metric_history else None
            results.append(
                GameResult(
                    model=cd_result.best_model,
                    config=opt_config,
                    metrics=metrics,
                    tracker=cd_result.tracker,
                    wall_times=cd_result.wall_times,
                )
            )
            warm = cd_result.model  # warm start the next λ point
            logger.info("trained config (%s): metrics=%s", opt_config.describe(), metrics)
        return results

    def select_best(
        self, results: List[GameResult], evaluation_suite: EvaluationSuite
    ) -> GameResult:
        """Best model by the primary validation metric (selectModels role,
        GameTrainingDriver.scala:701-766)."""
        primary = evaluation_suite.primary
        better = primary.better()
        best = None
        for r in results:
            if r.metrics is None:
                continue
            v = r.metrics[primary.name]
            if best is None or better(v, best.metrics[primary.name]):
                best = r
        return best if best is not None else results[-1]
