"""Random-effect coordinate: millions of tiny per-entity GLMs as vmapped
batched solves.

Parity target: reference ``RandomEffectCoordinate`` (photon-api
algorithm/RandomEffectCoordinate.scala:37-339) — the reference's hot loop is
`activeData.join(optimizationProblems).mapValues{ per-entity L-BFGS }`,
serial per Spark partition (SURVEY.md §3.2 "HOT LOOP"), plus
``RandomEffectOptimizationProblem`` (an RDD of per-entity problems) and
``RandomEffectOptimizationTracker`` (aggregated convergence stats).

TPU-first: each fixed-shape EntityBlock (E, n_max, d) trains ALL its entities
simultaneously with ``jax.vmap`` over the jittable L-BFGS — one SPMD program
per block instead of millions of serial solves. Entity rows shard over the
mesh's entity axis; there is no cross-entity communication (matching the
reference's embarrassing parallelism, but saturating the MXU with batched
(n_max, d) matvecs). The per-entity tracker reduces to aggregate counts
exactly like the reference's tracker.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.algorithm.coordinate import Coordinate
from photon_tpu.algorithm.solve_cache import SolveCache, default_cache
from photon_tpu.data.batch import LabeledBatch
from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.random_effect import (
    EntityBlock,
    RandomEffectDataset,
    compact_entity_blocks,
    pack_into_sizes,
    pearson_feature_mask,
)
from photon_tpu.models.game import (
    DatumScoringModel,
    ProjectedRandomEffectModel,
    RandomEffectModel,
)
from photon_tpu.obs.trace import span
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.ops.variance import coefficient_variances, normalize_variance_type
from photon_tpu.optim.common import (
    OptimizerConfig,
    REASON_DIVERGED,
    REASON_FUNCTION_VALUES_CONVERGED,
    REASON_GRADIENT_CONVERGED,
    REASON_MAX_ITERATIONS,
)
from photon_tpu.optim.lbfgs import minimize_lbfgs  # noqa: F401 (TRON/HVP paths)
from photon_tpu.optim.margin_lbfgs import minimize_lbfgs_margin
from photon_tpu.optim.newton import minimize_newton, spd_solve_lowering
from photon_tpu.optim.tron import minimize_tron
from photon_tpu.optim.owlqn import minimize_owlqn
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.types import OptimizerType, TaskType, VarianceComputationType
from photon_tpu.utils import faults

Array = jax.Array

# Widest per-entity dimension for which the default solver forms exact
# (d, d) Hessians: above this, batched Newton's E·d² HBM footprint and d³
# Cholesky cost lose to margin-LBFGS's d-linear iterations. Inside it the
# system is solved by column steps unrolled over the entity axis up to
# optim/newton.py's SPD_UNROLL_MAX_DIM (in blocks of SPD_UNROLL_MIN_LANES
# entities and more), by the library's Cholesky above.
NEWTON_AUTO_MAX_DIM = 128


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RandomEffectTrackerStats:
    """Aggregate convergence stats across entity solves
    (RandomEffectOptimizationTracker.scala role). A pytree so trackers ride
    along in coordinate-descent checkpoints.

    The per-row iteration/reason arrays stay ON DEVICE: building the tracker
    after a coordinate pass costs no host sync, so the coordinate-descent
    sequence never blocks mid-pass on diagnostics. Reading it is ONE
    device→host transfer a call, then numpy: ``summary()``,
    ``diagnostics_dict()`` and each aggregate property fetch the rows with
    one ``jax.device_get`` and apply no ``jnp`` operation. The host copies
    live for that call alone (the pytree's leaves stay the device rows).
    ``valid`` masks shape-bucket padding rows out of every aggregate.
    """

    iterations: Array  # (T,) per-row iteration counts, blocks concatenated
    reasons: Array  # (T,) per-row termination reason codes
    valid: Array  # (T,) bool — False for shape-bucket padding rows
    # (T,) int32 training rows of each entity, where the coordinate knows
    # them in tracker order (a full resident pass); None otherwise.
    samples: Optional[Array] = None
    # Which coordinate's pass this is and the allocated lanes of each block
    # it dispatched, in tracker order: what the lane-iteration counters are
    # published under and cut by when the tracker is read. Empty where no
    # coordinate made it (a merge of shards).
    coordinate: str = dataclasses.field(default="", metadata=dict(static=True))
    block_lanes: Tuple[int, ...] = dataclasses.field(
        default=(), metadata=dict(static=True)
    )

    @staticmethod
    def empty() -> "RandomEffectTrackerStats":
        z = jnp.zeros((0,), jnp.int32)
        return RandomEffectTrackerStats(z, z, jnp.zeros((0,), bool))

    def _publish_lane_iterations(self, valid: np.ndarray, iters: np.ndarray) -> None:
        """What lockstep costs, into the registry, once a tracker: a block's
        Newton loop runs until its slowest entity stops, so every real lane
        of it is RUN for that many iterations whatever it USED itself.
        ``re_lane_iterations_used_total`` is Σ over entities of their own
        iterations, ``re_lane_iterations_run_total`` Σ over blocks of real
        lanes × the block's longest; from the host copy a read already made."""
        if self.__dict__.get("_published") or not self.coordinate:
            return
        object.__setattr__(self, "_published", True)
        if sum(self.block_lanes) != iters.size or not iters.size:
            return
        from photon_tpu.obs.metrics import registry

        starts = np.cumsum((0,) + self.block_lanes[:-1])
        run = np.maximum.reduceat(iters, starts) * np.add.reduceat(
            valid.astype(np.int64), starts
        )
        labels = dict(coordinate=self.coordinate)
        registry().counter("re_lane_iterations_used_total", **labels).inc(
            int(iters.sum())
        )
        registry().counter("re_lane_iterations_run_total", **labels).inc(
            int(run.sum())
        )

    def _aggregates(self) -> dict:
        """The seven aggregates from one transfer. Counts and the mean's
        numerator are exact integer sums; the two means divide in float32,
        as the device form did."""
        h = jax.device_get(self)
        valid = h.valid
        iters = np.where(valid, h.iterations, 0).astype(np.int64)
        entities = int(valid.sum())
        self._publish_lane_iterations(valid, iters)

        def count(*codes) -> int:
            return int((np.isin(h.reasons, codes) & valid).sum())

        weighted = None
        if h.samples is not None:
            rows = np.where(valid, h.samples, 0).astype(np.int64)
            weighted = float(
                np.float32((rows * iters).sum())
                / np.float32(max(int(rows.sum()), 1))
            )
        return dict(
            entities=entities,
            converged=count(
                REASON_FUNCTION_VALUES_CONVERGED, REASON_GRADIENT_CONVERGED
            ),
            hit_max_iter=count(REASON_MAX_ITERATIONS),
            quarantined=count(REASON_DIVERGED),
            mean_iterations=float(
                np.float32(iters.sum()) / np.float32(max(entities, 1))
            ),
            max_iterations=int(iters.max()) if iters.size else 0,
            row_weighted_iterations=weighted,
        )

    @property
    def num_entities(self) -> int:
        return self._aggregates()["entities"]

    @property
    def num_converged(self) -> int:
        return self._aggregates()["converged"]

    @property
    def num_max_iter(self) -> int:
        return self._aggregates()["hit_max_iter"]

    @property
    def num_quarantined(self) -> int:
        """Entities whose solve diverged and kept their previous coefficients
        (the in-trace guard in solve_cache.block_solver)."""
        return self._aggregates()["quarantined"]

    @property
    def mean_iterations(self) -> float:
        return self._aggregates()["mean_iterations"]

    @property
    def max_iterations(self) -> int:
        return self._aggregates()["max_iterations"]

    @property
    def row_weighted_iterations(self) -> Optional[float]:
        """Σ rows_e · iterations_e ÷ Σ rows_e: the iterations of the mean
        ROW's entity, which is what the solve's work follows when entities
        are uneven (``mean_iterations`` weighs a 30-row user like a
        400,000-row one). None where the tracker holds no ``samples``."""
        return self._aggregates()["row_weighted_iterations"]

    def summary(self) -> str:
        a = self._aggregates()
        return (
            f"entities={a['entities']} converged={a['converged']} "
            f"hit_max_iter={a['hit_max_iter']} quarantined={a['quarantined']} "
            f"iters(mean={a['mean_iterations']:.1f}, max={a['max_iterations']})"
        )

    def diagnostics_dict(self) -> dict:
        """Report-ready aggregates: one device→host transfer of the rows,
        then host arithmetic. Still a read the dispatch loop must not make
        — call it at run-report finalize."""
        return dict(type="random_effect", **self._aggregates())


def newton_eligible(
    objective: GLMObjective, spec: OptimizerSpec, block_dim: int, has_mask: bool
) -> bool:
    """Static routing predicate for _solve_block: batched Newton serves
    smooth, unmasked, shift-free problems — by default up to
    NEWTON_AUTO_MAX_DIM, always under an explicit NEWTON spec."""
    has_shifts = (
        objective.normalization is not None
        and not objective.normalization.is_identity
        and objective.normalization.shifts is not None
    )
    return (
        objective.l1_weight == 0.0
        and not has_mask
        and not has_shifts
        and (
            spec.optimizer == OptimizerType.NEWTON
            or (
                spec.optimizer == OptimizerType.LBFGS
                and block_dim <= NEWTON_AUTO_MAX_DIM
            )
        )
    )


def _solve_block(
    block: EntityBlock,
    offsets: Array,  # (E, n_max) per-sample residual offsets
    w0: Array,  # (E, d) warm-start coefficients
    objective: GLMObjective,
    spec: OptimizerSpec,
    config: OptimizerConfig,
    feature_mask: Optional[Array] = None,  # (E, d) 0/1 Pearson mask
):
    """vmap one optimizer over all entities of a block. Returns (E, d) coefs +
    per-entity (iterations, reason) for the tracker.

    Solver routing (one production path):
    L1 → OWL-QN; explicit TRON honored; otherwise smooth unmasked problems at
    random-effect widths (d ≤ NEWTON_AUTO_MAX_DIM) run batched damped Newton
    (optim/newton.py — 3-5 iterations of MXU Hessian assembly + Cholesky,
    vs the reference's per-entity Breeze L-BFGS inside mapValues,
    RandomEffectCoordinate.scala:228-283), with margin-space L-BFGS as the
    wide-d / feature-masked / shift-normalized fallback.
    """
    use_newton = newton_eligible(
        objective, spec, block.dim, has_mask=feature_mask is not None
    )

    norm = objective.normalization
    folded = norm is not None and not norm.is_identity

    def solve_one(feat, lab, wt, off, w_init, fmask, tmask):
        lb = LabeledBatch(lab, feat, off, wt)
        # Models live in MODEL space; the folded objective optimizes in
        # transformed space (reference SingleNodeOptimizationProblem.scala:95
        # converts out, Optimizer.scala:167 converts the warm start in).
        w_start = norm.model_to_transformed_space(w_init) if folded else w_init
        if feature_mask is not None:
            # Optimize f_m(w) = f(w ∘ m): chain rule masks the gradient and
            # sandwiches the Hessian (M H M) so every solver sees a
            # consistent restricted objective.
            def vg(w):
                v, g = objective.value_and_grad(w * fmask, lb)
                return v, g * fmask

            def hvp_factory(w):
                hv = objective.linearized_hvp(w * fmask, lb)
                return lambda v: fmask * hv(fmask * v)
        else:
            vg = lambda w: objective.value_and_grad(w, lb)

            def hvp_factory(w):
                return objective.linearized_hvp(w, lb)

        if objective.l1_weight > 0.0:
            res = minimize_owlqn(
                vg, w_start, objective.l1_weight, config, objective.l1_mask(w_init)
            )
        elif use_newton:
            res = minimize_newton(objective, lb, w_start, config)
        elif spec.optimizer == OptimizerType.TRON:
            res = minimize_tron(
                vg, None, w_start, config, spec.max_cg_iter,
                hvp_factory=hvp_factory,
            )
        elif feature_mask is not None and (
            objective.normalization is not None
            and objective.normalization.shifts is not None
        ):
            # Shift normalization computes es over the FULL w, so masking X
            # columns does not silence masked coordinates (they'd train as
            # pseudo-intercepts). Keep the gradient-masked formulation.
            res = minimize_lbfgs(vg, w_start, config)
        else:
            # Margin-space L-BFGS on the feature-masked batch: X∘m keeps the
            # GLM margin structure, and masked coordinates (appearing only in
            # the separable L2 term) reach the same post-mask optimum as the
            # gradient-masked formulation.
            lb_m = (
                LabeledBatch(lab, feat * fmask[None, :], off, wt)
                if feature_mask is not None
                else lb
            )
            res = minimize_lbfgs_margin(objective, lb_m, w_start, config)
        w_out = res.w * fmask if feature_mask is not None else res.w
        if folded:
            w_out = norm.transformed_to_model_space(w_out)
        # Entities under the lower-bound filter keep their initial model
        # (reference filterActiveData semantics: not trained this pass).
        w_out = jnp.where(tmask, w_out, w_init)
        return w_out, res.iterations, res.reason_code

    fmask = (
        feature_mask
        if feature_mask is not None
        else jnp.ones((block.num_entities, block.dim), block.features.dtype)
    )
    return jax.vmap(solve_one)(
        block.features, block.label, block.weight, offsets, w0, fmask, block.train_mask
    )


def _dense_warm_start(coefs: Array, block: EntityBlock) -> Array:
    """Fresh (E_b, block.dim) warm-start buffer for a dense block.

    Always a gather (never a view of a live model array), so the solver
    cache may DONATE it; padded entity rows gather row 0 (inert:
    ``train_mask=False`` keeps their output at the warm start, and the
    final scatter drops them); padded feature columns warm-start at 0.
    """
    w0 = coefs[jnp.maximum(block.entity_idx, 0)]
    d = coefs.shape[1]
    if block.dim > d:
        w0 = jnp.pad(w0, ((0, 0), (0, block.dim - d)))
    return w0


@jax.jit
def _block_inputs(block: EntityBlock, runs, total_offset: Array, coefs: Array):
    """What a dense block's solve reads, in ONE launch: its residual offsets
    from the flat (n,) vector and its warm start gathered from the (E, d)
    table. Eager, the same work was six launches a block, and a heavy-tailed
    plan dispatches twenty blocks a pass. ``runs`` is ``block.runs`` (not a
    leaf of the block): with them the offsets are read one window a lane,
    without them gathered one by one."""
    offs = (
        block.gather_offsets(total_offset)
        if runs is None
        else runs.offsets(total_offset, block.n_max)
    )
    return offs, _dense_warm_start(coefs, block)


@jax.jit
def _merge_block_results(coefs: Array, entity_idx, ws, iterations, reasons):
    """A full pass's epilogue in ONE launch: every block's coefficients
    scattered into the (E, d) table (padding lanes target row E and are
    dropped) and the tracker's rows concatenated. One program a block plan;
    a coordinate with the active set on, whose block count changes from pass
    to pass, keeps the per-block scatters on every pass."""
    E, d = coefs.shape
    with jax.named_scope("re_table_scatter"):
        for idx, w in zip(entity_idx, ws):
            coefs = coefs.at[jnp.where(idx >= 0, idx, E)].set(
                w[:, :d].astype(coefs.dtype), mode="drop"
            )
    return (
        coefs,
        jnp.concatenate([jnp.ravel(i) for i in iterations]).astype(jnp.int32),
        jnp.concatenate([jnp.ravel(r) for r in reasons]).astype(jnp.int32),
        jnp.concatenate([jnp.ravel(e) >= 0 for e in entity_idx]),
    )


@dataclasses.dataclass
class RandomEffectCoordinate(Coordinate):
    """Per-entity GLM block over one RE type + feature shard."""

    coordinate_id: str
    dataset: RandomEffectDataset
    task: TaskType
    objective: GLMObjective
    optimizer_spec: OptimizerSpec = dataclasses.field(default_factory=OptimizerSpec)
    # SIMPLE (diag-inverse) or FULL (Cholesky inverse diagonal, vmapped over
    # entities); bool accepted for compatibility (True → SIMPLE).
    compute_variance: object = VarianceComputationType.NONE
    # Compiled-solver cache; None → the process-wide shared default
    # (algorithm/solve_cache.default_cache), so every coordinate / λ-sweep
    # config with the same static setup reuses one executable per shape
    # bucket instead of retracing each CD pass.
    solve_cache: Optional[SolveCache] = None
    # Convergence-gated active-set passes: pass k computes a per-entity
    # "still active" mask IN the solve graph (relative coefficient delta vs
    # ``convergence_tol``); at the next pass boundary the host fetches those
    # tiny (E,) masks — materialized a full pass ago, so the fetch drains no
    # queue — and only still-active entities are re-solved, compacted onto
    # entity allocations the first full pass already compiled (zero new
    # retraces by construction). Converged entities keep their coefficients
    # and scores. The mask fetch is the ONE opt-in host sync of this path;
    # everything else preserves the sync-free dispatch invariant.
    active_set: bool = False
    convergence_tol: float = 1e-4
    # Out-of-core residency: with a byte budget, block data lives in a host
    # master (optionally memory-mapped under ``device_spill_dir``) and only
    # a budgeted working set is device-resident, managed by
    # algorithm/re_store.ReDeviceStore. None → fully resident (default).
    device_budget_bytes: Optional[int] = None
    device_spill_dir: Optional[str] = None
    # Host-owned spill layout: with a member id, spill files live under
    # ``<device_spill_dir>/host-<k>/`` (re_store.partition_spill_dir) so a
    # ring rebalance moves files instead of re-streaming rows.
    device_spill_member: Optional[str] = None
    # Device placement for the entity-sharded multi-device path
    # (parallel/entity_shard.py): commit this coordinate's blocks,
    # coefficients, and solves to ONE device. The solve cache needs no
    # per-device keying — the same jitted executable serves every device of
    # a backend (one trace, bit-identical results), so sharded coordinates
    # share cache entries whenever their block geometry matches. None keeps
    # the default (backend-chosen) placement.
    device: Optional[object] = None

    def __post_init__(self):
        self.compute_variance = normalize_variance_type(self.compute_variance)
        if self.solve_cache is None:
            self.solve_cache = default_cache()
        # Per-entity solves keep only aggregate tracker stats (HBM budget).
        self._config = dataclasses.replace(
            self.optimizer_spec.config(), track_history=False
        )
        self._store = None
        self.last_residency_stats: Optional[dict] = None
        if self.device is not None:
            if self.dataset.projected:
                raise ValueError(
                    "per-device placement supports dense RE datasets only "
                    "(projected blocks route through the default device)"
                )
            if self.compute_variance != VarianceComputationType.NONE:
                raise ValueError(
                    "per-device placement does not support coefficient "
                    "variance computation (the variance pass assembles on "
                    "the default device)"
                )
        if self.device_budget_bytes:
            if self.dataset.projected:
                import logging

                logging.getLogger("photon_tpu").warning(
                    "coordinate %s: out-of-core residency supports dense RE "
                    "datasets only (projected blocks keep content-defined "
                    "col_map widths); training fully resident",
                    self.coordinate_id,
                )
            elif self.dataset.config.features_to_samples_ratio is not None:
                raise ValueError(
                    "out-of-core residency is incompatible with "
                    "features_to_samples_ratio (Pearson masks pin every "
                    "block on device at construction)"
                )
            elif self.compute_variance != VarianceComputationType.NONE:
                raise ValueError(
                    "out-of-core residency does not support coefficient "
                    "variance computation (the variance pass re-reads every "
                    "block outside the residency budget)"
                )
            else:
                from photon_tpu.algorithm.re_store import ReDeviceStore

                self._store = ReDeviceStore(
                    self.dataset.blocks,
                    self.device_budget_bytes,
                    self.coordinate_id,
                    self.device_spill_dir,
                    device=self.device,
                    spill_member=self.device_spill_member,
                )
                # Drop the device references: from here on the dataset's
                # blocks ARE the host master, and device placement happens
                # only through the store's budgeted upload stage.
                self.dataset.blocks = self._store.blocks
        if self.device is not None and self._store is None:
            # Commit every block to the owning device BEFORE derived state
            # (Pearson masks inherit placement from the block arrays).
            self.dataset.blocks = [
                jax.device_put(b, self.device) for b in self.dataset.blocks
            ]
        self._feature_masks: Dict[int, Array] = {}
        ratio = self.dataset.config.features_to_samples_ratio
        if ratio is not None:
            for i, block in enumerate(self.dataset.blocks):
                counts = jnp.sum(block.weight > 0, axis=1)
                # Per-entity cap: k_e = ratio × that entity's sample count
                # (reference RandomEffectDataConfiguration features/samples
                # ratio semantics).
                k_e = jnp.clip(
                    jnp.ceil(counts.astype(jnp.float32) * ratio).astype(jnp.int32),
                    1,
                    block.dim,
                )
                self._feature_masks[i] = pearson_feature_mask(
                    block, k_e, always_keep=self._block_intercept(block)
                )
        # Memoized per-block objectives: the solver-cache key pins the
        # normalization arrays by identity, so they must be built ONCE and
        # reused across CD passes (rebuilding each pass would defeat the
        # compile cache). Dense blocks memoize by block dim — same-dim dense
        # blocks share ONE objective object, which also lets the active-set
        # path pool their entities into one compacted dispatch under one
        # cache key. Projected blocks (content-defined col_maps) stay
        # per-block.
        self._block_objectives: List[GLMObjective] = []
        obj_memo: Dict[Tuple, GLMObjective] = {}
        for i, b in enumerate(self.dataset.blocks):
            memo_key = (b.dim, None) if b.col_map is None else (b.dim, i)
            obj = obj_memo.get(memo_key)
            if obj is None:
                obj = self._block_objective(b)
                obj_memo[memo_key] = obj
            self._block_objectives.append(obj)
        # Host-side valid-row masks/counts (entity_idx >= 0): the dataset
        # kept them from its build, so neither this constructor (one a fit)
        # nor the active-set accounting reads a block back from the device.
        self._block_valid_rows = self.dataset.lane_valid
        self._block_valid_counts = [
            int(np.sum(v)) for v in self._block_valid_rows
        ]
        self._total_valid_entities = int(sum(self._block_valid_counts))
        self._reset_active_set()

    def _block_intercept(self, block: EntityBlock) -> Optional[int]:
        """Intercept column in BLOCK-local space (global index mapped through
        the block's col_map under subspace projection)."""
        g = self.objective.intercept_index
        if g is None or block.col_map is None:
            return g
        pos = np.flatnonzero(np.asarray(block.col_map) == g)
        return int(pos[0]) if pos.size else None

    def _block_objective(self, block: EntityBlock) -> GLMObjective:
        """Objective with the intercept index (and any normalization
        vectors) remapped to block space — the regularization exemption and
        the folded normalization algebra must follow the projected columns."""
        local = self._block_intercept(block)
        norm = self.objective.normalization
        if block.col_map is not None and norm is not None and not norm.is_identity:
            norm = dataclasses.replace(
                norm,
                factors=None if norm.factors is None else norm.factors[block.col_map],
                shifts=None if norm.shifts is None else norm.shifts[block.col_map],
                intercept_index=local,
            )
            return dataclasses.replace(
                self.objective, intercept_index=local, normalization=norm
            )
        if (
            block.col_map is None
            and block.dim > self.dataset.dim
            and norm is not None
            and not norm.is_identity
        ):
            # Dense block padded to a d bucket: extend the normalization
            # vectors with identity entries (factor 1, shift 0) so the folded
            # algebra matches the padded width. Padded columns are all-zero
            # features, so their coefficients stay at the warm start.
            pad = block.dim - self.dataset.dim
            norm = dataclasses.replace(
                norm,
                factors=None
                if norm.factors is None
                else jnp.concatenate(
                    [norm.factors, jnp.ones((pad,), norm.factors.dtype)]
                ),
                shifts=None
                if norm.shifts is None
                else jnp.concatenate(
                    [norm.shifts, jnp.zeros((pad,), norm.shifts.dtype)]
                ),
            )
            return dataclasses.replace(
                self.objective, intercept_index=local, normalization=norm
            )
        if local == self.objective.intercept_index:
            return self.objective
        return dataclasses.replace(self.objective, intercept_index=local)

    # --- active-set pass gating -------------------------------------------

    def _reset_active_set(self) -> None:
        self._cd_pass = 0
        # [(device active mask, device quarantined mask, src_block, src_row)]
        # from the LAST dispatch — src maps route each mask row back to
        # (original block, row).
        self._pending_masks: Optional[list] = None
        self.last_active_set_stats: Optional[dict] = None
        self._fetched_quarantined = 0

    def begin_cd_pass(self, cd_iteration: int) -> None:
        """Pass-boundary hook, called by CoordinateDescent before this
        coordinate's update: a descent restarting at iteration 0 begins with
        a full (ungated) pass, discarding any mask state left over from a
        previous run of the same coordinate object. With an out-of-core
        store, this is also the residency epoch boundary (per-pass eviction
        accounting resets; resident blocks stay warm across passes)."""
        if cd_iteration == 0:
            self._reset_active_set()
        if self._store is not None:
            self._store.begin_pass(cd_iteration)

    def export_active_state(self) -> Optional[dict]:
        """Checkpointable snapshot of the active-set gate: the CD pass
        counter plus the RESOLVED per-block keep masks (host bool arrays).
        Called by CoordinateDescent at a pass-boundary checkpoint — the
        checkpoint write itself materializes every device array, so reading
        the masks here costs nothing extra. None when there is no gate state
        (active_set off, or no pass dispatched yet)."""
        if not self.active_set or self._pending_masks is None:
            return None
        keep = self._fetch_active_masks(count_quarantined=False)
        return dict(
            cd_pass=int(self._cd_pass),
            keep=[np.asarray(k) for k in keep],
        )

    def restore_active_state(self, state: Optional[dict]) -> None:
        """Inverse of :meth:`export_active_state`: reinstall the keep masks
        as identity-mapped pending entries so the first resumed pass is
        gated exactly like the pass the checkpoint interrupted would have
        been — a resume neither re-solves converged entities nor loses
        quarantine/retirement decisions."""
        self._reset_active_set()
        if not self.active_set or state is None:
            return
        self._cd_pass = int(state["cd_pass"])
        pending = []
        for i, k in enumerate(state["keep"]):
            k = np.asarray(k, bool)
            valid = self._block_valid_rows[i]
            sb = np.where(valid, i, -1).astype(np.int32)
            sr = np.where(
                valid, np.arange(k.shape[0], dtype=np.int32), -1
            ).astype(np.int32)
            pending.append((k, np.zeros(k.shape, bool), sb, sr))
        self._pending_masks = pending

    def _fetch_active_masks(self, count_quarantined: bool = True) -> List[np.ndarray]:
        """HOST fetch of the per-entity active masks the PREVIOUS pass
        computed in-graph — the one opt-in sync of the active-set path. The
        (E,) bool arrays were materialized a full CD pass ago, so the fetch
        does not stall the dispatch pipeline. Entities of blocks that were
        not dispatched last pass have no mask entry and stay retired (the
        active set shrinks monotonically within a descent).

        Divergence-quarantine counts piggyback on this same fetch (the masks
        travel together from the same dispatch), so the guards add no host
        syncs of their own."""
        active = [np.zeros((b.num_entities,), bool) for b in self.dataset.blocks]
        quarantined = 0
        with span("re_mask_fetch"):
            for mask_dev, quar_dev, sb, sr in self._pending_masks:
                valid = sr >= 0
                m = np.asarray(mask_dev) & valid
                for b in np.unique(sb[m]):
                    active[b][sr[m & (sb == b)]] = True
                if count_quarantined:
                    quarantined += int(np.sum(np.asarray(quar_dev) & valid))
        if count_quarantined:
            self._fetched_quarantined = quarantined
            if quarantined:
                from photon_tpu.obs.metrics import registry

                registry().counter(
                    "re_entities_quarantined", coordinate=self.coordinate_id
                ).inc(quarantined)
        return active

    def _compact_feature_mask(self, idxs, sb_local, sr, block_c):
        """Gather per-entity Pearson mask rows through the same src pairs a
        compacted block was built from (padding rows get all-ones — inert:
        train_mask=False pins their output to the warm start)."""
        if not self._feature_masks:
            return None
        parts = []
        real = sb_local >= 0
        for b in np.unique(sb_local[real]):
            rows = sr[real & (sb_local == b)]
            parts.append(self._feature_masks[idxs[b]][rows])
        pad = int(np.sum(~real))
        if pad:
            parts.append(jnp.ones((pad, block_c.dim), parts[0].dtype))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def _identity_entry(self, i: int):
        """Dispatch-plan entry for original block i (identity src maps;
        shape-bucket padding rows carry (-1, -1) so per-pass accounting and
        the next mask fetch both see only real entities)."""
        b = self.dataset.blocks[i]
        valid = self._block_valid_rows[i]
        return (
            b,
            self._block_objectives[i],
            self._feature_masks.get(i),
            np.where(valid, i, -1).astype(np.int32),
            np.where(valid, np.arange(b.num_entities), -1).astype(np.int32),
        )

    def _dense_dispatch_entries(
        self, keep: List[np.ndarray], to_device: bool = True
    ) -> list:
        """Dispatch plan for a gated dense pass: group same-geometry blocks,
        pool their still-active rows, and repack them onto entity
        allocations the first full pass already compiled (zero new retraces
        by construction — see data/random_effect.pack_into_sizes). Falls
        back to whole-block skipping when repacking would not shrink the
        dispatched allocation."""
        groups: Dict[Tuple[int, int], List[int]] = {}
        for i, b in enumerate(self.dataset.blocks):
            groups.setdefault((b.n_max, b.dim), []).append(i)
        entries = []
        for idxs in groups.values():
            keeps = [keep[i] for i in idxs]
            live = [i for i, k in zip(idxs, keeps) if k.any()]
            if not live:
                continue  # whole group converged: nothing to dispatch
            members = [self.dataset.blocks[i] for i in idxs]
            allowed = [b.num_entities for b in members]
            total = int(sum(int(k.sum()) for k in keeps))
            plan = pack_into_sizes(total, allowed)
            if sum(plan) >= sum(self.dataset.blocks[i].num_entities for i in live):
                # Repacking buys nothing over skipping the fully-converged
                # blocks — dispatch the live originals and skip the gathers.
                entries.extend(self._identity_entry(i) for i in live)
                continue
            obj = self._block_objectives[idxs[0]]
            idx_arr = np.asarray(idxs, np.int32)
            for block_c, sb_local, sr in compact_entity_blocks(
                members, keeps, allowed, to_device=to_device
            ):
                sb = np.where(
                    sb_local >= 0, idx_arr[np.maximum(sb_local, 0)], -1
                ).astype(np.int32)
                mask_c = self._compact_feature_mask(idxs, sb_local, sr, block_c)
                entries.append((block_c, obj, mask_c, sb, sr))
        return entries

    def _spd_solve_of(self, block: EntityBlock, objective, mask) -> str:
        """The lowering that solves the block's Newton system, static: by
        the route ``_solve_block`` takes and, on the Newton route, the
        block's width and lanes. ``"none"`` off it (OWL-QN, TRON
        and margin-L-BFGS solve no SPD system)."""
        if not newton_eligible(
            objective, self.optimizer_spec, block.dim, has_mask=mask is not None
        ):
            return "none"
        return spd_solve_lowering(block.dim, block.num_entities)

    def _publish_active_set_stats(
        self, gated: bool, dispatched_valid: int, dispatched_alloc: int,
        solved_by: Sequence[str],
    ) -> None:
        """Host-int accounting of the pass (no device reads): how many
        entities were re-solved vs skipped, and how much smaller the
        dispatched entity allocation was than a full pass. Whatever the
        gating, the pass's block solves are counted by the lowering that
        solved their Newton systems (``re_block_solves_total``'s
        ``spd_solve``: ``solved_by``, one a dispatched block, from
        ``_spd_solve_of``)."""
        from photon_tpu.obs.metrics import registry

        reg = registry()
        labels = dict(coordinate=self.coordinate_id)
        for how in solved_by:
            reg.counter("re_block_solves_total", spd_solve=how, **labels).inc()
        if not self.active_set:
            self.last_active_set_stats = None
            return
        total = self._total_valid_entities
        skipped = total - dispatched_valid
        full_alloc = int(sum(b.num_entities for b in self.dataset.blocks))
        ratio = (dispatched_alloc / full_alloc) if full_alloc else 0.0
        reg.gauge("re_entities_active", **labels).set(dispatched_valid)
        reg.counter("re_entities_skipped_total", **labels).inc(skipped)
        reg.histogram("re_compaction_ratio", **labels).observe(ratio)
        self.last_active_set_stats = dict(
            cd_pass=self._cd_pass,
            gated=gated,
            entities_total=total,
            entities_active=dispatched_valid,
            entities_skipped=skipped,
            entities_quarantined=self._fetched_quarantined,
            dispatched_blocks=len(solved_by),
            dispatched_entity_alloc=dispatched_alloc,
            full_entity_alloc=full_alloc,
            compaction_ratio=ratio,
        )

    def train(
        self,
        batch: GameBatch,
        residual_scores: Optional[Array] = None,
        initial_model=None,  # RandomEffectModel | ProjectedRandomEffectModel
    ) -> Tuple[DatumScoringModel, RandomEffectTrackerStats]:
        # Residuals for THIS coordinate's solves: batch offsets + other
        # coordinates' scores (addScoresToOffsets, gathered per block).
        total_offset = batch.offset
        if residual_scores is not None:
            total_offset = total_offset + residual_scores
        if self.device is not None:
            # One (n,) h2d per pass: the flat residual vector follows the
            # coordinate to its owning device so every block gather stays
            # device-local (mixed-device eager ops would otherwise fail).
            total_offset = jax.device_put(total_offset, self.device)
        if self.dataset.projected:
            return self._train_projected(total_offset, initial_model)
        if self._store is not None:
            return self._train_dense_ooc(batch, total_offset, initial_model)
        return self._train_dense(batch, total_offset, initial_model)

    def _train_dense(
        self, batch: GameBatch, total_offset: Array, initial_model
    ) -> Tuple[RandomEffectModel, RandomEffectTrackerStats]:
        E, d = self.dataset.num_entities, self.dataset.dim
        dtype = batch.offset.dtype
        if isinstance(initial_model, ProjectedRandomEffectModel):
            initial_model = initial_model.to_dense()
        coefs = (
            initial_model.coefficients
            if initial_model is not None
            else jnp.zeros((E, d), dtype)
        )
        if self.device is not None:
            # Host-numpy warm starts (out-of-core / sharded-merge models)
            # and fresh zeros both commit to the owning device; a table
            # already resident there passes through untouched.
            coefs = jax.device_put(coefs, self.device)
        # Active-set gate: from pass 2 on (mask state + a warm model), only
        # still-active entities are re-solved, repacked onto already-compiled
        # shapes; converged entities keep their ``coefs`` rows untouched.
        gated = (
            self.active_set
            and self._pending_masks is not None
            and initial_model is not None
        )
        if gated:
            keep = self._fetch_active_masks()
            with span("re_compact"):
                entries = self._dense_dispatch_entries(keep)
        else:
            entries = [self._identity_entry(i) for i in range(len(self.dataset.blocks))]
        tol = self.convergence_tol if self.active_set else None
        if self.device is not None and gated:
            # Compacted blocks are assembled on the default device; move
            # them (and their mask rows) to the owning device. Identity
            # entries are already resident — their puts are no-ops.
            entries = [
                (
                    jax.device_put(block, self.device),
                    obj,
                    None if mask is None else jax.device_put(mask, self.device),
                    sb,
                    sr,
                )
                for block, obj, mask, sb, sr in entries
            ]

        # Sync-free dispatch: issue EVERY block solve before touching any
        # result — no read-modify-write of ``coefs`` between dispatches, so
        # consecutive blocks pipeline on device instead of serializing
        # through the host.
        results = []
        pending = []
        with span("re_dispatch_blocks"):
            for block, obj, mask, sb, sr in entries:
                offs, w0 = _block_inputs(block, block.runs, total_offset, coefs)
                offs = faults.poison("solve.re_block", offs)
                solver = self.solve_cache.block_solver(
                    obj, self.optimizer_spec, self._config,
                    has_mask=mask is not None, convergence_tol=tol,
                )
                if gated and self.solve_cache.max_entries is None:
                    # Compacted shapes were all compiled during the full
                    # first pass; a retrace here is a bug. (With a bounded
                    # cache the entry may have been LRU-evicted — a rebuild
                    # is then legitimate, so the assertion is skipped.)
                    with self.solve_cache.expect_cached(
                        f"active-set dispatch {tuple(block.features.shape)}"
                    ):
                        out = solver(block, offs, w0, mask)
                else:
                    out = solver(block, offs, w0, mask)
                if tol is not None:
                    w, iters, reasons, act, quar = out
                    pending.append((act, quar, sb, sr))
                else:
                    w, iters, reasons = out
                results.append((block, w, iters, reasons))
        if tol is not None:
            self._pending_masks = pending
        self._publish_active_set_stats(
            gated,
            dispatched_valid=int(sum(int(np.sum(sb >= 0)) for *_x, sb, _sr in entries)),
            dispatched_alloc=int(sum(e[0].num_entities for e in entries)),
            solved_by=[self._spd_solve_of(*e[:3]) for e in entries],
        )
        self._cd_pass += 1

        # With the active set on, EVERY pass scatters block by block (async,
        # no host sync): each scatter's signature depends only on that block's
        # (E_alloc,) shape, which the full first pass therefore compiles, so
        # a gated pass that dispatches a different NUMBER of blocks reuses the
        # same executables (one whole-pass program would bake the block count
        # in and recompile at the first compaction; the tracker's two
        # concatenations still do, once a block count). Shape-bucket padding
        # rows target out-of-range row E and are dropped.
        if self.active_set or not results:
            for b, w, _i, _r in results:
                idx = jnp.where(b.entity_idx >= 0, b.entity_idx, E)
                coefs = coefs.at[idx].set(
                    w[:, :d].astype(coefs.dtype), mode="drop"
                )
            stats = self._tracker_stats(
                [(b.entity_idx, it, rs) for b, _w, it, rs in results],
                self.coordinate_id,
            )
        else:
            # Without it a pass dispatches the dataset's blocks in order, so
            # its block count is the plan's: one program merges them all.
            coefs, iters, reasons, valid = _merge_block_results(
                coefs,
                [b.entity_idx for b, *_ in results],
                [w for _b, w, _i, _r in results],
                [it for *_, it, _r in results],
                [rs for *_, rs in results],
            )
            stats = RandomEffectTrackerStats(
                iters, reasons, valid, samples=self.dataset.lane_samples,
                coordinate=self.coordinate_id,
                block_lanes=tuple(b.num_entities for b, *_ in results),
            )

        variances = None
        if self.compute_variance != VarianceComputationType.NONE:
            variances = self._block_variances(coefs, total_offset, dtype)

        model = RandomEffectModel(
            coefs, self.dataset.config.re_type, self.dataset.config.feature_shard,
            self.task, variances,
        )
        return model, stats

    def _train_dense_ooc(
        self, batch: GameBatch, total_offset: Array, initial_model
    ) -> Tuple[RandomEffectModel, RandomEffectTrackerStats]:
        """Out-of-core dense pass: host master coefficients and block data,
        device working set under the store's byte budget, traffic on the
        ingest pipeline machinery (h2d upload stage ahead of the dispatch
        loop, d2h download worker behind it, both bounded).

        Parity with :meth:`_train_dense` is BIT-EXACT by construction:

        * Every warm start gathers from ``coefs_prev`` — a host copy of the
          previous pass's coefficients, frozen at pass start. The resident
          path reads the same values: its scatters all land after every
          dispatch, so no solve ever observes another solve's update within
          a pass.
        * An uploaded block is a bit-identical copy of the resident path's
          block (same arrays, same bucket geometry) and therefore runs the
          SAME cached executable — zero retraces across evictions.
        * Results round-trip d2h losslessly (f32 copies, no arithmetic) and
          scatter into disjoint rows of ``coefs_out`` — order-independent,
          so download order cannot perturb values.

        The returned model carries HOST numpy coefficients (the master
        table); scoring gathers rows through them on demand, producing the
        same device values as a resident model.
        """
        from photon_tpu.algorithm.re_store import block_data_bytes
        from photon_tpu.io.pipeline import (
            DEFAULT_QUEUE_DEPTH,
            StageWorker,
            _finalize_pipeline_telemetry,
            _run_staged,
        )
        from photon_tpu.utils.timed import PipelineStats, record_pipeline

        store = self._store
        E, d = self.dataset.num_entities, self.dataset.dim
        if isinstance(initial_model, ProjectedRandomEffectModel):
            initial_model = initial_model.to_dense()
        coefs_prev = (
            np.asarray(initial_model.coefficients, np.float32)
            if initial_model is not None
            else np.zeros((E, d), np.float32)
        )
        coefs_out = coefs_prev.copy()
        gated = (
            self.active_set
            and self._pending_masks is not None
            and initial_model is not None
        )
        store.begin_pass(self._cd_pass)
        if gated:
            keep = self._fetch_active_masks()
            # The residency policy IS the active set: blocks whose entities
            # all converged are evicted right here, at the pass-boundary
            # sync the mask fetch already paid for.
            store.retire(
                [
                    i
                    for i, k in enumerate(keep)
                    if self._block_valid_counts[i] and not k.any()
                ]
            )
            with span("re_compact"):
                entries = self._dense_dispatch_entries(keep, to_device=False)
        else:
            entries = [
                self._identity_entry(i)
                for i in range(len(self.dataset.blocks))
            ]
        tol = self.convergence_tol if self.active_set else None

        # Residency keys: original blocks cache across passes under their
        # dataset index; compacted blocks are transient (their geometry
        # depends on this pass's active set — an entry could never hit) and
        # are released as soon as their results download.
        block_ids = {id(b): i for i, b in enumerate(self.dataset.blocks)}
        plan = []
        for j, entry in enumerate(entries):
            key = block_ids.get(id(entry[0]), ("compact", self._cd_pass, j))
            plan.append((key, entry))

        def upload(item):
            key, (block, obj, mask, sb, sr) = item
            eidx = np.asarray(block.entity_idx)
            w0 = coefs_prev[np.maximum(eidx, 0)]
            if block.dim > d:
                w0 = np.pad(w0, ((0, 0), (0, block.dim - d)))
            cacheable = isinstance(key, int)
            dev_block, w0_dev = store.acquire(key, block, w0, cacheable)
            return (
                block_data_bytes(block), key, cacheable, dev_block, obj,
                mask, sb, sr, eidx, w0_dev,
            )

        results_host: list = []
        pending_host: list = []

        def download(item):
            key, cacheable, sb, sr, eidx, out = item
            if tol is not None:
                w, iters, reasons, act, quar = out
            else:
                w, iters, reasons = out
            w_host = np.asarray(w)  # blocks until the device solve completes
            valid = eidx >= 0
            coefs_out[eidx[valid]] = w_host[valid, :d]
            results_host.append((eidx, np.asarray(iters), np.asarray(reasons)))
            if tol is not None:
                pending_host.append((np.asarray(act), np.asarray(quar), sb, sr))
            store.mark_solve_done()
            store.release(key, cacheable)

        label = f"re_store/{self.coordinate_id}"
        stats = PipelineStats(overlapped=True)
        record_pipeline(label, stats)
        solve_stage = stats.stage("solve")
        worker = StageWorker(
            "d2h", download, stats.stage("d2h"), depth=DEFAULT_QUEUE_DEPTH,
            nbytes_of=lambda item, _res: 4 * int(np.prod(item[5][0].shape)),
        )
        gen = _run_staged(
            lambda: iter(plan),
            lambda item: 0,
            [("h2d", upload, lambda out: out[0])],
            stats,
            depth=DEFAULT_QUEUE_DEPTH,
            overlap=True,
            source_name="plan",
        )
        t0_wall = time.perf_counter()
        try:
            with span("re_dispatch_blocks"):
                for (_nb, key, cacheable, dev_block, obj, mask, sb, sr,
                     eidx, w0_dev) in gen:
                    t0 = time.perf_counter()
                    offs = faults.poison(
                        "solve.re_block", dev_block.gather_offsets(total_offset)
                    )
                    solver = self.solve_cache.block_solver(
                        obj, self.optimizer_spec, self._config,
                        has_mask=mask is not None, convergence_tol=tol,
                    )
                    store.mark_solve_start()
                    if gated and self.solve_cache.max_entries is None:
                        with self.solve_cache.expect_cached(
                            f"out-of-core dispatch "
                            f"{tuple(dev_block.features.shape)}"
                        ):
                            out = solver(dev_block, offs, w0_dev, mask)
                    else:
                        out = solver(dev_block, offs, w0_dev, mask)
                    solve_stage.add_busy(time.perf_counter() - t0, 0)
                    worker.submit((key, cacheable, sb, sr, eidx, out))
            worker.close()
        except BaseException:
            store.abort_pass()
            worker.abort()
            raise
        finally:
            close = getattr(gen, "close", None)
            if close is not None:
                close()
            stats.wall_s = time.perf_counter() - t0_wall
            _finalize_pipeline_telemetry(label, stats)
            store.end_pass()

        if tol is not None:
            self._pending_masks = pending_host
        self._publish_active_set_stats(
            gated,
            dispatched_valid=int(
                sum(int(np.sum(sb >= 0)) for *_x, sb, _sr in entries)
            ),
            dispatched_alloc=int(sum(e[0].num_entities for e in entries)),
            solved_by=[self._spd_solve_of(*e[:3]) for e in entries],
        )
        self._cd_pass += 1
        self.last_residency_stats = dict(
            store.stats(), pipeline=stats.summary()
        )

        model = RandomEffectModel(
            coefs_out, self.dataset.config.re_type,
            self.dataset.config.feature_shard, self.task, None,
        )
        return model, self._tracker_stats(results_host, self.coordinate_id)

    def _train_projected(
        self, total_offset: Array, initial_model
    ) -> Tuple[ProjectedRandomEffectModel, RandomEffectTrackerStats]:
        """Per-block solves in the compact subspace: nothing of width
        ``d_full`` is ever materialized (model projection lives in the
        block's col_map).

        Active-set gating is WHOLE-BLOCK here: a projected block's
        content-defined col_map width cannot merge with another block's
        without a new shape (= a retrace), so a block is skipped only once
        every one of its entities has converged — its previous coefficients
        carry over untouched."""
        entity_block, entity_row, inv_maps = self.dataset.projection_tables()
        gated = (
            self.active_set
            and self._pending_masks is not None
            and isinstance(initial_model, ProjectedRandomEffectModel)
        )
        keep = self._fetch_active_masks() if gated else None
        tol = self.convergence_tol if self.active_set else None
        parts = []
        pending = []
        dispatched_valid = dispatched_alloc = 0
        solved_by = []
        block_coefs, block_vars, col_maps, block_offs = [], [], [], []
        # Sync-free dispatch: every block solve is issued before any
        # dependent work (variances) touches the outputs.
        with span("re_dispatch_blocks"):
            for i, block in enumerate(self.dataset.blocks):
                offs = faults.poison(
                    "solve.re_block", block.gather_offsets(total_offset)
                )
                col_maps.append(block.col_map)
                block_offs.append(offs)
                if gated and not keep[i].any():
                    prev = initial_model.block_coefs[i]
                    if prev.shape == (block.num_entities, block.dim):
                        # Fully-converged block: carry the warm coefficients
                        # (aliasing is safe — model arrays are never donated;
                        # _initial_block_coefs copies before a donated solve).
                        block_coefs.append(prev)
                        continue
                w0 = self._initial_block_coefs(block, i, initial_model)
                obj = self._block_objectives[i]
                mask = self._feature_masks.get(i)
                solver = self.solve_cache.block_solver(
                    obj, self.optimizer_spec, self._config,
                    has_mask=mask is not None, convergence_tol=tol,
                )
                if gated and self.solve_cache.max_entries is None:
                    with self.solve_cache.expect_cached(
                        f"active-set dispatch {tuple(block.features.shape)}"
                    ):
                        out = solver(block, offs, w0, mask)
                else:
                    out = solver(block, offs, w0, mask)
                if tol is not None:
                    w_new, iters, reasons, act, quar = out
                    pending.append(
                        (
                            act,
                            quar,
                            np.full((block.num_entities,), i, np.int32),
                            np.arange(block.num_entities, dtype=np.int32),
                        )
                    )
                else:
                    w_new, iters, reasons = out
                block_coefs.append(w_new)
                parts.append((block.entity_idx, iters, reasons))
                dispatched_valid += self._block_valid_counts[i]
                dispatched_alloc += block.num_entities
                solved_by.append(self._spd_solve_of(block, obj, mask))
        if tol is not None:
            self._pending_masks = pending
        self._publish_active_set_stats(
            gated, dispatched_valid, dispatched_alloc, solved_by
        )
        self._cd_pass += 1
        if self.compute_variance != VarianceComputationType.NONE:
            for i, block in enumerate(self.dataset.blocks):
                obj = self._block_objectives[i]

                def var_one(feat, lab, wt, off, w, _obj=obj):
                    lb = LabeledBatch(lab, feat, off, wt)
                    bn = _obj.normalization
                    bfolded = bn is not None and not bn.is_identity
                    wv = bn.model_to_transformed_space(w) if bfolded else w
                    v = coefficient_variances(_obj, wv, lb, self.compute_variance)
                    if bfolded and v is not None and bn.factors is not None:
                        v = v * bn.factors**2
                    return v

                block_vars.append(
                    jax.vmap(var_one)(
                        block.features, block.label, block.weight,
                        block_offs[i], block_coefs[i],
                    )
                )
        model = ProjectedRandomEffectModel(
            block_coefs=block_coefs,
            col_maps=col_maps,
            inv_maps=inv_maps,
            entity_block=entity_block,
            entity_row=entity_row,
            d_full=self.dataset.dim,
            re_type=self.dataset.config.re_type,
            feature_shard=self.dataset.config.feature_shard,
            task=self.task,
            block_variances=(
                block_vars
                if self.compute_variance != VarianceComputationType.NONE
                else None
            ),
        )
        return model, self._tracker_stats(parts, self.coordinate_id)

    def _initial_block_coefs(self, block, block_index: int, initial_model) -> Array:
        """Warm-start coefficients in block space from either model form.

        Always returns a buffer the caller exclusively owns (the solver
        cache DONATES it): a same-shape projected warm start is copied
        instead of aliased, so the caller's ``initial_model`` stays valid
        after the donated solve.
        """
        E_b, d_b = block.num_entities, block.dim
        if initial_model is None:
            return jnp.zeros((E_b, d_b), jnp.float32)
        if isinstance(initial_model, ProjectedRandomEffectModel):
            prev = initial_model.block_coefs[block_index]
            if prev.shape == (E_b, d_b):  # same dataset → same blocks
                return jnp.copy(prev)
            initial_model = initial_model.to_dense()
        # Dense (E, d_full) model: gather rows, project into block space
        # (a fresh gather — donation-safe; padded rows gather row 0, inert).
        return block.project_forward(
            initial_model.coefficients[jnp.maximum(block.entity_idx, 0)]
        )

    def _block_variances(self, coefs: Array, total_offset: Array, dtype) -> Array:
        """Per-entity coefficient variances, SIMPLE or FULL, vmapped per block
        (reference RandomEffectOptimizationProblem variance computation)."""
        E, d = self.dataset.num_entities, self.dataset.dim
        variances = jnp.ones((E, d), dtype)

        parts = []
        for i, block in enumerate(self.dataset.blocks):
            obj = self._block_objectives[i]
            norm = obj.normalization
            folded = norm is not None and not norm.is_identity

            def var_one(feat, lab, wt, off, w, _obj=obj, _norm=norm, _folded=folded):
                lb = LabeledBatch(lab, feat, off, wt)
                wv = _norm.model_to_transformed_space(w) if _folded else w
                v = coefficient_variances(_obj, wv, lb, self.compute_variance)
                if _folded and v is not None and _norm.factors is not None:
                    v = v * _norm.factors**2
                return v

            offs = block.gather_offsets(total_offset)
            v = jax.vmap(var_one)(
                block.features, block.label, block.weight, offs,
                _dense_warm_start(coefs, block),
            )
            parts.append((block, v))
        if parts:
            idx = jnp.concatenate(
                [jnp.where(b.entity_idx >= 0, b.entity_idx, E) for b, _v in parts]
            )
            v_all = jnp.concatenate([v[:, :d] for _b, v in parts])
            variances = variances.at[idx].set(v_all.astype(dtype), mode="drop")
        return variances

    @staticmethod
    def _tracker_stats(parts, coordinate: str = "") -> RandomEffectTrackerStats:
        """Assemble the on-device tracker from per-block
        ``(entity_idx, iterations, reasons)`` triples — concatenations only,
        NO device→host transfer (aggregates materialize in ``summary()``)."""
        if not parts:
            return RandomEffectTrackerStats.empty()
        iters = jnp.concatenate([jnp.ravel(it) for _e, it, _r in parts])
        reasons = jnp.concatenate([jnp.ravel(r) for _e, _i, r in parts])
        valid = jnp.concatenate([jnp.ravel(e) >= 0 for e, _i, _r in parts])
        return RandomEffectTrackerStats(
            iterations=iters.astype(jnp.int32),
            reasons=reasons.astype(jnp.int32),
            valid=valid,
            coordinate=coordinate,
            block_lanes=tuple(int(np.size(e)) for e, _i, _r in parts),
        )

    def score(self, model, batch: GameBatch) -> Array:
        return model.score(batch)

    def zero_model(self):
        if self.dataset.projected:
            entity_block, entity_row, inv_maps = self.dataset.projection_tables()
            return ProjectedRandomEffectModel(
                block_coefs=[
                    jnp.zeros((b.num_entities, b.dim), jnp.float32)
                    for b in self.dataset.blocks
                ],
                col_maps=[b.col_map for b in self.dataset.blocks],
                inv_maps=inv_maps,
                entity_block=entity_block,
                entity_row=entity_row,
                d_full=self.dataset.dim,
                re_type=self.dataset.config.re_type,
                feature_shard=self.dataset.config.feature_shard,
                task=self.task,
            )
        return RandomEffectModel(
            jnp.zeros((self.dataset.num_entities, self.dataset.dim), jnp.float32),
            self.dataset.config.re_type,
            self.dataset.config.feature_shard,
            self.task,
        )
