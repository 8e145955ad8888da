"""Random-effect dataset: ragged per-entity data → fixed-shape vmap blocks.

Parity target: reference ``RandomEffectDataset`` (photon-api
data/RandomEffectDataset.scala:52-647) — the most intricate structure in the
reference: per-entity grouped active data (with reservoir sampling bounds,
lower-bound filtering, Pearson feature selection), passive data, and
per-entity subspace projectors, partitioned by a bin-packing partitioner.

TPU-first design: grouping happens once at ingest on the host (numpy), and
produces dense blocks:

  features (E, n_max, d), label/offset-slot/weight (E, n_max), mask via
  weight==0, sample_index (E, n_max) int32 → row in the flat GameBatch.

- The **bin-packing partitioner** (RandomEffectDatasetPartitioner.scala:44-96)
  is unnecessary: after padding, every entity row costs the same, so a plain
  entity-axis sharding over the mesh is perfectly balanced. Bucketing by
  sample count (multiple blocks with different n_max) bounds padding waste —
  the analogue of the reference's per-partition 2GB budget.
- **Reservoir sampling** to ``active_upper_bound`` uses the same
  deterministic-key trick as the reference (byteswapped hash of the uid,
  RandomEffectDataset.scala:517-524) so recomputation/reruns are reproducible.
- **Passive data** (samples beyond the active bound) stays in the flat
  GameBatch and is scored by the gather path — no separate structure needed.
- **Pearson feature selection** (featureSelectionOnActiveData:582-596) is a
  per-entity top-k mask computed batched on device.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.data.batch import LabeledBatch
from photon_tpu.obs.trace import span

Array = jax.Array


def bucket_dim(x: int) -> int:
    """Round a block dimension UP to the geometric shape-bucket grid
    {1, 2, 3, 4, 6, 8, 12, 16, 24, 32, ...} (powers of two and 1.5×).

    Grid ratio ≤ 4/3 bounds per-dim padding waste at ~33% while collapsing
    heterogeneous entity populations onto a handful of block shapes, so the
    compiled-solver cache (algorithm/solve_cache.py) traces once per bucket
    instead of once per exact shape. Padding carries zero weight (samples)
    and ``train_mask=False`` / ``entity_idx=-1`` (entities), so results are
    bit-for-bit decoupled from real rows up to reduction order."""
    x = int(x)
    if x <= 2:
        return max(x, 1)
    p = 1 << (x - 1).bit_length()  # next power of two ≥ x
    if 3 * (p // 4) >= x:
        return 3 * (p // 4)  # 1.5 × previous power of two
    return p


def _publish_pad_waste(re_type: str, **dims: Tuple[int, int]) -> None:
    """Shape-bucket pad-waste telemetry, one (used, allocated) pair per dim
    (entities / samples / features). Published at dataset build — a one-time
    host-side step — so reading it never touches the solve hot path."""
    from photon_tpu.obs.metrics import registry

    reg = registry()
    for dim, (used, alloc) in dims.items():
        kw = dict(re_type=str(re_type), dim=dim)
        reg.counter("bucket_alloc_total", **kw).inc(int(alloc))
        reg.counter("bucket_used_total", **kw).inc(int(used))
        reg.histogram("bucket_pad_waste_ratio", **kw).observe(
            1.0 - (used / alloc) if alloc else 0.0
        )


# ---- block geometry: the rule, stated once ---------------------------------
#
# A coordinate's entities are cut into blocks (lanes, n_max, d) from their row
# counts alone; nothing on GameEstimator or the CLI chooses it.
#
# 1. Every entity's n_max is its row count on the ``bucket_dim`` grid: at most
#    1.5x its rows (4/3 above 1.5 x 2^k). Entities of one grid level share
#    blocks, so a heavy-tailed population pads each user to its own level and
#    never to the largest user's.
# 2. Where few rows live, the grid is made coarser: neighbouring levels are
#    joined, cheapest in added rows first, while the coordinate's allocated
#    rows stay within ``PLAN_MERGE_PAD_BOUND`` x its used rows. One level less
#    is one solver program, and one dispatched block a pass, less.
# 3. A block's lanes are its entities rounded up by ``lane_dim`` (at most
#    1/8 more), so allocated / used rows never pass 1.5 x 1.125 =
#    ``PLAN_PAD_CEILING`` and sit near 1.25-1.33 in practice.
# 4. No block's feature slab (lanes x n_max x d x itemsize) passes the
#    ``slab_budget`` its caller gives: a level over it is cut into equal
#    parts. The caller that places the blocks on a device owns the number
#    (``GameEstimator``: ``slab_budget_of`` that device's memory); this module
#    asks no device, and without a budget nothing is cut. One entity is never
#    cut: a user whose own rows pass the budget gets a one-lane block over it.
PLAN_MERGE_PAD_BOUND = 4.0 / 3.0
PLAN_PAD_CEILING = 1.5 * 1.125
PLAN_SLAB_DEVICE_SHARE = 128


def slab_budget_of(device_bytes: int) -> int:
    """The most bytes one block's feature slab may take on a device of
    ``device_bytes``: 1/128 of it (134 MB of a 16 GiB chip). The share keeps
    an even population's blocks at the lane counts they had before the plan
    (8192 users of ~512 rows x 16 floats: 2304 lanes for 4608), and what it
    buys is time, not memory: a block's Newton loop runs to its slowest
    entity, and uncut that fit was 6 % slower at the same peak (PERF.md §6)."""
    return int(device_bytes) // PLAN_SLAB_DEVICE_SHARE


def lane_dim(e: int) -> int:
    """Round a block's entity count UP to a multiple of 1/16 of the next
    power of two: exact up to 16, at most 1/8 more above. Finer than
    ``bucket_dim`` because a lane costs a Cholesky every Newton iteration,
    and still a grid, so a population that drifts a little from one training
    to the next lands on the shapes the compile cache already holds."""
    e = max(int(e), 1)
    step = max((1 << (e - 1).bit_length()) // 16, 1)
    return -(-e // step) * step


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """One block of the plan: which entities (positions in the count vector,
    ascending) and the allocated shape."""

    members: np.ndarray
    n_max: int
    lanes: int


def _plan_part(members: np.ndarray, counts: np.ndarray, n_max: int,
               bucketed: bool) -> BlockPlan:
    if not bucketed:
        return BlockPlan(members, int(max(counts[members].max(), 1)), members.size)
    return BlockPlan(members, int(n_max), lane_dim(members.size))


def plan_blocks(
    counts: np.ndarray,
    row_bytes: int,
    bucketed: bool = True,
    slab_budget: Optional[int] = None,
) -> List[BlockPlan]:
    """Block geometry for entities with ``counts`` rows each (all > 0) and
    ``row_bytes`` bytes a feature row, by the rule above. ``bucketed=False``
    keeps the grouping and allocates exact shapes; ``slab_budget=None`` cuts
    no level."""
    counts = np.asarray(counts, np.int64)
    if counts.size == 0:
        return []
    grid = {int(c): bucket_dim(int(c)) for c in np.unique(counts)}
    level = np.array([grid[int(c)] for c in counts], np.int64)
    # [n_max, entities] a level, ascending; joined where the rows are few.
    groups = [[int(n), int(np.sum(level == n))] for n in np.unique(level)]

    def allocated(gs) -> int:
        return sum(lane_dim(e) * n for n, e in gs)

    room = PLAN_MERGE_PAD_BOUND * float(counts.sum())
    while len(groups) > 1:
        joined = [
            groups[:i] + [[groups[i + 1][0], groups[i][1] + groups[i + 1][1]]]
            + groups[i + 2:]
            for i in range(len(groups) - 1)
        ]
        cost, best = min(((allocated(g), g) for g in joined), key=lambda cg: cg[0])
        if cost > room:
            break
        groups = best

    plans: List[BlockPlan] = []
    lower = 0
    for n_max, _e in groups:
        members = np.flatnonzero((level > lower) & (level <= n_max))
        lower = n_max
        parts = 1
        while True:
            cut = np.array_split(members, parts)
            if slab_budget is None or cut[0].size == 1 or all(
                lane_dim(m.size) * n_max * row_bytes <= slab_budget for m in cut
            ):
                break
            parts += 1
        plans.extend(_plan_part(m, counts, n_max, bucketed) for m in cut)
    return plans


def _byteswap64(x: np.ndarray) -> np.ndarray:
    """Deterministic sampling key (role of Spark's byteswap64 hash,
    RandomEffectDataset.scala:517-524)."""
    x = x.astype(np.uint64)
    x = ((x & np.uint64(0x00000000FFFFFFFF)) << np.uint64(32)) | (x >> np.uint64(32))
    x = ((x & np.uint64(0x0000FFFF0000FFFF)) << np.uint64(16)) | (
        (x >> np.uint64(16)) & np.uint64(0x0000FFFF0000FFFF)
    )
    x = ((x & np.uint64(0x00FF00FF00FF00FF)) << np.uint64(8)) | (
        (x >> np.uint64(8)) & np.uint64(0x00FF00FF00FF00FF)
    )
    # Mix (splitmix64 finalizer) for uniform ordering keys.
    x = x ^ (x >> np.uint64(30))
    x = x * np.uint64(0xBF58476D1CE4E5B9)
    x = x ^ (x >> np.uint64(27))
    x = x * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


@dataclasses.dataclass
class RandomEffectDataConfig:
    """Reference RandomEffectDataConfiguration (CoordinateDataConfiguration
    .scala:22-76): REType, shard, active-data bounds, feature selection."""

    re_type: str
    feature_shard: str
    active_upper_bound: Optional[int] = None  # numActiveDataPointsUpperBound
    active_lower_bound: Optional[int] = None  # lower bound on #samples/entity
    features_to_samples_ratio: Optional[float] = None  # Pearson selection cap
    # Round block shapes (E, n_max, d) UP to the geometric bucket grid (see
    # ``bucket_dim``) so heterogeneous entity populations collapse onto a
    # handful of cached solver executables (algorithm/solve_cache.py).
    # Padding rows carry zero weight; padded entities carry
    # ``train_mask=False`` and ``entity_idx=-1``. The feature dim is
    # bucketed for dense shards only — a projected block's col_map is
    # content-defined and must stay exact (model I/O maps its columns back
    # to global feature names).
    shape_bucketing: bool = True
    # Per-block feature-subspace compaction (reference
    # LinearSubspaceProjector.scala:36-88 / RandomEffectDataset.scala:383-432,
    # vmap-granularity: the union of a BLOCK's active columns instead of one
    # projector per entity). None = auto: on for sparse shard input, off for
    # dense. Blocks store a ``col_map`` back to the global feature space.
    subspace_projection: Optional[bool] = None


# A block whose lanes are each one contiguous run of the batch reads its
# residual offsets as one window of n_max a lane (``LaneRuns.offsets``) where
# n_max is at least this, and element by element (``gather_offsets``) below
# it. On a TPU v5e a window costs ~0.8 us whatever its length from 24 to 768
# rows and an element of the scalar gather ~7.2 ns, so windows win from ~110
# rows: 2304 x 512 lanes 8.45 -> 1.95 ms, 2048 x 768 11.26 -> 1.82, one lane
# of 524,288 3.76 -> 0.20; 3840 x 96 2.66 -> 3.05 and 49,152 x 24 8.57 ->
# 38.65 lose (PERF.md §6).
RUN_WINDOW_MIN_ROWS = 128


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LaneRuns:
    """Each lane's rows as ONE contiguous run of the batch: ``start`` (E,)
    int32 its first row, ``count`` (E,) int32 its rows (0 on padding
    lanes)."""

    start: Array
    count: Array

    def offsets(self, offsets: Array, n_max: int) -> Array:
        """``EntityBlock.gather_offsets`` of such a block, bit for bit: one
        window of ``n_max`` a lane, the slots past the lane's rows zeroed.
        The source is padded by ``n_max`` because XLA clamps a window's start
        so the window fits its operand, which would shift a run that ends
        within ``n_max`` of the batch's last row."""
        src = jnp.pad(offsets, (0, n_max))
        win = jax.vmap(lambda s: jax.lax.dynamic_slice(src, (s,), (n_max,)))(
            self.start
        )
        slot = jax.lax.broadcasted_iota(jnp.int32, win.shape, 1)
        return jnp.where(slot < self.count[:, None], win, 0.0)


def lane_runs(sample_index: np.ndarray, counts: np.ndarray) -> Optional[LaneRuns]:
    """The block's ``LaneRuns`` where it reads its offsets as runs: every
    lane's rows are consecutive rows of the batch, in order, and the block
    is ``RUN_WINDOW_MIN_ROWS`` slots deep or more. None otherwise."""
    if sample_index.shape[1] < RUN_WINDOW_MIN_ROWS:
        return None
    start = np.where(counts > 0, sample_index[:, 0], 0).astype(np.int32)
    slot = np.arange(sample_index.shape[1])
    runs = np.where(slot < counts[:, None], start[:, None] + slot, -1)
    if not np.array_equal(runs, sample_index):
        return None
    return LaneRuns(jnp.asarray(start), jnp.asarray(counts.astype(np.int32)))


@dataclasses.dataclass(frozen=True)
class EntityBlock:
    """One fixed-shape block of per-entity problems (vmap unit).

    entity_idx: (E,) dense entity index of each row; -1 marks a shape-bucket
      padding row (no entity — excluded from tracker stats and dropped at
      scatter time).
    features:   (E, n_max, d)
    label/weight: (E, n_max); padding samples have weight 0.
    sample_index: (E, n_max) int32 row into the flat GameBatch (-1 padding);
      used to gather residual offsets and scatter scores.
    train_mask: (E,) bool — False for entities filtered by the lower bound
      (they keep a zero model; reference filterActiveData:550-570) and for
      shape-bucket padding rows.
    runs: the block's ``LaneRuns`` where it reads its offsets as runs (see
      ``lane_runs``), else None. Not a leaf of the pytree: a solver traced on
      a block with runs serves a compacted block without them, and a block
      rebuilt from its leaves (``jax.device_put``, the out-of-core store)
      reads its offsets by ``sample_index``, which is always valid; so does
      a copy by ``dataclasses.replace``. Set by the fill.
    """

    entity_idx: Array
    features: Array
    label: Array
    weight: Array
    sample_index: Array
    train_mask: Array
    # Subspace projection (LinearSubspaceProjector role): block-local feature
    # column j corresponds to global column col_map[j]. None = identity
    # (block dim == shard dim).
    col_map: Optional[Array] = None
    runs: Optional[LaneRuns] = dataclasses.field(
        default=None, init=False, repr=False, compare=False
    )

    @property
    def num_entities(self) -> int:
        return self.features.shape[0]

    @property
    def n_max(self) -> int:
        return self.features.shape[1]

    @property
    def dim(self) -> int:
        """Block-local feature dimension (≤ shard dim under projection)."""
        return self.features.shape[2]

    def project_backward(self, w_block: Array, d_full: int) -> Array:
        """Block-space coefficients (E, dim) → global space (E, d_full)
        (reference LinearSubspaceProjector.projectBackward)."""
        if self.col_map is None:
            return w_block
        out = jnp.zeros((w_block.shape[0], d_full), w_block.dtype)
        return out.at[:, self.col_map].set(w_block)

    def project_forward(self, w_global: Array) -> Array:
        """Global-space coefficients (E, d_full) → block space (E, dim)
        (reference LinearSubspaceProjector.projectForward)."""
        if self.col_map is None:
            return w_global
        return w_global[:, self.col_map]

    def gather_offsets(self, offsets: Array) -> Array:
        """(E, n_max) per-sample offsets from the flat (n,) offset/residual
        array (addScoresToOffsets role — a gather, not a join)."""
        safe = jnp.maximum(self.sample_index, 0)
        return jnp.where(self.sample_index >= 0, offsets[safe], 0.0)


jax.tree_util.register_dataclass(
    EntityBlock,
    data_fields=[
        "entity_idx", "features", "label", "weight", "sample_index",
        "train_mask", "col_map",
    ],
    meta_fields=[],
)


@dataclasses.dataclass
class RandomEffectDataset:
    """All blocks for one random-effect coordinate + bookkeeping.

    ``dim`` is the GLOBAL shard dimension; under subspace projection each
    block's local dim (``block.dim``) may be far smaller."""

    config: RandomEffectDataConfig
    blocks: List[EntityBlock]
    num_entities: int  # total interned entities E for this RE type
    dim: int
    # (Σ lanes,) int32 rows of every lane, blocks in order, 0 on padding
    # lanes: the tracker weights iterations by it. None where there are no
    # blocks.
    lane_samples: Optional[Array] = None
    # One (lanes,) HOST bool array a block: True where the lane holds an
    # entity (``entity_idx >= 0``). Kept from the build's host arrays so a
    # coordinate built on this dataset (one a fit) reads no block back.
    lane_valid: List[np.ndarray] = dataclasses.field(default_factory=list)

    @property
    def num_active_samples(self) -> int:
        return int(sum(np.sum(np.asarray(b.weight) > 0) for b in self.blocks))

    @property
    def projected(self) -> bool:
        return any(b.col_map is not None for b in self.blocks)

    def projection_tables(self):
        """(entity_block, entity_row, inv_maps) for ProjectedRandomEffectModel:
        entity e's model lives at row entity_row[e] of block entity_block[e]
        (−1 = entity has no data); inv_maps[b] maps global→block columns."""
        entity_block = np.full((self.num_entities,), -1, np.int32)
        entity_row = np.zeros((self.num_entities,), np.int32)
        inv_maps = []
        for b, block in enumerate(self.blocks):
            eidx = np.asarray(block.entity_idx)
            real = eidx >= 0  # skip shape-bucket padding rows
            entity_block[eidx[real]] = b
            entity_row[eidx[real]] = np.arange(eidx.size, dtype=np.int32)[real]
            inv = np.full((self.dim,), -1, np.int32)
            if block.col_map is not None:
                inv[np.asarray(block.col_map)] = np.arange(block.dim, dtype=np.int32)
            else:
                inv = np.arange(self.dim, dtype=np.int32)
            inv_maps.append(jnp.asarray(inv))
        return jnp.asarray(entity_block), jnp.asarray(entity_row), inv_maps


def _shard_input(features, config: RandomEffectDataConfig):
    """``(sp_indices, sp_values, dense, n, d, project, feat_dtype)`` of a
    shard given dense or as a host padded-sparse triple."""
    if isinstance(features, tuple):
        sp_indices, sp_values, d = features
        sp_indices = np.asarray(sp_indices)
        sp_values = np.asarray(sp_values)
        project = True if config.subspace_projection is None else config.subspace_projection
        if not project:
            raise ValueError("sparse shard input requires subspace projection")
        return (sp_indices, sp_values, None, sp_indices.shape[0], d, project,
                sp_values.dtype)
    features = np.asarray(features)
    n, d = features.shape
    return (None, None, features, n, d, bool(config.subspace_projection),
            features.dtype)


@dataclasses.dataclass(frozen=True)
class EntityGrouping:
    """A coordinate's rows grouped by entity and planned into blocks, before
    any block is filled: ``entities`` holds (entity id, its active rows) in
    id order, the rows as the stable sort and the reservoir left them;
    ``plans`` the block geometry over their counts."""

    entities: List[Tuple[int, np.ndarray]]
    counts: np.ndarray
    plans: List[BlockPlan]
    d: int
    project: bool
    itemsize: int

    def run_rows(self) -> np.ndarray:
        """Every row a block holds, each entity's rows together and in their
        order, entities in the order of their first row. A batch laid out in
        this order gives every lane one contiguous run of rows. The order
        follows which rows share an entity, not what the entities are
        called, so the other coordinates' sums over the rows (the fixed
        effect's) see the same order however the ids are assigned."""
        if not self.entities:
            return np.zeros((0,), np.int64)
        first = np.array([rows.min() for _eid, rows in self.entities])
        return np.concatenate([self.entities[i][1] for i in np.argsort(first)])

    def window_share(self) -> float:
        """The share of the planned slots that sit in blocks deep enough to
        read their offsets as windows (``RUN_WINDOW_MIN_ROWS``) once every
        lane is a run."""
        slots = np.array([p.lanes * p.n_max for p in self.plans], np.int64)
        deep = np.array([p.n_max >= RUN_WINDOW_MIN_ROWS for p in self.plans])
        return float(slots[deep].sum() / slots.sum()) if slots.sum() else 0.0

    def block_bytes(self) -> int:
        """Device bytes the filled blocks take: features (at the shard's
        width, an upper bound for a projected block), label, weight and row
        index a slot."""
        return int(sum(
            p.lanes * p.n_max * (self.d * self.itemsize + 12) for p in self.plans
        ))


def group_entity_rows(
    entity_ids: np.ndarray,  # (n,) dense int32 entity index per sample
    features,  # (n, d) dense np array OR host sparse (indices, values, dim)
    config: RandomEffectDataConfig,
    uid: Optional[np.ndarray] = None,
    slab_budget: Optional[int] = None,
) -> EntityGrouping:
    """The grouping half of :func:`build_random_effect_dataset`: rows sorted
    by entity, the reservoir cap, and the block plan."""
    _spi, _spv, _dense, n, d, project, feat_dtype = _shard_input(features, config)
    uid = np.arange(n, dtype=np.int64) if uid is None else uid.astype(np.int64)

    # Group sample rows by entity (sorted for determinism).
    order = np.argsort(entity_ids, kind="stable")
    sorted_eids = entity_ids[order]
    uniq, starts = np.unique(sorted_eids, return_index=True)
    groups = np.split(order, starts[1:])

    # Drop the group of negative (unknown) entity ids if present.
    entities: List[Tuple[int, np.ndarray]] = [
        (int(eid), rows) for eid, rows in zip(uniq, groups) if eid >= 0
    ]

    # Reservoir-sample active data per entity (deterministic key on uid).
    ub = config.active_upper_bound
    if ub is not None:
        capped = []
        for eid, rows in entities:
            if len(rows) > ub:
                keys = _byteswap64(uid[rows])
                rows = rows[np.argsort(keys, kind="stable")[:ub]]
            capped.append((eid, rows))
        entities = capped

    # Block geometry, planned from the row counts.
    counts = np.array([len(rows) for _, rows in entities], np.int64)
    with span("plan"):
        # A projected block's width is its content's (known only once it is
        # grouped), so the byte budget holds dense blocks alone.
        d_alloc = bucket_dim(d) if config.shape_bucketing else d
        plans = plan_blocks(
            counts,
            0 if project else d_alloc * np.dtype(feat_dtype).itemsize,
            bucketed=config.shape_bucketing,
            slab_budget=slab_budget,
        )
    return EntityGrouping(
        entities, counts, plans, d, project, np.dtype(feat_dtype).itemsize
    )


def build_random_effect_dataset(
    entity_ids: np.ndarray,  # (n,) dense int32 entity index per sample
    features,  # (n, d) dense np array OR host sparse (indices, values, dim)
    label: np.ndarray,
    weight: np.ndarray,
    num_entities: int,
    config: RandomEffectDataConfig,
    uid: Optional[np.ndarray] = None,
    existing_model_mask: Optional[np.ndarray] = None,
    slab_budget: Optional[int] = None,
) -> RandomEffectDataset:
    """Host-side grouping: the TPU analogue of RandomEffectDataset.apply
    (reference :260-349 build pipeline).

    Samples per entity beyond ``active_upper_bound`` are dropped from active
    training data via deterministic reservoir sampling (they remain passive:
    still scored through the flat batch).

    ``existing_model_mask`` ((num_entities,) bool, warm-start only):
    entities WITHOUT an existing model are exempt from
    ``active_lower_bound`` — the reference's ignoreThresholdForNewModels
    flag (GameTrainingDriver.scala:169-172, RandomEffectDataset.scala:
    550-570: keep entity if count >= bound OR id not in existing keys).

    ``features`` is either a dense (n, d) array or a host-side padded-sparse
    triple ``(indices (n,k) int, values (n,k) float, dim)`` — the wide-shard
    route. Sparse input implies per-block subspace projection (compacting
    each block to the union of its entities' active columns, reference
    RandomEffectDataset.scala:383-432); dense input opts in via
    ``config.subspace_projection=True``.

    ``slab_budget`` (bytes) is rule 4 of the block plan above: the caller
    that owns the device gives it, and None cuts no level.
    """
    grouping = group_entity_rows(entity_ids, features, config, uid, slab_budget)
    return fill_entity_blocks(
        grouping, features, label, weight, num_entities, config,
        existing_model_mask,
    )


def fill_entity_blocks(
    grouping: EntityGrouping,
    features,
    label: np.ndarray,
    weight: np.ndarray,
    num_entities: int,
    config: RandomEffectDataConfig,
    existing_model_mask: Optional[np.ndarray] = None,
    row_of: Optional[np.ndarray] = None,
) -> RandomEffectDataset:
    """The fill half of :func:`build_random_effect_dataset`: the planned
    blocks from the shard's host arrays. ``row_of`` ((n,) int) is the row of
    the batch the blocks are trained on that each row of these arrays became
    (the batch laid out in another order); ``sample_index`` names those rows.
    A block whose lanes are each one contiguous run of them carries the
    runs (``EntityBlock.runs``)."""
    sp_indices, sp_values, features, _n, d, project, feat_dtype = _shard_input(
        features, config
    )
    entities, counts, plans = grouping.entities, grouping.counts, grouping.plans
    if not entities:
        return RandomEffectDataset(config, [], num_entities, d)
    lb = config.active_lower_bound or 0
    blocks: List[EntityBlock] = []
    lane_samples = []
    lane_valid = []
    with span("fill"):
        for plan in plans:
            sel, n_max, E_alloc = plan.members, plan.n_max, plan.lanes
            E = sel.size
            block_rows = np.concatenate([entities[gi][1] for gi in sel])

            # Subspace compaction: block feature space = union of active columns
            # (LinearSubspaceProjector per vmap block instead of per entity).
            col_map = inv_map = None
            if project:
                if sp_indices is not None:
                    active = sp_indices[block_rows][sp_values[block_rows] != 0]
                    col_map = np.unique(active).astype(np.int64)
                else:
                    col_map = np.flatnonzero(
                        np.any(features[block_rows] != 0, axis=0)
                    ).astype(np.int64)
                if col_map.size == 0:
                    col_map = np.zeros((1,), np.int64)  # degenerate all-zero block
                inv_map = np.full((d,), -1, dtype=np.int64)
                inv_map[col_map] = np.arange(col_map.size)
            d_block = int(col_map.size) if project else d

            # Shape bucketing: the plan rounded (E, n_max) up to its grids and d
            # follows here, so the solver cache keys collapse; padding is inert
            # by construction (weight 0, train_mask False, entity_idx −1).
            # Projected blocks keep their exact content-defined col_map width.
            n_used = int(counts[sel].sum())
            lane_samples.append(np.zeros((E_alloc,), np.int32))
            lane_samples[-1][:E] = counts[sel]
            d_used = d_block
            if config.shape_bucketing and not project:
                d_block = bucket_dim(d_block)
            _publish_pad_waste(
                config.re_type,
                entities=(E, E_alloc),
                samples=(n_used, E_alloc * n_max),
                features=(d_used, d_block),
            )

            feat = np.zeros((E_alloc, n_max, d_block), dtype=feat_dtype)
            lab = np.zeros((E_alloc, n_max), dtype=label.dtype)
            wt = np.zeros((E_alloc, n_max), dtype=weight.dtype)
            sidx = np.full((E_alloc, n_max), -1, dtype=np.int32)
            eidx = np.full((E_alloc,), -1, dtype=np.int32)
            tmask = np.zeros((E_alloc,), dtype=bool)
            for j, gi in enumerate(sel):
                eid, rows = entities[gi]
                m = len(rows)
                if sp_indices is not None:
                    # Scatter padded-sparse rows into the compact block space.
                    loc = inv_map[sp_indices[rows]]  # (m, k), −1 only for 0-values
                    vals = sp_values[rows]
                    keep = vals != 0
                    r_i, _k_i = np.nonzero(keep)
                    np.add.at(feat[j], (r_i, loc[keep]), vals[keep])
                elif project:
                    feat[j, :m] = features[rows][:, col_map]
                else:
                    # d_block ≥ d under bucketing; padded columns stay zero.
                    feat[j, :m, :d] = features[rows]
                lab[j, :m] = label[rows]
                wt[j, :m] = weight[rows]
                sidx[j, :m] = rows if row_of is None else row_of[rows]
                eidx[j] = eid
                tmask[j] = m >= lb or (
                    existing_model_mask is not None
                    and not bool(existing_model_mask[eid])
                )
            lane_valid.append(eidx >= 0)
            block = EntityBlock(
                entity_idx=jnp.asarray(eidx),
                features=jnp.asarray(feat),
                label=jnp.asarray(lab),
                weight=jnp.asarray(wt),
                sample_index=jnp.asarray(sidx),
                train_mask=jnp.asarray(tmask),
                col_map=None if col_map is None else jnp.asarray(col_map, jnp.int32),
            )
            object.__setattr__(block, "runs", lane_runs(sidx, lane_samples[-1]))
            blocks.append(block)
    return RandomEffectDataset(
        config, blocks, num_entities, d,
        lane_samples=jnp.asarray(np.concatenate(lane_samples)),
        lane_valid=lane_valid,
    )


def pack_into_sizes(total: int, allowed_sizes: Sequence[int]) -> List[int]:
    """Plan compacted block sizes for ``total`` active rows using ONLY sizes
    drawn from ``allowed_sizes`` — the entity allocations of the dataset's
    original blocks with the same (n_max, d) geometry. Every one of those
    allocations was compiled during the first full CD pass, so a plan drawn
    from this set lands exclusively on already-cached executables: the
    active-set path's zero-retrace guarantee holds by construction.

    Greedy: the smallest allowed size that holds the remainder, else the
    largest allowed size repeatedly.
    """
    sizes = sorted({int(s) for s in allowed_sizes})
    if not sizes:
        raise ValueError("pack_into_sizes needs at least one allowed size")
    plan: List[int] = []
    remaining = int(total)
    while remaining > 0:
        plan.append(next((s for s in sizes if s >= remaining), sizes[-1]))
        remaining -= plan[-1]
    return plan


def compact_entity_blocks(
    blocks: Sequence[EntityBlock],
    keep: Sequence[np.ndarray],
    allowed_sizes: Optional[Sequence[int]] = None,
    to_device: bool = True,
) -> List[Tuple[EntityBlock, np.ndarray, np.ndarray]]:
    """Repack the still-active rows of same-geometry dense blocks into the
    smallest already-compiled shapes (the active-set repack path).

    ``blocks`` must share (n_max, dim) and be dense (``col_map is None``) —
    projected blocks keep content-defined col_map widths that cannot merge
    without a retrace, so they use whole-block skipping instead. ``keep[i]``
    is a host bool array over block i's entity rows; shape-bucket padding
    rows (entity_idx == -1) must already be False there.

    Returns ``[(compacted_block, src_block, src_row), ...]``: the two int32
    arrays are the per-row entity_gather index map — for every row of the
    compacted block, the (source block index, source row) it was gathered
    from, (-1, -1) on the compacted block's own padding rows. The map routes
    the NEXT pass's per-row active masks back onto original blocks; merging
    coefficients back needs no map at all, because compacted rows carry
    their real ``entity_idx`` and the coordinate's single drop-mode scatter
    already lands them.

    ``to_device=False`` keeps the compacted block's leaves as host numpy —
    the out-of-core path's upload stage does the ``device_put`` itself, so
    compaction must not eagerly place blocks on device (that would double
    the device footprint outside the residency budget).
    """
    if not blocks:
        return []
    geom = {(b.n_max, b.dim, b.col_map is None) for b in blocks}
    if len(geom) != 1 or not next(iter(geom))[2]:
        raise ValueError(
            f"compact_entity_blocks needs same-geometry dense blocks, got {geom}"
        )
    src_block_parts, src_row_parts = [], []
    for i, k in enumerate(keep):
        rows = np.flatnonzero(np.asarray(k))
        src_block_parts.append(np.full(rows.shape, i, np.int32))
        src_row_parts.append(rows.astype(np.int32))
    src_block = np.concatenate(src_block_parts)
    src_row = np.concatenate(src_row_parts)
    total = int(src_block.size)
    if total == 0:
        return []
    if allowed_sizes is None:
        allowed_sizes = [b.num_entities for b in blocks]
    plan = pack_into_sizes(total, allowed_sizes)

    n_max, d = blocks[0].n_max, blocks[0].dim
    out: List[Tuple[EntityBlock, np.ndarray, np.ndarray]] = []
    start = 0
    for size in plan:
        sb = src_block[start:start + size]
        sr = src_row[start:start + size]
        start += sb.size
        pad = size - sb.size

        def gather(field, pad_arr, sb=sb, sr=sr, pad=pad):
            # Host-side numpy gather, deliberately: jnp advanced indexing
            # would eagerly compile one XLA gather kernel per distinct
            # selection shape — seconds of warmup landing in the first gated
            # pass. The repack is a pass-boundary host step by design, so
            # gather on host and ship only the compacted block to device.
            # src pairs are sorted (block asc, row asc), so concatenating
            # per-source gathers in block order preserves row order exactly.
            parts = [
                np.asarray(getattr(blocks[b], field))[sr[sb == b]]
                for b in np.unique(sb)
            ]
            if pad:
                parts.append(pad_arr)
            merged = parts[0] if len(parts) == 1 else np.concatenate(parts)
            if not to_device:
                return np.ascontiguousarray(merged)
            return jnp.asarray(merged)

        block_c = EntityBlock(
            entity_idx=gather("entity_idx", np.full((pad,), -1, np.int32)),
            features=gather(
                "features", np.zeros((pad, n_max, d), blocks[0].features.dtype)
            ),
            label=gather("label", np.zeros((pad, n_max), blocks[0].label.dtype)),
            weight=gather(
                "weight", np.zeros((pad, n_max), blocks[0].weight.dtype)
            ),
            sample_index=gather(
                "sample_index", np.full((pad, n_max), -1, np.int32)
            ),
            train_mask=gather("train_mask", np.zeros((pad,), bool)),
            col_map=None,
        )
        out.append(
            (
                block_c,
                np.concatenate([sb, np.full((pad,), -1, np.int32)]),
                np.concatenate([sr, np.full((pad,), -1, np.int32)]),
            )
        )
    return out


def pearson_feature_mask(
    block: EntityBlock,
    max_features: Array,
    always_keep: Optional[int] = None,
) -> Array:
    """Per-entity Pearson-correlation top-k feature mask (reference
    LocalDataset.filterFeaturesByPearsonCorrelationScore:103), batched on
    device: (E, d) 0/1 mask keeping each entity's top ``max_features[e]``
    most label-correlated features.

    Constant/absent columns (zero variance for that entity — including
    features the entity never touches) score 0 so they cannot crowd out
    informative features; the intercept column (``always_keep``) is exempt
    from the filter, matching the reference's interceptOpt convention.
    """
    w = block.weight  # (E, n_max) — 0 on padding
    tot = jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-12)
    X, y = block.features, block.label
    mx = jnp.sum(w[..., None] * X, axis=1) / tot  # (E, d)
    my = jnp.sum(w * y, axis=1, keepdims=True) / tot  # (E, 1)
    dx = X - mx[:, None, :]
    dy = (y - my)[..., None]
    cov = jnp.sum(w[..., None] * dx * dy, axis=1)
    vx = jnp.sum(w[..., None] * dx * dx, axis=1)
    vy = jnp.sum(w[..., None] * dy * dy, axis=1)
    corr = jnp.abs(cov / jnp.sqrt(jnp.maximum(vx * vy, 1e-24)))
    corr = jnp.where(vx < 1e-12, 0.0, corr)
    # Rank features per entity (0 = most correlated); keep rank < k_e.
    order = jnp.argsort(-corr, axis=1)
    ranks = jnp.argsort(order, axis=1)
    k_e = jnp.asarray(max_features).reshape(-1, 1)
    mask = (ranks < k_e).astype(X.dtype)
    if always_keep is not None:
        mask = mask.at[:, always_keep].set(1.0)
    return mask
