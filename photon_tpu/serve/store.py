"""Hot/cold entity coefficient store for online GAME scoring.

Photon ML's GAME shape — one global model plus millions of per-entity
models — makes serving a lookup-then-score problem. The lookup side is this
module: per-entity coefficient rows live COLD on the host (the numpy master
copy ``load_game_model(to_device=False)`` returns) and HOT in a
device-resident table under an explicit byte budget, with LRU demotion.
Request entity ids resolve to hot-table SLOTS; misses gather their rows from
the host master and upload them in one shape-bucketed scatter per batch, so
the device never holds more than the working set and the jitted scorer's
program shapes never change.

Slot discipline: coordinates sharing a random-effect type share ONE slot
assignment (their tables are indexed by the same ``entity_ids`` array in the
batch), so the LRU is per RE type with one device table per coordinate.
A type whose full table fits the budget is PINNED — full device residency,
entity ids pass through as slots, the miss path never runs. Unknown/cold
entities resolve to slot -1 and score 0, exactly the batch path's
cold-start semantics.

Projected (subspace) random-effect models get the same treatment at BLOCK
granularity: each per-block subspace table keeps a hot row pool, and the
device-resident ``entity_block``/``entity_row`` maps are rewritten by
scatter as entities promote and demote (a demoted entity's map entry goes
to -1 — it can never be read for a requested entity, because ``resolve``
promotes every entity of the batch before the scorer runs). Entity ids pass
through as indices for projected types either way, pinned or not.

The LRU policy itself (recency order, in-use protection, demotion
accounting) lives in data/residency.py — shared verbatim with the
out-of-core TRAINING store (algorithm/re_store.py), so serving and training
cannot drift on residency semantics.

Zero-downtime reload builds a NEW store (and scorer) for the incoming model
while the old one keeps serving, then swaps atomically — see
serve/engine.py. The store itself is single-writer: the engine serializes
``resolve``/upload under its batch lock.
"""

from __future__ import annotations

import base64
import dataclasses
import logging
from typing import Dict, List, Optional, Sequence

import numpy as np

from photon_tpu.data.random_effect import bucket_dim
from photon_tpu.data.residency import SlotLru
from photon_tpu.models.coefficients import Coefficients
from photon_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    ProjectedRandomEffectModel,
    RandomEffectModel,
)
from photon_tpu.models.glm import GeneralizedLinearModel
from photon_tpu.obs.metrics import registry
from photon_tpu.obs.trace import span
from photon_tpu.serve.routing import HashRing
from photon_tpu.utils import faults, resources

logger = logging.getLogger("photon_tpu")

_scatter_rows = None

_SHARD_AXIS = "data"  # mesh axis name for device-sharded hot tables


@dataclasses.dataclass
class StorePartition:
    """Entity-shard ownership for ONE fleet replica: this store serves only
    the entities the consistent-hash ring assigns ``replica_id`` (the same
    ring the front-end router uses, so a correctly routed request always
    lands on the owner). A non-owned (foreign) entity resolves to -1 —
    cold-start semantics, the random effect contributes 0 and the request
    scores FE-only on already-compiled shapes. That is the fleet's
    cross-shard fallback: mis-routed and orphaned entities degrade, never
    error.

    ``compact_host=True`` additionally shards the OOC host master by the
    same hash (``algorithm/re_store.py``-style keying, at entity-row
    granularity): only owned rows are kept host-side, so N replicas hold
    ~1/N of the coefficient bytes each. The trade: an entity that becomes
    owned AFTER build (ring rebalance toward this replica) has no host row
    and stays FE-only until the engine rebuilds the store (reload) — the
    documented re-home procedure.

    ``re_types=None`` shards every budget-managed type; the fleet normally
    passes just the routing RE type so secondary types stay fully
    replicated (exact scores on every replica). Pinned types (full device
    residency — they fit the budget) are never sharded: replicating a
    small table is cheaper than degrading its lookups."""

    replica_id: str
    ring: HashRing
    re_types: Optional[tuple] = None
    compact_host: bool = True

    def applies_to(self, re_type: str) -> bool:
        return self.re_types is None or re_type in self.re_types

    def owns(self, key) -> bool:
        return self.ring.owner(str(key)) == self.replica_id


def _owned_mask(
    partition: StorePartition, entity_index, num_entities: int
) -> np.ndarray:
    """(E,) bool: which dense entity indices this replica owns. Hashes the
    SAME string the router hashes — the raw entity id via the entity index
    when one exists, else the decimal index (callers that send pre-interned
    int keys route on that same decimal form)."""
    owned = np.zeros(num_entities, bool)
    for i in range(num_entities):
        key = entity_index.entity_id(i) if entity_index is not None else i
        owned[i] = partition.owns(key)
    return owned


def _oom_contained(re_type: str, fn):
    """Run a device scatter/upload with OOM containment: on
    RESOURCE_EXHAUSTED, release dropped table buffers (the scatters are
    functional — the superseded tables are garbage the allocator may still
    hold) and retry once, counting
    ``serve_store_oom_evictions_total{re_type}``. ``fn`` must be
    idempotent. A second OOM becomes a clean
    :class:`~photon_tpu.utils.resources.DeviceMemoryError`."""
    import gc

    try:
        return fn()
    except Exception as exc:
        if not resources.is_device_oom(exc):
            raise
        registry().counter(
            "serve_store_oom_evictions_total", re_type=re_type
        ).inc()
        logger.warning(
            "serve store: device OOM uploading %s rows; collecting dropped "
            "buffers and retrying once: %s", re_type, exc,
        )
        gc.collect()
        try:
            return fn()
        except Exception as exc2:
            if not resources.is_device_oom(exc2):
                raise
            raise resources.DeviceMemoryError(
                f"serve store: device OOM uploading {re_type} rows even "
                "after releasing dropped buffers. Shrink --hot-bytes / the "
                "hot-row capacity or the max batch size, or add device "
                "memory."
            ) from exc2


def _scatter(table, idx, rows):
    """Jitted hot-table row upload. ``idx`` is padded to a bucketed length
    with an out-of-range value (``mode="drop"`` discards it — NB negative
    indices WRAP in XLA scatters, so high-out-of-range is the safe filler).
    One executable per shape triple; ``warm_uploads`` compiles them before
    traffic. Shared by 2-D coefficient-table and 1-D entity-map scatters."""
    global _scatter_rows
    if _scatter_rows is None:
        import jax

        # NOT donated: the previous table buffer may still be referenced by
        # a scoring-model pytree a caller holds (e.g. the transformer's
        # init-time model) — donating it would invalidate those references.
        _scatter_rows = jax.jit(lambda t, i, r: t.at[i].set(r, mode="drop"))
    return _scatter_rows(table, idx, rows)


@dataclasses.dataclass
class _ReGroup:
    """All random-effect coordinates sharing one RE type: one slot LRU,
    one device table per coordinate."""

    re_type: str
    coord_ids: List[str]
    host_coefs: Dict[str, np.ndarray]  # cid -> (E, d) float32 master copy
    num_entities: int
    capacity: int  # H: hot rows (== num_entities when pinned)
    pinned: bool
    tables: Dict[str, object] = dataclasses.field(default_factory=dict)
    lru: Optional[SlotLru] = None
    # Fleet partition state: ``owned[i]`` is this replica's ownership of
    # dense entity i (None = unsharded type); ``compact_of[i]`` maps a full
    # entity index to its compacted host row (-1 = row absent host-side).
    owned: Optional[np.ndarray] = None
    compact_of: Optional[np.ndarray] = None
    # Device-shard state (multi-chip serving): the hot table is laid out as
    # S contiguous per-shard segments of ``shard_cap`` rows, sharded over
    # the device mesh's data axis so each segment is resident on the device
    # the training side trained it on (parallel/entity_shard.py — the same
    # plan, same ring, same hashed keys). Pinned groups address the table
    # through ``perm`` (entity → shard-grouped slot); unpinned groups run
    # one SlotLru per segment (``shard_lrus``) over disjoint slot ranges.
    shard_plan: Optional[object] = None
    shard_cap: Optional[int] = None
    perm: Optional[np.ndarray] = None  # pinned: (E,) entity -> slot
    shard_lrus: Optional[List[SlotLru]] = None

    @property
    def row_bytes(self) -> int:
        return sum(4 * c.shape[1] for c in self.host_coefs.values())

    def _lru_for(self, entity: int) -> SlotLru:
        if self.shard_lrus is not None:
            return self.shard_lrus[int(self.shard_plan.shard_of[entity])]
        return self.lru

    def slot_get(self, entity: int) -> Optional[int]:
        return self._lru_for(entity).get(entity)

    def slot_peek(self, entity: int) -> Optional[int]:
        return self._lru_for(entity).peek(entity)

    def slot_claim(self, entity: int, protected) -> int:
        return self._lru_for(entity).claim(entity, protected)

    def resident_count(self) -> int:
        if self.pinned:
            return self.num_entities
        if self.shard_lrus is not None:
            return sum(len(l) for l in self.shard_lrus)
        return len(self.lru)


@dataclasses.dataclass
class _ProjCoord:
    """One projected coordinate's hot state: per-block hot tables + the
    device entity→(block, row) maps the scorer gathers through."""

    cid: str
    sub: ProjectedRandomEffectModel  # host master (block_coefs as numpy)
    host_blocks: List[np.ndarray]  # [(E_b, d_b) float32]
    entity_block: np.ndarray  # (E,) host master map
    entity_row: np.ndarray  # (E,)
    capacities: List[int]  # hot rows per block
    lrus: List[Optional[SlotLru]]  # entity id -> hot row, per block
    tables: List[object]  # device [(H_b, d_b)]
    dev_entity_block: object  # device (E,) int32; -1 = cold (scores 0)
    dev_entity_row: object  # device (E,) int32
    demoted: List[int] = dataclasses.field(default_factory=list)

    @property
    def hot_bytes(self) -> int:
        return sum(
            4 * h * b.shape[1] for h, b in zip(self.capacities, self.host_blocks)
        )


@dataclasses.dataclass
class _ProjGroup:
    """Projected coordinates sharing one RE type. Unlike dense groups they
    need no shared slot space: ``resolve`` returns entity INDICES (the
    per-coordinate device maps translate entity → hot row), so each
    coordinate promotes into its own block tables independently."""

    re_type: str
    num_entities: int
    coords: List[_ProjCoord]
    pinned: bool  # every coordinate fully resident → no promotion path
    owned: Optional[np.ndarray] = None  # fleet partition mask (no compaction)


class HotColdEntityStore:
    """Entity-model residency manager + scoring-model factory.

    ``hot_bytes`` bounds the device bytes of CACHED random-effect tables
    (split across RE types proportionally to their full size). The floor is
    ``min_hot_rows`` per type — the engine passes its max batch size, which
    guarantees every unique entity of one batch fits resident simultaneously
    (the resolve path never has to evict a slot the current batch needs).
    """

    def __init__(
        self,
        model: GameModel,
        entity_indexes: Optional[Dict] = None,
        hot_bytes: int = 64 << 20,
        min_hot_rows: int = 64,
        partition: Optional[StorePartition] = None,
        device_shards: Optional[int] = None,
    ):
        import jax

        self._entity_indexes = dict(entity_indexes or {})
        self._partition = partition
        # Multi-chip mode: split every dense hot table into ``device_shards``
        # entity shards (consistent-hash plan shared with training) and lay
        # them out over the device mesh's data axis. The mesh spans the
        # largest device count that divides the shard count, so per-shard
        # segments chunk evenly; a single-device backend degrades to S
        # segments on one chip (same slot discipline, no mesh surprises).
        self._device_shards: Optional[int] = None
        self._mesh = None
        self._table_sharding = None
        self._replicated_sharding = None
        if device_shards:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec

            S = int(device_shards)
            devs = jax.devices()
            n_use = max(
                k for k in range(1, min(S, len(devs)) + 1) if S % k == 0
            )
            self._device_shards = S
            self._mesh = Mesh(
                np.asarray(devs[:n_use]), (_SHARD_AXIS,)
            )
            self._table_sharding = NamedSharding(
                self._mesh, PartitionSpec(_SHARD_AXIS)
            )
            self._replicated_sharding = NamedSharding(
                self._mesh, PartitionSpec()
            )
        self._groups: Dict[str, _ReGroup] = {}
        self._proj_groups: Dict[str, _ProjGroup] = {}
        self._re_subs: Dict[str, RandomEffectModel] = {}
        base: Dict[str, object] = {}

        by_type: Dict[str, List] = {}
        proj_by_type: Dict[str, List] = {}
        for cid, sub in model.models.items():
            if isinstance(sub, RandomEffectModel):
                by_type.setdefault(sub.re_type, []).append((cid, sub))
            elif isinstance(sub, ProjectedRandomEffectModel):
                proj_by_type.setdefault(sub.re_type, []).append((cid, sub))
            else:
                base[cid] = jax.device_put(sub)

        # One budget pool across dense AND projected types, split
        # proportionally to each type's full table size.
        budget_total = sum(
            sum(4 * np.asarray(s.coefficients).shape[1] for _, s in subs)
            * max(np.asarray(subs[0][1].coefficients).shape[0], 1)
            for subs in by_type.values()
        ) + sum(
            sum(self._proj_full_bytes(s) for _, s in subs)
            for subs in proj_by_type.values()
        )
        reg = registry()
        for re_type, subs in by_type.items():
            host = {
                cid: np.ascontiguousarray(
                    np.asarray(s.coefficients, dtype=np.float32)
                )
                for cid, s in subs
            }
            E = {c.shape[0] for c in host.values()}
            if len(E) != 1:
                raise ValueError(
                    f"RE type {re_type!r}: coordinates disagree on entity "
                    f"count {sorted(E)}"
                )
            E = E.pop()
            row_bytes = sum(4 * c.shape[1] for c in host.values())
            full_bytes = row_bytes * max(E, 1)
            share = (
                int(hot_bytes * full_bytes / budget_total)
                if budget_total
                else hot_bytes
            )
            cap = max(int(min_hot_rows), share // max(row_bytes, 1))
            pinned = cap >= E
            cap = min(cap, E) if pinned else cap
            owned = None
            compact_of = None
            # Partition applies only to budget-managed (unpinned) types: a
            # pinned table is fully resident everywhere, so sharding it
            # would degrade lookups to save nothing.
            if partition is not None and partition.applies_to(re_type) \
                    and not pinned:
                owned = _owned_mask(
                    partition, self._entity_indexes.get(re_type), E
                )
                owned_count = int(owned.sum())
                # The shard, not the full table, is this replica's working
                # set: capacity beyond the owned count would never fill.
                cap = max(int(min_hot_rows), min(cap, max(owned_count, 1)))
                if partition.compact_host:
                    sel = np.flatnonzero(owned)
                    compact_of = np.full(E, -1, np.int32)
                    compact_of[sel] = np.arange(sel.size, dtype=np.int32)
                    host = {
                        cid: np.ascontiguousarray(host[cid][sel])
                        for cid in host
                    }
                reg.gauge(
                    "serve_store_owned_entities", re_type=re_type
                ).set(owned_count)
            shard_plan = None
            shard_cap = None
            perm = None
            shard_lrus = None
            if self._device_shards:
                from photon_tpu.parallel.entity_shard import build_shard_plan

                shard_plan = build_shard_plan(
                    E,
                    self._device_shards,
                    entity_index=self._entity_indexes.get(re_type),
                )
                S = shard_plan.n_shards
                if pinned:
                    # Shard-grouped full residency: segment s holds shard
                    # s's entities at their local indices, padded to the
                    # largest shard so segments chunk evenly over the mesh.
                    shard_cap = max(int(shard_plan.counts.max()), 1)
                    cap = S * shard_cap
                    perm = (
                        shard_plan.shard_of.astype(np.int64) * shard_cap
                        + shard_plan.local_of
                    ).astype(np.int32)
                else:
                    # Budget split evenly across segments, floored at
                    # min_hot_rows EACH: one batch's entities may all hash
                    # to a single shard, and its segment alone must hold
                    # them resident simultaneously.
                    shard_cap = max(int(min_hot_rows), cap // S)
                    cap = S * shard_cap
                    shard_lrus = [
                        SlotLru(
                            shard_cap,
                            on_demote=self._demote_counter(re_type),
                            base=s * shard_cap,
                        )
                        for s in range(S)
                    ]
            group = _ReGroup(
                re_type=re_type,
                coord_ids=[cid for cid, _ in subs],
                host_coefs=host,
                num_entities=E,
                capacity=max(cap, 1),
                pinned=pinned,
                owned=owned,
                compact_of=compact_of,
                shard_plan=shard_plan,
                shard_cap=shard_cap,
                perm=perm,
                shard_lrus=shard_lrus,
            )
            if pinned:
                # ``device_put`` returns at once and the table lands later;
                # the fence ends the span when it has, so the upload's
                # seconds are read here and not under whichever fence comes
                # next (the scorer's warm-up, which cannot start before).
                with span("table_upload"):
                    if perm is not None:
                        tabs = {}
                        for cid in group.coord_ids:
                            t = np.zeros(
                                (group.capacity, host[cid].shape[1]),
                                np.float32,
                            )
                            t[perm] = host[cid]
                            tabs[cid] = jax.device_put(
                                t, self._table_sharding
                            )
                        group.tables = tabs
                    else:
                        group.tables = {
                            cid: jax.device_put(host[cid])
                            for cid in group.coord_ids
                        }
                    jax.block_until_ready(group.tables)
            else:
                group.tables = {
                    cid: jax.device_put(
                        np.zeros(
                            (group.capacity, host[cid].shape[1]), np.float32
                        ),
                        self._table_sharding,
                    )
                    for cid in group.coord_ids
                }
                if shard_lrus is None:
                    group.lru = SlotLru(
                        group.capacity, on_demote=self._demote_counter(re_type)
                    )
            self._groups[re_type] = group
            for cid, s in subs:
                self._re_subs[cid] = s
            reg.gauge("serve_store_hot_rows", re_type=re_type).set(
                group.capacity
            )
            reg.gauge("serve_store_hot_bytes", re_type=re_type).set(
                group.capacity * row_bytes
            )
            reg.gauge("serve_store_pinned", re_type=re_type).set(int(pinned))
        for re_type, subs in proj_by_type.items():
            group = self._build_proj_group(
                re_type, subs, hot_bytes, budget_total, min_hot_rows
            )
            # Projected types shard by predicate only (foreign → -1); their
            # block-structured host masters stay whole — block compaction
            # would need a remap per block and buys little (the maps are
            # int32, the blocks are small by construction).
            if partition is not None and partition.applies_to(re_type) \
                    and not group.pinned:
                group.owned = _owned_mask(
                    partition,
                    self._entity_indexes.get(re_type),
                    group.num_entities,
                )
            self._proj_groups[re_type] = group
            hot = sum(c.hot_bytes for c in group.coords)
            reg.gauge("serve_store_hot_rows", re_type=re_type).set(
                sum(sum(c.capacities) for c in group.coords)
            )
            reg.gauge("serve_store_hot_bytes", re_type=re_type).set(hot)
            reg.gauge("serve_store_pinned", re_type=re_type).set(
                int(group.pinned)
            )
        self._base = base

    @staticmethod
    def _proj_full_bytes(sub: ProjectedRandomEffectModel) -> int:
        return sum(
            4 * np.asarray(b).shape[0] * np.asarray(b).shape[1]
            for b in sub.block_coefs
        )

    def _demote_counter(self, re_type: str):
        def on_demote(_victim, _slot):
            registry().counter(
                "serve_store_demotions_total", re_type=re_type
            ).inc()

        return on_demote

    def _build_proj_group(
        self, re_type, subs, hot_bytes, budget_total, min_hot_rows
    ) -> _ProjGroup:
        """Per-block hot/cold state for projected coordinates. Budget share
        splits across a coordinate's blocks proportionally to block size,
        floored at ``min_hot_rows`` rows per block — any one batch's
        entities may all land in one block, so every block must be able to
        hold a full batch's worth of hot rows simultaneously."""
        import jax

        coords: List[_ProjCoord] = []
        num_entities = 0
        for cid, sub in subs:
            host_blocks = [
                np.ascontiguousarray(np.asarray(b, dtype=np.float32))
                for b in sub.block_coefs
            ]
            entity_block = np.asarray(sub.entity_block, np.int32)
            entity_row = np.asarray(sub.entity_row, np.int32)
            E = int(entity_block.shape[0])
            num_entities = max(num_entities, E)
            full_bytes = sum(4 * b.shape[0] * b.shape[1] for b in host_blocks)
            share = (
                int(hot_bytes * full_bytes / budget_total)
                if budget_total
                else hot_bytes
            )
            capacities: List[int] = []
            for b in host_blocks:
                b_bytes = 4 * b.shape[0] * max(b.shape[1], 1)
                b_share = (
                    int(share * b_bytes / full_bytes) if full_bytes else share
                )
                cap = max(
                    int(min_hot_rows), b_share // max(4 * b.shape[1], 1)
                )
                capacities.append(max(min(cap, b.shape[0]), 1))
            pinned = all(
                c >= b.shape[0] for c, b in zip(capacities, host_blocks)
            )
            demoted: List[int] = []
            if pinned:
                capacities = [b.shape[0] for b in host_blocks]
                tables = [jax.device_put(b) for b in host_blocks]
                lrus: List[Optional[SlotLru]] = [None] * len(host_blocks)
                dev_entity_block = jax.device_put(entity_block)
                dev_entity_row = jax.device_put(entity_row)
            else:
                tables = [
                    jax.device_put(np.zeros((c, b.shape[1]), np.float32))
                    for c, b in zip(capacities, host_blocks)
                ]
                demote = self._proj_demoter(re_type, demoted)
                lrus = [SlotLru(c, on_demote=demote) for c in capacities]
                # Everything starts COLD: map entries are -1 until promoted.
                dev_entity_block = jax.device_put(
                    np.full((E,), -1, np.int32)
                )
                dev_entity_row = jax.device_put(np.zeros((E,), np.int32))
            coords.append(
                _ProjCoord(
                    cid=cid,
                    sub=sub,
                    host_blocks=host_blocks,
                    entity_block=entity_block,
                    entity_row=entity_row,
                    capacities=capacities,
                    lrus=lrus,
                    tables=tables,
                    dev_entity_block=dev_entity_block,
                    dev_entity_row=dev_entity_row,
                    demoted=demoted,
                )
            )
        return _ProjGroup(
            re_type=re_type,
            num_entities=num_entities,
            coords=coords,
            pinned=all(self._coord_pinned(c) for c in coords),
        )

    def _proj_demoter(self, re_type: str, demoted: List[int]):
        counter = self._demote_counter(re_type)

        def on_demote(victim, slot):
            demoted.append(int(victim))
            counter(victim, slot)

        return on_demote

    @staticmethod
    def _coord_pinned(coord: _ProjCoord) -> bool:
        return all(lru is None for lru in coord.lrus)

    # -- residency ---------------------------------------------------------

    @property
    def device_shards(self) -> Optional[int]:
        """Hot-table shard count in multi-chip mode (None = single-table)."""
        return self._device_shards

    @property
    def mesh(self):
        """The device mesh sharded hot tables live on (None = unsharded).
        The engine replicates request batches over it so the jitted scorer
        sees consistent placements; the score merge is the one all-gather
        XLA inserts for the slot gather against the sharded table."""
        return self._mesh

    @property
    def batch_sharding(self):
        """Replicated NamedSharding for request batches (None = unsharded)."""
        return self._replicated_sharding

    def shard_snapshot(self, re_type: str) -> Optional[dict]:
        """The entity→shard assignment identity for ``re_type`` — comparable
        against ``EntityShardPlan.snapshot()`` from the training side (tests
        assert train and serve derive the same assignment from the ring)."""
        group = self._groups.get(re_type)
        if group is None or group.shard_plan is None:
            return None
        return group.shard_plan.snapshot()

    @property
    def re_types(self) -> List[str]:
        """RE types under hot/cold management (table-swapped at scoring)."""
        return list(self._groups)

    @property
    def entity_re_types(self) -> List[str]:
        """Every RE type a batch must carry entity ids for — dense managed
        groups plus projected (entity-index-addressed) types."""
        return list(self._groups) + [
            t for t in self._proj_groups if t not in self._groups
        ]

    def group(self, re_type: str) -> Optional[_ReGroup]:
        return self._groups.get(re_type)

    def proj_group(self, re_type: str) -> Optional[_ProjGroup]:
        return self._proj_groups.get(re_type)

    def _intern(self, re_type: str, key, num_entities: int) -> int:
        """Request entity key → dense [0, E) index; -1 when unknown."""
        if isinstance(key, str):
            eidx = self._entity_indexes.get(re_type)
            i = eidx.lookup(key) if eidx is not None else -1
        else:
            i = int(key)
        return i if 0 <= i < num_entities else -1

    def resolve(self, re_type: str, keys: Sequence) -> np.ndarray:
        """Entity keys (interned ints or raw string ids) → hot-table slots
        (dense groups) or entity indices (projected groups), promoting
        misses from the host master. -1 rows (cold start) pass through and
        score 0. Single-writer: the engine's batch lock serializes calls."""
        faults.check("serve.store_resolve", label=re_type)
        group = self._groups.get(re_type)
        if group is None:
            proj = self._proj_groups.get(re_type)
            if proj is None:
                return np.full(len(keys), -1, np.int32)
            ids = np.fromiter(
                (self._intern(re_type, k, proj.num_entities) for k in keys),
                dtype=np.int32,
                count=len(keys),
            )
            if proj.owned is not None:
                ids = self._mask_foreign(re_type, proj.owned, None, ids)
            if not proj.pinned:
                self._promote_projected(proj, ids)
            return ids
        ids = np.fromiter(
            (self._intern(re_type, k, group.num_entities) for k in keys),
            dtype=np.int64,
            count=len(keys),
        )
        if group.owned is not None or group.compact_of is not None:
            ids = self._mask_foreign(
                re_type, group.owned, group.compact_of, ids
            )
        if group.pinned:
            ids = ids.astype(np.int32)
            if group.perm is None:
                return ids
            # Device-sharded pinned table: slots are shard-grouped, so the
            # passthrough routes through the entity→slot permutation.
            out = np.full(len(ids), -1, np.int32)
            pos = ids >= 0
            out[pos] = group.perm[ids[pos]]
            return out

        reg = registry()
        slots = np.empty(len(ids), np.int32)
        in_use = set()
        misses: List[int] = []  # entity ids needing upload, slot assigned
        hits = 0
        for j, e in enumerate(ids):
            e = int(e)
            if e < 0:
                slots[j] = -1
                continue
            slot = group.slot_get(e)
            if slot is not None:
                if e not in in_use and e not in misses:
                    hits += 1
            else:
                slot = self._claim_slot(group, e, in_use)
                misses.append(e)
            in_use.add(e)
            slots[j] = slot
        if hits:
            reg.counter("serve_store_hits_total", re_type=re_type).inc(hits)
        if misses:
            reg.counter("serve_store_misses_total", re_type=re_type).inc(
                len(misses)
            )
            # Idempotent: a pure scatter of host rows into already-claimed
            # slots, so the OOM containment may safely run it twice.
            _oom_contained(re_type, lambda: self._upload(group, misses))
        return slots

    def _mask_foreign(
        self,
        re_type: str,
        owned: Optional[np.ndarray],
        compact_of: Optional[np.ndarray],
        ids: np.ndarray,
    ) -> np.ndarray:
        """Foreign (non-owned, or owned-but-host-row-absent after a ring
        rebalance onto a compacted master) entities → -1. They score
        FE-only — the fleet's degrade-instead-of-error fallback — and are
        counted per type so the soak can prove correctly routed traffic
        never takes this path."""
        pos = np.flatnonzero(ids >= 0)
        if pos.size == 0:
            return ids
        idx = ids[pos].astype(np.int64)
        servable = (
            owned[idx] if owned is not None
            else np.ones(idx.size, bool)
        )
        if compact_of is not None:
            servable = servable & (compact_of[idx] >= 0)
        foreign = int(pos.size - servable.sum())
        if foreign:
            registry().counter(
                "serve_store_foreign_total", re_type=re_type
            ).inc(foreign)
            ids = ids.copy()
            ids[pos[~servable]] = -1
        return ids

    def set_partition(self, partition: Optional[StorePartition]) -> None:
        """Swap the ownership predicate live (ring rebalance / drain).
        Cheap — only the owned masks recompute; compacted host rows are NOT
        re-fetched, so an entity newly owned by this replica but absent
        from its compacted master stays FE-only until the engine rebuilds
        the store (the reload-based re-home procedure). Hot rows that just
        became foreign age out of the LRU naturally — they can no longer be
        requested through resolve. Callers serialize with resolve (the
        engine's batch lock)."""
        self._partition = partition
        for re_type, group in self._groups.items():
            if group.pinned:
                continue
            if partition is not None and partition.applies_to(re_type):
                group.owned = _owned_mask(
                    partition,
                    self._entity_indexes.get(re_type),
                    group.num_entities,
                )
            else:
                # Unsharded again; compact_of (if any) keeps masking the
                # rows this replica never had.
                group.owned = None
        for re_type, proj in self._proj_groups.items():
            if (partition is not None and partition.applies_to(re_type)
                    and not proj.pinned):
                proj.owned = _owned_mask(
                    partition,
                    self._entity_indexes.get(re_type),
                    proj.num_entities,
                )
            else:
                proj.owned = None

    def partition_stats(self) -> Optional[dict]:
        """Shard-ownership summary for ``/healthz``'s fleet snapshot."""
        part = self._partition
        if part is None:
            return None
        types = {}
        for re_type, group in self._groups.items():
            if group.owned is None and group.compact_of is None:
                continue
            types[re_type] = dict(
                owned=(
                    int(group.owned.sum()) if group.owned is not None
                    else None
                ),
                entities=group.num_entities,
                compacted=group.compact_of is not None,
                host_rows=(
                    int(next(iter(group.host_coefs.values())).shape[0])
                    if group.host_coefs else 0
                ),
            )
        for re_type, proj in self._proj_groups.items():
            if proj.owned is not None:
                types[re_type] = dict(
                    owned=int(proj.owned.sum()),
                    entities=proj.num_entities,
                    compacted=False,
                    projected=True,
                )
        return dict(
            replica_id=part.replica_id,
            ring_version=part.ring.version,
            ring_members=len(part.ring),
            compact_host=part.compact_host,
            re_types=types,
        )

    # -- warm shard handoff ------------------------------------------------

    def shard_export(
        self,
        target_snapshot: dict,
        target_member: Optional[str] = None,
        include_cold: bool = True,
    ) -> dict:
        """Everything a new owner needs BEFORE the ring flips: for each
        sharded dense group, the entities this replica serves today whose
        owner changes under ``target_snapshot`` (optionally only those
        moving to ``target_member``), their host coefficient rows (raw
        float32 bytes, base64 — exact, so handed-off rows score
        bit-identically), and a hot flag for rows currently resident in
        this replica's device cache. ``include_cold=False`` trims the
        payload to the hot set — the join case, where the newcomer built
        its own host shard from disk and only needs cache warmth.
        Callers serialize with resolve (the engine's batch lock)."""
        part = self._partition
        out = dict(
            fromReplica=part.replica_id if part is not None else None,
            targetVersion=int(target_snapshot.get("version", 0)),
            groups=[],
        )
        if part is None:
            return out
        target = HashRing.from_snapshot(target_snapshot)
        for re_type, group in self._groups.items():
            if group.pinned or not part.applies_to(re_type):
                continue
            eidx = self._entity_indexes.get(re_type)
            keys: List[object] = []
            hot: List[bool] = []
            dense: List[int] = []
            for i in range(group.num_entities):
                if group.owned is not None and not group.owned[i]:
                    continue
                if group.compact_of is not None and group.compact_of[i] < 0:
                    continue  # no host row here — nothing to hand off
                key = eidx.entity_id(i) if eidx is not None else i
                new_owner = target.owner(key)
                if new_owner == part.replica_id:
                    continue
                if target_member is not None and new_owner != target_member:
                    continue
                is_hot = group.slot_peek(i) is not None
                if not include_cold and not is_hot:
                    continue
                keys.append(key)
                hot.append(bool(is_hot))
                dense.append(i)
            if not keys:
                continue
            idx = np.asarray(dense, np.int64)
            src = (
                group.compact_of[idx].astype(np.int64)
                if group.compact_of is not None
                else idx
            )
            coords = {}
            for cid in group.coord_ids:
                rows = np.ascontiguousarray(
                    group.host_coefs[cid][src], dtype=np.float32
                )
                coords[cid] = dict(
                    dim=int(rows.shape[1]),
                    rows=base64.b64encode(rows.tobytes()).decode("ascii"),
                )
            out["groups"].append(
                dict(reType=re_type, keys=keys, hot=hot, coords=coords)
            )
        return out

    def shard_import(self, payload: dict, upload_chunk: int = 64) -> dict:
        """Install a peer's :meth:`shard_export` payload: append host rows
        this (compacted) master lacks — killing the FE-only window that
        otherwise follows a drain, since ``set_partition`` never re-fetches
        rows — and pre-promote the peer's hot set into the device cache so
        the first post-flip requests hit instead of miss. ``upload_chunk``
        must not exceed the warmed max batch size (the scatter buckets are
        already compiled; a bigger chunk would retrace). Callers serialize
        with resolve (the engine's batch lock)."""
        stats = dict(rowsAdded=0, rowsKnown=0, unknownKeys=0, promoted=0)
        reg = registry()
        for rec in payload.get("groups") or []:
            re_type = rec.get("reType")
            group = self._groups.get(re_type)
            if group is None or group.pinned:
                continue
            keys = rec.get("keys") or []
            hot_flags = list(rec.get("hot") or [False] * len(keys))
            ids = np.fromiter(
                (self._intern(re_type, k, group.num_entities) for k in keys),
                dtype=np.int64,
                count=len(keys),
            )
            known = ids >= 0
            stats["unknownKeys"] += int((~known).sum())
            decoded: Optional[Dict[str, np.ndarray]] = {}
            for cid in group.coord_ids:
                c = (rec.get("coords") or {}).get(cid)
                if c is None:
                    decoded = None
                    break
                arr = np.frombuffer(
                    base64.b64decode(c["rows"]), np.float32
                ).reshape(-1, int(c["dim"]))
                if arr.shape[0] != len(keys):
                    decoded = None
                    break
                decoded[cid] = arr
            if decoded is None:
                continue
            kn = np.flatnonzero(known)
            if group.compact_of is not None and kn.size:
                missing = kn[group.compact_of[ids[kn]] < 0]
                if missing.size:
                    base_rows = int(
                        next(iter(group.host_coefs.values())).shape[0]
                        if group.host_coefs
                        else 0
                    )
                    for cid in group.coord_ids:
                        group.host_coefs[cid] = np.ascontiguousarray(
                            np.vstack(
                                [group.host_coefs[cid], decoded[cid][missing]]
                            )
                        )
                    group.compact_of[ids[missing]] = base_rows + np.arange(
                        missing.size, dtype=np.int32
                    )
                    stats["rowsAdded"] += int(missing.size)
                    reg.counter(
                        "serve_store_handoff_rows_total", re_type=re_type
                    ).inc(int(missing.size))
                stats["rowsKnown"] += int(kn.size - missing.size)
            else:
                stats["rowsKnown"] += int(kn.size)
            promote = [
                int(e)
                for e, h in zip(ids, hot_flags)
                if h and e >= 0 and group.slot_peek(int(e)) is None
            ]
            if group.compact_of is not None:
                promote = [e for e in promote if group.compact_of[e] >= 0]
            promote = promote[: group.capacity]
            chunk_n = max(1, int(upload_chunk))
            promoted_here = 0
            for start in range(0, len(promote), chunk_n):
                chunk = promote[start:start + chunk_n]
                for e in chunk:
                    group.slot_claim(e, ())
                _oom_contained(
                    re_type, lambda c=list(chunk): self._upload(group, c)
                )
                promoted_here += len(chunk)
            if promoted_here:
                stats["promoted"] += promoted_here
                reg.counter(
                    "serve_store_handoff_promoted_total", re_type=re_type
                ).inc(promoted_here)
        return stats

    def _claim_slot(self, group: _ReGroup, entity: int, in_use: set) -> int:
        # Demotes the least-recently-used entity that is NOT part of the
        # current batch. capacity ≥ max batch size guarantees a victim.
        try:
            return group.slot_claim(entity, in_use)
        except RuntimeError:
            what = (
                f"shard segment capacity {group.shard_cap}"
                if group.shard_lrus is not None
                else f"capacity {group.capacity}"
            )
            raise RuntimeError(
                f"hot store for {group.re_type!r} exhausted: batch has more "
                f"unique entities than {what}"
            ) from None

    def _upload(self, group: _ReGroup, entities: List[int]) -> None:
        """One bucketed scatter per coordinate: miss count pads up the
        shape grid, filler indices land out of range and drop."""
        faults.check("serve.store_upload", label=group.re_type)
        m = len(entities)
        m_b = bucket_dim(m)
        idx = np.full(m_b, group.capacity, np.int32)
        idx[:m] = [group.slot_peek(e) for e in entities]
        ent = np.asarray(entities, np.int64)
        if group.compact_of is not None:
            # Only servable entities reach here (resolve masked the rest),
            # so every compacted row index is valid.
            ent = group.compact_of[ent].astype(np.int64)
        for cid in group.coord_ids:
            host = group.host_coefs[cid]
            rows = np.zeros((m_b, host.shape[1]), np.float32)
            rows[:m] = host[ent]
            group.tables[cid] = _scatter(group.tables[cid], idx, rows)

    def _promote_projected(self, proj: _ProjGroup, ids: np.ndarray) -> None:
        """Promote this batch's entities into each projected coordinate's
        per-block hot tables and rewrite the device entity maps. A demoted
        victim's map entry is scattered to -1 in the same pass — stale rows
        are never read because every REQUESTED entity is promoted here,
        before the scorer runs."""
        reg = registry()
        batch_ids = [int(e) for e in ids if e >= 0]
        for coord in proj.coords:
            if self._coord_pinned(coord):
                continue
            # Injected ``oom`` rules here take the same contained
            # gc-and-retry path a real allocator failure would.
            _oom_contained(
                proj.re_type,
                lambda: faults.check("serve.store_upload",
                                     label=proj.re_type),
            )
            # Entities of this batch grouped by their host block, for the
            # per-block in-use protection sets.
            in_use_by_block: Dict[int, set] = {}
            for e in batch_ids:
                b = int(coord.entity_block[e])
                if b >= 0:
                    in_use_by_block.setdefault(b, set()).add(e)
            misses: List[int] = []  # promoted entity ids, slot assigned
            rows_of: Dict[int, int] = {}
            hits = 0
            seen = set()
            for e in batch_ids:
                if e in seen:
                    continue
                seen.add(e)
                b = int(coord.entity_block[e])
                if b < 0:
                    continue  # entity has no model in this coordinate
                lru = coord.lrus[b]
                slot = lru.get(e)
                if slot is not None:
                    hits += 1
                    continue
                slot = self._claim_proj_slot(
                    proj, coord, b, e, in_use_by_block[b]
                )
                rows_of[e] = slot
                misses.append(e)
            if hits:
                reg.counter(
                    "serve_store_hits_total", re_type=proj.re_type
                ).inc(hits)
            if not misses and not coord.demoted:
                continue
            if misses:
                reg.counter(
                    "serve_store_misses_total", re_type=proj.re_type
                ).inc(len(misses))
                _oom_contained(
                    proj.re_type,
                    lambda: self._upload_projected_rows(
                        coord, misses, rows_of
                    ),
                )
            _oom_contained(
                proj.re_type,
                lambda: self._rewrite_proj_maps(proj, coord, misses, rows_of),
            )

    def _claim_proj_slot(
        self, proj: _ProjGroup, coord: _ProjCoord, block: int, entity: int,
        in_use: set,
    ) -> int:
        try:
            return coord.lrus[block].claim(entity, in_use)
        except RuntimeError:
            raise RuntimeError(
                f"hot store for {proj.re_type!r} exhausted: batch has more "
                f"unique entities in block {block} than capacity "
                f"{coord.capacities[block]}"
            ) from None

    def _upload_projected_rows(
        self, coord: _ProjCoord, misses: List[int], rows_of: Dict[int, int]
    ) -> None:
        """Bucketed row scatter per block that has promotions."""
        by_block: Dict[int, List[int]] = {}
        for e in misses:
            by_block.setdefault(int(coord.entity_block[e]), []).append(e)
        for b, ents in by_block.items():
            m = len(ents)
            m_b = bucket_dim(m)
            idx = np.full(m_b, coord.capacities[b], np.int32)
            idx[:m] = [rows_of[e] for e in ents]
            host = coord.host_blocks[b]
            rows = np.zeros((m_b, host.shape[1]), np.float32)
            rows[:m] = host[coord.entity_row[np.asarray(ents, np.int64)]]
            coord.tables[b] = _scatter(coord.tables[b], idx, rows)

    def _rewrite_proj_maps(
        self, proj: _ProjGroup, coord: _ProjCoord, misses: List[int],
        rows_of: Dict[int, int],
    ) -> None:
        """One bucketed scatter pair updating the device entity maps for
        this resolve: promoted entities point at their new hot rows,
        demotion victims go cold (-1)."""
        # Drain IN PLACE: the SlotLru on_demote closures captured this list
        # object at build time — rebinding would orphan it and every later
        # victim would silently keep its stale (hot) map entry. The clear
        # happens only after both scatters land, so an OOM-contained retry
        # of this whole function still sees every victim (no demotions can
        # occur in between — nothing here claims slots).
        victims = list(coord.demoted)
        m = len(misses) + len(victims)
        m_b = bucket_dim(m)
        E = coord.entity_block.shape[0]
        idx = np.full(m_b, E, np.int32)  # out-of-range filler → dropped
        blk = np.full(m_b, -1, np.int32)
        row = np.zeros(m_b, np.int32)
        idx[: len(victims)] = victims
        for j, e in enumerate(misses):
            idx[len(victims) + j] = e
            blk[len(victims) + j] = int(coord.entity_block[e])
            row[len(victims) + j] = rows_of[e]
        coord.dev_entity_block = _scatter(coord.dev_entity_block, idx, blk)
        coord.dev_entity_row = _scatter(coord.dev_entity_row, idx, row)
        coord.demoted.clear()

    def warm_uploads(self, max_batch: int) -> None:
        """Compile the upload scatters for every miss-count bucket ≤
        ``max_batch`` (no-op rows: every filler index drops), so promotion
        never compiles under a request. Projected map scatters warm to
        2×max_batch — one resolve may rewrite a miss AND a victim entry per
        promoted entity."""
        import jax

        for group in self._groups.values():
            if group.pinned:
                continue
            m = 1
            while True:
                m_b = bucket_dim(m)
                idx = np.full(m_b, group.capacity, np.int32)
                for cid in group.coord_ids:
                    d = group.host_coefs[cid].shape[1]
                    group.tables[cid] = _scatter(
                        group.tables[cid], idx, np.zeros((m_b, d), np.float32)
                    )
                if m_b >= bucket_dim(max_batch):
                    break
                m = m_b + 1
            for cid in group.coord_ids:
                jax.block_until_ready(group.tables[cid])
        for proj in self._proj_groups.values():
            for coord in proj.coords:
                if self._coord_pinned(coord):
                    continue
                E = coord.entity_block.shape[0]
                m = 1
                while True:
                    m_b = bucket_dim(m)
                    for b, table in enumerate(coord.tables):
                        idx = np.full(m_b, coord.capacities[b], np.int32)
                        coord.tables[b] = _scatter(
                            table, idx,
                            np.zeros((m_b, table.shape[1]), np.float32),
                        )
                    if m_b >= bucket_dim(2 * max_batch):
                        break
                    m = m_b + 1
                m = 1
                while True:
                    m_b = bucket_dim(m)
                    idx = np.full(m_b, E, np.int32)
                    zeros = np.zeros(m_b, np.int32)
                    coord.dev_entity_block = _scatter(
                        coord.dev_entity_block, idx, zeros
                    )
                    coord.dev_entity_row = _scatter(
                        coord.dev_entity_row, idx, zeros
                    )
                    if m_b >= bucket_dim(2 * max_batch):
                        break
                    m = m_b + 1
                jax.block_until_ready(coord.dev_entity_block)
                for table in coord.tables:
                    jax.block_until_ready(table)

    # -- delta overlay -----------------------------------------------------

    def clone_with_delta(
        self,
        re_rows: Dict[str, tuple],
        fixed: Optional[Dict[str, np.ndarray]] = None,
    ) -> "HotColdEntityStore":
        """A NEW store serving base ⊕ delta without reloading the base
        model: per-entity coefficient rows (``re_rows``: cid → (idx, rows),
        the shape ``io/model_io.py:read_delta_rows`` returns) overlay copies
        of the touched host masters, and fixed-effect means (``fixed``:
        cid → (d,) array) replace the base means value-only — the scoring
        pytree structure is unchanged, so a transformer warmed on the base
        scores the clone without a retrace.

        Sharing discipline: entity indexes, RE submodel metadata, projected
        groups, and every UNTOUCHED dense group are shared with the base
        store, hot cache included — safe because untouched host masters are
        byte-identical and the engine serializes every resolve/upload under
        one batch lock. Touched groups get copied hosts; pinned tables are
        rewritten by one functional bucketed scatter per coordinate (the
        base version's tables are never mutated — multi-version residency
        holds), unpinned groups restart cold with fresh tables + LRU and
        refill on demand from the patched master.

        Raises ValueError when the delta cannot be applied in place —
        unknown coordinate, projected coordinate, feature-dim mismatch, or
        an entity index outside the base entity space (the delta grew the
        entity set). Callers treat that as "fall back to a full
        resolved-model load".
        """
        import jax

        re_rows = re_rows or {}
        fixed = fixed or {}
        proj_cids = {
            c.cid for proj in self._proj_groups.values() for c in proj.coords
        }
        group_of: Dict[str, _ReGroup] = {
            cid: g for g in self._groups.values() for cid in g.coord_ids
        }
        for cid, (idx, rows) in re_rows.items():
            if cid in proj_cids:
                raise ValueError(
                    f"delta touches projected coordinate {cid!r}; in-place "
                    "apply supports dense random effects only"
                )
            group = group_of.get(cid)
            if group is None:
                raise ValueError(
                    f"delta coordinate {cid!r} is not a random-effect "
                    "coordinate of the base model"
                )
            idx = np.asarray(idx)
            rows = np.asarray(rows, np.float32)
            host = group.host_coefs[cid]
            if rows.ndim != 2 or rows.shape[1] != host.shape[1]:
                raise ValueError(
                    f"delta rows for {cid!r} have width "
                    f"{rows.shape[1] if rows.ndim == 2 else rows.shape}, "
                    f"base table has {host.shape[1]}"
                )
            if int(idx.shape[0]) != int(rows.shape[0]):
                raise ValueError(
                    f"delta for {cid!r}: {idx.shape[0]} indices vs "
                    f"{rows.shape[0]} rows"
                )
            if idx.size and (
                int(idx.min()) < 0 or int(idx.max()) >= group.num_entities
            ):
                raise ValueError(
                    f"delta for {cid!r} addresses entities outside the base "
                    f"entity space [0, {group.num_entities}) — the delta "
                    "grew the entity set"
                )
        for cid, means in fixed.items():
            sub = self._base.get(cid)
            if not isinstance(sub, FixedEffectModel):
                raise ValueError(
                    f"delta fixed effect {cid!r} is not a fixed-effect "
                    "coordinate of the base model"
                )
            means = np.asarray(means, np.float32)
            old = np.asarray(sub.model.coefficients.means)
            if means.shape != old.shape:
                raise ValueError(
                    f"delta fixed effect {cid!r} has shape {means.shape}, "
                    f"base has {old.shape}"
                )

        new = object.__new__(HotColdEntityStore)
        new._entity_indexes = self._entity_indexes
        new._re_subs = self._re_subs
        new._proj_groups = self._proj_groups
        new._partition = self._partition
        new._device_shards = self._device_shards
        new._mesh = self._mesh
        new._table_sharding = self._table_sharding
        new._replicated_sharding = self._replicated_sharding
        base = dict(self._base)
        for cid, means in fixed.items():
            sub = base[cid]
            coefs = sub.model.coefficients
            base[cid] = FixedEffectModel(
                model=GeneralizedLinearModel(
                    Coefficients(
                        jax.device_put(np.asarray(means, np.float32)),
                        coefs.variances,
                    ),
                    sub.model.task,
                ),
                feature_shard=sub.feature_shard,
            )
        new._base = base
        groups: Dict[str, _ReGroup] = {}
        for re_type, group in self._groups.items():
            touched = {
                cid: re_rows[cid] for cid in group.coord_ids if cid in re_rows
            }
            if not touched:
                groups[re_type] = group
                continue
            host2: Dict[str, np.ndarray] = {}
            for cid in group.coord_ids:
                if cid in touched:
                    idx, rows = touched[cid]
                    idx = np.asarray(idx, np.int64)
                    rows = np.asarray(rows, np.float32)
                    if group.compact_of is not None:
                        # Sharded host master: the delta addresses full
                        # entity space; rows this replica doesn't hold are
                        # another replica's to apply.
                        cidx = group.compact_of[idx].astype(np.int64)
                        keep = cidx >= 0
                        idx, rows = cidx[keep], rows[keep]
                    h = group.host_coefs[cid].copy()
                    h[idx] = rows
                    host2[cid] = h
                else:
                    host2[cid] = group.host_coefs[cid]
            g2 = _ReGroup(
                re_type=re_type,
                coord_ids=list(group.coord_ids),
                host_coefs=host2,
                num_entities=group.num_entities,
                capacity=group.capacity,
                pinned=group.pinned,
                owned=group.owned,
                compact_of=group.compact_of,
                shard_plan=group.shard_plan,
                shard_cap=group.shard_cap,
                perm=group.perm,
            )
            if group.pinned:
                tables: Dict[str, object] = {}
                for cid in group.coord_ids:
                    if cid not in touched:
                        tables[cid] = group.tables[cid]
                        continue
                    idx, rows = touched[cid]
                    idx = np.asarray(idx, np.int64)
                    rows = np.asarray(rows, np.float32)
                    m = int(idx.shape[0])
                    m_b = bucket_dim(m)
                    # capacity == num_entities when pinned: the filler
                    # index is out of range and drops, like _upload's.
                    # Device-sharded tables are addressed through the
                    # entity→slot permutation (shard-grouped layout).
                    pad_idx = np.full(m_b, group.capacity, np.int32)
                    pad_idx[:m] = (
                        group.perm[idx] if group.perm is not None else idx
                    )
                    pad_rows = np.zeros((m_b, rows.shape[1]), np.float32)
                    pad_rows[:m] = rows
                    tables[cid] = _oom_contained(
                        re_type,
                        lambda t=group.tables[cid], i=pad_idx, r=pad_rows: (
                            _scatter(t, i, r)
                        ),
                    )
                g2.tables = tables
            else:
                g2.tables = {
                    cid: jax.device_put(
                        np.zeros(
                            (g2.capacity, host2[cid].shape[1]), np.float32
                        ),
                        self._table_sharding,
                    )
                    for cid in group.coord_ids
                }
                if group.shard_lrus is not None:
                    g2.shard_lrus = [
                        SlotLru(
                            group.shard_cap,
                            on_demote=self._demote_counter(re_type),
                            base=s * group.shard_cap,
                        )
                        for s in range(group.shard_plan.n_shards)
                    ]
                else:
                    g2.lru = SlotLru(
                        g2.capacity, on_demote=self._demote_counter(re_type)
                    )
            groups[re_type] = g2
        new._groups = groups
        registry().counter("serve_store_delta_clones_total").inc()
        return new

    # -- scoring model -----------------------------------------------------

    def scoring_model(self) -> GameModel:
        """The model the jitted scorer runs: device submodels, with every
        cached random-effect table swapped in (slot-indexed). Pytree
        structure is identical call to call and reload to reload — the
        tables change VALUE only, so the scorer never retraces."""
        models = dict(self._base)
        for re_type, group in self._groups.items():
            for cid in group.coord_ids:
                models[cid] = self._re_subs[cid].with_coefficients(
                    group.tables[cid]
                )
        for proj in self._proj_groups.values():
            for coord in proj.coords:
                sub = coord.sub
                # Auxiliary arrays (variances) are dropped like the dense
                # ``with_coefficients`` path: one pytree structure across
                # reloads, never a retrace on swap.
                models[coord.cid] = ProjectedRandomEffectModel(
                    block_coefs=list(coord.tables),
                    col_maps=list(sub.col_maps),
                    inv_maps=list(sub.inv_maps),
                    entity_block=coord.dev_entity_block,
                    entity_row=coord.dev_entity_row,
                    d_full=sub.d_full,
                    re_type=sub.re_type,
                    feature_shard=sub.feature_shard,
                    task=sub.task,
                )
        return GameModel(models)

    def stats(self) -> Dict[str, dict]:
        out = {}
        for re_type, group in self._groups.items():
            out[re_type] = dict(
                entities=group.num_entities,
                hot_capacity=group.capacity,
                hot_resident=group.resident_count(),
                pinned=group.pinned,
                hot_bytes=group.capacity * group.row_bytes,
            )
            if group.owned is not None:
                out[re_type]["owned_entities"] = int(group.owned.sum())
                out[re_type]["compacted_host"] = group.compact_of is not None
            if group.shard_plan is not None:
                out[re_type]["device_shards"] = group.shard_plan.n_shards
                out[re_type]["shard_rows"] = group.shard_cap
        for re_type, proj in self._proj_groups.items():
            out[re_type] = dict(
                entities=proj.num_entities,
                hot_capacity=sum(sum(c.capacities) for c in proj.coords),
                hot_resident=sum(
                    sum(c.capacities)
                    if self._coord_pinned(c)
                    else sum(len(l) for l in c.lrus if l is not None)
                    for c in proj.coords
                ),
                pinned=proj.pinned,
                hot_bytes=sum(c.hot_bytes for c in proj.coords),
                projected=True,
            )
        return out
