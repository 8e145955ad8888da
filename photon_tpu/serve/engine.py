"""Online GAME serving engine: micro-batched scoring with zero-downtime
model reload.

Composition of the two sibling modules plus the batch stack's own scorer:
a :class:`~photon_tpu.serve.batcher.MicroBatcher` admits and batches
requests, a :class:`~photon_tpu.serve.store.HotColdEntityStore` resolves
entity ids to device-resident coefficient rows, and the SAME jitted
``GameTransformer`` program the batch scoring driver runs produces the
scores — which is what makes the CI bit-parity check (serve vs batch
driver, atol=0) meaningful rather than aspirational.

The no-retrace contract, end to end:

1. startup ``warm_up`` scores an inert template batch at EVERY row bucket in
   ``bucket_grid(max_batch_size)`` and compiles every hot-store upload
   scatter, so all program shapes exist before traffic;
2. every live batch pads up the same grid (``pad_game_batch``), so it lands
   on a warmed shape;
3. the per-batch scoring model swaps table VALUES only (identical pytree
   structure via ``with_coefficients``), so promotions and reloads reuse the
   compiled program.

``retraces_since_warmup`` exposes the in-trace counter delta — the
observable the serve CI stage and ``bench.py --serve-ab`` assert to be 0.

Reload is build-then-swap: the incoming model gets its OWN store +
transformer + warm-up while the old state keeps serving; the swap happens
under the engine's scoring lock, so in-flight batches drain on the old
state and the next batch scores on the new one. No request ever observes a
half-loaded model.

Graceful degradation (ISSUE 6): a reload whose build/warm-up fails leaves
the OLD state serving (the failure is reported via :class:`ReloadError` and
``stats()['last_reload_error']``), and each managed RE type carries a
circuit breaker — repeated ``resolve`` failures trip it, after which that
type's entity ids resolve to -1 (cold start ⇒ the RE contributes 0, i.e.
FE-only scoring, on already-compiled program shapes) until a cooldown
half-opens it. Requests keep answering throughout; ``stats()`` (and the
HTTP ``/healthz``) report the degraded set.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.index_map import EntityIndex, IndexMap
from photon_tpu.data.padding import bucket_grid, pad_game_batch
from photon_tpu.data.random_effect import bucket_dim
from photon_tpu.estimators.game_transformer import GameTransformer
from photon_tpu.models.game import GameModel
from photon_tpu.obs.metrics import registry
from photon_tpu.obs.export import exporter_health
from photon_tpu.obs.host import start_sentinel
from photon_tpu.obs.report import telemetry_sink_health
from photon_tpu.obs.quality import QualityConfig, QualityPlane, task_name
from photon_tpu.obs.slo import SLOTracker
from photon_tpu.obs.trace import flight_recorder, tracer
from photon_tpu.serve.admission import (
    INTERACTIVE,
    AdmissionConfig,
    AdmissionController,
)
from photon_tpu.serve.batcher import MicroBatcher, ScoreRequest
from photon_tpu.serve.store import HotColdEntityStore, StorePartition
from photon_tpu.utils import faults, resources

logger = logging.getLogger("photon_tpu")


class ReloadError(RuntimeError):
    """A reload failed to build/warm the new model generation. The old
    generation is still serving — the error is a report, not an outage."""


@dataclasses.dataclass
class ServeConfig:
    max_batch_size: int = 64  # rounded UP onto the bucket_dim grid
    max_delay_ms: float = 2.0  # oldest request's max queue dwell
    queue_cap: int = 1024  # admission bound; beyond it submits shed
    hot_bytes: int = 64 << 20  # device budget for cached RE tables
    default_deadline_ms: Optional[float] = None  # per-request unless given
    breaker_threshold: int = 3  # consecutive resolve failures to trip
    breaker_cooldown_s: float = 30.0  # open duration before half-open probe
    admission: Optional[AdmissionConfig] = None  # per-tenant quotas/classes
    max_versions: int = 2  # resident generations (primary + candidates)
    shadow_fraction: float = 0.0  # of primary traffic re-scored on shadow
    # Fraction of label-joined records re-scored on EACH shadow candidate
    # (its online-quality lane). 1.0 gives every candidate a dense
    # (score, label) stream — the experiment plane's GP observations.
    shadow_quality_fraction: float = 1.0
    # A promotion is "settled" (rollback parent unpinned, breaker-trip
    # monitoring window closed) this many seconds after promote(). <= 0
    # keeps the parent pinned until the next promote/rollback.
    promotion_settle_s: float = 300.0
    # Multi-chip serving: split every dense hot table into this many
    # entity shards laid out over the device mesh (same consistent-hash
    # plan the sharded trainer uses — parallel/entity_shard.py). None =
    # single-device tables. Scores merge with the one all-gather XLA
    # inserts for the slot gather against the sharded table.
    device_shards: Optional[int] = None


class _Breaker:
    """Per-RE-type circuit breaker. Single-writer (the engine's batch lock
    serializes _assemble), so plain fields suffice."""

    def __init__(self, threshold: int, cooldown_s: float):
        self.threshold = max(int(threshold), 1)
        self.cooldown_s = float(cooldown_s)
        self.failures = 0
        self.open_until = 0.0
        self.trips = 0

    @property
    def open(self) -> bool:
        return time.monotonic() < self.open_until

    def record_failure(self) -> bool:
        """Count one failure; returns True when this one trips the breaker
        (reaching the threshold, or failing the half-open probe after a
        cooldown — that re-trips immediately)."""
        half_open_probe = self.open_until > 0.0 and not self.open
        self.failures += 1
        if half_open_probe or self.failures >= self.threshold:
            self.open_until = time.monotonic() + self.cooldown_s
            self.failures = 0
            self.trips += 1
            return True
        return False

    def record_success(self) -> None:
        self.failures = 0
        self.open_until = 0.0


def _features_from_json(features: Dict) -> Dict:
    """Inverse of the spool's ``_jsonable_features``: dict payloads pass
    through, 2-list (indices, values) pairs become sparse tuples, dense
    lists become float32 vectors — the shapes ``_dense_row`` accepts."""
    out: Dict[str, object] = {}
    for shard, val in (features or {}).items():
        if isinstance(val, dict):
            out[shard] = val
        elif (isinstance(val, (list, tuple)) and len(val) == 2
              and isinstance(val[0], (list, tuple))):
            out[shard] = (
                np.asarray(val[0], np.int64),
                np.asarray(val[1], np.float32),
            )
        else:
            out[shard] = np.asarray(val, np.float32)
    return out


def _model_task(model: GameModel):
    """The GLM task this model family trains (for the quality plane's
    link/loss choice): first task found on any coordinate's model."""
    for m in getattr(model, "models", {}).values():
        task = getattr(m, "task", None) or getattr(
            getattr(m, "model", None), "task", None
        )
        if task is not None:
            return task
    return None


@dataclasses.dataclass
class _State:
    """Everything that swaps atomically on reload."""

    store: HotColdEntityStore
    transformer: GameTransformer
    model_version: str
    warm_traces: int  # trace_count right after warm-up


class _ShadowLane:
    """Per-candidate shadow accounting. Each resident candidate that
    shadows primary traffic owns one lane: its own traffic fraction, its
    own fractional-sampling accumulator (the N-way split stays exact and
    RNG-free — candidate ``i`` at fraction ``f`` scores every ``1/f``-th
    primary request regardless of what the other lanes sample), and its
    own divergence record so N concurrent candidates never alias into one
    series."""

    __slots__ = ("fraction", "acc", "count", "div_sum", "div_max",
                 "samples", "quality_acc", "started_at", "seq")

    def __init__(self, fraction: float, seq: int):
        self.fraction = float(fraction)
        self.acc = 0.0  # divergence-sampling accumulator
        self.count = 0
        self.div_sum = 0.0
        self.div_max = 0.0
        self.samples: deque = deque(maxlen=256)
        self.quality_acc = 0.0  # label re-score accumulator (quality lane)
        self.started_at = time.time()
        self.seq = seq  # start order; highest = "the" shadow for legacy API

    def stats(self, version: str) -> Dict:
        return dict(
            version=version,
            fraction=self.fraction,
            count=self.count,
            max_divergence=self.div_max,
            mean_divergence=self.div_sum / self.count if self.count else 0.0,
        )


class ServingEngine:
    """In-process serving core; cli/game_serving.py adds the HTTP front end.

    ``model`` must be the HOST-side master (``load_game_model(...,
    to_device=False)``) — the store decides what becomes device-resident.
    """

    def __init__(
        self,
        model: GameModel,
        entity_indexes: Optional[Dict[str, EntityIndex]] = None,
        index_maps: Optional[Dict[str, IndexMap]] = None,
        config: Optional[ServeConfig] = None,
        model_version: str = "0",
        partition: Optional[StorePartition] = None,
    ):
        start_sentinel()  # host pauses (collections, stalls) on the span clock
        self.config = config or ServeConfig()
        self.max_batch = bucket_dim(int(self.config.max_batch_size))
        # Fleet shard ownership: every generation's store is built with the
        # current partition; set_partition swaps the predicate live.
        self._partition = partition
        self._entity_indexes = dict(entity_indexes or {})
        self._index_maps = dict(index_maps or {})
        self._shard_dims = model.feature_shard_dims()
        self._intercept_col = {
            shard: (
                self._index_maps[shard].get_index(IndexMap.INTERCEPT)
                if shard in self._index_maps
                else -1
            )
            for shard in self._shard_dims
        }
        self._lock = threading.RLock()
        self._reloads = 0
        self._reload_failures = 0
        self._last_reload_error: Optional[str] = None
        # Per-RE-type circuit breakers: engine-owned (they outlive reloads —
        # a flapping store should stay degraded across a model swap).
        self._breakers: Dict[str, _Breaker] = {}
        # Admission lives HERE (the one device-owning process), never in
        # front-end workers — quota state must be globally consistent no
        # matter how many processes fan requests in.
        self.admission = AdmissionController(self.config.admission)
        # Multi-version residency: every generation is a full _State (its own
        # store + transformer + warm-up), but versions differ only by table
        # VALUES, so marginal versions cost memory — never a live-path
        # compile. ``_primary`` answers unpinned traffic; ``_shadows`` maps
        # candidate version → lane: each lane re-scores its own deterministic
        # fraction of primary traffic (independent fractional accumulators,
        # so an N-way split stays exact and RNG-free) without touching
        # responses. The single-shadow rollout API (start_shadow /
        # stop_shadow / shadow_stats with no argument) operates on the most
        # recently started lane.
        state = self._build_state(model, model_version)
        self._states: Dict[str, _State] = {state.model_version: state}
        self._primary: str = state.model_version
        self._shadows: Dict[str, _ShadowLane] = {}
        self._shadow_seq = 0  # start order; newest lane answers legacy API
        self._shadow_fraction = float(self.config.shadow_fraction)
        self._promotion: Optional[Dict] = None
        # Feedback spool (streaming freshness loop): when attached, every
        # scored primary request is offered to the spool's label join.
        self._feedback = None
        # SLO plane: availability + latency fed per completion, staleness
        # sampled against the last primary-generation change. Lives on the
        # engine (the one device-owning process); fleet replicas each run
        # their own and the scrape merges them.
        self.slo = SLOTracker()
        # Model-quality plane (obs/quality.py): streaming AUC/calibration
        # over the spool's joined (score, label) pairs, keyed by
        # (model_version, tenant, re_type). ``enable_quality_baseline``
        # adds the frozen-baseline lane (labeled traffic re-scored on a
        # pinned generation) so freshness lift is measured, not modeled.
        self.quality = QualityPlane(
            QualityConfig(task=task_name(_model_task(model)))
        )
        self._quality_baseline: Optional[str] = None
        self._quality_fraction = 1.0
        self._quality_acc = 0.0  # fractional-sampling accumulator
        self._last_model_update = time.time()
        self.batcher = MicroBatcher(
            self._score_batch,
            max_batch_size=self.max_batch,
            max_delay_s=self.config.max_delay_ms / 1000.0,
            queue_cap=self.config.queue_cap,
        )

    # -- state construction (startup and reload share it) -------------------

    def _build_state(self, model: GameModel, version: str) -> _State:
        """Store + transformer + FULL warm-up for one model generation.
        Runs entirely off the scoring lock so reloads never stall traffic.

        Warm-up is the engine's biggest allocation burst (every hot table
        plus every solve-cache executable for the batch grid), so a device
        OOM here gets contained: release the partial build, collect dropped
        buffers, retry once. The retry rebuilds from the host master — no
        caller ever sees a half-warmed generation. A second OOM raises a
        clean :class:`~photon_tpu.utils.resources.DeviceMemoryError` (the
        reload path keeps serving the old generation)."""

        def build() -> _State:
            faults.check("serve.warm_up", label=version)
            span = tracer().span
            with span("store_build"):
                store = HotColdEntityStore(
                    model,
                    self._entity_indexes,
                    hot_bytes=self.config.hot_bytes,
                    # Floor: one batch's unique entities always fit resident.
                    min_hot_rows=self.max_batch,
                    partition=self._partition,
                    device_shards=self.config.device_shards,
                )
            with span("warm_uploads"):
                store.warm_uploads(self.max_batch)
            with span("transformer_warm_up"):
                transformer = GameTransformer(store.scoring_model())
                template = self._template_batch(store)
                transformer.warm_up(
                    template, bucket_grid(self.max_batch),
                    sharding=store.batch_sharding,
                )
            return _State(store, transformer, version, transformer.trace_count)

        with tracer().span("serve/warm_up"):
            try:
                return resources.oom_retry(
                    build, site="serve.warm_up",
                    counter="serve_warmup_oom_retries_total",
                )
            except Exception as exc:
                if not resources.is_device_oom(exc):
                    raise
                raise resources.DeviceMemoryError(
                    f"serve engine: device OOM warming up model version "
                    f"{version!r} even after retry. Shrink --hot-bytes or "
                    "--max-batch, evict serving versions, or add device "
                    "memory."
                ) from exc

    def _template_batch(self, store: HotColdEntityStore) -> GameBatch:
        """1-row inert batch with the production layout: dense zero features
        per shard, entity -1 (cold start) per RE type. Tracing is
        shape-driven, so values are irrelevant."""
        import jax.numpy as jnp

        return GameBatch(
            label=jnp.zeros(1, jnp.float32),
            offset=jnp.zeros(1, jnp.float32),
            weight=jnp.ones(1, jnp.float32),
            features={
                s: jnp.zeros((1, d), jnp.float32)
                for s, d in self._shard_dims.items()
            },
            entity_ids={
                rt: jnp.full(1, -1, jnp.int32)
                for rt in store.entity_re_types
            },
        )

    # -- request assembly ---------------------------------------------------

    def _dense_row(self, shard: str, value) -> np.ndarray:
        """One request's feature payload → dense (d,) float32. Serving
        always densifies: per-row dot products over a fixed d are row-count
        independent, which is what buys bit-parity with the batch driver."""
        d = self._shard_dims[shard]
        row = np.zeros(d, np.float32)
        icpt = self._intercept_col.get(shard, -1)
        if icpt >= 0:
            row[icpt] = 1.0
        if value is None:
            return row
        if isinstance(value, dict):
            imap = self._index_maps.get(shard)
            for k, v in value.items():
                if isinstance(k, str):
                    if imap is None:
                        raise ValueError(
                            f"string feature keys need an index map for "
                            f"shard {shard!r}"
                        )
                    j = imap.get_index(k)
                else:
                    j = int(k)
                if 0 <= j < d:
                    row[j] = v  # unknown features drop (batch-path parity)
            return row
        if (
            isinstance(value, (tuple, list))
            and len(value) == 2
            and not np.isscalar(value[0])
            and np.ndim(value[0]) == 1
            and np.ndim(value[1]) == 1
            and len(value[0]) == len(value[1])
            and len(value[0]) != d
        ):
            idx = np.asarray(value[0], np.int64)
            vals = np.asarray(value[1], np.float32)
            ok = (idx >= 0) & (idx < d)
            row[idx[ok]] = vals[ok]
            return row
        # Dense vectors are taken verbatim — the caller owns every column,
        # intercept included (that's what the parity harness feeds).
        arr = np.asarray(value, np.float32)
        if arr.shape != (d,):
            raise ValueError(
                f"shard {shard!r} expects a ({d},) vector, got {arr.shape}"
            )
        return arr

    def _assemble(
        self, requests: List[ScoreRequest], store: HotColdEntityStore
    ) -> GameBatch:
        n = len(requests)
        features = {}
        for shard in self._shard_dims:
            features[shard] = np.stack(
                [self._dense_row(shard, r.features.get(shard)) for r in requests]
            )
        entity_ids = {}
        for rt in store.entity_re_types:
            keys = [r.entity_ids.get(rt, -1) for r in requests]
            slots, batch_degraded = self._resolve_guarded(store, rt, keys)
            entity_ids[rt] = slots
            if batch_degraded:
                # Breaker-open / failed resolve: every request in this
                # batch scored FE-only for this type — flight-recorder bait.
                for r in requests:
                    r.degraded = True
            elif self._partition is not None and self._partition.applies_to(rt):
                # Foreign (non-owned) entities degrade FE-only per request.
                for r, key in zip(requests, keys):
                    if key != -1 and not self._partition.owns(key):
                        r.degraded = True
        return GameBatch(
            label=np.zeros(n, np.float32),
            offset=np.asarray([r.offset for r in requests], np.float32),
            weight=np.ones(n, np.float32),
            features=features,
            entity_ids=entity_ids,
        )

    def _breaker(self, re_type: str) -> _Breaker:
        b = self._breakers.get(re_type)
        if b is None:
            b = self._breakers[re_type] = _Breaker(
                self.config.breaker_threshold, self.config.breaker_cooldown_s
            )
        return b

    def _resolve_guarded(
        self, store: HotColdEntityStore, re_type: str, keys: List
    ) -> tuple:
        """``store.resolve`` behind the RE type's circuit breaker. Open
        breaker (or a failing resolve) degrades THIS batch's type to all
        -1 slots — cold-start semantics, so the random effect contributes 0
        and scoring proceeds FE-only on already-compiled shapes. Returns
        ``(slots, degraded)`` so the assembler can mark the requests for
        the flight recorder."""
        breaker = self._breaker(re_type)
        reg = registry()
        if breaker.open:
            reg.counter("serve_requests_degraded_total", re_type=re_type).inc(
                len(keys)
            )
            return np.full(len(keys), -1, np.int32), True
        try:
            slots = store.resolve(re_type, keys)
        except Exception as exc:  # noqa: BLE001 — degrade, never crash
            reg.counter("serve_store_errors_total", re_type=re_type).inc()
            if breaker.record_failure():
                reg.counter("serve_breaker_trips_total", re_type=re_type).inc()
                logger.warning(
                    "serving: circuit breaker for RE type %r OPEN for "
                    "%.1fs after resolve failure: %s",
                    re_type, breaker.cooldown_s, exc,
                )
            else:
                logger.warning(
                    "serving: resolve failed for RE type %r (%d/%d to "
                    "breaker trip): %s",
                    re_type, breaker.failures, breaker.threshold, exc,
                )
            reg.counter("serve_requests_degraded_total", re_type=re_type).inc(
                len(keys)
            )
            return np.full(len(keys), -1, np.int32), True
        breaker.record_success()
        return slots, False

    # -- the batcher's score_fn --------------------------------------------

    @property
    def _state(self) -> _State:
        """The primary generation's state (legacy single-version alias)."""
        return self._states[self._primary]

    def _resolve_version(self, pin: Optional[str]) -> str:
        """A version pin → resident state key: exact match, else basename
        (callers pin ``gen-3``; the engine may key the full model dir).
        Unknown pins raise ValueError (→ HTTP 400 in the front end)."""
        if pin is None:
            return self._primary
        pin = str(pin)
        if pin in self._states:
            return pin
        for key in self._states:
            if os.path.basename(str(key).rstrip("/")) == pin:
                return key
        raise ValueError(
            f"unknown model version {pin!r}; resident: "
            f"{sorted(self.versions)}"
        )

    def _score_on(self, state: _State, requests: List[ScoreRequest]) -> np.ndarray:
        import jax

        n = len(requests)
        span = tracer().span
        with span("score"):
            faults.check("serve.score")
            with span("assemble"):
                batch = self._assemble(requests, state.store)
                batch = pad_game_batch(batch, bucket_dim(n), xp=np)
            registry().histogram("serve_h2d_bytes").observe(
                sum(a.nbytes for a in jax.tree_util.tree_leaves(batch))
            )
            with span("h2d"):
                # Sharded hot tables live on a mesh: replicate the batch
                # over it so the jitted scorer sees consistent placements (a
                # plain device_put would commit to device 0 and fail the
                # jit's incompatible-devices check against mesh-resident
                # tables).
                dev = jax.device_put(batch, state.store.batch_sharding)
            with span("launch"):  # returns at dispatch
                scores = state.transformer.transform(
                    dev, model=state.store.scoring_model()
                )
            with span("d2h"):  # the wait for the device, then the copy back
                return np.asarray(scores)[:n]

    def _score_batch(self, requests: List[ScoreRequest]) -> Sequence[float]:
        with self._lock:  # vs promote/reload swap; store.resolve single-writer
            out = np.zeros(len(requests), np.float32)
            groups: Dict[str, List[int]] = {}
            for i, r in enumerate(requests):
                key = r.model_version or self._primary
                if key not in self._states:
                    # Pinned version evicted between submit and flush (a
                    # promote/evict race): the primary answers rather than
                    # failing the whole batch.
                    registry().counter("serve_pin_fallback_total").inc()
                    logger.warning(
                        "serving: pinned version %r evicted before flush; "
                        "scoring on primary %r", key, self._primary,
                    )
                    key = self._primary
                    r.degraded = True
                # Record the generation that ACTUALLY scores this request —
                # the front ends report req.model_version, and the caller
                # must never see a pin label a score it didn't produce.
                r.model_version = key
                groups.setdefault(key, []).append(i)
            for key, idxs in groups.items():
                sub = [requests[i] for i in idxs]
                scores = self._score_on(self._states[key], sub)
                out[idxs] = scores
                if key == self._primary:
                    if self._shadows:
                        self._maybe_shadow_score(sub, scores)
                    if self._feedback is not None:
                        self._record_feedback(sub, scores)
            return out

    def _record_feedback(
        self, requests: List[ScoreRequest], scores: np.ndarray
    ) -> None:
        """Land scored primary requests in the feedback spool's label join.
        Observability-only: a spool failure counts, never surfaces to the
        caller or the scoring path."""
        spool = self._feedback
        if spool is None:
            return
        try:
            for r, s in zip(requests, scores):
                if r.uid is None:
                    continue  # no join key: the label could never match
                spool.observe_scored(
                    uid=r.uid,
                    features=r.features,
                    entity_ids=r.entity_ids,
                    offset=r.offset,
                    score=float(s),
                    model_version=r.model_version,
                    tenant=getattr(r, "tenant", None),
                    trace=getattr(r, "trace", None),
                )
        except Exception as exc:  # noqa: BLE001 — feedback never hurts callers
            registry().counter("feedback_errors_total").inc()
            logger.warning("serving: feedback spool observe failed: %s", exc)

    def _maybe_shadow_score(
        self, requests: List[ScoreRequest], primary_scores: np.ndarray
    ) -> None:
        """Re-score a deterministic ``shadow_fraction`` sample of primary
        traffic on the shadow generation, recording score divergence.
        Responses are untouched — shadow cost is observability only, and a
        shadow failure degrades to "no sample", never to a caller error.

        Fault site ``serve.shadow_diverge`` perturbs the shadow scores so
        the watcher's divergence bound must refuse the candidate. With N
        concurrent lanes the fault takes the candidate basename as its
        label, so a plan can regress one candidate and leave the rest."""
        reg = registry()
        for key, lane in list(self._shadows.items()):
            if key not in self._states:
                continue  # lane outlived its generation (evict race)
            take: List[int] = []
            for i in range(len(requests)):
                lane.acc += lane.fraction
                if lane.acc >= 1.0:
                    lane.acc -= 1.0
                    take.append(i)
            if not take:
                continue
            short = os.path.basename(key.rstrip("/"))
            state = self._states[key]
            try:
                shadow_scores = np.asarray(
                    self._score_on(state, [requests[i] for i in take]),
                    np.float32,
                )
            except Exception as exc:  # noqa: BLE001 — never hurts callers
                reg.counter(
                    "serve_shadow_errors_total", model_version=short
                ).inc()
                logger.warning(
                    "serving: shadow scoring on %r failed: %s", key, exc
                )
                continue
            if faults.injector().fire(
                "serve.shadow_diverge", label=short
            ) is not None:
                shadow_scores = shadow_scores + 1.0
            # The candidate label keeps N concurrent shadow series apart —
            # an unlabeled serve_shadow_divergence would alias every lane
            # into one histogram.
            hist = reg.histogram("serve_shadow_divergence",
                                 model_version=short)
            for j, i in enumerate(take):
                p, s = float(primary_scores[i]), float(shadow_scores[j])
                div = abs(s - p)
                hist.observe(div)
                lane.count += 1
                lane.div_sum += div
                lane.div_max = max(lane.div_max, div)
                lane.samples.append(
                    dict(uid=requests[i].uid, primary=p, shadow=s,
                         divergence=div)
                )
            reg.counter(
                "serve_shadow_scored_total", model_version=short
            ).inc(len(take))

    # -- public API ---------------------------------------------------------

    def submit(
        self,
        request: ScoreRequest,
        deadline_s: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: str = INTERACTIVE,
        model_version: Optional[str] = None,
    ):
        """Admit (quota + priority class), then enqueue. Shed requests
        raise on THIS thread (``QuotaExceededError``/``BackpressureError``,
        both → HTTP 429); admitted requests return a Future and report
        their end-to-end latency into ``serve_tenant_latency_s``.

        ``model_version`` (or ``request.model_version``) pins the request to
        a resident generation; unknown pins raise ValueError here, on the
        caller's thread."""
        pin = model_version or request.model_version
        if pin is not None:
            with self._lock:
                request.model_version = self._resolve_version(pin)
        if tenant is not None:
            request.tenant = tenant  # per-tenant feedback sampling
        if deadline_s is None and self.config.default_deadline_ms is not None:
            deadline_s = self.config.default_deadline_ms / 1000.0
        self.admission.admit(
            tenant,
            priority,
            queue_depth=self.batcher.queue_depth,
            queue_cap=self.config.queue_cap,
        )
        t0 = time.monotonic()
        fut = self.batcher.submit(request, deadline_s, priority=priority)

        def _observe_done(f):
            dt = time.monotonic() - t0
            # Traced requests stamp their trace id as an OpenMetrics
            # exemplar on the tenant-latency histogram, linking the
            # scrape to the flight-recorder tree for the same request.
            tr = getattr(request, "trace", None)
            tid = tr.get("traceId") if isinstance(tr, dict) else None
            self.admission.observe_latency(tenant, dt, trace_id=tid)
            # SLO feed: availability (admitted requests that errored) and
            # latency for successes; staleness sampled per completion
            # against the last primary-generation change. All host math.
            try:
                ok = f.exception() is None
            except Exception:  # noqa: BLE001 — cancelled futures count bad
                ok = False
            self.slo.record_request(ok, dt if ok else None)
            self.slo.record_staleness(time.time() - self._last_model_update)

        fut.add_done_callback(_observe_done)
        return fut

    def score(
        self,
        features: Dict[str, object],
        entity_ids: Optional[Dict[str, object]] = None,
        offset: float = 0.0,
        deadline_s: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: str = INTERACTIVE,
        model_version: Optional[str] = None,
    ) -> float:
        """Synchronous convenience wrapper: one request, blocking."""
        return self.submit(
            ScoreRequest(features, dict(entity_ids or {}), offset),
            deadline_s,
            tenant=tenant,
            priority=priority,
            model_version=model_version,
        ).result()

    @property
    def model_version(self) -> str:
        return self._primary

    @property
    def versions(self) -> List[str]:
        return list(self._states)

    @property
    def shadow_version(self) -> Optional[str]:
        """The most recently started shadow candidate (legacy single-shadow
        view); None when no lane is active."""
        lane = self._newest_shadow_locked()
        return lane[0] if lane else None

    @property
    def shadow_versions(self) -> List[str]:
        """All active shadow candidates, oldest lane first."""
        with self._lock:
            return sorted(self._shadows, key=lambda k: self._shadows[k].seq)

    def _newest_shadow_locked(self) -> Optional[Tuple[str, "_ShadowLane"]]:
        if not self._shadows:
            return None
        key = max(self._shadows, key=lambda k: self._shadows[k].seq)
        return key, self._shadows[key]

    @property
    def retraces_since_warmup(self) -> int:
        """0 is the contract; anything else means a live batch compiled.
        Summed over every resident generation — a candidate that compiles
        on live traffic is just as much a contract breach as the primary."""
        return sum(
            s.transformer.trace_count - s.warm_traces
            for s in self._states.values()
        )

    def _total_trips(self) -> int:
        return sum(b.trips for b in self._breakers.values())

    def _maybe_settle_promotion_locked(self) -> None:
        """Clear ``_promotion`` once its monitoring window has passed:
        ``promotion_settle_s`` after promote(), the promoted generation is
        considered adopted — the rollback parent unpins (becomes evictable)
        and ``trips_since_promotion`` stops counting against it. Without
        this the parent stays pinned forever and, at the default
        ``max_versions=2``, the pin set alone fills the residency cap."""
        promo = self._promotion
        settle = float(self.config.promotion_settle_s or 0.0)
        if promo is None or settle <= 0:
            return
        if time.time() - promo["at"] >= settle:
            self._promotion = None
            logger.info(
                "serving: promotion of %r settled after %.0fs; parent %r "
                "no longer pinned", promo["version"], settle, promo["parent"],
            )

    def _evict_locked(self, protect: Optional[str] = None) -> None:
        """Drop oldest resident generations beyond ``max_versions``. The
        primary, the shadow, the current promotion's parent (the rollback
        target), and ``protect`` (a generation being loaded right now) are
        never evicted — residency may temporarily exceed the cap rather
        than drop any of those."""
        cap = max(int(self.config.max_versions), 1)
        self._maybe_settle_promotion_locked()
        keep = {self._primary, protect, self._quality_baseline}
        keep.update(self._shadows)  # every live candidate lane stays pinned
        if self._promotion is not None:
            keep.add(self._promotion["parent"])
        for key in list(self._states):
            if len(self._states) <= cap:
                break
            if key in keep:
                continue
            del self._states[key]
            logger.info("serving: evicted resident generation %r", key)
        if len(self._states) > cap:
            logger.warning(
                "serving: %d generations resident over max_versions=%d "
                "(primary/shadow/rollback-parent/loading are never evicted)",
                len(self._states), cap,
            )

    def load_version(
        self, model: GameModel, model_version: Optional[str] = None
    ) -> Dict:
        """Build + warm ``model`` as a RESIDENT generation without touching
        the primary. Traffic can pin to it immediately; ``start_shadow`` /
        ``promote`` move it through the rollout lifecycle.

        A failed build/warm-up raises :class:`ReloadError`; nothing resident
        changes — the error is also visible in
        ``stats()['last_reload_error']`` until a load succeeds."""
        self._reloads += 1
        version = model_version or f"reload-{self._reloads}"
        try:
            faults.check("serve.reload")
            new_state = self._build_state(model, version)  # off the lock
        except Exception as exc:  # noqa: BLE001 — keep serving what we have
            self._reload_failures += 1
            self._last_reload_error = f"{version}: {exc}"
            registry().counter("serve_reload_failures_total").inc()
            logger.warning(
                "serving: load of %r failed (%s); resident generations "
                "unchanged", version, exc,
            )
            raise ReloadError(
                f"reload to {version!r} failed: {exc}"
            ) from exc
        with self._lock:
            self._states[new_state.model_version] = new_state
            self._evict_locked(protect=new_state.model_version)
            resident = new_state.model_version in self._states
        if not resident:
            # _evict_locked protects the new generation, so this is a
            # should-never-happen backstop — but success must only ever be
            # reported for a generation that is actually resident.
            self._reload_failures += 1
            self._last_reload_error = f"{version}: evicted during load"
            registry().counter("serve_reload_failures_total").inc()
            raise ReloadError(
                f"reload to {version!r} failed: evicted during load"
            )
        self._last_reload_error = None
        registry().counter("serve_model_reloads_total").inc()
        return dict(model_version=version, store=new_state.store.stats())

    def load_delta_version(
        self, base_version: str, delta: Dict, model_version: str
    ) -> Dict:
        """Register a micro-generation as a RESIDENT version by applying a
        per-entity delta onto an already-resident base — no disk load of the
        full model, no store rebuild, no warm-up pass.

        ``delta`` is the ``io.model_io.read_delta_rows`` payload:
        ``{"re_rows": {cid: (entity_idx, rows)}, "fixed": {cid: means}}``.
        The clone's scoring pytree has the same structure as the base's, so
        the BASE's warmed transformer serves it — a delta load is O(changed
        rows) in device work and compiles nothing (the scatter shapes hit
        the module-global jit cache). Raises :class:`ReloadError` when the
        base is not resident or the delta is not applicable in place (entity
        growth, projected coordinate) — callers fall back to a full
        ``load_version``."""
        self._reloads += 1
        version = model_version
        try:
            faults.check("serve.reload")
            with self._lock:
                base_key = self._resolve_version(base_version)
                base_state = self._states[base_key]
            with tracer().span("serve/delta_apply"):
                store = base_state.store.clone_with_delta(
                    delta.get("re_rows") or {}, delta.get("fixed") or {}
                )
            # Shared transformer: identical pytree structure means zero new
            # traces; warm_traces snapshots the shared counter so the
            # retrace contract stays a strict zero-iff-no-retrace signal.
            new_state = _State(
                store, base_state.transformer, version,
                base_state.transformer.trace_count,
            )
        except Exception as exc:  # noqa: BLE001 — keep serving what we have
            self._reload_failures += 1
            self._last_reload_error = f"{version}: {exc}"
            registry().counter("serve_reload_failures_total").inc()
            logger.warning(
                "serving: delta load of %r onto %r failed (%s); resident "
                "generations unchanged", version, base_version, exc,
            )
            raise ReloadError(
                f"delta load to {version!r} failed: {exc}"
            ) from exc
        with self._lock:
            self._states[version] = new_state
            self._evict_locked(protect=version)
            resident = version in self._states
        if not resident:
            self._reload_failures += 1
            self._last_reload_error = f"{version}: evicted during load"
            registry().counter("serve_reload_failures_total").inc()
            raise ReloadError(
                f"delta load to {version!r} failed: evicted during load"
            )
        self._last_reload_error = None
        registry().counter("serve_delta_loads_total").inc()
        return dict(
            model_version=version, base=base_key, store=new_state.store.stats()
        )

    # -- feedback spool (streaming freshness loop) --------------------------

    def attach_feedback(self, spool) -> None:
        """Attach a :class:`~photon_tpu.stream.spool.FeedbackSpool`: scored
        primary requests land in its label join; :meth:`feedback_label`
        completes the join. The engine owns the spool's lifecycle from here
        (closed with the engine)."""
        self._feedback = spool
        # Every completed label join also feeds the model-quality plane
        # (called outside the spool lock; failures count, never raise).
        spool.on_join = self._on_feedback_join

    def feedback_label(
        self, uid: str, label: float, ts: Optional[float] = None
    ) -> bool:
        """Report an observed label for a previously scored request. True
        when the joined record landed in the spool."""
        if self._feedback is None:
            raise ValueError("feedback spool not enabled on this engine")
        return self._feedback.observe_label(uid, label, ts)

    # -- model-quality plane (obs/quality.py) -------------------------------

    def enable_quality_baseline(
        self, model_version: str, fraction: float = 1.0
    ) -> None:
        """Pin a resident generation as the quality plane's FROZEN
        BASELINE: a deterministic ``fraction`` of labeled traffic is
        re-scored on it (observability-only — a failure degrades to no
        sample), so per-version AUC lift is the difference of two measured
        online curves over the same requests. The version must be resident
        and stays so: the baseline joins the never-evicted pin set
        (primary, shadow, rollback parent) for as long as it is enabled."""
        with self._lock:
            key = self._resolve_version(model_version)
        self._quality_baseline = key
        self._quality_fraction = float(fraction)
        self._quality_acc = 0.0
        self.quality.set_baseline(key)
        logger.info(
            "serving: quality baseline pinned to %r (fraction %.3f)",
            key, fraction,
        )

    def _on_feedback_join(self, rec: dict) -> None:
        """One joined (score, label) record from the spool → the quality
        plane, plus the frozen-baseline lane's re-score when enabled."""
        ids = rec.get("entityIds") or {}
        re_type = ",".join(sorted(ids)) if ids else ""
        tenant = rec.get("tenant")
        trace_id = (rec.get("trace") or {}).get("traceId")
        label = float(rec.get("label") or 0.0)
        self.quality.observe(
            score=float(rec.get("score") or 0.0),
            label=label,
            model_version=rec.get("modelVersion"),
            tenant=tenant,
            re_type=re_type,
            ts=rec.get("ts"),
            label_ts=rec.get("labelTs"),
            trace_id=trace_id,
            slo=self.slo,
        )
        rec_version = os.path.basename(
            str(rec.get("modelVersion") or "").rstrip("/")
        )
        self._candidate_quality_lanes(rec, label, tenant, re_type,
                                      trace_id, rec_version)
        base = self._quality_baseline
        if base is None:
            return
        if rec_version == os.path.basename(str(base).rstrip("/")):
            return  # the baseline scored it already — no second lane
        self._quality_acc += self._quality_fraction
        if self._quality_acc < 1.0:
            return
        self._quality_acc -= 1.0
        try:
            score = self._baseline_score(rec, base)
        except Exception as exc:  # noqa: BLE001 — lane never hurts callers
            registry().counter("quality_baseline_errors_total").inc()
            logger.warning(
                "serving: baseline quality re-score on %r failed: %s",
                base, exc,
            )
            return
        self.quality.observe(
            score=score,
            label=label,
            model_version=base,
            tenant=tenant,
            re_type=re_type,
            ts=rec.get("ts"),
            label_ts=rec.get("labelTs"),
            trace_id=trace_id,
            slo=self.slo,  # no-op for the baseline key (plane skips it)
        )
        registry().counter("quality_baseline_scored_total").inc()

    def _candidate_quality_lanes(
        self, rec: dict, label: float, tenant, re_type: str, trace_id,
        rec_version: str,
    ) -> None:
        """Re-score one joined label on EVERY active shadow candidate and
        feed the quality plane under that candidate's version key — the
        per-candidate streaming AUC/deviance the experiment plane's GP
        observes. Observability-only (a failure degrades to no sample), no
        SLO feed: a bad CANDIDATE must burn its own quality series and get
        poisoned, never page the primary's gate."""
        if not self._shadows:
            return
        frac = float(self.config.shadow_quality_fraction)
        if frac <= 0.0:
            return
        for key, lane in list(self._shadows.items()):
            short = os.path.basename(str(key).rstrip("/"))
            if short == rec_version:
                continue  # the candidate scored it already (pinned traffic)
            lane.quality_acc += frac
            if lane.quality_acc < 1.0:
                continue
            lane.quality_acc -= 1.0
            try:
                score = self._baseline_score(rec, key)
            except Exception as exc:  # noqa: BLE001 — never hurts callers
                registry().counter(
                    "quality_candidate_errors_total", model_version=short
                ).inc()
                logger.warning(
                    "serving: candidate quality re-score on %r failed: %s",
                    key, exc,
                )
                continue
            self.quality.observe(
                score=score,
                label=label,
                model_version=key,
                tenant=tenant,
                re_type=re_type,
                ts=rec.get("ts"),
                label_ts=rec.get("labelTs"),
                trace_id=trace_id,
                slo=None,  # candidate lanes never feed the global gate
            )
            registry().counter(
                "quality_candidate_scored_total", model_version=short
            ).inc()

    def _baseline_score(self, rec: dict, base: str) -> float:
        """Score one spool record's features on the pinned baseline
        generation, bypassing admission and the SLO request feed (an
        internal measurement must not spend tenant quota or count against
        availability). Shapes pad onto the warmed bucket grid, so the lane
        keeps the zero-retrace contract."""
        req = ScoreRequest(
            _features_from_json(rec.get("features") or {}),
            dict(rec.get("entityIds") or {}),
            float(rec.get("offset") or 0.0),
        )
        with self._lock:
            key = self._resolve_version(base)
            state = self._states[key]
            return float(self._score_on(state, [req])[0])

    def start_shadow(
        self, model_version: str, fraction: Optional[float] = None
    ) -> None:
        """Mirror a deterministic sample of primary traffic onto a resident
        candidate. Each call ADDS a lane (or resets an existing one), so N
        candidates can shadow concurrently — each with its own fraction,
        accumulator, and divergence record; the no-argument legacy API
        (``stop_shadow()`` / ``shadow_stats()`` / ``shadow_version``)
        addresses the most recently started lane. Starting an already
        shadowing version resets its record so a quota check reads the new
        phase only."""
        with self._lock:
            key = self._resolve_version(model_version)
            if key == self._primary:
                raise ValueError("cannot shadow the primary onto itself")
            frac = float(fraction) if fraction is not None \
                else self._shadow_fraction
            self._shadow_fraction = frac
            self._shadow_seq += 1
            self._shadows[key] = _ShadowLane(frac, self._shadow_seq)
        logger.info(
            "serving: shadowing %.3f of primary traffic onto %r "
            "(%d concurrent lane(s))", frac, key, len(self._shadows),
        )

    def stop_shadow(self, model_version: Optional[str] = None) -> None:
        """Stop one candidate's lane, or EVERY lane when no version is
        given (the legacy single-shadow call)."""
        with self._lock:
            if model_version is None:
                self._shadows.clear()
                return
            key = self._resolve_version(model_version)
            self._shadows.pop(key, None)

    def shadow_stats(self, model_version: Optional[str] = None) -> Dict:
        """Divergence record for one candidate lane (``model_version``), or
        the legacy single-shadow view: the most recently started lane's
        record plus a ``candidates`` map carrying EVERY lane keyed by
        version — N concurrent shadows never alias into one series."""
        with self._lock:
            if model_version is not None:
                key = self._resolve_version(model_version)
                lane = self._shadows.get(key)
                if lane is None:
                    return dict(version=None, count=0,
                                max_divergence=0.0, mean_divergence=0.0)
                return lane.stats(key)
            per_lane = {
                k: lane.stats(k) for k, lane in self._shadows.items()
            }
            newest = self._newest_shadow_locked()
            if newest is None:
                return dict(version=None, count=0, max_divergence=0.0,
                            mean_divergence=0.0, candidates=per_lane)
            out = newest[1].stats(newest[0])
            out["candidates"] = per_lane
            return out

    def shadow_samples(
        self, model_version: Optional[str] = None
    ) -> List[Dict]:
        """Recent (uid, primary, shadow) score pairs — the rollout soak's
        bit-exactness evidence. One lane's samples when ``model_version``
        is given, else the most recently started lane's."""
        with self._lock:
            if model_version is not None:
                key = self._resolve_version(model_version)
                lane = self._shadows.get(key)
                return list(lane.samples) if lane else []
            newest = self._newest_shadow_locked()
            return list(newest[1].samples) if newest else []

    def promote(self, model_version: str) -> Dict:
        """Make a resident generation the primary, remembering the previous
        primary as the ROLLBACK PARENT (pinned against eviction). The swap
        happens under the scoring lock: in-flight batches drain on the old
        primary, the next batch scores on the new one — same zero-downtime
        story as reload, zero compiles because the state is already warm."""
        with self._lock:
            key = self._resolve_version(model_version)
            if key == self._primary:
                return dict(model_version=key, parent=None)
            parent = self._primary
            self._promotion = dict(
                version=key,
                parent=parent,
                at=time.time(),
                trips_at=self._total_trips(),
            )
            self._primary = key
            self._shadows.pop(key, None)  # a primary never shadows itself
            self._last_model_update = time.time()  # SLO staleness clock
        registry().counter("serve_promotions_total").inc()
        logger.info("serving: promoted %r (parent %r)", key, parent)
        return dict(model_version=key, parent=parent)

    def trips_since_promotion(self) -> int:
        """Breaker trips since the last ``promote`` — the watcher's rollback
        signal. 0 when nothing was promoted, or once the promotion's
        ``promotion_settle_s`` monitoring window has passed."""
        with self._lock:
            self._maybe_settle_promotion_locked()
            promo = self._promotion
            return self._total_trips() - promo["trips_at"] if promo else 0

    def promotion_in_window(self) -> bool:
        """True while a promotion is inside its ``promotion_settle_s``
        monitoring window — the span during which the SLO gate may still
        unwind it (after settle, a rollback target no longer exists)."""
        with self._lock:
            self._maybe_settle_promotion_locked()
            return self._promotion is not None

    def rollback(self, reason: str = "") -> Optional[str]:
        """Demote the promoted generation back to its parent. Returns the
        demoted version (for the caller to poison), or None when there is
        no promotion to unwind or the parent is gone."""
        with self._lock:
            promo = self._promotion
            if promo is None or promo["parent"] not in self._states:
                return None
            demoted = self._primary
            self._primary = promo["parent"]
            self._promotion = None
            self._shadows.clear()
        registry().counter("serve_rollbacks_total").inc()
        logger.warning(
            "serving: rolled back %r -> %r (%s)",
            demoted, self._primary, reason or "no reason given",
        )
        return demoted

    def reload(self, model: GameModel, model_version: Optional[str] = None) -> Dict:
        """Zero-downtime swap to ``model``: load as a resident generation,
        then promote it. The direct path (no shadow phase) — the rollout
        watcher uses load_version/start_shadow/promote instead.

        A failed build/warm-up raises :class:`ReloadError` and leaves the
        OLD state serving, untouched — the error is also visible in
        ``stats()['last_reload_error']`` until a reload succeeds."""
        out = self.load_version(model, model_version)
        with tracer().span("serve/reload_swap"):
            self.promote(out["model_version"])
        return out

    def set_partition(self, partition: Optional[StorePartition]) -> Dict:
        """Swap the fleet shard-ownership predicate live on EVERY resident
        generation's store (ring rebalance / membership change). Rows the
        new predicate disowns age out of the hot set; newly-owned rows
        promote on their next request (or, for compacted hosts, after the
        next reload rebuilds the host subset)."""
        with self._lock:
            self._partition = partition
            for state in self._states.values():
                state.store.set_partition(partition)
            stats = self._state.store.partition_stats()
        return dict(
            partition=stats,
            versions=sorted(self._states),
        )

    def shard_export(
        self,
        target_snapshot: Dict,
        target_member: Optional[str] = None,
        include_cold: bool = True,
    ) -> Dict:
        """Warm-handoff export from the PRIMARY generation's store (the one
        live traffic resolves against), serialized with scoring on the
        batch lock — see ``HotColdEntityStore.shard_export``."""
        with self._lock:
            return self._state.store.shard_export(
                target_snapshot,
                target_member=target_member,
                include_cold=include_cold,
            )

    def shard_import(self, payload: Dict) -> Dict:
        """Install a peer's handoff payload on EVERY resident generation's
        store (host rows + hot-set pre-promotion) under the batch lock.
        Upload chunks stay within the warmed scatter buckets, so the
        zero-post-warmup-retrace contract holds through a handoff."""
        out: Dict = {}
        with self._lock:
            for version, state in self._states.items():
                out[version] = state.store.shard_import(
                    payload, upload_chunk=self.max_batch
                )
        return out

    def stats(self) -> Dict:
        state = self._state
        degraded = sorted(
            rt for rt, b in self._breakers.items() if b.open
        )
        trips = self.trips_since_promotion()  # may settle the promotion
        promo = self._promotion
        return dict(
            model_version=state.model_version,
            versions=sorted(self._states),
            primary=self._primary,
            shadow=self.shadow_version,
            shadows=self.shadow_versions,
            shadow_stats=self.shadow_stats(),
            promotion=dict(promo) if promo else None,
            trips_since_promotion=trips,
            queue_depth=self.batcher.queue_depth,
            max_batch_size=self.max_batch,
            trace_count=state.transformer.trace_count,
            retraces_since_warmup=self.retraces_since_warmup,
            store=state.store.stats(),
            partition=state.store.partition_stats(),
            degraded=bool(degraded) or self._last_reload_error is not None,
            degraded_re_types=degraded,
            breaker_trips={
                rt: b.trips for rt, b in self._breakers.items() if b.trips
            },
            reload_failures=self._reload_failures,
            last_reload_error=self._last_reload_error,
            tenants=self.admission.snapshot(),
            feedback=(
                self._feedback.stats() if self._feedback is not None else None
            ),
            slo=self._slo_block(),
            quality=self._quality_block(),
            telemetry_sink=telemetry_sink_health(),
            flight_recorder=flight_recorder().stats(),
            otlp_exporter=exporter_health(),
        )

    def _slo_block(self) -> Dict:
        """The ``/healthz`` SLO block; also the flush point that mirrors
        burn/state into gauges so the ``/metrics`` scrape carries them."""
        self.slo.record_staleness(time.time() - self._last_model_update)
        try:
            self.slo.publish_metrics()
        except Exception:  # noqa: BLE001 — stats must never fail on obs
            pass
        snap = self.slo.snapshot()
        snap["model_staleness_now_s"] = time.time() - self._last_model_update
        return snap

    def _quality_block(self) -> Dict:
        """The healthz model-quality block; also the flush point mirroring
        windowed per-version AUC/ECE/lift into ``quality_*`` gauges so the
        ``/metrics`` scrape (and the fleet merge) carries them."""
        try:
            self.quality.publish()
        except Exception:  # noqa: BLE001 — stats must never fail on obs
            pass
        return self.quality.snapshot()

    def close(self, drain: bool = True) -> None:
        self.batcher.close(drain=drain)
        if self._feedback is not None:
            try:
                self._feedback.close()
            except Exception:  # noqa: BLE001 — close must not raise
                logger.exception("serving: feedback spool close failed")


def load_engine(
    model_dir: str,
    artifacts_dir: Optional[str] = None,
    config: Optional[ServeConfig] = None,
    model_version: Optional[str] = None,
    partition: Optional[StorePartition] = None,
) -> ServingEngine:
    """Build an engine from a trained model directory the way the batch
    scoring driver would: index maps + entity indexes from the artifacts
    dir (default: the model dir's parent = the training output dir), model
    loaded HOST-side (the store owns device residency)."""
    from photon_tpu.io.model_io import (
        delta_info,
        load_resolved_game_model,
        model_re_types,
        read_model_metadata,
        resolve_delta_chain,
    )

    artifacts = artifacts_dir or os.path.dirname(model_dir.rstrip("/"))
    # A cold start can land directly on a delta micro-generation (LATEST
    # points at it): the coordinate/shard universe then comes from the
    # whole resolved chain, not the layer's few touched coordinates.
    layers = (
        resolve_delta_chain(model_dir)
        if delta_info(model_dir) is not None
        else [model_dir]
    )
    meta: Dict[str, object] = {"coordinates": {}}
    for layer in layers:
        for cid, info in read_model_metadata(layer).get(
            "coordinates", {}
        ).items():
            meta["coordinates"].setdefault(cid, info)
    index_maps: Dict[str, IndexMap] = {}
    for coord in meta.get("coordinates", {}).values():
        shard = coord.get("featureShard")
        path = os.path.join(artifacts, f"index-map-{shard}.json")
        if shard and shard not in index_maps and os.path.exists(path):
            index_maps[shard] = IndexMap.load(path)
    entity_indexes: Dict[str, EntityIndex] = {}
    for re_type in model_re_types(meta):
        path = os.path.join(artifacts, f"entity-index-{re_type}.json")
        if os.path.exists(path):
            entity_indexes[re_type] = EntityIndex.load(path)
    model = load_resolved_game_model(
        model_dir, index_maps, entity_indexes, to_device=False
    )
    engine = ServingEngine(
        model,
        entity_indexes=entity_indexes,
        index_maps=index_maps,
        config=config,
        model_version=model_version or model_dir.rstrip("/"),
        partition=partition,
    )
    # The publish root this engine was loaded from: generation manifests
    # live here, which is what the /v1/experiment rollup reads.
    engine.artifacts_dir = artifacts
    return engine
