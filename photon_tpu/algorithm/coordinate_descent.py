"""Block coordinate descent over named coordinates — the GAME outer loop.

Parity target: reference ``CoordinateDescent`` (photon-lib
algorithm/CoordinateDescent.scala:43-670): update-sequence validation with
locked coordinates (:71-121), the running summedScores residual with
incremental update `summed − oldScores + previousScores` (:441-446),
best-model tracking by validation metric (:576-626), and the
descend/descendWithValidation split (:373-472 / :493-640).

TPU-first: per-coordinate scores are flat (n,) arrays aligned to the
GameBatch sample axis; the residual for coordinate c is simply
``total_scores - scores[c]`` — the reference's persist/unpersist + outer-join
choreography (CoordinateDescent.scala:257-341) has no analogue because
everything is resident device arrays.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from photon_tpu.algorithm.coordinate import Coordinate
from photon_tpu.data.game_data import GameBatch, RowLayout
from photon_tpu.models.game import GameModel
from photon_tpu.obs.metrics import registry
from photon_tpu.obs.trace import span

Array = jax.Array
logger = logging.getLogger(__name__)


@contextmanager
def _export_trace():
    """When an OTLP exporter is installed (``--otlp-endpoint``), run the
    body under a minted trace context so its spans become traced and flow
    through the tracer sink to the collector — the training-side
    enrollment of the serve-side export path. Without an exporter this is
    a no-op: spans stay untraced and pay nothing new. The trace is
    finished against the flight recorder so the open-trace table never
    accumulates training passes."""
    from photon_tpu.obs.export import active_exporter

    if active_exporter() is None:
        yield
        return
    from photon_tpu.obs.trace import flight_recorder, mint_context, tracer

    ctx = mint_context()
    t0 = time.monotonic()
    try:
        with tracer().attach_context(ctx):
            yield
    finally:
        flight_recorder().finish(ctx.trace_id, time.monotonic() - t0)


@dataclasses.dataclass
class CoordinateDescentResult:
    model: GameModel
    best_model: GameModel
    best_metric: Optional[float]
    metric_history: List[Dict[str, float]]
    tracker: Dict[str, list]
    # Host-measured wall seconds per (coordinate, CD pass) solve — the
    # driver-level timing the reference's OptimizationStatesTracker records
    # per optimizer iteration (OptimizationStatesTracker.scala:61-113). Here
    # a whole solve is ONE compiled program, so the solve is the smallest
    # host-observable unit; per-iteration loss/|grad| live in the jit-side
    # history rings instead.
    wall_times: Dict[str, List[float]] = dataclasses.field(default_factory=dict)

    def summary(self) -> str:
        """Per-coordinate optimization summary table (toSummaryString role):
        the jit-recorded per-iteration loss/|grad| histories joined with the
        host-side wall time of each solve."""
        lines: List[str] = []
        for cid, diags in self.tracker.items():
            walls = self.wall_times.get(cid, [])
            for p, diag in enumerate(diags):
                wall = f"{walls[p]:.3f}s" if p < len(walls) else "n/a"
                lines.append(f"-- coordinate {cid!r}, CD pass {p} (wall {wall})")
                body = diag.summary() if hasattr(diag, "summary") else repr(diag)
                lines.extend("   " + ln for ln in body.splitlines())
        return "\n".join(lines)


class CoordinateDescent:
    """Runs the update sequence for ``num_iterations`` passes.

    Args:
      coordinates: coordinate_id -> Coordinate (training problems).
      update_sequence: order of coordinate updates per pass.
      locked_coordinates: ids scored from a fixed pretrained model but never
        retrained (partial retraining, reference CoordinateDescent.scala:55).
    """

    def __init__(
        self,
        coordinates: Dict[str, Coordinate],
        update_sequence: Sequence[str],
        num_iterations: int = 1,
        locked_coordinates: Sequence[str] = (),
    ):
        locked = set(locked_coordinates)
        # Validation (reference :71-121): every id in the sequence must have a
        # coordinate; locked ids must NOT be (re)trained but must exist.
        missing = [c for c in update_sequence if c not in coordinates]
        if missing:
            raise ValueError(f"update sequence references unknown coordinates: {missing}")
        dup = [c for c in update_sequence if update_sequence.count(c) > 1]
        if dup:
            raise ValueError(f"duplicate coordinates in update sequence: {sorted(set(dup))}")
        if not update_sequence:
            raise ValueError("empty update sequence")
        self.coordinates = coordinates
        self.update_sequence = list(update_sequence)
        self.num_iterations = num_iterations
        self.locked = locked

    def run(
        self,
        batch: GameBatch,
        initial_model: Optional[GameModel] = None,
        validation_batch: Optional[GameBatch] = None,
        validation_fn: Optional[Callable[[GameModel, GameBatch], Dict[str, float]]] = None,
        better: Callable[[float, float], bool] = lambda new, old: new < old,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 1,
        checkpoint_tag: Optional[str] = None,
        checkpoint_keep_last: Optional[int] = None,
        emitter=None,  # utils.events.EventEmitter; optimization-log events
        profile: bool = True,
        layout: RowLayout = RowLayout(),
    ) -> CoordinateDescentResult:
        """Descend; with validation data, tracks the best model seen across
        iterations by the primary metric (descendWithValidation role).

        ``better(new, old)`` encodes metric direction (reference
        EvaluatorType.op); default assumes lower-is-better.

        ``profile=True`` (default) blocks on each coordinate's scores so
        ``wall_times`` covers device execution. ``profile=False`` removes
        every ``block_until_ready`` between coordinate updates — back-to-back
        coordinates stay enqueued on device with no host sync, and the
        recorded wall times measure dispatch only.

        Outside the coordinate updates ``run`` reads nothing back from the
        device: the trackers it returns hold device arrays, and each costs
        one transfer when somebody reads it (``summary()``,
        ``diagnostics_dict()``). Two readers live here: the optimisation
        summary, built only when this module's logger is enabled for INFO
        (the drivers under ``photon_tpu/cli`` are), and the per-update event
        of an ``emitter`` under ``profile``.

        With ``checkpoint_dir``, full descent state (models, score arrays,
        iteration counter, metric history) is persisted every
        ``checkpoint_every`` iterations and training RESUMES from the latest
        checkpoint found there — mid-training recovery the reference lacks
        (its warm start is model-only, SURVEY.md §5).
        A checkpoint keeps its score vectors in the order of the rows as
        given: ``layout`` is the order ``batch`` was laid out in where the
        caller laid it out (``GameEstimator``), so a run laid out otherwise,
        or not at all, resumes them.
        ``checkpoint_keep_last`` caps how many step files survive (the
        writer prunes the oldest after each publish; on a full disk it also
        prunes before retrying). A save that still fails with ENOSPC after
        the writer's prune-and-retry degrades to a logged warning plus
        ``checkpoint_write_failures_total`` and TRAINING CONTINUES — a full
        checkpoint disk must not kill a run that can still produce its
        final model (degradation priority: the finished artifact outranks
        intermediate durability).
        """
        n = batch.n
        dtype = batch.offset.dtype

        # Initialize models + per-coordinate score vectors.
        models: Dict[str, object] = {}
        scores: Dict[str, Array] = {}
        for cid in self.update_sequence:
            coord = self.coordinates[cid]
            if initial_model is not None and initial_model.get(cid) is not None:
                models[cid] = initial_model.get(cid)
            else:
                if cid in self.locked:
                    raise ValueError(f"locked coordinate {cid} needs a pretrained model")
                models[cid] = None
            scores[cid] = (
                self.coordinates[cid].score(models[cid], batch)
                if models[cid] is not None
                else jnp.zeros((n,), dtype)
            )

        total_scores = jnp.zeros((n,), dtype)
        for s in scores.values():
            total_scores = total_scores + s

        tracker: Dict[str, list] = {cid: [] for cid in self.update_sequence}
        wall_times: Dict[str, List[float]] = {cid: [] for cid in self.update_sequence}
        metric_history: List[Dict[str, float]] = []
        best_metric: Optional[float] = None
        # Seed the best-model slot from the warm start only when a validation
        # pass will actually run and can replace it; without validation the
        # seed would survive to the end and the caller would get the initial
        # model back with every trained pass discarded.
        has_validation = validation_fn is not None and validation_batch is not None
        best_model = GameModel(dict(models)) if (
            has_validation and all(m is not None for m in models.values())
        ) else None

        start_it = 0
        if checkpoint_dir is not None:
            if checkpoint_every < 1:
                raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every}")
            from photon_tpu.utils.checkpoint import (
                LegacyCheckpointError,
                load_checkpoint,
            )

            tag = checkpoint_tag or ",".join(self.update_sequence)
            state = step = None
            try:
                # step=None → resume-robust load: a torn newest step (machine
                # crash mid-save) is skipped with a warning and the run
                # resumes one pass earlier; it raises only when EVERY step is
                # unreadable (corruption is never silently discarded).
                state, step = load_checkpoint(checkpoint_dir)
            except FileNotFoundError:
                pass  # fresh directory: nothing to resume
            except LegacyCheckpointError as exc:
                # Only v1 (pickle) checkpoints remain: an upgrade must not
                # turn a resumable job into a crash loop — restart the sweep
                # from step 0 (ADVICE r3).
                logger.warning(
                    "ignoring unreadable legacy checkpoint at %s (%s); "
                    "restarting training from step 0",
                    checkpoint_dir, exc,
                )
            if state is not None:
                if state.get("tag") != tag:
                    raise ValueError(
                        f"checkpoint at {checkpoint_dir} was written for a "
                        f"different configuration (saved tag {state.get('tag')!r}"
                        f" != current {tag!r}); clear the directory or point "
                        "checkpoint_dir elsewhere"
                    )
                with span("cd/resume_restore"):
                    models = state["models"]
                    scores = {
                        cid: layout.from_original(s)
                        for cid, s in state["scores"].items()
                    }
                    total_scores = layout.from_original(state["total_scores"])
                    metric_history = state["metric_history"]
                    best_metric = state["best_metric"]
                    best_model = state["best_model"]
                    tracker = state["tracker"]
                    wall_times = state.get(
                        "wall_times", {cid: [] for cid in self.update_sequence}
                    )
                    # Reinstall per-coordinate active-set gate state (pass
                    # counter + keep masks) so the first resumed pass is gated
                    # exactly like an uninterrupted run's would be. Older
                    # checkpoints without the field restore to a full pass.
                    active_state = state.get("active_state") or {}
                    for cid, coord in self.coordinates.items():
                        restore = getattr(coord, "restore_active_state", None)
                        if restore is not None:
                            restore(active_state.get(cid))
                start_it = step + 1
                registry().counter("cd_resumes_total").inc()
                logger.info(
                    "resuming coordinate descent from checkpoint step %d", step
                )

        single = len(self.update_sequence) == 1 and self.num_iterations == 1

        for it in range(start_it, self.num_iterations):
            for cid in self.update_sequence:
                if cid in self.locked:
                    continue
                coord = self.coordinates[cid]
                # Pass-boundary hook (duck-typed): active-set coordinates
                # reset their mask state when a descent (re)starts at
                # iteration 0, so reusing a coordinate object across runs
                # always begins with a full pass.
                begin_pass = getattr(coord, "begin_cd_pass", None)
                if begin_pass is not None:
                    begin_pass(it)
                t0 = time.monotonic()
                # One coordinate update = exchange + solve + score. The
                # solve span holds every launch of the coordinate's update
                # (its scores too: the fixed effect's come out of the solve
                # program itself); under ``profile`` the score span ends on
                # the fence, so the device time inside this span's interval
                # is this coordinate's, whatever programs the solve launches.
                # The closing exchange is dispatched after the fence: its
                # two elementwise launches may run inside the NEXT update's
                # interval.
                with _export_trace(), span(f"cd/iter{it}/{cid}"):
                    with span("exchange"):
                        # Residual: all OTHER coordinates' scores
                        # (summedScores − thisCoordinateScores, reference
                        # :441-446).
                        residual = (
                            None if single else total_scores - scores[cid]
                        )
                    with span("solve"):
                        model, diag, new_scores = coord.update(
                            batch, residual, models[cid], scores[cid]
                        )
                    with span("score"):
                        if profile:
                            # The clock must cover device execution, not
                            # dispatch.
                            jax.block_until_ready(new_scores)
                    with span("exchange"):
                        total_scores = total_scores - scores[cid] + new_scores
                wall = time.monotonic() - t0
                scores[cid] = new_scores
                models[cid] = model
                tracker[cid].append(diag)
                wall_times[cid].append(wall)
                registry().counter(
                    "cd_coordinate_updates_total", coordinate=cid
                ).inc()
                logger.info(
                    "CD iter %d coordinate %s trained in %.2fs", it, cid, wall
                )
                if emitter is not None:
                    from photon_tpu.utils.events import optimization_log_event

                    # diag.summary() reads device-resident history arrays —
                    # a host sync. Under profile=False the dispatch loop must
                    # stay sync-free, so the event carries the summary only
                    # when profiling; the run report reads the same
                    # diagnostics once at finalize either way.
                    emitter.emit(
                        optimization_log_event(
                            coordinate=cid,
                            cd_iteration=it,
                            wall_s=wall,
                            summary=(
                                diag.summary()
                                if profile and hasattr(diag, "summary")
                                else None
                            ),
                            # Active-set accounting: host ints the coordinate
                            # derived from masks it had ALREADY fetched at
                            # the pass boundary — reading them here adds no
                            # sync. None for ungated coordinates.
                            active_set=getattr(
                                coord, "last_active_set_stats", None
                            ),
                            # Out-of-core residency accounting (host ints the
                            # coordinate's store tracked during the pass) —
                            # None for fully-resident coordinates.
                            residency=getattr(
                                coord, "last_residency_stats", None
                            ),
                        )
                    )

            if validation_fn is not None and validation_batch is not None:
                game_model = GameModel(dict(models))
                metrics = validation_fn(game_model, validation_batch)
                metric_history.append(metrics)
                primary = next(iter(metrics.values()))
                if best_metric is None or better(primary, best_metric):
                    best_metric = primary
                    best_model = game_model
                logger.info("CD iter %d validation: %s", it, metrics)

            registry().counter("cd_iterations_total").inc()

            def _save_checkpoint(it=it):
                from photon_tpu.utils import resources
                from photon_tpu.utils.checkpoint import save_checkpoint

                with span("cd/checkpoint_save"):
                    # Active-set gate state rides along (duck-typed): the
                    # resolved keep masks are host bools; the save gathers
                    # every device array anyway, so this adds no extra syncs.
                    active_state = {
                        cid: coord.export_active_state()
                        for cid, coord in self.coordinates.items()
                        if getattr(coord, "export_active_state", None)
                        is not None
                    }
                    try:
                        save_checkpoint(
                            checkpoint_dir,
                            dict(
                                models=models,
                                scores={
                                    cid: layout.to_original(s)
                                    for cid, s in scores.items()
                                },
                                total_scores=layout.to_original(total_scores),
                                metric_history=metric_history,
                                best_metric=best_metric,
                                best_model=best_model,
                                tracker=tracker,
                                wall_times=wall_times,
                                active_state=active_state,
                                tag=checkpoint_tag or ",".join(self.update_sequence),
                            ),
                            it,
                            keep_last=checkpoint_keep_last,
                        )
                    except OSError as exc:
                        # The writer already pruned + retried; a persistent
                        # full disk degrades to lost intermediate durability,
                        # not a lost run.
                        if not resources.is_enospc(exc):
                            raise
                        registry().counter(
                            "checkpoint_write_failures_total"
                        ).inc()
                        logger.warning(
                            "checkpoint save at pass %d failed even after "
                            "pruning (disk full under %s); continuing "
                            "WITHOUT a checkpoint this pass: %s",
                            it, checkpoint_dir, exc,
                        )

            saved = False
            if checkpoint_dir is not None and (it + 1) % checkpoint_every == 0:
                _save_checkpoint()
                saved = True

            # Cooperative SIGTERM/SIGINT: the pass boundary is the safe stop
            # — every coordinate's state is consistent and (when a
            # checkpoint dir exists) durable, so --resume continues from
            # exactly here.
            from photon_tpu.utils.shutdown import (
                GracefulShutdown,
                shutdown_requested,
            )

            signum = shutdown_requested()
            if signum is not None:
                if checkpoint_dir is not None and not saved:
                    _save_checkpoint()
                logger.warning(
                    "coordinate descent stopping after pass %d on signal %d",
                    it, signum,
                )
                raise GracefulShutdown(signum)

            # Same cooperative boundary handles host memory pressure: at the
            # watchdog's hard level, checkpoint what we have and raise a
            # clean actionable error instead of waiting for the OOM-killer's
            # unexplained SIGKILL.
            from photon_tpu.utils import resources

            try:
                resources.check_memory(f"coordinate_descent pass {it}")
            except resources.HostMemoryPressureError:
                if checkpoint_dir is not None and not saved:
                    _save_checkpoint()
                raise

        final = GameModel(dict(models))
        if best_model is None:
            best_model = final
        result = CoordinateDescentResult(
            model=final,
            best_model=best_model,
            best_metric=best_metric,
            metric_history=metric_history,
            tracker=tracker,
            wall_times=wall_times,
        )
        # The summary reads every tracker back from the device: built only
        # for a log that will show it.
        if logger.isEnabledFor(logging.INFO):
            summary = result.summary()
            if summary:
                logger.info("optimization summary:\n%s", summary)
        return result
