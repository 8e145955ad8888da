"""Legacy single-GLM training driver.

Parity target: reference legacy ``Driver`` (photon-client Driver.scala:60-558)
with its INIT→PREPROCESSED→TRAINED→VALIDATED stage machine (DriverStage
.scala:20-55): read data (Avro or LIBSVM) → summarize/normalize → λ sweep
with warm start (ModelTraining.trainGeneralizedLinearModel role,
photon-api ModelTraining.scala:54-200) → validate per λ → select best by the
task's default metric → write models (text + Avro) + lifecycle events.
"""

from __future__ import annotations

import argparse
import enum
import json
import logging
import os
import time
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from photon_tpu.utils.compile_cache import configure_compile_cache
from photon_tpu.cli.common import add_validation_arg, setup_logging, task_of
from photon_tpu.data.batch import LabeledBatch
from photon_tpu.data.index_map import IndexMap
from photon_tpu.data.normalization import build_normalization_context
from photon_tpu.data.stats import compute_feature_stats
from photon_tpu.evaluation.metrics_map import (
    metrics_map,
    sanitize_for_json,
    selection_metric,
)
from photon_tpu.io.data_reader import FeatureShardConfig, read_merged
from photon_tpu.io.libsvm import read_libsvm
from photon_tpu.io.model_io import publish_latest_pointer, save_game_model
from photon_tpu.io.schemas import BAYESIAN_LINEAR_MODEL_SCHEMA
from photon_tpu.io.avro import write_avro_records
from photon_tpu.models.coefficients import Coefficients
from photon_tpu.models.game import FixedEffectModel, GameModel
from photon_tpu.models.glm import GeneralizedLinearModel
from photon_tpu.ops.losses import loss_for_task
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.factory import OptimizerSpec, make_optimizer
from photon_tpu.types import NormalizationType, OptimizerType, TaskType
from photon_tpu.utils.events import (
    EventEmitter,
    optimization_log_event,
    setup_event,
    training_finish_event,
    training_start_event,
)

class DriverStage(enum.Enum):
    """Reference DriverStage.scala:20-55 state machine."""

    INIT = 0
    PREPROCESSED = 1
    TRAINED = 2
    VALIDATED = 3


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("train-glm")
    p.add_argument("--training-data", required=True,
                   help="Avro path/dir/glob, or LIBSVM text file with --format libsvm")
    p.add_argument("--validation-data", default=None)
    p.add_argument("--format", default="avro", choices=["avro", "libsvm"])
    p.add_argument("--output-dir", required=True)
    p.add_argument("--task", default="LOGISTIC_REGRESSION", choices=[t.name for t in TaskType])
    p.add_argument("--optimizer", default="LBFGS", choices=[o.name for o in OptimizerType])
    p.add_argument("--regularization-weights", default="0.1,1,10,100")
    p.add_argument(
        "--regularization-type", default=None,
        choices=["NONE", "L1", "L2", "ELASTIC_NET"],
        help="reference REGULARIZATION_TYPE_OPTION: NONE ignores the "
             "weights, L1/L2 force the elastic-net alpha to 1/0, "
             "ELASTIC_NET uses --elastic-net-alpha as given",
    )
    p.add_argument("--elastic-net-alpha", type=float, default=0.0)
    p.add_argument("--max-iterations", type=int, default=None)
    p.add_argument("--tolerance", type=float, default=None)
    p.add_argument(
        "--optimization-state-tracker",
        action=argparse.BooleanOptionalAction, default=True,
        help="per-iteration (loss, |grad|) tracker rings "
             "(OPTIMIZATION_STATE_TRACKER_OPTION)",
    )
    p.add_argument(
        "--validate-per-iteration", action="store_true",
        help="compute the validation MetricsMap at EVERY optimizer "
             "iteration count (reference VALIDATE_PER_ITERATION; replays "
             "the deterministic solve at increasing max-iter — expensive, "
             "like the reference's warning says)",
    )
    p.add_argument(
        "--feature-dimension", type=int, default=None,
        help="explicit feature-space dimension for libsvm input "
             "(FEATURE_DIMENSION option; inferred when omitted)",
    )
    p.add_argument("--normalization", default="NONE", choices=[t.name for t in NormalizationType])
    p.add_argument("--intercept", action=argparse.BooleanOptionalAction, default=True)
    p.add_argument("--coefficient-box", default=None,
                   help="lower,upper box constraint applied to all coefficients")
    p.add_argument("--selected-features-file", default=None,
                   help="Avro file of FeatureNameTermAvro records; only "
                        "these features are used for training (reference "
                        "SELECTED_FEATURES_FILE, avro format only)")
    p.add_argument(
        "--constraint-string",
        default=None,
        help="JSON array of per-feature bounds "
             '[{"name": ..., "term": ..., "lowerBound": ..., "upperBound": ...}] '
             "with GLMSuite wildcard semantics (reference "
             "io/deprecated/GLMSuite.scala:190-260)",
    )
    p.add_argument(
        "--compute-variance",
        nargs="?",
        const="SIMPLE",
        default="NONE",
        choices=["NONE", "SIMPLE", "FULL"],
        help="coefficient variances (bare flag = SIMPLE diag-inverse; FULL = "
             "Cholesky inverse diagonal)",
    )
    p.add_argument("--event-listeners", nargs="*", default=[],
                   help="dotted paths of event listener callables")
    p.add_argument("--event-listener", action="append", default=[],
                   dest="event_listener",
                   help="register one event listener by path "
                        "('pkg.module:attr'); repeatable")
    p.add_argument("--telemetry-out", default=None,
                   help="write the unified run report (spans + metrics + "
                        "per-lambda solver diagnostics) as schema-stable "
                        "JSONL to this path")
    p.add_argument("--summarization-output-dir", default=None,
                   help="write per-feature summary statistics as "
                        "FeatureSummarizationResultAvro "
                        "(writeBasicStatistics role)")
    p.add_argument("--stream-ingest-chunk-rows", type=int, default=0,
                   help="avro format: multi-pass streaming ingest "
                        "(io/pipeline.py) — pass 1 decodes container "
                        "blocks once (chunks of this many rows, teed into "
                        "a byte-budgeted host replay cache) while distinct-"
                        "scanning the feature space; pass 2 replays decoded "
                        "chunks through assemble + host→device pipeline "
                        "stages, concatenating on device — decode is never "
                        "paid twice and host RAM never holds the assembled "
                        "dataset")
    p.add_argument("--replay-cache-mb", type=int, default=1024,
                   help="host byte budget (MiB) for the decoded-chunk "
                        "replay cache; when the stream outgrows it the "
                        "cache spills and later passes re-stream from disk "
                        "(host memory stays bounded either way)")
    add_validation_arg(p)
    from photon_tpu.cli.common import add_active_set_args, add_out_of_core_args

    add_active_set_args(p)
    add_out_of_core_args(p)
    p.add_argument("--checkpoint-dir", default=None,
                   help="λ-sweep checkpoint/resume directory: one durable "
                        "step per completed λ (results + the warm-start "
                        "vector), written through the atomic checkpoint "
                        "machinery; a killed run resumes at the next λ. "
                        "Resumes automatically when state exists")
    p.add_argument("--resume", action="store_true",
                   help="resume an interrupted λ sweep from --checkpoint-dir "
                        "(requires checkpoint state to exist; auto-resume "
                        "merely uses it when present)")
    p.add_argument("--checkpoint-keep-last", type=int, default=None,
                   help="keep only the newest K λ-step files (pruned after "
                        "each save; also pruned before the disk-full "
                        "retry). NB a resumed sweep replays pruned λs. "
                        "Default: keep everything, or "
                        "PHOTON_TPU_CHECKPOINT_KEEP_LAST")
    p.add_argument("--verbose", action="store_true")
    return p


def _selected_features_index_map(args) -> Optional[IndexMap]:
    """SELECTED_FEATURES_FILE role (PhotonMLCmdLineParser.scala:203-205,
    GLMSuite.getSelectedFeatureSetFromFile): an Avro file of
    FeatureNameTermAvro records restricting the training feature space.
    Features outside the set are dropped at ingest (the reader masks
    features absent from a provided index map)."""
    if not args.selected_features_file:
        return None
    if args.format == "libsvm":
        raise ValueError(
            "--selected-features-file applies to the avro format "
            "(features are name/term keyed)"
        )
    from photon_tpu.io.avro import AvroReader

    keys = set()
    with AvroReader(args.selected_features_file) as r:
        for rec in r:
            keys.add(IndexMap.key(rec["name"], rec.get("term") or ""))
    if not keys:
        raise ValueError(
            f"no features in {args.selected_features_file}"
        )
    return IndexMap.build(sorted(keys), add_intercept=args.intercept)


def _stream_load_avro(args, path: str, index_map: Optional[IndexMap]):
    """Streaming multi-pass avro load (decode once, replay from a
    byte-budgeted host cache):

    pass 1  stream_avro_columnar decodes container blocks into ColumnarRows
            chunks, teed into a ChunkReplayCache; the same pass distinct-
            scans feature keys in global first-occurrence order — the exact
            IndexMap the slurping reader builds (skipped when the map is
            supplied, e.g. --selected-features-file or validation data).
    pass 2  replays decoded chunks (re-streams from disk if the cache
            spilled its byte budget) through the assemble + h2d pipeline
            stages (io/pipeline.py), concatenating on device — each chunk's
            transfer overlaps earlier chunks' placement via async dispatch,
            and host RAM never holds the assembled dataset.
    """
    from photon_tpu.io.columnar import stream_avro_columnar
    from photon_tpu.io.data_reader import _expand_paths
    from photon_tpu.io.pipeline import (
        ChunkReplayCache,
        assemble_host_batches,
        columnar_nbytes,
        device_chunks_from,
        materialize_game_batch,
    )

    chunk_rows = args.stream_ingest_chunk_rows
    paths = _expand_paths([path])
    cache = ChunkReplayCache(
        lambda: stream_avro_columnar(paths, chunk_rows),
        byte_budget=args.replay_cache_mb << 20,
        nbytes=columnar_nbytes,
    )
    imap = index_map
    if imap is None:
        seen: Dict[str, None] = {}
        for cols in cache:
            ids = [
                cols.bags[b].key_ids
                for b in ("features",)
                if b in cols.bags and cols.bags[b].key_ids.size
            ]
            if ids:
                for i in np.unique(np.concatenate(ids)):
                    seen.setdefault(cols.intern[i], None)
        imap = IndexMap.build(seen, add_intercept=args.intercept)
    cfg = {
        "features": FeatureShardConfig(
            feature_bags=["features"], has_intercept=args.intercept
        )
    }
    batch = materialize_game_batch(
        device_chunks_from(
            lambda: assemble_host_batches(
                iter(cache), cfg, {"features": imap}
            ),
            telemetry_label="train-ingest",
        )
    )
    log = logging.getLogger("photon_tpu.train_glm")
    log.info(
        "streaming ingest: decode passes=%d replay passes=%d cache=%s",
        cache.source_passes, cache.replay_passes,
        "spilled" if cache.spilled
        else f"{cache.cached_bytes >> 20} MiB held",
    )
    cache.close()  # the batch is materialized; delete any disk spool now
    return batch.labeled_batch("features"), imap


def _load(args, path: Optional[str], index_map=None):
    if path is None:
        return None, index_map
    if args.format == "libsvm":
        X, y = read_libsvm(path, dim=args.feature_dimension)
        if args.intercept:
            X = np.concatenate([X, np.ones((X.shape[0], 1), np.float32)], axis=1)
        imap = index_map or IndexMap.build(
            [str(j + 1) for j in range(X.shape[1] - (1 if args.intercept else 0))],
            add_intercept=args.intercept,
        )
        return LabeledBatch(jnp.asarray(y), jnp.asarray(X)), imap
    if int(getattr(args, "stream_ingest_chunk_rows", 0) or 0) > 0:
        return _stream_load_avro(args, path, index_map)
    cfg = {"features": FeatureShardConfig(feature_bags=["features"], has_intercept=args.intercept)}
    batch, imaps, _ = read_merged(
        [path], cfg, index_maps=None if index_map is None else {"features": index_map}
    )
    return batch.labeled_batch("features"), imaps["features"]


def run(args) -> Dict:
    setup_logging(args.verbose)
    from photon_tpu.obs import begin_run, finalize_run_report, span

    begin_run()  # fresh spans / metrics / phase records for THIS run
    from photon_tpu.utils import resources as _resources

    # Host RSS watchdog: inert without a detectable limit; under pressure
    # pipeline depths tighten, and the λ boundary below fails cleanly at the
    # hard level instead of catching the OOM-killer's SIGKILL.
    _resources.start_watchdog()
    if getattr(args, "re_active_set", False):
        logging.getLogger(__name__).warning(
            "--re-active-set is a no-op for the single-GLM driver (no "
            "random-effect coordinates); it only affects GAME training"
        )
    if getattr(args, "re_device_budget_mb", None):
        logging.getLogger(__name__).warning(
            "--re-device-budget-mb is a no-op for the single-GLM driver "
            "(no random-effect coordinates); it only affects GAME training"
        )
    task = task_of(args)
    stage = DriverStage.INIT
    emitter = EventEmitter()
    for name in list(args.event_listeners) + list(
        getattr(args, "event_listener", [])
    ):
        emitter.register_by_name(name)
    emitter.emit(setup_event(driver="train_glm", task=args.task,
                             optimizer=args.optimizer))

    if args.validate_per_iteration and args.validation_data is None:
        raise ValueError(
            "--validate-per-iteration requires --validation-data"
        )
    train, imap = _load(args, args.training_data,
                        _selected_features_index_map(args))
    valid, _ = _load(args, args.validation_data, imap)
    from photon_tpu.data.validators import DataValidationType, validate_labeled_batch

    validation_mode = DataValidationType[args.data_validation]
    validate_labeled_batch(train, task, validation_mode)
    if valid is not None:
        validate_labeled_batch(valid, task, validation_mode)
    icpt = imap.get_index(IndexMap.INTERCEPT) if args.intercept else None
    if icpt is not None and icpt < 0:
        icpt = None

    norm = None
    norm_type = NormalizationType[args.normalization]
    if norm_type != NormalizationType.NONE or args.summarization_output_dir:
        stats = compute_feature_stats(train, icpt)
        if norm_type != NormalizationType.NONE:
            norm = build_normalization_context(
                norm_type, stats.mean, stats.std, stats.abs_max, icpt
            )
        if args.summarization_output_dir:
            from photon_tpu.io.model_io import write_basic_statistics

            write_basic_statistics(
                stats, imap,
                os.path.join(args.summarization_output_dir, "part-00000.avro"),
            )
    stage = DriverStage.PREPROCESSED

    box = None
    if args.coefficient_box:
        lo, hi = (float(x) for x in args.coefficient_box.split(","))
        d = train.dim
        box = (jnp.full((d,), lo, jnp.float32), jnp.full((d,), hi, jnp.float32))
    if args.constraint_string:
        from photon_tpu.data.constraints import constraint_bound_vectors

        if box is not None:
            raise ValueError(
                "--constraint-string and --coefficient-box are exclusive"
            )
        bounds = constraint_bound_vectors(
            args.constraint_string, imap, train.dim, icpt
        )
        if bounds is not None:
            box = (jnp.asarray(bounds[0]), jnp.asarray(bounds[1]))

    # REGULARIZATION_TYPE_OPTION semantics (PhotonMLCmdLineParser.scala:
    # 100-116): NONE ignores the weights entirely; L1/L2 pin the
    # elastic-net mix; ELASTIC_NET takes the alpha as given.
    if args.regularization_type == "NONE":
        args.regularization_weights = "0"
    elif args.regularization_type == "L1":
        args.elastic_net_alpha = 1.0
    elif args.regularization_type == "L2":
        args.elastic_net_alpha = 0.0

    weights = sorted(float(x) for x in args.regularization_weights.split(","))
    weights.reverse()  # strongest first: warm start toward weaker reg
    loss = loss_for_task(task)
    emitter.emit(training_start_event(task=task.value, weights=weights))

    from photon_tpu.algorithm.solve_cache import default_cache
    from photon_tpu.utils.shutdown import (
        GracefulShutdown,
        handle_termination,
        shutdown_requested,
    )

    models: List[Dict] = []
    solver_diags: List = []
    solver_walls: List[float] = []
    w = jnp.zeros((train.dim,), jnp.float32)

    # λ-sweep checkpoint/resume: one step per completed λ through the atomic
    # checkpoint machinery (utils/checkpoint.py). The tag pins the sweep
    # configuration — a resumed run must be solving the SAME problem, or the
    # restored warm-start chain would silently change the results.
    ckpt_dir = args.checkpoint_dir
    ckpt_tag = "|".join([
        args.task, args.optimizer, f"{args.elastic_net_alpha:g}",
        ",".join(f"{lam:g}" for lam in weights),
    ])
    start_idx = 0
    if ckpt_dir and args.validate_per_iteration:
        raise ValueError(
            "--checkpoint-dir is incompatible with --validate-per-iteration "
            "(per-iteration replay handles are not persistable)"
        )
    if args.resume and not ckpt_dir:
        raise ValueError("--resume requires --checkpoint-dir")
    if ckpt_dir:
        from photon_tpu.utils.checkpoint import (
            LegacyCheckpointError,
            latest_step,
            load_checkpoint,
        )

        if args.resume and latest_step(ckpt_dir) is None:
            raise ValueError(f"--resume: no checkpoint state under {ckpt_dir}")
        log = logging.getLogger("photon_tpu.train_glm")
        state = step = None
        try:
            state, step = load_checkpoint(ckpt_dir)
        except FileNotFoundError:
            pass
        except LegacyCheckpointError as exc:
            log.warning("ignoring legacy checkpoint under %s: %s", ckpt_dir, exc)
        if state is not None:
            if state.get("tag") != ckpt_tag:
                log.warning(
                    "checkpoint under %s is for a different λ-sweep "
                    "configuration; starting fresh", ckpt_dir,
                )
            else:
                models = list(state["models"])
                solver_diags = list(state["solver_diags"])
                solver_walls = list(state["solver_walls"])
                w = state["w"]
                start_idx = step + 1
                log.info(
                    "resuming λ sweep from checkpoint: %d/%d weights done",
                    start_idx, len(weights),
                )
                from photon_tpu.obs import registry as _registry

                _registry().counter("glm_sweep_resumes_total").inc()

    for lam_idx, lam in enumerate(weights):
        if lam_idx < start_idx:
            continue  # restored from checkpoint
        objective = GLMObjective(
            loss=loss,
            l2_weight=(1.0 - args.elastic_net_alpha) * lam,
            l1_weight=args.elastic_net_alpha * lam,
            intercept_index=icpt,
            normalization=norm,
        )
        spec = OptimizerSpec(
            OptimizerType[args.optimizer], args.max_iterations, args.tolerance,
            box=box, track_history=args.optimization_state_tracker,
        )
        # λ solves route through the shared compiled-solver cache — same
        # semantics as make_optimizer, but retraces and hits are accounted
        # (and a repeated λ config reuses one executable).
        solve = default_cache().fe_solver(objective, spec, train)
        w0_lam = w
        t0 = time.monotonic()
        with span(f"glm/lambda{lam:g}"):
            with span("solve"):
                result, _scores = solve(w, train)
        solver_walls.append(time.monotonic() - t0)
        solver_diags.append(result)
        w = result.w  # warm start (ModelTraining.scala:162-200)
        w_model = norm.transformed_to_model_space(w) if norm is not None else w
        from photon_tpu.ops.variance import (
            coefficient_variances,
            normalize_variance_type,
        )

        variances = coefficient_variances(
            objective, w, train, normalize_variance_type(args.compute_variance)
        )
        models.append(
            {
                "lambda": lam,
                "w": w_model,
                "variances": variances,
                "loss": float(result.value),
                "iterations": int(result.iterations),
                "reason": result.convergence_reason.value,
                # Replay handles for --validate-per-iteration (stripped
                # from the serialized summary).
                "_objective": objective,
                "_spec": spec,
                "_w0": w0_lam,
            }
        )
        emitter.emit(
            optimization_log_event(
                reg_weight=lam, loss=float(result.value),
                iterations=int(result.iterations),
                convergence=result.convergence_reason.value,
            )
        )
        if ckpt_dir:
            from photon_tpu.utils import resources
            from photon_tpu.utils.checkpoint import save_checkpoint

            # Replay handles (_objective/_spec/_w0) are live closures, not
            # persistable — strip them; everything else (including the
            # OptimizeResult diagnostics) round-trips through the manifest.
            try:
                save_checkpoint(
                    ckpt_dir,
                    dict(
                        tag=ckpt_tag,
                        w=w,
                        models=[
                            {k: v for k, v in m.items() if not k.startswith("_")}
                            for m in models
                        ],
                        solver_diags=solver_diags,
                        solver_walls=solver_walls,
                    ),
                    lam_idx,
                    keep_last=args.checkpoint_keep_last,
                )
            except OSError as exc:
                # The writer already pruned + retried. A disk that stays
                # full costs resumability, not the sweep: the final model
                # summary still gets written at the end.
                if not resources.is_enospc(exc):
                    raise
                from photon_tpu.obs.metrics import registry

                registry().counter("checkpoint_write_failures_total").inc()
                logging.getLogger("photon_tpu.train_glm").warning(
                    "λ-sweep checkpoint at λ=%g failed even after pruning "
                    "(disk full under %s); continuing WITHOUT a checkpoint "
                    "for this λ: %s", lam, ckpt_dir, exc,
                )
        signum = shutdown_requested()
        if signum is not None:
            logging.getLogger("photon_tpu.train_glm").warning(
                "λ sweep stopping after λ=%g on signal %d", lam, signum
            )
            finalize_run_report(
                "train_glm", path=args.telemetry_out, emitter=emitter
            )
            raise GracefulShutdown(signum)
        # Same cooperative boundary handles hard host memory pressure: the
        # finished λ steps are already durable (when --checkpoint-dir is
        # set), so failing HERE is clean and resumable.
        from photon_tpu.utils import resources as _resources

        _resources.check_memory(f"train_glm λ={lam:g}")
    stage = DriverStage.TRAINED

    # Validation + model selection (Driver.computeAndLogModelMetrics:353 +
    # Driver.modelSelection:416 roles): every λ gets the task's FULL
    # MetricsMap (Evaluation.scala:31-128) — MAE/MSE/RMSE for regression,
    # AUPR/AUROC/peak-F1 for classifiers, per-datum log-likelihood + AIC
    # where defined — then the best model is picked by the task's
    # selection metric (ModelSelection.scala:36-63).
    log = logging.getLogger("photon_tpu.train_glm")
    best_idx = len(models) - 1
    if valid is not None:
        sel_name, larger_better = selection_metric(task)
        best_val = None
        for i, m in enumerate(models):
            margins = valid.margins(m["w"])
            mmap = metrics_map(
                task, margins, valid.label, coefficients=m["w"]
            )
            m["validation"] = mmap
            log.info("Model with lambda = %g:", m["lambda"])
            if args.validate_per_iteration:
                # VALIDATE_PER_ITERATION (Driver.scala:354-376): metrics at
                # every iteration count. The deterministic solver replayed
                # from the same warm start with max_iter=j reproduces the
                # tracker's state-j coefficients exactly; one compile per j.
                import dataclasses as _dc

                per_iter = []
                for j in range(1, int(m["iterations"]) + 1):
                    spec_j = _dc.replace(m["_spec"], max_iter=j)
                    res_j = make_optimizer(m["_objective"], spec_j)(
                        m["_w0"], train
                    )
                    w_j = (norm.transformed_to_model_space(res_j.w)
                           if norm is not None else res_j.w)
                    mm_j = metrics_map(task, valid.margins(w_j), valid.label,
                                       coefficients=w_j)
                    per_iter.append(mm_j)
                    for name in sorted(mm_j):  # Driver.scala:368-373 shape
                        log.info("Iteration: [%6d] Metric: [%s] value: %s",
                                 j, name, mm_j[name])
                m["per_iteration_validation"] = per_iter
            for name in sorted(mmap):  # Driver.scala:400-405 log shape
                log.info("Metric: [%s] value: %s", name, mmap[name])
            v = mmap[sel_name]
            if best_val is None or (
                v > best_val if larger_better else v < best_val
            ):
                best_val, best_idx = v, i
        log.info(
            "Regularization weight of the best model is: %g",
            models[best_idx]["lambda"],
        )
        stage = DriverStage.VALIDATED

    os.makedirs(args.output_dir, exist_ok=True)
    # Text models (IOUtils.writeModelsInText role): one file per λ.
    for m in models:
        path = os.path.join(args.output_dir, f"model-lambda-{m['lambda']:g}.txt")
        with open(path, "w") as f:
            f.write(f"# task={task.value} lambda={m['lambda']:g} loss={m['loss']:.6e}\n")
            wv = np.asarray(m["w"])
            for j in np.flatnonzero(np.abs(wv) > 0):
                key = imap.get_feature_name(int(j)) or str(j)
                f.write(f"{key}\t{wv[j]:.8g}\n")
    # Avro model output for the best model (BayesianLinearModelAvro).
    best = models[best_idx]
    game = GameModel(
        {
            "global": FixedEffectModel(
                GeneralizedLinearModel(
                    Coefficients(best["w"], best["variances"]), task
                ),
                "features",
            )
        }
    )
    save_game_model(game, os.path.join(args.output_dir, "best"), {"features": imap})
    # fsync'd LATEST pointer: game_serving --reload-poll-interval follows
    # it, so a retrain hot-swaps into a live server with zero downtime.
    publish_latest_pointer(args.output_dir, "best")
    summary = {
        "best_lambda": best["lambda"],
        "models": [
            {k: v for k, v in m.items()
             if k not in ("w", "variances") and not k.startswith("_")}
            for m in models
        ],
        "stage": stage.name,
    }
    with open(os.path.join(args.output_dir, "training-summary.json"), "w") as f:
        # Non-finite metrics (e.g. AIC at the n−k−1=0 pole) become null:
        # the bare token Infinity is not RFC-8259 JSON.
        json.dump(sanitize_for_json(summary), f, indent=2)
    emitter.emit(training_finish_event(best_lambda=best["lambda"]))
    finalize_run_report(
        "train_glm",
        path=args.telemetry_out,
        emitter=emitter,
        trackers=[{
            "label": "glm",
            # One tracker row per λ solve (the driver's CD-analogue: the
            # λ sweep IS its coordinate sequence).
            "tracker": {"global": solver_diags},
            "wall_times": {"global": solver_walls},
        }],
    )
    return summary


def main(argv=None):
    args = build_parser().parse_args(argv)
    configure_compile_cache()
    from photon_tpu.utils.shutdown import GracefulShutdown, handle_termination

    try:
        with handle_termination():
            summary = run(args)
    except GracefulShutdown as exc:
        # Telemetry was finalized and the last completed λ is durable in
        # --checkpoint-dir; 128+signum is the conventional signal exit.
        raise SystemExit(128 + exc.signum) from exc
    print(json.dumps({"best_lambda": summary["best_lambda"]}))


if __name__ == "__main__":
    main()
