"""GAME training driver.

Parity target: reference ``GameTrainingDriver`` (photon-client
cli/game/training/GameTrainingDriver.scala:54-873): read train/validation
Avro → feature maps → stats/normalization → reg-weight cross-product →
GameEstimator.fit → model selection → save models + index maps.

Usage example (grammar mirrors README.md:293-296):

  python -m photon_tpu.cli.game_training \\
    --input-paths train/ --validation-paths valid/ --output-dir out/ \\
    --feature-shard-configurations name=globalShard \\
    --coordinate-configurations \\
      name=global,feature.shard=globalShard,optimizer=LBFGS,reg.weights=0.1|1|10 \\
      name=perUser,feature.shard=globalShard,random.effect.type=userId,reg.weights=1 \\
    --update-sequence global,perUser --evaluators AUC
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Dict

import jax.numpy as jnp
import numpy as np

from photon_tpu.utils.compile_cache import configure_compile_cache
from photon_tpu.cli.common import (
    add_common_args,
    parse_coordinate_config,
    parse_feature_shard_config,
    setup_logging,
    task_of,
)
from photon_tpu.data.normalization import build_normalization_context
from photon_tpu.data.stats import compute_feature_stats
from photon_tpu.data.index_map import IndexMap
from photon_tpu.estimators.game_estimator import GameEstimator
from photon_tpu.evaluation.metrics_map import sanitize_for_json
from photon_tpu.evaluation.suite import EvaluationSuite, EvaluatorSpec
from photon_tpu.io.data_reader import read_merged
from photon_tpu.io.model_io import (
    load_game_model,
    publish_latest_pointer,
    save_game_model,
)
from photon_tpu.types import NormalizationType
from photon_tpu.utils.timed import Timed


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("game-training")
    add_common_args(p)
    from photon_tpu.cli.common import add_validation_arg

    add_validation_arg(p)
    from photon_tpu.cli.common import add_active_set_args, add_out_of_core_args

    add_active_set_args(p)
    add_out_of_core_args(p)
    p.add_argument("--validation-paths", nargs="*", default=None)
    p.add_argument("--coordinate-configurations", nargs="+", required=True)
    p.add_argument("--update-sequence", required=True,
                   help="comma-separated coordinate ids")
    p.add_argument("--coordinate-descent-iterations", type=int, default=1)
    p.add_argument("--evaluators", nargs="*", default=["AUC"])
    p.add_argument("--normalization", default="NONE",
                   choices=[t.name for t in NormalizationType])
    p.add_argument("--model-input-dir", default=None, help="warm-start model dir")
    p.add_argument("--locked-coordinates", default="",
                   help="comma-separated coordinate ids to keep fixed (partial retrain)")
    p.add_argument(
        "--coordinate-constraints",
        default=None,
        help='JSON object: coordinate id → constraint array, e.g. '
             '{"global": [{"name": "f1", "term": "", "lowerBound": 0}]}. '
             "GLMSuite bound semantics, resolved against the coordinate's "
             "feature-shard index map; fixed-effect coordinates only",
    )
    p.add_argument(
        "--output-mode",
        default="BEST",
        choices=["BEST", "ALL", "NONE", "EXPLICIT", "TUNED"],
        help="reference ModelOutputMode: BEST = best model overall, ALL = "
             "every trained model, EXPLICIT = best of the explicit λ grid, "
             "TUNED = best hyperparameter-tuned model, NONE = no model output",
    )
    # Hyperparameter auto-tuning (reference GameTrainingDriver.scala:651-692).
    p.add_argument(
        "--hyper-parameter-tuning",
        default="NONE",
        choices=["NONE", "RANDOM", "BAYESIAN"],
        help="tune regularization hyperparameters after the explicit grid "
             "(RANDOM = Sobol search, BAYESIAN = GP + expected improvement)",
    )
    p.add_argument("--hyper-parameter-tuning-iter", type=int, default=10)
    p.add_argument(
        "--hyper-parameter-batch-size", type=int, default=1,
        help="candidates evaluated concurrently per tuning round (>1 uses "
             "the vmapped one-program path when the setup allows it — "
             "TPU-parallel tuning, absent in the reference)",
    )
    p.add_argument(
        "--hyper-parameter-tuner",
        default="ATLAS",
        choices=["DUMMY", "ATLAS"],
        help="tuner implementation (reference HyperparameterTunerFactory)",
    )
    p.add_argument(
        "--variance-computation",
        nargs="?",
        const="SIMPLE",
        default="NONE",
        choices=["NONE", "SIMPLE", "FULL"],
        help="coefficient variances: SIMPLE = inverse diagonal Hessian, "
             "FULL = diagonal of Cholesky-inverted Hessian (reference "
             "DistributedOptimizationProblem.scala:83-103); bare flag = SIMPLE",
    )
    p.add_argument(
        "--model-sparsity-threshold", type=float, default=1e-4,
        help="minimum absolute coefficient value considered nonzero when "
             "persisting a model (reference modelSparsityThreshold, default "
             "VectorUtils.DEFAULT_SPARSITY_THRESHOLD = 1e-4)",
    )
    p.add_argument(
        "--ignore-threshold-for-new-models", action="store_true",
        help="during warm start, entities WITHOUT an existing model bypass "
             "the random-effect active-data lower bound (reference "
             "ignoreThresholdForNewModels; requires --model-input-dir)",
    )
    p.add_argument("--checkpoint-dir", default=None,
                   help="mid-training checkpoint/resume directory (resumes "
                        "automatically when state exists)")
    p.add_argument("--checkpoint-every", type=int, default=1,
                   help="checkpoint cadence in CD iterations")
    p.add_argument("--checkpoint-keep-last", type=int, default=None,
                   help="keep only the newest K step files per checkpoint "
                        "dir (pruned after each save; also pruned before "
                        "the disk-full retry). Default: keep everything, "
                        "or PHOTON_TPU_CHECKPOINT_KEEP_LAST")
    p.add_argument("--resume", action="store_true",
                   help="resume an interrupted run from --checkpoint-dir: "
                        "requires checkpoint state to exist (auto-resume "
                        "merely uses it when present) and keeps the "
                        "existing --output-dir instead of failing on it")
    p.add_argument("--event-listeners", nargs="*", default=[],
                   help="dotted paths of event listener callables "
                        "(Driver.scala:99-108 registration role)")
    p.add_argument("--event-listener", action="append", default=[],
                   dest="event_listener",
                   help="register one event listener by path "
                        "('pkg.module:attr'); repeatable")
    p.add_argument("--telemetry-out", default=None,
                   help="write the unified run report (spans + metrics + "
                        "coordinate-descent diagnostics) as schema-stable "
                        "JSONL to this path")
    p.add_argument("--otlp-endpoint", default=None,
                   help="base URL of an OTLP/HTTP collector accepting JSON; "
                        "CD pass spans and the metrics registry export there "
                        "(bounded queue, drop-and-count on outage — export "
                        "never blocks training)")
    p.add_argument("--otlp-metrics-interval", type=float, default=15.0,
                   help="seconds between registry-snapshot exports (0 = "
                        "spans only)")
    p.add_argument("--summarization-output-dir", default=None,
                   help="write per-feature summary statistics as "
                        "FeatureSummarizationResultAvro, one file per shard "
                        "(ModelProcessingUtils.writeBasicStatistics role)")
    p.add_argument("--feature-index-dir", default=None,
                   help="directory of index-map-<shard>.json files written "
                        "by the feature-indexing driver; skips the distinct "
                        "scan (reference offHeapIndexMapDir role) and is "
                        "required for --stream-ingest-chunk-rows")
    p.add_argument("--stream-ingest-chunk-rows", type=int, default=0,
                   help="read training/validation data through the chunked "
                        "streaming path (host memory bounded by one chunk; "
                        "chunks assemble on the device) instead of the "
                        "slurping reader; needs --feature-index-dir "
                        "(a stream cannot be distinct-scanned first)")
    return p


def run(args) -> Dict:
    setup_logging(args.verbose)
    from photon_tpu.obs import begin_run, finalize_run_report
    from photon_tpu.utils import resources

    begin_run()  # fresh spans / metrics / phase records for THIS run
    from photon_tpu.obs.export import maybe_install_exporter

    otlp = maybe_install_exporter(
        getattr(args, "otlp_endpoint", None), "photon-tpu-training",
        metrics_interval_s=float(
            getattr(args, "otlp_metrics_interval", 0.0) or 0.0
        ),
    )
    # Host RSS watchdog: inert without a detectable limit (cgroup or
    # PHOTON_TPU_RSS_LIMIT_BYTES); under pressure it tightens pipeline queue
    # depths / replay budgets, and the CD pass boundary fails cleanly at the
    # hard level instead of catching the OOM-killer's SIGKILL.
    resources.start_watchdog()
    task = task_of(args)
    from photon_tpu.utils.events import EventEmitter, setup_event

    emitter = EventEmitter()
    for name in list(args.event_listeners) + list(
        getattr(args, "event_listener", [])
    ):
        emitter.register_by_name(name)
    emitter.emit(
        setup_event(
            driver="game_training",
            task=args.task,
            update_sequence=args.update_sequence,
        )
    )

    shard_configs: Dict = {}
    for spec in args.feature_shard_configurations:
        shard_configs.update(parse_feature_shard_config(spec))
    coord_configs = [parse_coordinate_config(s) for s in args.coordinate_configurations]
    update_sequence = [s.strip() for s in args.update_sequence.split(",") if s.strip()]
    by_id = {c.coordinate_id: c for c in coord_configs}
    coord_configs = [by_id[cid] for cid in update_sequence]  # order = sequence

    entity_id_columns = {
        c.re_type: c.re_type
        for c in coord_configs
        if hasattr(c, "re_type")
    }

    from photon_tpu.cli.common import parse_input_column_names, resolve_input_paths
    from photon_tpu.data.validators import DataValidationType, validate_game_batch
    from photon_tpu.utils.io_utils import process_output_dir

    column_names = parse_input_column_names(
        getattr(args, "input_column_names", None)
    )
    if args.resume:
        # Explicit resume: checkpoint state must exist (a typo'd dir must
        # not silently start over), and the half-written output dir of the
        # interrupted run is expected — keep it (override would DELETE it,
        # and the checkpoint dir often lives inside).
        from photon_tpu.utils.checkpoint import latest_step

        if not args.checkpoint_dir:
            raise SystemExit("--resume requires --checkpoint-dir")
        # The estimator checkpoints each sweep config under cfg_<i>/; state
        # in ANY of them (or directly in the dir, for older layouts) counts.
        cfg_dirs = [args.checkpoint_dir] + sorted(
            os.path.join(args.checkpoint_dir, d)
            for d in (os.listdir(args.checkpoint_dir)
                      if os.path.isdir(args.checkpoint_dir) else [])
            if d.startswith("cfg_")
        )
        if all(latest_step(d) is None for d in cfg_dirs):
            raise SystemExit(
                f"--resume: no checkpoint state under {args.checkpoint_dir}"
            )
        os.makedirs(args.output_dir, exist_ok=True)
    else:
        process_output_dir(args.output_dir, args.override_output_dir)

    # Pre-built index maps (feature-indexing driver output; reference
    # offHeapIndexMapDir role). Mandatory for streaming ingest — a stream
    # cannot be distinct-scanned first.
    preloaded_maps = None
    if args.feature_index_dir:
        preloaded_maps = {}
        for shard in shard_configs:
            path = os.path.join(
                args.feature_index_dir, f"index-map-{shard}.json"
            )
            try:
                preloaded_maps[shard] = IndexMap.load(path)
            except OSError as exc:
                raise SystemExit(
                    f"--feature-index-dir: cannot read {path} ({exc}); "
                    "expected index-map-<shard>.json files as written by "
                    "the feature-indexing driver, one per configured "
                    f"feature shard ({sorted(shard_configs)})"
                ) from exc
    chunk_rows = int(getattr(args, "stream_ingest_chunk_rows", 0) or 0)
    if chunk_rows > 0 and preloaded_maps is None:
        raise SystemExit(
            "--stream-ingest-chunk-rows requires --feature-index-dir "
            "(run the feature-indexing driver first)"
        )

    def read(paths, index_maps, entity_indexes, intern_new):
        if chunk_rows > 0:
            # Pipelined ingest (io/pipeline.py): decode → assemble → h2d on
            # worker threads with bounded queues, so each chunk's host work
            # overlaps earlier chunks' device placement; unpadded chunks
            # concatenate into one device-resident batch.
            from photon_tpu.io.data_reader import concat_game_batches
            from photon_tpu.io.pipeline import stream_device_batches

            eidx = entity_indexes if entity_indexes is not None else {}
            try:
                chunks = list(stream_device_batches(
                    paths, shard_configs, index_maps,
                    entity_id_columns=entity_id_columns, entity_indexes=eidx,
                    intern_new_entities=intern_new, chunk_rows=chunk_rows,
                    column_names=column_names,
                    telemetry_label="game-train-ingest",
                ))
            except (RuntimeError, ValueError) as exc:
                # Streaming never silently slurps (the user asked for
                # bounded host memory) — fail with actionable guidance.
                raise SystemExit(
                    f"streaming ingest unavailable for {paths}: {exc}; "
                    "drop --stream-ingest-chunk-rows to use the row-codec "
                    "fallback reader"
                ) from exc
            if not chunks:
                raise SystemExit(
                    f"streaming ingest read zero data blocks from {paths}"
                )
            return concat_game_batches([c.batch for c in chunks]), index_maps, eidx
        return read_merged(
            paths, shard_configs, index_maps=index_maps,
            entity_id_columns=entity_id_columns, entity_indexes=entity_indexes,
            intern_new_entities=intern_new, column_names=column_names,
        )

    with Timed("driver/read-train"):
        batch, index_maps, entity_indexes = read(
            resolve_input_paths(args), preloaded_maps, None, True
        )
    # Row-level sanity checks on train + validation data
    # (GameTrainingDriver.scala:415-432).
    validation_mode = DataValidationType[args.data_validation]
    validate_game_batch(batch, task, validation_mode)
    valid_batch = None
    if args.validation_paths:
        with Timed("driver/read-validation"):
            valid_batch, _, _ = read(
                args.validation_paths, index_maps, entity_indexes, False
            )
        validate_game_batch(valid_batch, task, validation_mode)

    # Feature stats + normalization per shard (GameTrainingDriver.scala:434-440).
    intercept_indices = {
        shard: index_maps[shard].get_index(IndexMap.INTERCEPT)
        for shard in shard_configs
        if index_maps[shard].get_index(IndexMap.INTERCEPT) >= 0
    }
    normalization = {}
    norm_type = NormalizationType[args.normalization]
    if norm_type != NormalizationType.NONE or args.summarization_output_dir:
        for shard in shard_configs:
            stats = compute_feature_stats(
                batch.labeled_batch(shard), intercept_indices.get(shard)
            )
            if norm_type != NormalizationType.NONE:
                normalization[shard] = build_normalization_context(
                    norm_type, stats.mean, stats.std, stats.abs_max,
                    intercept_indices.get(shard),
                )
            if args.summarization_output_dir:
                from photon_tpu.io.model_io import write_basic_statistics

                write_basic_statistics(
                    stats, index_maps[shard],
                    os.path.join(
                        args.summarization_output_dir, shard, "part-00000.avro"
                    ),
                )

    # Per-feature constraint maps → per-coordinate bound vectors
    # (GLMSuite.scala:49-126 semantics, GAME-side extension).
    if args.coordinate_constraints:
        import dataclasses as _dc

        from photon_tpu.data.constraints import constraint_bound_vectors
        from photon_tpu.estimators.config import FixedEffectCoordinateConfig

        cmap = json.loads(args.coordinate_constraints)
        unknown = set(cmap) - {c.coordinate_id for c in coord_configs}
        if unknown:
            raise ValueError(f"constraints for unknown coordinates: {sorted(unknown)}")
        for i, c in enumerate(coord_configs):
            entries = cmap.get(c.coordinate_id)
            if entries is None:
                continue
            if not isinstance(c, FixedEffectCoordinateConfig):
                raise ValueError(
                    f"coordinate constraints apply to fixed-effect coordinates "
                    f"only; '{c.coordinate_id}' is a random-effect coordinate"
                )
            bounds = constraint_bound_vectors(
                json.dumps(entries),
                index_maps[c.feature_shard],
                batch.features[c.feature_shard].shape[1],
                intercept_indices.get(c.feature_shard),
            )
            if bounds is not None:
                coord_configs[i] = _dc.replace(
                    c, box=(jnp.asarray(bounds[0]), jnp.asarray(bounds[1]))
                )

    warm = None
    if args.model_input_dir:
        warm = load_game_model(args.model_input_dir, index_maps, entity_indexes)

    num_entities = {k: len(v) for k, v in entity_indexes.items()}
    suite = EvaluationSuite(
        [EvaluatorSpec.parse(e) for e in args.evaluators], num_entities
    ) if args.evaluators else None

    estimator = GameEstimator(
        task=task,
        coordinate_configs=coord_configs,
        num_iterations=args.coordinate_descent_iterations,
        intercept_indices=intercept_indices,
        normalization=normalization,
        num_entities=num_entities,
        locked_coordinates=[s for s in args.locked_coordinates.split(",") if s],
        variance_computation=args.variance_computation,
        ignore_threshold_for_new_models=args.ignore_threshold_for_new_models,
        warm_start_model=warm,
        re_active_set=args.re_active_set,
        re_convergence_tol=args.re_convergence_tol,
        re_device_budget_mb=args.re_device_budget_mb,
        re_spill_dir=args.re_spill_dir,
        re_spill_member=args.re_spill_member,
    )
    from photon_tpu.utils.events import training_finish_event, training_start_event

    emitter.emit(
        training_start_event(
            task=task.value, coordinates=list(update_sequence)
        )
    )
    from photon_tpu.utils.shutdown import GracefulShutdown, handle_termination

    try:
        with handle_termination():
            results = estimator.fit(
                batch,
                validation_batch=valid_batch,
                evaluation_suite=suite if valid_batch is not None else None,
                initial_model=warm,
                checkpoint_dir=args.checkpoint_dir,
                checkpoint_every=args.checkpoint_every,
                checkpoint_keep_last=args.checkpoint_keep_last,
                emitter=emitter,
            )
    except GracefulShutdown as exc:
        # The CD loop already wrote a final pass-boundary checkpoint;
        # finalize telemetry so the interrupted run still reports, then
        # exit with the conventional killed-by-signal code.
        finalize_run_report(
            "game_training", path=args.telemetry_out, emitter=emitter
        )
        if otlp is not None:
            from photon_tpu.obs.export import uninstall_exporter

            try:
                otlp.export_metrics()
                otlp.flush(timeout_s=3.0)
            except Exception:  # noqa: BLE001
                pass
            uninstall_exporter()
        raise SystemExit(128 + exc.signum) from exc

    # --- hyperparameter auto-tuning (runHyperparameterTuning role,
    # reference GameTrainingDriver.scala:651-692) ---
    tuned_results = []
    if args.hyper_parameter_tuning != "NONE":
        tuned_results = _run_hyperparameter_tuning(
            args, estimator, results, batch, valid_batch, suite
        )

    os.makedirs(args.output_dir, exist_ok=True)
    summary = {"configs": [], "tuned_configs": [], "best": None}

    def _select(candidates):
        if not candidates:
            return None
        if suite is not None and valid_batch is not None:
            return estimator.select_best(candidates, suite)
        return candidates[-1]

    # Model selection across explicit + tuned (selectModels role,
    # GameTrainingDriver.scala:701-766): EXPLICIT/TUNED restrict the pool.
    if args.output_mode == "EXPLICIT":
        best = _select(results)
    elif args.output_mode == "TUNED":
        best = _select(tuned_results)
        if best is None:
            raise ValueError(
                "--output-mode TUNED requires --hyper-parameter-tuning with "
                "at least one successful tuning iteration"
            )
    else:
        best = _select(results + tuned_results)

    for key, pool in (("configs", results), ("tuned_configs", tuned_results)):
        for i, r in enumerate(pool):
            summary[key].append({"config": r.config.describe(), "metrics": r.metrics})
            if args.output_mode == "ALL":
                save_game_model(
                    r.model,
                    os.path.join(args.output_dir, "models", f"{key}-{i}"),
                    index_maps, entity_indexes,
                    sparsity_threshold=args.model_sparsity_threshold,
                )
    if args.output_mode != "NONE":
        save_game_model(
            best.model, os.path.join(args.output_dir, "best"),
            index_maps, entity_indexes,
            sparsity_threshold=args.model_sparsity_threshold,
            extra_metadata={"config": best.config.describe()},
        )
        for shard, imap in index_maps.items():
            imap.save(os.path.join(args.output_dir, f"index-map-{shard}.json"))
        for re_type, eidx in entity_indexes.items():
            eidx.save(os.path.join(args.output_dir, f"entity-index-{re_type}.json"))
        # Artifacts are on disk; NOW flip the fsync'd LATEST pointer so a
        # polling game_serving (--reload-poll-interval) hot-swaps a fully
        # written generation, never a partial one.
        publish_latest_pointer(args.output_dir, "best")
    summary["best"] = {"config": best.config.describe(), "metrics": best.metrics}
    with open(os.path.join(args.output_dir, "training-summary.json"), "w") as f:
        # Non-finite metrics (e.g. AIC at the n−k−1=0 pole) become null:
        # the bare token Infinity is not RFC-8259 JSON.
        json.dump(sanitize_for_json(summary), f, indent=2)
    emitter.emit(
        training_finish_event(best=None if best is None else best.config.describe())
    )
    finalize_run_report(
        "game_training",
        path=args.telemetry_out,
        emitter=emitter,
        trackers=[
            {
                "label": f"{key}[{i}]",
                "tracker": r.tracker,
                "wall_times": r.wall_times,
            }
            for key, pool in (
                ("config", results), ("tuned", tuned_results)
            )
            for i, r in enumerate(pool)
        ],
    )
    if otlp is not None:
        from photon_tpu.obs.export import uninstall_exporter

        try:
            otlp.export_metrics()
            otlp.flush(timeout_s=3.0)
        except Exception:  # noqa: BLE001 — export is best-effort at exit
            pass
        uninstall_exporter()
    return summary


def _run_hyperparameter_tuning(args, estimator, results, batch, valid_batch, suite):
    """Bayesian/random search over regularization hyperparameters, seeded
    with the explicit grid as prior observations."""
    import logging

    from photon_tpu.estimators.evaluation_function import (
        GameEstimatorEvaluationFunction,
    )
    from photon_tpu.hyperparameter.serialization import observations_to_json
    from photon_tpu.hyperparameter.tuner import TunerName, TuningMode, get_tuner

    logger = logging.getLogger("photon_tpu.driver")
    if valid_batch is None or suite is None:
        raise ValueError(
            "--hyper-parameter-tuning requires --validation-paths and "
            "--evaluators (the tuner optimizes the primary validation metric)"
        )
    base_config = results[0].config
    is_opt_max = suite.primary.better()(1.0, 0.0)
    fn = GameEstimatorEvaluationFunction(
        estimator, base_config, batch, valid_batch, suite, is_opt_max
    )
    if fn.dim == 0:
        logger.warning(
            "hyperparameter tuning requested but no coordinate is "
            "regularized in the base configuration; skipping"
        )
        return []
    tuner = get_tuner(TunerName[args.hyper_parameter_tuner])
    with Timed(f"driver/hyperparameter-tuning[{args.hyper_parameter_tuning}]"):
        _best_x, _best_v, observations = tuner.search(
            args.hyper_parameter_tuning_iter,
            fn.dim,
            TuningMode[args.hyper_parameter_tuning],
            fn,
            search_range=fn.search_range,
            prior_observations=fn.convert_observations(results),
            batch_size=args.hyper_parameter_batch_size,
        )
    if _best_x is not None and not fn.results:
        # The batched fast path evaluates metrics without materializing
        # models; one sequential fit of the winning candidate gives the
        # TUNED output mode a model to save.
        fn(np.asarray(_best_x))
    os.makedirs(args.output_dir, exist_ok=True)
    with open(
        os.path.join(args.output_dir, "hyperparameter-observations.json"), "w"
    ) as f:
        f.write(observations_to_json(observations, fn.names))
    logger.info(
        "hyperparameter tuning: %d candidates evaluated, observations saved",
        len(fn.results),
    )
    return fn.results


def main(argv=None):
    args = build_parser().parse_args(argv)
    configure_compile_cache()
    summary = run(args)
    print(json.dumps(summary["best"]))


if __name__ == "__main__":
    main()
