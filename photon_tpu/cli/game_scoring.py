"""GAME scoring driver.

Parity target: reference ``GameScoringDriver`` (photon-client
cli/game/scoring/GameScoringDriver.scala:39-284): feature maps → read data →
load GameModel → GameTransformer → save ScoringResultAvro (+ optional
evaluation).
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
    parse_feature_shard_config,
    setup_logging,
)
from photon_tpu.data.index_map import EntityIndex, IndexMap
from photon_tpu.estimators.game_transformer import GameTransformer
from photon_tpu.evaluation.suite import EvaluationSuite, EvaluatorSpec
from photon_tpu.io.data_reader import read_merged
from photon_tpu.io.model_io import (
    load_game_model,
    model_re_types,
    read_model_metadata,
)
from photon_tpu.io.scores import save_scores


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("game-scoring")
    add_common_args(p)
    p.add_argument("--model-input-dir", required=True)
    p.add_argument("--model-artifacts-dir", default=None,
                   help="dir holding index-map-*.json / entity-index-*.json "
                        "(defaults to the training output dir = parent of model dir)")
    p.add_argument("--evaluators", nargs="*", default=[])
    p.add_argument("--model-id", default="game-model")
    p.add_argument("--stream-ingest-chunk-rows", type=int, default=0,
                   help="score through the chunked streaming reader: host "
                        "memory bounded by one chunk of features (scores/"
                        "labels/ids accumulate — they are O(n) scalars); "
                        "chunks pad to a multiple of this (sparse nnz "
                        "widths bucket to powers of two) so the scoring "
                        "program compiles for a handful of shapes, not one "
                        "per chunk")
    p.add_argument("--ingest-queue-depth", type=int, default=None,
                   help="bound (in chunks) on each inter-stage pipeline "
                        "queue (default: measured double-buffering depth, "
                        "io/pipeline.py)")
    p.add_argument("--serial-ingest", action="store_true",
                   help="run the ingest stages inline on the consumer "
                        "thread instead of on pipeline worker threads "
                        "(the pre-pipeline behavior; the bench A/B control)")
    p.add_argument("--event-listeners", nargs="*", default=[],
                   help="dotted paths of event listener callables")
    p.add_argument("--event-listener", action="append", default=[],
                   dest="event_listener",
                   help="register one event listener by path "
                        "('pkg.module:attr'); repeatable")
    p.add_argument("--telemetry-out", default=None,
                   help="write the unified run report (spans + metrics + "
                        "ingest-pipeline occupancy) as schema-stable JSONL "
                        "to this path")
    from photon_tpu.cli.common import add_active_set_args, add_out_of_core_args

    add_active_set_args(p)
    add_out_of_core_args(p)
    return p


def run(args) -> Dict:
    setup_logging(args.verbose)
    if getattr(args, "re_active_set", False):
        import logging

        logging.getLogger(__name__).warning(
            "--re-active-set is a no-op for the scoring driver (nothing is "
            "trained); it only affects GAME training"
        )
    if getattr(args, "re_device_budget_mb", None):
        import logging

        logging.getLogger(__name__).warning(
            "--re-device-budget-mb is a no-op for the scoring driver "
            "(nothing is trained); it only affects GAME training"
        )
    from photon_tpu.obs import begin_run, finalize_run_report
    from photon_tpu.utils.events import (
        EventEmitter,
        setup_event,
        training_finish_event,
    )

    begin_run()  # fresh spans / metrics / phase records for THIS run
    emitter = EventEmitter()
    for name in list(getattr(args, "event_listeners", [])) + list(
        getattr(args, "event_listener", [])
    ):
        emitter.register_by_name(name)
    emitter.emit(
        setup_event(driver="game_scoring", model_input_dir=args.model_input_dir)
    )
    shard_configs: Dict = {}
    for spec in args.feature_shard_configurations:
        shard_configs.update(parse_feature_shard_config(spec))

    artifacts = args.model_artifacts_dir or os.path.dirname(
        args.model_input_dir.rstrip("/")
    )
    index_maps = {}
    for shard in shard_configs:
        index_maps[shard] = IndexMap.load(
            os.path.join(artifacts, f"index-map-{shard}.json")
        )
    entity_indexes: Dict[str, EntityIndex] = {}
    re_types = model_re_types(read_model_metadata(args.model_input_dir))
    for re_type in re_types:
        path = os.path.join(artifacts, f"entity-index-{re_type}.json")
        if os.path.exists(path):
            entity_indexes[re_type] = EntityIndex.load(path)

    model = load_game_model(args.model_input_dir, index_maps, entity_indexes)

    from photon_tpu.cli.common import parse_input_column_names, resolve_input_paths
    from photon_tpu.utils.io_utils import process_output_dir

    process_output_dir(args.output_dir, args.override_output_dir)
    column_names = parse_input_column_names(
        getattr(args, "input_column_names", None)
    )
    read_kwargs = dict(
        entity_id_columns={rt: rt for rt in re_types},
        entity_indexes=entity_indexes, intern_new_entities=False,
        column_names=column_names,
    )

    suite = None
    if args.evaluators:
        num_entities = {k: len(v) for k, v in entity_indexes.items()}
        suite = EvaluationSuite(
            [EvaluatorSpec.parse(e) for e in args.evaluators], num_entities
        )

    chunk_rows = int(getattr(args, "stream_ingest_chunk_rows", 0) or 0)
    if chunk_rows > 0:
        # Streaming: decode → assemble → h2d run as pipeline stages
        # (io/pipeline.py; worker threads + bounded queues unless
        # --serial-ingest) overlapping the jitted scorer via async dispatch.
        # Feature chunks are scored and dropped; only the O(n)-scalar
        # columns (scores/labels/weights/uids/entity ids) accumulate.
        # Chunks pad to a chunk_rows multiple so the jitted scoring program
        # compiles for at most a couple of shapes.
        import time

        from photon_tpu.data.game_data import GameBatch
        from photon_tpu.io.pipeline import (
            DEFAULT_QUEUE_DEPTH,
            stream_device_batches,
        )
        from photon_tpu.utils.timed import PipelineStats

        transformer = GameTransformer(model, None)
        acc: Dict[str, list] = {
            "scores": [], "label": [], "weight": [], "uid": [],
            **{rt: [] for rt in re_types},
        }
        overlap = not getattr(args, "serial_ingest", False)
        stats = PipelineStats(overlapped=overlap)
        compute = stats.stage("compute")
        gen = stream_device_batches(
            resolve_input_paths(args), shard_configs, index_maps,
            chunk_rows=chunk_rows, pad_rows_to=chunk_rows,
            depth=getattr(args, "ingest_queue_depth", None)
            or DEFAULT_QUEUE_DEPTH,
            overlap=overlap, telemetry_label="scoring-ingest", stats=stats,
            **read_kwargs,
        )
        while True:
            # Only the STREAM can be "unavailable" — scoring errors must
            # surface as themselves, not as advice to drop the flag.
            try:
                chunk = next(gen)
            except StopIteration:
                break
            except (RuntimeError, ValueError) as exc:
                raise SystemExit(
                    f"streaming ingest unavailable: {exc}; drop "
                    "--stream-ingest-chunk-rows to use the slurping reader"
                ) from exc
            n, b = chunk.n, chunk.batch
            t0 = time.perf_counter()
            s = transformer.transform(b)
            scores_np = np.asarray(s)  # blocks: device compute wall
            compute.add_busy(time.perf_counter() - t0)
            acc["scores"].append(scores_np[:n])
            acc["label"].append(np.asarray(b.label)[:n])
            acc["weight"].append(np.asarray(b.weight)[:n])
            # uids were renumbered globally by the assemble stage, so
            # scores.avro matches the slurp path's UniqueSampleId sequence.
            acc["uid"].append(np.asarray(b.uid)[:n])
            for rt in re_types:
                acc[rt].append(np.asarray(b.entity_ids[rt])[:n])
        if not acc["scores"]:
            raise SystemExit("streaming ingest read zero data blocks")
        scores = np.concatenate(acc["scores"])
        labels = np.concatenate(acc["label"])
        weights = np.concatenate(acc["weight"])
        uid_arr = np.concatenate(acc["uid"])
        metrics = None
        if suite is not None:
            eval_batch = GameBatch(
                label=jnp.asarray(labels),
                offset=jnp.zeros(len(labels), jnp.float32),
                weight=jnp.asarray(weights),
                features={},
                entity_ids={rt: jnp.asarray(np.concatenate(acc[rt]))
                            for rt in re_types},
            )
            metrics = suite.evaluate_scores(jnp.asarray(scores), eval_batch)
        pipeline_summary = stats.summary()
    else:
        batch, _, _ = read_merged(
            resolve_input_paths(args), shard_configs, index_maps=index_maps,
            **read_kwargs,
        )
        transformer = GameTransformer(model, suite)
        scores = np.asarray(transformer.transform(batch))
        labels = np.asarray(batch.label)
        weights = np.asarray(batch.weight)
        uid_arr = np.asarray(batch.uid)
        metrics = transformer.last_metrics if suite is not None else None
        pipeline_summary = None

    os.makedirs(args.output_dir, exist_ok=True)
    save_scores(
        os.path.join(args.output_dir, "scores.avro"),
        scores,
        args.model_id,
        uids=[str(int(u)) for u in uid_arr],
        labels=labels,
        weights=weights,
    )
    out = {"numScored": int(scores.shape[0])}
    if pipeline_summary is not None:
        out["ingestPipeline"] = pipeline_summary
    if metrics is not None:
        out["metrics"] = metrics
        with open(os.path.join(args.output_dir, "scoring-metrics.json"), "w") as f:
            json.dump(metrics, f, indent=2)
    emitter.emit(training_finish_event(num_scored=out["numScored"]))
    finalize_run_report(
        "game_scoring", path=args.telemetry_out, emitter=emitter
    )
    return out


def main(argv=None):
    args = build_parser().parse_args(argv)
    configure_compile_cache()
    print(json.dumps(run(args)))


if __name__ == "__main__":
    main()
