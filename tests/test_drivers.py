"""Driver end-to-end tests: full CLI paths against fixture Avro on local FS.

Mirrors the reference's GameTrainingDriverIntegTest /
GameScoringDriverIntegTest / DriverTest (SURVEY.md §4 driver E2E tests).
"""

import json
import os

import numpy as np
import pytest

from photon_tpu.cli import feature_indexing, game_scoring, game_training, train_glm
from photon_tpu.io.avro import write_avro_records
from photon_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA

rng = np.random.default_rng(23)


def write_fixture(path, n=400, d=6, n_users=8, seed_shift=0.0, block_records=None):
    """Synthetic logistic GLMix data as TrainingExampleAvro."""
    w = np.linspace(-1, 1, d)
    user_bias = np.linspace(-2, 2, n_users)
    records = []
    for i in range(n):
        x = rng.normal(size=d)
        u = i % n_users
        logit = x @ w + user_bias[u] + seed_shift
        y = float(rng.uniform() < 1 / (1 + np.exp(-logit)))
        records.append(
            {
                "uid": str(i),
                "label": y,
                "features": [
                    {"name": f"x{j}", "term": "", "value": float(x[j])} for j in range(d)
                ],
                "metadataMap": {"userId": f"u{u}"},
                "weight": 1.0,
                "offset": 0.0,
            }
        )
    kw = {} if block_records is None else {"block_records": block_records}
    write_avro_records(path, TRAINING_EXAMPLE_SCHEMA, records, **kw)


@pytest.fixture(scope="module")
def fixture_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("fixtures")
    write_fixture(str(d / "train.avro"))
    write_fixture(str(d / "valid.avro"), n=200)
    return d


def test_game_training_and_scoring_drivers(fixture_dir, tmp_path):
    out = tmp_path / "out"
    args = game_training.build_parser().parse_args(
        [
            "--input-paths", str(fixture_dir / "train.avro"),
            "--validation-paths", str(fixture_dir / "valid.avro"),
            "--output-dir", str(out),
            "--feature-shard-configurations", "name=globalShard",
            "--coordinate-configurations",
            "name=global,feature.shard=globalShard,optimizer=LBFGS,reg.weights=1|10",
            "name=perUser,feature.shard=globalShard,random.effect.type=userId,reg.weights=1",
            "--update-sequence", "global,perUser",
            "--evaluators", "AUC", "LOGISTIC_LOSS",
        ]
    )
    summary = game_training.run(args)
    assert len(summary["configs"]) == 2  # reg-weight sweep: 2 λ points
    assert summary["best"]["metrics"]["AUC"] > 0.7
    assert (out / "best" / "model-metadata.json").exists()
    assert (out / "index-map-globalShard.json").exists()
    assert (out / "entity-index-userId.json").exists()
    # Publication contract: the fsync'd LATEST pointer names the final
    # generation, so a polling game_serving picks the model up unattended.
    assert (out / "LATEST").read_text().strip() == "best"

    # Scoring driver consumes the training output.
    score_out = tmp_path / "scores"
    sargs = game_scoring.build_parser().parse_args(
        [
            "--input-paths", str(fixture_dir / "valid.avro"),
            "--output-dir", str(score_out),
            "--feature-shard-configurations", "name=globalShard",
            "--model-input-dir", str(out / "best"),
            "--model-artifacts-dir", str(out),
            "--evaluators", "AUC",
        ]
    )
    result = game_scoring.run(sargs)
    assert result["numScored"] == 200
    assert result["metrics"]["AUC"] > 0.7
    assert (score_out / "scores.avro").exists()


def test_warm_start_and_locked_coordinates(fixture_dir, tmp_path):
    out1 = tmp_path / "m1"
    base = [
        "--input-paths", str(fixture_dir / "train.avro"),
        "--feature-shard-configurations", "name=s",
        "--update-sequence", "global",
        "--evaluators",
    ]
    args = game_training.build_parser().parse_args(
        base[:2] + ["--output-dir", str(out1)] + base[2:] + [
            "--coordinate-configurations",
            "name=global,feature.shard=s,reg.weights=1",
        ]
    )
    game_training.run(args)
    # Warm start from the saved model.
    out2 = tmp_path / "m2"
    args2 = game_training.build_parser().parse_args(
        base[:2] + ["--output-dir", str(out2)] + base[2:] + [
            "--coordinate-configurations",
            "name=global,feature.shard=s,reg.weights=1",
            "--model-input-dir", str(out1 / "best"),
        ]
    )
    summary = game_training.run(args2)
    assert summary["configs"]


def test_legacy_glm_driver_libsvm(tmp_path):
    # a1a-style LIBSVM fixture (README demo workload shape).
    libsvm = tmp_path / "train.txt"
    lines = []
    w = np.array([1.5, -2.0, 0.5, 1.0])
    for i in range(300):
        x = rng.normal(size=4)
        y = 1 if rng.uniform() < 1 / (1 + np.exp(-x @ w)) else -1
        feats = " ".join(f"{j+1}:{x[j]:.4f}" for j in range(4))
        lines.append(f"{y:+d} {feats}")
    libsvm.write_text("\n".join(lines))
    out = tmp_path / "glm-out"
    args = train_glm.build_parser().parse_args(
        [
            "--training-data", str(libsvm),
            "--validation-data", str(libsvm),
            "--format", "libsvm",
            "--output-dir", str(out),
            "--regularization-weights", "0.1,1,10",
            "--optimizer", "TRON",
            "--telemetry-out", str(tmp_path / "run.jsonl"),
        ]
    )
    summary = train_glm.run(args)
    assert summary["stage"] == "VALIDATED"
    # Each λ's TRON solve reports its passes over X: the start, 5 an outer
    # iteration, 2 a CG product, and the solve program's score pass.
    rows = [r["diagnostics"] for r in map(
        json.loads, (tmp_path / "run.jsonl").read_text().splitlines())
        if r["record"] == "coordinate_descent"]
    assert len(rows) == 3
    for d in rows:
        assert d["eval_unit"] == "x_passes"
        assert d["evals"] == 3 + 5 * d["iterations"] + 2 * d["cg_steps"]
    assert len(summary["models"]) == 3
    # Best model by AUC present + text model files written.
    assert any(f.startswith("model-lambda-") for f in os.listdir(out))
    assert (out / "best" / "model-metadata.json").exists()
    assert (out / "LATEST").read_text().strip() == "best"
    aucs = [m["validation"]["Area under ROC"] for m in summary["models"]]
    assert max(aucs) > 0.75


def test_legacy_driver_elastic_net_sparsity(tmp_path):
    libsvm = tmp_path / "t.txt"
    lines = []
    for i in range(200):
        x = rng.normal(size=10)
        y = 1 if rng.uniform() < 1 / (1 + np.exp(-(2 * x[0] - 1.5 * x[1]))) else -1
        feats = " ".join(f"{j+1}:{x[j]:.4f}" for j in range(10))
        lines.append(f"{y:+d} {feats}")
    libsvm.write_text("\n".join(lines))
    out = tmp_path / "o"
    args = train_glm.build_parser().parse_args(
        [
            "--training-data", str(libsvm), "--format", "libsvm",
            "--output-dir", str(out),
            "--regularization-weights", "5",
            "--elastic-net-alpha", "1.0",
        ]
    )
    train_glm.run(args)
    # L1 must have zeroed most noise coefficients in the text model.
    (model_file,) = [f for f in os.listdir(out) if f.startswith("model-lambda-")]
    nnz = sum(1 for line in open(out / model_file) if not line.startswith("#"))
    assert nnz <= 6


def test_feature_indexing_driver(fixture_dir, tmp_path):
    out = tmp_path / "idx"
    args = feature_indexing.build_parser().parse_args(
        [
            "--input-paths", str(fixture_dir / "train.avro"),
            "--output-dir", str(out),
            "--feature-shard-configurations", "name=g",
            "--num-partitions", "3",
        ]
    )
    result = feature_indexing.run(args)
    assert result["g"] == 7  # 6 features + intercept
    from photon_tpu.data.native_index import NativeIndexMap

    nim = NativeIndexMap(str(out / "index-store-g"))
    assert len(nim) == 7
    assert nim.get_index("x0") >= 0
    nim.close()


def test_game_training_hyperparameter_tuning(fixture_dir, tmp_path):
    """BAYESIAN tuning on a deliberately-bad explicit grid must find a
    better λ and TUNED output mode must save the tuned best."""
    out = tmp_path / "tuned"
    args = game_training.build_parser().parse_args(
        [
            "--input-paths", str(fixture_dir / "train.avro"),
            "--validation-paths", str(fixture_dir / "valid.avro"),
            "--output-dir", str(out),
            "--feature-shard-configurations", "name=globalShard",
            "--coordinate-configurations",
            # Far-too-strong regularization: the grid underfits badly.
            "name=global,feature.shard=globalShard,optimizer=LBFGS,reg.weights=2000|5000",
            "--update-sequence", "global",
            "--evaluators", "AUC",
            "--hyper-parameter-tuning", "BAYESIAN",
            "--hyper-parameter-tuning-iter", "6",
            "--output-mode", "TUNED",
        ]
    )
    summary = game_training.run(args)
    assert len(summary["configs"]) == 2
    assert len(summary["tuned_configs"]) == 6
    best_grid = max(c["metrics"]["AUC"] for c in summary["configs"])
    best_tuned = max(c["metrics"]["AUC"] for c in summary["tuned_configs"])
    assert best_tuned > best_grid  # tuning beat the explicit grid
    assert summary["best"]["metrics"]["AUC"] == best_tuned
    assert (out / "best" / "model-metadata.json").exists()
    # Search history persisted in prior-observation format.
    obs_path = out / "hyperparameter-observations.json"
    assert obs_path.exists()
    records = json.loads(obs_path.read_text())["records"]
    assert len(records) == 2 + 6  # grid priors + tuned candidates
    assert all("global.weight" in r and "evaluationValue" in r for r in records)


def test_summarization_output(fixture_dir, tmp_path):
    """--summarization-output-dir writes FeatureSummarizationResultAvro
    readable by the from-spec codec (writeBasicStatistics role,
    ModelProcessingUtils.scala:516)."""
    from photon_tpu.io.avro import read_avro_records

    out = tmp_path / "out"
    summ = tmp_path / "summ"
    args = game_training.build_parser().parse_args(
        [
            "--input-paths", str(fixture_dir / "train.avro"),
            "--output-dir", str(out),
            "--feature-shard-configurations", "name=s",
            "--coordinate-configurations", "name=global,feature.shard=s,reg.weights=1",
            "--update-sequence", "global",
            "--evaluators",
            "--summarization-output-dir", str(summ),
        ]
    )
    game_training.run(args)
    recs = read_avro_records(str(summ / "s" / "part-00000.avro"))
    by_name = {r["featureName"]: r["metrics"] for r in recs}
    assert "x0" in by_name and "(INTERCEPT)" in by_name
    m = by_name["x0"]
    assert set(m) == {"mean", "variance", "min", "max", "normL1", "normL2", "numNonzeros"}
    assert m["max"] >= m["min"]
    assert by_name["(INTERCEPT)"]["mean"] == pytest.approx(1.0)
    assert m["numNonzeros"] > 0


def test_game_training_with_normalization(fixture_dir, tmp_path):
    """GAME CLI with --normalization STANDARDIZATION: stats → contexts →
    folded solves → model-space models (r4 conversion contract). Completes
    with an AUC comparable to the unnormalized run on the same data."""
    out_plain = tmp_path / "plain"
    out_norm = tmp_path / "norm"
    common = [
        "--input-paths", str(fixture_dir / "train.avro"),
        "--validation-paths", str(fixture_dir / "valid.avro"),
        "--feature-shard-configurations", "name=globalShard",
        "--coordinate-configurations",
        "name=global,feature.shard=globalShard,optimizer=LBFGS,reg.weights=1",
        "name=perUser,feature.shard=globalShard,random.effect.type=userId,reg.weights=1",
        "--update-sequence", "global,perUser",
        "--evaluators", "AUC",
    ]
    aucs = {}
    for out, extra in ((out_plain, []),
                       (out_norm, ["--normalization", "STANDARDIZATION"])):
        args = game_training.build_parser().parse_args(
            common + ["--output-dir", str(out)] + extra
        )
        summary = game_training.run(args)
        aucs[str(out)] = summary["best"]["metrics"]["AUC"]
    plain, norm = aucs[str(out_plain)], aucs[str(out_norm)]
    assert norm > 0.7, aucs
    # Same data, mild regularization: folded-normalized fit must be in the
    # same quality class (the pre-fix bug scored transformed-space w on raw
    # features, cratering this).
    assert abs(norm - plain) < 0.05, aucs


def test_game_training_streaming_ingest(fixture_dir, tmp_path):
    """--stream-ingest-chunk-rows + --feature-index-dir: the chunked
    host-bounded read path must train to the same result as the slurp
    (reference offHeapIndexMapDir + per-partition read flow)."""
    from photon_tpu.io.columnar import _load_lib

    if _load_lib() is None:
        pytest.skip("native decoder unavailable")

    # Stage 1: feature indexing (writes index-map-<shard>.json).
    idx_dir = tmp_path / "fidx"
    fargs = feature_indexing.build_parser().parse_args(
        [
            "--input-paths", str(fixture_dir / "train.avro"),
            "--output-dir", str(idx_dir),
            "--feature-shard-configurations", "name=globalShard",
        ]
    )
    feature_indexing.run(fargs)

    common = [
        "--validation-paths", str(fixture_dir / "valid.avro"),
        "--feature-shard-configurations", "name=globalShard",
        "--coordinate-configurations",
        "name=global,feature.shard=globalShard,optimizer=LBFGS,reg.weights=1",
        "name=perUser,feature.shard=globalShard,random.effect.type=userId,reg.weights=1",
        "--update-sequence", "global,perUser",
        "--evaluators", "AUC",
    ]
    out_stream = tmp_path / "out_stream"
    sargs = game_training.build_parser().parse_args(
        ["--input-paths", str(fixture_dir / "train.avro"),
         "--output-dir", str(out_stream),
         "--feature-index-dir", str(idx_dir),
         "--stream-ingest-chunk-rows", "128"] + common
    )
    s_stream = game_training.run(sargs)

    out_slurp = tmp_path / "out_slurp"
    aargs = game_training.build_parser().parse_args(
        ["--input-paths", str(fixture_dir / "train.avro"),
         "--output-dir", str(out_slurp),
         "--feature-index-dir", str(idx_dir)] + common
    )
    s_slurp = game_training.run(aargs)

    # Same index maps + same data => identical training outcome.
    assert s_stream["best"]["metrics"]["AUC"] == pytest.approx(
        s_slurp["best"]["metrics"]["AUC"], abs=1e-6
    )
    assert s_stream["best"]["metrics"]["AUC"] > 0.7


def test_stream_ingest_requires_index_dir(fixture_dir, tmp_path):
    args = game_training.build_parser().parse_args(
        [
            "--input-paths", str(fixture_dir / "train.avro"),
            "--output-dir", str(tmp_path / "o"),
            "--feature-shard-configurations", "name=globalShard",
            "--coordinate-configurations",
            "name=global,feature.shard=globalShard,reg.weights=1",
            "--update-sequence", "global",
            "--stream-ingest-chunk-rows", "64",
        ]
    )
    with pytest.raises(SystemExit):
        game_training.run(args)


def test_game_scoring_streaming_matches_slurp(fixture_dir, tmp_path):
    """Streaming scoring (chunked features, padded program shapes) must
    produce bit-identical scores and metrics to the slurping path."""
    from photon_tpu.io.columnar import _load_lib

    if _load_lib() is None:
        pytest.skip("native decoder unavailable")

    out = tmp_path / "train_out"
    targs = game_training.build_parser().parse_args(
        [
            "--input-paths", str(fixture_dir / "train.avro"),
            "--output-dir", str(out),
            "--feature-shard-configurations", "name=g",
            "--coordinate-configurations",
            "name=global,feature.shard=g,reg.weights=1",
            "name=perUser,feature.shard=g,random.effect.type=userId,reg.weights=1",
            "--update-sequence", "global,perUser",
        ]
    )
    game_training.run(targs)

    # Multi-BLOCK scoring input: chunk_rows=64 with 50-row blocks yields
    # several chunks, exercising cross-chunk uid renumbering and metric
    # accumulation (a single-block file would stream as ONE chunk).
    multi = tmp_path / "valid_multiblock.avro"
    write_fixture(str(multi), n=200, block_records=50)
    from photon_tpu.io.columnar import stream_avro_columnar
    assert len(list(stream_avro_columnar([str(multi)], chunk_rows=64))) > 1

    def score(extra, sub):
        sdir = tmp_path / sub
        sargs = game_scoring.build_parser().parse_args(
            [
                "--input-paths", str(multi),
                "--output-dir", str(sdir),
                "--feature-shard-configurations", "name=g",
                "--model-input-dir", str(out / "best"),
                "--model-artifacts-dir", str(out),
                "--evaluators", "AUC", "AUC:userId",
            ] + extra
        )
        r = game_scoring.run(sargs)
        from photon_tpu.io.scores import load_scores
        recs = load_scores(str(sdir / "scores.avro"))
        return r, [rr["uid"] for rr in recs], [rr["predictionScore"] for rr in recs]

    r_slurp, uid_slurp, sc_slurp = score([], "sc_slurp")
    r_stream, uid_stream, sc_stream = score(
        ["--stream-ingest-chunk-rows", "64"], "sc_stream"
    )
    assert r_stream["numScored"] == r_slurp["numScored"] == 200
    assert r_stream["metrics"] == pytest.approx(r_slurp["metrics"], abs=1e-6)
    assert uid_stream == uid_slurp  # order preserved
    np.testing.assert_allclose(sc_stream, sc_slurp, rtol=0, atol=0)


def test_legacy_driver_per_iteration_validation_and_reg_type(tmp_path):
    """VALIDATE_PER_ITERATION + REGULARIZATION_TYPE parity: per-iteration
    MetricsMaps land in the summary (one per iteration, final map equal to
    the standard validation map), and --regularization-type NONE ignores
    the weights (PhotonMLCmdLineParser.scala:100-116, Driver.scala:354-376)."""
    libsvm = tmp_path / "t.txt"
    lines = []
    w = np.array([1.0, -1.5, 0.5])
    for i in range(200):
        x = rng.normal(size=3)
        y = 1 if rng.uniform() < 1 / (1 + np.exp(-x @ w)) else -1
        lines.append(f"{y:+d} " + " ".join(f"{j+1}:{x[j]:.4f}" for j in range(3)))
    libsvm.write_text("\n".join(lines))
    out = tmp_path / "o"
    args = train_glm.build_parser().parse_args(
        [
            "--training-data", str(libsvm),
            "--validation-data", str(libsvm),
            "--format", "libsvm",
            "--output-dir", str(out),
            "--regularization-weights", "1",
            "--max-iterations", "8",
            "--validate-per-iteration",
        ]
    )
    summary = train_glm.run(args)
    (m,) = summary["models"]
    per_iter = m["per_iteration_validation"]
    assert len(per_iter) == m["iterations"]
    assert per_iter[-1]["Area under ROC"] == pytest.approx(
        m["validation"]["Area under ROC"], abs=1e-6
    )
    # AUROC at the last iteration should not be worse than at the first.
    assert per_iter[-1]["Area under ROC"] >= per_iter[0]["Area under ROC"] - 1e-3

    # NONE regularization type ignores the weight list.
    out2 = tmp_path / "o2"
    args2 = train_glm.build_parser().parse_args(
        [
            "--training-data", str(libsvm), "--format", "libsvm",
            "--output-dir", str(out2),
            "--regularization-weights", "0.1,1,10",
            "--regularization-type", "NONE",
        ]
    )
    summary2 = train_glm.run(args2)
    assert len(summary2["models"]) == 1
    assert summary2["models"][0]["lambda"] == 0.0
