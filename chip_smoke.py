#!/usr/bin/env python3
"""chip_smoke.py — GLMix train → score → serve on the TPU, through the drivers.

The quickest proof that the system still starts on the chip:

    python3 chip_smoke.py

Exit 0 and a last stdout line ``{"ok": true, "device": {...}}`` mean every
phase passed ON A TPU. Anything else — no accelerator, a directory without
the repo, a phase that failed — is a non-zero exit and no result line. No
flag or environment variable changes that.

One process for each chip: this parent never imports JAX. It generates the
data from a seed and runs the phases as child processes one after another —
the drivers through ``python -m photon_tpu.cli.*`` exactly as a user calls
them, the in-process phases through ``chip_smoke.py --phase <name>`` — and
every child shares one compile cache (photon_tpu/utils/compile_cache.py).

Phases: device (backend assertion, versions, native libraries built from
the tracked sources) → train (game_training on an Avro file) → score and
serve (game_scoring, then game_serving --workers 2 over HTTP) → full width
(GameEstimator.fit at N = 2^21 against a float32 host reference) → kernels
(every pallas_call compiled by Mosaic, against its jax.numpy form) → four
chips (when four are visible).
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
# Data and models (tens of MB) stay on the machine; the small result files
# go where the chip tool brings them back from.
WORK = os.path.join(HERE, ".chip_smoke_work")
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")

SEED = 20260926
SIZES = dict(
    # Driver phases: one Avro file through game_training / scoring / serving.
    train_rows=1 << 15, valid_rows=1 << 12, unseen_rows=64,
    d_fix=256, d_re=16, entities=4096,
    # Full-width phase: bench.py's headline shape through GameEstimator.fit.
    full_n=1 << 21, full_valid=1 << 17,
    # Kernel phase: the same shapes (E entities of n / E = 512 rows each).
    kernel_n=1 << 21,
    # Four-chip phase.
    multi_entities=4096, multi_rows=(64, 256), multi_d_re=16,
    fused_entities=2048, fused_rows_per=64, fused_d_fe=256, fused_d_re=16,
)
# Stated tolerances, with where each comes from (CHANGES.md PR 21 has what
# the chip showed against them).
TOL = dict(
    # Device model vs the float32 host reference after the same two
    # block-exact coordinate-descent passes. The first chip run showed
    # 1.4e-6 and identical AUC to five digits; the bounds are ~70× that.
    objective_rel=1e-4, auc_abs=1e-3,
    # A kernel vs its jax.numpy form at HIGHEST matmul precision, error
    # normalized by the largest reference magnitude. Mosaic's float32 dot at
    # the default precision is ONE bf16 pass with both operands cut to bf16,
    # so the bound is 2·2^-8 when every term errs the same way (the first
    # chip run showed 2.2e-3 on the gradient, where XLA's float32 matvec was
    # exact to HIGHEST). The bf16 kernel also rounds w to bf16 by design.
    kernel_f32=2 * 2.0 ** -8, kernel_bf16=2e-2,
    # HTTP score vs the batch driver's for the same row, relative to
    # max(1, |score|). Bit-equal on the CPU (tier-1 asserts it); on the v5e
    # the row reduction of compute_score associates differently at the
    # serving bucket shapes than at the batch shape, and half the rows
    # differ in the last bit (ROADMAP D9, S6).
    serve_rel=2e-6,
    # glmix_train_step(use_pallas=True) vs the XLA lowering of the same step.
    train_step_rel=2e-2,
    # Fused pjit step across mesh sizes: the FE gradient psum reorders the
    # reduction, so this one comparison is allclose-level by construction.
    fused_mesh_abs=1e-3,
)


def _log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def _fail(msg: str, code: int = 1) -> "None":
    print(f"chip_smoke.py: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def _check(cond: bool, msg: str) -> None:
    """An assertion that survives ``python -O``."""
    if not cond:
        _fail(msg)


class _Expectations:
    """For a phase of many comparisons: each one is made and logged before
    the phase fails, so one chip run shows all of them."""

    def __init__(self, phase: str):
        self.phase = phase
        self.unmet = []

    def expect(self, cond: bool, msg: str) -> None:
        if not cond:
            self.unmet.append(msg)
            _log(f"{self.phase}: NOT MET: {msg}")

    def settle(self) -> None:
        _check(not self.unmet, f"{self.phase}: " + "; ".join(self.unmet))


# ---------------------------------------------------------------------------
# Parent side: no JAX in this process.
# ---------------------------------------------------------------------------


def _child_env() -> dict:
    env = dict(os.environ)
    parts = [HERE] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(parts))
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _run(cmd, what: str) -> float:
    """Run one child to its end; its non-zero exit is the smoke's."""
    _log(f"{what}: {' '.join(cmd[:4])} ...")
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, cwd=HERE, env=_child_env())
    wall = time.perf_counter() - t0
    if proc.returncode != 0:
        _fail(f"{what} exited {proc.returncode}", proc.returncode or 1)
    _log(f"{what}: ok in {wall:.1f}s")
    return wall


def _run_phase_child(name: str) -> dict:
    wall = _run([sys.executable, os.path.abspath(__file__), "--phase", name],
                f"phase {name}")
    with open(os.path.join(WORK, f"result_{name}.json")) as f:
        result = json.load(f)
    result["wall_s"] = round(wall, 1)
    return result


def _write_result(name: str, result: dict) -> None:
    with open(os.path.join(WORK, f"result_{name}.json"), "w") as f:
        json.dump(result, f)


def _sigmoid(z):
    return 1.0 / (1.0 + np.exp(-z))


def generate_driver_data() -> dict:
    """Seeded GLMix data as TrainingExampleAvro files, written with the
    repo's own codec: a ``features`` bag for the fixed effect, a
    ``userFeatures`` bag for the per-user random effect, ``userId`` in the
    metadata map. The last ``unseen_rows`` validation rows carry user ids
    that never occur in training."""
    from photon_tpu.io.avro import write_avro_records
    from photon_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA

    s = SIZES
    n_tr, n_va = s["train_rows"], s["valid_rows"]
    df, dr, E = s["d_fix"] - 1, s["d_re"] - 1, s["entities"]  # + intercepts
    rng = np.random.default_rng(SEED)
    n = n_tr + n_va
    Xf = rng.normal(size=(n, df)).astype(np.float32)
    Xr = rng.normal(size=(n, dr)).astype(np.float32)
    users = rng.integers(0, E, size=n)
    w_fix = (rng.normal(size=df) / np.sqrt(df)).astype(np.float32)
    # Per-user effect mostly in the intercept: learnable from the ~8 rows a
    # user has at this size.
    user_bias = rng.normal(scale=1.5, size=E).astype(np.float32)
    user_w = rng.normal(scale=0.3, size=(E, dr)).astype(np.float32)
    logits = Xf @ w_fix + user_bias[users] + np.sum(Xr * user_w[users], axis=1)
    y = (rng.uniform(size=n) < _sigmoid(logits)).astype(np.float32)
    user_names = [f"user{u}" for u in users]
    for k in range(s["unseen_rows"]):
        user_names[n - 1 - k] = f"unseen{k}"

    schema = copy.deepcopy(TRAINING_EXAMPLE_SCHEMA)
    schema["fields"].insert(3, {
        "name": "userFeatures",
        "type": {"type": "array", "items": "FeatureAvro"},
    })
    f_names = [f"f{j}" for j in range(df)]
    u_names = [f"u{j}" for j in range(dr)]

    def records(lo, hi):
        for i in range(lo, hi):
            yield {
                "uid": str(i - lo),
                "label": float(y[i]),
                "features": [
                    {"name": f_names[j], "term": "", "value": float(Xf[i, j])}
                    for j in range(df)
                ],
                "userFeatures": [
                    {"name": u_names[j], "term": "", "value": float(Xr[i, j])}
                    for j in range(dr)
                ],
                "metadataMap": {"userId": user_names[i]},
                "weight": 1.0,
                "offset": 0.0,
            }

    paths = {}
    for name, lo, hi in (("train", 0, n_tr), ("valid", n_tr, n)):
        paths[name] = os.path.join(WORK, f"{name}.avro")
        write_avro_records(paths[name], schema, records(lo, hi))
    return dict(paths=paths, Xf=Xf, Xr=Xr, user_names=user_names,
                f_names=f_names, u_names=u_names, n_train=n_tr, n=n)


_SHARDS = [
    "name=global,feature.bags=features",
    "name=per_user,feature.bags=userFeatures",
]


def _read_jsonl(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _report_facts(path: str) -> dict:
    """What the smoke asserts from a driver's run report."""
    recs = _read_jsonl(path)
    (env,) = [r for r in recs if r["record"] == "env"]
    native = [r["value"] for r in recs
              if r["record"] == "metric" and r["metric"] == "avro_decoder_native"]
    cd = [
        dict(coordinate=r["coordinate"], iteration=r["cd_iteration"],
             **{k: r["diagnostics"].get(k) for k in
                ("reason", "iterations", "entities", "converged",
                 "hit_max_iter", "quarantined")
                if k in r["diagnostics"]})
        for r in recs if r["record"] == "coordinate_descent"
    ]
    return dict(jax_backend=env["jax_backend"],
                device_count=env["device_count"],
                avro_decoder_native=native, cd=cd)


def phase_train(data: dict) -> dict:
    """``python -m photon_tpu.cli.game_training`` twice on the Avro file:
    the fixed effect alone, then the GLMix model the later phases use."""
    common = [
        "--input-paths", data["paths"]["train"],
        "--validation-paths", data["paths"]["valid"],
        "--feature-shard-configurations", *_SHARDS,
        "--evaluators", "AUC",
    ]
    fe_cfg = "name=global,feature.shard=global,reg.weights=1"
    re_cfg = ("name=per_user,feature.shard=per_user,"
              "random.effect.type=userId,reg.weights=1")
    runs = {}
    for tag, extra in (
        ("fe_only", ["--coordinate-configurations", fe_cfg,
                     "--update-sequence", "global"]),
        ("glmix", ["--coordinate-configurations", fe_cfg, re_cfg,
                   "--update-sequence", "global,per_user",
                   "--coordinate-descent-iterations", "2"]),
    ):
        out = os.path.join(WORK, f"model_{tag}")
        report = os.path.join(OUT, f"train_{tag}.jsonl")
        wall = _run(
            [sys.executable, "-m", "photon_tpu.cli.game_training", *common,
             *extra, "--output-dir", out, "--telemetry-out", report],
            f"train[{tag}]",
        )
        with open(os.path.join(out, "training-summary.json")) as f:
            summary = json.load(f)
        with open(os.path.join(out, "LATEST")) as f:
            latest = f.read().strip()
        facts = _report_facts(report)
        _check(latest == "best" and os.path.isfile(
            os.path.join(out, "best", "model-metadata.json")),
            f"train[{tag}]: no published model under {out}")
        _check(facts["jax_backend"] == "tpu",
               f"train[{tag}]: run report says jax_backend="
               f"{facts['jax_backend']!r}, not tpu")
        _check(facts["avro_decoder_native"] == [1],
               f"train[{tag}]: the native Avro decoder did not load "
               f"(avro_decoder_native={facts['avro_decoder_native']})")
        runs[tag] = dict(auc=summary["best"]["metrics"]["AUC"],
                         wall_s=round(wall, 1), cd=facts["cd"], model_dir=out)
        _log(f"train[{tag}]: validation AUC {runs[tag]['auc']:.4f}; "
             f"solver outcomes {facts['cd']}")
    _check(runs["glmix"]["auc"] > runs["fe_only"]["auc"],
           f"GLMix validation AUC {runs['glmix']['auc']:.4f} does not improve "
           f"on the fixed-effect-only pass {runs['fe_only']['auc']:.4f}")
    return dict(
        auc_fe_only=runs["fe_only"]["auc"], auc_glmix=runs["glmix"]["auc"],
        decoder="native", jax_backend="tpu",
        wall_s={k: v["wall_s"] for k, v in runs.items()},
        cd=runs["glmix"]["cd"], model_dir=runs["glmix"]["model_dir"],
    )


def _http(port: int, path: str, body: bytes = None) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body,
        method="POST" if body is not None else "GET",
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def phase_score_serve(data: dict, model_dir: str) -> dict:
    """game_scoring on the files the trainer read, then game_serving
    --workers 2: HTTP scores must be BIT-equal to the batch driver's (both
    reduce through Coefficients.compute_score), an unseen entity included."""
    from photon_tpu.io.avro import AvroReader

    score_out = os.path.join(WORK, "scores")
    report = os.path.join(OUT, "score.jsonl")
    score_wall = _run(
        [sys.executable, "-m", "photon_tpu.cli.game_scoring",
         "--input-paths", data["paths"]["train"], data["paths"]["valid"],
         "--output-dir", score_out,
         "--feature-shard-configurations", *_SHARDS,
         "--model-input-dir", os.path.join(model_dir, "best"),
         "--model-artifacts-dir", model_dir,
         "--evaluators", "AUC", "--telemetry-out", report],
        "score",
    )
    facts = _report_facts(report)
    _check(facts["jax_backend"] == "tpu", "score: run report is not from a TPU")
    _check(facts["avro_decoder_native"] == [1],
           "score: the native Avro decoder did not load")
    with AvroReader(os.path.join(score_out, "scores.avro")) as reader:
        batch_scores = np.asarray(
            [rec["predictionScore"] for rec in reader], np.float64
        )
    _check(batch_scores.shape == (data["n"],),
           f"score: {batch_scores.shape[0]} scores for {data['n']} rows")
    _check(bool(np.all(np.isfinite(batch_scores))), "score: non-finite scores")

    # Rows to replay over HTTP: a few trained entities from each file, and
    # unseen entities (the last validation rows).
    rows = [0, 1, 2, data["n_train"] - 1, data["n_train"], data["n_train"] + 7,
            data["n"] - 1, data["n"] - 2]
    log_path = os.path.join(OUT, "serve.log")
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "photon_tpu.cli.game_serving",
             "--model-input-dir", model_dir, "--workers", "2", "--port", "0"],
            cwd=HERE, env=_child_env(), stdout=subprocess.PIPE,
            stderr=log, start_new_session=True, text=True,
        )
    t0 = time.perf_counter()
    try:
        stdout_lines = []
        banner = {}

        def drain():
            for line in proc.stdout:
                stdout_lines.append(line)
                if not banner and line.lstrip().startswith("{"):
                    try:
                        msg = json.loads(line)
                    except ValueError:
                        continue
                    if msg.get("serving") is True:
                        banner.update(msg)

        reader_thread = threading.Thread(target=drain, daemon=True)
        reader_thread.start()
        while not banner:
            if proc.poll() is not None:
                _fail(f"serve: game_serving exited {proc.returncode} before "
                      f"its start-up banner (see {log_path})")
            if time.perf_counter() - t0 > 600:
                _fail("serve: no start-up banner within 600 s")
            time.sleep(0.2)
        warm_s = time.perf_counter() - t0
        port = banner["port"]
        _log(f"serve: up on port {port} after {warm_s:.1f}s "
             f"(model {banner.get('modelVersion')})")
        diffs = []
        for i in rows:
            body = json.dumps({
                "features": {
                    "global": {k: float(v) for k, v in
                               zip(data["f_names"], data["Xf"][i])},
                    "per_user": {k: float(v) for k, v in
                                 zip(data["u_names"], data["Xr"][i])},
                },
                "entityIds": {"userId": data["user_names"][i]},
            }).encode()
            got = np.float32(_http(port, "/v1/score", body)["score"])
            want = np.float32(batch_scores[i])
            _log(f"serve: row {i} ({data['user_names'][i]}) http={got!r} "
                 f"batch={want!r}")
            diffs.append(abs(float(got) - float(want)) / max(1.0, abs(float(want))))
        bit_equal = sum(d == 0.0 for d in diffs)
        _log(f"serve: {bit_equal}/{len(rows)} rows bit-equal to the batch "
             f"driver, largest relative difference {max(diffs):.2e} "
             f"(tolerance {TOL['serve_rel']})")
        _check(max(diffs) <= TOL["serve_rel"],
               f"serve: HTTP scores differ from the batch driver's by {diffs}")
        health = _http(port, "/healthz")
        _check(health["retraces_since_warmup"] == 0,
               f"serve: retraces_since_warmup={health['retraces_since_warmup']}")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        reader_thread.join(timeout=10)
        _check(rc == 0, f"serve: exit code {rc} after SIGTERM (see {log_path})")
    finally:
        if proc.poll() is None:  # never leave the server or its workers
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    return dict(num_scored=int(batch_scores.shape[0]),
                http_rows=len(rows), http_rows_bit_equal=bit_equal,
                http_max_rel_diff=max(diffs),
                unseen_entity_rows=2, retraces_since_warmup=0,
                sigterm_exit=0, warmup_s=round(warm_s, 1),
                wall_s=dict(score=round(score_wall, 1),
                            serve=round(time.perf_counter() - t0, 1)))


def main() -> None:
    if not os.path.isdir(os.path.join(HERE, "photon_tpu")):
        _fail("no photon_tpu package beside chip_smoke.py; "
              "run it from the root of a checkout")
    from photon_tpu.utils.compile_cache import (
        DEFAULT_DIR, ENV_VAR, cache_entry_count,
    )

    t_start = time.perf_counter()
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(OUT, exist_ok=True)
    cache_dir = os.environ.get(ENV_VAR) or DEFAULT_DIR
    entries_before = cache_entry_count(cache_dir)
    _log(f"compile cache: {cache_dir} "
         f"({'from ' + ENV_VAR if os.environ.get(ENV_VAR) else 'default path'}), "
         f"{entries_before} entries before")

    # First the device: the child exits non-zero, naming the backend it
    # found, unless that is a TPU. Nothing is generated before this.
    device = _run_phase_child("device")
    phases = {"device": device}

    _log("generating the driver phases' Avro data from the seed")
    t0 = time.perf_counter()
    data = generate_driver_data()
    _log(f"data: {data['n_train']} + {data['n'] - data['n_train']} rows in "
         f"{time.perf_counter() - t0:.1f}s")

    phases["train"] = phase_train(data)
    phases["score_serve"] = phase_score_serve(data, phases["train"]["model_dir"])
    phases["full_width"] = _run_phase_child("full_width")
    phases["kernels"] = _run_phase_child("kernels")
    if device["count"] >= 4:
        phases["four_chips"] = _run_phase_child("four_chips")
    else:
        _log(f"phase four_chips skipped: {device['count']} device(s)")
        phases["four_chips"] = {"skipped": f"{device['count']} device(s)"}

    entries_after = cache_entry_count(cache_dir)
    _log(f"compile cache: {entries_after} entries after "
         f"(+{entries_after - entries_before})")
    summary = dict(
        ok=True,
        device={k: device[k] for k in ("platform", "kind", "count")},
        versions=device["versions"],
        decoder=phases["train"]["decoder"],
        compile_cache=dict(dir=cache_dir, entries_before=entries_before,
                           entries_after=entries_after),
        phases=phases,
        seconds=round(time.perf_counter() - t_start, 1),
    )
    with open(os.path.join(OUT, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print("summary: " + json.dumps(summary))
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)


# ---------------------------------------------------------------------------
# Child side: one process, one chip. Every phase starts at _require_tpu().
# ---------------------------------------------------------------------------


def _require_tpu() -> dict:
    """The device, as JAX reports it — or exit non-zero naming what was
    found instead. Nothing turns this off."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke.py: needs a TPU; the JAX default backend is "
              f"{backend!r} (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). "
              "Nothing ran.", file=sys.stderr, flush=True)
        sys.exit(3)
    devices = jax.devices()
    return dict(platform=devices[0].platform, kind=devices[0].device_kind,
                count=len(devices))


class _CompileClock:
    """Seconds JAX spent compiling (tracing, lowering, backend compile or
    cache retrieval) and persistent-cache hits, from jax.monitoring."""

    _DURATIONS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.backend_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in self._DURATIONS:
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.backend_seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return dict(compile_s=round(self.seconds, 2),
                    backend_compile_s=round(self.backend_seconds, 2),
                    cache_hits=self.cache_hits, cache_misses=self.cache_misses)


def child_device() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    device = _require_tpu()
    device["versions"] = dict(jax=jax.__version__, jaxlib=jaxlib.__version__,
                              libtpu=md.version("libtpu"))
    _log(f"device: platform={device['platform']} "
         f"device_kind={device['kind']!r} count={device['count']} "
         f"jax={jax.__version__} libtpu={device['versions']['libtpu']}")
    # Built from what git tracks: a copied tree keeps no mtimes, and the
    # loaders fall back to pure Python in silence when a build fails.
    from photon_tpu.data import native_index
    from photon_tpu.io import columnar

    for name, mod in (("libavro_decode.so", columnar),
                      ("libindex_store.so", native_index)):
        so = mod.build_native_lib(force=True)
        _check(so is not None, f"building {name} from the tracked source failed")
        _log(f"native: built {os.path.relpath(so, HERE)}")
    _check(columnar._load_lib() is not None, "libavro_decode.so does not load")
    return device


def _peak_bytes():
    """Peak device memory of this process so far, where the backend reports
    it (a TPU does)."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return int(stats["peak_bytes_in_use"]) if stats else None


def _auc(scores: np.ndarray, labels: np.ndarray) -> float:
    """Rank AUC with average ranks for ties (plain numpy)."""
    order = np.argsort(scores, kind="stable")
    s = scores[order]
    ranks = np.empty(len(s), np.float64)
    boundaries = np.flatnonzero(np.r_[True, s[1:] != s[:-1], True])
    for lo, hi in zip(boundaries[:-1], boundaries[1:]):
        ranks[lo:hi] = 0.5 * (lo + hi - 1) + 1.0
    pos = labels[order] > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def make_glmix_arrays(n: int, d_fix: int, d_re: int, E: int, seed: int,
                      users=None):
    """bench.py's headline shape (N × d_fix fixed effect, d_re per-user
    random effect over E users, intercepts in column 0), with a real user
    effect so the random-effect coordinate has something to find. ``users``
    given: each row's user, in place of the uniform draw."""
    rng = np.random.default_rng(seed)
    Xf = rng.standard_normal(size=(n, d_fix), dtype=np.float32)
    Xf[:, 0] = 1.0
    Xr = rng.standard_normal(size=(n, d_re), dtype=np.float32)
    Xr[:, 0] = 1.0
    drawn = rng.integers(0, E, size=n).astype(np.int32)
    users = drawn if users is None else users
    w_fix = (rng.normal(size=d_fix) / np.sqrt(d_fix)).astype(np.float32)
    w_user = rng.normal(scale=0.5, size=(E, d_re)).astype(np.float32)
    logits = Xf @ w_fix + np.einsum("nd,nd->n", Xr, w_user[users])
    y = (rng.uniform(size=n) < _sigmoid(logits)).astype(np.float32)
    return Xf, Xr, users, y


def _logloss_sum(z: np.ndarray, y: np.ndarray) -> float:
    z = z.astype(np.float64)
    return float(np.sum(np.logaddexp(0.0, z) - y * z))


def glmix_objective(Xf, Xr, users, y, w, W, l2) -> float:
    """The regularized GLMix training objective in float64 on the host
    (intercepts, column 0, unregularized) — one yardstick for the device
    model and the reference model alike."""
    z = Xf @ w + np.einsum("nd,nd->n", Xr, W[users])
    reg = 0.5 * l2 * (float(np.sum(w[1:].astype(np.float64) ** 2))
                      + float(np.sum(W[:, 1:].astype(np.float64) ** 2)))
    return _logloss_sum(z, y) + reg


def reference_glmix_cd(Xf, Xr, users, y, E, l2, passes, log=_log):
    """Plain float32 reference on the host: the same block coordinate
    descent (fixed effect, then per-user effects, each against the other's
    scores), every block solved to its optimum by exact Newton. numpy's
    float32 matmuls are full precision, which is the point."""
    n, d = Xf.shape
    d_re = Xr.shape[1]
    w = np.zeros(d, np.float32)
    W = np.zeros((E, d_re), np.float32)
    lam_f = np.full(d, l2, np.float64)
    lam_f[0] = 0.0
    lam_r = np.full(d_re, l2, np.float32)
    lam_r[0] = 0.0
    # Per-user padded slabs (weight 0 on padding).
    order = np.argsort(users, kind="stable")
    counts = np.bincount(users, minlength=E)
    n_max = int(counts.max())
    starts = np.r_[0, np.cumsum(counts)[:-1]]
    slot = np.arange(n) - np.repeat(starts, counts)
    rows = np.full((E, n_max), 0, np.int64)
    mask = np.zeros((E, n_max), np.float32)
    rows[users[order], slot] = order
    mask[users[order], slot] = 1.0
    Xr_pad = Xr[rows] * mask[..., None]
    y_pad = y[rows] * mask
    chunk = 1 << 17
    re_scores = np.zeros(n, np.float32)
    for it in range(passes):
        # Fixed effect: Newton on the full batch, Hessian accumulated in
        # float64 over row chunks.
        for newton_it in range(25):
            z = Xf @ w + re_scores
            p = _sigmoid(z)
            g = (Xf.T @ (p - y)).astype(np.float64) + lam_f * w
            if float(np.abs(g).max()) <= 1e-2:  # of sums over n rows
                break
            d2 = (p * (1.0 - p)).astype(np.float32)
            H = np.diag(lam_f)
            for lo in range(0, n, chunk):
                Xc = Xf[lo:lo + chunk]
                H += (Xc * d2[lo:lo + chunk, None]).T.astype(np.float32) @ Xc
            step = np.linalg.solve(H, g)
            f0 = _logloss_sum(z, y) + 0.5 * float(np.sum(lam_f * w * w))
            t = 1.0
            while True:  # step halving: Newton from zero can overshoot
                w_try = (w - t * step).astype(np.float32)
                f_try = (_logloss_sum(Xf @ w_try + re_scores, y)
                         + 0.5 * float(np.sum(lam_f * w_try * w_try)))
                if f_try <= f0 or t < 1e-3:
                    break
                t *= 0.5
            w = w_try
        fe_scores = Xf @ w
        log(f"reference pass {it}: fixed effect |g|={np.linalg.norm(g):.3g} "
            f"after {newton_it + 1} Newton iterations")
        # Per-user effects: batched Newton over the padded slabs.
        off_pad = fe_scores[rows] * mask
        for newton_it in range(25):
            z = np.einsum("end,ed->en", Xr_pad, W) + off_pad
            p = _sigmoid(z)
            g = np.einsum("end,en->ed", Xr_pad, (p - y_pad) * mask) + lam_r * W
            gmax = float(np.abs(g).max())
            if gmax <= 1e-4:
                break
            H = np.einsum("end,en,enf->edf", Xr_pad, p * (1.0 - p) * mask, Xr_pad)
            H += np.diag(lam_r + 1e-6)
            W = (W - np.linalg.solve(H, g[..., None])[..., 0]).astype(np.float32)
        re_scores = np.einsum("nd,nd->n", Xr, W[users])
        log(f"reference pass {it}: per-user effects max|g|={gmax:.3g} "
            f"after {newton_it + 1} Newton iterations")
    return w, W


def child_full_width() -> dict:
    """GameEstimator.fit on in-memory GameBatches at full width, against
    the host reference."""
    device = _require_tpu()
    import jax
    import jax.numpy as jnp

    from photon_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    clock = _CompileClock()
    from photon_tpu.data.game_data import GameBatch
    from photon_tpu.estimators.config import (
        FixedEffectCoordinateConfig, GameOptimizationConfig,
        RandomEffectCoordinateConfig, RegularizationConfig,
    )
    from photon_tpu.estimators.game_estimator import GameEstimator
    from photon_tpu.evaluation import EvaluationSuite
    from photon_tpu.evaluation.suite import EvaluatorSpec
    from photon_tpu.types import TaskType

    s = SIZES
    n, n_va, E = s["full_n"], s["full_valid"], s["entities"]
    l2, passes = 1.0, 2
    _log(f"full width: generating N={n} (+{n_va} validation) × d={s['d_fix']}, "
         f"d_re={s['d_re']}, E={E}")
    Xf, Xr, users, y = make_glmix_arrays(n + n_va, s["d_fix"], s["d_re"], E,
                                         SEED + 1)

    def batch(sl):
        return GameBatch(
            label=jnp.asarray(y[sl]),
            offset=jnp.zeros(len(y[sl]), jnp.float32),
            weight=jnp.ones(len(y[sl]), jnp.float32),
            features={"global": jnp.asarray(Xf[sl]),
                      "per_user": jnp.asarray(Xr[sl])},
            entity_ids={"userId": jnp.asarray(users[sl])},
        )

    tr, va = slice(0, n), slice(n, n + n_va)
    train, valid = batch(tr), batch(va)
    estimator = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs=[
            FixedEffectCoordinateConfig("global", "global"),
            RandomEffectCoordinateConfig("per_user", "userId", "per_user"),
        ],
        num_iterations=passes,
        intercept_indices={"global": 0, "per_user": 0},
        num_entities={"userId": E},
    )
    suite = EvaluationSuite([EvaluatorSpec.parse("AUC")])
    config = GameOptimizationConfig(reg={
        "global": RegularizationConfig(weight=l2),
        "per_user": RegularizationConfig(weight=l2),
    })

    def fit():
        t0 = time.perf_counter()
        (result,) = estimator.fit(train, validation_batch=valid,
                                  evaluation_suite=suite,
                                  optimization_configs=[config])
        jax.block_until_ready(jax.tree_util.tree_leaves(result.model))
        return result, time.perf_counter() - t0

    result, first_wall = fit()  # compiles
    first = clock.snapshot()
    result, run_wall = fit()    # same shapes: every solver already compiled
    second = clock.snapshot()
    _log(f"full width: first fit {first_wall:.1f}s of which compiling "
         f"{first['compile_s']}s (persistent-cache hits {first['cache_hits']}, "
         f"misses {first['cache_misses']}); second fit {run_wall:.1f}s "
         f"(+{second['compile_s'] - first['compile_s']:.2f}s compiling)")

    outcomes = {}
    for cid, diags in result.tracker.items():
        outcomes[cid] = [d.diagnostics_dict() for d in diags]
        for i, d in enumerate(outcomes[cid]):
            _log(f"full width: pass {i} {cid}: {d}")
    model = result.model
    w_dev = np.asarray(model.get("global").model.coefficients.means, np.float32)
    W_dev = np.asarray(model.get("per_user").coefficients, np.float32)
    _check(w_dev.shape == (s["d_fix"],) and W_dev.shape == (E, s["d_re"]),
           f"full width: coefficient shapes {w_dev.shape}, {W_dev.shape}")
    _check(bool(np.all(np.isfinite(w_dev)) and np.all(np.isfinite(W_dev))),
           "full width: non-finite coefficients")
    peak = _peak_bytes()

    t0 = time.perf_counter()
    w_ref, W_ref = reference_glmix_cd(Xf[tr], Xr[tr], users[tr], y[tr], E, l2,
                                      passes)
    ref_s = time.perf_counter() - t0
    obj_dev = glmix_objective(Xf[tr], Xr[tr], users[tr], y[tr], w_dev, W_dev, l2)
    obj_ref = glmix_objective(Xf[tr], Xr[tr], users[tr], y[tr], w_ref, W_ref, l2)

    def valid_auc(w, W):
        z = Xf[va] @ w + np.einsum("nd,nd->n", Xr[va], W[users[va]])
        return _auc(z.astype(np.float64), y[va])

    auc_dev, auc_ref = valid_auc(w_dev, W_dev), valid_auc(w_ref, W_ref)
    auc_suite = float(result.metrics["AUC"])
    obj_rel = abs(obj_dev - obj_ref) / abs(obj_ref)
    _log(f"full width: objective device {obj_dev:.6g} vs reference "
         f"{obj_ref:.6g} (rel {obj_rel:.2e}, tolerance {TOL['objective_rel']}); "
         f"validation AUC device {auc_dev:.5f} (evaluator: {auc_suite:.5f}) vs "
         f"reference {auc_ref:.5f} (tolerance {TOL['auc_abs']}); reference "
         f"took {ref_s:.1f}s; peak_bytes_in_use {peak}")
    _check(obj_rel <= TOL["objective_rel"],
           f"full width: objective off the reference by {obj_rel:.3e}")
    _check(abs(auc_dev - auc_ref) <= TOL["auc_abs"]
           and abs(auc_suite - auc_dev) <= TOL["auc_abs"],
           f"full width: AUC {auc_dev} / {auc_suite} vs reference {auc_ref}")
    re_last = outcomes["per_user"][-1]
    return dict(
        device=device, n=n, d_fix=s["d_fix"], d_re=s["d_re"], entities=E,
        cd_passes=passes,
        first_fit_s=round(first_wall, 2), compile=first,
        second_fit_s=round(run_wall, 2),
        objective=dict(device=obj_dev, reference=obj_ref, rel=obj_rel),
        auc=dict(device=auc_dev, evaluator=auc_suite, reference=auc_ref),
        solver_outcomes=outcomes,
        entities_converged=f"{re_last['converged']}/{re_last['entities']}",
        peak_bytes_in_use=peak, reference_s=round(ref_s, 1),
    )


def _not_interpreted(fn, *args) -> None:
    """The lowered program must hold the Mosaic custom call."""
    import jax

    text = jax.jit(fn).lower(*args).as_text()
    _check("tpu_custom_call" in text,
           f"{getattr(fn, '__name__', fn)}: lowered without tpu_custom_call "
           "(interpreted?)")


def _rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def child_kernels() -> dict:
    """Each pallas_call at the full-width shapes, bf16 and f32: compiled by
    Mosaic (not interpreted), run, and compared with its jax.numpy form."""
    device = _require_tpu()
    import jax
    import jax.numpy as jnp

    from photon_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    clock = _CompileClock()
    from photon_tpu.data.batch import LabeledBatch
    from photon_tpu.data.random_effect import (
        RandomEffectDataConfig, build_random_effect_dataset,
    )
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.ops.pallas_glm import (
        fused_data_hvp, fused_data_value_and_grad,
    )
    from photon_tpu.optim.common import OptimizerConfig
    from photon_tpu.parallel.train_step import glmix_train_step

    HIGHEST = jax.lax.Precision.HIGHEST
    s = SIZES
    n, d, d_re, E = s["kernel_n"], s["d_fix"], s["d_re"], s["entities"]
    # n / E rows a user exactly: one level of the block plan's grid, so the
    # dataset is the ONE block the fused step takes.
    users = np.random.default_rng(SEED + 4).permutation(
        np.arange(n, dtype=np.int32) % E)
    Xf, Xr, users, y = make_glmix_arrays(n, d, d_re, E, SEED + 2, users=users)
    rng = np.random.default_rng(SEED + 3)
    w = (rng.normal(size=d) / np.sqrt(d)).astype(np.float32)
    v = rng.normal(size=d).astype(np.float32)
    off = (0.1 * rng.normal(size=n)).astype(np.float32)
    wt = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    yj, offj, wtj = jnp.asarray(y), jnp.asarray(off), jnp.asarray(wt)
    wj, vj = jnp.asarray(w), jnp.asarray(v)
    results = {}
    checks = _Expectations("kernels")

    def compare(key, errs, tol, **extra):
        results[key] = dict(err_vs_highest=errs, tolerance=tol, **extra)
        _log(f"kernel {key}: {results[key]}")
        checks.expect(
            all(np.isfinite(e) and e <= tol for e in errs.values()),
            f"{key}: {errs} above {tol}")

    # --- is block_until_ready a fence? Time a long dependent chain both
    # ways; if it returned at enqueue, the first time would be ~0.
    X32 = jnp.asarray(Xf)

    @jax.jit
    def chain(p, X):
        def body(_, p):
            g = jnp.tanh(X @ p) @ X
            return g / jnp.maximum(jnp.linalg.norm(g), 1.0)
        return jax.lax.fori_loop(0, 40, body, p)

    w1, w2 = jax.block_until_ready((wj * 1.01, wj * 1.02))
    jax.block_until_ready(chain(wj, X32))  # compiles
    t0 = time.perf_counter()
    out = chain(w1, X32)
    t_enqueue = time.perf_counter() - t0
    jax.block_until_ready(out)
    t_bur = time.perf_counter() - t0
    t0 = time.perf_counter()
    np.asarray(chain(w2, X32))
    t_host = time.perf_counter() - t0
    results["fence"] = dict(enqueue_s=t_enqueue, block_until_ready_s=t_bur,
                            host_transfer_s=t_host)
    _log(f"fence: the call returned after {t_enqueue * 1e3:.2f} ms, "
         f"block_until_ready after {t_bur * 1e3:.1f} ms; the same chain timed "
         f"to a host copy of its result took {t_host * 1e3:.1f} ms")
    _check(t_bur >= 0.8 * t_host and t_enqueue <= 0.5 * t_bur,
           "block_until_ready did not wait for the device")

    # --- fixed-effect kernels
    for name, X in (("f32", X32), ("bf16", X32.astype(jnp.bfloat16))):
        tol = TOL["kernel_f32" if name == "f32" else "kernel_bf16"]
        Xr32 = X.astype(jnp.float32)  # the values the kernel really reads

        @jax.jit
        def ref_vg(wv, Xv):
            z = jnp.dot(Xv, wv, precision=HIGHEST) + offj
            val = jnp.sum(wtj * LogisticLoss.value(z, yj))
            dz = wtj * LogisticLoss.dz(z, yj)
            return val, jnp.dot(dz, Xv, precision=HIGHEST), z

        @jax.jit
        def xla_vg(wv, Xv):  # the product's XLA path: default precision
            z = Xv @ wv + offj
            dz = wtj * LogisticLoss.dz(z, yj)
            return jnp.sum(wtj * LogisticLoss.value(z, yj)), Xv.T @ dz, z

        val_r, grad_r, z_r = ref_vg(wj, Xr32)
        val_x, grad_x, z_x = xla_vg(wj, Xr32)
        for rm in (False, True):
            fn = lambda wv, Xv: fused_data_value_and_grad(  # noqa: E731
                LogisticLoss, wv, Xv, yj, offj, wtj, return_margins=rm)
            _not_interpreted(fn, wj, X)
            outs = jax.jit(fn)(wj, X)
            jax.block_until_ready(outs)
            errs = dict(value=_rel_err(outs[0], val_r),
                        grad=_rel_err(outs[1], grad_r))
            if rm:
                errs["margins"] = _rel_err(outs[2], z_r)
            compare(
                f"value_and_grad[{name},margins={rm}]", errs, tol,
                xla_default_err_vs_highest=dict(
                    value=_rel_err(val_x, val_r), grad=_rel_err(grad_x, grad_r),
                    margins=_rel_err(z_x, z_r)),
            )

        d2 = wtj * LogisticLoss.dzz(z_r, yj)
        hv_r = jax.jit(lambda vv, Xv: jnp.dot(
            d2 * jnp.dot(Xv, vv, precision=HIGHEST), Xv, precision=HIGHEST
        ))(vj, Xr32)
        hv_x = jax.jit(lambda vv, Xv: Xv.T @ (d2 * (Xv @ vv)))(vj, Xr32)
        fn = lambda vv, Xv: fused_data_hvp(vv, Xv, d2)  # noqa: E731
        _not_interpreted(fn, vj, X)
        hv = jax.jit(fn)(vj, X)
        compare(f"hvp[{name}]", dict(hv=_rel_err(hv, hv_r)), tol,
                xla_default_err_vs_highest=_rel_err(hv_x, hv_r))
        del Xr32

    # --- one fused GLMix step on bf16 X: what the benchmark times first
    ds = build_random_effect_dataset(
        users, Xr, y, np.ones(n, np.float32), E,
        RandomEffectDataConfig(re_type="userId", feature_shard="re"),
    )
    (block,) = ds.blocks
    fe_cfg = OptimizerConfig(max_iter=30, track_history=False)
    re_cfg = OptimizerConfig(max_iter=8, tol=1e-6, track_history=False)
    re_obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0)
    fe_batch = LabeledBatch(yj, X32.astype(jnp.bfloat16))
    del X32
    Xr_j, users_j = jnp.asarray(Xr), jnp.asarray(users)
    step_out = {}
    for use_pallas in (True, False):
        fe_obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0,
                              intercept_index=0, use_pallas=use_pallas)
        step = jax.jit(glmix_train_step(fe_obj, re_obj, fe_cfg, re_cfg))
        args = (jnp.zeros(d, jnp.float32), jnp.zeros((E, d_re), jnp.float32),
                fe_batch, block, Xr_j, users_j)
        if use_pallas:
            _check("tpu_custom_call" in step.lower(*args).as_text(),
                   "glmix_train_step(use_pallas=True) lowered without Mosaic")
        t0 = time.perf_counter()
        out = jax.block_until_ready(step(*args))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(step(*args))
        step_out[use_pallas] = dict(out=out, first_s=first,
                                    second_s=time.perf_counter() - t0)
    w_p, coefs_p, scores_p, fe_evals, re_visits = step_out[True]["out"]
    w_x, coefs_x, scores_x, _, _ = step_out[False]["out"]
    _check(w_p.shape == (d,) and coefs_p.shape == (E, d_re)
           and scores_p.shape == (n,), "glmix_train_step: output shapes")
    _check(bool(jnp.all(jnp.isfinite(w_p)) & jnp.all(jnp.isfinite(coefs_p))
                & jnp.all(jnp.isfinite(scores_p))),
           "glmix_train_step: non-finite output")
    ll_p = _logloss_sum(np.asarray(scores_p), y)
    ll_x = _logloss_sum(np.asarray(scores_x), y)
    ll_0 = n * float(np.log(2.0))
    results["glmix_train_step"] = dict(
        fe_x_passes=int(fe_evals), re_sample_visits=int(re_visits),
        logloss_pallas=ll_p, logloss_xla=ll_x, logloss_at_zero=ll_0,
        w_rel_vs_xla=_rel_err(w_p, w_x),
        first_call_s={k: round(v["first_s"], 2) for k, v in step_out.items()},
        second_call_s={k: round(v["second_s"], 3) for k, v in step_out.items()},
    )
    _log(f"glmix_train_step: {results['glmix_train_step']}")
    checks.expect(ll_p < 0.9 * ll_0,
                  "glmix_train_step: the loss did not go down")
    checks.expect(abs(ll_p - ll_x) / ll_x <= TOL["train_step_rel"],
                  f"glmix_train_step: Pallas log-loss {ll_p} vs XLA {ll_x}")
    checks.settle()
    results["compile"] = clock.snapshot()
    results["device"] = device
    return results


def child_four_chips() -> dict:
    """One process over four chips: the entity-sharded coordinate, the
    fused pjit step and the device-sharded hot tables, each against the
    same code on one chip."""
    device = _require_tpu()
    _check(device["count"] >= 4, f"four_chips needs 4 devices, found {device}")
    import jax
    import jax.numpy as jnp

    from photon_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    clock = _CompileClock()
    from photon_tpu.algorithm.sharded_random_effect import (
        ShardedRandomEffectCoordinate,
    )
    from photon_tpu.algorithm.solve_cache import SolveCache
    from photon_tpu.data.batch import LabeledBatch
    from photon_tpu.data.game_data import GameBatch
    from photon_tpu.data.index_map import EntityIndex
    from photon_tpu.data.random_effect import (
        RandomEffectDataConfig, build_random_effect_dataset,
    )
    from photon_tpu.estimators.game_transformer import GameTransformer
    from photon_tpu.models.coefficients import Coefficients
    from photon_tpu.models.game import (
        FixedEffectModel, GameModel, RandomEffectModel,
    )
    from photon_tpu.models.glm import GeneralizedLinearModel
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optim.common import OptimizerConfig
    from photon_tpu.optim.factory import OptimizerSpec
    from photon_tpu.parallel.entity_shard import build_shard_plan
    from photon_tpu.parallel.mesh import make_mesh
    from photon_tpu.parallel.train_step import (
        game_entity_sharded_train_step, stack_shard_blocks,
    )
    from photon_tpu.serve import HotColdEntityStore
    from photon_tpu.types import OptimizerType, TaskType

    s = SIZES
    devs = jax.devices()
    out = {"device": device}
    checks = _Expectations("four chips")
    expect = checks.expect

    # --- (a) CD with ShardedRandomEffectCoordinate, 1 chip vs 4 chips
    rng = np.random.default_rng(SEED + 4)
    E, d_re = s["multi_entities"], s["multi_d_re"]
    counts = rng.integers(*s["multi_rows"], size=E)
    eids = np.repeat(np.arange(E, dtype=np.int32), counts)
    n = eids.size
    Xr = rng.normal(size=(n, d_re)).astype(np.float32)
    Xr[:, 0] = 1.0
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    ones = np.ones(n, np.float32)
    offsets = jnp.asarray(0.25 * np.sin(np.arange(n, dtype=np.float32)))
    batch = GameBatch(
        label=jnp.asarray(y), offset=jnp.zeros(n, jnp.float32),
        weight=jnp.asarray(ones), features={"re": jnp.asarray(Xr)},
        entity_ids={"userId": jnp.asarray(eids)},
    )
    cfg = RandomEffectDataConfig(
        re_type="userId", feature_shard="re",
        shape_bucketing=True, subspace_projection=False,
    )
    warmup, steady = 2, 3
    coefs, sharded = {}, {}
    for nd in (1, 4):
        cache = SolveCache(donate=True)
        coord = ShardedRandomEffectCoordinate.build(
            coordinate_id="per_user", entity_ids=eids, features=Xr, label=y,
            weight=ones, num_entities=E, config=cfg,
            task=TaskType.LOGISTIC_REGRESSION,
            objective=GLMObjective(loss=LogisticLoss, l2_weight=0.5),
            optimizer_spec=OptimizerSpec(optimizer=OptimizerType.NEWTON,
                                         max_iter=4, tol=1e-9),
            devices=devs[:nd], solve_cache=cache,
        )
        model, walls, retraces = None, [], []
        for it in range(warmup + steady):
            coord.begin_cd_pass(it)
            mark = cache.trace_mark()
            t0 = time.perf_counter()
            model, _ = coord.train(batch, offsets, model)
            walls.append(time.perf_counter() - t0)
            retraces.append(cache.traces_since(mark))
        placed = sorted({
            d.id for m in coord._shard_models
            for d in m.coefficients.devices()
        })
        coefs[nd] = np.asarray(model.coefficients, np.float32)
        sharded[nd] = dict(
            shard_coefficient_devices=placed,
            retraces_per_pass=[int(r) for r in retraces],
            pass_walls_s=[round(w_, 4) for w_ in walls],
            steady_wall_s=round(min(walls[warmup:]), 4),
            wall_samples_per_s=round(n / min(walls[warmup:]), 1),
        )
        _log(f"four chips: sharded coordinate on {nd} chip(s): {sharded[nd]}")
        expect(len(placed) == nd,
               f"shard coefficients sit on devices {placed}, wanted {nd}")
        expect(sum(retraces[warmup:]) == 0,
               f"retraces after warm-up on {nd} chip(s): {retraces}")
    expect(bool(np.array_equal(coefs[1], coefs[4])),
           "sharded coordinate: 4-chip coefficients differ from 1-chip")
    out["sharded_coordinate"] = dict(n_samples=int(n), entities=E,
                                     bit_equal_1_vs_4=True, **{
                                         f"chips_{k}": v
                                         for k, v in sharded.items()})

    # --- (b) the fused pjit step on a 1-device and a 4-device mesh
    S = 8
    Ef, rows_per = s["fused_entities"], s["fused_rows_per"]
    d_fe, d_re_f = s["fused_d_fe"], s["fused_d_re"]
    nf = Ef * rows_per
    rng = np.random.default_rng(SEED + 5)
    eids_f = np.repeat(np.arange(Ef, dtype=np.int32), rows_per)[
        rng.permutation(nf)]
    Xf = rng.normal(size=(nf, d_fe)).astype(np.float32)
    Xr_f = rng.normal(size=(nf, d_re_f)).astype(np.float32)
    y_f = (rng.uniform(size=nf) < 0.5).astype(np.float32)
    w_f = np.ones(nf, np.float32)
    plan = build_shard_plan(Ef, n_shards=S, seed=0)
    cfg_f = RandomEffectDataConfig(
        re_type="userId", feature_shard="re",
        shape_bucketing=True, subspace_projection=False,
    )
    blocks = [
        build_random_effect_dataset(se, Xr_f, y_f, w_f, int(plan.counts[k]),
                                    cfg_f).blocks[0]
        for k, se in enumerate(plan.shard_sample_entities(eids_f))
    ]
    stacked = stack_shard_blocks(blocks)
    E_s = stacked.entity_idx.shape[1]
    obj = GLMObjective(loss=LogisticLoss, l2_weight=1.0)
    fused = {}
    for nd in (1, 4):
        mesh = make_mesh(n_data=nd, devices=devs[:nd])
        step, place = game_entity_sharded_train_step(
            mesh, obj, obj, OptimizerConfig(max_iter=10, tol=1e-8),
            OptimizerConfig(max_iter=4, tol=1e-9),
        )
        fe = LabeledBatch(label=jnp.asarray(y_f), features=jnp.asarray(Xf),
                          offset=jnp.zeros(nf, jnp.float32),
                          weight=jnp.asarray(w_f))
        args = place(
            np.zeros(d_fe, np.float32), np.zeros((S, E_s, d_re_f), np.float32),
            fe, stacked, Xr_f, plan.shard_of[eids_f].astype(np.int32),
            plan.local_of[eids_f].astype(np.int32),
        )
        wf, rc = args[0], args[1]
        wf, rc, _, _, _ = step(wf, rc, *args[2:])  # compiles
        jax.block_until_ready(rc)
        t0 = time.perf_counter()
        wf, rc, scores, _, _ = step(wf, rc, *args[2:])
        jax.block_until_ready(rc)
        wall = time.perf_counter() - t0
        fused[nd] = dict(rc=np.asarray(rc, np.float32),
                         wf=np.asarray(wf, np.float32), wall_s=wall,
                         rc_devices=len(rc.sharding.device_set))
        _log(f"four chips: fused step on a {nd}-device mesh: "
             f"{wall * 1e3:.1f} ms, coefficient slab over "
             f"{fused[nd]['rc_devices']} device(s)")
        expect(fused[nd]["rc_devices"] == nd,
               "fused step: coefficient slab not spread over the mesh")
    drift = float(np.abs(fused[4]["rc"] - fused[1]["rc"]).max())
    expect(drift <= TOL["fused_mesh_abs"],
           f"fused step: 4-device mesh drifts {drift} from 1 device")
    out["fused_step"] = dict(
        n_samples=int(nf), max_abs_drift_1_vs_4=drift,
        bit_equal_1_vs_4=bool(np.array_equal(fused[1]["rc"], fused[4]["rc"])),
        wall_s={k: round(v["wall_s"], 4) for k, v in fused.items()},
    )

    # --- (c) HotColdEntityStore(device_shards=4) scoring
    d_a, Es = 32, s["multi_entities"]
    rng = np.random.default_rng(SEED + 6)
    model = GameModel({
        "global": FixedEffectModel(GeneralizedLinearModel(
            Coefficients(np.linspace(-1, 1, d_a).astype(np.float32)),
            TaskType.LOGISTIC_REGRESSION), "shardA"),
        "per_user": RandomEffectModel(
            rng.normal(size=(Es, d_re)).astype(np.float32), "userId", "shardB",
            TaskType.LOGISTIC_REGRESSION),
    })
    eidx = EntityIndex()
    for e in range(Es):
        eidx.intern(f"user{e}")
    m = 512
    users = rng.integers(0, Es, size=m)
    xa = rng.normal(size=(m, d_a)).astype(np.float32)
    xb = rng.normal(size=(m, d_re)).astype(np.float32)

    def score_via(store):
        transformer = GameTransformer(store.scoring_model())
        slots = store.resolve("userId", [f"user{u}" for u in users])
        b = GameBatch(
            label=jnp.zeros(m, jnp.float32), offset=jnp.zeros(m, jnp.float32),
            weight=jnp.ones(m, jnp.float32),
            features={"shardA": jnp.asarray(xa), "shardB": jnp.asarray(xb)},
            entity_ids={"userId": jnp.asarray(slots, jnp.int32)},
        )
        b = jax.device_put(b, store.batch_sharding)
        first = np.asarray(transformer.transform(b), np.float32)
        again = np.asarray(transformer.transform(b), np.float32)
        expect(bool(np.array_equal(first, again)), "store: unstable scores")
        return first, transformer.trace_count

    plain = HotColdEntityStore(model, {"userId": eidx}, hot_bytes=1 << 30)
    shard4 = HotColdEntityStore(model, {"userId": eidx}, hot_bytes=1 << 30,
                                device_shards=4)
    want, _ = score_via(plain)
    got, traces = score_via(shard4)
    table = shard4.group("userId").tables["per_user"]
    n_table_devices = len(table.sharding.device_set)
    _log(f"four chips: hot table {table.shape} over {n_table_devices} devices; "
         f"scorer traced {traces} time(s) for 2 calls")
    store_equal = bool(np.array_equal(got, want))
    store_diff = float(np.abs(got - want).max())
    expect(n_table_devices == 4, "store: hot table not on four devices")
    expect(store_equal,
           f"store: device-sharded scores differ from the one-chip store "
           f"({int(np.sum(got != want))}/{m} rows, max abs {store_diff:.2e})")
    expect(traces == 1, f"store: scorer traced {traces} times")
    out["hot_tables"] = dict(table_devices=n_table_devices,
                             bit_equal_vs_one_chip=store_equal,
                             max_abs_diff=store_diff, scorer_traces=traces)
    out["compile"] = clock.snapshot()
    checks.settle()
    return out


_CHILDREN = {
    "device": child_device,
    "full_width": child_full_width,
    "kernels": child_kernels,
    "four_chips": child_four_chips,
}


def child_main(name: str) -> None:
    os.makedirs(WORK, exist_ok=True)
    _write_result(name, _CHILDREN[name]())


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--phase", choices=sorted(_CHILDREN),
                        help="run one in-process phase (how the parent "
                             "starts its children); it asserts the TPU too")
    cli = parser.parse_args()
    if cli.phase:
        child_main(cli.phase)
    else:
        main()
