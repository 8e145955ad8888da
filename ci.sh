#!/usr/bin/env bash
# CI entry point (role of the reference's Travis matrix, .travis.yml:30-34:
# rat | unit | integration). Everything runs on a virtual 8-device CPU mesh
# (tests/conftest.py forces it), so no accelerator is needed for correctness.
#
# Usage: ./ci.sh [static|unit|dryrun|telemetry|active-set|ooc|serve|faults|soak|fleet|rollout|streaming|exhaustion|obs|quality|experiments|install|kernels|all]   (default: all)
set -euo pipefail
cd "$(dirname "$0")"

stage="${1:-all}"

run_static() {
    # Fast fail-first pass: import-time breakage (syntax errors, bad
    # top-level references) surfaces in seconds instead of after the
    # 800s pytest stage.
    echo "== static: compileall + pyflakes =="
    python -m compileall -q photon_tpu bench.py bench_configs.py
    if python -c "import pyflakes" 2>/dev/null; then
        python -m pyflakes photon_tpu bench.py bench_configs.py
        echo "   pyflakes OK"
    else
        echo "   pyflakes not installed; compileall only"
    fi
}

run_native() {
    # Source-only native dir (no committed binaries, VERDICT r3 #9): a fresh
    # clone compiles both libraries here; runtime mtime-recompile remains a
    # dev convenience only.
    echo "== native: g++ build of avro_decode + index_store =="
    for lib in avro_decode index_store; do
        g++ -O2 -std=c++17 -shared -fPIC \
            -o "photon_tpu/native/lib${lib}.so" \
            "photon_tpu/native/${lib}.cpp"
        echo "   lib${lib}.so built"
    done
}

run_unit() {
    echo "== unit + integration tests (virtual 8-device CPU mesh) =="
    python -m pytest tests/ -x -q
}

run_dryrun() {
    echo "== multichip dryrun (8-device mesh compile + run + parity) =="
    python __graft_entry__.py
    # Device-sharded coordinate gate: sharded-at-8 vs sharded-at-1 RE
    # coefficients must be bit-identical, with zero post-warmup solve-cache
    # retraces at both device counts (subprocess per count — the virtual
    # mesh width must be fixed before the first jax touch).
    echo "== multichip gate (sharded-vs-single parity + zero retrace) =="
    tmp="$(mktemp -d)"
    for n in 1 8; do
        python bench.py --multichip-worker "$n" "$tmp/rung$n"
    done
    python - "$tmp" <<'EOF'
import json, sys
import numpy as np

tmp = sys.argv[1]
c1 = np.load(f"{tmp}/rung1.npy")
c8 = np.load(f"{tmp}/rung8.npy")
assert np.array_equal(c1, c8), "sharded-at-8 != sharded-at-1 (bit parity)"
for n in (1, 8):
    with open(f"{tmp}/rung{n}.json") as f:
        r = json.load(f)
    assert r["post_warmup_retraces"] == 0, (n, r["retraces_per_pass"])
f1 = np.load(f"{tmp}/rung1.fused.npy")
f8 = np.load(f"{tmp}/rung8.fused.npy")
drift = float(np.abs(f1 - f8).max())
assert drift <= 1e-3, f"fused-step cross-mesh drift {drift}"
print(f"   parity OK, retraces 0, fused drift {drift:.2e}")
EOF
    rm -rf "$tmp"
}

run_telemetry() {
    # End-to-end smoke of the unified run report: train a tiny GLM with
    # --telemetry-out and assert the JSONL parses, carries at least one span
    # per CD iteration (the λ sweep), the solve-cache counters, and no
    # NaN/Inf anywhere in the artifact.
    echo "== telemetry: train_glm --telemetry-out smoke =="
    tmp="$(mktemp -d)"
    python - "$tmp" <<'EOF'
import sys, os, json, collections
import numpy as np

tmp = sys.argv[1]
rng = np.random.default_rng(3)
lines = []
for _ in range(200):
    x = rng.normal(size=5)
    y = 1 if rng.uniform() < 1 / (1 + np.exp(-(x[0] - x[1]))) else -1
    feats = " ".join(f"{j + 1}:{x[j]:.4f}" for j in range(5))
    lines.append(f"{y:+d} {feats}")
data = os.path.join(tmp, "train.txt")
with open(data, "w") as f:
    f.write("\n".join(lines))

from photon_tpu.cli import train_glm

tele = os.path.join(tmp, "run.jsonl")
args = train_glm.build_parser().parse_args([
    "--training-data", data, "--format", "libsvm",
    "--output-dir", os.path.join(tmp, "out"),
    "--regularization-weights", "0.1,1",
    "--max-iterations", "10",
    "--telemetry-out", tele,
])
train_glm.run(args)

text = open(tele).read()
assert "NaN" not in text and "Infinity" not in text, "non-finite leaked"
from photon_tpu.obs import validate_record
records = [json.loads(line) for line in text.splitlines()]
for rec in records:
    validate_record(rec)
kinds = collections.Counter(r["record"] for r in records)
assert kinds["meta"] == 1 and kinds["env"] == 1, kinds
cd_rows = [r for r in records if r["record"] == "coordinate_descent"]
spans = [r for r in records if r["record"] == "span"]
solve_spans = [s for s in spans if s["name"].startswith("glm/lambda")
               and s["name"].endswith("/solve")]
assert len(cd_rows) == 2, cd_rows
# ≥1 span per CD iteration (train_glm's λ sweep is its coordinate sequence)
assert len(solve_spans) >= len(cd_rows), (solve_spans, cd_rows)
cache = {r["metric"]: r["value"] for r in records
         if r["record"] == "metric" and r["metric"].startswith("solve_cache_")}
assert cache.get("solve_cache_calls") == 2, cache
assert "solve_cache_hits" in cache and "solve_cache_traces" in cache, cache
print(f"   {len(records)} records, {len(spans)} spans, "
      f"solve_cache={ {k: v for k, v in sorted(cache.items())} } OK")
EOF
    rm -rf "$tmp"
}

run_active_set() {
    # Gated-vs-full smoke for the convergence-gated active-set RE passes:
    # a 3-pass synthetic GAME workload run twice must reach the SAME final
    # objective (rtol 1e-5), skip entities from pass 2 on, and keep the
    # solve-cache trace counter identical to the full run. Timing is NOT
    # asserted here (CI machines vary); bench.py --active-set-ab measures
    # the wall-clock side.
    echo "== active-set: 3-pass gated-vs-full parity smoke =="
    python - <<'EOF'
import numpy as np
import jax.numpy as jnp

from photon_tpu.algorithm.coordinate_descent import CoordinateDescent
from photon_tpu.algorithm.fixed_effect import FixedEffectCoordinate
from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
from photon_tpu.algorithm.solve_cache import SolveCache
from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.random_effect import (
    RandomEffectDataConfig, build_random_effect_dataset,
)
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.types import OptimizerType, TaskType
from photon_tpu.utils.events import EventEmitter

rng = np.random.default_rng(7)
E, d_re, d_fe = 96, 6, 5
counts = rng.integers(37, 47, size=E)
eids = np.repeat(np.arange(E, dtype=np.int32), counts)
n = eids.size
Xr = rng.normal(size=(n, d_re)).astype(np.float32)
Xr[eids % 3 != 0] = 0.0  # cold cohort: retires from pass 2 deterministically
Xf = rng.normal(size=(n, d_fe)).astype(np.float32)
Xf[:, 0] = 1.0
y = (rng.uniform(size=n) < 0.5).astype(np.float32)
w = np.ones(n, np.float32)
batch = GameBatch(
    label=jnp.asarray(y), offset=jnp.zeros(n, jnp.float32),
    weight=jnp.asarray(w),
    features={"global": jnp.asarray(Xf), "re": jnp.asarray(Xr)},
    entity_ids={"userId": jnp.asarray(eids)},
)
ds = build_random_effect_dataset(
    eids, Xr, y, w, E,
    RandomEffectDataConfig(re_type="userId", feature_shard="re",
                           shape_bucketing=True, subspace_projection=False),
    slab_budget=24 * 48 * d_re * 4,  # four same-geometry blocks to compact
)

def run(active):
    cache = SolveCache(donate=True)
    fe = FixedEffectCoordinate(
        coordinate_id="global", feature_shard="global",
        task=TaskType.LOGISTIC_REGRESSION,
        objective=GLMObjective(loss=LogisticLoss, l2_weight=1.0,
                               intercept_index=0),
        optimizer_spec=OptimizerSpec(optimizer=OptimizerType.LBFGS,
                                     max_iter=50, tol=1e-9),
        solve_cache=cache,
    )
    re = RandomEffectCoordinate(
        coordinate_id="per_user", dataset=ds,
        task=TaskType.LOGISTIC_REGRESSION,
        objective=GLMObjective(loss=LogisticLoss, l2_weight=0.5),
        optimizer_spec=OptimizerSpec(optimizer=OptimizerType.NEWTON,
                                     max_iter=25, tol=1e-9),
        solve_cache=cache, active_set=active, convergence_tol=1e-4,
    )
    events = []
    em = EventEmitter(); em.register(events.append)
    cd = CoordinateDescent(coordinates={"global": fe, "per_user": re},
                           update_sequence=["global", "per_user"],
                           num_iterations=3)
    res = cd.run(batch, profile=True, emitter=em)
    total = np.asarray(res.model.get("global").score(batch)
                       + res.model.get("per_user").score(batch))
    obj = float(np.mean(w * np.logaddexp(0.0, -(2 * y - 1) * total)))
    stats = [e.payload["active_set"] for e in events
             if e.name == "PhotonOptimizationLogEvent"
             and e.payload.get("coordinate") == "per_user"]
    return obj, cache.stats.traces, stats

obj_f, traces_f, _ = run(False)
obj_g, traces_g, stats = run(True)
rel = abs(obj_g - obj_f) / max(abs(obj_f), 1e-30)
assert rel <= 1e-5, f"parity violated: {obj_f} vs {obj_g} (rel {rel:.3g})"
assert traces_f == traces_g, f"trace counters differ: {traces_f} vs {traces_g}"
skipped = [s["entities_skipped"] for s in stats]
assert skipped[0] == 0 and all(s > 0 for s in skipped[1:]), skipped
print(f"   objective {obj_g:.6f} (rel {rel:.1e}), traces {traces_g}, "
      f"skipped/pass {skipped} OK")
EOF
}

run_ooc() {
    # Out-of-core residency smoke: the same RE coordinate trained twice —
    # fully resident and under a quarter-footprint device budget — must
    # produce BIT-identical coefficients (objective rel ≤ 1e-6 follows),
    # see at least 2 eviction waves, and compile nothing after the warm-up
    # pass. Timing is NOT asserted here; bench.py --out-of-core-ab
    # measures the throughput-retention and overlap side.
    echo "== ooc: quarter-budget residency parity smoke =="
    python - <<'EOF'
import numpy as np
import jax.numpy as jnp

from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
from photon_tpu.algorithm.re_store import block_device_cost
from photon_tpu.algorithm.solve_cache import SolveCache
from photon_tpu.data.game_data import GameBatch
from photon_tpu.data.random_effect import (
    RandomEffectDataConfig, build_random_effect_dataset,
)
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.ops.objective import GLMObjective
from photon_tpu.optim.factory import OptimizerSpec
from photon_tpu.types import OptimizerType, TaskType

rng = np.random.default_rng(7)
E, d_re = 96, 6
counts = rng.integers(37, 47, size=E)
eids = np.repeat(np.arange(E, dtype=np.int32), counts)
n = eids.size
Xr = rng.normal(size=(n, d_re)).astype(np.float32)
y = (rng.uniform(size=n) < 0.5).astype(np.float32)
w = np.ones(n, np.float32)
batch = GameBatch(
    label=jnp.asarray(y), offset=jnp.zeros(n, jnp.float32),
    weight=jnp.asarray(w), features={"re": jnp.asarray(Xr)},
    entity_ids={"userId": jnp.asarray(eids)},
)
cfg = RandomEffectDataConfig(re_type="userId", feature_shard="re",
                             shape_bucketing=True, subspace_projection=False)
SLAB = 24 * 48 * d_re * 4  # four same-geometry blocks, so a budget evicts

def run(budget, passes=4):
    cache = SolveCache(donate=True)
    coord = RandomEffectCoordinate(
        coordinate_id="per_user",
        dataset=build_random_effect_dataset(eids, Xr, y, w, E, cfg,
                                            slab_budget=SLAB),
        task=TaskType.LOGISTIC_REGRESSION,
        objective=GLMObjective(loss=LogisticLoss, l2_weight=0.5),
        optimizer_spec=OptimizerSpec(optimizer=OptimizerType.NEWTON,
                                     max_iter=25, tol=1e-9),
        solve_cache=cache, device_budget_bytes=budget,
    )
    model, warm_mark = None, None
    for it in range(passes):
        coord.begin_cd_pass(it)
        model, _ = coord.train(batch, None, model)
        if it == 0:
            warm_mark = cache.trace_mark()
    return model, coord, cache.traces_since(warm_mark)

footprint = sum(block_device_cost(b) for b in
                build_random_effect_dataset(eids, Xr, y, w, E, cfg,
                                            slab_budget=SLAB).blocks)
ref, _, ref_post = run(None)
ooc, coord, ooc_post = run(footprint // 4)
st = coord.last_residency_stats
assert np.array_equal(np.asarray(ref.coefficients),
                      np.asarray(ooc.coefficients)), "coefficients diverged"
s_ref, s_ooc = np.asarray(ref.score(batch)), np.asarray(ooc.score(batch))
obj = lambda s: float(np.mean(w * np.logaddexp(0.0, -(2 * y - 1) * s)))
rel = abs(obj(s_ooc) - obj(s_ref)) / max(abs(obj(s_ref)), 1e-30)
assert rel <= 1e-6, f"objective parity violated: rel={rel:.3g}"
waves = sum(1 for e in st["pass_evictions"] if e > 0)
assert waves >= 2, f"expected >=2 eviction waves, got {st['pass_evictions']}"
assert ooc_post == 0, f"post-warmup retraces: {ooc_post}"
assert st["peak_bytes"] <= st["effective_budget_bytes"], st
print(f"   footprint {footprint} B @ budget {footprint // 4} B: "
      f"bit-identical coefs, rel {rel:.1e}, evictions/pass "
      f"{st['pass_evictions']}, post-warmup traces {ooc_post} OK")
EOF
}

run_serve() {
    # Online-serving smoke: train a tiny GAME model, batch-score it with the
    # game_scoring driver, then push the SAME rows through the in-process
    # serving engine from many threads. Asserts (1) bit-parity — every
    # micro-batched score equals the batch driver's, atol=0; (2) the
    # in-trace retrace counter stays 0 after warm-up; (3) backpressure
    # sheds with the explicit error.
    echo "== serve: concurrent micro-batch parity + zero-retrace smoke =="
    tmp="$(mktemp -d)"
    python - "$tmp" <<'EOF'
import os, sys, threading
import numpy as np

tmp = sys.argv[1]
rng = np.random.default_rng(23)

from photon_tpu.io.avro import write_avro_records
from photon_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA

def write_fixture(path, n, d=6, n_users=8):
    w = np.linspace(-1, 1, d)
    bias = np.linspace(-2, 2, n_users)
    records = []
    for i in range(n):
        x = rng.normal(size=d)
        u = i % n_users
        y = float(rng.uniform() < 1 / (1 + np.exp(-(x @ w + bias[u]))))
        records.append(dict(
            uid=str(i), label=y,
            features=[{"name": f"x{j}", "term": "", "value": float(x[j])}
                      for j in range(d)],
            metadataMap={"userId": f"u{u}"}, weight=1.0, offset=0.0))
    write_avro_records(path, TRAINING_EXAMPLE_SCHEMA, records)

train, valid = os.path.join(tmp, "train.avro"), os.path.join(tmp, "valid.avro")
# 48 users > the engine's 32-row hot floor, so the hot store actually runs
# its LRU promote/demote path (8 users would pin the whole table).
write_fixture(train, 600, n_users=48)
write_fixture(valid, 256, n_users=48)

from photon_tpu.cli import game_scoring, game_training

out = os.path.join(tmp, "out")
game_training.run(game_training.build_parser().parse_args([
    "--input-paths", train, "--output-dir", out,
    "--feature-shard-configurations", "name=globalShard",
    "--coordinate-configurations",
    "name=global,feature.shard=globalShard,optimizer=LBFGS,reg.weights=1",
    "name=perUser,feature.shard=globalShard,random.effect.type=userId,reg.weights=1",
    "--update-sequence", "global,perUser",
]))
score_out = os.path.join(tmp, "scores")
game_scoring.run(game_scoring.build_parser().parse_args([
    "--input-paths", valid, "--output-dir", score_out,
    "--feature-shard-configurations", "name=globalShard",
    "--model-input-dir", os.path.join(out, "best"),
    "--model-artifacts-dir", out,
]))
from photon_tpu.io.scores import load_scores
batch_score = {r["uid"]: np.float32(r["predictionScore"])
               for r in load_scores(os.path.join(score_out, "scores.avro"))}

# Same rows, served: dense feature vectors from the same reader + index maps.
from photon_tpu.cli.common import parse_feature_shard_config
from photon_tpu.data.index_map import EntityIndex, IndexMap
from photon_tpu.io.data_reader import read_merged
from photon_tpu.serve import ScoreRequest, ServeConfig, load_engine

imap = IndexMap.load(os.path.join(out, "index-map-globalShard.json"))
eidx = EntityIndex.load(os.path.join(out, "entity-index-userId.json"))
batch, _, _ = read_merged(
    [valid], parse_feature_shard_config("name=globalShard"),
    index_maps={"globalShard": imap},
    entity_id_columns={"userId": "userId"},
    entity_indexes={"userId": eidx}, intern_new_entities=False,
)
X = np.asarray(batch.features["globalShard"])
eids = np.asarray(batch.entity_ids["userId"])
uids = [str(int(u)) for u in np.asarray(batch.uid)]
n = X.shape[0]

engine = load_engine(
    os.path.join(out, "best"), artifacts_dir=out,
    config=ServeConfig(max_batch_size=32, max_delay_ms=5.0,
                       # force the LRU path: budget far below the full table
                       hot_bytes=1),
)
assert not engine.stats()["store"]["userId"]["pinned"], engine.stats()

results = [None] * n
def worker(lo, hi):
    futs = [(i, engine.submit(ScoreRequest(
        {"globalShard": X[i]}, {"userId": int(eids[i])})))
        for i in range(lo, hi)]
    for i, f in futs:
        results[i] = np.float32(f.result(timeout=60))
threads = [threading.Thread(target=worker, args=(lo, min(lo + 16, n)))
           for lo in range(0, n, 16)]
for t in threads: t.start()
for t in threads: t.join()

exact = sum(results[i] == batch_score[uids[i]] for i in range(n))
assert exact == n, f"bit-parity: only {exact}/{n} scores exact"
assert engine.retraces_since_warmup == 0, engine.stats()

# Backpressure sheds with the explicit error (cap 1, pile on a 2nd+3rd).
from photon_tpu.serve import BackpressureError
from photon_tpu.serve.engine import ServingEngine  # noqa: F401 (doc pointer)
shed_engine = load_engine(
    os.path.join(out, "best"), artifacts_dir=out,
    config=ServeConfig(max_batch_size=1, max_delay_ms=200.0, queue_cap=1))
shed = 0
for _ in range(50):
    try:
        shed_engine.submit(ScoreRequest({"globalShard": X[0]},
                                        {"userId": int(eids[0])}))
    except BackpressureError:
        shed += 1
assert shed > 0, "queue_cap=1 under a 50-request burst must shed"
shed_engine.close()
engine.close()
print(f"   {n}/{n} scores bit-exact vs batch driver, retraces=0, "
      f"shed={shed}/50 OK")
EOF
    rm -rf "$tmp"
}

run_faults() {
    # Crash-safe resume smoke: SIGKILL the trainer mid-sweep via the
    # fault-injection harness (kill fires right after the first checkpoint
    # publish), then rerun with --resume and assert the final artifacts
    # match an uninterrupted baseline run to rel 1e-6 per λ.
    echo "== faults: SIGKILL mid-train + --resume objective parity =="
    tmp="$(mktemp -d)"
    python - "$tmp" <<'EOF'
import json, os, signal, subprocess, sys
import numpy as np

tmp = sys.argv[1]
rng = np.random.default_rng(11)
lines = []
for _ in range(120):
    x = rng.normal(size=4)
    y = 1 if rng.uniform() < 1 / (1 + np.exp(-(x[0] - x[2]))) else -1
    feats = " ".join(f"{j + 1}:{x[j]:.4f}" for j in range(4))
    lines.append(f"{y:+d} {feats}")
data = os.path.join(tmp, "train.txt")
with open(data, "w") as f:
    f.write("\n".join(lines))

def run(outdir, resume=False, plan=None):
    env = dict(os.environ)
    env.pop("PHOTON_TPU_FAULT_PLAN", None)
    if plan is not None:
        env["PHOTON_TPU_FAULT_PLAN"] = json.dumps(plan)
    cmd = [sys.executable, "-m", "photon_tpu.cli.train_glm",
           "--training-data", data, "--format", "libsvm",
           "--output-dir", outdir,
           "--checkpoint-dir", os.path.join(outdir, "ckpt"),
           "--regularization-weights", "10,1,0.1",
           "--max-iterations", "15"]
    if resume:
        cmd.append("--resume")
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)

base = os.path.join(tmp, "base")
r = run(base)
assert r.returncode == 0, r.stderr

faulted = os.path.join(tmp, "faulted")
kill_plan = {"rules": [{"site": "checkpoint.after_save", "kind": "kill",
                        "at": [0]}]}
r = run(faulted, plan=kill_plan)
assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr)

r = run(faulted, resume=True)
assert r.returncode == 0, r.stderr
assert "resuming" in (r.stdout + r.stderr)

def summary(outdir):
    with open(os.path.join(outdir, "training-summary.json")) as f:
        return json.load(f)

a, b = summary(base), summary(faulted)
assert a["best_lambda"] == b["best_lambda"], (a, b)
assert len(b["models"]) == len(a["models"]) == 3, b
worst = 0.0
for ma, mb in zip(a["models"], b["models"]):
    assert ma["lambda"] == mb["lambda"]
    rel = abs(mb["loss"] - ma["loss"]) / max(abs(ma["loss"]), 1e-30)
    worst = max(worst, rel)
    assert rel <= 1e-6, (ma, mb, rel)
print(f"   kill @ first checkpoint, resume parity: "
      f"worst per-λ loss rel {worst:.2e} (≤ 1e-6) OK")
EOF
    rm -rf "$tmp"
}

run_soak() {
    # Multi-process serving smoke: forked HTTP workers + scorer process
    # under mixed-tenant load with LATEST-pointer reload churn and an
    # abusive tenant. run_serve_soak asserts the PR-7 acceptance bar
    # itself: zero caller-visible errors, per-tenant fairness under abuse
    # (abuser sheds 429s, others hold p99), HTTP-vs-batch bit parity,
    # zero retraces after warm-up, and a clean SIGTERM drain (exit 0).
    echo "== soak: multi-process serve under quota + reload churn =="
    JAX_PLATFORMS=cpu python bench.py --serve-soak \
        --soak-duration 8 --soak-workers 2
    echo "   serve-soak smoke OK"
}

run_fleet() {
    # Scorer-fleet smoke: 3 consistent-hash replicas over disjoint ring
    # shards of the entity store, driven through the routing front end.
    # run_fleet_soak --fleet-smoke asserts the ISSUE 13 drill: bit parity
    # vs an in-process engine, a serve.replica_kill fault-plan SIGKILL
    # surviving with zero caller errors (shard degrades FE-only, re-homes
    # on revive), a live join + drain/leave, disjoint per-replica hit
    # rates, and fleet-global admission charging ONE token bucket. The
    # 2.2x QPS scaling bar runs in the full (non-smoke) soak only.
    echo "== fleet: 3-replica parity + kill/rejoin + fleet admission =="
    JAX_PLATFORMS=cpu python bench.py --fleet-soak --fleet-smoke
    echo "   fleet-soak smoke OK"
    # Cross-host transport drill: the same frame protocol over TCP
    # loopback with the HMAC handshake, warm shard handoff through a
    # live join AND drain (per-replica hit rate holds — no cold dip, no
    # FE-only window), a SIGKILL+revive with zero caller errors, zero
    # post-warmup retraces, and the probe set bit-identical over TCP,
    # Unix sockets, and the batch engine.
    echo "== fleet: TCP transport parity + warm shard handoff =="
    JAX_PLATFORMS=cpu python bench.py --fleet-handoff --fleet-smoke
    echo "   fleet-handoff smoke OK"
}

run_rollout() {
    # Continuous-rollout smoke: the full generation lifecycle in one
    # process — train gen-1, serve it, incremental-retrain gen-2, shadow
    # it on live traffic and promote, REFUSE a checksum-corrupted
    # generation at the validation gate, then trip the circuit breaker on
    # a promoted generation and auto-roll back to its parent (poisoned,
    # never re-promoted). run_rollout_soak asserts the ISSUE 8 bar
    # itself: zero caller-visible errors, zero retraces after warm-up,
    # and post-rollback bit parity with direct pinned scoring.
    echo "== rollout: train -> shadow -> promote -> gate-refuse -> rollback =="
    JAX_PLATFORMS=cpu python bench.py --rollout-soak
    echo "   rollout-soak smoke OK"
}

run_streaming() {
    # Streaming-freshness smoke: the full feedback -> micro-generation
    # loop live — serving lands scored requests + labels in the spool,
    # the continuous updater turns sealed segments into per-entity DELTA
    # micro-generations, and the rollout watcher shadows + promotes each
    # one under uninterrupted load. run_streaming_soak asserts the
    # ISSUE 11 bar itself: >=3 promotions, zero caller errors, zero
    # retraces, staleness p95 < 60 s, <=1% entities and <5% bytes per
    # delta, shadow bit-parity, and SIGKILL crash-resume bit-equivalence.
    echo "== streaming: feedback spool -> delta micro-generations -> promote =="
    JAX_PLATFORMS=cpu python bench.py --streaming-soak
    echo "   streaming-soak smoke OK"
    # Sharded freshness plane (ISSUE 17): 2 entity-hash-routed shard
    # workers over live-spooled traffic — composed model bit-identical to
    # the single updater, zero post-warmup retraces per shard, concurrent
    # flock'd publishes rebasing to one linear lineage. (The >=3x scaling
    # bar is asserted by the full `bench.py --updater-shard-ab`, not in
    # CI — shared boxes are too noisy to gate on a throughput ratio.)
    echo "== streaming: 2-shard updater A/B (parity + retrace + lineage) =="
    JAX_PLATFORMS=cpu python bench.py --updater-shard-ab --shard-smoke
    echo "   updater-shard-ab smoke OK"
}

run_exhaustion() {
    # Resource-exhaustion smoke: device OOM, disk-full, and host memory
    # pressure injected through training, spill, checkpoint, telemetry,
    # and serving. run_exhaustion_soak asserts the ISSUE 10 bar itself:
    # the run completes with zero caller-visible errors, coefficients and
    # scores stay bit-identical to the unconstrained fault-free run, the
    # checkpoint writer prunes-and-retries under ENOSPC, and no partial
    # artifact (*.tmp, spool-*.pkl) survives on disk.
    echo "== exhaustion: OOM + ENOSPC + RSS-pressure containment =="
    JAX_PLATFORMS=cpu python bench.py --exhaustion-soak
    echo "   exhaustion-soak smoke OK"
}

run_obs() {
    # Observability plane: W3C-style trace context propagated worker →
    # relay → replica (ONE trace, spans from ≥3 pids, correct nesting),
    # the tail-based flight recorder + /v1/traces, fleet-merged /metrics
    # with per-replica labels, metric-name aliases, and the SLO burn-rate
    # state machine (tests/test_obs_plane.py asserts the ISSUE 14 bar
    # itself). tests/test_obs_export.py covers the ISSUE 15 export loop:
    # OTLP-shaped span/metric batches vs a mock collector, retry/backoff
    # + drop-and-count on a dead collector, deterministic histogram
    # exemplars, ring-overflow accounting, and the SLO gate's
    # freeze/rollback/unfreeze cycle. Then the tracing-on vs tracing-off
    # serve A/B (now WITH the exporter shipping every traced span to a
    # live mock collector): median per-pass p99 overhead <= 5%, zero
    # post-warmup retraces, sync-free telemetry pin re-asserted. Finally
    # the SLO-breach actuation drill: injected latency burn aborts a
    # shadow candidate and rolls back a settling promotion with zero
    # caller errors, and a /metrics exemplar resolves through the CLI.
    echo "== obs: tracing + export + fleet /metrics + SLO actuation =="
    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
        tests/test_obs_plane.py tests/test_obs_export.py
    echo "   obs plane + export tests OK"
    JAX_PLATFORMS=cpu python bench.py --obs-overhead-ab
    echo "   obs overhead A/B OK"
    JAX_PLATFORMS=cpu python bench.py --slo-rollback-drill
    echo "   SLO rollback drill OK"
}

run_quality() {
    # Model-quality plane (ISSUE 18): the streaming evaluator's invariants
    # (histogram AUC within its tie bound of the exact auc_roc incl. ties
    # and single-class windows, merge == accumulate associativity, window
    # rotation monotone under clock skew), then the freshness-lift smoke:
    # live drifting traffic against fresh-delta serving vs a frozen pinned
    # baseline — measured online AUC lift must be positive, zero caller
    # errors, zero post-warmup retraces — and the quality-burn drill: an
    # injected label shift pages auc_drop and actuates a counted rollback
    # + promotion freeze through the unchanged SLO gate.
    echo "== quality: streaming evaluator unit suite =="
    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
        tests/test_quality.py
    echo "   quality evaluator tests OK"
    echo "== quality: freshness-lift smoke (lift + burn drill) =="
    JAX_PLATFORMS=cpu python bench.py --freshness-lift --smoke
    echo "   freshness-lift smoke OK"
}

run_experiments() {
    # Continuous online experiment plane (ISSUE 20): GP proposal
    # determinism + search-history serialization round-trip + crash-resume
    # from durable manifest records (tests/test_experiment.py), and the
    # GLM family audit — EVERY task type (linear, logistic, Poisson,
    # smoothed hinge) through train → serve → stream → rollout with the
    # family's own quality-plane loss (tests/test_glm_family.py). Then the
    # live smokes: the GLM-family traffic drill across all four task
    # types, and the experiment soak — a GP-driven sweep holding 4
    # concurrent shadow candidates under live traffic, quality-burn
    # poisoning of an injected regression, SIGKILL of the manager
    # mid-round resuming without re-training, and the GP winner landing
    # within tolerance of an offline exhaustive λ sweep.
    echo "== experiments: GP determinism + resume + GLM family tests =="
    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
        tests/test_experiment.py tests/test_glm_family.py
    echo "   experiment + GLM family tests OK"
    echo "== experiments: GLM family traffic smoke (all task types) =="
    JAX_PLATFORMS=cpu python bench.py --glm-family --smoke
    echo "   glm-family smoke OK"
    echo "== experiments: GP live-sweep soak smoke =="
    JAX_PLATFORMS=cpu python bench.py --experiment-soak --smoke
    echo "   experiment-soak smoke OK"
}

run_kernels() {
    # Kernel-surface smoke: interpret-mode parity for the FE Pallas kernels
    # (fused value+grad/HVP), the RE block solve's zero-retrace discipline,
    # every pallas_call and the RE block solve through the real TPU compiler
    # ahead of time, and a dead-code gate — the round-4 FE A/B DELETED the
    # losing lowerings, so their per-call tile_n override must stay gone from
    # the public signatures (no quietly resurrected code paths in
    # ops/pallas_glm.py).
    echo "== kernels: FE Pallas parity smoke + deleted-lowering gate =="
    JAX_PLATFORMS=cpu python - <<'EOF'
import inspect

from photon_tpu.ops.pallas_glm import (
    fused_data_hvp,
    fused_data_value_and_grad,
)

for fn in (fused_data_value_and_grad, fused_data_hvp):
    params = inspect.signature(fn).parameters
    assert "tile_n" not in params, (
        f"{fn.__name__} grew a tile_n override back — the losing FE "
        "lowerings were deleted in the round-4 A/B (BENCH_FULL.md)"
    )
print("   deleted-lowering gate OK (no tile_n in public signatures)")
EOF
    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
        tests/test_pallas_glm.py \
        tests/test_re_block_solve.py::test_zero_post_warmup_retraces \
        tests/test_tpu_aot_compile.py
    echo "   kernels smoke OK"
}

run_install() {
    echo "== packaging: editable install + console entry points =="
    tmp="$(mktemp -d)"
    python -m venv "$tmp/venv"
    # Air-gapped CI: no index access, and the base interpreter may itself be
    # a venv (so --system-site-packages wouldn't see its packages). Bridge
    # the parent environment's site-packages (setuptools for the build,
    # jax/numpy for runtime) via PYTHONPATH instead.
    parent_site="$(python -c 'import site; print(site.getsitepackages()[0])')"
    PYTHONPATH="$parent_site" "$tmp/venv/bin/pip" install -q --no-deps \
        --no-build-isolation -e .
    # Entry points must resolve and print usage without touching a backend.
    for cmd in photon-tpu-game-training photon-tpu-game-scoring \
               photon-tpu-train-glm photon-tpu-feature-indexing \
               photon-tpu-name-and-term-bags photon-tpu-game-serving \
               photon-tpu-game-incremental photon-tpu-game-streaming \
               photon-tpu-game-experiment photon-tpu-obs; do
        PYTHONPATH="$parent_site" "$tmp/venv/bin/$cmd" --help > /dev/null
        echo "   $cmd --help OK"
    done
    # The sharded freshness plane must be reachable from the installed
    # entry point, not just the module: --updater-shards (and the
    # materializing router switch) are part of the CLI contract.
    PYTHONPATH="$parent_site" "$tmp/venv/bin/photon-tpu-game-streaming" \
        --help | grep -q -- "--updater-shards"
    PYTHONPATH="$parent_site" "$tmp/venv/bin/photon-tpu-game-streaming" \
        --help | grep -q -- "--route-spool"
    echo "   photon-tpu-game-streaming exposes --updater-shards/--route-spool OK"
    # Quality-plane surfaces (ISSUE 18): late-label replay + FE-retrain
    # actuation flags on the streaming driver, and the quality subcommand
    # on the obs CLI.
    PYTHONPATH="$parent_site" "$tmp/venv/bin/photon-tpu-game-streaming" \
        --help | grep -q -- "--late-replay-cadence"
    PYTHONPATH="$parent_site" "$tmp/venv/bin/photon-tpu-game-streaming" \
        --help | grep -q -- "--fe-retrain"
    PYTHONPATH="$parent_site" "$tmp/venv/bin/photon-tpu-obs" \
        quality --help > /dev/null
    echo "   quality-plane CLI surfaces OK (--late-replay-cadence/--fe-retrain/quality)"
    # Experiment-plane surfaces (ISSUE 20): the sweep driver's core flags
    # and the experiments rollup on the obs CLI.
    PYTHONPATH="$parent_site" "$tmp/venv/bin/photon-tpu-game-experiment" \
        --help | grep -q -- "--rounds"
    PYTHONPATH="$parent_site" "$tmp/venv/bin/photon-tpu-obs" \
        experiments --help > /dev/null
    echo "   experiment-plane CLI surfaces OK (--rounds/experiments)"
    rm -rf "$tmp"
}

case "$stage" in
    static) run_static ;;
    native) run_native ;;
    unit) run_unit ;;
    dryrun) run_dryrun ;;
    telemetry) run_telemetry ;;
    active-set) run_active_set ;;
    ooc) run_ooc ;;
    serve) run_serve ;;
    faults) run_faults ;;
    soak) run_soak ;;
    fleet) run_fleet ;;
    rollout) run_rollout ;;
    streaming) run_streaming ;;
    exhaustion) run_exhaustion ;;
    install) run_install ;;
    kernels) run_kernels ;;
    obs) run_obs ;;
    quality) run_quality ;;
    experiments) run_experiments ;;
    all) run_static; run_native; run_install; run_dryrun; run_telemetry; run_active_set; run_ooc; run_serve; run_faults; run_soak; run_fleet; run_rollout; run_streaming; run_exhaustion; run_obs; run_quality; run_experiments; run_kernels; run_unit ;;
    *) echo "unknown stage: $stage" >&2; exit 2 ;;
esac
echo "CI ($stage) PASSED"
