"""Fault-injection harness + fault-tolerance behaviors (ISSUE 6).

Covers the tentpole end to end: plan-driven deterministic injection
(utils/faults.py), pipeline retry/skip-budget/no-hang semantics
(io/pipeline.py), divergence quarantine in the RE block solves and the FE
rollback backstop (algorithm/solve_cache.py), the zero-sync invariant of the
quarantine accounting, kill-and-resume parity of the λ-sweep driver
(subprocess SIGKILL via the fault plan), graceful-shutdown plumbing
(utils/shutdown.py + CD pass-boundary polling), and serving degradation
(reload failure keeps the old model; the store circuit breaker degrades to
FE-only and recovers).
"""

import json
import os
import signal as _signal
import subprocess
import sys
import time
from collections import Counter

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_tpu.utils import faults
from photon_tpu.utils.faults import (
    FaultPlan,
    FaultRule,
    PermanentInjectedFault,
    TransientInjectedFault,
)

rng = np.random.default_rng(23)


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    """Every test starts AND ends with no fault plan: a leaked injector
    would poison unrelated tests through the process-global hook sites."""
    monkeypatch.delenv(faults.FAULT_PLAN_ENV, raising=False)
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# Harness: plans, determinism, env configuration, poison
# ---------------------------------------------------------------------------


def test_rule_at_indices_and_max_count():
    plan = FaultPlan(rules=(
        FaultRule("s.y", kind="transient", at=(1, 3), max_count=1),
    ))
    inj = faults.FaultInjector(plan)
    fires = [inj.fire("s.y") is not None for _ in range(5)]
    # at=(1,3) wants calls 1 and 3; max_count=1 caps it at the first.
    assert fires == [False, True, False, False, False]
    assert inj.counts() == {"s.y": 1}


def test_probabilistic_rules_are_deterministic():
    plan = FaultPlan(seed=7, rules=(FaultRule("s.x", kind="transient", p=0.3),))

    def seq():
        inj = faults.FaultInjector(plan)
        return [inj.fire("s.x") is not None for _ in range(200)]

    a, b = seq(), seq()
    assert a == b  # per-site seeded RNG: same plan → same firing sequence
    assert 20 < sum(a) < 120


def test_plan_from_env_inline_and_file(tmp_path, monkeypatch):
    plan = {"seed": 3, "rules": [{"site": "demo.site", "kind": "permanent",
                                  "at": [0]}]}
    monkeypatch.setenv(faults.FAULT_PLAN_ENV, json.dumps(plan))
    faults.reset()  # next hook re-reads the environment
    assert faults.active("demo.site")
    assert not faults.active("other.site")
    with pytest.raises(PermanentInjectedFault):
        faults.check("demo.site")
    faults.check("demo.site")  # at=[0] fired once; later calls pass

    p = tmp_path / "plan.json"
    p.write_text(json.dumps(plan))
    monkeypatch.setenv(faults.FAULT_PLAN_ENV, str(p))
    faults.reset()
    with pytest.raises(PermanentInjectedFault):
        faults.check("demo.site")


def test_poison_numpy_and_jax_and_original_untouched():
    faults.configure(FaultPlan(rules=(FaultRule("s.p", kind="nan", p=1.0),)))
    a = np.ones((3, 2), np.float32)
    out = faults.poison("s.p", a)
    assert np.isnan(out[0]).all() and np.isfinite(out[1:]).all()
    assert np.isfinite(a).all()  # copy-on-poison: caller's array untouched
    j = faults.poison("s.p", jnp.ones((4,), jnp.float32))
    j = np.asarray(j)
    assert np.isnan(j[0]) and np.isfinite(j[1:]).all()


def test_rule_validation():
    with pytest.raises(ValueError):
        FaultRule("s", kind="bogus")
    with pytest.raises(ValueError):
        FaultRule("s", p=1.5)
    assert isinstance(
        faults.exception_for(FaultRule("s"), "s"), TransientInjectedFault
    )


# ---------------------------------------------------------------------------
# Pipeline: retry with backoff, skip budget, no-hang failure propagation
# ---------------------------------------------------------------------------


def _staged(stage_fn, items, policy, overlap):
    from photon_tpu.io.pipeline import _run_staged
    from photon_tpu.utils.timed import PipelineStats

    return list(_run_staged(
        lambda: iter(items), lambda x: 0,
        [("work", stage_fn, lambda x: 0)],
        PipelineStats(overlapped=overlap), 2, overlap, retry=policy,
    ))


@pytest.mark.parametrize("overlap", [True, False])
def test_pipeline_transient_retry_then_succeed(overlap):
    from photon_tpu.io.pipeline import RetryPolicy

    attempts = Counter()

    def flaky(x):
        attempts[x] += 1
        if x == 2 and attempts[x] <= 2:
            raise TimeoutError("transient hiccup")
        return x * 10

    policy = RetryPolicy(max_retries=2, backoff_s=0.001, backoff_max_s=0.002)
    out = _staged(flaky, range(5), policy, overlap)
    assert out == [0, 10, 20, 30, 40]  # complete and in order
    assert attempts[2] == 3  # two retries, then success


@pytest.mark.parametrize("overlap", [True, False])
def test_pipeline_skip_budget_drops_poisoned_chunk(overlap):
    from photon_tpu.io.pipeline import RetryPolicy

    def poisoned(x):
        if x == 1:
            raise RuntimeError("poisoned chunk")  # non-transient: no retries
        return x

    policy = RetryPolicy(max_retries=1, backoff_s=0.001, skip_budget=1)
    assert _staged(poisoned, range(4), policy, overlap) == [0, 2, 3]


def test_pipeline_exhausted_budget_raises_promptly():
    from photon_tpu.io.pipeline import RetryPolicy

    def poisoned(x):
        if x >= 1:
            raise RuntimeError(f"poisoned chunk {x}")
        return x

    policy = RetryPolicy(max_retries=0, backoff_s=0.001, skip_budget=1)
    t0 = time.monotonic()
    # Chunk 1 eats the budget; chunk 2 must surface in the consumer (the
    # no-hang guarantee: the error propagates, the consumer never blocks).
    with pytest.raises(RuntimeError, match="poisoned chunk 2"):
        _staged(poisoned, range(4), policy, overlap=True)
    assert time.monotonic() - t0 < 30


def test_ingest_fault_plan_injects_and_recovers():
    """Integration through the real hook site: an injected transient at
    ingest.h2d is retried and the stream completes, in order."""
    from photon_tpu.io.pipeline import BatchChunk, RetryPolicy, device_chunks_from

    faults.configure(FaultPlan(rules=(
        FaultRule("ingest.h2d", kind="transient", at=(0,)),
    )))
    chunks = [
        BatchChunk(np.full((4,), float(i), np.float32), 4, i) for i in range(3)
    ]
    out = list(device_chunks_from(
        lambda: iter(chunks),
        retry=RetryPolicy(max_retries=2, backoff_s=0.001),
    ))
    assert [int(np.asarray(c.batch)[0]) for c in out] == [0, 1, 2]
    assert faults.injector().counts() == {"ingest.h2d": 1}


def test_retry_policy_env_overrides(monkeypatch):
    from photon_tpu.io.pipeline import (
        MAX_RETRIES_ENV,
        SKIP_BUDGET_ENV,
        default_retry_policy,
    )

    monkeypatch.setenv(MAX_RETRIES_ENV, "5")
    monkeypatch.setenv(SKIP_BUDGET_ENV, "3")
    p = default_retry_policy()
    assert p.max_retries == 5 and p.skip_budget == 3


# ---------------------------------------------------------------------------
# Divergence guards: RE quarantine, FE rollback, zero-sync invariant
# ---------------------------------------------------------------------------

E, D = 12, 4


def _re_problem():
    counts = np.full(E, 30)
    eids = np.repeat(np.arange(E, dtype=np.int32), counts)
    n = eids.size
    X = rng.normal(size=(n, D)).astype(np.float32)
    X[:, 0] = 1.0
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    w = np.ones(n, np.float32)
    return eids, X, y, w


def _re_batch(eids, X, y, w):
    from photon_tpu.data.game_data import GameBatch

    return GameBatch(
        label=jnp.asarray(y),
        offset=jnp.zeros(y.shape[0], jnp.float32),
        weight=jnp.asarray(w),
        features={"re": jnp.asarray(X)},
        entity_ids={"userId": jnp.asarray(eids)},
    )


def _re_coordinate(eids, X, y, w, **kw):
    from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
    from photon_tpu.algorithm.solve_cache import SolveCache
    from photon_tpu.data.random_effect import (
        RandomEffectDataConfig,
        build_random_effect_dataset,
    )
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optim.factory import OptimizerSpec
    from photon_tpu.types import OptimizerType, TaskType

    ds = build_random_effect_dataset(
        eids, X, y, w, E,
        RandomEffectDataConfig(re_type="userId", feature_shard="re"),
    )
    return RandomEffectCoordinate(
        coordinate_id="per_user",
        dataset=ds,
        task=TaskType.LOGISTIC_REGRESSION,
        objective=GLMObjective(loss=LogisticLoss, l2_weight=0.5,
                               intercept_index=0),
        optimizer_spec=OptimizerSpec(
            optimizer=OptimizerType.NEWTON, max_iter=25, tol=1e-9
        ),
        solve_cache=SolveCache(donate=True),
        **kw,
    )


def test_re_nan_poison_quarantines_then_recovers():
    """A poisoned block dispatch quarantines only the affected entities:
    they keep their warm start (finite), everything else trains, and the
    NEXT pass — fault exhausted — heals them."""
    eids, X, y, w = _re_problem()
    faults.configure(FaultPlan(rules=(
        FaultRule("solve.re_block", kind="nan", at=(0,)),
    )))
    coord = _re_coordinate(eids, X, y, w)
    batch = _re_batch(eids, X, y, w)

    model, stats = coord.train(batch)
    coefs = np.asarray(model.coefficients)[:E]
    assert np.isfinite(coefs).all()
    q = int(stats.num_quarantined)
    assert q >= 1
    # Quarantined rows kept the zero warm start; every other entity trained.
    zero_rows = int(np.sum(~np.any(coefs != 0.0, axis=-1)))
    assert zero_rows == q

    model2, stats2 = coord.train(batch, None, model)
    assert int(stats2.num_quarantined) == 0
    coefs2 = np.asarray(model2.coefficients)[:E]
    assert np.isfinite(coefs2).all()
    assert np.all(np.any(coefs2 != 0.0, axis=-1))  # healed entities trained


def test_quarantine_accounting_is_sync_free(monkeypatch):
    """The divergence guards piggyback the one pass-boundary mask fetch:
    with a quarantine actually firing, run(profile=False) still performs
    ZERO jax.block_until_ready calls, and the active-set stats + metrics
    registry report the quarantined entities."""
    from photon_tpu.algorithm.coordinate_descent import CoordinateDescent
    from photon_tpu.obs import begin_run
    from photon_tpu.obs.metrics import registry

    eids, X, y, w = _re_problem()
    faults.configure(FaultPlan(rules=(
        FaultRule("solve.re_block", kind="nan", at=(0,)),
    )))
    begin_run()
    coord = _re_coordinate(eids, X, y, w, active_set=True,
                           convergence_tol=1e-4)
    batch = _re_batch(eids, X, y, w)

    calls = {"n": 0}
    real = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    cd = CoordinateDescent(
        coordinates={"per_user": coord},
        update_sequence=["per_user"],
        num_iterations=2,
    )
    cd.run(batch, profile=False)
    assert calls["n"] == 0  # guards added no host syncs

    st = coord.last_active_set_stats
    assert st is not None and st["entities_quarantined"] >= 1
    counted = registry().counter(
        "re_entities_quarantined", coordinate="per_user"
    ).value
    assert counted >= 1
    begin_run()


def test_fe_solver_rolls_back_non_finite_to_warm_start():
    from photon_tpu.algorithm.solve_cache import SolveCache
    from photon_tpu.data.batch import LabeledBatch
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optim.factory import OptimizerSpec
    from photon_tpu.types import ConvergenceReason

    n, d = 64, 5
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[0, 1] = np.nan  # corrupt row: every objective eval goes non-finite
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    lb = LabeledBatch(jnp.asarray(y), jnp.asarray(X))
    solve = SolveCache(donate=False).fe_solver(
        GLMObjective(loss=LogisticLoss, l2_weight=0.1, intercept_index=0),
        OptimizerSpec(),
    )
    res, _scores = solve(jnp.zeros((d,), jnp.float32), lb)
    w = np.asarray(res.w)
    assert np.isfinite(w).all() and (w == 0.0).all()  # rolled back to w0
    assert res.convergence_reason == ConvergenceReason.DIVERGED


# ---------------------------------------------------------------------------
# Graceful shutdown: signal→flag conversion + CD pass-boundary checkpoint
# ---------------------------------------------------------------------------


def test_handle_termination_converts_first_signal():
    from photon_tpu.utils.shutdown import handle_termination, shutdown_requested

    assert shutdown_requested() is None
    with handle_termination():
        os.kill(os.getpid(), _signal.SIGTERM)
        deadline = time.monotonic() + 5
        while shutdown_requested() is None and time.monotonic() < deadline:
            time.sleep(0.005)
        assert shutdown_requested() == _signal.SIGTERM
    assert shutdown_requested() is None  # state cleared on exit


def test_cd_graceful_shutdown_checkpoints_then_raises(tmp_path, monkeypatch):
    from photon_tpu.algorithm.coordinate_descent import CoordinateDescent
    from photon_tpu.utils import shutdown as shut
    from photon_tpu.utils.checkpoint import latest_step

    monkeypatch.setattr(
        shut, "shutdown_requested", lambda: int(_signal.SIGTERM)
    )
    eids, X, y, w = _re_problem()
    coord = _re_coordinate(eids, X, y, w)
    batch = _re_batch(eids, X, y, w)
    ck = str(tmp_path / "ck")
    cd = CoordinateDescent(
        coordinates={"per_user": coord},
        update_sequence=["per_user"],
        num_iterations=5,
    )
    with pytest.raises(shut.GracefulShutdown):
        cd.run(batch, checkpoint_dir=ck)
    # Stopped at the first pass boundary, with that pass durable.
    assert latest_step(ck) == 0


# ---------------------------------------------------------------------------
# Kill-and-resume parity (the ci.sh faults criterion, in-repo)
# ---------------------------------------------------------------------------


def _write_libsvm(path, n=48, d=3, seed=5):
    r = np.random.default_rng(seed)
    X = r.normal(size=(n, d))
    beta = r.normal(size=d)
    y = (r.uniform(size=n) < 1 / (1 + np.exp(-X @ beta))).astype(int)
    with open(path, "w") as f:
        for i in range(n):
            feats = " ".join(f"{j + 1}:{X[i, j]:.6f}" for j in range(d))
            f.write(f"{y[i]} {feats}\n")


def _run_train_glm(data, outdir, ckpt=None, resume=False, plan=None):
    cmd = [
        sys.executable, "-m", "photon_tpu.cli.train_glm",
        "--training-data", str(data), "--format", "libsvm",
        "--output-dir", str(outdir),
        "--regularization-weights", "10,1,0.1",
        "--max-iterations", "15",
    ]
    if ckpt:
        cmd += ["--checkpoint-dir", str(ckpt)]
    if resume:
        cmd += ["--resume"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(faults.FAULT_PLAN_ENV, None)
    if plan is not None:
        env[faults.FAULT_PLAN_ENV] = json.dumps(plan)
    return subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=300
    )


def test_train_glm_kill_and_resume_parity(tmp_path):
    """SIGKILL right after the first λ checkpoint becomes durable, then
    --resume: final losses match an uninterrupted sweep at rel ≤ 1e-6 (the
    restored warm-start vector reproduces the same λ trajectory)."""
    data = tmp_path / "train.libsvm"
    _write_libsvm(data)

    base = _run_train_glm(data, tmp_path / "base")
    assert base.returncode == 0, base.stderr

    plan = {"rules": [
        {"site": "checkpoint.after_save", "kind": "kill", "at": [0]}
    ]}
    killed = _run_train_glm(
        data, tmp_path / "out", ckpt=tmp_path / "ck", plan=plan
    )
    assert killed.returncode == -_signal.SIGKILL, killed.stderr

    resumed = _run_train_glm(
        data, tmp_path / "out", ckpt=tmp_path / "ck", resume=True
    )
    assert resumed.returncode == 0, resumed.stderr
    assert "resuming" in (resumed.stderr + resumed.stdout).lower()

    sa = json.loads((tmp_path / "base" / "training-summary.json").read_text())
    sb = json.loads((tmp_path / "out" / "training-summary.json").read_text())
    assert sa["best_lambda"] == sb["best_lambda"]
    assert len(sa["models"]) == len(sb["models"]) == 3
    for ma, mb in zip(sa["models"], sb["models"]):
        assert ma["lambda"] == mb["lambda"]
        assert mb["loss"] == pytest.approx(ma["loss"], rel=1e-6)


def test_train_glm_resume_without_state_fails(tmp_path):
    data = tmp_path / "train.libsvm"
    _write_libsvm(data)
    out = _run_train_glm(
        data, tmp_path / "out", ckpt=tmp_path / "empty-ck", resume=True
    )
    assert out.returncode != 0
    assert "no checkpoint state" in out.stderr


# ---------------------------------------------------------------------------
# Serving: reload failure keeps the old model; breaker degrades + recovers
# ---------------------------------------------------------------------------

D_FIX, D_RE, N_ENT = 5, 3, 16


def _serve_model(scale=1.0):
    from photon_tpu.models.coefficients import Coefficients
    from photon_tpu.models.game import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.models.glm import GeneralizedLinearModel
    from photon_tpu.types import TaskType

    w_fix = (scale * np.linspace(-1, 1, D_FIX)).astype(np.float32)
    w_re = (scale * rng.normal(size=(N_ENT, D_RE))).astype(np.float32)
    return GameModel({
        "global": FixedEffectModel(
            GeneralizedLinearModel(
                Coefficients(np.asarray(w_fix)), TaskType.LOGISTIC_REGRESSION
            ),
            "shardA",
        ),
        "per_user": RandomEffectModel(
            np.asarray(w_re), "userId", "shardB",
            TaskType.LOGISTIC_REGRESSION,
        ),
    })


def _serve_engine(**cfg):
    from photon_tpu.data.index_map import EntityIndex
    from photon_tpu.serve.engine import ServeConfig, ServingEngine

    eidx = EntityIndex()
    for e in range(N_ENT):
        eidx.intern(f"user{e}")
    defaults = dict(max_batch_size=4, max_delay_ms=1.0, hot_bytes=1 << 30)
    defaults.update(cfg)
    model = _serve_model()
    return ServingEngine(
        model, entity_indexes={"userId": eidx}, config=ServeConfig(**defaults)
    )


def test_reload_failure_keeps_old_model_serving():
    from photon_tpu.serve.engine import ReloadError

    eng = _serve_engine()
    try:
        feats = {
            "shardA": rng.normal(size=D_FIX).astype(np.float32),
            "shardB": rng.normal(size=D_RE).astype(np.float32),
        }
        v0 = eng.model_version
        s_before = np.float32(eng.score(feats, {"userId": "user1"}))

        faults.configure(FaultPlan(rules=(
            FaultRule("serve.reload", kind="permanent", at=(0,)),
        )))
        with pytest.raises(ReloadError):
            eng.reload(_serve_model(scale=-2.0), "v-broken")
        assert eng.model_version == v0  # old generation still installed
        assert np.float32(eng.score(feats, {"userId": "user1"})) == s_before
        st = eng.stats()
        assert st["reload_failures"] == 1 and st["degraded"]
        assert "v-broken" in st["last_reload_error"]

        # Fault exhausted: the next reload succeeds and clears the error.
        info = eng.reload(_serve_model(scale=-2.0), "v2")
        assert info["model_version"] == "v2" and eng.model_version == "v2"
        st = eng.stats()
        assert st["last_reload_error"] is None and not st["degraded"]
    finally:
        eng.close()


def test_breaker_degrades_to_fe_only_then_recovers():
    eng = _serve_engine(breaker_threshold=2, breaker_cooldown_s=0.3)
    try:
        feats = {
            "shardA": rng.normal(size=D_FIX).astype(np.float32),
            "shardB": rng.normal(size=D_RE).astype(np.float32),
        }
        full = np.float32(eng.score(feats, {"userId": "user3"}))
        # FE-only reference: an unknown entity resolves -1 (cold start), so
        # the random effect contributes exactly 0.
        fe_only = np.float32(eng.score(feats, {"userId": "no-such-user"}))
        assert full != fe_only

        faults.configure(FaultPlan(rules=(
            FaultRule("serve.store_resolve", kind="transient", p=1.0,
                      max_count=2),
        )))
        # Failures 1 and 2: each batch degrades to FE-only; #2 trips.
        assert np.float32(eng.score(feats, {"userId": "user3"})) == fe_only
        assert np.float32(eng.score(feats, {"userId": "user3"})) == fe_only
        st = eng.stats()
        assert st["degraded"] and st["degraded_re_types"] == ["userId"]
        assert st["breaker_trips"] == {"userId": 1}
        # Open breaker: still answering, FE-only, no resolve attempted.
        assert np.float32(eng.score(feats, {"userId": "user3"})) == fe_only

        time.sleep(0.4)  # cooldown elapses → half-open probe
        # Fault plan exhausted (max_count=2): the probe succeeds and closes
        # the breaker — full-fidelity scores again.
        assert np.float32(eng.score(feats, {"userId": "user3"})) == full
        st = eng.stats()
        assert not st["degraded"] and st["degraded_re_types"] == []
    finally:
        eng.close()


def test_breaker_half_open_probe_under_concurrent_load():
    """Half-open probing with callers hammering the engine: the trip, the
    open window, the probe, and the close all happen while 6 threads score
    concurrently — and NO caller ever sees an error (degraded FE-only
    answers during the outage, full fidelity after recovery)."""
    import threading

    eng = _serve_engine(breaker_threshold=2, breaker_cooldown_s=0.2)
    try:
        feats = {
            "shardA": rng.normal(size=D_FIX).astype(np.float32),
            "shardB": rng.normal(size=D_RE).astype(np.float32),
        }
        full = np.float32(eng.score(feats, {"userId": "user3"}))
        fe_only = np.float32(eng.score(feats, {"userId": "no-such-user"}))
        assert full != fe_only

        faults.configure(FaultPlan(rules=(
            FaultRule("serve.store_resolve", kind="transient", p=1.0,
                      max_count=4),
        )))
        stop = time.monotonic() + 1.2
        errors, scores = [], []
        lock = threading.Lock()

        def hammer():
            while time.monotonic() < stop:
                try:
                    s = np.float32(eng.score(feats, {"userId": "user3"}))
                except Exception as exc:  # noqa: BLE001 — must not happen
                    with lock:
                        errors.append(repr(exc))
                    return
                with lock:
                    scores.append(s)

        threads = [threading.Thread(target=hammer) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:3]
        seen = set(scores)
        # Every answer is one of the two legitimate fidelities — never
        # garbage, never an exception.
        assert seen <= {full, fe_only} and fe_only in seen
        st = eng.stats()
        assert st["breaker_trips"].get("userId", 0) >= 1
        # Fault budget exhausted → a half-open probe closed the breaker
        # while load was still running: full fidelity again at the end.
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if np.float32(
                eng.score(feats, {"userId": "user3"})
            ) == full and not eng.stats()["degraded"]:
                break
            time.sleep(0.05)
        else:
            raise AssertionError(f"breaker never recovered: {eng.stats()}")
    finally:
        eng.close()
