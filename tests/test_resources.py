"""Resource-exhaustion containment (utils/resources.py + wiring).

One degradation priority — model artifacts > training progress >
observability — wired through every allocating layer:

- fault kinds ``enospc``/``oom``/``rss`` (utils/faults.py) and the
  classifiers in utils/resources.py;
- checkpoint writer: tmp cleanup on failure, keep-last-K pruning, ENOSPC
  prune-and-retry (utils/checkpoint.py);
- telemetry report: degrade to a counted drop instead of crashing the
  driver at finalize (obs/report.py);
- replay cache: spool-write fallback to legacy re-stream with partial-file
  cleanup, torn-spool recovery with exact chunk parity, dead-letter write
  failure never masking the chunk error (io/pipeline.py);
- device OOM containment with evict-harder + budget shrink and bit parity
  in the RE training store (algorithm/re_store.py) and gc-and-retry in the
  serving store (serve/store.py);
- RSS watchdog levels, pressure tightening of pipeline depth and serving
  admission, and the clean hard-pressure error at the CD pass boundary.
"""

import errno
import glob
import os
import pickle
import threading

import numpy as np
import jax.numpy as jnp
import pytest

from photon_tpu.obs.metrics import registry, reset_registry
from photon_tpu.utils import faults, resources
from photon_tpu.utils.faults import FaultPlan, FaultRule


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv(faults.FAULT_PLAN_ENV, raising=False)
    monkeypatch.delenv(resources.RSS_LIMIT_ENV, raising=False)
    faults.reset()
    reset_registry()
    resources.stop_watchdog()
    yield
    faults.reset()
    resources.stop_watchdog()


def _plan(*rules, seed=0):
    return faults.configure(FaultPlan(seed=seed, rules=tuple(rules)))


# ---------------------------------------------------------------------------
# Fault kinds + classifiers
# ---------------------------------------------------------------------------


def test_enospc_fault_kind_raises_oserror_with_enospc_errno():
    _plan(FaultRule("w.x", kind="enospc", at=(0,)))
    with pytest.raises(OSError) as ei:
        faults.check("w.x")
    assert ei.value.errno == errno.ENOSPC
    assert resources.is_enospc(ei.value)
    assert isinstance(ei.value, faults.EnospcInjectedFault)


def test_oom_fault_kind_matches_resource_exhausted_classifier():
    _plan(FaultRule("u.y", kind="oom", at=(0,)))
    with pytest.raises(RuntimeError) as ei:
        faults.check("u.y")
    assert "RESOURCE_EXHAUSTED" in str(ei.value)
    assert resources.is_device_oom(ei.value)
    # Real non-exhaustion errors stay unclassified.
    assert not resources.is_device_oom(RuntimeError("boom"))
    assert not resources.is_enospc(OSError(errno.EIO, "io error"))


def test_rss_fault_kind_is_inert_outside_the_watchdog():
    _plan(FaultRule("rss.sample", kind="rss", p=1.0))
    faults.check("rss.sample")  # must not raise — only the sampler acts
    arr = faults.poison("rss.sample", np.ones(3))
    assert not np.isnan(arr).any()


def test_oom_retry_calls_evict_hook_and_counts():
    calls = []

    def attempt():
        calls.append("try")
        if calls.count("try") < 3:
            raise RuntimeError("RESOURCE_EXHAUSTED: arena full")
        return 42

    out = resources.oom_retry(
        attempt, site="t", evict=lambda i: calls.append(f"evict{i}"),
        retries=2,
    )
    assert out == 42
    assert calls == ["try", "evict0", "try", "evict1", "try"]
    assert registry().find("device_oom_retries_total", site="t").value == 2
    # Final OOM and non-OOM errors propagate untouched.
    with pytest.raises(RuntimeError):
        resources.oom_retry(
            lambda: (_ for _ in ()).throw(
                RuntimeError("RESOURCE_EXHAUSTED: no")),
            site="t", retries=1,
        )
    with pytest.raises(ValueError):
        resources.oom_retry(
            lambda: (_ for _ in ()).throw(ValueError("x")), site="t")


# ---------------------------------------------------------------------------
# Checkpoint writer: tmp cleanup, keep-last, ENOSPC prune-and-retry
# ---------------------------------------------------------------------------


def _no_tmp(directory):
    return glob.glob(os.path.join(directory, "*.tmp"))


def test_save_checkpoint_failure_leaves_no_tmp_file(tmp_path):
    from photon_tpu.utils.checkpoint import save_checkpoint

    d = str(tmp_path)
    # A non-disk-space write failure propagates — but the partial tmp must
    # be cleaned up either way (satellite: the old path leaked it).
    _plan(FaultRule("checkpoint.io", kind="transient", at=(0,)))
    with pytest.raises(faults.TransientInjectedFault):
        save_checkpoint(d, dict(w=np.arange(4.0)), 0)
    assert _no_tmp(d) == []
    assert not os.path.exists(os.path.join(d, "step_0.npz"))


def test_save_checkpoint_keep_last_prunes_oldest(tmp_path):
    from photon_tpu.utils.checkpoint import latest_step, save_checkpoint

    d = str(tmp_path)
    for step in range(5):
        save_checkpoint(d, dict(w=np.full(3, float(step))), step, keep_last=2)
    steps = [n for n in sorted(os.listdir(d)) if n.startswith("step_")]
    assert steps == ["step_3.npz", "step_4.npz"]
    assert latest_step(d) == 4
    assert registry().find("checkpoint_pruned_total").value == 3


def test_save_checkpoint_keep_last_env_default(tmp_path, monkeypatch):
    from photon_tpu.utils.checkpoint import (
        CHECKPOINT_KEEP_LAST_ENV,
        save_checkpoint,
    )

    monkeypatch.setenv(CHECKPOINT_KEEP_LAST_ENV, "1")
    d = str(tmp_path)
    for step in range(3):
        save_checkpoint(d, dict(w=np.zeros(2)), step)
    steps = [n for n in sorted(os.listdir(d)) if n.startswith("step_")]
    assert steps == ["step_2.npz"]


def test_save_checkpoint_enospc_prunes_and_retries(tmp_path):
    from photon_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

    d = str(tmp_path)
    for step in range(3):
        save_checkpoint(d, dict(w=np.full(3, float(step))), step)
    # Disk full exactly once, on the next save: the writer must prune older
    # steps, retry, and publish — no error to the caller, no tmp files.
    _plan(FaultRule("checkpoint.io", kind="enospc", at=(0,), max_count=1))
    save_checkpoint(d, dict(w=np.full(3, 3.0)), 3)
    steps = sorted(n for n in os.listdir(d) if n.startswith("step_"))
    assert steps == ["step_2.npz", "step_3.npz"]  # pruned to 1 + the new one
    assert _no_tmp(d) == []
    state, step = load_checkpoint(d)
    assert step == 3
    assert np.array_equal(np.asarray(state["w"]), np.full(3, 3.0))
    assert registry().find("disk_enospc_total", site="checkpoint.io").value == 1


def test_save_checkpoint_persistent_enospc_raises_without_tmp(tmp_path):
    from photon_tpu.utils.checkpoint import save_checkpoint

    d = str(tmp_path)
    _plan(FaultRule("checkpoint.io", kind="enospc", p=1.0))
    with pytest.raises(OSError) as ei:
        save_checkpoint(d, dict(w=np.zeros(2)), 0)
    assert resources.is_enospc(ei.value)
    assert _no_tmp(d) == []


# ---------------------------------------------------------------------------
# Telemetry report: degrade, never crash the driver at finalize
# ---------------------------------------------------------------------------


def test_write_run_report_degrades_on_write_failure(tmp_path):
    from photon_tpu.obs.report import write_run_report

    path = str(tmp_path / "report.jsonl")
    _plan(FaultRule("telemetry.write", kind="enospc", at=(0,)))
    write_run_report(path, [dict(record="meta", x=1)])  # must not raise
    assert not os.path.exists(path)
    assert _no_tmp(str(tmp_path)) == []
    assert registry().find("telemetry_write_failures_total").value == 1
    # Next write (disk recovered) succeeds normally.
    write_run_report(path, [dict(record="meta", x=2)])
    assert os.path.exists(path)


# ---------------------------------------------------------------------------
# Replay cache: spool ENOSPC fallback + torn-spool recovery
# ---------------------------------------------------------------------------


class _Chunk:
    def __init__(self, i):
        self.index = i
        self.data = np.full(64, float(i))


def _chunks(n=6):
    def factory():
        for i in range(n):
            yield _Chunk(i)

    return factory


def _indices(it):
    return [c.index for c in it]


def _replay_cache(spill):
    from photon_tpu.io.pipeline import ChunkReplayCache

    # Budget fits exactly two 512-byte chunks; the rest spools.
    return ChunkReplayCache(
        _chunks(), byte_budget=2 * 64 * 8 + 1,
        nbytes=lambda c: c.data.nbytes, spill_dir=spill,
    )


def test_replay_spool_enospc_falls_back_to_restream(tmp_path):
    spill = str(tmp_path / "spill")
    cache = _replay_cache(spill)
    _plan(FaultRule("spool.write", kind="enospc", at=(0,)))
    # The failure happens mid-pass; training must still see every chunk.
    assert _indices(cache) == list(range(6))
    assert cache.spilled
    # Fallback is sticky: legacy re-stream, no spool files left behind.
    assert glob.glob(os.path.join(spill, "spool-*.pkl")) == []
    assert _indices(cache) == list(range(6))
    assert cache.source_passes == 2  # decode re-paid: the legacy path
    assert registry().find("replay_spill_fallbacks_total").value == 1


def test_replay_torn_spool_recovers_with_exact_parity(tmp_path):
    spill = str(tmp_path / "spill")
    cache = _replay_cache(spill)
    assert _indices(cache) == list(range(6))  # pass 1: 2 in RAM, 4 spooled
    spools = glob.glob(os.path.join(spill, "spool-*.pkl"))
    assert len(spools) == 1
    # Tear the spool: keep one intact pickle record, truncate into garbage
    # (a crash or bit rot between passes).
    with open(spools[0], "rb") as f:
        first = pickle.load(f)
        intact = f.tell()
    assert first.index == 2  # memory prefix holds 0,1; spool starts at 2
    with open(spools[0], "rb+") as f:
        f.truncate(intact + 7)
    got = _indices(cache)  # replay pass hits the tear and must recover
    assert got == list(range(6))
    assert registry().find("replay_spool_torn_total").value == 1
    assert glob.glob(os.path.join(spill, "spool-*.pkl")) == []  # cleaned up
    # The cache rebuilds (memory + a fresh spool) on the next pass.
    assert _indices(cache) == list(range(6))
    assert _indices(cache) == list(range(6))


def test_dead_letter_write_failure_does_not_mask_chunk_error(tmp_path):
    from photon_tpu.io.pipeline import _SkipBudget

    dl = str(tmp_path / "letters.jsonl")
    _plan(FaultRule("deadletter.write", kind="enospc", p=1.0))
    budget = _SkipBudget(2, dl)
    # The sidecar append fails; dead_letter must swallow it (the original
    # chunk error is what the skip budget is accounting for) and count it.
    budget.dead_letter("decode", _Chunk(1), RuntimeError("original"))
    assert registry().find("dead_letter_write_failures_total").value == 1
    # No record landed (at most an empty file, as with a real full disk).
    assert not os.path.exists(dl) or os.path.getsize(dl) == 0
    budget.dead_letter("decode", _Chunk(2), RuntimeError("original"))
    assert registry().find("dead_letter_write_failures_total").value == 2
    # Disk recovers: the sidecar works again without a restart.
    faults.reset()
    budget.dead_letter("decode", _Chunk(3), RuntimeError("original"))
    with open(dl) as f:
        assert len(f.readlines()) == 1


# ---------------------------------------------------------------------------
# RE training store: spill fallback + device OOM containment, bit parity
# ---------------------------------------------------------------------------

RE_E, RE_D = 32, 4
_re_rng = np.random.default_rng(11)
_re_counts = _re_rng.integers(5, 11, size=RE_E)
RE_EIDS = np.repeat(np.arange(RE_E, dtype=np.int32), _re_counts)
RE_N = RE_EIDS.size
RE_X = _re_rng.normal(size=(RE_N, RE_D)).astype(np.float32)
RE_Y = (_re_rng.uniform(size=RE_N) < 0.5).astype(np.float32)
RE_W = np.ones(RE_N, np.float32)


def _re_dataset():
    from photon_tpu.data.random_effect import (
        RandomEffectDataConfig,
        build_random_effect_dataset,
    )

    cfg = RandomEffectDataConfig(
        re_type="userId", feature_shard="re",
        shape_bucketing=True,
    )
    return build_random_effect_dataset(RE_EIDS, RE_X, RE_Y, RE_W, RE_E, cfg)


def test_re_spill_enospc_falls_back_to_host_memory(tmp_path):
    from photon_tpu.algorithm.re_store import host_entity_block

    spill = str(tmp_path / "re-spill")
    os.makedirs(spill)
    block = _re_dataset().blocks[0]
    # Field 1 ("features") hits a full disk; it must stay in host RAM with
    # identical values while the other fields spill normally.
    _plan(FaultRule("re_store.spill", kind="enospc", at=(1,)))
    out = host_entity_block(block, spill_dir=spill, index=0)
    for name in ("entity_idx", "features", "label", "weight"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out, name)), np.asarray(getattr(block, name))
        )
    assert not isinstance(out.features, np.memmap)
    assert isinstance(out.label, np.memmap)
    assert registry().find("re_spill_fallbacks_total").value == 1
    # No partial .npy left for the failed field.
    saved = sorted(os.path.basename(p) for p in glob.glob(f"{spill}/*.npy"))
    assert "block00000_features.npy" not in saved
    assert len(saved) == 5


def test_re_store_oom_shrinks_budget_and_retries():
    from photon_tpu.algorithm.re_store import ReDeviceStore

    blocks = _re_dataset().blocks
    assert len(blocks) >= 2
    store = ReDeviceStore(blocks, budget_bytes=1 << 30, coordinate_id="per-x")

    def w0(b):
        return np.zeros((b.num_entities, b.dim), np.float32)

    # Fill the working set, then inject one OOM on the next upload.
    for k in range(len(store.blocks) - 1):
        store.acquire(k, store.blocks[k], w0(store.blocks[k]), cacheable=True)
        store.release(k, cacheable=True)
    _plan(FaultRule("re_store.upload", kind="oom", at=(0,), max_count=1))
    last = len(store.blocks) - 1
    blk = store.blocks[last]
    dev_block, dev_w0 = store.acquire(last, blk, w0(blk), cacheable=True)
    # Containment: evicted the unprotected working set, halved the budget,
    # retried — the caller never saw the OOM and the data is bit-identical.
    np.testing.assert_array_equal(
        np.asarray(dev_block.features), np.asarray(blk.features)
    )
    np.testing.assert_array_equal(np.asarray(dev_w0), w0(blk))
    assert store.effective_budget == max(store._max_cost, (1 << 30) // 2)
    assert store.lru.resident == [last]
    assert registry().find(
        "re_device_budget_shrinks_total", coordinate="per-x"
    ).value == 1
    store.release(last, cacheable=True)


def test_re_store_oom_at_floor_raises_device_memory_error():
    from photon_tpu.algorithm.re_store import ReDeviceStore

    blocks = _re_dataset().blocks
    store = ReDeviceStore(blocks, budget_bytes=1, coordinate_id="per-y")
    _plan(FaultRule("re_store.upload", kind="oom", p=1.0))
    with pytest.raises(resources.DeviceMemoryError) as ei:
        store.acquire(
            0, store.blocks[0],
            np.zeros((store.blocks[0].num_entities, store.blocks[0].dim),
                     np.float32),
            cacheable=True,
        )
    assert "largest single" in str(ei.value)


def _train_re_ooc(plan):
    from photon_tpu.algorithm.random_effect import RandomEffectCoordinate
    from photon_tpu.data.game_data import GameBatch
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optim.factory import OptimizerSpec
    from photon_tpu.types import OptimizerType, TaskType

    faults.reset()
    if plan is not None:
        faults.configure(plan)
    batch = GameBatch(
        label=jnp.asarray(RE_Y), offset=jnp.zeros(RE_N, jnp.float32),
        weight=jnp.asarray(RE_W), features={"re": jnp.asarray(RE_X)},
        entity_ids={"userId": jnp.asarray(RE_EIDS)},
    )
    coord = RandomEffectCoordinate(
        "per_user", _re_dataset(), TaskType.LOGISTIC_REGRESSION,
        GLMObjective(loss=LogisticLoss, l2_weight=0.5),
        optimizer_spec=OptimizerSpec(
            optimizer=OptimizerType.NEWTON, max_iter=20, tol=1e-9),
        device_budget_bytes=1,  # floor: one block resident at a time
    )
    model = None
    for it in range(2):
        coord.begin_cd_pass(it)
        model, _stats = coord.train(batch, None, model)
    return np.asarray(model.coefficients)


def test_re_store_oom_training_bit_parity():
    """End-to-end: an OOC RE training run with device OOM injected at the
    upload edge produces coefficients bit-identical to the fault-free run —
    containment changes residency, never values."""
    clean = _train_re_ooc(None)
    faulted = _train_re_ooc(FaultPlan(rules=(
        FaultRule("re_store.upload", kind="oom", at=(0, 5), max_count=2),
    )))
    assert np.array_equal(clean, faulted)  # bit parity, not approx


# ---------------------------------------------------------------------------
# Serving store: OOM gc-and-retry
# ---------------------------------------------------------------------------


def test_serve_oom_contained_retries_once_then_hard_fails():
    from photon_tpu.serve.store import _oom_contained

    calls = []

    def flaky():
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")
        return 42

    assert _oom_contained("userId", flaky) == 42
    assert registry().find(
        "serve_store_oom_evictions_total", re_type="userId"
    ).value == 1
    with pytest.raises(resources.DeviceMemoryError):
        _oom_contained("userId", lambda: (_ for _ in ()).throw(
            RuntimeError("RESOURCE_EXHAUSTED: nope")))
    with pytest.raises(ValueError):
        _oom_contained("userId", lambda: (_ for _ in ()).throw(
            ValueError("not memory")))


# ---------------------------------------------------------------------------
# RSS watchdog: levels, tightening, clean hard-pressure error
# ---------------------------------------------------------------------------


def test_watchdog_levels_from_injected_rss_rules():
    wd = resources.RssWatchdog(limit_bytes=1 << 62)  # never trips for real
    assert wd.sample() == resources.LEVEL_OK
    _plan(
        FaultRule("rss.sample", kind="rss", at=(0,), message="soft squeeze"),
        FaultRule("rss.sample", kind="rss", at=(1,), message="hard limit"),
    )
    assert wd.sample() == resources.LEVEL_SOFT
    wd.check()  # soft: advisory only
    assert wd.sample() == resources.LEVEL_HARD
    with pytest.raises(resources.HostMemoryPressureError) as ei:
        wd.check("unit test")
    assert "OOM-killer" in str(ei.value) and "unit test" in str(ei.value)
    assert wd.sample() == resources.LEVEL_OK  # pressure clears
    assert registry().find(
        "rss_pressure_events_total", level="soft"
    ).value == 1
    assert registry().find("host_rss_bytes").value > 0


def test_watchdog_real_thresholds(monkeypatch):
    readings = iter([80, 90, 99])
    monkeypatch.setattr(resources, "_read_rss_bytes", lambda: next(readings))
    wd = resources.RssWatchdog(limit_bytes=100, soft_fraction=0.85,
                               hard_fraction=0.95)
    assert wd.sample() == resources.LEVEL_OK
    assert wd.sample() == resources.LEVEL_SOFT
    assert wd.sample() == resources.LEVEL_HARD


def test_watchdog_inert_without_a_limit(monkeypatch):
    monkeypatch.setattr(resources, "_cgroup_mem_limit", lambda: None)
    wd = resources.RssWatchdog()
    assert wd.limit_bytes is None
    assert wd.sample() == resources.LEVEL_OK
    wd.check()  # never raises


def test_pressure_tightens_depth_and_cap():
    assert resources.tightened_depth(4) == 4  # no watchdog: untouched
    assert resources.tightened_cap(64) == 64
    # interval_s is huge so the daemon thread never races the manual samples.
    wd = resources.start_watchdog(limit_bytes=1 << 62, interval_s=3600)
    _plan(FaultRule("rss.sample", kind="rss", at=(0,), message="soft"))
    wd.sample()
    assert resources.memory_pressure()
    assert resources.pressure_level() == resources.LEVEL_SOFT
    assert resources.tightened_depth(4) == 1
    assert resources.tightened_cap(64) == 32
    _plan(FaultRule("rss.sample", kind="rss", at=(0,), message="hard"))
    wd.sample()
    assert resources.tightened_cap(64) == 16
    with pytest.raises(resources.HostMemoryPressureError):
        resources.check_memory("here")


def test_replay_cache_stops_caching_under_memory_pressure(tmp_path):
    # Soft pressure folds into the replay cache's admission decision: the
    # in-RAM prefix stops growing even though the byte budget has room.
    wd = resources.start_watchdog(limit_bytes=1 << 62, interval_s=3600)
    _plan(FaultRule("rss.sample", kind="rss", p=1.0, message="soft"))
    wd.sample()
    cache = _replay_cache(str(tmp_path / "spill"))
    assert _indices(cache) == list(range(6))
    assert cache.cached_bytes == 0  # everything went to the spool
    assert cache.spilled


def test_batcher_sheds_under_pressure_instead_of_queueing():
    from photon_tpu.serve.batcher import (
        BackpressureError,
        MicroBatcher,
        ScoreRequest,
    )

    gate = threading.Event()

    def scorer(reqs):
        gate.wait(5.0)
        return [0.0] * len(reqs)

    b = MicroBatcher(scorer, max_batch_size=1, max_delay_s=0.005,
                     queue_cap=8, name="prs")
    try:
        wd = resources.start_watchdog(limit_bytes=1 << 62, interval_s=3600)
        _plan(FaultRule("rss.sample", kind="rss", p=1.0, message="hard"))
        wd.sample()
        # Effective admission cap under hard pressure is 8 // 4 = 2: far
        # fewer than 10 submissions fit before backpressure trips.
        with pytest.raises(BackpressureError) as ei:
            for _ in range(10):
                b.submit(ScoreRequest({}))
        assert "2" in str(ei.value)
    finally:
        gate.set()
        b.close(drain=False)


def test_cd_raises_clean_host_memory_error_at_pass_boundary(tmp_path):
    from photon_tpu.algorithm.coordinate_descent import CoordinateDescent
    from photon_tpu.algorithm.fixed_effect import FixedEffectCoordinate
    from photon_tpu.data.game_data import GameBatch
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.objective import GLMObjective
    from photon_tpu.optim.factory import OptimizerSpec
    from photon_tpu.types import TaskType
    from photon_tpu.utils.checkpoint import latest_step

    rng = np.random.default_rng(3)
    n, d = 64, 4
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 0] = 1.0
    batch = GameBatch(
        label=jnp.asarray((rng.random(n) < 0.5).astype(np.float32)),
        offset=jnp.zeros(n, jnp.float32),
        weight=jnp.ones(n, jnp.float32),
        features={"global": jnp.asarray(X)},
        entity_ids={},
    )
    fixed = FixedEffectCoordinate(
        "global", "global", TaskType.LOGISTIC_REGRESSION,
        GLMObjective(loss=LogisticLoss, l2_weight=1.0, intercept_index=0),
        OptimizerSpec(),
    )
    wd = resources.start_watchdog(limit_bytes=1 << 62, interval_s=3600)
    _plan(FaultRule("rss.sample", kind="rss", p=1.0, message="hard"))
    wd.sample()
    ckpt = str(tmp_path / "ckpt")
    cd = CoordinateDescent({"global": fixed}, ["global"], num_iterations=3)
    with pytest.raises(resources.HostMemoryPressureError):
        cd.run(batch, checkpoint_dir=ckpt)
    # The pass boundary checkpointed before raising — the run is resumable.
    assert latest_step(ckpt) == 0
