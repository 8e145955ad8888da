"""Host pauses on the span clock (photon_tpu/obs/host.py): every collection
and every stall of the process as a root span with its cause, the counters
beside them, and the benchmark readers that turn them into per-layer
metrics."""

import gc
import logging
import os
import subprocess
import sys
import threading
import time
import types

import pytest

from benchmark import layers
from benchmark.readers import registry_delta, ring_overlap, trace_spans
from photon_tpu.obs import host
from photon_tpu.obs import trace as obs_trace
from photon_tpu.obs.metrics import registry
from photon_tpu.obs.trace import Tracer, get_spans, reset_tracer, tracer


def _now() -> float:
    """The span clock: seconds since the tracer's epoch."""
    return time.monotonic() - tracer()._epoch


def _wait(predicate, timeout: float = 5.0) -> bool:
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if predicate():
            return True
        time.sleep(0.02)
    return False


def _stalls(lo: float, hi: float):
    """Stall spans that overlap [lo, hi] on the span clock."""
    return [s for s in get_spans() if s.name.startswith("host/stall/")
            and s.start_s < hi and s.start_s + s.duration_s > lo]


def _value(name: str, **labels) -> float:
    inst = registry().find(name, **labels)
    return inst.value if inst is not None and inst.value is not None else 0


@pytest.fixture()
def sentinel():
    s = host.start_sentinel()
    time.sleep(0.15)  # a few ticks to stand on
    return s


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


# -- collections ------------------------------------------------------------------


def test_a_collection_is_counted_and_leaves_a_root_span(sentinel):
    before = _value("host_gc_collections_total", generation="2")
    t0 = _now()
    with tracer().span("outer") as outer:
        gc.collect()
    assert _wait(lambda: _value("host_gc_collections_total", generation="2") > before)
    assert _value("host_gc_seconds_total", generation="2") > 0
    assert _wait(lambda: any(s.name == "host/gc/gen2" and s.start_s >= t0
                             for s in get_spans()))
    mine = [s for s in get_spans() if s.name == "host/gc/gen2" and s.start_s >= t0]
    assert all(s.parent is None for s in mine)
    assert not any(s.name.startswith(outer + "/host") for s in get_spans())


def test_the_hook_takes_no_lock_of_the_tracer_or_the_registry(sentinel):
    before = _value("host_gc_collections_total", generation="2")

    def collect_under_the_locks():
        with tracer()._lock, registry()._lock:
            gc.collect()

    worker = threading.Thread(target=collect_under_the_locks, daemon=True)
    worker.start()
    worker.join(timeout=30)
    assert not worker.is_alive(), "a collection blocked on a lock its caller held"
    assert _wait(lambda: _value("host_gc_collections_total", generation="2") > before)


def test_collections_everywhere_deadlock_nothing(sentinel):
    done = []

    def work(i):
        end = time.monotonic() + 2.0
        n = 0
        while time.monotonic() < end:
            with tracer().span(f"worker{i}"):
                registry().counter("host_pause_drill_total", worker=i).inc()
                _ = [[j] for j in range(8)]
            n += 1
        done.append(n)

    threshold = gc.get_threshold()
    threads = [threading.Thread(target=work, args=(i,), daemon=True) for i in range(4)]
    gc.set_threshold(1)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        gc.set_threshold(*threshold)
    try:
        assert not any(t.is_alive() for t in threads)
        assert len(done) == 4 and all(n > 0 for n in done)
        # no update lost to a collection in the middle of one
        assert sum(_value("host_pause_drill_total", worker=i) for i in range(4)) \
            == sum(done)
        # the sampler lived through it and still drains the hook
        assert sentinel.alive()
        before = _value("host_gc_collections_total", generation="2")
        gc.collect()
        assert _wait(lambda: _value("host_gc_collections_total", generation="2") > before)
    finally:
        reset_tracer()  # the drill filled the ring with its collections


# -- stalls -----------------------------------------------------------------------


def _hold_the_interpreter_lock(seconds: float):
    """Spin in pure Python with the switch interval raised, so the sampler
    cannot take the lock meanwhile; the stall spans that overlap the spin."""
    time.sleep(host.THREADS_EVERY_S + 0.1)  # a fresh per-thread CPU reading
    switch = sys.getswitchinterval()
    t0 = _now()
    sys.setswitchinterval(1.0)
    try:
        _spin(seconds)
    finally:
        sys.setswitchinterval(switch)
    t1 = _now()
    time.sleep(0.3)
    return _stalls(t0, t1)


def test_a_thread_holding_the_interpreter_lock_is_a_busy_stall(sentinel, caplog):
    caplog.set_level(logging.WARNING, logger="photon_tpu.obs.stall")
    # A spin that the host itself deschedules for long runs under half a
    # core and is rightly no `busy` stall: three tries.
    for _ in range(3):
        stalls = _hold_the_interpreter_lock(0.2)
        assert len(stalls) == 1, [(s.name, s.duration_s) for s in stalls]
        if stalls[0].name == "host/stall/busy":
            break
    (stall,) = stalls
    assert stall.name == "host/stall/busy" and stall.parent is None
    assert 0.15 <= stall.duration_s <= 0.3
    lines = [r.getMessage() for r in caplog.records
             if r.name == "photon_tpu.obs.stall" and "cause=busy" in r.getMessage()]
    assert lines
    assert f"most cpu: {threading.current_thread().name} " in lines[-1]
    assert f"{threading.current_thread().name}: " in lines[-1].split("frames: ")[1]


def test_a_stopped_process_is_a_preempted_stall(sentinel):
    code = ("import os, signal, sys, time\n"
            "pid = int(sys.argv[1])\n"
            "time.sleep(0.3)\n"
            "os.kill(pid, signal.SIGSTOP)\n"
            "time.sleep(0.2)\n"
            "os.kill(pid, signal.SIGCONT)\n")
    t0 = _now()
    proc = subprocess.run([sys.executable, "-c", code, str(os.getpid())],
                          timeout=60)
    time.sleep(0.3)
    assert proc.returncode == 0
    stalls = _stalls(t0, _now())
    assert any(s.name == "host/stall/preempted" and 0.12 <= s.duration_s <= 0.5
               for s in stalls), [(s.name, s.duration_s) for s in stalls]


def test_a_quiet_second_records_no_stall(sentinel):
    # The host may pause any process of its own accord; three quiet seconds
    # in a row that each record a stall are the sentinel's fault.
    for _ in range(3):
        t0 = _now()
        time.sleep(1.0)
        t1 = _now()
        time.sleep(0.05)
        if not _stalls(t0, t1):
            return
    pytest.fail(f"every quiet second recorded a stall: {_stalls(t0, t1)}")


@pytest.mark.parametrize("args,kwargs,cause", [
    ((0.2, 0.15, 0, 1.0), {}, "gc"),
    ((0.2, 0.15, 3, 0.0), {"psi_io": 0.9}, "gc"),
    ((0.2, 0.0, 1, 1.0), {}, "fault"),
    ((0.2, 0.0, 0, 1.0), {"psi_memory": 0.6}, "fault"),
    ((0.2, 0.0, 0, 1.0), {"psi_io": 0.7}, "io"),
    ((0.2, 0.0, 0, 0.1), {}, "preempted"),
    ((0.2, 0.0, 0, 0.9), {}, "busy"),
    ((0.2, 0.0, 0, 0.4), {}, "unexplained"),
])
def test_a_stall_is_put_down_to_the_first_cause_that_fits(args, kwargs, cause):
    assert host.classify(*args, **kwargs) == cause


# -- the one sampler thread -------------------------------------------------------


def test_the_rss_watchdog_samples_on_the_one_host_sampler_thread(sentinel):
    from photon_tpu.utils import resources

    resources.stop_watchdog()
    wd = resources.start_watchdog(limit_bytes=1 << 62, interval_s=0.1)
    try:
        assert _wait(lambda: wd._last_rss > 0)
        names = [t.name for t in threading.enumerate()]
        assert names.count("photon-host-sampler") == 1
        assert "rss-watchdog" not in names
        assert host.start_sentinel() is sentinel
    finally:
        resources.stop_watchdog()


def test_the_per_thread_snapshot_keeps_one_descriptor_a_live_thread():
    proc = host._ProcFiles()
    try:
        done = threading.Event()
        worker = threading.Thread(target=done.wait, daemon=True)
        worker.start()
        snap = proc.thread_cpu()
        assert {threading.get_native_id(), worker.native_id} <= set(snap)
        assert str(worker.native_id) in proc.tasks
        done.set()
        worker.join()  # returns before the OS thread is gone: wait for that
        assert _wait(lambda: str(worker.native_id) not in os.listdir("/proc/self/task"))
        snap = proc.thread_cpu()
        assert worker.native_id not in snap
        assert str(worker.native_id) not in proc.tasks  # closed, not leaked
        proc.close()
        proc._keep = 0  # at the descriptor cap: each file opened and closed
        assert threading.get_native_id() in proc.thread_cpu()
        assert proc.tasks == {}
    finally:
        proc.close()


def test_the_sentinel_says_it_runs_and_counts_its_own_cpu(sentinel):
    assert _wait(lambda: _value("host_sentinel_running") == 1)
    assert _wait(lambda: registry().find("host_sentinel_cpu_seconds_total") is not None)


# -- the benchmark's readers --------------------------------------------------------

RUNNING = [{"metric": "host_sentinel_running", "labels": {}, "value": 1}]
FIT_READER = dict(within="^game-estimator/", unit="^game-estimator/prepare-datasets$",
                  skip=1, requires="host_sentinel_running")


# Synthetic times lie far past the spans the running sentinel may record
# into the same ring while a test runs.
T = 1e6


@pytest.fixture()
def ring(monkeypatch):
    """A private ring of three fits: fit 0 over T + [0, 3] s, fit 1 [3, 4],
    fit 2 [4, 5], each a prepare span and a train span with a child."""
    tr = Tracer()
    for a, b, c in ((0.0, 1.0, 3.0), (3.0, 3.1, 4.0), (4.0, 4.1, 5.0)):
        tr.record("game-estimator/prepare-datasets", b - a, parent="", start_s=T + a)
        tr.record("game-estimator/train[cfg]", c - b, parent="", start_s=T + b)
        tr.record("cd/iter0/global", (c - b) / 2, parent="game-estimator/train[cfg]",
                  start_s=T + b)
    monkeypatch.setattr(obs_trace, "_TRACER", tr)
    return tr


def test_ring_overlap_reads_the_pauses_inside_every_fit_but_the_first(ring):
    ring.record("host/stall/busy", 0.5, parent="", start_s=T + 2.0)       # fit 0
    ring.record("host/stall/gc", 0.15, parent="", start_s=T + 3.9)        # fits 1, 2
    ring.record("host/stall/preempted", 0.1, parent="", start_s=T + 5.2)  # after
    ring.record("host/gc/gen2", 0.02, parent="", start_s=T + 4.5)
    facts = dict(registry_after=RUNNING)
    assert ring_overlap.read(dict(FIT_READER, span="^host/stall/"), facts) == \
        pytest.approx(75.0)
    assert ring_overlap.read(dict(FIT_READER, span="^host/gc/"), facts) == \
        pytest.approx(10.0)
    assert layers.read_metric("host_stall_ms", facts) == pytest.approx(75.0)
    assert layers.read_metric("host_gc_ms", facts) == pytest.approx(10.0)


def test_ring_overlap_is_zero_with_the_sentinel_and_no_pause(ring):
    facts = dict(registry_after=RUNNING)
    assert layers.read_metric("host_stall_ms", facts) == 0.0
    assert layers.read_metric("host_gc_ms", facts) == 0.0


def test_ring_overlap_reads_nothing_without_the_sentinel_or_after_a_drop(ring):
    ring.record("host/stall/busy", 0.5, parent="", start_s=T + 3.5)
    assert layers.read_metric("host_stall_ms", dict(registry_after=[])) is None
    ring.dropped_spans = 1
    assert layers.read_metric("host_stall_ms", dict(registry_after=RUNNING)) is None


def test_ring_overlap_reads_nothing_with_one_fit_only(monkeypatch):
    tr = Tracer()
    tr.record("game-estimator/prepare-datasets", 1.0, parent="", start_s=T)
    monkeypatch.setattr(obs_trace, "_TRACER", tr)
    assert layers.read_metric("host_stall_ms", dict(registry_after=RUNNING)) is None


def _counter(name, value, **labels):
    return {"metric": name, "type": "counter", "labels": labels, "value": value}


def test_registry_delta_reads_what_the_window_added():
    before = RUNNING + [_counter("host_stall_seconds_total", 0.1, cause="busy"),
                        _counter("host_gc_seconds_total", 0.05, generation="0")]
    after = RUNNING + [_counter("host_stall_seconds_total", 0.4, cause="busy"),
                       _counter("host_stall_seconds_total", 0.1, cause="gc"),
                       _counter("host_gc_seconds_total", 0.25, generation="0"),
                       _counter("host_gc_seconds_total", 0.1, generation="2")]
    facts = dict(registry_before=before, registry_after=after, window_s=2.0)
    assert layers.read_metric("serve_stall_ms", facts) == pytest.approx(200.0)
    assert layers.read_metric("serve_gc_ms", facts) == pytest.approx(150.0)
    assert registry_delta.read(
        dict(metric="host_stall_seconds_total", requires="host_sentinel_running"),
        facts) == pytest.approx(0.4)


def test_registry_delta_is_zero_with_the_sentinel_and_nothing_without_it():
    quiet = dict(registry_before=RUNNING, registry_after=RUNNING, window_s=30.0)
    assert layers.read_metric("serve_stall_ms", quiet) == 0.0
    assert layers.read_metric("serve_gc_ms", quiet) == 0.0
    older = dict(registry_before=[], registry_after=[], window_s=30.0)
    assert layers.read_metric("serve_stall_ms", older) is None
    assert layers.read_metric("serve_gc_ms", older) is None


@pytest.fixture()
def traced(monkeypatch):
    """A traced slice of 2 s over one device busy for its first half, and the
    host spans ``load`` finds there (filled by the test)."""
    spans = []
    device = dict(busy=[(T, T + 1.0)], launch_starts=[])
    monkeypatch.setattr(trace_spans, "load", lambda path: ([device], spans))
    facts = dict(registry_after=RUNNING, trace_path="trace.pb", traced_fits=2,
                 trace=types.SimpleNamespace(window=(T, T + 2.0), window_s=2.0))
    return spans, facts


def test_gc_idle_reads_the_device_idle_inside_collections(traced):
    spans, facts = traced
    spans += [("host/gc/gen0", T + 0.5, T + 1.5), ("host/gc/gen2", T + 1.8, T + 1.9)]
    assert layers.read_metric("gc_idle_ms", facts) == pytest.approx(300.0)


def test_gc_idle_is_zero_with_the_sentinel_and_no_collection_in_the_slice(traced):
    spans, facts = traced
    spans += [("game-estimator/train[cfg]", T, T + 2.0)]
    assert layers.read_metric("gc_idle_ms", facts) == 0.0


def test_gc_idle_reads_nothing_without_the_sentinel_a_trace_or_a_device(
        traced, monkeypatch):
    spans, facts = traced
    spans += [("host/gc/gen2", T + 1.5, T + 1.6)]
    assert layers.read_metric("gc_idle_ms", dict(facts, registry_after=[])) is None
    assert layers.read_metric("gc_idle_ms", dict(facts, trace=None)) is None
    monkeypatch.setattr(trace_spans, "load", lambda path: ([], spans))
    assert layers.read_metric("gc_idle_ms", facts) is None
