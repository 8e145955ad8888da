"""Host pauses on the span clock: every garbage collection and every stall
of this process, each with its cause.

A span times the work it wraps, so a pause of the whole process shows as one
long fit or batch when it falls inside a span, and not at all between spans.
This module records the pauses themselves, through the same ``Tracer`` ring
and metrics registry as everything else, on the process's one host sampler
thread.

**Collections.** A ``gc.callbacks`` hook, installed once a process, holds
``jax.profiler.TraceAnnotation("photon/host/gc/gen<N>")`` open from a
collection's ``start`` to its ``stop``, the other callbacks included (looked
up as ``trace._annotation`` does, never imported), so that under a profiler
session a collection lies on the device trace's clock beside the device's
idle gaps. The hook may run
inside any code, also while ``Tracer._lock`` or a registry lock is held
(``Tracer._append`` builds a list under its lock, and that can start a
collection), so it takes no lock and opens no span: it appends
``(generation, t0, t1)`` to a bounded deque, whose ``append`` is atomic. The
sampler drains the deque into ``host_gc_seconds_total{generation}`` and
``host_gc_collections_total{generation}`` for every collection, and into a
root span ``host/gc/gen<N>`` for every gen-1 and gen-2 collection and every
gen-0 one of ``GC_SPAN_MIN_S`` or more.

**Stalls.** The sampler wakes every ``TICK_S`` and reads the clock and one
``getrusage(RUSAGE_SELF)``; every ``PRESSURE_EVERY_S`` it also reads
``/proc/pressure/{cpu,memory,io}`` (``some total``) and ``/proc/stat``'s
steal, where readable, and at most once every ``THREADS_EVERY_S`` each
thread's CPU from ``/proc/self/task/*/stat`` (a jax process has many native
threads). A wake ``STALL_S`` or more past its due time is a stall: nothing
of this process ran Python for that long. It is recorded as the root span
``host/stall/<cause>`` with its true start and length, in
``host_stall_seconds_total{cause}`` and ``host_stalls_total{cause}``, and as
one WARNING line on the logger ``photon_tpu.obs.stall`` that gives the
numbers the cause rests on, the thread that burned the most CPU around it,
and the top frames of every Python thread as the sampler found them on
waking. ``classify`` names the cause.

The sampler also runs the periodic jobs other layers hand it (the RSS
watchdog of ``utils/resources.py``), publishes ``host_sentinel_running`` 1
(so a reader tells "no stall" from "no sentinel") and counts its own CPU in
``host_sentinel_cpu_seconds_total``. ``start_sentinel`` starts it, once a
process; ``GameEstimator.fit``, ``ServingEngine`` and every driver's
``begin_run`` call it.
"""

from __future__ import annotations

import gc
import logging
import os
import resource
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from photon_tpu.obs.metrics import registry
from photon_tpu.obs.trace import _annotation, tracer

logger = logging.getLogger("photon_tpu.obs.stall")

# Each wake takes the interpreter lock from whichever thread holds it. On the
# chip's host (gVisor) a 10 ms tick cost a thread that dispatches to the chip
# ~2 % of its rate and a 20 ms one nothing measurable; a stall of 70 ms or
# more is still always caught, its start placed within one tick.
TICK_S = 0.020
# Ten times the interpreter's 5 ms switch interval, and under a tenth of the
# smallest stall on record (0.8 s).
STALL_S = 0.050
PRESSURE_EVERY_S = 0.100
THREADS_EVERY_S = 1.0
GC_SPAN_MIN_S = 0.001
GC_PATHS = ("host/gc/gen0", "host/gc/gen1", "host/gc/gen2")
TOP_FRAMES = 5

_CLK_TCK = os.sysconf("SC_CLK_TCK")
_PSI = {k: f"/proc/pressure/{k}" for k in ("cpu", "memory", "io")}


def classify(length_s: float, gc_s: float, major_faults: int, cpu_cores: float,
             psi_memory: float = 0.0, psi_io: float = 0.0) -> str:
    """The cause of a stall of ``length_s``, first match wins. ``gc_s`` is
    the part of it that collections cover; ``cpu_cores`` the process's CPU
    seconds over the sampler's interval ÷ that interval; ``psi_*`` the PSI
    ``some`` seconds ÷ their interval.

    - ``gc``: collections cover at least half of the stall;
    - ``fault``: a major fault, or PSI memory ``some`` at half or more;
    - ``io``: PSI io ``some`` at half or more;
    - ``preempted``: the process got under a quarter of one core: the OS did
      not run it (preemption, a stop signal, a VM pause; the log line's PSI
      cpu, steal and involuntary-switch numbers tell which);
    - ``busy``: the process ran at least half of one core: a thread of ours
      held the interpreter lock or computed (the log line names it);
    - ``unexplained``: none of these.
    """
    if gc_s >= 0.5 * length_s:
        return "gc"
    if major_faults > 0 or psi_memory >= 0.5:
        return "fault"
    if psi_io >= 0.5:
        return "io"
    if cpu_cores < 0.25:
        return "preempted"
    if cpu_cores >= 0.5:
        return "busy"
    return "unexplained"


class _GcHook:
    """Two ``gc.callbacks`` entries, the first and the last, so that a
    collection's time takes in every other callback too (jax's calls into
    XLA on both phases). The collector runs one collection at a time, so
    ``_t0`` and ``_ann`` are never written by two at once."""

    def __init__(self, maxlen: int = 1 << 14):
        self.done: deque = deque(maxlen=maxlen)
        self._t0: Optional[float] = None
        self._ann = None

    def install(self) -> None:
        if self.on_start not in gc.callbacks:
            gc.callbacks.insert(0, self.on_start)
            gc.callbacks.append(self.on_stop)

    def on_start(self, phase: str, info: dict) -> None:
        if phase != "start":
            return
        ann = _annotation(GC_PATHS[info["generation"]])
        if ann is not None:
            ann.__enter__()
        self._ann = ann
        self._t0 = time.monotonic()

    def on_stop(self, phase: str, info: dict) -> None:
        if phase != "stop":
            return
        t0, self._t0 = self._t0, None
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        if t0 is not None:  # None: installed while this collection ran
            self.done.append((info["generation"], t0, time.monotonic()))


class _ProcFiles:
    """The ``/proc`` files the sampler reads, through descriptors opened
    once: ``pread`` at offset 0 reads a proc file afresh at a third to a
    quarter of the cost of opening it. On the chip's host (gVisor, 177
    threads) a per-thread snapshot costs 2.6 ms this way and 10.6 ms opening
    each file. A process that runs out of descriptors would fail elsewhere,
    so at most a quarter of its limit stay open."""

    def __init__(self):
        self.psi = {key: _open(path) for key, path in _PSI.items()}
        self.stat = _open("/proc/stat")
        self.tasks: Dict[str, int] = {}
        soft = resource.getrlimit(resource.RLIMIT_NOFILE)[0]
        self._keep = 1024 if soft == resource.RLIM_INFINITY else soft // 4

    def close(self) -> None:
        for fd in [*self.psi.values(), self.stat, *self.tasks.values()]:
            if fd is not None:
                os.close(fd)
        self.psi, self.stat, self.tasks = {}, None, {}

    def thread_cpu(self) -> Dict[int, Tuple[str, int]]:
        """``{native id: (comm, user + system ticks)}`` of every thread."""
        out: Dict[int, Tuple[str, int]] = {}
        try:
            tids = os.listdir("/proc/self/task")
        except OSError:
            return out
        fds = self.tasks
        for tid in tids:
            fd = fds.get(tid)
            try:
                if fd is None:
                    fd = os.open(f"/proc/self/task/{tid}/stat", os.O_RDONLY)
                    if len(fds) < self._keep:
                        fds[tid] = fd
                try:
                    raw = os.pread(fd, 512, 0)
                finally:
                    if fds.get(tid) != fd:
                        os.close(fd)
                close = raw.rindex(b")")
                rest = raw[close + 2:].split()
                out[int(tid)] = (raw[raw.index(b"(") + 1:close].decode(errors="replace"),
                                 int(rest[11]) + int(rest[12]))
            except (OSError, ValueError, IndexError):
                # a thread gone (its id may come back as another's): reopen
                if tid in fds:
                    os.close(fds.pop(tid))
        for tid in fds.keys() - set(tids):
            os.close(fds.pop(tid))
        return out

    def psi_some_us(self) -> Dict[str, Optional[int]]:
        """PSI ``some total`` (µs) by resource; None where unreadable."""
        out: Dict[str, Optional[int]] = {}
        for key, fd in self.psi.items():
            line = _first_line(fd)
            try:
                out[key] = int(line[line.rindex(b"total=") + 6:])
            except (AttributeError, ValueError):
                out[key] = None
        return out

    def steal_ticks(self) -> Optional[int]:
        """Steal time of all CPUs, in clock ticks; None where unreadable."""
        line = _first_line(self.stat)
        try:
            return int(line.split()[8])
        except (AttributeError, ValueError, IndexError):
            return None


def _open(path: str) -> Optional[int]:
    try:
        return os.open(path, os.O_RDONLY)
    except OSError:
        return None


def _first_line(fd: Optional[int]) -> Optional[bytes]:
    if fd is None:
        return None
    try:
        return os.pread(fd, 256, 0).split(b"\n", 1)[0]
    except OSError:
        return None


def _top_frames(frame, n: int = TOP_FRAMES) -> str:
    """The innermost ``n`` frames, innermost first, without reading sources."""
    out = []
    while frame is not None and len(out) < n:
        code = frame.f_code
        out.append(f"{os.path.basename(code.co_filename)}:{frame.f_lineno} "
                   f"{code.co_name}")
        frame = frame.f_back
    return " < ".join(out)


class HostSampler:
    """The process's one host sampler thread: the stall sentinel, the drain
    of the collection hook, and the periodic jobs of other layers."""

    def __init__(self, hook: _GcHook):
        self.hook = hook
        self._jobs: Tuple[list, ...] = ()
        self._jobs_lock = threading.Lock()
        # Held while a tick works; a fork waits for it (see _before_fork).
        self.work = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._pending: List[Tuple[int, float, float]] = []
        self._proc: Optional[_ProcFiles] = None
        self._psi = (0.0, {})
        self._steal = (0.0, None)
        self._tasks: Tuple[float, Dict[int, Tuple[str, int]]] = (0.0, {})
        self._cpu = 0.0

    # -- other layers' periodic work ------------------------------------------

    def every(self, interval_s: float, fn: Callable[[], object]) -> list:
        """Call ``fn`` on this thread every ``interval_s`` (to the nearest
        ``PRESSURE_EVERY_S``), the first time one interval from now. Returns
        the handle ``cancel`` takes."""
        job = [interval_s, time.monotonic() + interval_s, fn]
        with self._jobs_lock:
            self._jobs = self._jobs + (job,)
        return job

    def cancel(self, job: list) -> None:
        with self._jobs_lock:
            self._jobs = tuple(j for j in self._jobs if j is not job)

    # -- the thread -------------------------------------------------------------

    def start(self) -> "HostSampler":
        self._thread = threading.Thread(target=self._run, name="photon-host-sampler",
                                        daemon=True)
        self._thread.start()
        return self

    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _run(self) -> None:
        clock, usage, me = time.monotonic, resource.getrusage, resource.RUSAGE_SELF
        proc = self._proc = _ProcFiles()
        now = clock()
        self._psi, self._steal = (now, proc.psi_some_us()), (now, proc.steal_ticks())
        self._tasks, self._cpu = (now, proc.thread_cpu()), time.thread_time()
        prev_t, prev_ru = now, usage(me)
        due = slow_due = now + TICK_S
        while True:
            pause = due - clock()
            if pause > 0:
                time.sleep(pause)
            now, ru = clock(), usage(me)
            with self.work:
                try:
                    if now - due >= STALL_S:
                        self._stall(due, now, prev_t, prev_ru, ru)
                    if now >= slow_due:
                        slow_due = now + PRESSURE_EVERY_S
                        self._slow(now)
                except Exception:  # the sampler must never die of a reading
                    logger.exception("host sampler tick failed")
            prev_t, prev_ru = now, ru
            due = clock() + TICK_S

    def _drain(self) -> None:
        done = self.hook.done
        for _ in range(len(done)):
            self._pending.append(done.popleft())

    def _publish_collections(self) -> None:
        self._drain()
        pending, self._pending = self._pending, []
        if not pending:
            return
        tr = tracer()
        by_gen: Dict[int, List[float]] = {}
        for gen, t0, t1 in pending:
            tally = by_gen.setdefault(gen, [0, 0.0])
            tally[0] += 1
            tally[1] += t1 - t0
            if gen or t1 - t0 >= GC_SPAN_MIN_S:
                tr.record(GC_PATHS[gen], t1 - t0, parent="", start_s=t0 - tr._epoch)
        reg = registry()
        for gen, (n, seconds) in by_gen.items():
            reg.counter("host_gc_collections_total", generation=gen).inc(n)
            reg.counter("host_gc_seconds_total", generation=gen).inc(seconds)

    def _slow(self, now: float) -> None:
        self._publish_collections()
        proc = self._proc
        self._psi, self._steal = (now, proc.psi_some_us()), (now, proc.steal_ticks())
        if now - self._tasks[0] >= THREADS_EVERY_S:
            self._tasks = (now, proc.thread_cpu())
        reg = registry()  # looked up each time: begin_run() resets it
        reg.gauge("host_sentinel_running").set(1)
        cpu = time.thread_time()
        reg.counter("host_sentinel_cpu_seconds_total").inc(cpu - self._cpu)
        self._cpu = cpu
        for job in self._jobs:
            if now >= job[1]:
                job[1] = now + job[0]
                try:
                    job[2]()
                except Exception:
                    logger.exception("host sampler job %r failed", job[2])

    def _stall(self, start: float, end: float, prev_t: float, ru0, ru1) -> None:
        frames = sys._current_frames()  # first: what runs as the sampler wakes
        length, interval = end - start, end - prev_t
        self._drain()
        gc_s = sum(max(0.0, min(t1, end) - max(t0, start))
                   for _, t0, t1 in self._pending)
        running = self.hook._t0  # a collection whose stop callbacks still run
        if running is not None:
            gc_s += max(0.0, end - max(running, start))
        cpu_cores = (ru1.ru_utime - ru0.ru_utime + ru1.ru_stime - ru0.ru_stime) / interval
        major_faults = ru1.ru_majflt - ru0.ru_majflt
        t_psi, psi0 = self._psi
        psi1 = self._proc.psi_some_us()
        psi = {k: (psi1[k] - psi0[k]) * 1e-6 / (end - t_psi) for k in psi1
               if psi1[k] is not None and psi0.get(k) is not None}
        t_steal, steal0 = self._steal
        steal1 = self._proc.steal_ticks()
        steal_s = (None if steal0 is None or steal1 is None
                   else (steal1 - steal0) / _CLK_TCK)
        t_tasks, tasks0 = self._tasks
        tasks1 = self._proc.thread_cpu()
        self._tasks = (end, tasks1)
        cause = classify(length, gc_s, major_faults, cpu_cores,
                         psi.get("memory", 0.0), psi.get("io", 0.0))

        tr = tracer()
        tr.record(f"host/stall/{cause}", length, parent="", start_s=start - tr._epoch)
        reg = registry()
        reg.counter("host_stall_seconds_total", cause=cause).inc(length)
        reg.counter("host_stalls_total", cause=cause).inc()

        threads = threading.enumerate()
        by_native = {t.native_id: t.name for t in threads}
        by_ident = {t.ident: t.name for t in threads}
        top = max(tasks1, default=None,
                  key=lambda tid: tasks1[tid][1] - tasks0.get(tid, ("", 0))[1])
        if top is None:
            burner = "unknown"
        else:
            ms = (tasks1[top][1] - tasks0.get(top, ("", 0))[1]) * 1e3 / _CLK_TCK
            burner = (f"{by_native.get(top, tasks1[top][0])} (tid {top}) "
                      f"{ms:.0f} ms in {end - t_tasks:.3f} s")
        me = threading.get_ident()
        stacks = "; ".join(f"{by_ident.get(ident, ident)}: {_top_frames(frame)}"
                           for ident, frame in frames.items() if ident != me)
        psi_text = " ".join(f"{k} {psi[k]:.2f}" for k in sorted(psi)) or "unreadable"
        steal_text = "unreadable" if steal_s is None else f"{steal_s:.3f} s"
        logger.warning(
            "host stall %.1f ms at +%.3f s cause=%s; over the last %.3f s: cpu "
            "%.2f cores, gc %.1f ms, major faults %d, involuntary switches %d, "
            "psi some %s, steal %s; most cpu: %s; frames: %s",
            length * 1e3, start - tr._epoch, cause, interval, cpu_cores,
            gc_s * 1e3, major_faults, ru1.ru_nivcsw - ru0.ru_nivcsw, psi_text,
            steal_text, burner, stacks)


_HOOK = _GcHook()
_SAMPLER: Optional[HostSampler] = None
_LOCK = threading.Lock()
_FORK_HELD: Optional[HostSampler] = None


def start_sentinel() -> HostSampler:
    """Install the collection hook and start the sampler thread, once a
    process (again in a forked child). Idempotent and cheap to call often."""
    global _SAMPLER
    s = _SAMPLER
    if s is not None and s.alive():
        return s
    with _LOCK:
        if _SAMPLER is None or not _SAMPLER.alive():
            _HOOK.install()
            _SAMPLER = HostSampler(_HOOK).start()
        return _SAMPLER


def _before_fork() -> None:
    # The child keeps only the forking thread: let it not inherit a registry
    # or tracer lock that the sampler held mid-tick.
    global _FORK_HELD
    s = _SAMPLER
    if s is not None and s.alive() and threading.get_ident() != s._thread.ident:
        s.work.acquire()
        _FORK_HELD = s


def _after_fork_in_parent() -> None:
    global _FORK_HELD
    s, _FORK_HELD = _FORK_HELD, None
    if s is not None:
        s.work.release()


def _after_fork_in_child() -> None:
    global _SAMPLER, _LOCK, _FORK_HELD
    if _SAMPLER is not None and _SAMPLER._proc is not None:
        _SAMPLER._proc.close()  # the parent's descriptors, copied by the fork
    _SAMPLER, _FORK_HELD, _LOCK = None, None, threading.Lock()
    _HOOK.done.clear()


os.register_at_fork(before=_before_fork, after_in_parent=_after_fork_in_parent,
                    after_in_child=_after_fork_in_child)
