"""Resource-exhaustion containment: one policy for device OOM, disk-full,
and host memory pressure.

Every budget in the tree (device residency bytes, replay-cache bytes,
pipeline queue depths, telemetry report bytes) is a *guess* about a ceiling
the OS and the XLA allocator enforce for real. This module is what happens
when the guess is wrong, governed by a single degradation priority:

    model artifacts (checkpoints, published generations)
        > training progress
        > observability (telemetry, dead letters, reports)

Concretely:

- **Device OOM** (``XlaRuntimeError: RESOURCE_EXHAUSTED``, caught nowhere
  before this layer): the residency stores evict harder, shrink their
  effective byte budget toward the floor (the largest single block — the
  same floor :class:`~photon_tpu.data.residency.ByteBudgetLru` already
  admits at), and retry once. Bit parity is preserved because the
  out-of-core path is value-identical at any budget. A hard
  :class:`DeviceMemoryError` fires only when the floor itself cannot fit.
- **Disk full** (``ENOSPC``/``EDQUOT``): observability writers degrade to
  counted drops (``disk_enospc_total{site}``, never raising into the
  training loop); the replay spool falls back to the legacy re-stream path
  and removes its partial file; the checkpoint writer prunes older steps
  (keep-last-K) and retries before giving up, never leaving a tmp file.
- **Host RSS pressure**: a cgroup-aware sampler (:class:`RssWatchdog`, a job
  of the process's one host sampler thread in :mod:`photon_tpu.obs.host`)
  publishes a pressure level that allocating layers
  poll — pipeline queue depths and the serving admission cap tighten at
  *soft* pressure; at *hard* pressure the training loop's pass-boundary
  check raises a clean, actionable :class:`HostMemoryPressureError` instead
  of letting the kernel OOM-killer produce an unexplained SIGKILL.

All paths are exercised by the ``enospc``/``oom``/``rss`` kinds in
:mod:`photon_tpu.utils.faults` and the ``bench.py --exhaustion-soak`` /
``ci.sh exhaustion`` smokes.
"""

from __future__ import annotations

import errno
import gc
import logging
import os
import threading
from typing import Callable, Optional

from photon_tpu.utils import faults

logger = logging.getLogger(__name__)

RSS_LIMIT_ENV = "PHOTON_TPU_RSS_LIMIT_BYTES"
RSS_SOFT_ENV = "PHOTON_TPU_RSS_SOFT_FRACTION"
RSS_HARD_ENV = "PHOTON_TPU_RSS_HARD_FRACTION"

#: Pressure levels published by the watchdog (monotone: OK < SOFT < HARD).
LEVEL_OK, LEVEL_SOFT, LEVEL_HARD = 0, 1, 2
_LEVEL_NAMES = {LEVEL_OK: "ok", LEVEL_SOFT: "soft", LEVEL_HARD: "hard"}


class ResourceExhaustedError(RuntimeError):
    """Base for clean, actionable exhaustion failures raised by this layer
    (as opposed to a raw allocator traceback or an OOM-killer SIGKILL)."""


class DeviceMemoryError(ResourceExhaustedError):
    """Device memory exhausted even after evict-harder + budget shrink down
    to the floor (largest single block). The message says which knob to
    turn; there is no safe automatic recovery below the floor."""


class HostMemoryPressureError(ResourceExhaustedError):
    """Host RSS crossed the hard-pressure threshold. Raised at a cooperative
    check point (pass boundary), before the kernel OOM-killer would have
    SIGKILLed the process with no explanation."""


# ---------------------------------------------------------------------------
# Failure classification
# ---------------------------------------------------------------------------


def is_device_oom(exc: BaseException) -> bool:
    """True for a device allocator OOM: a real ``XlaRuntimeError`` whose
    message carries ``RESOURCE_EXHAUSTED`` / ``Out of memory``, or the
    injected :class:`~photon_tpu.utils.faults.DeviceOomInjectedFault`
    (whose message embeds the same marker). Classified by message rather
    than type so we need no import of jaxlib internals."""
    if not isinstance(exc, Exception):
        return False
    msg = str(exc)
    return "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg


def is_enospc(exc: BaseException) -> bool:
    """True for a disk-full/quota failure (``ENOSPC`` or ``EDQUOT``),
    including the injected ``enospc`` fault kind which carries the errno."""
    return isinstance(exc, OSError) and exc.errno in (
        errno.ENOSPC,
        getattr(errno, "EDQUOT", errno.ENOSPC),
    )


def _metrics():
    from photon_tpu.obs import registry

    return registry()


# ---------------------------------------------------------------------------
# Device OOM containment
# ---------------------------------------------------------------------------


def oom_retry(
    attempt: Callable[[], object],
    *,
    site: str,
    evict: Optional[Callable[[int], None]] = None,
    retries: int = 1,
    counter: str = "device_oom_retries_total",
    **labels,
):
    """Run ``attempt``; on device OOM call ``evict(attempt_index)`` (the
    caller's evict-harder / budget-shrink hook), ``gc.collect()`` to release
    dropped device buffers, and retry up to ``retries`` times. Counts each
    contained OOM in ``counter{site=...}``. Non-OOM exceptions propagate
    untouched; the final OOM propagates to the caller, which decides whether
    it is a hard :class:`DeviceMemoryError`."""
    for i in range(retries + 1):
        try:
            return attempt()
        except Exception as exc:  # noqa: BLE001 — classified below
            if not is_device_oom(exc) or i >= retries:
                raise
            logger.warning(
                "device OOM at %s (attempt %d/%d): evicting harder and "
                "retrying: %s", site, i + 1, retries + 1, exc,
            )
            try:
                _metrics().counter(counter, site=site, **labels).inc()
            except Exception:
                pass
            if evict is not None:
                evict(i)
            gc.collect()
    raise AssertionError("unreachable")  # pragma: no cover


# ---------------------------------------------------------------------------
# Disk-full containment
# ---------------------------------------------------------------------------


class DiskBudgetGuard:
    """Shared ENOSPC policy for one writer site (replay spool,
    ``--re-spill-dir``, dead-letter sidecar, telemetry sink, checkpoint
    writer). It does three things, all cheap:

    - ``check()`` runs the fault hook for the site, so an ``enospc`` rule in
      the plan raises exactly where a real full disk would;
    - ``record(exc)`` classifies an ``OSError`` (counts
      ``disk_enospc_total{site}`` vs ``disk_write_failures_total{site}``)
      and returns True when it was a disk-space failure;
    - ``cleanup(*paths)`` best-effort-unlinks partial artifacts so a failed
      write never leaks the very space a retry needs.

    The *policy* on failure (drop / fall back / prune-and-retry) stays with
    the caller, because it differs by degradation priority.
    """

    def __init__(self, site: str):
        self.site = site

    def check(self) -> None:
        faults.check(self.site)

    def record(self, exc: BaseException) -> bool:
        full = is_enospc(exc)
        try:
            name = "disk_enospc_total" if full else "disk_write_failures_total"
            _metrics().counter(name, site=self.site).inc()
        except Exception:
            pass
        return full

    def cleanup(self, *paths: Optional[str]) -> None:
        for p in paths:
            if not p:
                continue
            try:
                os.unlink(p)
            except OSError:
                pass


# ---------------------------------------------------------------------------
# Host RSS watchdog
# ---------------------------------------------------------------------------


def _cgroup_mem_limit() -> Optional[int]:
    """Container memory limit, cgroup v2 then v1 (same spirit as
    ``io.columnar._available_cores``). None when unlimited/undetectable."""
    for path in ("/sys/fs/cgroup/memory.max",
                 "/sys/fs/cgroup/memory/memory.limit_in_bytes"):
        try:
            raw = open(path).read().strip()
        except OSError:
            continue
        if raw == "max":
            continue
        try:
            limit = int(raw)
        except ValueError:
            continue
        # v1 reports ~PTRDIFF_MAX when unlimited.
        if 0 < limit < (1 << 60):
            return limit
    return None


def _read_rss_bytes() -> Optional[int]:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource as _resource

        return _resource.getrusage(_resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return None


class RssWatchdog:
    """Samples host RSS against a limit (env override → cgroup) on the
    process's one host sampler thread (:mod:`photon_tpu.obs.host`) and
    publishes a pressure level other layers poll.

    - ``level()`` → LEVEL_OK / LEVEL_SOFT / LEVEL_HARD (lock-free read).
    - ``check(site)`` → raises :class:`HostMemoryPressureError` at hard
      pressure; called at cooperative boundaries (CD pass loop, λ sweep).
    - Gauges ``host_rss_bytes`` / ``host_rss_limit_bytes`` /
      ``host_rss_pressure_level``; transitions count
      ``rss_pressure_events_total{level}``.
    - The ``rss.sample`` fault site lets a plan simulate pressure: a fired
      ``rss`` rule with ``"hard"`` in its message reads as hard pressure,
      any other fired ``rss`` rule as soft.

    With no detectable limit the watchdog is inert (level stays OK) — same
    contract as an uncontainerised host with abundant RAM.
    """

    def __init__(
        self,
        limit_bytes: Optional[int] = None,
        soft_fraction: Optional[float] = None,
        hard_fraction: Optional[float] = None,
        interval_s: float = 0.5,
    ):
        if limit_bytes is None:
            env = os.environ.get(RSS_LIMIT_ENV, "").strip()
            if env:
                limit_bytes = int(env)
            else:
                limit_bytes = _cgroup_mem_limit()
        self.limit_bytes = limit_bytes
        self.soft_fraction = float(
            soft_fraction if soft_fraction is not None
            else os.environ.get(RSS_SOFT_ENV, 0.85))
        self.hard_fraction = float(
            hard_fraction if hard_fraction is not None
            else os.environ.get(RSS_HARD_ENV, 0.95))
        self.interval_s = interval_s
        self._level = LEVEL_OK
        self._last_rss = 0
        self._sampler = self._job = None

    # -- sampling ----------------------------------------------------------

    def sample(self) -> int:
        """Take one sample and return the new level. Called by the thread
        loop; tests and single-threaded drivers may call it directly."""
        rss = _read_rss_bytes() or 0
        self._last_rss = rss
        level = LEVEL_OK
        if self.limit_bytes:
            frac = rss / self.limit_bytes
            if frac >= self.hard_fraction:
                level = LEVEL_HARD
            elif frac >= self.soft_fraction:
                level = LEVEL_SOFT
        rule = faults.injector().fire("rss.sample")
        if rule is not None and rule.kind == "rss":
            level = LEVEL_HARD if "hard" in rule.message else LEVEL_SOFT
        prev, self._level = self._level, level
        try:
            m = _metrics()
            m.gauge("host_rss_bytes").set(rss)
            m.gauge("host_rss_limit_bytes").set(self.limit_bytes or 0)
            m.gauge("host_rss_pressure_level").set(level)
            if level != prev and level != LEVEL_OK:
                m.counter("rss_pressure_events_total",
                          level=_LEVEL_NAMES[level]).inc()
        except Exception:
            pass
        if level != prev and level != LEVEL_OK:
            logger.warning(
                "host memory pressure %s: rss=%d limit=%s (queue depths and "
                "admission caps tighten; hard pressure fails the run cleanly "
                "at the next pass boundary)",
                _LEVEL_NAMES[level], rss, self.limit_bytes,
            )
        return level

    def _sample_logged(self) -> None:
        try:
            self.sample()
        except Exception:  # the watchdog must never kill its host
            logger.exception("rss watchdog sample failed")

    def start(self) -> "RssWatchdog":
        """Sample every ``interval_s`` on the host sampler thread, the first
        time one interval from now."""
        if self._job is None:
            from photon_tpu.obs.host import start_sentinel

            self._sampler = start_sentinel()
            self._job = self._sampler.every(self.interval_s, self._sample_logged)
        return self

    def stop(self) -> None:
        sampler, job = self._sampler, self._job
        self._sampler = self._job = None
        if job is not None:
            sampler.cancel(job)

    # -- polling API -------------------------------------------------------

    def level(self) -> int:
        return self._level

    def check(self, site: str = "") -> None:
        if self._level >= LEVEL_HARD:
            raise HostMemoryPressureError(
                f"host RSS {self._last_rss} of limit {self.limit_bytes} "
                f"crossed the hard-pressure fraction "
                f"{self.hard_fraction:.2f}"
                + (f" at {site}" if site else "")
                + "; stopping cleanly before the kernel OOM-killer does it "
                "for us. Lower --replay-cache-mb / --re-device-budget-mb / "
                "queue depths, raise the container memory limit, or tune "
                f"{RSS_SOFT_ENV}/{RSS_HARD_ENV}."
            )


# ---------------------------------------------------------------------------
# Process-wide watchdog + pressure helpers (the only API poll sites use)
# ---------------------------------------------------------------------------

_watchdog: Optional[RssWatchdog] = None
_watchdog_lock = threading.Lock()


def watchdog() -> Optional[RssWatchdog]:
    return _watchdog


def start_watchdog(**kwargs) -> RssWatchdog:
    """Install and start the process-wide watchdog (CLI entry points call
    this once). Idempotent: a second call returns the existing one."""
    global _watchdog
    with _watchdog_lock:
        if _watchdog is None:
            _watchdog = RssWatchdog(**kwargs).start()
        return _watchdog


def stop_watchdog() -> None:
    global _watchdog
    with _watchdog_lock:
        wd, _watchdog = _watchdog, None
    if wd is not None:
        wd.stop()


def pressure_level() -> int:
    wd = _watchdog
    return wd.level() if wd is not None else LEVEL_OK


def memory_pressure() -> bool:
    """True at soft pressure or worse — layers that can cheaply hold less
    (replay cache admission, prefetch depth) consult this."""
    return pressure_level() >= LEVEL_SOFT


def tightened_depth(depth: int) -> int:
    """Pipeline prefetch/queue depth under the current pressure level:
    unchanged when OK, 1 under any pressure (each queue slot pins a decoded
    host block, so depth is the cheapest RSS to give back)."""
    return 1 if (pressure_level() >= LEVEL_SOFT and depth > 1) else depth


def tightened_cap(cap: int) -> int:
    """Admission-queue cap under the current pressure level: unchanged when
    OK, halved at soft pressure, quartered (min 1) at hard — serving sheds
    by backpressure rather than dying by OOM-killer."""
    level = pressure_level()
    if level >= LEVEL_HARD:
        return max(1, cap // 4)
    if level >= LEVEL_SOFT:
        return max(1, cap // 2)
    return cap


def check_memory(site: str = "") -> None:
    """Raise :class:`HostMemoryPressureError` at hard pressure. Training
    loops call this at pass boundaries, next to the shutdown poll."""
    wd = _watchdog
    if wd is not None:
        wd.check(site)


__all__ = [
    "LEVEL_HARD",
    "LEVEL_OK",
    "LEVEL_SOFT",
    "DeviceMemoryError",
    "DiskBudgetGuard",
    "HostMemoryPressureError",
    "ResourceExhaustedError",
    "RssWatchdog",
    "check_memory",
    "is_device_oom",
    "is_enospc",
    "memory_pressure",
    "oom_retry",
    "pressure_level",
    "start_watchdog",
    "stop_watchdog",
    "tightened_cap",
    "tightened_depth",
    "watchdog",
]
