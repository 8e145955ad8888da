"""The device as JAX reports it, the compile clock and the compile cache.

Copied in spirit from ``chip_smoke.py`` (``_require_tpu``, ``_CompileClock``,
``_peak_bytes``): the benchmark keeps its own copy so that no later change to
the program can move the yardstick.
"""

from __future__ import annotations

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def process_start_time() -> float:
    """Unix time at which this process was created (``/proc``), so that
    ``setup_s`` covers the interpreter's start and the imports too. Falls
    back to "now" where ``/proc`` is not there."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        ticks = float(fields[19])  # starttime: field 22 of the whole line
        with open("/proc/stat") as f:
            btime = next(float(l.split()[1]) for l in f if l.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError, StopIteration):
        return time.time()


def configure_cache() -> str:
    """JAX's persistent compilation cache at ONE fixed path inside the
    checkout (``<checkout>/.jax_cache``, the program's own default), or where
    ``JAX_COMPILATION_CACHE_DIR`` says. The program's helper is what every
    driver calls, so the benchmark and the program agree on the directory."""
    from photon_tpu.utils.compile_cache import configure_compile_cache

    return configure_compile_cache()


def require_tpu(chips: int) -> dict:
    """The device block of the result line, or exit non-zero naming what was
    found instead. No flag or variable turns this off."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"benchmark: needs a TPU; the JAX default backend is {backend!r} "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). Nothing ran.",
              file=sys.stderr, flush=True)
        sys.exit(3)
    devices = jax.devices()
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chip(s); JAX sees "
              f"{len(devices)}. Nothing ran.", file=sys.stderr, flush=True)
        sys.exit(3)
    return describe_device()


def describe_device() -> dict:
    import jax

    devices = jax.devices()
    return dict(platform=devices[0].platform, kind=devices[0].device_kind,
                count=len(devices))


def peak_bytes() -> int:
    """Peak device memory of this process so far on the fullest chip."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats()
        if stats:
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from
    the persistent cache), and the cache's hits and misses, from
    ``jax.monitoring``. ``backend_compiles`` counts programs that reached the
    backend compiler or the cache: inside a measured window it must stay 0."""

    _DURATIONS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.backend_seconds = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in self._DURATIONS:
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.backend_seconds += duration
                self.backend_compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return dict(compile_s=round(self.seconds, 3),
                    backend_compile_s=round(self.backend_seconds, 3),
                    backend_compiles=self.backend_compiles,
                    cache_hits=self.cache_hits, cache_misses=self.cache_misses)
