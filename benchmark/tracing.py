"""The profiler, around a steady slice of the window, and the benchmark's
own host annotations (``bench/fit``, ``bench/submit``, ``bench/reference``),
which land in the same trace as the device's operations."""

from __future__ import annotations

import glob
import os
import shutil
import time
from typing import Optional


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """``start()`` … ``stop()`` around a slice; off unless the run was
    started with ``--trace 1``. The trace is written under the checkout's
    work directory and removed (with the run's work directory) once it has been reduced."""

    def __init__(self, ctx):
        self.enabled = bool(ctx.trace)
        self.dir = os.path.join(ctx.work_dir, "trace")
        self.path: Optional[str] = None
        self.started = self.stopped = False
        self.t_start = self.t_stop = None

    def start(self) -> None:
        if not self.enabled or self.started:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # no Python call stacks: small trace
        options.host_tracer_level = 2     # TraceAnnotation events are kept
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.started = True
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        if not self.started or self.stopped:
            return
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.stopped = True
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        self.path = found[0] if found else None

    @property
    def wall_s(self) -> Optional[float]:
        if self.t_start is None or self.t_stop is None:
            return None
        return self.t_stop - self.t_start


class Slice(Tracer):
    """A slice counted in completed units (fits). The profiler starts before
    unit ``first``; that unit pays the profiler's own start and is annotated
    ``bench-warm/…``, outside the window. The ``count`` units after it are
    the slice."""

    def __init__(self, ctx, first: int, count: int):
        super().__init__(ctx)
        self.first, self.count = first, count
        self.units = 0

    def before(self, done: int) -> None:
        if done == self.first:
            self.start()

    def name(self, done: int, unit: str) -> str:
        warm = self.started and not self.stopped and done == self.first
        return f"bench-warm/{unit}" if warm else f"bench/{unit}"

    def after(self, done: int) -> None:
        if self.started and not self.stopped:
            if done == self.first + 1:
                self.t_start = time.perf_counter()   # the slice begins here
            self.units = done - self.first - 1
            if self.units >= self.count:
                self.stop()

    def fits_wall(self) -> dict:
        return dict(fits=self.units if self.stopped else 0, wall_s=self.wall_s)
