"""The program's own spans against the device's operations, on the one clock
of the profiler's trace.

While a span of ``photon_tpu.obs.trace`` is open the program holds a
``TraceAnnotation`` named ``photon/<path>`` open, so a traced run has those
events on the host planes. This reader selects spans by path, cuts them to
the window ``facts["trace"]`` has, and reads:

- ``busy_ms``: the union of the device's operation intervals inside the
  spans. A coordinate update (``cd/iter<i>/<cid>``) ends on a fence, so this
  is that coordinate's device time whatever programs or kernels it launches.
  Its closing ``exchange`` is dispatched after the fence: those two
  elementwise launches (~0.1 ms) may run inside the next update's interval.
- ``idle_ms``: the spans' own extent less that;
- ``wall_ms``: the spans' summed length;
- ``launches``: events of the "XLA Modules" line that start inside a span;
- ``share``: ``wall_ms`` over the window, in %.

params: ``span`` (a regex searched in the path, ``photon/`` left off) or
``coordinate_type`` (``fixed`` / ``random``: the updates of the coordinates
of that type, their ids taken from ``facts["counts"]``); ``value`` (one of
the five above); ``per`` (a fact to divide by, or ``span`` for the mean over
the occurrences that reach into the window, a cut one counted whole).

Nothing when no span matches (a program without the spans, as before PR 27):
never 0.

Two limits, both read on the chip (PERF.md section 5, PR 27). Only a span
that ends on a fence owns the device time inside it: dispatch is
asynchronous, so the fixed effect's ``solve`` child holds 1.5 ms of device
time and its ``score`` child, which fences, the solve's 100 ms. And the
profile's host and device clocks agree to about a millisecond (scoring
launches appear to start inside the ``h2d`` span that precedes their
dispatch), so ``busy_ms`` and ``launches`` mean something for spans of tens
of milliseconds and up, and a launch at a span's edge may count next door.
"""

from __future__ import annotations

import bisect
import functools
import re
from typing import List, Sequence, Tuple

from benchmark import reduce

PREFIX = "photon/"
UPDATE = r"(^|/)cd/iter\d+/(%s)$"


@functools.lru_cache(maxsize=1)
def load(path: str):
    """``(devices, spans)`` of one trace file: per device plane its merged
    operation intervals and its launches' start times, and the host planes'
    ``photon/*`` events as ``(path, start_s, end_s)``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if reduce.DEVICE_PLANE.match(plane.name):
            lines = {line.name: [(e.start_ns * 1e-9, e.duration_ns * 1e-9)
                                 for e in line.events] for line in plane.lines}
            modules = lines.get(reduce.MODULE_LINE, [])
            ops = lines.get(reduce.OP_LINE) or modules
            devices.append(dict(
                busy=reduce.merge([(s, s + d) for s, d in ops]),
                launch_starts=sorted(s for s, _ in modules)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name[len(PREFIX):], e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events if e.name.startswith(PREFIX)]
    return devices, sorted(spans, key=lambda e: e[1])


def overlap(a: Sequence[reduce.Interval], b: Sequence[reduce.Interval]) -> float:
    """Seconds two merged, sorted interval lists share."""
    i = j = 0
    shared = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            shared += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return shared


def inside(merged: Sequence[reduce.Interval], t: float) -> bool:
    i = bisect.bisect_right(merged, (t, float("inf"))) - 1
    return i >= 0 and t < merged[i][1]


def pattern(params: dict, facts: dict):
    kind = params.get("coordinate_type")
    if kind is None:
        return re.compile(params["span"])
    ids = [cid for cid, c in (facts.get("counts") or {}).items()
           if c["type"] == kind]
    if not ids:
        return None
    return re.compile(UPDATE % "|".join(re.escape(i) for i in ids))


def measure(devices: list, spans: List[Tuple[str, float, float]],
            window: reduce.Interval, value: str):
    """``(number, occurrences)`` of the selected spans inside the window."""
    cut = reduce.clip([(a, b) for _, a, b in spans], window)
    if not cut or (not devices and value in ("busy_ms", "idle_ms", "launches")):
        return None, 0   # no such span, or no device plane to hold it against
    covered = reduce.merge(cut)
    n_dev = len(devices)
    if value == "wall_ms":
        number = reduce.total(cut) * 1e3
    elif value == "share":
        number = 100.0 * reduce.total(cut) / (window[1] - window[0])
    elif value == "launches":
        number = sum(inside(covered, s) for dev in devices
                     for s in dev["launch_starts"]) / n_dev
    elif value in ("busy_ms", "idle_ms"):
        busy = sum(overlap(dev["busy"], covered) for dev in devices) / n_dev
        number = (busy if value == "busy_ms"
                  else reduce.total(covered) - busy) * 1e3
    else:
        raise KeyError(f"trace_spans: no value {value!r}")
    return number, len(cut)


def read(params: dict, facts: dict):
    trace = facts.get("trace")
    if trace is None or not facts.get("trace_path") or not trace.window_s:
        return None
    rx = pattern(params, facts)
    if rx is None:
        return None
    devices, spans = load(facts["trace_path"])
    number, occurrences = measure(
        devices, [s for s in spans if rx.search(s[0])], trace.window,
        params["value"])
    if number is None:
        return None
    per = params.get("per")
    if per == "span":
        return number / occurrences
    return number / facts[per] if per else number
