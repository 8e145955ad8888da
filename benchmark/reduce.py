"""From a profiler trace (``.xplane.pb``) to numbers: the traced window, the
seconds in which an operation ran on the device, device time per launched
program and per operation, and the idle gaps named by what the host was
doing. Reads the file with nothing but JAX (``ProfileData``).

What the trace has today, and what is read from it:

- a plane per chip (``/device:TPU:<n>``) with a line ``XLA Modules`` (one
  event per launched program, named ``jit_<function>(<id>)``) and a line
  ``XLA Ops`` (one event per operation, Pallas kernels among them);
- a host plane whose thread lines carry the benchmark's own
  ``TraceAnnotation`` events (``bench/fit``, ``bench/submit``,
  ``bench/reference``) on the same clock.

The window is the stretch the ``bench/*`` annotations cover (the reference's
left out), cut to the trace; without annotations, the device events' extent.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
ANNOTATION = re.compile(r"^bench/")
NOT_WINDOW = ("bench/reference",)

Interval = Tuple[float, float]  # start, end in seconds


@dataclasses.dataclass
class Launch:
    name: str
    start_s: float
    seconds: float
    ops: Tuple[str, ...]


@dataclasses.dataclass
class Trace:
    window: Interval
    busy_s: float
    launches: List[Launch]
    modules: Dict[str, Tuple[int, float]]
    ops: Dict[str, Tuple[int, float]]
    gaps: List[Tuple[str, float]]
    annotations: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def top_ops(self, k: int) -> List[List]:
        """The device operations that took most time, ``[name, seconds]``,
        under their short names (the trace's are whole HLO instructions)."""
        short: Dict[str, float] = {}
        for name, (_, sec) in self.ops.items():
            key = short_op(name)
            short[key] = short.get(key, 0.0) + sec
        return [[n, s] for n, s in sorted(short.items(), key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k: int) -> List[List]:
        """The longest idle gaps, ``[what the host was doing, seconds]``."""
        return [[name, sec] for name, sec in
                sorted(self.gaps, key=lambda g: -g[1])[:k]]


def module_name(event_name: str) -> str:
    """``jit_traced(123456)`` → ``jit_traced``: the id differs run to run."""
    return re.sub(r"\(\d+\)$", "", event_name)


def short_op(name: str) -> str:
    """``%body.9 = (…) custom-call(…), custom_call_target="tpu_custom_call", …``
    → ``body.9 custom-call tpu_custom_call``; ``%fusion.3 = … fusion(…)`` →
    ``fusion.3 fusion``."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:80]
    kind = re.search(r"\s([a-z][a-z\-]*)\(", " " + rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    parts = [head.lstrip("%")]
    if kind:
        parts.append(kind.group(1))
    if target:
        parts.append(target.group(1))
    return " ".join(parts)[:80]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events]


def read_planes(path: str):
    """``(devices, annotations)``: per device plane its module and op events,
    and the host's ``bench/*`` annotation events, all ``(name, start_s, dur_s)``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, annotations = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: _events(line) for line in plane.lines}
            devices.append(dict(name=plane.name,
                                modules=lines.get(MODULE_LINE, []),
                                ops=lines.get(OP_LINE, [])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                annotations += [e for e in _events(line) if ANNOTATION.match(e[0])]
    return devices, sorted(annotations, key=lambda e: e[1])


KEEP_GAPS = 10


def name_gaps(raw, modules, annotations) -> List[Tuple[str, float]]:
    """Name each idle gap ``(length, start, end)`` by the ``bench/*``
    annotation that encloses its middle and by the programs around it: inside
    one launch, or between the launch that ended before it and the next."""
    mods = sorted((s, s + d, module_name(n)) for n, s, d in modules)
    starts = [m[0] for m in mods]
    out = []
    for length, a, b in raw:
        mid = 0.5 * (a + b)
        host = next((n for n, s, d in annotations if s <= mid < s + d),
                    "no annotation")
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mods[i][1] >= b:
            where = f"inside {mods[i][2]}"
        else:
            prev = mods[i][2] if i >= 0 else "start"
            nxt = mods[i + 1][2] if i + 1 < len(mods) else "end"
            where = f"after {prev} before {nxt}"
        out.append((f"{host}: {where}", length))
    return out


def reduce_events(devices: list, annotations: list) -> Trace:
    spans = [(s, s + d) for name, s, d in annotations if name not in NOT_WINDOW]
    dev_spans = [(s, s + d) for dev in devices
                 for _, s, d in (dev["ops"] or dev["modules"])]
    extent = (min(a for a, _ in dev_spans), max(b for _, b in dev_spans)) \
        if dev_spans else (0.0, 0.0)
    window = (min(a for a, _ in spans), max(b for _, b in spans)) if spans else extent

    launches: List[Launch] = []
    modules: Dict[str, List[float]] = {}
    ops: Dict[str, List[float]] = {}
    busy = []
    gaps: List[Tuple[str, float]] = []
    for dev in devices:
        op_events = sorted(dev["ops"], key=lambda e: e[1])
        intervals = merge(clip([(s, s + d) for _, s, d in
                                (op_events or dev["modules"])], window))
        busy.append(total(intervals))
        for name, s, d in op_events:
            if s + d > window[0] and s < window[1]:
                acc = ops.setdefault(name, [0, 0.0])
                acc[0] += 1
                acc[1] += d
        starts = [e[1] for e in op_events]
        for name, s, d in sorted(dev["modules"], key=lambda e: e[1]):
            if s + d <= window[0] or s >= window[1]:
                continue
            lo, hi = bisect.bisect_left(starts, s), bisect.bisect_right(starts, s + d)
            inside = tuple(sorted({op_events[i][0] for i in range(lo, hi)}))
            short = module_name(name)
            launches.append(Launch(short, s, d, inside))
            acc = modules.setdefault(short, [0, 0.0])
            acc[0] += 1
            acc[1] += d
        edges = [window[0]] + [x for iv in intervals for x in iv] + [window[1]]
        raw = [(b - a, a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        gaps += name_gaps(sorted(raw, reverse=True)[:KEEP_GAPS], dev["modules"],
                          annotations)
    n_dev = max(len(devices), 1)
    return Trace(
        window=window, busy_s=sum(busy) / n_dev, launches=launches,
        modules={k: (int(c), s) for k, (c, s) in modules.items()},
        ops={k: (int(c), s) for k, (c, s) in ops.items()},
        gaps=gaps, annotations=annotations)


def reduce(path: str) -> Trace:
    devices, annotations = read_planes(path)
    return reduce_events(devices, annotations)
