"""Milliseconds a unit of work by which some spans of the program's in-memory
ring (``photon_tpu.obs.trace.get_spans()``) overlap the root spans of that
work: how much of a host pause fell inside the fits, which no profiled slice
of three fits is long enough to catch.

The units are counted by the root spans that open one (a fit opens
``game-estimator/prepare-datasets``); the first ``skip`` units are left out,
so the fit of set-up, with its compiles, does not count. Only time inside a
unit's root spans counts, so the profiler's start and stop between fits and
the reference after the window stay out.

params: ``span`` (regex searched in the path of the spans measured),
``within`` (regex of the root spans of the work), ``unit`` (regex of the root
span that opens one unit), ``skip`` (units left out first), ``requires`` (a
registry gauge that says the program records such spans at all).

Nothing where the program lacks the gauge (an older program, which records no
such span), where the ring dropped any span, or where no unit is left; 0 where
the program records them and none fell inside the work.
"""

import re

from benchmark import reduce
from benchmark.readers import registry_value, trace_spans


def read(params: dict, facts: dict):
    from photon_tpu.obs import trace

    snapshot = facts.get("registry_after")
    if snapshot is None:
        from benchmark import program

        snapshot = program.registry_snapshot()
    if registry_value._total(snapshot, params["requires"], {}) is None:
        return None
    if trace.tracer().dropped_spans:
        return None
    spans = trace.get_spans()
    within, unit = re.compile(params["within"]), re.compile(params["unit"])
    roots = sorted((s.start_s, s.start_s + s.duration_s, s.name) for s in spans
                   if s.parent is None and within.search(s.name))
    starts = [a for a, _, name in roots if unit.search(name)]
    skip = int(params.get("skip", 0))
    if len(starts) <= skip:
        return None
    work = reduce.merge([(a, b) for a, b, _ in roots if a >= starts[skip]])
    rx = re.compile(params["span"])
    hits = reduce.merge([(s.start_s, s.start_s + s.duration_s) for s in spans
                         if rx.search(s.name)])
    return trace_spans.overlap(work, hits) * 1e3 / (len(starts) - skip)
