"""Summed seconds of the spans in the program's in-memory ring
(``photon_tpu.obs.trace.get_spans()``) whose path matches: for what lies
outside the profiled slice, the set-up. The ring keeps the newest 100,000
spans and sheds the oldest, which is the set-up this reader is for, so once
it has dropped any span the reader returns nothing. params: ``span`` (a regex
searched in the path)."""

import re


def read(params: dict, facts: dict):
    from photon_tpu.obs import trace

    if trace.tracer().dropped_spans:
        return None
    rx = re.compile(params["span"])
    seconds = [s.duration_s for s in trace.get_spans() if rx.search(s.name)]
    return sum(seconds) if seconds else None
