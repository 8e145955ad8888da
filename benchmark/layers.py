"""Per-layer metrics of a traced run: each is a small reader of its own,
found by the name in ``benchmark/metrics/<metric>.json``. A reader that finds
nothing to read returns nothing, and the metric is left out of the line."""

from __future__ import annotations

import importlib
import json
import math
import os
from typing import Dict, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def metric_file(name: str) -> dict:
    with open(os.path.join(HERE, "metrics", name + ".json")) as f:
        return json.load(f)


def read_metric(name: str, facts: dict):
    spec = metric_file(name)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(spec.get("params", {}), facts)


def read_all(per_layer: list, facts: dict, log) -> Tuple[Dict, Dict]:
    """``(metrics, extra)``: the metrics of the result line, and ``device``
    (``busy_s``, ``window_s``) and ``breakdown`` from the reduced trace."""
    from benchmark import reduce

    extra: Dict = {}
    facts = dict(facts)
    if facts.get("trace_path"):
        trace = reduce.reduce(facts["trace_path"])
        facts["trace"] = trace
        extra["device"] = dict(busy_s=trace.busy_s, window_s=trace.window_s)
        extra["breakdown"] = dict(device_ops=trace.top_ops(10),
                                  idle_gaps=trace.top_gaps(10))
        log(f"trace reduced: window {trace.window_s:.4f}s busy {trace.busy_s:.4f}s "
            f"modules {sorted(trace.modules.items(), key=lambda kv: -kv[1][1])[:8]}")
    metrics = {}
    for m in per_layer:
        try:
            value = read_metric(m["name"], facts)
        except (KeyError, ZeroDivisionError, TypeError) as exc:
            log(f"metric {m['name']}: nothing to read ({exc!r})")
            value = None
        if value is None or not math.isfinite(value):
            continue
        metrics[m["name"]] = dict(value=value, unit=m["unit"])
    return metrics, extra
