"""``work_share`` for a counting function kept in a module of the benchmark
of its own (``benchmark/<module>.py``), which may find nothing to count.

params: those of ``work_share`` and ``module``. Nothing where the function
returns nothing (a program that reports no such count) or the time is
missing.
"""

import importlib

from benchmark import layers, work


def read(params: dict, facts: dict):
    module = importlib.import_module(f"benchmark.{params['module']}")
    need = getattr(module, params["work"])(facts)
    if need is None:
        return None
    least, _ = work.least_seconds(need["flops"], need["bytes"],
                                  work.peaks(facts["device_kind"]))
    ms = layers.read_metric(params["time"]["metric"], facts)
    if not ms:
        return None
    return 100.0 * least / (ms / 1e3)
