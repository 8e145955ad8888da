"""A share of the chip's peak, in %: the least time the chip could take for
the work the algorithm needs (``benchmark/work.py``), over the time it took.

params: ``work`` (a function of ``work.py`` that takes the run's facts),
``bound`` (``roofline``: the larger of flops ÷ peak flops and bytes ÷ peak
bandwidth; ``flops``: flops ÷ peak flops alone, for an ``mfu``), ``scale``
(a fact to multiply the work by, e.g. the traced fits), and ``time``: either
``{"metric": <another metric's file>}`` whose value is in ms, or
``{"fact": <seconds>}``.
"""

from benchmark import layers, work


def read(params: dict, facts: dict):
    need = getattr(work, params["work"])(facts)
    peak = work.peaks(facts["device_kind"])
    if params.get("bound", "roofline") == "flops":
        least = need["flops"] / peak["flops_per_s"]
    else:
        least, _ = work.least_seconds(need["flops"], need["bytes"], peak)
    if params.get("scale"):
        least *= facts[params["scale"]]
    time = params["time"]
    if "metric" in time:
        ms = layers.read_metric(time["metric"], facts)
        if ms is None:
            return None
        seconds = ms / 1e3
    else:
        seconds = facts.get(time["fact"])
    if not seconds:
        return None
    return 100.0 * least / seconds
