"""Mean of one histogram of the program's metrics registry over the window:
(sum after − sum before) ÷ (count after − count before), times ``scale``.
params: ``metric``, ``scale``."""


def _stats(snapshot, name):
    total, count = 0.0, 0
    for rec in snapshot or []:
        if rec.get("metric") == name and rec.get("stats"):
            total += rec["stats"].get("sum") or 0.0
            count += rec["stats"].get("count") or 0
    return total, count


def read(params: dict, facts: dict):
    s0, c0 = _stats(facts.get("registry_before"), params["metric"])
    s1, c1 = _stats(facts.get("registry_after"), params["metric"])
    if c1 - c0 <= 0:
        return None
    return (s1 - s0) / (c1 - c0) * float(params.get("scale", 1.0))
