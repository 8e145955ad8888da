"""A counter or gauge of the program's metrics registry, summed over the
label sets that match. The registry is read where the traffic module left a
snapshot (``facts["registry_after"]``) and from the live process otherwise,
so a cell of a kind that takes no snapshot reads it too.

params: ``metric``; ``labels`` (a subset every summed instrument has to
carry); ``over`` (a second metric, same labels: the value is a share of it);
``form``: ``sum`` (default), or ``unused_share`` = 100 · (1 − metric ÷ over).
A program without the instrument (an older one) reads nothing.
"""


def _total(snapshot, name, labels):
    total, hit = 0.0, False
    for rec in snapshot:
        if rec.get("metric") != name or rec.get("value") is None:
            continue
        have = rec.get("labels") or {}
        if all(str(have.get(k)) == str(v) for k, v in labels.items()):
            total += float(rec["value"])
            hit = True
    return total if hit else None


def read(params: dict, facts: dict):
    snapshot = facts.get("registry_after")
    if snapshot is None:
        from benchmark import program

        snapshot = program.registry_snapshot()
    labels = params.get("labels", {})
    value = _total(snapshot, params["metric"], labels)
    if value is None:
        return None
    if params.get("form", "sum") == "sum":
        return value
    whole = _total(snapshot, params["over"], labels)
    if not whole:
        return None
    return 100.0 * (1.0 - value / whole)
