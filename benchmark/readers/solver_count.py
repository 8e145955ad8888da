"""A count the program's solvers reported for ONE fit (``program.
tracker_counts``), summed over the coordinates of one type.

params: ``type`` (``fixed`` or ``random``), ``key`` (``evals``,
``newton_iterations``, ``max_iterations``, ...), ``per`` (a key of the same
coordinate to divide by, e.g. ``entities``).
"""


def read(params: dict, facts: dict):
    counts = facts.get("counts")
    if not counts:
        return None
    total, hit = 0.0, False
    for c in counts.values():
        if c["type"] != params["type"] or params["key"] not in c:
            continue
        value = float(c[params["key"]])
        if params.get("per"):
            value /= float(c[params["per"]])
        total += value
        hit = True
    return total if hit else None
