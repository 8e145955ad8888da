"""A percentile (nearest rank) of a list the harness itself recorded.
params: ``fact``, ``q``."""

import math


def read(params: dict, facts: dict):
    values = sorted(facts.get(params["fact"]) or [])
    if not values:
        return None
    q = float(params["q"])
    return float(values[min(len(values) - 1, max(0, math.ceil(q * len(values)) - 1))])
