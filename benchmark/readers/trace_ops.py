"""Summed device time (ms) of the operations whose name matches, from the
reduced trace's "XLA Ops" line. params: ``ops`` (regex), ``per`` (a fact)."""

import re


def read(params: dict, facts: dict):
    trace = facts.get("trace")
    if trace is None:
        return None
    rx = re.compile(params["ops"])
    hits = [(c, s) for name, (c, s) in trace.ops.items() if rx.search(name)]
    if not hits:
        return None
    total = sum(s for _, s in hits) * 1e3
    return total / facts[params["per"]] if params.get("per") else total
