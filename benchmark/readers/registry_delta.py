"""What a counter of the program's metrics registry gained over the window:
``facts["registry_after"]`` less ``facts["registry_before"]``, summed over
every label set, times ``scale``, over a fact (``per``).

params: ``metric``; ``requires`` (a registry gauge that says the program
keeps the counter at all: where the window added nothing the counter may not
exist yet, and that reads 0); ``scale`` (default 1); ``per`` (a fact to
divide by, such as ``window_s``).

Nothing where no snapshots were taken or the program lacks the gauge (an
older one).
"""

from benchmark.readers import registry_value


def read(params: dict, facts: dict):
    before, after = facts.get("registry_before"), facts.get("registry_after")
    if before is None or after is None:
        return None
    if registry_value._total(after, params["requires"], {}) is None:
        return None
    gained = ((registry_value._total(after, params["metric"], {}) or 0.0)
              - (registry_value._total(before, params["metric"], {}) or 0.0))
    value = gained * float(params.get("scale", 1.0))
    per = params.get("per")
    return value / facts[per] if per else value
