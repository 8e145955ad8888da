"""Device time or launch count of the programs whose module name matches,
from the reduced trace's "XLA Modules" line.

params: ``modules`` (regexes, any may match), ``with_op`` / ``without_op``
(a regex on the names of the device operations that ran inside the launch:
keep only launches that hold such an operation, or none), ``need_op`` (the
metric exists only while SOME launch of the trace holds such an operation),
``value`` (``ms`` or ``count``) and ``per`` (a fact to divide by, or
``launch``).
"""

import re


def select(params: dict, facts: dict):
    trace = facts.get("trace")
    if trace is None:
        return None
    pats = [re.compile(p) for p in params["modules"]]
    launches = [l for l in trace.launches if any(p.search(l.name) for p in pats)]
    need = params.get("need_op")
    if need and not any(re.search(need, op) for l in trace.launches for op in l.ops):
        return None
    if params.get("with_op"):
        rx = re.compile(params["with_op"])
        launches = [l for l in launches if any(rx.search(op) for op in l.ops)]
    if params.get("without_op"):
        rx = re.compile(params["without_op"])
        launches = [l for l in launches if not any(rx.search(op) for op in l.ops)]
    return launches


def read(params: dict, facts: dict):
    launches = select(params, facts)
    if not launches:
        return None
    if params.get("value", "ms") == "count":
        total = float(len(launches))
    else:
        total = sum(l.seconds for l in launches) * 1e3
    per = params.get("per")
    if per == "launch":
        return total / len(launches)
    if per:
        return total / facts[per]
    return total
