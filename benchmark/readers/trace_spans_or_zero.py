"""``trace_spans`` for spans that come and go with what the run did, such as
a collection's: 0 where the program records such spans and none fell in the
traced slice, which ``trace_spans`` leaves out as it would for a program
without them.

params: those of ``trace_spans``, and ``requires`` (a registry gauge that
says the program records such spans at all).

Nothing where the program lacks the gauge (an older program), where the run
was not traced, or where the trace holds no device plane to hold the spans
against (``busy_ms``, ``idle_ms`` and ``launches`` need one).
"""

from benchmark.readers import registry_value, trace_spans


def read(params: dict, facts: dict):
    snapshot = facts.get("registry_after")
    if snapshot is None:
        from benchmark import program

        snapshot = program.registry_snapshot()
    if registry_value._total(snapshot, params["requires"], {}) is None:
        return None
    trace = facts.get("trace")
    if trace is None or not facts.get("trace_path") or not trace.window_s:
        return None
    number = trace_spans.read(params, facts)
    if number is not None:
        return number
    devices, _ = trace_spans.load(facts["trace_path"])
    if not devices and params["value"] in ("busy_ms", "idle_ms", "launches"):
        return None
    return 0.0
