"""The device's idle share of the traced window, in %: 1 − the union of the
intervals in which an operation ran on the device ÷ the window."""


def read(params: dict, facts: dict):
    trace = facts.get("trace")
    if trace is None or not trace.window_s:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
