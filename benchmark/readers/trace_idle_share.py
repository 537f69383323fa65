"""Reader ``trace_idle_share``: 100 x (1 - device-busy time / length) of the
traced slice, from the profiler's trace (``harness/trace.py``). Nothing where
no device operation was traced."""


def read(facts: dict, params: dict):
    red = facts.get("trace")
    if not red or red["window_s"] <= 0 or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
