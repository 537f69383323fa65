"""Reader ``counter_ratio``: the window's movement of one or more of the
program's counters, over a count of the window that the benchmark made itself.

params: ``counters`` (names in the program's registry, summed), ``per`` (a key
of the driver's facts: ``mhashes``, ``ops``, ...), ``scale`` (default 1).
Returns nothing where a counter does not exist or the denominator is 0.
"""


def read(facts: dict, params: dict):
    before, after = facts["counters_before"], facts["counters_after"]
    per = facts.get(params["per"])
    if not per or any(c not in after for c in params["counters"]):
        return None
    moved = sum(after[c] - before.get(c, 0.0) for c in params["counters"])
    return moved / per * float(params.get("scale", 1.0))
