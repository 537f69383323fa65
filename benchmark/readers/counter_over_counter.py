"""Reader ``counter_over_counter``: the window's movement of some of the
program's counters over its movement of others, where both halves of a ratio
are counted by the program at one seam (rows needed over rows dispatched).

params: ``num`` and ``den`` (names in the program's registry, each list
summed), ``scale`` (default 1).
Returns nothing where a counter does not exist or ``den`` did not move.
"""


def read(facts: dict, params: dict):
    before, after = facts["counters_before"], facts["counters_after"]
    if any(c not in after for c in params["num"] + params["den"]):
        return None

    def moved(names):
        return sum(after[c] - before.get(c, 0.0) for c in names)

    den = moved(params["den"])
    if not den:
        return None
    return moved(params["num"]) / den * float(params.get("scale", 1.0))
