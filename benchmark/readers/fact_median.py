"""Reader ``fact_median``: the median of a list the driver kept of the window
(``op_seconds``: each operation's wall on the host clock), times ``scale``.
A steadier statistic beside an end-to-end rate; nothing for an empty list."""

import statistics


def read(facts: dict, params: dict):
    values = facts.get(params["list"])
    if not values:
        return None
    return statistics.median(values) * float(params.get("scale", 1.0))
