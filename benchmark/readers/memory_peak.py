"""Reader ``memory_peak``: ``memory_stats()["peak_bytes_in_use"]`` of the
fullest chip after the window, in units of ``bytes_per_unit`` (default 1e6).
Nothing where the backend does not report it."""


def read(facts: dict, params: dict):
    peak = facts.get("memory_peak_bytes")
    if not peak:
        return None
    return peak / float(params.get("bytes_per_unit", 1e6))
