"""Reader ``trace_roofline``: the least time the chip could take for the
Keccak work of the traced slice (``harness/work.py`` over the slice's tries,
``harness/peaks.py`` for the chip), over ALL device-busy time of the slice:
no program of the system under test is named yet, so gathers, scatters and
copies count in the denominator. Adds which bound it was to the run's notes.
Nothing where no device operation was traced."""

from benchmark.harness.peaks import peaks_for
from benchmark.harness.work import least_seconds


def read(facts: dict, params: dict):
    red, work = facts.get("trace"), facts.get("slice_work")
    if not red or not work or red["busy_s"] <= 0:
        return None
    least, bound = least_seconds(work, peaks_for(facts["device"]["kind"]))
    facts.setdefault("notes", []).append(
        f"roofline of the slice: {work['n_hashes']} hashes, "
        f"{work['n_blocks']} permutations, least {least:.6f}s bound by "
        f"{bound}, device busy {red['busy_s']:.6f}s")
    return 100.0 * least / red["busy_s"]
