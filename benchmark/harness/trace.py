"""From a profiler trace to numbers: device-busy time, idle share, the device
operations that took most time, and idle time by what the host was doing.

Two steps, so that the arithmetic can be checked on a small recorded trace
(``tests/data/small_trace.json``, worked out by hand in ``tests/test_trace.py``):

``read_xplane(path)``  an ``.xplane.pb`` -> ``{"device": {plane: [[name,
    start_ns, dur_ns], ...]}, "host": {line: [[name, start_ns, dur_ns], ...]}}``
    with the device planes' operation line and every host line.
``reduce_events(events, span)``  that event list -> the numbers.

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the slice (the first host event named ``span``), averaged
over the device planes. An idle gap is a maximal interval of the slice with no
operation on the first device; it is attributed to the innermost host event
that covers its midpoint (the shortest one, over all host threads), and the
gaps' seconds are summed by that name.
"""

from __future__ import annotations

import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SMALL_GAP_NS = 20_000
TOP = 10


def read_xplane(path) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    out = {"device": {}, "host": {}}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["device"][plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events]
                if evs:
                    out["host"][f"{line.name}#{k}"] = evs
    return out


_OP = re.compile(r"^(%[\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])")
_KIND = re.compile(r"kind=(\w+)")


def short_op_name(name: str) -> str:
    """An HLO line as the trace names it, cut to the instruction, the type
    of its (first) result and the fusion kind: "%fusion u8[8912896] kCustom"."""
    m = _OP.match(name)
    if not m:
        return name[:80]
    kind = _KIND.search(name)
    return f"{m.group(1)} {m.group(2)}" + (f" {kind.group(1)}" if kind else "")


def union_intervals(starts: np.ndarray, ends: np.ndarray):
    """Sorted, merged copies of the intervals."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), dtype=bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.nonzero(new)[0]
    last = np.append(first[1:] - 1, len(s) - 1)
    return s[first], reach[last]


def _clip(events, w0: float, w1: float):
    a = np.array([[e[1], e[1] + e[2]] for e in events], dtype=np.float64)
    if not len(a):
        return np.empty(0), np.empty(0), []
    s, e = np.clip(a[:, 0], w0, w1), np.clip(a[:, 1], w0, w1)
    keep = e > s
    return s[keep], e[keep], [short_op_name(ev[0])
                              for ev, k in zip(events, keep) if k]


def _slice_window(events: dict, span: str):
    for evs in events["host"].values():
        for name, start, dur in evs:
            if name == span:
                return start, start + dur
    every = [e for evs in events["device"].values() for e in evs]
    if not every:
        return 0.0, 0.0
    return (min(e[1] for e in every), max(e[1] + e[2] for e in every))


class _HostLine:
    """Events of one host thread with each event's parent, so that the
    innermost event covering an instant is a short walk."""

    def __init__(self, evs):
        evs = sorted(evs, key=lambda e: (e[1], -e[2]))
        self.names = [e[0] for e in evs]
        self.starts = np.array([e[1] for e in evs])
        self.ends = np.array([e[1] + e[2] for e in evs])
        self.parent = np.full(len(evs), -1, dtype=np.int64)
        stack: list[int] = []
        for i in range(len(evs)):
            while stack and self.ends[stack[-1]] <= self.starts[i]:
                stack.pop()
            if stack:
                self.parent[i] = stack[-1]
            stack.append(i)

    def innermost(self, t: float):
        i = int(np.searchsorted(self.starts, t, side="right")) - 1
        while i >= 0 and self.ends[i] <= t:
            i = int(self.parent[i])
        if i < 0:
            return None
        return self.names[i], float(self.ends[i] - self.starts[i])


def reduce_events(events: dict, span: str) -> dict:
    w0, w1 = _slice_window(events, span)
    window = w1 - w0
    out = {"window_s": window / 1e9, "busy_s": 0.0, "device_ops": [],
           "idle_gaps": [], "n_device_events": 0,
           "planes": sorted(events["device"])}
    if window <= 0 or not events["device"]:
        return out
    busy, per_name = [], {}
    first_union = None
    for plane in sorted(events["device"]):
        s, e, names = _clip(events["device"][plane], w0, w1)
        out["n_device_events"] += len(s)
        for n, d in zip(names, (e - s).tolist()):
            per_name[n] = per_name.get(n, 0.0) + d
        us, ue = union_intervals(s, e)
        busy.append(float((ue - us).sum()))
        if first_union is None:
            first_union = (us, ue)
    n_planes = len(busy)
    out["busy_s"] = sum(busy) / n_planes / 1e9
    out["device_ops"] = [[n, d / n_planes / 1e9] for n, d in sorted(
        per_name.items(), key=lambda kv: -kv[1])[:TOP]]
    # idle gaps of the first device, by what the host was doing
    us, ue = first_union
    g0 = np.concatenate([[w0], ue])
    g1 = np.concatenate([us, [w1]])
    keep = g1 > g0
    g0, g1 = g0[keep], g1[keep]
    lines = [_HostLine(evs) for evs in events["host"].values()]
    by_name: dict[str, float] = {}
    for a, b in zip(g0.tolist(), g1.tolist()):
        if b - a < SMALL_GAP_NS:
            name = f"(gaps under {SMALL_GAP_NS // 1000} us)"
        else:
            mid, best = (a + b) / 2, None
            for ln in lines:
                hit = ln.innermost(mid)
                if hit and hit[0] != span and (best is None or hit[1] < best[1]):
                    best = hit
            name = best[0] if best else f"{span} (no inner host event)"
        by_name[name] = by_name.get(name, 0.0) + (b - a)
    out["idle_gaps"] = [[n, d / 1e9] for n, d in sorted(
        by_name.items(), key=lambda kv: -kv[1])[:TOP]]
    out["n_idle_gaps"] = int(len(g0))
    return out
