"""The reduction from a trace to numbers, on a small recorded trace whose
answers were worked out by hand (``data/small_trace.json``: seven device
operations and six host events of one operation traced on the chip).

By hand, in ns from the start of the harness span (4,722,487,960 long):
  device ops   480 + 741 + 1,428 + 1,166 + 821 = 4,636 at 518,694,042..518,698,681
               (with holes of 1 ns and 2 ns), then 178,000 + 308,200 = 486,200
               at 1,949,265,700..1,949,751,900; busy = 490,836
  idle gaps    0..518,694,042 (518,694,042; midpoint 259,347,021 lies in no
               host event but the span), 518,698,681..1,949,265,700
               (1,430,567,019; midpoint 1,233,982,190 lies in the digest fetch
               np.asarray, 910,750,000..1,997,326,000), 1,949,751,900..end
               (2,772,736,060; no inner event), and 3 ns of holes
  check        518,694,042 + 1,430,567,019 + 2,772,736,060 + 3 + 490,836
               = 4,722,487,960
"""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import trace
from benchmark.readers import trace_idle_share, trace_roofline

DATA = json.loads((Path(__file__).parent / "data" / "small_trace.json").read_text())


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_events(DATA["events"], DATA["span"])


def test_busy_time_window_and_idle_share(reduced):
    assert reduced["window_s"] == pytest.approx(4.72248796, abs=1e-12)
    assert reduced["busy_s"] == pytest.approx(490_836e-9, abs=1e-12)
    assert reduced["n_device_events"] == 7
    share = trace_idle_share.read({"trace": reduced}, {})
    assert share == pytest.approx(100 * (1 - 490_836 / 4_722_487_960), abs=1e-9)


def test_idle_gaps_are_attributed_by_hand(reduced):
    gaps = dict(reduced["idle_gaps"])
    span = "bench.rebuild.commit_chunk (no inner host event)"
    assert gaps[span] == pytest.approx((518_694_042 + 2_772_736_060) * 1e-9, abs=1e-12)
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(1_430_567_019e-9, abs=1e-12)
    assert gaps["(gaps under 20 us)"] == pytest.approx(3e-9, abs=1e-13)
    assert len(gaps) == 3 and reduced["n_idle_gaps"] == 5
    assert sum(gaps.values()) + reduced["busy_s"] == pytest.approx(
        reduced["window_s"], abs=1e-9)


def test_top_device_ops_by_short_name(reduced):
    ops = reduced["device_ops"]
    assert ops[0] == ["%copy.44 u8[1048576,32]", pytest.approx(308_200e-9)]
    assert ops[1] == ["%fusion.6 u8[1048576,32] kCustom", pytest.approx(178_000e-9)]
    assert ops[2][0] == "%reshape.241 u32[2048,2]"
    assert len(ops) == 7 and len(ops) <= trace.TOP


def test_union_merges_overlaps_and_the_slice_clips():
    s, e = trace.union_intervals(np.array([5.0, 0.0, 1.0, 20.0]),
                                 np.array([9.0, 3.0, 6.0, 21.0]))
    assert s.tolist() == [0.0, 20.0] and e.tolist() == [9.0, 21.0]
    events = {"device": {"/device:TPU:0": [["%a = u8[1] x", 0, 10], ["%b = u8[1] x", 95, 10]],
                         "/device:TPU:1": [["%a = u8[1] x", 40, 20]]},
              "host": {"t": [["slice", 5, 95]]}}
    red = trace.reduce_events(events, "slice")
    # chip 0 is busy 5..10 and 95..100 of the slice 5..100, chip 1 40..60:
    # (10 + 20) / 2 chips
    assert red["window_s"] == pytest.approx(95e-9)
    assert red["busy_s"] == pytest.approx(15e-9)


def test_readers_return_nothing_where_nothing_ran_on_the_device():
    empty = trace.reduce_events({"device": {}, "host": {"t": [["s", 0, 10]]}}, "s")
    assert empty["busy_s"] == 0.0
    assert trace_idle_share.read({"trace": empty}, {}) is None
    assert trace_roofline.read({"trace": empty, "slice_work": {"bytes": 1, "ops": 1},
                                "device": {"kind": "TPU v5 lite"}}, {}) is None
    assert trace_idle_share.read({"trace": None}, {}) is None
