"""The trace reduction, on a hand-made event list and on a small trace
recorded on a TPU v5 lite by ``record_trace.py``."""
import json
import os

import pytest

import tracefile
from fedrun import ROUND_SPAN, WINDOW_SPAN

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union():
    assert tracefile.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                                (5, 8)]


def test_reduce_by_hand():
    ms = 1e6  # events in ns
    trace = {
        "host": [(WINDOW_SPAN, 0, 100 * ms), (ROUND_SPAN, 0, 60 * ms),
                 (ROUND_SPAN, 60 * ms, 100 * ms)],
        "devices": {"/device:TPU:0": {
            "modules": [("jit_group(12)", 5 * ms, 45 * ms),
                        ("jit_add(3)", 50 * ms, 55 * ms),
                        ("jit_group(12)", 62 * ms, 95 * ms),
                        ("jit_group(12)", 95 * ms, 130 * ms)],
            "ops": [("fusion.1", 5 * ms, 30 * ms), ("dot.2", 30 * ms, 45 * ms),
                    ("scatter", 50 * ms, 55 * ms),
                    ("fusion.1", 62 * ms, 130 * ms)]}}}
    r = tracefile.reduce(trace, WINDOW_SPAN, gap_spans=(WINDOW_SPAN,
                                                        ROUND_SPAN))
    assert r["window_s"] == pytest.approx(0.100)
    # ops clipped to the window: [5,45] + [50,55] + [62,100] = 83 ms
    assert r["busy_s"] == pytest.approx(0.083)
    assert r["programs"] == pytest.approx({"jit_group": 0.078,
                                           "jit_add": 0.005})
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.063)]
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.round after window start (x1)"] == pytest.approx(0.005)
    assert gaps["bench.round after jit_group (x1)"] == pytest.approx(0.005)
    assert gaps["bench.round after jit_add (x1)"] == pytest.approx(0.007)
    assert sum(gaps.values()) == pytest.approx(0.100 - 0.083)


def test_self_times_of_nested_ops():
    t = tracefile.self_times([
        ("%while.1 = (s32[]) while(...)", 0, 100),
        ("%fusion.2 = bf16[4,8]{1,0} fusion(...)", 10, 40),
        ("%fusion.2 = bf16[4,8]{1,0} fusion(...)", 50, 70),
        ("%copy.3 = f32[2]{0} copy(...)", 100, 110)])
    assert t == {"while.1 (s32[])": 50, "fusion.2 bf16[4,8]": 50,
                 "copy.3 f32[2]": 10}


def test_reduce_without_window_is_empty():
    assert tracefile.reduce({"host": [], "devices": {}}, WINDOW_SPAN) == {}


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(DATA, "small.xplane.pb")
    if not os.path.exists(path):
        pytest.fail("the recorded trace is missing; make it on a TPU with "
                    "record_trace.py")
    with open(os.path.join(DATA, "small.json")) as f:
        meta = json.load(f)
    return tracefile.load(DATA, (WINDOW_SPAN, ROUND_SPAN)), meta


def test_recorded_trace(recorded):
    trace, meta = recorded
    assert list(trace["devices"]) == ["/device:TPU:0"]
    names = [n for n, *_ in trace["host"]]
    assert names.count(WINDOW_SPAN) == 1 and names.count(ROUND_SPAN) == 2
    r = tracefile.reduce(trace, WINDOW_SPAN,
                         gap_spans=(WINDOW_SPAN, ROUND_SPAN))
    assert set(r["programs"]) >= {"jit_group", "jit_server"}
    assert 0 < r["busy_s"] < r["window_s"]
    # the device ran every op inside a program
    assert sum(v for _, v in r["device_ops"]) <= sum(
        r["programs"].values()) * 1.001
    # each round sleeps on the host between the two programs.  The device
    # clock of this trace leads the host's by about a millisecond, so the
    # first round's jit_group falls just before the window opens; the
    # second round's sleep is an idle gap after jit_group
    gaps = dict(r["idle_gaps"])
    after_group = [v for k, v in gaps.items()
                   if k.startswith(f"{ROUND_SPAN} after jit_group")]
    assert after_group and after_group[0] >= 0.9 * meta["sleeps_s"][1]
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)
