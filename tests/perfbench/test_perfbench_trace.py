"""The trace-to-metrics reduction, on a small hand-checked trace and on a
small trace recorded on the chip (data/recorded_trace.json, when present)."""

import json
import os

import pytest

from perfbench_tiny import ROOT  # noqa: F401  (puts the repo root on sys.path)

from perfbench.harness import tracered

M = tracered.CYCLE_MARKER

# Two cycles of 1.0 s, 0.2 s apart.  Per cycle on the device: a scatter, the
# round's while (0.4 s, with two body ops traced inside it), a smaller while
# in the compaction program, and a fusion after it.
SMALL = {
    "device": {
        "/device:TPU:0": [
            ["fusion.1", 0.10, 0.05],
            ["while.33", 0.30, 0.40],
            ["fusion.212", 0.31, 0.10],   # inside the while
            ["cond.42", 0.45, 0.20],      # inside the while
            ["while.7", 0.72, 0.03],
            ["fusion.9", 0.80, 0.05],
            ["fusion.1", 1.30, 0.05],
            ["while.33", 1.50, 0.40],
            ["fusion.212", 1.51, 0.10],
            ["while.7", 1.92, 0.03],
            ["fusion.9", 2.00, 0.05],
            ["fusion.9", 2.30, 0.05],     # after the last cycle's end: outside
        ]
    },
    "host": [
        [M, 0.0, 1.0],
        [M, 1.2, 1.0],
        ["sidecar_sync", 0.0, 0.25],
        ["feed_apply", 0.15, 0.10],
        ["sidecar_round", 0.25, 0.75],
        ["fetch_decode", 0.28, 0.50],
        ["apply_outcome", 0.85, 0.10],
        ["sidecar_sync", 1.2, 0.25],
        ["sidecar_round", 1.45, 0.75],
    ],
}


def test_union_and_covered():
    merged = tracered.union([[0, 1], [0.5, 2], [3, 4], [3.2, 3.5]])
    assert merged == [[0, 2], [3, 4]]
    assert tracered.covered(merged, 1.5, 3.5) == pytest.approx(1.0)


def test_top_level_drops_nested_events():
    top = tracered.top_level(SMALL["device"]["/device:TPU:0"])
    assert [e[0] for e in top].count("fusion.212") == 0
    assert [e[0] for e in top].count("while.33") == 2


def test_kernel_is_the_top_level_while_with_most_time():
    top = tracered.top_level(SMALL["device"]["/device:TPU:0"])
    assert tracered.find_kernel(top) == "while.33"
    assert tracered.find_kernel([["fusion.1", 0, 1]]) == ""
    # XLA's other spellings of the op
    assert tracered.find_kernel([["%while.5 = (s32[]) while(...)", 0, 1]]) != ""
    assert tracered.find_kernel([["meanwhile_fusion", 0, 1]]) == ""


def test_reduction_of_the_small_trace():
    r = tracered.reduce_trace(SMALL)
    assert r["traced_cycles"] == 2 and r["kernel_name"] == "while.33" and r["kernel_calls"] == 2
    assert r["window_s"] == pytest.approx(2.2)
    # busy: cycle 1 0.05+0.40+0.03+0.05, cycle 2 the same; the event at 2.30 is outside
    assert r["busy_s"] == pytest.approx(1.06)
    assert r["device_idle_share_pct"] == pytest.approx(100 * (1 - 1.06 / 2.2))
    assert r["kernel_device_s_total"] == pytest.approx(0.8)
    assert r["kernel_device_s_per_cycle"] == pytest.approx(0.4)
    # after the kernel's end and before the cycle's: while.7 and fusion.9
    assert r["post_round_device_s_per_cycle"] == pytest.approx(0.08)
    ops = dict(r["device_ops"])
    assert ops["while.33"] == pytest.approx(0.8) and ops["fusion.212"] == pytest.approx(0.2)


def test_idle_gaps_go_to_the_innermost_open_span():
    gaps = dict(tracered.reduce_trace(SMALL)["idle_gaps"])
    # cycle 1: idle [0, .10] -> sidecar_sync; [.15, .25] is inside feed_apply
    assert gaps["feed_apply"] == pytest.approx(0.10)
    # fetch_decode is open over [.28,.78]: idle [.28,.30], [.70,.72] and [.75,.78]
    assert gaps["fetch_decode"] == pytest.approx(0.02 + 0.02 + 0.03)
    # [.85,.95] of the idle stretch [.85,1.0] is inside apply_outcome
    assert gaps["apply_outcome"] == pytest.approx(0.10)
    total_idle = 2.2 - 1.06
    between_cycles = 0.2  # no program span is open there
    assert sum(gaps.values()) == pytest.approx(total_idle - between_cycles)


def test_nothing_to_read_gives_nothing():
    assert tracered.reduce_trace({"device": {}, "host": SMALL["host"]}) == {}
    assert tracered.reduce_trace({"device": SMALL["device"], "host": []}) == {}


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "recorded_trace.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded chip trace in the tree")
def test_reduction_of_the_recorded_chip_trace():
    with open(RECORDED, encoding="utf-8") as f:
        doc = json.load(f)
    r = tracered.reduce_trace(doc["trace"])
    want = doc["expect"]
    assert r["kernel_name"] == want["kernel_name"]
    assert r["traced_cycles"] == want["traced_cycles"]
    for key in ("busy_s", "window_s", "kernel_device_s_total", "post_round_device_s_per_cycle"):
        assert r[key] == pytest.approx(want[key], rel=1e-6), key
    assert 0 < r["device_idle_share_pct"] < 100
    assert [n for n, _ in r["idle_gaps"]][:3] == want["top_gaps"]


def _back_to_back():
    """Two events on the device's 1 ns grid, the second starting on the very
    nanosecond the first ends, whose float seconds say otherwise: start + dur
    of the first rounds above the start of the second."""
    for start_ns in range(733_546_492, 733_546_492 + 5000):
        for dur_ns in (216_059_336, 95_000_003, 1_234_567):
            s, d, nxt = start_ns * 1e-9, dur_ns * 1e-9, (start_ns + dur_ns) * 1e-9
            if s + d > nxt:
                return s, d, nxt
    raise AssertionError("no such pair in the range searched")


def test_top_level_decides_nesting_on_whole_nanoseconds():
    s, d, nxt = _back_to_back()
    assert s + d > nxt  # what `start >= end` on float seconds tripped over
    events = [["fusion.1", s, d], ["while.39", nxt, 0.09], ["fusion.7", nxt + 1e-3, 0.01]]
    top = tracered.top_level(events)
    assert [e[0] for e in top] == ["fusion.1", "while.39"]  # the call is kept, its body op nested
    # an event that starts one nanosecond before the other ends IS inside it
    inside = [["while.39", s, d], ["fusion.7", nxt - 1e-9, 1e-9]]
    assert [e[0] for e in tracered.top_level(inside)] == ["while.39"]
    # and the kernel's seconds count every call
    trace = {
        "device": {"/device:TPU:0": [["fusion.1", s, d], ["while.39", nxt, 0.09]]},
        "host": [[M, s - 0.01, d + 0.2]],
    }
    r = tracered.reduce_trace(trace)
    assert r["kernel_calls"] == 1 and r["kernel_device_s_total"] == pytest.approx(0.09)
