"""The general reader of per-layer metrics, and adding to the benchmark by
files only."""

import json
import os
import re

import pytest

from perfbench_tiny import make_tiny

from perfbench.harness import layers, roofline
from perfbench.harness.cell import Cell, CellError

TREE = {
    "name": "sidecar_round", "dur_s": 1.0,
    "children": [
        {"name": "assemble", "dur_s": 0.2},
        {"name": "round", "dur_s": 0.6, "children": [
            {"name": "feed_apply", "dur_s": 0.1, "children": [{"name": "feed_apply", "dur_s": 0.05}]},
            {"name": "fetch_decode", "dur_s": 0.3},
        ]},
        {"name": "feed_apply", "dur_s": 0.05},
    ],
}
SYNC = {"name": "sidecar_sync", "dur_s": 0.4, "children": [{"name": "feed_apply", "dur_s": 0.3}]}
CYCLES = [
    {"spans": [SYNC, TREE], "uploads": 30, "kernel_iters": 1000, "traced": True, "wall_s": 1.5},
    {"spans": [SYNC, TREE], "uploads": 34, "kernel_iters": 1010, "traced": False, "wall_s": 1.7},
    {"spans": [], "uploads": 32, "kernel_iters": 1005, "traced": False, "wall_s": 1.6},
]
TRACE = {"kernel_device_s_total": 0.25, "device_kind": "TPU v5 lite", "device_idle_share_pct": 55.0}
CTX = {
    "cycles": CYCLES, "trace": TRACE, "run": {"memory_peak_bytes": 123},
    "shapes": {"nodes": 50_000, "queues": 64, "resources": 2},
}


@pytest.mark.parametrize(
    "spec,want",
    [
        ({"kind": "span_sum", "spans": ["assemble"]}, 0.2),
        # a nested match counts once; both roots are searched
        ({"kind": "span_sum", "spans": ["feed_apply"]}, 0.3 + 0.1 + 0.05),
        ({"kind": "span_sum", "spans": ["feed_apply"], "root": "sidecar_round"}, 0.15),
        ({"kind": "span_sum", "spans": ["nothing_by_this_name"]}, 0.0),
        ({"kind": "cycle_field", "field": "uploads", "reduce": "median"}, 32),
        ({"kind": "cycle_field", "field": "uploads", "reduce": "sum"}, 96),
        ({"kind": "cycle_field", "field": "kernel_iters", "reduce": "sum", "cycles": "traced"}, 1000),
        ({"kind": "cycle_field", "field": "absent"}, None),
        ({"kind": "trace_field", "field": "device_idle_share_pct"}, 55.0),
        ({"kind": "run_field", "field": "memory_peak_bytes"}, 123),
        (
            {"kind": "ratio", "scale": 1e6,
             "num": {"kind": "trace_field", "field": "kernel_device_s_total"},
             "den": {"kind": "cycle_field", "field": "kernel_iters", "reduce": "sum", "cycles": "traced"}},
            250.0,
        ),
    ],
)
def test_reader(spec, want):
    got = layers.read(spec, CTX)
    assert got == pytest.approx(want) if want is not None else got is None


def test_no_trace_no_device_metric():
    ctx = dict(CTX, trace={})
    assert layers.read({"kind": "trace_field", "field": "busy_s"}, ctx) is None
    roof = {"kind": "roofline", "bytes": "round_trip_bytes",
            "seconds": {"kind": "trace_field", "field": "kernel_device_s_total"},
            "trips": {"kind": "cycle_field", "field": "kernel_iters", "reduce": "sum", "cycles": "traced"}}
    assert layers.read(roof, ctx) is None
    share = layers.read(roof, CTX)
    # 1,000 trips x (50,000 nodes x 9 B + 64 queues x 8 B + 16 B) over 819 GB/s, of 0.25 s
    assert share == pytest.approx(100 * 1000 * (450_000 + 512 + 16) / 819e9 / 0.25)
    assert 0 < share < 100


def test_unknown_device_kind_is_an_error():
    assert roofline.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError, match="not in perfbench/peaks.json"):
        roofline.peak("TPU v9 imaginary", "hbm_bytes_per_s")


def test_percentile():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert layers.percentile(xs, 50) == 3.0
    assert layers.percentile(xs, 75) == 4.0
    assert layers.percentile(xs, 100) == 5.0


def test_cell_mix_and_span_sum_metric_added_as_files_only(tmp_path):
    bench = make_tiny(tmp_path)
    # a span-sum metric over a span no shipped metric reads: one more file, one more entry
    doc = json.load(open(bench))
    new = {"name": "submit_many_s", "unit": "s", "better": "lower", "source": "program_span",
           "layer": "mirror and feed", "moves": "cycle_p50_s"}
    doc["per_layer"].append(new)
    json.dump(doc, open(bench, "w"))
    reader = dict(new, read={"kind": "span_sum", "spans": ["fetch_decode"], "reduce": "median"})
    with open(os.path.join(tmp_path, "perfbench", "layers", "submit_many_s.json"), "w") as f:
        json.dump(reader, f)
    cell = Cell(bench, "tiny.steady-40")
    assert cell.config["name"] == "tiny" and cell.traffic["cap"] == 40
    assert cell.scheduling()["maximumSchedulingBurst"] == 40
    found = {m["name"]: r for m, r in cell.per_layer()}
    assert "downloads_per_cycle" in found and "submit_many_s" in found
    assert layers.read(found["submit_many_s"]["read"], CTX) == pytest.approx(0.3)
    with pytest.raises(CellError):
        Cell(bench, "no.such-cell")


def test_window_refill_share_reads_the_two_counters():
    from perfbench_tiny import load

    spec = load("perfbench", "layers", "window_refill_share.json")["read"]
    cycles = [dict(c, window_refills=n) for c, n in zip(CYCLES, (14, 16, 12))]
    got = layers.read(spec, dict(CTX, cycles=cycles))
    assert got == pytest.approx(100.0 * (14 + 16 + 12) / (1000 + 1010 + 1005))
    # a program without the counter: nothing to read, the metric is left out
    assert layers.read(spec, CTX) is None


POOL_CYCLES = [
    {"preempted": ["r00000001", "j000000007"], "pool": {"preempted": 2, "scheduled": 40, "termination": "cap"},
     "scatter_rows": {"sg_rows": 512}},
    {"preempted": [], "pool": {"preempted": 0, "scheduled": 38, "brand_new_counter": 5}, "scatter_rows": None},
    {"preempted": [], "error": "UNAVAILABLE"},  # a cycle with no response has no stats
]


@pytest.mark.parametrize(
    "field,reduce,want",
    [
        ("pool.preempted", "sum", 2),  # the record's own `preempted` is the list of ids
        ("pool.scheduled", "min", 38),
        ("pool.brand_new_counter", "max", 5),  # a counter a later program adds: no code, a file
        ("scatter_rows.sg_rows", "max", 512),
        ("pool.no_such_counter", "sum", None),  # nothing to read: the metric is left out
        ("pool.preempted.deeper", "sum", None),
        ("no_such_group.x", "sum", None),
    ],
)
def test_cycle_field_reads_into_a_group_of_the_record(field, reduce, want):
    spec = {"kind": "cycle_field", "field": field, "reduce": reduce}
    assert layers.read(spec, dict(CTX, cycles=POOL_CYCLES)) == want


TERMINATIONS = [
    {"termination": "exhausted", "pool": {"termination": "exhausted", "scheduled": 480}},
    {"termination": "global_burst", "pool": {"termination": "global_burst", "scheduled": 1000}},
    {"termination": "exhausted", "pool": {"termination": "exhausted", "scheduled": 520}},
    {"termination": "exhausted", "pool": {"termination": "exhausted", "scheduled": 0}},
    {"error": "UNAVAILABLE"},  # no response: no sample, neither a 0 nor a 1
]


@pytest.mark.parametrize(
    "spec,want",
    [
        # the samples become 0 / 1 before the reduction
        ({"field": "termination", "equals": "exhausted", "reduce": "mean"}, 0.75),
        ({"field": "termination", "equals": "exhausted", "reduce": "sum"}, 3),
        ({"field": "termination", "equals": "global_burst", "reduce": "mean"}, 0.25),
        ({"field": "pool.termination", "equals": "max_iterations", "reduce": "max"}, 0),
        ({"field": "pool.scheduled", "equals": 0, "reduce": "sum"}, 1),  # any value, not only a string
        ({"field": "pool.no_such_key", "equals": "exhausted", "reduce": "mean"}, None),
        # how many samples there are: a record without the field is none
        ({"field": "termination", "reduce": "count"}, 4),
        ({"field": "termination", "equals": "max_iterations", "reduce": "count"}, 4),
        # without `equals`: the reader as it was
        ({"field": "pool.scheduled", "reduce": "median"}, 500),
    ],
)
def test_cycle_field_equals_counts_a_value(spec, want):
    got = layers.read(dict(spec, kind="cycle_field"), dict(CTX, cycles=TERMINATIONS))
    assert got == pytest.approx(want) if want is not None else got is None


@pytest.mark.parametrize(
    "name,cycles,want",
    [
        ("exhausted_round_share", TERMINATIONS, 75.0),
        ("exhausted_round_share", POOL_CYCLES, None),  # a record without the field: left out, never 0
        ("scheduled_per_cycle", TERMINATIONS, 500),
        ("scheduled_per_cycle", POOL_CYCLES, 39),
        ("preempted_per_cycle", POOL_CYCLES, 1.0),
    ],
)
def test_the_round_kernel_counters_of_pr33_are_files(name, cycles, want):
    from perfbench_tiny import load

    doc = load("perfbench", "layers", name + ".json")
    assert doc["layer"] == "round kernel" and doc["moves"] == "cycle_p50_s"
    got = layers.read(doc["read"], dict(CTX, cycles=cycles))
    assert got == pytest.approx(want) if want is not None else got is None


def test_no_shipped_layer_file_reads_a_field_the_record_lacks(tmp_path):
    """Every counter a shipped layer file reads as a `cycle_field` is in a
    cycle's record as the runner builds it (the tiny cell's first round)."""
    import glob

    from perfbench_tiny import ROOT
    from perfbench.harness.runner import Run

    def fields(spec):
        if isinstance(spec, dict):
            if spec.get("kind") == "cycle_field":
                yield spec["field"]
            for v in spec.values():
                yield from fields(v)

    run = Run(Cell(make_tiny(tmp_path), "tiny.steady-40"), 3, 1.0, False)
    run.start(str(tmp_path / "data"))
    try:
        run.load_mirror()
        rec = run.cycle()
    finally:
        run.stop()
    assert rec["pool"]["preempted"] == 0 and rec["pool"]["scheduled"] == 40 == rec["scheduled"]
    assert rec["preempted"] == [] and rec["termination"] == rec["pool"]["termination"]
    assert not [k for k, v in rec["pool"].items() if isinstance(v, (dict, list))]
    for path in glob.glob(os.path.join(ROOT, "perfbench", "layers", "*.json")):
        for field in fields(json.load(open(path))):
            if not field.endswith("_s"):  # the clocks: some are the window's to take
                assert layers.field_of(rec, field) is not None, (path, field)


def test_the_checker_imports_nothing_of_the_program():
    from perfbench_tiny import ROOT

    for name in ("checker.py", "layers.py", "roofline.py"):
        src = open(os.path.join(ROOT, "perfbench", "harness", name), encoding="utf-8").read()
        assert not re.search(r"^\s*(from|import)\s+armada_tpu|__import__|importlib", src, re.M), name
