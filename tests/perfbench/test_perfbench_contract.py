"""BENCHMARK.json against the contract's limits, and against its own files."""

import os
import re

import pytest

from perfbench_tiny import ROOT, load

BENCH = load("BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _names():
    out = [("command word", w) for w in BENCH["command"]]
    for c in BENCH["configs"]:
        out.append(("config", c["name"]))
        out += [("reduced", k) for k in c["reduced"]]
    for w in BENCH["workloads"]:
        out += [("workload", w["name"]), ("config ref", w["config"]), ("traffic", w["traffic"])]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        out.append(("metric", m["name"]))
    return out


@pytest.mark.parametrize("kind,name", [n for n in _names() if n[0] != "command word"])
def test_name_is_allowed(kind, name):
    assert NAME.match(name), f"{kind} {name!r}"


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    if "bound" in metric:  # end to end
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
        allowed |= {"bound"}
    else:
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
        allowed |= {"layer", "moves"}
    assert set(metric) <= allowed
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_top_level_shape():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert 1 <= len(BENCH["paths"]) <= 16
    assert len(BENCH["command"]) <= 32
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"], ids=lambda e: e["name"])
def test_texts_fit(entry):
    for key in ("why", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(config):
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    doc = load(*config["file"].split("/"))
    assert doc["source"] == config["source"]
    assert doc["reduced"] == config["reduced"] and len(config["reduced"]) <= 16
    assert doc["guarantees"] and doc["assumed"]
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_and_metrics(cell):
    """Every cell finds its files by name, reports setup_s, another end-to-end
    metric and a per-layer metric; and what a per-layer metric `moves` is
    reported wherever the metric is."""
    assert os.path.exists(os.path.join(ROOT, "perfbench", "traffic", cell["traffic"] + ".json"))
    here = lambda m: cell["name"] in m.get("workloads", [cell["name"]])  # noqa: E731
    e2e = {m["name"] for m in BENCH["end_to_end"] if here(m)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in BENCH["per_layer"] if here(m)]
    assert layer
    for m in layer:
        assert m["moves"] in e2e, m["name"]
        doc = load("perfbench", "layers", m["name"] + ".json")
        assert doc["layer"] == m["layer"] and doc["unit"] == m["unit"]
        assert doc["moves"] == m["moves"] and doc["source"] == m["source"]


@pytest.mark.parametrize("mix", sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "perfbench", "traffic"))))
def test_traffic_mix(mix):
    """The keys the one generator reads, in every mix file there is, declared
    or kept for later; and the optional two of a full fleet (PR 33): a service
    rate under the arrivals, and how many jobs a cycle a count may be off."""
    doc = load("perfbench", "traffic", mix + ".json")
    assert doc["name"] == mix and doc["loop"] == "closed"
    for key in ("submits_per_cycle", "lifetime_cycles", "cap", "traced_cycles"):
        assert isinstance(doc[key], int) and doc[key] > 0, key
    assert doc["logical_cycle_s"] > 0 and doc.get("min_warm_cycles", 0) >= 0
    if "completions_per_cycle" in doc:
        assert isinstance(doc["completions_per_cycle"], int)
        assert 0 < doc["completions_per_cycle"] <= doc["submits_per_cycle"]
    slack = doc.get("stationary_slack_per_cycle", 0)
    assert 0 <= slack < doc["submits_per_cycle"]
    # a mix that lets a count drift gives the two readings its slack lies between
    assert not slack or doc["stationary_slack_per_cycle_why"]


def test_no_shipped_mix_or_configuration_states_a_full_fleet_key():
    """`steady-1k` is what it was: completions echo leases, the window stands
    still exactly; no shipped configuration fills its fleet or has rounds that
    give up (none states `maxQueueLookback`).  The rules of a full fleet are
    named by the tests' tiny full fleet until a cell with a sourced running
    set brings them (PERF.md section 7, row 1)."""
    for mix in ("steady-1k", "bulk-5k"):
        doc = load("perfbench", "traffic", mix + ".json")
        assert "completions_per_cycle" not in doc and "stationary_slack_per_cycle" not in doc
    names = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "perfbench", "configs")))
    assert names == ["cluster-100k-5k", "envelope-1m-50k", "tenants-925q-1m-50k"]
    for name in names:
        doc = load("perfbench", "configs", name + ".json")
        assert "running_fill" not in doc["world"] and "running_jobs" in doc["world"]
        assert "maxQueueLookback" not in doc["scheduling"]


def test_the_three_round_counters_are_declared_for_every_cell():
    """`exhausted_round_share`, `preempted_per_cycle`, `scheduled_per_cycle`:
    a file and an entry each, layer "round kernel", no `workloads` list (every
    cell's rounds report a termination and both counts)."""
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in ("exhausted_round_share", "preempted_per_cycle", "scheduled_per_cycle"):
        assert "workloads" not in entries[name] and entries[name]["layer"] == "round kernel"
        assert entries[name]["moves"] == "cycle_p50_s" and entries[name]["source"] == "program_counter"
        assert load("perfbench", "layers", name + ".json")["name"] == name


def test_paths_hold_only_allowed_file_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in BENCH["paths"]:
        for dirpath, dirnames, files in os.walk(os.path.join(ROOT, p)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                assert ok.match(rel), rel
