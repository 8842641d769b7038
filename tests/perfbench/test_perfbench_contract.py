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
    assert world_form_problems(doc["world"]) == []


def world_form_problems(world):
    """What is wrong with the FORM of a `world` block's optional keys
    (perfbench/README.md, "A configuration"), as a list of sentences; none of
    it pins a value a later configuration may need.  The nodes are stated
    once, by the short form or by `node_types` (a name, cores, memory, and a
    `count` or a `share` each; extra resources among `resources`); a
    `job_kinds` entry has a share, a size, extra resources among `resources`,
    a `node_selector` that some `node_types` entry's labels satisfy, and a
    gang cardinality that is a whole number from 1 or such numbers with
    weights; `queue_demand` is "1/k", the one demand the generator draws."""
    out = []
    resources = list(world["resources"])
    if resources[:2] != ["cpu", "memory"]:
        out.append("`resources` starts with cpu and memory")
    if world.get("queue_demand", "1/k") != "1/k":
        out.append("`queue_demand` is \"1/k\": the generator draws no other")
    if ("node_types" in world) == ("node_cores" in world):
        out.append("the nodes are stated once: `node_cores` x `memory_per_core`, or `node_types`")
    types = world.get("node_types", [])
    for t in types:
        if not {"name", "cores", "memory"} <= set(t) or ("count" in t) == ("share" in t):
            out.append(f"node type {t.get('name')!r}: a name, cores, memory, and a `count` or a `share`")
        if not set(t.get("resources", {})) <= set(resources[2:]):
            out.append(f"node type {t.get('name')!r} has a resource that `resources` does not list")
    for n, kind in enumerate(world.get("job_kinds", [])):
        name = kind.get("name", f"kind{n}")
        if not (0 < kind.get("share", 0) <= 1 and {"cpu_milli", "memory"} <= set(kind)):
            out.append(f"job kind {name}: a `share` over 0 and at most 1, `cpu_milli` and `memory`")
        if not set(kind.get("resources", {})) <= set(resources[2:]):
            out.append(f"job kind {name} asks a resource that `resources` does not list")
        selector = kind.get("node_selector", {})
        if selector and not any(all(t.get("labels", {}).get(k) == v for k, v in selector.items()) for t in types):
            out.append(f"job kind {name}: no `node_types` entry's labels satisfy its node_selector")
        cardinality = kind.get("gang", {}).get("cardinality", 1)
        sizes = cardinality if isinstance(cardinality, dict) else {cardinality: 1}
        if not sizes or not all(str(c).isdigit() and int(c) >= 1 and w > 0 for c, w in sizes.items()):
            out.append(f"job kind {name}: a gang's cardinality is a whole number from 1, or such numbers with weights")
    return out


GANG_FLEET = {  # a labelled three-resource fleet in the fewest keys (the tests' tiny gang cell, perfbench_tiny.py, runs the same)
    "resources": ["cpu", "memory", "nvidia.com/gpu"], "node_cores": None, "memory_per_core": None,
    "node_types": [{"name": "cpu", "share": 0.8, "cores": 32, "memory": 128},
                   {"name": "a100", "share": 0.2, "cores": 16, "memory": 64, "resources": {"nvidia.com/gpu": 8},
                    "labels": {"accelerator": "a100"}, "taints": [{"key": "nvidia.com/gpu", "value": "present", "effect": "NoSchedule"}]}],
    "job_kinds": [{"name": "gang", "share": 0.048, "cpu_milli": 16000, "memory": 64, "resources": {"nvidia.com/gpu": 8},
                   "node_selector": {"accelerator": "a100"}, "tolerations": [{"key": "nvidia.com/gpu", "operator": "Exists"}],
                   "gang": {"cardinality": 8}}],
}


def _kind(**over):
    return dict(GANG_FLEET, job_kinds=[dict(GANG_FLEET["job_kinds"][0], **over)])


@pytest.mark.parametrize(
    "keys,says",
    [
        ({}, None),  # the accepted form states none of the new keys
        (GANG_FLEET, None),
        (_kind(gang={"cardinality": {"4": 3, "16": 1}, "uniformity_label": "zone"}, share=1.0), None),  # any value of the form
        (_kind(resources={"amd.com/gpu": 1}), "asks a resource that `resources` does not list"),
        (_kind(node_selector={"accelerator": "h100"}), "labels satisfy its node_selector"),
        (dict(_kind(), node_types=None, node_cores=[16], memory_per_core=4), "labels satisfy its node_selector"),
        (_kind(gang={"cardinality": 0}), "whole number from 1"),
        (_kind(share=0), "a `share` over 0"),
        (dict(GANG_FLEET, node_cores=[16]), "stated once"),
        (dict(GANG_FLEET, node_types=[{"name": "x", "cores": 8, "memory": 8}]), "a `count` or a `share`"),
        (dict(GANG_FLEET, resources=["cpu", "memory"]), "does not list"),
        ({"queue_demand": "uniform"}, "draws no other"),
    ],
    ids=["the-accepted-form", "a-gang-fleet", "other-values", "unlisted-resource", "selector-nobody-satisfies", "selector-without-node-types",
         "cardinality-0", "no-share", "nodes-twice", "type-without-count", "gpu-not-listed", "queue-demand"],
)
def test_the_form_of_a_world_block(keys, says):
    world = _updated(dict(load("perfbench", "configs", "cluster-100k-5k.json")["world"]), dict(keys))
    problems = world_form_problems(world)
    if says is None:
        assert problems == []
    else:
        assert any(says in p for p in problems), problems


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


ACCEPTED_CONFIGS = ("cluster-100k-5k", "envelope-1m-50k", "tenants-925q-1m-50k")
ACCEPTED_MIXES = ("steady-1k", "bulk-5k")


@pytest.mark.parametrize("name", ACCEPTED_CONFIGS + ACCEPTED_MIXES)
def test_the_accepted_files_are_what_they_were(name):
    """By NAME, not by listing a directory: a sixth file beside them fails
    nothing.  `steady-1k` and `bulk-5k`: completions echo leases and the window
    stands still exactly; the three configurations of PR 24 and PR 28 spread
    `running_jobs` over the fleet, fill nothing and state no lookback (none of
    their rounds gives up)."""
    if name in ACCEPTED_MIXES:
        doc = load("perfbench", "traffic", name + ".json")
        assert "completions_per_cycle" not in doc and "stationary_slack_per_cycle" not in doc
    else:
        doc = load("perfbench", "configs", name + ".json")
        assert "running_fill" not in doc["world"] and "running_jobs" in doc["world"]
        assert "maxQueueLookback" not in doc["scheduling"]


def full_fleet_problems(configs, mixes, bench):
    """What is missing where a full fleet is declared, as a list of sentences
    (perfbench/README.md, "A full fleet").  `configs` and `mixes` are the files
    of `perfbench/configs/` and `perfbench/traffic/` by name; `bench` is
    BENCHMARK.json.  Structure only, cell by cell: a configuration whose
    `world` has `running_fill` states `maxQueueLookback` (invariant 9 reads it
    from the configuration alone) and derives its running set (no
    `running_jobs`), and a cell of it runs a mix with a service rate UNDER its
    arrivals and a slack with the two readings it lies between.  Which classes
    arrive, and which fleets a mix with a service rate may run on, are not
    rules of form: `correct` decides them, on the chip."""
    out = []
    file_of = {c["name"]: c["file"].rsplit("/", 1)[-1][:-5] for c in bench["configs"]}
    for cell in bench["workloads"]:
        config, mix = configs[file_of[cell["config"]]], mixes[cell["traffic"]]
        if "running_fill" not in config["world"]:
            continue
        lookback = config["scheduling"].get("maxQueueLookback")
        if not (isinstance(lookback, int) and lookback > 0):
            out.append(f"{cell['name']}: a full fleet's rounds can give up, and invariant 9 needs `maxQueueLookback` stated")
        if "running_jobs" in config["world"]:
            out.append(f"{cell['name']}: `running_fill` derives the running set: no `running_jobs` beside it")
        if not 0 < mix.get("completions_per_cycle", 0) < mix["submits_per_cycle"]:
            out.append(f"{cell['name']}: a full fleet needs a service rate under the arrivals (`completions_per_cycle`)")
        if not (mix.get("stationary_slack_per_cycle", 0) > 0 and mix.get("stationary_slack_per_cycle_why")):
            out.append(f"{cell['name']}: a full fleet needs `stationary_slack_per_cycle` and its `_why`")
    return out


def _shipped(kind):
    d = os.path.join(ROOT, "perfbench", kind)
    return {f[:-5]: load("perfbench", kind, f) for f in sorted(os.listdir(d)) if f.endswith(".json")}


def test_a_full_fleet_is_declared_whole():
    """Over every workload of BENCHMARK.json, whoever added it: the contract
    of a full fleet that perfbench/README.md states.  No cell declares one yet
    (PERF.md section 7, row 1), so the scratch cases below are what shows the
    rule at work."""
    assert full_fleet_problems(_shipped("configs"), _shipped("traffic"), BENCH) == []


# a full fleet and its mix as a later PR would add them, in the fewest keys
# (the tests' tiny full fleet, perfbench_tiny.py, runs the same keys)
FULL = {"running_fill": 1.0, "running_jobs": None, "scheduling": {"maxQueueLookback": 100000}}
OVERLOAD = {"completions_per_cycle": 500, "stationary_slack_per_cycle": 50,
            "stationary_slack_per_cycle_why": "between a sound window's reading and the filling phase's"}


def _updated(doc, keys):
    for key, value in keys.items():
        if value is None:
            doc.pop(key, None)
        else:
            doc[key] = value
    return doc


def _scratch(mix=(), scheduling=(), **world):
    """The shipped files with a scratch configuration beside them (a copy of
    `cluster-100k-5k`, its `world` and `scheduling` updated; None removes a
    key) and a scratch cell that runs it under `steady-1k` updated by `mix`."""
    import copy

    configs, mixes, bench = _shipped("configs"), _shipped("traffic"), copy.deepcopy(BENCH)
    configs["scratch"] = copy.deepcopy(configs["cluster-100k-5k"])
    _updated(configs["scratch"]["world"], world)
    _updated(configs["scratch"]["scheduling"], dict(scheduling))
    mixes["scratch-mix"] = _updated(copy.deepcopy(mixes["steady-1k"]), dict(mix))
    bench["configs"].append({"name": "scratch", "file": "perfbench/configs/scratch.json"})
    bench["workloads"].append({"name": "scratch.cell", "config": "scratch", "traffic": "scratch-mix"})
    return configs, mixes, bench


@pytest.mark.parametrize(
    "scratch,says",
    [
        (dict(), None),  # one more configuration and cell that break no rule fail nothing
        (dict(FULL, mix=OVERLOAD), None),  # nor does a full fleet, declared whole
        (dict(FULL, mix=OVERLOAD, preemptible_share=0.7), None),  # whatever arrives: `correct` decides that
        (dict(mix=OVERLOAD), None),  # nor a mix with a service rate on a fleet that is not full
        (dict(FULL, mix=OVERLOAD, scheduling={}), "maxQueueLookback"),
        (dict(FULL, mix=OVERLOAD, running_jobs=2500), "no `running_jobs`"),
        (dict(FULL), "a service rate under the arrivals"),  # run by `steady-1k` as it is
        (dict(FULL, mix=dict(OVERLOAD, completions_per_cycle=1000)), "a service rate under the arrivals"),
        (dict(FULL, mix=dict(OVERLOAD, stationary_slack_per_cycle_why=None)), "and its `_why`"),
    ],
    ids=["a-fourth-configuration", "a-full-fleet", "prod-arrivals", "a-service-rate-on-an-empty-fleet", "no-lookback",
         "running-jobs-too", "run-by-steady-1k", "service-rate-not-under-arrivals", "slack-without-its-why"],
)
def test_the_full_fleet_rule_on_a_scratch_configuration(scratch, says):
    problems = [p for p in full_fleet_problems(*_scratch(**scratch)) if p.startswith("scratch.cell: ")]
    if says is None:
        assert problems == []
    else:
        assert any(says in p for p in problems), problems


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
