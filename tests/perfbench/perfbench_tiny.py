"""Shared by the perfbench tests: the repo root on sys.path, and a tiny copy
of the benchmark (its own BENCHMARK.json, one new configuration, one new
traffic mix, one new per-layer metric) made of files only: the plain tiny
cell, the tiny full fleet (`full=`) or the tiny gang cell on a fleet with GPU
nodes (`gangs=`)."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RUN = os.path.join(ROOT, "perfbench", "run.py")


def load(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return json.load(f)


GPU = "nvidia.com/gpu"
GPU_TAINT = {"key": GPU, "value": "present", "effect": "NoSchedule"}


def gang_world(nodes=120, share=0.5, cardinality=None, uniformity_label="", gang_preemptible_share=None, cpu_share=0.8):
    """The `world` keys of the tiny GANG cell: a fifth of the nodes carry 8
    GPUs, the label `accelerator=a100` and a taint that keeps other jobs off;
    `share` of the submits are members of gangs of cardinality 1-4 (in equal
    numbers, unless `cardinality` says otherwise) that ask one GPU each, select
    the label and tolerate the taint; the rest is the grid's."""
    gpu = round(nodes * (1 - cpu_share))
    kind = {
        "name": "gang-a100", "share": share, "cpu_milli": 2000, "memory": 8, "resources": {GPU: 1},
        "node_selector": {"accelerator": "a100"},
        "tolerations": [dict(GPU_TAINT, operator="Equal")],
        "gang": {"cardinality": cardinality or {"1": 1, "2": 1, "3": 1, "4": 1}, "uniformity_label": uniformity_label},
    }
    if gang_preemptible_share is not None:
        kind["preemptible_share"] = gang_preemptible_share
    return {
        "resources": ["cpu", "memory", GPU],
        "node_cores": None, "memory_per_core": None,
        "node_types": [
            {"name": "cpu", "count": nodes - gpu, "cores": 32, "memory": 128},
            {"name": "a100", "count": gpu, "cores": 16, "memory": 64, "resources": {GPU: 8},
             "labels": {"accelerator": "a100", "zone": "z1"}, "taints": [GPU_TAINT]},
        ],
        "job_kinds": [kind],
    }


def make_tiny(root, nodes=120, queued=1500, running=60, burst=40, lifetime=3, full=None, gangs=None):
    """A benchmark root under `root` with one tiny cell, `tiny.steady-40`,
    added as files only; returns the path of its BENCHMARK.json.

    `full` (a dict) makes it the tiny FULL-FLEET cell instead: the fleet
    filled by capacity with the first queues over their share, so that rounds
    preempt.  Its keys: `world` (updates of the world block, over
    `running_fill` 1.0 and `running_queue_demand` "1/k"), `traffic`
    (updates of the mix: `completions_per_cycle`,
    `stationary_slack_per_cycle`) and `scheduling` (updates of the scheduling
    block, over `maxQueueLookback` 100000: a full fleet's rounds can give up,
    and invariant 9 reads the lookback from the configuration alone).

    `gangs` (a dict, possibly empty) makes it the tiny GANG cell: the
    arguments of `gang_world` (a fifth of the nodes with GPUs, a label and a
    taint; gangs of cardinality 1-4 among the submits), with `world`,
    `traffic` and `scheduling` updates as `full` has them.  With the default
    sizes a batch of 40 jobs holds 20 members: two gangs of each cardinality
    from 1 to 4 (`World.unit_counts`), the same in every batch."""
    root = str(root)
    data = os.path.join(root, "perfbench")
    shutil.copytree(os.path.join(ROOT, "perfbench", "layers"), os.path.join(data, "layers"))
    os.makedirs(os.path.join(data, "configs"))
    os.makedirs(os.path.join(data, "traffic"))
    bench = load("BENCHMARK.json")
    config = load("perfbench", "configs", "cluster-100k-5k.json")
    config["name"] = "tiny"
    config["world"].update(
        nodes=nodes, queued_jobs=queued, running_jobs=running, queues=8,
        executors=2, mirror_chunk=1000,
    )
    config["scheduling"]["shapeBucket"] = 256
    if full is not None:
        del config["world"]["running_jobs"]
        config["world"].update(
            running_fill=1.0, running_queue_demand="1/k", running_cpu_milli=[4000, 2000],
            running_memory=8,
        )
        config["world"].update(full.get("world", {}))
        config["scheduling"].update({"maxQueueLookback": 100_000}, **full.get("scheduling", {}))
    if gangs is not None:
        more = {k: gangs[k] for k in ("world", "traffic", "scheduling") if k in gangs}
        made = gang_world(nodes, **{k: v for k, v in gangs.items() if k not in more})
        for key, value in {**made, **more.get("world", {})}.items():
            if value is None:
                config["world"].pop(key, None)
            else:
                config["world"][key] = value
        config["scheduling"].update({"indexedNodeLabels": ["accelerator"], "indexedTaints": [GPU]}, **more.get("scheduling", {}))
    traffic = load("perfbench", "traffic", "steady-1k.json")
    traffic.update(
        name="steady-40", submits_per_cycle=burst, cap=burst,
        lifetime_cycles=lifetime, traced_cycles=2,
    )
    traffic.update((full or gangs or {}).get("traffic", {}))
    extra = {
        "name": "downloads_per_cycle", "layer": "decode and apply", "unit": "count",
        "better": "lower", "moves": "cycle_p50_s", "source": "program_counter",
        "read": {"kind": "cycle_field", "field": "downloads", "reduce": "median"},
    }
    # a counter of the round's own stats JSON, read as a file and no code
    pooled = dict(
        extra, name="preempted_max_per_cycle",
        read={"kind": "cycle_field", "field": "pool.preempted", "reduce": "max"},
    )
    added = [extra, pooled]
    if gangs is not None:
        # the record's two fields of a gang cell, each read by a file (the
        # form a cell on a GPU fleet declares them in)
        added += [
            dict(extra, name=f"{field}_per_cycle", layer="round kernel", better="higher",
                 read={"kind": "cycle_field", "field": field, "reduce": "mean"})
            for field in ("gang_members_leased", "gpu_leases")
        ]
    for path, doc in [
        (os.path.join(data, "configs", "tiny.json"), config),
        (os.path.join(data, "traffic", "steady-40.json"), traffic),
    ] + [(os.path.join(data, "layers", m["name"] + ".json"), m) for m in added]:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f)
    bench["configs"] = [
        {"name": "tiny", "source": "test", "file": "perfbench/configs/tiny.json",
         "reduced": [], "why": "test"}
    ]
    bench["workloads"] = [
        {"name": "tiny.steady-40", "config": "tiny", "traffic": "steady-40",
         "chips": 1, "why": "test"}
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    for m in added:
        bench["per_layer"].append({k: m[k] for k in ("name", "unit", "better", "source", "layer", "moves")})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return path
