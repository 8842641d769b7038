"""Shared by the perfbench tests: the repo root on sys.path, and a tiny copy
of the benchmark (its own BENCHMARK.json, one new configuration, one new
traffic mix, one new per-layer metric) made of files only."""

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


def make_tiny(root, nodes=120, queued=1500, running=60, burst=40, lifetime=3, full=None):
    """A benchmark root under `root` with one tiny cell, `tiny.steady-40`,
    added as files only; returns the path of its BENCHMARK.json.

    `full` (a dict) makes it the tiny FULL-FLEET cell instead: the fleet
    filled by capacity with the first queues over their share, so that rounds
    preempt.  Its keys: `world` (updates of the world block, over
    `running_fill` 1.0 and `running_queue_demand` "1/k"), `traffic`
    (updates of the mix: `completions_per_cycle`,
    `stationary_slack_per_cycle`) and `scheduling` (updates of the scheduling
    block, over `maxQueueLookback` 100000: a full fleet's rounds can give up,
    and invariant 9 reads the lookback from the configuration alone)."""
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
    traffic = load("perfbench", "traffic", "steady-1k.json")
    traffic.update(
        name="steady-40", submits_per_cycle=burst, cap=burst,
        lifetime_cycles=lifetime, traced_cycles=2,
    )
    traffic.update((full or {}).get("traffic", {}))
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
    for path, doc in (
        (os.path.join(data, "configs", "tiny.json"), config),
        (os.path.join(data, "traffic", "steady-40.json"), traffic),
        (os.path.join(data, "layers", "downloads_per_cycle.json"), extra),
        (os.path.join(data, "layers", "preempted_max_per_cycle.json"), pooled),
    ):
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
    for added in (extra, pooled):
        bench["per_layer"].append({k: added[k] for k in ("name", "unit", "better", "source", "layer", "moves")})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return path
