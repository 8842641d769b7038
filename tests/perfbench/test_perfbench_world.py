"""The seeded generator: a seed changes the order, never the amount of work."""

import numpy as np
import pytest

from perfbench_tiny import load

from perfbench.harness.world import World, exact_counts

SIZES = dict(
    load("perfbench", "configs", "cluster-100k-5k.json")["world"],
    nodes=403, queued_jobs=5003, running_jobs=207, queues=13,
)


@pytest.mark.parametrize("seeds", [(1, 2), (7, 3_000_000_019), (0, 2**31 + 5)])
def test_two_seeds_same_histograms_other_order(seeds):
    a, b = World(SIZES, seeds[0]), World(SIZES, seeds[1])
    assert a.histograms() == b.histograms()
    assert (a.job_queue != b.job_queue).any() and (a.node_cores != b.node_cores).any()
    # later submits too: every cycle's batch has one histogram
    for w in (a, b):
        w.extend(1000, 5.0)
        w.extend(1000, 6.0)
    for w in (a, b):
        first = np.bincount(w.job_queue[5003:6003], minlength=13)
        second = np.bincount(w.job_queue[6003:7003], minlength=13)
        assert (first == second).all()
        assert (np.bincount(w.job_shape[5003:6003]) == np.bincount(w.job_shape[6003:7003])).all()
    assert (np.bincount(a.job_shape[5003:6003]) == np.bincount(b.job_shape[5003:6003])).all()


def test_same_seed_same_world():
    a, b = World(SIZES, 11), World(SIZES, 11)
    assert (a.job_queue == b.job_queue).all() and (a.job_submit == b.job_submit).all()
    assert (a.run_node == b.run_node).all()


@pytest.mark.parametrize("n,weights", [(1000, [1, 1 / 2, 1 / 3]), (7, [0.7, 0.3]), (0, [1, 1]), (5, [1] * 8)])
def test_exact_counts(n, weights):
    counts = exact_counts(n, weights)
    assert counts.sum() == n
    ideal = n * np.asarray(weights) / np.sum(weights)
    assert (np.abs(counts - ideal) < 1.0).all()


def test_demand_skew_and_shapes():
    w = World(SIZES, 5)
    h = w.histograms()
    assert h["job_queue"][0] > h["job_queue"][-1] * 10  # 1/k over 13 queues
    assert h["runs_per_node_max"] == 1
    share = sum(c for c, s in zip(h["job_shape"], w.shapes) if s[2]) / 5003
    assert abs(share - 0.7) < 0.001
    with pytest.raises(KeyError):
        w.job_number("j999999999")
    with pytest.raises(KeyError):
        w.job_number("r00000001")
    assert w.job_number("j000000042") == 42


# ---- a fleet filled by capacity (`running_fill`, `running_queue_demand`) ----

FULL = {k: v for k, v in SIZES.items() if k != "running_jobs"}


def _full(seed, **over):
    return World({**FULL, "running_fill": 1.0, "running_queue_demand": "1/k", **over}, seed)


@pytest.mark.parametrize(
    "over",
    [
        dict(running_cpu_milli=[8000], running_memory=32),  # fills 16/32/64/96 cores exactly
        dict(running_cpu_milli=[4000, 2000], running_memory=8),
        dict(running_cpu_milli=[500, 1000, 2000], running_memory=4, running_fill=0.7),
        dict(running_cpu_milli=[3000], running_memory=20, running_fill=0.9),  # memory binds first
    ],
    ids=["exact", "two-sizes", "accepted-shapes-0.7", "memory-bound"],
)
def test_fill_by_capacity_fills_every_node_size_and_overfills_none(over):
    from perfbench.harness.checker import Checker

    w = _full(5, **over)
    fill = over.get("running_fill", 1.0)
    used = np.zeros_like(w.node_total)
    np.add.at(used, w.run_node, w.run_shape_req[w.run_shape])
    room = np.floor(fill * w.node_total) - used
    assert (room >= 0).all(), "no node is over its share"
    smallest = w.run_shape_req.min(axis=0)
    # within one job of the smallest running shape, in the resource that binds
    assert ((room < smallest).any(axis=1)).all()
    classes = load("perfbench", "configs", "cluster-100k-5k.json")["scheduling"]["priorityClasses"]
    assert Checker(w, cap=10, queue_cap=10, priority_classes=classes).violations == []
    assert w.histograms()["running_jobs"] == len(w.run_shape) == len(w.run_queue) == len(w.run_node)
    if over["running_cpu_milli"] == [8000]:
        assert (used == w.node_total).all()


@pytest.mark.parametrize("seeds", [(1, 2), (7, 3_000_000_019)])
def test_fill_two_seeds_same_histograms_other_order(seeds):
    a, b = (_full(s, running_cpu_milli=[4000, 2000], running_memory=8) for s in seeds)
    ha, hb = a.histograms(), b.histograms()
    assert ha == hb
    assert ha["node_fill_pct"][100] == len(a.node_cores)  # every node full, whatever its size
    assert len([n for n in ha["runs_per_node"] if n]) == 4  # one count per node size
    assert (a.run_queue != b.run_queue).any() and (a.run_shape != b.run_shape).any()
    share = sum(c for c, s in zip(ha["run_shape"], a.run_shapes) if s[2]) / ha["running_jobs"]
    assert abs(share - 0.5) < 0.005


def test_running_queue_demand():
    skewed = _full(5, running_cpu_milli=[8000], running_memory=32).histograms()["run_queue"]
    assert skewed[0] > 10 * skewed[-1] and skewed == sorted(skewed, reverse=True)
    flat = World(dict(FULL, running_fill=1.0, running_cpu_milli=[8000], running_memory=32), 5)
    assert max(flat.histograms()["run_queue"]) - min(flat.histograms()["run_queue"]) <= 1
    # without running_fill the key still applies, and "uniform" is what absent means
    assert (
        World(dict(SIZES, running_queue_demand="uniform"), 5).run_queue == World(SIZES, 5).run_queue
    ).all()
    assert World(dict(SIZES, running_queue_demand="1/k"), 5).histograms()["run_queue"][0] > 40


@pytest.mark.parametrize(
    "sizes",
    [
        dict(SIZES, running_fill=1.0),  # both running_jobs and running_fill
        dict(FULL, running_fill=1.5),
        dict(SIZES, running_queue_demand="zipf"),
    ],
    ids=["both", "share-over-1", "unknown-demand"],
)
def test_bad_fill_keys_are_refused(sizes):
    with pytest.raises(ValueError):
        World(sizes, 1)


@pytest.mark.parametrize("seed", [7, 3_300_000_019])
def test_the_envelope_filled_by_eight_core_runs(seed):
    """The full fleet PR 33 measured and did not declare (PERF.md section 7,
    row 1), at a hundredth: the envelope's world with runs of 8 cores and 32
    of memory filling every node to the last core and unit of memory, 26 runs
    a four nodes (325,000 at 50,000 nodes), 2 % of them preemptible, 13 of 64
    queues holding two thirds; every queued job `batch`."""
    sizes = dict(load("perfbench", "configs", "envelope-1m-50k.json")["world"], nodes=500, queued_jobs=10_000)
    del sizes["running_jobs"]
    sizes.update(running_fill=1.0, running_queue_demand="1/k", running_cpu_milli=[8000], running_memory=32,
                 running_preemptible_share=0.02, preemptible_share=1.0)
    w = World(sizes, seed)
    h = w.histograms()
    assert h["running_jobs"] == 500 // 4 * (2 + 4 + 8 + 12) == 3250
    assert h["node_fill_pct"][100] == 500 and h["runs_per_node"][2::2][:6] == [125, 125, 0, 125, 0, 125]
    used = np.zeros_like(w.node_total)
    np.add.at(used, w.run_node, w.run_shape_req[w.run_shape])
    assert (used == w.node_total).all()  # memory too: 32 a run, 4 a core
    assert sum(c for c, s in zip(h["run_shape"], w.run_shapes) if s[2]) == 65  # 2 %
    assert 0.66 < sum(h["run_queue"][:13]) / 3250 < 0.68
    assert all(w.shapes[s][2] for s in np.unique(w.job_shape)) and len(np.unique(w.job_shape)) == 12
    later = w.extend(1000, 1.0)  # a cycle's arrivals
    assert {w.class_name(w.shapes[s][2]) for s in w.job_shape[later.start:later.stop]} == {"batch"}


def test_initial_runs_are_known_by_id():
    w = World(SIZES, 5)
    assert w.run_number("r00000017") == 17
    for bad in ("r00000207", "j000000017", "r17", "rXXXXXXXX"):
        with pytest.raises(KeyError):
            w.run_number(bad)
    assert w.class_name(True) == "batch" and w.class_name(False) == "prod"


# ---- the accepted configurations draw what they drew before this file changed ----

# sha256 over the tables and over the serialized first submit request, taken
# from the PARENT's world.py (commit cbf7655, before PR 27 touched it), at a
# size cut only in nodes / queued_jobs / running_jobs.
PINS = {
    ("envelope-1m-50k", 7): (
        "71cafd8854f4b1041916a7be7193d7537ee97f6c899903b8f929c37b85798de0",
        "15c620bd32ff308679fec4e08cc8de90dbbc4006a902290c0f89282428d68960",
    ),
    ("envelope-1m-50k", 3000000019): (
        "2659919631c63aefa703fae18f799f013f52c655c6a4af1fa25a1841dac1e915",
        "5216ac0e391722feb2f33f152da37f170a38289fc12a51d40b583d803fa0ef12",
    ),
    ("cluster-100k-5k", 7): (
        "f9c8e03bda9da404059233e488874686239c14906061772a7f61afcc4571a806",
        "e9ff50b97aa8cbc49ddc722248258cf01a131786dbd5c27e5b00877acb859db4",
    ),
    ("cluster-100k-5k", 3000000019): (
        "7aed99ce3794b1d3dddee99819ac2aeb520850e9492ad848ba71f3650c250648",
        "5d87b432660db1aab16924b228d7a64722b98d0b97837dd4b01357e1e96f90dd",
    ),
}
CUTS = {
    "envelope-1m-50k": dict(nodes=500, queued_jobs=10007, running_jobs=251),
    "cluster-100k-5k": dict(nodes=403, queued_jobs=5003, running_jobs=207),
}


@pytest.mark.parametrize("config,seed", sorted(PINS))
def test_accepted_configurations_draw_what_they_drew(config, seed):
    import hashlib

    from armada_tpu.rpc import rpc_pb2 as pb

    w = World(dict(load("perfbench", "configs", config + ".json")["world"], **CUTS[config]), seed)
    h = hashlib.sha256()
    for name in ("node_cores", "job_queue", "job_shape", "job_submit", "run_shape", "run_queue", "run_node"):
        a = getattr(w, name)
        h.update(name.encode() + str(a.dtype).encode() + a.tobytes())
    # the first cycle's submits, as runner.prebuild makes them
    numbers = w.extend_batches(1000, [(10**12 + 10**9) / 1e9])[0]
    req = pb.SyncStateRequest(session_id="perfbench", jobs=w.job_states(numbers))
    wire = hashlib.sha256(req.SerializeToString(deterministic=True)).hexdigest()
    assert (h.hexdigest(), wire) == PINS[(config, seed)]
