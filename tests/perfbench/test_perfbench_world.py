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


# ---- gangs, a third resource, node types (`job_kinds`, `resources`, `node_types`) ----

from perfbench_tiny import GPU, GPU_TAINT, gang_world  # noqa: E402

from perfbench.harness.cell import CellError  # noqa: E402
from perfbench.harness.world import admits, gangs_for  # noqa: E402


def _gang_sizes(nodes=120, queued=1800, **kw):
    sizes = dict(SIZES, nodes=nodes, queued_jobs=queued, running_jobs=60, queues=8, **gang_world(nodes, **kw))
    return {k: v for k, v in sizes.items() if v is not None}


def _batch_histogram(w, numbers):
    """What a seed may not change in a batch: jobs a shape, jobs a queue among
    the single jobs and among each cardinality's gangs, gangs a size."""
    lo, hi = numbers.start, numbers.stop
    gang = w.job_gang[lo:hi]
    sizes = np.where(gang >= 0, w.gang_size[np.maximum(gang, 0)], 1)
    return (
        np.bincount(w.job_shape[lo:hi], minlength=len(w.shapes)).tolist(),
        {int(c): np.bincount(w.job_queue[lo:hi][sizes == c], minlength=8).tolist() for c in np.unique(sizes)},
    )


@pytest.mark.parametrize("seeds", [(1, 2), (7, 3_000_000_019)])
def test_gang_world_two_seeds_same_histograms_other_order(seeds):
    a, b = (World(_gang_sizes(), s) for s in seeds)
    assert a.histograms() == b.histograms()
    h = a.histograms()
    assert h["node_kind"] == {"cpu": 96, "a100": 24} and h["jobs_by_kind"] == {"grid": 900, "gang-a100": 900}
    assert h["gangs_by_size"] == {2: 90, 3: 90, 4: 90}  # and 90 of cardinality 1, which are single jobs
    assert (a.job_queue != b.job_queue).any() and (a.node_kind != b.node_kind).any()
    # every batch of one size: the same jobs a shape, the same gangs of every size, the same queues' share
    batches = [w.extend_batches(60, [5.0, 6.0]) for w in (a, b)]
    first = _batch_histogram(a, batches[0][0])
    assert all(_batch_histogram(w, r) == first for w, rs in zip((a, b), batches) for r in rs)
    assert a.unit_counts(60).sum() == 30 + 3 + 9 and (a.unit_counts(60) == b.unit_counts(60)).all()
    gangs = a.job_gang[batches[0][0].start:batches[0][0].stop]
    assert sorted(np.bincount(gangs[gangs >= 0] - gangs[gangs >= 0].min()).tolist()) == [2] * 3 + [3] * 3 + [4] * 3


@pytest.mark.parametrize("seed", [5, 3_800_000_003])
def test_a_gangs_members_are_consecutive_in_one_queue_at_one_time(seed):
    w = World(_gang_sizes(uniformity_label="zone"), seed)
    w.extend_batches(60, [1.0, 2.0])
    assert len(w.gang_size) == 270 + 18 and (np.diff(w.gang_start) > 0).all()
    for g in range(len(w.gang_size)):
        members = w.members(int(w.gang_start[g]))
        assert list(members) == list(range(int(w.gang_start[g]), int(w.gang_start[g]) + int(w.gang_size[g])))
        for col in (w.job_queue, w.job_shape, w.job_submit, w.job_gang):
            assert len(set(col[members.start:members.stop].tolist())) == 1
    single = int(np.flatnonzero(w.job_gang < 0)[0])
    assert list(w.members(single)) == [single]
    # no SyncState splits a gang, whatever the chunk
    for chunk in (7, 100, 1000):
        ranges = list(w.chunks(w.num_jobs, chunk))
        assert [r.start for r in ranges[1:]] == [r.stop for r in ranges[:-1]] and ranges[-1].stop == w.num_jobs
        assert all(w.job_gang[r.stop] != w.job_gang[r.stop - 1] or w.job_gang[r.stop] < 0 for r in ranges[:-1])
    plain = World(SIZES, seed)  # a world without gangs is cut where it always was
    assert [(r.start, r.stop) for r in plain.chunks(5003, 1000)] == [(lo, min(lo + 1000, 5003)) for lo in range(0, 5003, 1000)]


def test_the_third_resource_and_the_node_types_reach_every_table_and_the_wire():
    w = World(_gang_sizes(uniformity_label="zone"), 5)
    assert w.resources == ("cpu", "memory", GPU)
    assert w.node_total.shape == (120, 3) and w.shape_req.shape == (len(w.shapes), 3) and w.run_shape_req.shape[1] == 3
    gpu_nodes = w.node_kind == 1
    assert (w.node_total[gpu_nodes] == [16000, 64000, 8000]).all() and (w.node_total[~gpu_nodes] == [32000, 128000, 0]).all()
    assert (w.node_cores[gpu_nodes] == 16).all()
    kind = [s for s, name in enumerate(w.shape_kind) if name == "gang-a100"]
    assert len(kind) == 2 and (w.shape_req[kind] == [2000, 8000, 1000]).all()
    assert [w.shapes[s][2] for s in kind] == [True, False]  # one spec template a (kind, class)
    # who may sit where: the grid and the initial runs on the untainted nodes, the kind on the labelled ones
    assert w.shape_admits[kind].tolist() == [[False, True]] * 2 and w.shape_admits[:24].tolist() == [[True, False]] * 24
    assert w.run_shape_admits.tolist() == [[True, False]] * len(w.run_shapes)
    assert not gpu_nodes[w.run_node].any() and w.histograms()["runs_per_node_max"] == 1
    # the wire: a member's spec and a GPU node
    member = int(w.gang_start[0])
    m = w.job_states([member, member + 1, int(np.flatnonzero(w.job_gang < 0)[0])])
    assert m[0].spec.gang_id == m[1].spec.gang_id == "g00000000" and m[0].spec.gang_cardinality == w.gang_size[0]
    assert m[0].spec.gang_node_uniformity_label == "zone" and dict(m[0].spec.node_selector) == {"accelerator": "a100"}
    assert dict(m[0].spec.resources.milli) == {"cpu": 2000, "memory": 8000, GPU: 1000}
    assert [(t.key, t.operator, t.value, t.effect) for t in m[0].spec.tolerations] == [(GPU, "Equal", "present", "NoSchedule")]
    assert m[2].spec.gang_id == "" and m[2].spec.gang_cardinality == 0 and not m[2].spec.node_selector
    nodes = [n for snap in w.executor_snapshots(1) for n in snap.nodes]
    assert [n.id for n in nodes] == w.node_ids
    for n, is_gpu in zip(nodes, gpu_nodes):
        assert (dict(n.labels), [(t.key, t.value, t.effect) for t in n.taints], n.resources.milli.get(GPU, 0)) == (
            ({"accelerator": "a100", "zone": "z1"}, [(GPU, "present", "NoSchedule")], 8000) if is_gpu else ({}, [], 0)
        )


@pytest.mark.parametrize(
    "selector,tolerations,labels,taints,ok",
    [
        ({}, [], {}, [], True),
        ({"a": "1"}, [], {"a": "1", "b": "2"}, [], True),
        ({"a": "1"}, [], {"a": "2"}, [], False),
        ({"a": "1"}, [], {}, [], False),
        ({}, [], {}, [GPU_TAINT], False),
        ({}, [dict(GPU_TAINT, operator="Equal")], {}, [GPU_TAINT], True),
        ({}, [{"key": GPU, "operator": "Equal", "value": "absent"}], {}, [GPU_TAINT], False),
        ({}, [{"key": GPU, "operator": "Exists"}], {}, [GPU_TAINT], True),
        ({}, [{"operator": "Exists"}], {}, [GPU_TAINT, {"key": "x", "effect": "NoExecute"}], True),
        ({}, [{"key": GPU, "operator": "Exists", "effect": "NoExecute"}], {}, [GPU_TAINT], False),
        ({}, [], {}, [{"key": "x", "value": "y", "effect": "PreferNoSchedule"}], True),
        ({}, [dict(GPU_TAINT, operator="Equal")], {}, [GPU_TAINT, {"key": "x", "value": "y"}], False),
    ],
)
def test_admits(selector, tolerations, labels, taints, ok):
    assert admits(selector, tolerations, labels, taints) is ok


@pytest.mark.parametrize(
    "members,sizes,weights,want",
    [(48, [8], [1], [6]), (20, [1, 2, 3, 4], [1, 1, 1, 1], [2, 2, 2, 2]), (0, [8], [1], [0]), (7, [2, 4], [3, 1], None),
     (50, [8], [1], None), (14, [2, 4], [3, 1], [5, 1])],
)
def test_gangs_for(members, sizes, weights, want):
    counts, nearest = gangs_for(members, sizes, weights)
    if want is None:
        assert counts is None and nearest[0] < members and (nearest[1] is None or nearest[1] > members)
    else:
        assert counts.tolist() == want and nearest is None


@pytest.mark.parametrize(
    "change,says",
    [
        (dict(queued_jobs=1802, job_kinds=[dict(gang_world()["job_kinds"][0], gang={"cardinality": 2})]), "900 or 902 members would"),
        (dict(job_kinds=[dict(gang_world()["job_kinds"][0], gang={"cardinality": 8}, share=0.05)]), "88 or 96 members would"),
        (dict(job_kinds=[dict(gang_world()["job_kinds"][0], resources={"amd.com/gpu": 1})]), "is not among the configuration's `resources`"),
        (dict(job_kinds=[dict(gang_world()["job_kinds"][0], node_selector={"accelerator": "h100"})]), "no node type satisfies its node_selector"),
        (dict(job_kinds=[dict(gang_world()["job_kinds"][0], tolerations=[])]), "no node type satisfies"),
        (dict(job_kinds=[dict(gang_world()["job_kinds"][0], share=1.5)]), "shares add up to at most 1"),
        (dict(job_kinds=[dict(gang_world()["job_kinds"][0], gang={"cardinality": {"0": 1}})]), "whole number from 1"),
        (dict(node_cores=[16], memory_per_core=4), "states its nodes once"),
        (dict(node_types=[dict(gang_world()["node_types"][0], count=5)] + gang_world()["node_types"][1:]), "add up to 29"),
        (dict(node_types=[{"name": "a", "share": 1, "cores": 8, "memory": 8}, {"name": "b", "count": 3, "cores": 8, "memory": 8}]), "every entry a `count`"),
        (dict(resources=["memory", "cpu"]), "cpu and memory come first"),
        (dict(queue_demand="uniform"), "\"1/k\" is the one demand"),
        (dict(running_jobs=None, running_fill=1.0), "not node_types yet"),
    ],
    ids=["an-odd-member", "no-whole-gangs", "unlisted-resource", "selector-nobody-satisfies", "taint-not-tolerated", "shares-over-1",
         "cardinality-0", "nodes-twice", "counts-do-not-add-up", "count-and-share", "cpu-first", "queue-demand", "fill-with-node-types"],
)
def test_what_the_new_keys_cannot_state_is_refused_and_says_why(change, says):
    sizes = {k: v for k, v in dict(_gang_sizes(), **change).items() if v is not None}
    with pytest.raises(CellError, match=says.replace("(", r"\(")):
        World(sizes, 1)


def test_queue_demand_is_what_it_draws():
    """`queue_demand` "1/k" is what the generator draws; any other word was
    drawn as 1/k too until PR 38 and is refused now; absent means "1/k"."""
    assert (World({k: v for k, v in SIZES.items() if k != "queue_demand"}, 5).job_queue == World(SIZES, 5).job_queue).all()
    with pytest.raises(CellError, match="1/k"):
        World(dict(SIZES, queue_demand="zipf"), 5)


def test_node_types_by_share_and_labels_without_taints():
    """Shares give exact counts; a label that no job selects and no taint
    leaves the running set spread over every node, as it is without the label."""
    types = [{"name": "small", "share": 0.75, "cores": 16, "memory": 64, "labels": {"zone": "a"}},
             {"name": "big", "share": 0.25, "cores": 64, "memory": 256, "labels": {"zone": "b"}}]
    sizes = {k: v for k, v in SIZES.items() if k not in ("node_cores", "memory_per_core")}
    w = World(dict(sizes, node_types=types), 5)
    assert w.histograms()["node_kind"] == {"small": 302, "big": 101} and w.shape_admits.all()
    bare = World(dict(sizes, node_types=[{k: v for k, v in t.items() if k != "labels"} for t in types]), 5)
    assert (bare.node_cores == w.node_cores).all() and (bare.run_node == w.run_node).all()
    assert (bare.job_queue == w.job_queue).all() and (bare.node_total == w.node_total).all()
    assert len(set(w.run_node.tolist())) == 207 and (np.bincount(w.node_cores)[[16, 64]] == [302, 101]).all()
