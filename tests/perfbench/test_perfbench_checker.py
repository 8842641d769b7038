"""The plain checker accepts an honest replay and rejects doctored answers."""

import numpy as np
import pytest

from perfbench_tiny import load

from perfbench.harness.checker import Checker
from perfbench.harness.world import World

SIZES = dict(
    load("perfbench", "configs", "cluster-100k-5k.json")["world"],
    nodes=50, queued_jobs=400, running_jobs=20, queues=4,
)
CLASSES = load("perfbench", "configs", "cluster-100k-5k.json")["scheduling"]["priorityClasses"]


def honest(world, cap=10, cycles=4):
    """Records of a scheduler that leases the first `cap` queued jobs to the
    emptiest nodes each cycle; the client completes them two cycles later."""
    out, nxt, running = [], 0, 20
    queued = 400
    for k in range(cycles):
        submitted = list(world.extend(cap, float(k)))
        completed = list(range((k - 2) * cap, (k - 1) * cap)) if k >= 2 else []
        queued += len(submitted)
        running -= len(completed)
        leases = [
            (world.job_id(i), world.node_ids[(7 * i) % 50], world.queue_names[world.job_queue[i]])
            for i in range(nxt, nxt + cap)
        ]
        out.append(
            dict(submitted=submitted, completed=completed, leases=leases, preempted=[],
                 num_queued=queued, num_running=running)
        )
        nxt += cap
        queued -= cap
        running += cap
    return out


def run(world, records, cap=10):
    c = Checker(world, cap=cap, queue_cap=cap, priority_classes=CLASSES)
    for n, r in enumerate(records):
        c.cycle(n, r)
    return c


def test_honest_replay_passes():
    w = World(SIZES, 3)
    assert run(w, honest(w)).violations == []


def _double_lease(w, recs):
    recs[2]["leases"][0] = recs[1]["leases"][0]


def _unknown_node(w, recs):
    j, _, q = recs[1]["leases"][3]
    recs[1]["leases"][3] = (j, "n999999", q)


def _unknown_job(w, recs):
    _, n, q = recs[1]["leases"][3]
    recs[1]["leases"][3] = ("j123456789", n, q)


def _cap_exceeded(w, recs):
    extra = [(w.job_id(i), w.node_ids[i % 50], w.queue_names[w.job_queue[i]]) for i in range(300, 305)]
    recs[3]["leases"] += extra


def _over_capacity(w, recs):
    node = w.node_ids[0]
    for r in recs:
        r["leases"] = [(j, node, q) for j, _, q in r["leases"]]


def _not_yet_submitted(w, recs):
    _, n, q = recs[0]["leases"][0]
    recs[0]["leases"][0] = (w.job_id(400 + 35), n, w.queue_names[w.job_queue[435]])


def _lost_lease(w, recs):
    recs[2]["num_running"] -= 1  # the mirror forgot a lease it returned


def _wrong_queue(w, recs):
    j, n, q = recs[1]["leases"][0]
    recs[1]["leases"][0] = (j, n, next(x for x in w.queue_names if x != q))


@pytest.mark.parametrize(
    "doctor,expect",
    [
        (_double_lease, "leased twice"),
        (_unknown_node, "unknown node"),
        (_unknown_job, "unknown job"),
        (_cap_exceeded, "per-round cap"),
        (_over_capacity, "holds"),
        (_not_yet_submitted, "before it was submitted"),
        (_lost_lease, "scheduler counts"),
        (_wrong_queue, "reported in queue"),
    ],
)
def test_doctored_response_is_rejected(doctor, expect):
    w = World(SIZES, 3)
    records = honest(w)
    doctor(w, records)
    c = run(w, records)
    assert any(expect in v for v in c.violations), c.violations
    assert c.bad_cycles


def test_per_queue_cap():
    w = World(SIZES, 3)
    c = Checker(w, cap=10, queue_cap=2, priority_classes=CLASSES)
    for n, r in enumerate(honest(w)):
        c.cycle(n, r)
    assert any("per-queue cap" in v for v in c.violations)


# ---- preemption: invariants 6-8 and the corrected count arithmetic ----


def preempting(world, cap=10, cycles=5):
    """`honest`, with a round that preempts: cycle 1 ends the run of a `batch`
    job leased in cycle 0 and of a `batch` job of the initial running set.  The
    client then never completes the first, and the scheduler's running count
    is one lower from cycle 2 on (two runs ended, one completion fewer)."""
    recs = honest(world, cap, cycles)
    victim = next(i for i in range(cap) if world.shapes[world.job_shape[i]][2])
    initial = next(r for r, s in enumerate(world.run_shape) if world.run_shapes[s][2])
    recs[1]["preempted"] = [world.job_id(victim), f"r{initial:08d}"]
    recs[2]["completed"].remove(victim)
    for r in recs[2:]:
        r["num_running"] -= 1
    return recs, victim, initial


def check(world, records, cap=10):
    c = Checker(world, cap=cap, queue_cap=cap, priority_classes=CLASSES)
    for n, r in enumerate(records):
        c.cycle(n, r)
    return c


def test_honest_preemption_passes_and_frees_the_right_nodes():
    w = World(SIZES, 3)
    recs, victim, initial = preempting(w)
    before = Checker(w, cap=10, queue_cap=10, priority_classes=CLASSES).used.copy()
    c = check(w, recs[:2])
    assert c.violations == []
    # the initial run's room is back on ITS node, the leased job's on the node it was leased to
    node = w.run_node[initial]
    leased_to = w.node_index[recs[0]["leases"][victim][1]]
    expect = before.copy()
    for k in (0, 1):
        for job_id, node_id, _ in recs[k]["leases"]:
            expect[w.node_index[node_id]] += w.shape_req[w.job_shape[w.job_number(job_id)]]
    expect[node] -= w.run_shape_req[w.run_shape[initial]]
    expect[leased_to] -= w.shape_req[w.job_shape[victim]]
    assert (c.used == expect).all()
    assert not c.run_live[initial] and c.run_live.sum() == len(w.run_shape) - 1
    assert check(w, recs).violations == []  # counts: queued by submits - leases, running less the preempted


def _then_completed(w, recs, victim, initial):
    recs[2]["completed"].append(victim)


def _then_leased(w, recs, victim, initial):
    recs[3]["leases"][0] = recs[0]["leases"][victim]


def _prod_preempted(w, recs, victim, initial):
    prod = next(i for i in range(10) if not w.shapes[w.job_shape[i]][2])
    recs[1]["preempted"][0] = w.job_id(prod)


def _prod_initial_preempted(w, recs, victim, initial):
    prod = next(r for r, s in enumerate(w.run_shape) if not w.run_shapes[s][2])
    recs[1]["preempted"][1] = f"r{prod:08d}"


def _leased_and_preempted(w, recs, victim, initial):
    recs[1]["preempted"].append(recs[1]["leases"][0][0])


def _preempted_twice(w, recs, victim, initial):
    recs[2]["preempted"] = [w.job_id(victim)]


def _initial_preempted_twice(w, recs, victim, initial):
    recs[2]["preempted"] = [f"r{initial:08d}"]


def _never_leased(w, recs, victim, initial):
    recs[1]["preempted"][0] = w.job_id(399)  # queued, holds no lease


def _unknown_initial(w, recs, victim, initial):
    recs[1]["preempted"][1] = "r00000020"  # the running set has 20: r00000000..19


def _requeued(w, recs, victim, initial):
    for r in recs[2:]:
        r["num_queued"] += 1  # the accepted checker's arithmetic: a preempted job back in the backlog


def _still_running(w, recs, victim, initial):
    for r in recs[2:]:
        r["num_running"] += 2  # the mirror kept the runs the round says it ended


@pytest.mark.parametrize(
    "doctor,expect",
    [
        (_then_completed, "that was preempted"),
        (_then_leased, "leased after it was preempted"),
        (_prod_preempted, "not preemptible"),
        (_prod_initial_preempted, "not preemptible"),
        (_leased_and_preempted, "leased and preempted in one round"),
        (_preempted_twice, "twice"),
        (_initial_preempted_twice, "twice"),
        (_never_leased, "holds no lease"),
        (_unknown_initial, "unknown job"),
        (_requeued, "scheduler counts"),
        (_still_running, "scheduler counts"),
    ],
)
def test_doctored_preemption_is_rejected(doctor, expect):
    w = World(SIZES, 3)
    recs, victim, initial = preempting(w)
    doctor(w, recs, victim, initial)
    c = check(w, recs)
    assert any(expect in v for v in c.violations), c.violations
    assert c.bad_cycles


def test_capacity_is_checked_where_a_preemption_freed_room():
    """A node filled to the brim by its initial runs: a lease there passes only
    in the round that preempts enough of them, and the node is looked at even
    in a round that only preempts on it."""
    sizes = {k: v for k, v in SIZES.items() if k != "running_jobs"}
    sizes.update(running_fill=1.0, running_cpu_milli=[8000], running_memory=32)
    w = World(sizes, 3)
    victim = next(r for r, s in enumerate(w.run_shape) if w.run_shapes[s][2])
    node = w.node_ids[w.run_node[victim]]
    job = next(i for i in range(400) if w.shapes[w.job_shape[i]][0] == 4000)
    lease = (w.job_id(job), node, w.queue_names[w.job_queue[job]])
    rec = dict(submitted=[], completed=[], leases=[lease], preempted=[], num_queued=400, num_running=len(w.run_shape))
    assert any("holds" in v for v in check(w, [rec]).violations)
    assert check(w, [dict(rec, preempted=[f"r{victim:08d}"])]).violations == []
    # a preemption alone touches the node: an overfull node shows in that round
    c = Checker(w, cap=10, queue_cap=10, priority_classes=CLASSES)
    c.used[w.run_node[victim]] += 2 * w.run_shape_req[w.run_shape[victim]]
    c.cycle(0, dict(rec, leases=[], preempted=[f"r{victim:08d}"]))
    assert any("holds" in v for v in c.violations)


def test_the_configuration_decides_which_class_is_preemptible():
    w = World(SIZES, 3)
    recs, victim, initial = preempting(w)
    none = {name: dict(pc, preemptible=False) for name, pc in CLASSES.items()}
    c = Checker(w, cap=10, queue_cap=10, priority_classes=none)
    for n, r in enumerate(recs):
        c.cycle(n, r)
    assert sum("not preemptible" in v for v in c.violations) == 2


# ---- invariant 9: a round that gives up has nothing left to place ----

FULL_SIZES = {k: v for k, v in SIZES.items() if k != "running_jobs"}
FULL_SIZES.update(running_fill=1.0, running_cpu_milli=[8000], running_memory=32, preemptible_share=1.0)


def giving_up(world):
    """Two rounds on a fleet that its initial runs fill exactly.  Round 0
    preempts one preemptible initial run (8 cores, 32 of memory freed on its
    node) and leases two 4-core jobs there: the node is full again.  Before
    round 1 the client reports one of the two finished; round 1 leases one
    4-core job into that room, preempts nothing, stays under its cap of 10 and
    says `exhausted`: every node is full, so nothing is left that fits."""
    victim = next(r for r, s in enumerate(world.run_shape) if world.run_shapes[s][2])
    node = world.node_ids[world.run_node[victim]]
    four = [i for i in range(400) if world.shapes[world.job_shape[i]][:2] == (4000, 9)][:3]
    lease = lambda i: (world.job_id(i), node, world.queue_names[world.job_queue[i]])  # noqa: E731
    n_runs = len(world.run_shape)
    return [
        dict(submitted=[], completed=[], leases=[lease(four[0]), lease(four[1])],
             preempted=[f"r{victim:08d}"], termination="global_burst", num_queued=400, num_running=n_runs),
        dict(submitted=[], completed=[four[0]], leases=[lease(four[2])], preempted=[],
             termination="exhausted", num_queued=398, num_running=n_runs),
    ], node


def _lease_removed(w, recs):
    recs[1]["leases"] = []  # the round gave up with four cores free on a node


def _gave_up_and_preempted(w, recs):
    _lease_removed(w, recs)
    other = next(
        r for r, s in enumerate(w.run_shape)
        if w.run_shapes[s][2] and f"r{r:08d}" != recs[0]["preempted"][0]
    )
    recs[1]["preempted"] = [f"r{other:08d}"]  # the second pass may free room nothing retries


def _stopped_at_its_cap(w, recs):
    _lease_removed(w, recs)
    recs[1]["termination"] = "global_burst"


def _says_nothing(w, recs):
    _lease_removed(w, recs)
    del recs[1]["termination"]  # a program that reports no termination is not held to it


@pytest.mark.parametrize(
    "doctor,kwargs,reported",
    [
        (None, {}, False),  # the honest record
        (_lease_removed, {}, True),
        (_gave_up_and_preempted, {}, False),
        (_stopped_at_its_cap, {}, False),
        (_says_nothing, {}, False),
        # a round that leased its cap did not give up, whatever it says (round 0 is over a cap of 0 too)
        (_lease_removed, {"cap": 0}, False),
        # no queue holds anything within a lookback of 0
        (_lease_removed, {"lookback": 0}, False),
    ],
)
def test_a_round_that_gives_up_is_held_to_nothing_left_fits(doctor, kwargs, reported):
    w = World(FULL_SIZES, 3)
    recs, node = giving_up(w)
    if doctor:
        doctor(w, recs)
    c = Checker(w, priority_classes=CLASSES, **{"cap": 10, "queue_cap": 10, "lookback": 100_000, **kwargs})
    for n, r in enumerate(recs):
        c.cycle(n, r)
    said = [v for v in c.violations if "while a job still fits" in v]
    assert bool(said) == reported, c.violations
    if reported:
        # it names the round, a shape, the queue that holds it and the node with room
        assert said[0].startswith("cycle 1:") and node in said[0] and "[4000, 23000] free" in said[0]
        assert "500 cpu thousandths" in said[0] and c.bad_cycles == {1}
    assert [v for v in c.violations if v not in said and "per-round cap" not in v] == []


def test_a_round_that_gives_up_needs_the_configurations_lookback():
    """Invariant 9 takes no default of the program's: where the configuration
    states no `maxQueueLookback`, a round that gives up is reported for that,
    honest or not, and a round that does not give up is not."""
    w = World(FULL_SIZES, 3)
    recs, _ = giving_up(w)
    c = check(w, recs)
    assert c.violations == ["cycle 1: the round gave up (exhausted) and the configuration states no "
                            'maxQueueLookback: "nothing left fits" cannot be held']
    assert c.bad_cycles == {1}
    assert check(w, [recs[0], dict(recs[1], termination="global_burst")]).violations == []


def test_only_what_a_queue_holds_within_its_lookback_counts():
    """Room for a small job is a violation only if some queue holds a small
    job among its first `lookback` queued jobs, in the queue's own order, at
    the round's start; and a queue that leased its per-queue cap is out."""
    w = World(FULL_SIZES, 3)
    recs, node = giving_up(w)
    # round 1 fills the four free cores but for 500 thousandths: only the smallest shape still fits
    i_35 = [i for i in range(400) if w.shapes[w.job_shape[i]][0] in (2000, 1000, 500)]
    by_cpu = {cpu: [i for i in i_35 if w.shapes[w.job_shape[i]][:2] == (cpu, mem)] for cpu, mem in ((2000, 5), (1000, 3), (500, 1))}
    take = [by_cpu[2000][0], by_cpu[1000][0], by_cpu[500][0]]  # 3.5 cores, 9 of memory
    recs[1]["leases"] = [(w.job_id(i), node, w.queue_names[w.job_queue[i]]) for i in take]

    def violations(**kwargs):
        c = Checker(w, priority_classes=CLASSES, **{"cap": 10, "queue_cap": 10, "lookback": 100_000, **kwargs})
        for n, r in enumerate(recs):
            c.cycle(n, r)
        return c.violations

    assert any("500 cpu thousandths" in v for v in violations())
    # the queue's order is by submit time: how deep the first half-core job of any queue sits
    depth = []
    for q in range(4):
        mine = np.flatnonzero(w.job_queue[:400] == q)
        mine = mine[np.argsort(w.job_submit[mine], kind="stable")]
        mine = [i for i in mine if w.job_id(i) not in {job_id for job_id, _, _ in recs[0]["leases"]}]
        small = [n for n, i in enumerate(mine) if w.shapes[w.job_shape[i]][0] == 500 and i not in take]
        depth.append(small[0])
    assert min(depth) >= 1  # else the case below shows nothing
    assert violations(lookback=min(depth)) == []  # no queue sees a half-core job that early
    assert any("500 cpu thousandths" in v for v in violations(lookback=min(depth) + 1))


def test_a_queue_at_its_per_queue_cap_holds_nothing_against_the_round():
    """Round 1 leaves half a core free and leased one job from EACH queue:
    under a per-queue cap of 1 no queue may lease more, so nothing is left to
    place; under a cap of 2 the half-core jobs they all hold still fit."""
    w = World(FULL_SIZES, 3)
    recs, node = giving_up(w)
    want = {0: (1000, 3), 1: (1000, 3), 2: (1000, 3), 3: (500, 1)}
    take = [
        next(i for i in range(400) if w.job_queue[i] == q and w.shapes[w.job_shape[i]][:2] == shape)
        for q, shape in want.items()
    ]
    recs[1]["leases"] = [(w.job_id(i), node, w.queue_names[w.job_queue[i]]) for i in take]
    for queue_cap, reported in ((1, False), (2, True)):
        c = Checker(w, cap=10, queue_cap=10, priority_classes=CLASSES, lookback=100_000)
        c.cycle(0, recs[0])
        c.queue_cap = queue_cap  # round 0's two leases may share a queue: not this test's
        c.cycle(1, recs[1])
        assert [v for v in c.violations if "while a job still fits" not in v] == []
        assert bool(c.violations) == reported, c.violations


# ---- gangs, selectors and taints, a third resource: invariants 10-12, and 9 for a gang ----

from perfbench_tiny import gang_world  # noqa: E402

GANG_SIZES = {
    k: v
    for k, v in dict(SIZES, nodes=50, queued_jobs=400, running_jobs=20, queues=4, **gang_world(50, uniformity_label="zone")).items()
    if v is not None
}


def gang_honest(w, cycles=5, units=6):
    """Records of a scheduler that each cycle leases, in job-number order, the
    next `units` single jobs and the next two whole gangs, each job to the
    emptiest node (by jobs so far) of a type that admits it; the client
    completes a round's leases two cycles later.  Cycle 3 preempts, whole, the
    first `batch` gang still running: the client never completes it."""
    out, status, held = [], np.zeros(0, np.int8), np.zeros(len(w.node_ids), np.int64)
    queued, running, leased_in, victim = 400, 20, [], None
    for k in range(cycles):
        submitted = list(w.extend(20, float(k)))
        status = np.concatenate([status, np.zeros(w.num_jobs - len(status), np.int8)])
        completed = [i for i in (leased_in[k - 2] if k >= 2 else []) if status[i] == 1]
        status[completed] = 2
        queued += len(submitted)
        running -= len(completed)
        free = np.flatnonzero(status == 0)
        singles = free[w.job_gang[free] < 0][:units]
        gangs = np.unique(w.job_gang[free[w.job_gang[free] >= 0]])[:2]
        picked = list(singles) + [i for g in gangs for i in w.members(int(w.gang_start[g]))]
        leases = []
        for i in picked:
            nodes = np.flatnonzero(w.shape_admits[w.job_shape[i]][w.node_kind])
            node = nodes[np.argmin(held[nodes])]
            held[node] += 1
            leases.append((w.job_id(int(i)), w.node_ids[node], w.queue_names[w.job_queue[i]]))
        status[picked] = 1
        leased_in.append([int(i) for i in picked])
        preempted = []
        if k == 3:
            earlier = np.unique(w.job_gang[[i for r in leased_in[1:3] for i in r if w.job_gang[i] >= 0 and status[i] == 1]])
            victim = next(int(g) for g in earlier if w.shapes[w.job_shape[w.gang_start[g]]][2])
            preempted = [w.job_id(i) for i in w.members(int(w.gang_start[victim]))]
            status[list(w.members(int(w.gang_start[victim])))] = 3
        out.append(dict(submitted=submitted, completed=completed, leases=leases, preempted=preempted,
                        num_queued=queued, num_running=running))
        queued -= len(picked)
        running += len(picked) - len(preempted)
    return out, victim


def gang_check(w, records, cap=40, queue_cap=40, lookback=None):
    c = Checker(w, cap=cap, queue_cap=queue_cap, priority_classes=CLASSES, lookback=lookback)
    for n, r in enumerate(records):
        c.cycle(n, r)
    return c


def test_an_honest_gang_replay_passes_in_every_resource():
    w = World(GANG_SIZES, 3)
    recs, victim = gang_honest(w)
    c = gang_check(w, recs)
    assert c.violations == [] and c.used.shape == (50, 3)
    assert recs[3]["preempted"] and all(len(r["leases"]) > 6 for r in recs)
    # the preempted gang's GPUs are free again, the live members' are held
    live = [i for i, node in c.node_of.items() if w.shape_req[w.job_shape[i], 2]]
    assert c.used[:, 2].sum() == 1000 * len(live) and not set(w.members(int(w.gang_start[victim]))) & set(live)


def _member(w, recs, k, n=-1):
    """(place in round k's leases, lease) of the n-th gang member there."""
    at = [p for p, (job_id, _, _) in enumerate(recs[k]["leases"]) if w.job_gang[w.job_number(job_id)] >= 0][n]
    return at, recs[k]["leases"][at]


def _member_lease_dropped(w, recs, victim):
    del recs[4]["leases"][_member(w, recs, 4)[0]]


def _member_leased_a_round_later(w, recs, victim):
    at, lease = _member(w, recs, 3)
    del recs[3]["leases"][at]
    recs[4]["leases"].append(lease)
    recs[4]["num_queued"] += 1
    recs[4]["num_running"] -= 1


def _member_off_the_label(w, recs, victim):
    at, (job_id, _, queue) = _member(w, recs, 4)
    recs[4]["leases"][at] = (job_id, w.node_ids[int(np.flatnonzero(w.node_kind == 0)[0])], queue)


def _single_onto_the_taint(w, recs, victim):
    job_id, _, queue = recs[4]["leases"][0]
    assert w.shape_kind[w.job_shape[w.job_number(job_id)]] == "grid"
    recs[4]["leases"][0] = (job_id, w.node_ids[int(np.flatnonzero(w.node_kind == 1)[0])], queue)


def _preempted_member_dropped(w, recs, victim):
    recs[3]["preempted"].pop()
    del recs[4:]


def _one_member_preempted(w, recs, victim):
    recs[3]["preempted"] = recs[3]["preempted"][:1]
    del recs[4:]


def _gpus_over_capacity(w, recs, victim):
    node = w.node_ids[int(np.flatnonzero(w.node_kind == 1)[0])]
    for r in recs:
        r["leases"] = [(j, node if w.shape_req[w.job_shape[w.job_number(j)], 2] else n, q) for j, n, q in r["leases"]]


@pytest.mark.parametrize(
    "doctor,says,others",
    [
        (_member_lease_dropped, "leased in part", 0),
        (_member_leased_a_round_later, "leased in part", 1),  # the round that leases the straggler is reported too
        (_member_off_the_label, "which does not admit it", 0),
        (_single_onto_the_taint, "which does not admit it", 0),
        (_preempted_member_dropped, "preempted in part: 1 of its members", 0),
        (_one_member_preempted, "preempted in part", 0),
        (_gpus_over_capacity, "nvidia.com/gpu)", None),
    ],
)
def test_a_doctored_gang_record_is_reported_by_its_invariant(doctor, says, others):
    w = World(GANG_SIZES, 3)
    recs, victim = gang_honest(w)
    doctor(w, recs, victim)
    c = gang_check(w, recs)
    mine = [v for v in c.violations if says in v]
    assert mine, c.violations
    if others is not None:  # and by no other invariant
        assert len(c.violations) == len(mine) == 1 + others, c.violations


def test_a_gang_across_two_values_of_its_uniformity_label():
    """Invariant 10's second half: every GPU node carries zone z1 in the tiny
    world, so a second zone is a second node type, and a gang with a member in
    each is reported; the same members within one zone are not."""
    sizes = dict(GANG_SIZES)
    a100 = sizes["node_types"][1]
    sizes["node_types"] = [sizes["node_types"][0], dict(a100, count=5),
                           dict(a100, name="a100-z2", count=5, labels=dict(a100["labels"], zone="z2"))]
    w = World(sizes, 3)
    recs, _ = gang_honest(w, cycles=2)
    assert gang_check(w, recs).violations == [] or all("uniformity" in v for v in gang_check(w, recs).violations)
    z = {zone: w.node_ids[int(np.flatnonzero(w.node_kind == k)[0])] for zone, k in (("z1", 1), ("z2", 2))}
    members = [p for p, (j, _, _) in enumerate(recs[1]["leases"]) if w.job_gang[w.job_number(j)] >= 0]
    for p in members:  # every gang of the round within z1
        recs[1]["leases"][p] = (recs[1]["leases"][p][0], z["z1"], recs[1]["leases"][p][2])
    for r in recs[:1]:
        r["leases"] = [(j, z["z2"] if w.job_gang[w.job_number(j)] >= 0 else n, q) for j, n, q in r["leases"]]
    assert gang_check(w, recs).violations == []
    j, _, q = recs[1]["leases"][members[-1]]
    recs[1]["leases"][members[-1]] = (j, z["z2"], q)
    violations = gang_check(w, recs).violations
    assert len(violations) == 1 and "leased across values ['z1', 'z2'] of its uniformity label 'zone'" in violations[0]


def _gave_up(w, cap=40, fill_gpus_to=None, lookback=1000):
    """One round that leased nothing and reports `exhausted`, on a world whose
    GPU nodes hold `fill_gpus_to` members each from an earlier round (through
    honest leases of the backlog's own gangs and singles of the kind)."""
    recs = [dict(submitted=[], completed=[], leases=[], preempted=[], num_queued=400, num_running=20)]
    if fill_gpus_to is not None:
        kind = np.flatnonzero(w.shape_req[w.job_shape[:400], 2] > 0)
        gpu_nodes = np.flatnonzero(w.node_kind == 1)
        take, leases = iter(kind.tolist()), []
        for node in gpu_nodes:
            for _ in range(fill_gpus_to):
                i = next(take)
                leases.append((w.job_id(i), w.node_ids[node], w.queue_names[w.job_queue[i]]))
        recs[0]["leases"] = leases
        recs.append(dict(submitted=[], completed=[], leases=[], preempted=[], num_queued=400 - len(leases),
                         num_running=20 + len(leases)))
    recs[-1]["termination"] = "exhausted"
    return recs


def test_a_round_that_gives_up_while_a_gang_still_fits():
    """Invariant 9 for a gang: its members are of one shape, so it fits where
    the nodes that admit it have room for as many members as it has, within one
    value of its uniformity label.  GPU nodes (10 of them, 8 GPUs each, a
    member asks one) that hold 8 members each have room for none; with 7 each
    they have room for 10 members between them, which a gang of 4 fits."""
    sizes = dict(GANG_SIZES, job_cpu_milli=[64000])  # no grid job fits a 32-core node: only the kind can
    w = World(sizes, 3)
    # (members of whole gangs leased out of the backlog leave their gangs in part there: those are left out)
    full = gang_check(w, _gave_up(w, fill_gpus_to=8), cap=400, queue_cap=400, lookback=1000)
    assert [v for v in full.violations if "gave up" in v] == []
    room = gang_check(w, _gave_up(w, fill_gpus_to=7), cap=400, queue_cap=400, lookback=1000)
    said = [v for v in room.violations if "gave up" in v]
    assert len(said) == 1 and ("a job still fits" in said[0] or "a gang still fits" in said[0])
    # with the kind's single jobs out of the way (cardinality 2-4 only) it is the gang that is named
    sizes["job_kinds"] = [dict(sizes["job_kinds"][0], gang={"cardinality": {"2": 1, "4": 1}, "uniformity_label": "zone"})]
    w = World(dict(sizes, queued_jobs=396), 3)  # 198 members: 33 gangs of 2 and 33 of 4
    recs = _gave_up(w, fill_gpus_to=7)
    for r in recs:
        r["num_queued"] -= 4
    room = gang_check(w, recs, cap=400, queue_cap=400, lookback=1000)
    said = [v for v in room.violations if "gave up" in v]
    assert len(said) == 1 and "a gang still fits" in said[0] and "room for 10 such members" in said[0], room.violations
    # a cap with less room than the gang has members leaves it out (the round's, or its queue's)
    capped = gang_check(w, recs, cap=1, queue_cap=400, lookback=1000)
    assert [v for v in capped.violations if "gave up" in v] == []
    capped = gang_check(w, recs, cap=400, queue_cap=1, lookback=1000)
    assert [v for v in capped.violations if "gave up" in v] == []
