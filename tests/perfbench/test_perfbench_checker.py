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
