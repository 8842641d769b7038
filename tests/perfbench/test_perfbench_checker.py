"""The plain checker accepts an honest replay and rejects doctored answers."""

import pytest

from perfbench_tiny import load

from perfbench.harness.checker import Checker
from perfbench.harness.world import World

SIZES = dict(
    load("perfbench", "configs", "cluster-100k-5k.json")["world"],
    nodes=50, queued_jobs=400, running_jobs=20, queues=4,
)


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
    c = Checker(world, cap=cap, queue_cap=cap)
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
    c = Checker(w, cap=10, queue_cap=2)
    for n, r in enumerate(honest(w)):
        c.cycle(n, r)
    assert any("per-queue cap" in v for v in c.violations)
