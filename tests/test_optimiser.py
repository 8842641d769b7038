"""Optimiser tests: targeted preemption places stuck jobs.

Modeled on the reference's optimiser tests (internal/scheduler/scheduling/
optimiser/node_scheduler_test.go): victims picked in ideal order (away
guests, then most-over-fair-share queues, newest first), size caps honored,
cheapest node chosen.
"""

import pytest

from armada_tpu.core.config import PoolConfig, PriorityClass, SchedulingConfig
from armada_tpu.core.types import JobSpec, NodeSpec, RunningJob
from armada_tpu.scheduler.optimiser import Optimiser, OptimiserConfig

CFG = SchedulingConfig(shape_bucket=32)
F = CFG.resource_list_factory()


def node(nid, cpu="8"):
    return NodeSpec(
        id=nid, pool="default", total_resources=F.from_mapping({"cpu": cpu, "memory": "32"})
    )


def spec(jid, queue="q", cpu="4", pc="armada-preemptible", submit=0.0):
    return JobSpec(
        id=jid,
        queue=queue,
        priority_class=pc,
        submit_time=submit,
        resources=F.from_mapping({"cpu": cpu, "memory": "2"}),
    )


def running(jid, nid, queue="hog", cpu="4", submit=0.0, away=False):
    return RunningJob(job=spec(jid, queue=queue, cpu=cpu, submit=submit), node_id=nid, away=away)


def opt(**kw):
    return Optimiser(CFG, OptimiserConfig(enabled=True, **kw))


def test_disabled_returns_nothing():
    o = Optimiser(CFG, OptimiserConfig(enabled=False))
    assert o.optimise([spec("s")], [node("n0")], [], {}, {}) == []


def test_preempts_over_share_victims_newest_first():
    runs = [
        running("old", "n0", submit=1.0),
        running("new", "n0", submit=9.0),
    ]
    decisions = opt().optimise(
        [spec("stuck", queue="starved")],
        [node("n0")],
        runs,
        actual_share={"hog": 0.9, "starved": 0.0},
        fair_share={"hog": 0.5, "starved": 0.5},
    )
    (d,) = decisions
    assert d.job_id == "stuck" and d.node_id == "n0"
    # only one 4cpu victim needed; the NEWEST goes first
    assert d.preempted_job_ids == ["new"]


def test_away_guests_evicted_before_home_jobs():
    runs = [
        running("home-job", "n0", submit=9.0),
        running("guest", "n0", submit=1.0, away=True),
    ]
    (d,) = opt().optimise(
        [spec("stuck", queue="starved")],
        [node("n0")],
        runs,
        actual_share={"hog": 0.9},
        fair_share={"hog": 0.5},
    )
    assert d.preempted_job_ids == ["guest"]


def test_size_cap_protects_large_victims():
    runs = [running("big", "n0", cpu="8")]
    decisions = opt(maximum_job_size_to_preempt={"cpu": "4", "memory": "64"}).optimise(
        [spec("stuck", cpu="8")],
        [node("n0")],
        runs,
        actual_share={"hog": 1.0},
        fair_share={"hog": 0.5},
    )
    assert decisions == []  # the only victim is oversized


def test_non_preemptible_home_jobs_are_safe():
    runs = [running("prod", "n0", cpu="8")]
    runs = [RunningJob(job=spec("prod", queue="hog", cpu="8", pc="armada-default"), node_id="n0")]
    assert (
        opt().optimise(
            [spec("stuck", cpu="8")],
            [node("n0")],
            runs,
            actual_share={"hog": 1.0},
            fair_share={"hog": 0.5},
        )
        == []
    )


def test_cheapest_node_wins():
    # n0 needs 2 preemptions (all 2cpu victims), n1 needs 1 (4cpu victim)
    runs = [
        running("a1", "n0", cpu="2", submit=1),
        running("a2", "n0", cpu="2", submit=2),
        running("a3", "n0", cpu="2", submit=3),
        running("a4", "n0", cpu="2", submit=4),
        running("b1", "n1", cpu="4", submit=5),
        running("b2", "n1", cpu="4", submit=6),
    ]
    (d,) = opt().optimise(
        [spec("stuck", cpu="4")],
        [node("n0"), node("n1")],
        runs,
        actual_share={"hog": 1.0},
        fair_share={"hog": 0.3},
    )
    assert d.node_id == "n1" and len(d.preempted_job_ids) == 1


def _starved_big_job(tmp_path, **config):
    """Eight small hog jobs fill the cluster; then a big job arrives for
    another queue that normal rounds can't place (same priority, fair-share
    eviction disabled).  Two more steps; returns (plane, the big job's id,
    the job states)."""
    from armada_tpu.server import JobSubmitItem, QueueRecord
    from tests.control_plane import ControlPlane

    cfg = SchedulingConfig(
        shape_bucket=32,
        protected_fraction_of_fair_share=100.0,  # normal eviction off
        default_priority_class="armada-preemptible",
        **config,
    )
    cp = ControlPlane.build(tmp_path, config=cfg, runtime_s=600.0)
    cp.server.create_queue(QueueRecord("hog"))
    cp.server.create_queue(QueueRecord("starved"))
    cp.server.submit_jobs(
        "hog", "fill", [JobSubmitItem(resources={"cpu": "2", "memory": "2"}) for _ in range(8)]
    )
    for ex in cp.executors:
        ex.run_once()
    cp.step()
    assert sum(1 for s in cp.job_states().values() if s == "leased") == 8

    (big,) = cp.server.submit_jobs(
        "starved", "big", [JobSubmitItem(resources={"cpu": "8", "memory": "8"})]
    )
    cp.step()
    cp.step()
    return cp, big, cp.job_states()


def test_end_to_end_optimiser_unsticks_job(tmp_path):
    """The optimiser preempts over-share victims for the stuck big job."""
    cp, big, states = _starved_big_job(tmp_path, optimiser_enabled=True)
    assert states[big] == "leased", states
    # exactly one node's worth of hogs (4 x 2cpu) was preempted
    assert sum(1 for s in states.values() if s == "failed") == 4
    cp.close()


@pytest.mark.parametrize("incremental", [False, True], ids=["legacy", "incremental"])
def test_optimiser_reads_the_cycles_own_leases_where_there_is_no_feed(tmp_path, incremental):
    """The optimiser takes the running set from the feed where there is one,
    else from the round's inputs and the cycle's own decisions, whose views
    are then built once a cycle (an `away_prepare` span), and never where
    the feed serves.  Both paths unstick the big job with the same four
    preemptions."""
    from armada_tpu.ops.trace import recorder, reset_recorder
    from tests.test_trace import _find

    reset_recorder()
    cp, big, states = _starved_big_job(
        tmp_path, optimiser_enabled=True, incremental_problem_build=incremental
    )
    assert states[big] == "leased", states
    assert sum(1 for s in states.values() if s == "failed") == 4
    built = [len(_find(t.root, "away_prepare")) for t in recorder().last()]
    assert built and set(built) == ({0} if incremental else {1})
    reset_recorder()
    cp.close()


def test_optimiser_off_leaves_job_stuck(tmp_path):
    cp, big, states = _starved_big_job(tmp_path)
    assert states[big] == "queued"
    cp.close()

def test_banned_node_never_hosts_the_retry():
    """Retry anti-affinity reaches the optimiser: a stuck retry is not placed
    back on the node its attempt died on (scheduler.go:522-568)."""
    runs = [running("victim", "n0", submit=9.0)]
    # Without bans the optimiser would preempt on n0.
    (d,) = opt().optimise(
        [spec("stuck", queue="starved")],
        [node("n0")],
        runs,
        actual_share={"hog": 0.9},
        fair_share={"hog": 0.5},
    )
    assert d.node_id == "n0"
    # With the ban, n0 is off-limits and nothing places.
    assert (
        opt().optimise(
            [spec("stuck", queue="starved")],
            [node("n0")],
            runs,
            actual_share={"hog": 0.9},
            fair_share={"hog": 0.5},
            banned_nodes={"stuck": ("n0",)},
        )
        == []
    )
    # A second (banned-free) node wins instead, preferring no-preemption fit.
    (d2,) = opt().optimise(
        [spec("stuck", queue="starved")],
        [node("n0"), node("n1")],
        runs,
        actual_share={"hog": 0.9},
        fair_share={"hog": 0.5},
        banned_nodes={"stuck": ("n0",)},
    )
    assert d2.node_id == "n1" and d2.preempted_job_ids == []
