"""Home/away cross-pool scheduling tests.

Modeled on the reference's away-scheduling behavior (scheduling_algo.go
216-283, nodedb.go:450-466): a pool lends leftover capacity to jobs from its
configured away pools at the lowest priority; home jobs evict away guests
whenever they need the capacity back.
"""

import pytest

from armada_tpu.core.config import PoolConfig, SchedulingConfig
from armada_tpu.core.types import NodeSpec
from armada_tpu.server import JobSubmitItem, QueueRecord
from armada_tpu.executor import ExecutorService, FakeClusterContext
from tests.control_plane import ControlPlane

# gpu pool hosts away jobs from the cpu pool
CFG = SchedulingConfig(
    shape_bucket=32,
    pools=(
        PoolConfig("cpu", away_pools=("gpu",)),
        PoolConfig("gpu"),
    ),
)


@pytest.fixture(autouse=True, params=[False, True], ids=["legacy", "incremental"])
def _problem_build_mode(request, monkeypatch):
    """Every away scenario runs on both problem-build paths: the away pass
    exercises the incremental feed's pool_restricted index + running_of
    reconstruction (scheduler/incremental_algo.py)."""
    import dataclasses

    import tests.test_home_away as m

    monkeypatch.setattr(
        m, "CFG", dataclasses.replace(CFG, incremental_problem_build=request.param)
    )


def build_plane(tmp_path, cpu_nodes=1, gpu_nodes=2):
    cp = ControlPlane.build(tmp_path, config=CFG, executor_specs={})
    factory = CFG.resource_list_factory()
    for pool, ex_id, n in (("cpu", "ex-cpu", cpu_nodes), ("gpu", "ex-gpu", gpu_nodes)):
        if n == 0:
            continue
        nodes = [
            NodeSpec(
                id=f"{ex_id}-n{i}",
                pool=pool,
                executor=ex_id,
                total_resources=factory.from_mapping({"cpu": "8", "memory": "32"}),
            )
            for i in range(n)
        ]
        cluster = FakeClusterContext(nodes, factory, runtime_of=lambda s: 5.0)
        cp.executors.append(
            ExecutorService(ex_id, pool, cluster, cp.executor_api, factory, clock=cp.clock)
        )
    cp.server.create_queue(QueueRecord("qa"))
    cp.server.create_queue(QueueRecord("qb"))
    for ex in cp.executors:
        ex.run_once()
    return cp


def item(cpu="4", pools=("cpu",), **kw):
    return JobSubmitItem(
        resources={"cpu": cpu, "memory": "2"}, pools=pools, **kw
    )


def leases_by_pool(cp):
    out = {}
    txn = cp.jobdb.read_txn()
    for j in txn.all_jobs():
        run = j.latest_run
        if run is not None and not run.in_terminal_state():
            out[j.id] = (run.pool, run.pool_scheduled_away, run.scheduled_at_priority)
    return out


def test_overflow_schedules_away_at_low_priority(tmp_path):
    cp = build_plane(tmp_path)
    # cpu pool fits 2 x 4cpu; submit 4 -> 2 home, 2 away on gpu nodes
    ids = cp.server.submit_jobs("qa", "js", [item() for _ in range(4)])
    cp.ingest()
    cp.scheduler.cycle()
    leases = leases_by_pool(cp)
    assert len(leases) == 4
    pools = sorted(p for p, _, _ in leases.values())
    assert pools == ["cpu", "cpu", "gpu", "gpu"]
    for pool, away, prio in leases.values():
        if pool == "gpu":
            assert away and prio == CFG.priority_ladder()[0]
        else:
            assert not away
    cp.close()


def test_home_jobs_evict_away_guests(tmp_path):
    cp = build_plane(tmp_path, cpu_nodes=1, gpu_nodes=1)
    # Fill the gpu pool with away guests from the cpu pool...
    away_ids = cp.server.submit_jobs("qa", "guests", [item() for _ in range(4)])
    cp.ingest()
    cp.scheduler.cycle()
    cp.ingest()
    leases = leases_by_pool(cp)
    away_on_gpu = [j for j, (p, a, _) in leases.items() if p == "gpu" and a]
    assert len(away_on_gpu) == 2

    # ...then gpu-home jobs arrive and need that capacity back.
    home_ids = cp.server.submit_jobs(
        "qb", "homecoming", [item(pools=("gpu",)) for _ in range(2)]
    )
    cp.ingest()
    res = cp.scheduler.cycle()
    kinds = res.events_by_kind()
    # the home jobs leased; the away guests were preempted (urgency eviction)
    assert kinds.get("job_run_leased", 0) >= 2
    preempted_ids = {job.id for job, _ in res.scheduler_result.preempted}
    assert preempted_ids and preempted_ids <= set(away_on_gpu)
    leases = leases_by_pool(cp)
    for hid in home_ids:
        assert leases[hid][0] == "gpu" and not leases[hid][1]
    cp.close()


def test_away_only_feasibility_passes_validation(tmp_path):
    # No cpu-pool executors at all: a cpu-home job validates via the gpu
    # pool's away hosting and schedules there.
    cp = build_plane(tmp_path, cpu_nodes=0, gpu_nodes=1)
    ids = cp.server.submit_jobs("qa", "nohome", [item()])
    cp.ingest()
    res = cp.scheduler.cycle()
    assert res.events_by_kind().get("job_validated") == 1
    leases = leases_by_pool(cp)
    assert leases[ids[0]][0] == "gpu" and leases[ids[0]][1]
    cp.close()


def test_reclaim_through_executors_same_cycle(tmp_path):
    """Full-stack reclaim: away guests' pods must be deleted BEFORE the new
    home pods are submitted in the same lease response, or the home pods
    bounce off still-full nodes (the delete-before-submit ordering)."""
    cp = build_plane(tmp_path, cpu_nodes=1, gpu_nodes=2)
    cp.server.submit_jobs("qa", "o", [item() for _ in range(6)])
    cp.step()
    cp.step()
    # gpu-home jobs need the whole gpu nodes that away guests currently hold
    home = cp.server.submit_jobs(
        "qb",
        "train",
        [JobSubmitItem(resources={"cpu": "8", "memory": "8"}, pools=("gpu",)) for _ in range(2)],
    )
    cp.step()
    cp.step()
    states = cp.job_states()
    assert all(states[h] == "leased" for h in home), states
    # home pods actually landed in the cluster (not rejected)
    gpu_cluster = next(ex.cluster for ex in cp.executors if ex.id == "ex-gpu")
    pods = {p.job_id for p in gpu_cluster.pod_states()}
    assert set(home) <= pods
    cp.close()


def test_away_pass_sees_same_cycle_home_leases(tmp_path):
    """No double-booking: capacity the home round leased THIS cycle must be
    invisible to the away pass (stale running-set regression)."""
    cp = build_plane(tmp_path, cpu_nodes=1, gpu_nodes=1)
    # one gpu-home job takes the ENTIRE gpu node in the same cycle as a
    # cpu overflow job that would otherwise fit there
    cp.server.submit_jobs(
        "qb", "big", [JobSubmitItem(resources={"cpu": "8", "memory": "8"}, pools=("gpu",))]
    )
    overflow = cp.server.submit_jobs("qa", "of", [item(), item(), item()])
    cp.ingest()
    cp.scheduler.cycle()
    leases = leases_by_pool(cp)
    on_gpu = [(j, a) for j, (p, a, _) in leases.items() if p == "gpu"]
    # exactly the home job; no away guest squeezed onto the full node
    assert len(on_gpu) == 1 and not on_gpu[0][1]
    # cpu pool took 2 of the overflow; the third stays queued (no capacity)
    assert sum(1 for p, _, _ in leases.values() if p == "cpu") == 2
    cp.close()


def test_away_guests_never_preempt_home_jobs(tmp_path):
    """An away round must not evict the host pool's home jobs, even
    preemptible ones over their fair share."""
    cfg = SchedulingConfig(
        shape_bucket=32,
        pools=(PoolConfig("cpu", away_pools=("gpu",)), PoolConfig("gpu")),
        protected_fraction_of_fair_share=0.5,
    )
    cp = ControlPlane.build(tmp_path, config=cfg, executor_specs={})
    factory = cfg.resource_list_factory()
    nodes = [
        NodeSpec(
            id="g0",
            pool="gpu",
            executor="exg",
            total_resources=factory.from_mapping({"cpu": "8", "memory": "32"}),
        )
    ]
    cluster = FakeClusterContext(nodes, factory, runtime_of=lambda s: 60.0)
    cp.executors.append(
        ExecutorService("exg", "gpu", cluster, cp.executor_api, factory, clock=cp.clock)
    )
    cp.server.create_queue(QueueRecord("qa"))
    cp.server.create_queue(QueueRecord("qb"))
    for ex in cp.executors:
        ex.run_once()
    # qb fills the gpu pool with PREEMPTIBLE home jobs (way over fair share)
    hogs = cp.server.submit_jobs(
        "qb",
        "hogs",
        [
            JobSubmitItem(
                resources={"cpu": "4", "memory": "2"},
                pools=("gpu",),
                priority_class="armada-preemptible",
            )
            for _ in range(2)
        ],
    )
    cp.step()
    cp.step()
    # qa's cpu-home jobs arrive wanting to go away onto gpu
    cp.server.submit_jobs("qa", "guests", [item() for _ in range(2)])
    cp.ingest()
    res = cp.scheduler.cycle()
    # nothing preempted: guests wait instead of displacing home jobs
    assert res.scheduler_result.preempted == []
    states = cp.job_states()
    assert all(states[h] == "leased" for h in hogs)
    cp.close()


def test_no_away_without_config(tmp_path):
    cfg = SchedulingConfig(
        shape_bucket=32, pools=(PoolConfig("cpu"), PoolConfig("gpu"))
    )
    cp = ControlPlane.build(tmp_path, config=cfg, executor_specs={})
    factory = cfg.resource_list_factory()
    nodes = [
        NodeSpec(
            id="g0",
            pool="gpu",
            executor="exg",
            total_resources=factory.from_mapping({"cpu": "8", "memory": "32"}),
        )
    ]
    cluster = FakeClusterContext(nodes, factory)
    cp.executors.append(
        ExecutorService("exg", "gpu", cluster, cp.executor_api, factory, clock=cp.clock)
    )
    cp.server.create_queue(QueueRecord("qa"))
    for ex in cp.executors:
        ex.run_once()
    ids = cp.server.submit_jobs("qa", "js", [item()])
    cp.ingest()
    res = cp.scheduler.cycle()
    # cpu-home job cannot run anywhere: rejected at validation (no cpu fleet,
    # gpu does not host cpu jobs)
    assert res.events_by_kind().get("job_errors") == 1
    cp.close()


def test_away_views_are_built_where_the_away_round_reads_them(tmp_path):
    """The away pass's views of the cycle's own decisions (the home leases
    as RunningJobs, the home preemptions) are built once a cycle on the
    legacy path, whose away round reads them; on the incremental feed the
    host's running set comes from the feed, and no view is built.  Both
    lease the same: two at home, two away at the lowest priority."""
    from armada_tpu.ops.trace import recorder, reset_recorder
    from tests.test_trace import _find

    cp = build_plane(tmp_path)
    cp.server.submit_jobs("qa", "js", [item() for _ in range(4)])
    cp.ingest()
    reset_recorder()
    cp.scheduler.cycle()
    leases = sorted(leases_by_pool(cp).values())
    low = CFG.priority_ladder()[0]
    home = CFG.priority_class(CFG.default_priority_class).priority
    assert leases == sorted(
        [("cpu", False, home)] * 2 + [("gpu", True, low)] * 2
    )
    built = [s for t in recorder().last() for s in _find(t.root, "away_prepare")]
    if CFG.incremental_problem_build:
        assert built == []
    else:
        (span,) = built
        # the home round's two leases, viewed once for the gpu host's round
        assert span.args == {"scheduled": 2}
    reset_recorder()
    cp.close()
