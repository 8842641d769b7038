"""The scheduling sidecar: the round kernel behind a gRPC boundary.

The reference's SchedulingAlgo.Schedule (scheduling_algo.go:36-41) is an
in-process interface; the sidecar exports the same boundary over the wire so
an external (Go) control plane can use the TPU kernel.  The core property is
EQUALITY: a world mirrored through SyncState and scheduled via ScheduleRound
must produce exactly the decisions the in-process FairSchedulingAlgo makes
on the same world.
"""

from __future__ import annotations

import copy
import dataclasses
import pickle

import grpc
import pytest

from armada_tpu.core.config import PriorityClass, SchedulingConfig
from armada_tpu.core.types import NodeSpec, Queue
from armada_tpu.events import events_pb2 as epb
from armada_tpu.jobdb.job import Job, JobRun, JobSpec
from armada_tpu.jobdb.jobdb import JobDb
from armada_tpu.rpc import rpc_pb2 as pb
from armada_tpu.rpc.client import ScheduleClient, job_state_of
from armada_tpu.rpc.server import make_server
from armada_tpu.scheduler.algo import FairSchedulingAlgo
from armada_tpu.scheduler.executors import ExecutorSnapshot
from armada_tpu.scheduler.sidecar import ScheduleSidecar

NOW_NS = 1_000_000_000_000


def config_for(incremental: bool) -> SchedulingConfig:
    return SchedulingConfig(
        shape_bucket=64,
        enable_assertions=True,
        incremental_problem_build=incremental,
        protected_fraction_of_fair_share=0.5,
        priority_classes={
            "pc-high": PriorityClass(
                "pc-high", priority=3000, preemptible=False
            ),
            "pc-low": PriorityClass(
                "pc-low", priority=1000, preemptible=True
            ),
        },
        default_priority_class="pc-low",
    )


def build_world(config):
    """Nodes, queues and Job rows exercising the whole JobState surface:
    mixed priority classes, a gang, node bans, pool restrictions, running
    jobs (incl. an away run) and preemption pressure from an over-share
    queue."""
    F = config.resource_list_factory()
    nodes = [
        NodeSpec(
            id=f"n{i:02d}",
            pool="default",
            executor="ex1",
            total_resources=F.from_mapping({"cpu": "8", "memory": "32"}),
            labels={"rack": f"r{i % 3}"},
        )
        for i in range(12)
    ]
    queues = [Queue("alpha", 2.0), Queue("beta", 1.0), Queue("gamma", 1.0)]

    def spec(jid, queue, pc="pc-low", cpu="2", mem="8", prio=0, **kw):
        return JobSpec(
            id=jid,
            queue=queue,
            jobset="set1",
            priority_class=pc,
            priority=prio,
            submit_time=float(int(jid[1:]) if jid[1:].isdigit() else 1),
            resources=F.from_mapping({"cpu": cpu, "memory": mem}),
            **kw,
        )

    jobs = []
    # hog queue "alpha": runs the whole cluster at low PC (2 cpu free per
    # node) -> beta's 4-cpu jobs fit nowhere without fair-share eviction
    for i in range(12):
        s = spec(f"r{i:03d}", "alpha", cpu="6", mem="24")
        jobs.append(
            Job(
                spec=s,
                queued=False,
                validated=True,
                runs=(
                    JobRun(
                        id=f"run-r{i:03d}",
                        job_id=s.id,
                        executor="ex1",
                        node_id=f"n{i:02d}",
                        node_name=f"n{i:02d}",
                        pool="default",
                        scheduled_at_priority=1000,
                        running=True,
                        running_ns=NOW_NS - 10**9,
                    ),
                ),
            )
        )
    # one away run (home/away semantics must survive the wire): rides in
    # n11's leftover capacity at the away level, first to go under pressure
    s = spec("r100", "alpha", cpu="2", mem="8")
    jobs.append(
        Job(
            spec=s,
            queued=False,
            validated=True,
            runs=(
                JobRun(
                    id="run-r100",
                    job_id="r100",
                    executor="ex1",
                    node_id="n11",
                    node_name="n11",
                    pool="default",
                    scheduled_at_priority=0,
                    pool_scheduled_away=True,
                    running=True,
                ),
            ),
        )
    )
    # queued: beta wants capacity (forces eviction of alpha's preemptible
    # runs), gamma brings a gang + a banned job + a priority spread
    for i in range(6):
        jobs.append(
            Job(
                spec=spec(f"q{i:03d}", "beta", cpu="4", mem="16", prio=i),
                queued=True,
                validated=True,
            )
        )
    for i in range(3):
        jobs.append(
            Job(
                spec=spec(
                    f"g{i:03d}",
                    "gamma",
                    gang_id="gang1",
                    gang_cardinality=3,
                    cpu="2",
                    mem="8",
                ),
                queued=True,
                validated=True,
            )
        )
    # retry anti-affinity: failed attempts on n00/n01 ban those nodes
    s = spec("q100", "gamma", cpu="1", mem="4")
    jobs.append(
        Job(
            spec=s,
            queued=True,
            validated=True,
            runs=(
                JobRun(
                    id="old-1",
                    job_id="q100",
                    node_id="n00",
                    node_name="n00",
                    failed=True,
                    run_attempted=True,
                ),
                JobRun(
                    id="old-2",
                    job_id="q100",
                    node_id="n01",
                    node_name="n01",
                    failed=True,
                    run_attempted=True,
                ),
            ),
        )
    )
    # an unvalidated job must be invisible to scheduling on both sides
    jobs.append(Job(spec=spec("q200", "beta"), queued=True, validated=False))
    executors = [
        ExecutorSnapshot(
            id="ex1",
            pool="default",
            nodes=tuple(nodes),
            last_update_ns=NOW_NS,
        )
    ]
    return nodes, queues, jobs, executors


def run_in_process(config, queues, jobs, executors):
    jobdb = JobDb(config)
    feed = None
    if config.incremental_problem_build:
        from armada_tpu.scheduler.incremental_algo import IncrementalProblemFeed

        feed = IncrementalProblemFeed(config)
        feed.attach(jobdb)
    txn = jobdb.write_txn()
    txn.upsert(jobs)
    txn.commit()
    algo = FairSchedulingAlgo(
        config,
        queues=lambda: queues,
        clock_ns=lambda: NOW_NS,
        collect_stats=False,
        feed=feed,
    )
    txn = jobdb.write_txn()
    result = algo.schedule(txn, executors, now_ns=NOW_NS)
    txn.commit()
    return result, jobdb


@pytest.fixture()
def sidecar_env():
    """A live Schedule service + client; yields (client, sidecar)."""
    made = []

    def start(config):
        sidecar = ScheduleSidecar(config, clock_ns=lambda: NOW_NS)
        server, port = make_server(schedule_sidecar=sidecar)
        client = ScheduleClient(f"127.0.0.1:{port}")
        made.append((server, client))
        return client, sidecar

    yield start
    for server, client in made:
        client.close()
        server.stop(0)


@pytest.mark.parametrize("incremental", [False, True])
def test_sidecar_round_equals_in_process(sidecar_env, incremental):
    config = config_for(incremental)
    nodes, queues, jobs, executors = build_world(config)
    inproc, _ = run_in_process(config, queues, jobs, executors)
    in_sched = {job.id: run.node_id for job, run in inproc.scheduled}
    in_preempted = {job.id for job, _ in inproc.preempted}
    assert in_sched, "scenario must schedule something"
    assert in_preempted, "scenario must preempt something"

    client, _ = sidecar_env(config)
    sid = client.create_session()
    client.sync_state(
        sid,
        jobs=jobs,
        executors=executors,
        queues=queues,
        factory=config.resource_list_factory(),
    )
    resp = client.schedule_round(sid, now_ns=NOW_NS)
    side_sched = {l.job_id: l.node_id for l in resp.scheduled}
    side_preempted = {p.job_id for p in resp.preempted}
    assert side_sched == in_sched
    assert side_preempted == in_preempted
    # lease metadata a Go caller applies to its jobDb
    for lease in resp.scheduled:
        assert lease.run_id and lease.pool == "default"
        assert lease.executor == "ex1"
    # the banned job avoided its ban set on both sides
    if "q100" in side_sched:
        assert side_sched["q100"] not in ("n00", "n01")
    assert "q200" not in side_sched  # unvalidated stays invisible
    # gang atomicity survived the wire
    gang_placed = [j for j in side_sched if j.startswith("g")]
    assert len(gang_placed) in (0, 3)


@pytest.mark.parametrize("incremental", [False, True])
def test_sidecar_steady_state_deltas(sidecar_env, incremental):
    """Cycle 2 ships only deltas: the mirror already holds cycle 1's
    decisions (the sidecar committed them), so the caller syncs just new
    submits and the round schedules them without disturbing settled jobs."""
    config = config_for(incremental)
    nodes, queues, jobs, executors = build_world(config)
    client, _ = sidecar_env(config)
    sid = client.create_session()
    F = config.resource_list_factory()
    client.sync_state(
        sid, jobs=jobs, executors=executors, queues=queues, factory=F
    )
    r1 = client.schedule_round(sid, now_ns=NOW_NS)
    placed_r1 = {l.job_id for l in r1.scheduled}
    preempted_r1 = {p.job_id for p in r1.preempted}
    assert placed_r1

    fresh = Job(
        spec=JobSpec(
            id="fresh1",
            queue="beta",
            jobset="set1",
            priority_class="pc-low",
            submit_time=2000.0,
            resources=F.from_mapping({"cpu": "1", "memory": "2"}),
        ),
        queued=True,
        validated=True,
    )
    client.sync_state(sid, jobs=[fresh])
    r2 = client.schedule_round(sid, now_ns=NOW_NS + 10**9)
    placed_r2 = {l.job_id for l in r2.scheduled}
    assert "fresh1" in placed_r2
    # cycle 1's placements are leased in the mirror now -- they must not be
    # re-scheduled as if still queued
    assert not (placed_r2 & placed_r1)
    # nothing preempted twice either
    assert not ({p.job_id for p in r2.preempted} & preempted_r1)


def test_sidecar_sessions_and_errors(sidecar_env):
    config = config_for(False)
    client, sidecar = sidecar_env(config)
    with pytest.raises(grpc.RpcError) as err:
        client.schedule_round("nope")
    assert err.value.code() == grpc.StatusCode.NOT_FOUND

    # per-session config via YAML (reference key schema)
    sid = client.create_session(
        config_yaml=(
            "maximumSchedulingBurst: 1\n"
            "maximumPerQueueSchedulingBurst: 1\n"
            "priorityClasses:\n"
            "  pc-high: {priority: 3000}\n"
            "  pc-low: {priority: 1000, preemptible: true}\n"
            "defaultPriorityClass: pc-low\n"
        )
    )
    assert sidecar.session(sid).config.maximum_scheduling_burst == 1
    nodes, queues, jobs, executors = build_world(config)
    client.sync_state(
        sid,
        jobs=[j for j in jobs if j.queued],
        executors=executors,
        queues=queues,
        factory=config.resource_list_factory(),
    )
    resp = client.schedule_round(sid, now_ns=NOW_NS)
    assert len(resp.scheduled) <= 1  # burst cap from the session config
    client.close_session(sid)
    with pytest.raises(grpc.RpcError):
        client.schedule_round(sid)


def test_sidecar_terminal_and_delete_free_capacity(sidecar_env):
    """A terminal sync (or a delete) releases the job's capacity: the next
    round can place a job that previously did not fit."""
    config = config_for(False)
    F = config.resource_list_factory()
    node = NodeSpec(
        id="n0",
        pool="default",
        executor="ex1",
        total_resources=F.from_mapping({"cpu": "4", "memory": "16"}),
    )
    executors = [
        ExecutorSnapshot(
            id="ex1", pool="default", nodes=(node,), last_update_ns=NOW_NS
        )
    ]
    queues = [Queue("alpha", 1.0)]

    def job(jid, queued, cpu="4"):
        s = JobSpec(
            id=jid,
            queue="alpha",
            jobset="s",
            priority_class="pc-high",
            submit_time=1.0,
            resources=F.from_mapping({"cpu": cpu, "memory": "8"}),
        )
        runs = ()
        if not queued:
            runs = (
                JobRun(
                    id=f"run-{jid}",
                    job_id=jid,
                    node_id="n0",
                    node_name="n0",
                    pool="default",
                    scheduled_at_priority=3000,
                    running=True,
                ),
            )
        return Job(spec=s, queued=queued, validated=True, runs=runs)

    client, _ = sidecar_env(config)
    sid = client.create_session()
    client.sync_state(
        sid,
        jobs=[job("occupier", queued=False), job("waiter", queued=True)],
        executors=executors,
        queues=queues,
        factory=F,
    )
    r1 = client.schedule_round(sid, now_ns=NOW_NS)
    assert not r1.scheduled  # node full, non-preemptible occupant
    # occupier finished: caller syncs the terminal state
    done = job_state_of(job("occupier", queued=False))
    done.terminal = True
    client.sync_state(sid, jobs=[done])
    r2 = client.schedule_round(sid, now_ns=NOW_NS + 10**9)
    assert {l.job_id for l in r2.scheduled} == {"waiter"}


def test_serve_hosts_algo_port(tmp_path):
    """`serve --algo-port` exposes the sidecar next to the control plane."""
    from armada_tpu.cli.serve import start_control_plane

    plane = start_control_plane(
        data_dir=str(tmp_path / "data"),
        port=0,
        algo_port=0,
        cycle_interval_s=3600,
    )
    try:
        assert plane.algo_port
        client = ScheduleClient(f"127.0.0.1:{plane.algo_port}")
        sid = client.create_session()
        assert sid
        client.close_session(sid)
        client.close()
    finally:
        plane.stop()


def test_sidecar_fifo_tie_break_matches(sidecar_env):
    """submit_time must survive the wire: same queue/PC/priority, capacity
    for one -- the EARLIER submit wins on both sides (without submit_time on
    JobState both would tie at 0.0 and the id tie-break would pick the
    other job)."""
    config = config_for(False)
    F = config.resource_list_factory()
    node = NodeSpec(
        id="n0",
        pool="default",
        executor="ex1",
        total_resources=F.from_mapping({"cpu": "4", "memory": "16"}),
    )
    executors = [
        ExecutorSnapshot(
            id="ex1", pool="default", nodes=(node,), last_update_ns=NOW_NS
        )
    ]
    queues = [Queue("alpha", 1.0)]
    jobs = [
        # lexicographically-smaller id submitted LATER: the id tie-break
        # and the submit-time order disagree, so a dropped submit_time flips
        # the winner
        Job(
            spec=JobSpec(
                id="aaa",
                queue="alpha",
                jobset="s",
                priority_class="pc-low",
                submit_time=10.0,
                resources=F.from_mapping({"cpu": "4", "memory": "8"}),
            ),
            queued=True,
            validated=True,
        ),
        Job(
            spec=JobSpec(
                id="zzz",
                queue="alpha",
                jobset="s",
                priority_class="pc-low",
                submit_time=5.0,
                resources=F.from_mapping({"cpu": "4", "memory": "8"}),
            ),
            queued=True,
            validated=True,
        ),
    ]
    inproc, _ = run_in_process(config, queues, jobs, executors)
    in_sched = {job.id for job, _ in inproc.scheduled}
    assert in_sched == {"zzz"}

    client, _ = sidecar_env(config)
    sid = client.create_session()
    client.sync_state(
        sid, jobs=jobs, executors=executors, queues=queues, factory=F
    )
    resp = client.schedule_round(sid, now_ns=NOW_NS)
    assert {l.job_id for l in resp.scheduled} == {"zzz"}


def test_sidecar_session_id_collision_rejected(sidecar_env):
    """A caller-chosen session id that is already live must abort
    ALREADY_EXISTS, never silently replace the existing mirror."""
    client, _ = sidecar_env(config_for(False))
    assert client.create_session("prod") == "prod"
    with pytest.raises(grpc.RpcError) as err:
        client.create_session("prod")
    assert err.value.code() == grpc.StatusCode.ALREADY_EXISTS
    client.close_session("prod")
    assert client.create_session("prod") == "prod"  # reusable after close


def test_sidecar_short_job_penalty_rides_terminal_runs(sidecar_env):
    """A terminal job's final run must cross the wire (pool + running_ns):
    the short-job penalty keeps charging the queue for it, and a preempted
    run is exempt -- both mirrored through job_state_of."""
    import dataclasses as dc

    base = config_for(False)
    pool = dc.replace(
        base.pools[0], short_job_penalty_cutoff_s=3600.0
    )
    config = dc.replace(base, pools=(pool,))
    F = config.resource_list_factory()
    nodes = [
        NodeSpec(
            id=f"n{i}",
            pool="default",
            executor="ex1",
            total_resources=F.from_mapping({"cpu": "4", "memory": "16"}),
        )
        for i in range(2)
    ]
    executors = [
        ExecutorSnapshot(
            id="ex1",
            pool="default",
            nodes=tuple(nodes),
            last_update_ns=NOW_NS,
        )
    ]
    queues = [Queue("churner", 1.0), Queue("steady", 1.0)]

    def terminal_job(jid, preempted):
        s = JobSpec(
            id=jid,
            queue="churner",
            jobset="s",
            priority_class="pc-low",
            submit_time=1.0,
            resources=F.from_mapping({"cpu": "4", "memory": "8"}),
        )
        return Job(
            spec=s,
            queued=False,
            validated=True,
            failed=True,
            runs=(
                JobRun(
                    id=f"run-{jid}",
                    job_id=jid,
                    node_id="n0",
                    node_name="n0",
                    pool="default",
                    running=False,
                    failed=not preempted,
                    preempted=preempted,
                    run_attempted=True,
                    running_ns=NOW_NS - 10**9,  # died 1s in: "short"
                ),
            ),
        )

    for preempted, expect_penalty in ((False, True), (True, False)):
        jobs = [terminal_job("dead1", preempted)]
        inproc, _ = run_in_process(config, queues, jobs, executors)
        client, sidecar = sidecar_env(config)
        sid = client.create_session()
        client.sync_state(
            sid, jobs=jobs, executors=executors, queues=queues, factory=F
        )
        # the penalty is visible via the algo's internal scan: mirror and
        # source must agree on whether the dead run still charges churner
        session = sidecar.session(sid)
        mirrored = session.jobdb.read_txn().get("dead1")
        assert session.algo.short_job_penalty.applies(
            mirrored, NOW_NS
        ) is expect_penalty, f"preempted={preempted}"


# --- the sync's batch conversion against its one-message reference ----------
# A SyncState's JobStates are converted in ONE pass over interned spec
# templates (sidecar._jobs_from_states, events/convert.SpecTemplates); the
# plain reference stays `_job_from_state(msg, factory)`, and every Job the
# pass returns must be indistinguishable from the reference's.


def _state(job_id="j1", spec=None, **kw):
    if spec is None:
        spec = epb.JobSpec(
            priority_class="pc-low",
            resources=epb.Resources(milli={"cpu": 2000, "memory": 8000}),
        )
    kw.setdefault("queued", True)
    return pb.JobState(
        job_id=job_id, queue="qa", jobset="set-1", spec=spec, validated=True,
        submit_time=12.5, **kw,
    )


def _run_state(**kw):
    base = dict(
        run_id="run-1", node_id="n03", node_name="node-3", executor="ex1",
        pool="default", scheduled_at_priority=1000, has_scheduled_at_priority=True,
    )
    return pb.JobRunState(**{**base, **kw})


def _rich_spec():
    return epb.JobSpec(
        priority_class="pc-high",
        resources=epb.Resources(milli={"cpu": 500, "memory": 1000}),
        node_selector={"zone": "a", "disk": "ssd"},
        tolerations=[
            epb.Toleration(key="gpu", operator="Exists", effect="NoSchedule"),
            epb.Toleration(key="team", value="x"),
        ],
        gang_id="g1",
        gang_cardinality=3,
        gang_node_uniformity_label="rack",
        pools=["default", "away"],
        price_band="B",
        namespace="ns1",
        annotations={"a": "1"},
        labels={"app": "web"},
        services=[epb.ServiceSpec(type="Headless", ports=[80, 443], name="svc")],
        ingress=[
            epb.IngressSpec(
                ports=[8080], annotations={"k": "v"}, tls_enabled=True,
                cert_name="c", use_cluster_ip=True,
            )
        ],
        node_type_scores=[
            epb.NodeTypeScore(node_type="v5e", throughput=2.0),
            epb.NodeTypeScore(node_type="a100", throughput=1.5),
        ],
    )


def _resources_spec(milli):
    return epb.JobSpec(priority_class="pc-low", resources=epb.Resources(milli=milli))


_MESSAGE_FORMS = {
    "queued_submit": lambda: _state(),
    "leased_and_running": lambda: _state(
        queued=False, run=_run_state(running=True, running_ns=NOW_NS - 5, away=True)
    ),
    "leased_not_running_no_priority": lambda: _state(
        queued=False,
        run=_run_state(has_scheduled_at_priority=False, node_name="", pool=""),
    ),
    "terminal_with_run": lambda: _state(
        queued=False, terminal=True, run=_run_state(running_ns=NOW_NS - 10**9)
    ),
    "terminal_never_ran": lambda: _state(queued=True, terminal=True),
    "terminal_and_preempted": lambda: _state(
        queued=False, terminal=True,
        run=_run_state(running_ns=NOW_NS - 10**9, preempted=True),
    ),
    "banned_nodes": lambda: _state(banned_nodes=["n01", "n07"]),
    "banned_nodes_and_run": lambda: _state(
        queued=False, banned_nodes=["n01"], run=_run_state(running=True)
    ),
    "pools": lambda: _state(pools=["default", "spot"]),
    "rich_spec": lambda: _state(spec=_rich_spec()),
    "no_resources": lambda: _state(spec=epb.JobSpec(priority_class="pc-low")),
    "unknown_resource": lambda: _state(
        spec=_resources_spec({"cpu": 1000, "unobtainium": 7})
    ),
    "nonzero_priority": lambda: _state(
        priority=7,
        spec=epb.JobSpec(
            priority_class="pc-low", priority=3,
            resources=epb.Resources(milli={"cpu": 1000}),
        ),
    ),
}


def _assert_fields_equal(got, want):
    """Every dataclass field equal AND of the same type (a bool is not an
    int here), recursing into the frozen dataclasses and their tuples."""
    assert type(got) is type(want)
    for f in dataclasses.fields(want):
        g, w = getattr(got, f.name), getattr(want, f.name)
        assert type(g) is type(w), (type(want).__name__, f.name, g, w)
        if dataclasses.is_dataclass(w) and not isinstance(w, type):
            if hasattr(w, "atoms"):
                assert g.factory == w.factory
                assert g.atoms.dtype == w.atoms.dtype
                assert g.atoms.tolist() == w.atoms.tolist()
            else:
                _assert_fields_equal(g, w)
        elif isinstance(w, tuple) and w and dataclasses.is_dataclass(w[0]):
            assert len(g) == len(w)
            for a, b in zip(g, w):
                _assert_fields_equal(a, b)
        else:
            assert g == w, (type(want).__name__, f.name, g, w)
    # the generated __eq__ agrees, and so does a dict of the instance
    assert got == want
    assert set(vars(got)) >= {f.name for f in dataclasses.fields(want)}


def _reference_terminal_sync(msgs, state: dict) -> dict:
    """The bookkeeping loop as `_apply_sync_locked` ran it before the pass."""
    state = dict(state)
    for m in msgs:
        if m.terminal:
            state[m.job_id] = int(m.run.running_ns)
        else:
            state.pop(m.job_id, None)
    return state


@pytest.mark.parametrize("form", sorted(_MESSAGE_FORMS))
def test_batch_conversion_equals_the_one_message_reference(form):
    from armada_tpu.events.convert import SpecTemplates
    from armada_tpu.scheduler.sidecar import _job_from_state, _jobs_from_states

    F = config_for(False).resource_list_factory()
    msg = _MESSAGE_FORMS[form]()
    twin = type(msg)()
    twin.CopyFrom(msg)
    twin.job_id, twin.queue, twin.submit_time = "j2", "qb", 99.0
    other = _state(job_id="j3", spec=_resources_spec({"cpu": 123}))
    assert twin.spec.SerializeToString() == msg.spec.SerializeToString()
    assert other.spec.SerializeToString() != msg.spec.SerializeToString()
    batch = [msg, twin, other]

    seen_before = {"j1": 5, "j2": 6, "j3": 7, "elsewhere": 8}
    terminal_synced = dict(seen_before)
    templates = SpecTemplates(F)
    jobs, spec_hits = _jobs_from_states(batch, templates, terminal_synced)

    assert len(jobs) == 3
    for got, m in zip(jobs, batch):
        _assert_fields_equal(got, _job_from_state(m, F))
    assert terminal_synced == _reference_terminal_sync(batch, seen_before)
    # two templates, one hit (the twin)
    assert (len(templates), spec_hits) == (2, 1)
    # a second pass over the same messages is served from the table alone
    again, spec_hits = _jobs_from_states(batch, templates, {})
    assert (len(templates), spec_hits) == (2, 3)
    assert again == jobs

    # equal spec bytes share what the spec converts to; different bytes never
    a, b, c = (j.spec for j in jobs)
    for name in (
        "resources", "node_selector", "tolerations", "annotations", "labels",
        "services", "ingress", "node_type_scores",
    ):
        assert getattr(a, name) is getattr(b, name), name
    assert c.resources is not a.resources
    assert c.resources.atoms is not a.resources.atoms
    assert c.node_selector is not a.node_selector
    assert (a.id, a.queue, a.submit_time) == ("j1", "qa", 12.5)
    assert (b.id, b.queue, b.submit_time) == ("j2", "qb", 99.0)

    # ordinary instances: replace and the with_* methods work on them
    job = jobs[0]
    assert dataclasses.replace(job.spec, id="x").id == "x"
    assert dataclasses.replace(job.spec, id="x").resources is job.spec.resources
    bumped = job.with_priority(11)
    assert (bumped.priority, bumped.requested_priority) == (11, 11)
    assert bumped.spec is job.spec and job.priority == int(msg.priority)
    assert job.with_queued(False).queued_version == job.queued_version + 1
    leased = job.with_new_run(JobRun(id="r-new", job_id=job.id, node_id="n00"))
    assert leased.runs[:-1] == job.runs and leased.latest_run.id == "r-new"
    for run in job.runs:
        assert run.with_running(running_ns=3).running_ns == 3
        assert run.with_running().running_ns == run.running_ns
        assert run.with_failed().failed and not run.with_failed().running
    assert pickle.loads(pickle.dumps(job)) == job
    assert copy.deepcopy(job) == job


def test_spec_template_table_is_bounded_by_a_reset():
    from armada_tpu.events.convert import SpecTemplates
    from armada_tpu.scheduler.sidecar import _job_from_state, _jobs_from_states

    F = config_for(False).resource_list_factory()
    templates = SpecTemplates(F, bound=4)
    batch = [
        _state(job_id=f"j{i}", spec=_resources_spec({"cpu": 100 + i}))
        for i in range(11)
    ]
    jobs, spec_hits = _jobs_from_states(batch, templates, {})
    assert spec_hits == 0 and 0 < len(templates) <= 4
    assert jobs == [_job_from_state(m, F) for m in batch]
    assert [j.spec.resources.get("cpu") for j in jobs] == [100 + i for i in range(11)]
    # the default bound holds too, and a repeat within it hits
    assert SpecTemplates.BOUND >= 24
    jobs, spec_hits = _jobs_from_states(batch[-3:], templates, {})
    assert spec_hits == 3 and len(templates) <= 4


def test_a_write_to_shared_atoms_raises():
    from armada_tpu.events.convert import SpecTemplates
    from armada_tpu.scheduler.sidecar import _jobs_from_states

    F = config_for(False).resource_list_factory()
    (a, b), _ = _jobs_from_states(
        [_state("j1"), _state("j2")], SpecTemplates(F), {}
    )
    assert a.spec.resources.atoms is b.spec.resources.atoms
    with pytest.raises(ValueError):
        a.spec.resources.atoms[0] = 1
    with pytest.raises(ValueError):
        a.spec.resources.atoms += 1
    # arithmetic makes new vectors, as ever
    assert a.spec.resources.add(b.spec.resources).get("cpu") == 4000
    assert b.spec.resources.get("cpu") == 2000


def test_sessions_do_not_share_spec_templates():
    from armada_tpu.scheduler.sidecar import ScheduleSession

    one = ScheduleSession("one", config_for(False))
    narrow = dataclasses.replace(
        config_for(False), supported_resource_types=(("cpu", "1m"),)
    )
    two = ScheduleSession("two", narrow)
    assert one.factory != two.factory
    one.sync_jobs([_state("j1"), _state("j2")])
    assert (len(one._spec_templates), len(two._spec_templates)) == (2 - 1, 0)
    two.sync_jobs([_state("j1")])
    assert (len(one._spec_templates), len(two._spec_templates)) == (1, 1)
    j_one = one.jobdb.read_txn().get("j1")
    j_two = two.jobdb.read_txn().get("j1")
    assert j_one.spec.resources.factory is one.factory
    assert j_two.spec.resources.factory is two.factory
    assert len(j_one.spec.resources.atoms) != len(j_two.spec.resources.atoms)
    assert one.jobdb.read_txn().get("j2").spec.resources is j_one.spec.resources
