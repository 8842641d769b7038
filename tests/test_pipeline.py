"""Shadow-pipelined steady cycle (round 7): the soundness boundary, pinned.

The pipeline hides decision-independent host work in the device round's
shadow and prefetches decision-independent slab content mid-cycle
(IncrementalBuilder.prefetch_content -> DeviceDeltaCache.scatter_content).
Full two-cycle double-buffering is known-UNSOUND (cycle N+1's problem must
see cycle N's leases -- CLAUDE.md); these tests pin the line that IS sound:

1. *Prefetch bit-equality*: interleaving content prefetches with the cycle
   stream leaves the device problem bit-identical to materialize() every
   cycle -- content may ship early, order/demand/scalars never do.
2. *Prefetch guards*: slab growth, market pools and stale device caches
   all skip (the rows ride the next bundle / full upload instead).
3. *Pipelined == sequential*: the same multi-cycle world driven with
   ARMADA_PIPELINE=1 and =0 yields identical per-round decisions, mirror
   state, and (in-process) identical ordered event streams -- across both
   assemble modes, multiple seeds, and a slab-growing burst cycle.
4. *Sequential-path guard*: the sidecar-vs-in-process parity scenario runs
   under ARMADA_PIPELINE=0 so the escape hatch can't rot.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from armada_tpu.core.config import PoolConfig, PriorityClass, SchedulingConfig
from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob
from armada_tpu.models import decode_result, schedule_round
from armada_tpu.models.incremental import IncrementalBuilder
from armada_tpu.models.slab import DeviceDeltaCache

NOW_NS = 1_000_000_000_000


def make_config(**kw) -> SchedulingConfig:
    return SchedulingConfig(
        shape_bucket=64,
        priority_classes={
            "low": PriorityClass("low", priority=100, preemptible=True),
            "high": PriorityClass("high", priority=1000, preemptible=False),
        },
        default_priority_class="high",
        maximum_scheduling_burst=16,
        **kw,
    )


def make_world(cfg, num_nodes=12, num_queues=3):
    F = cfg.resource_list_factory()
    nodes = [
        NodeSpec(
            id=f"n{i}",
            pool="default",
            total_resources=F.from_mapping({"cpu": "16", "memory": "64"}),
        )
        for i in range(num_nodes)
    ]
    queues = [Queue(f"q{i}", weight=1.0 + i) for i in range(num_queues)]
    return F, nodes, queues


def make_job(F, i, queue, pc="high", cpu=2, sub=None):
    return JobSpec(
        id=f"j{i}",
        queue=queue,
        priority_class=pc,
        submit_time=float(i if sub is None else sub),
        resources=F.from_mapping({"cpu": str(cpu), "memory": "1"}),
    )


def assert_device_equals_materialize(bundle, dev):
    truth = bundle.materialize()
    for name, dev_arr, host_arr in zip(dev._fields, dev, truth):
        np.testing.assert_array_equal(
            np.asarray(dev_arr),
            np.asarray(host_arr),
            err_msg=f"prefetch drift in field {name}",
        )


def run_cycle(builder, cache, check_bits=True):
    bundle, ctx = builder.assemble_delta()
    dev = cache.apply(bundle)
    if check_bits:
        assert_device_equals_materialize(bundle, dev)
    res = schedule_round(
        dev,
        num_levels=len(ctx.ladder) + 2,
        max_slots=ctx.max_slots,
        slot_width=ctx.slot_width,
    )
    return decode_result(res, ctx), ctx


def apply_decisions(builder, spec_of, outcome):
    builder.remove_many(outcome.scheduled.keys())
    leases = []
    for jid, nid in outcome.scheduled.items():
        spec = spec_of.get(jid)
        if spec is not None:
            leases.append(RunningJob(job=spec, node_id=nid))
    builder.lease_many(leases)
    for jid in outcome.preempted:
        builder.unlease(jid)


# --- 1. prefetch bit-equality ------------------------------------------------


def test_prefetch_content_bit_equality():
    """Cycles that interleave mid-cycle content prefetches stay bit-equal
    to materialize(); prefetched rows leave the next bundle's payload."""
    cfg = make_config()
    F, nodes, queues = make_world(cfg)
    b = IncrementalBuilder(cfg, "default", queues)
    b.set_nodes(nodes)
    cache = DeviceDeltaCache()
    spec_of = {}
    nid = 0

    def submit(n, queue="q0", cpu=2):
        nonlocal nid
        specs = [make_job(F, nid + i, queue, cpu=cpu) for i in range(n)]
        nid += n
        for s in specs:
            spec_of[s.id] = s
        b.submit_many(specs)
        return specs

    submit(20)
    outcome, _ = run_cycle(b, cache)
    assert outcome.scheduled
    prefetches = 0
    for cycle in range(4):
        # shadow-equivalent slot: next cycle's decision-independent feed
        # ships BEFORE this cycle's decisions apply
        submit(5, queue=f"q{cycle % 3}")
        shipped = b.prefetch_content(cache)
        if shipped:
            prefetches += 1
        # decisions from the round just taken (decision-dependent tail)
        apply_decisions(b, spec_of, outcome)
        outcome, _ = run_cycle(b, cache)
    assert prefetches >= 3, "steady cycles must take the prefetch path"
    assert cache.content_prefetches == prefetches


def test_prefetch_payload_leaves_next_bundle():
    """A prefetched slot that is NOT re-dirtied is excluded from the next
    bundle's scatter payload (the transfer the pipeline exists to move)."""
    cfg = make_config()
    F, nodes, queues = make_world(cfg)
    b = IncrementalBuilder(cfg, "default", queues)
    b.set_nodes(nodes)
    cache = DeviceDeltaCache()
    b.submit_many([make_job(F, i, "q0") for i in range(8)])
    bundle, _ = b.assemble_delta()
    cache.apply(bundle)
    fresh = [make_job(F, 100 + i, "q1") for i in range(4)]
    b.submit_many(fresh)
    shipped = b.prefetch_content(cache)
    assert shipped == 4
    bundle2, _ = b.assemble_delta()
    dev = cache.apply(bundle2)
    # none of the fresh submits' slots re-ship in the cycle bundle
    fresh_slots = {
        int(b.jobs.slot[row])
        for row in b.jobs.live_rows()
        if b.jobs.ids[row].tobytes().rstrip(b"\0").decode().startswith("j10")
    }
    assert fresh_slots, "fresh submits must be live"
    assert not (set(int(x) for x in bundle2.sg_idx) & fresh_slots)
    assert_device_equals_materialize(bundle2, dev)


def test_prefetch_skips_on_slab_growth():
    """Submits that grow the slab (epoch bump) make the prefetch a no-op;
    the next apply rides the full-upload fallback bit-exactly."""
    cfg = make_config()
    F, nodes, queues = make_world(cfg)
    b = IncrementalBuilder(cfg, "default", queues)
    b.set_nodes(nodes)
    cache = DeviceDeltaCache()
    b.submit_many([make_job(F, i, "q0") for i in range(8)])
    bundle, _ = b.assemble_delta()
    cache.apply(bundle)
    epoch0 = b._sg.epoch
    b.submit_many([make_job(F, 1000 + i, "q1") for i in range(200)])
    assert b._sg.epoch > epoch0, "batch must grow the slab"
    assert b.prefetch_content(cache) == 0
    bundle2, _ = b.assemble_delta()
    dev = cache.apply(bundle2)
    assert_device_equals_materialize(bundle2, dev)


def test_prefetch_skips_market_and_stale_cache():
    cfg = make_config(pools=(PoolConfig("default", market_driven=True),))
    F, nodes, queues = make_world(cfg)
    m = IncrementalBuilder(
        cfg, "default", queues, bid_price_of=lambda job: 1.0
    )
    m.set_nodes(nodes)
    cache = DeviceDeltaCache()
    m.submit_many([make_job(F, i, "q0") for i in range(4)])
    bundle, _ = m.assemble_delta()
    cache.apply(bundle)
    m.submit_many([make_job(F, 10 + i, "q0") for i in range(2)])
    # market: per-slot prices are a per-cycle function of the bid table --
    # never prefetched
    assert m.prefetch_content(cache) == 0

    cfg2 = make_config()
    F2, nodes2, queues2 = make_world(cfg2)
    b = IncrementalBuilder(cfg2, "default", queues2)
    b.set_nodes(nodes2)
    b.submit_many([make_job(F2, i, "q0") for i in range(4)])
    b.assemble_delta()  # bundle emitted but never applied anywhere
    b.submit_many([make_job(F2, 10 + i, "q0") for i in range(2)])
    # stale/fresh cache (not at the last bundle's state): skip
    assert b.prefetch_content(DeviceDeltaCache()) == 0


# --- 3. pipelined == sequential ---------------------------------------------


def _sidecar_scenario(monkeypatch, pipelined: bool, incremental: bool, seed: int):
    """One scripted multi-cycle sidecar session; returns per-round decisions
    and the final mirror state."""
    from armada_tpu.rpc.client import job_state_of
    from armada_tpu.scheduler.sidecar import ScheduleSidecar
    from armada_tpu.jobdb.job import Job, JobRun
    from armada_tpu.scheduler.executors import ExecutorSnapshot

    monkeypatch.setenv("ARMADA_PIPELINE", "1" if pipelined else "0")
    # force the scatter-prefetch path on the CPU backend so the pipelined
    # arm exercises the full stage-(b) machinery, not just the shadow order
    monkeypatch.setenv("ARMADA_PIPELINE_PREFETCH", "1" if pipelined else "0")

    cfg = make_config(
        incremental_problem_build=incremental, enable_assertions=True
    )
    F, nodes, queues = make_world(cfg)
    rng = np.random.default_rng(seed)
    sidecar = ScheduleSidecar(cfg, clock_ns=lambda: NOW_NS)
    sid = sidecar.create_session()
    s = sidecar.session(sid)
    executors = [
        ExecutorSnapshot(
            id="ex1", pool="default", nodes=tuple(nodes), last_update_ns=NOW_NS
        )
    ]
    s.apply_sync(executors=executors, queues=queues)

    nid = [0]

    def jobs(n, cycle):
        out = []
        for _ in range(n):
            i = nid[0]
            nid[0] += 1
            out.append(
                Job(
                    spec=make_job(
                        F,
                        i,
                        f"q{int(rng.integers(0, 3))}",
                        pc="low" if rng.random() < 0.5 else "high",
                        cpu=int(rng.integers(1, 5)),
                        sub=cycle * 1000 + i,
                    ),
                    queued=True,
                    validated=True,
                )
            )
        return out

    rounds = []
    running_states = {}
    now = NOW_NS
    # cycle sizes: steady, steady, BURST (grows the slab past bucket 64),
    # steady drain
    for cycle, batch in enumerate((24, 8, 90, 6)):
        sync_jobs = [job_state_of(j) for j in jobs(batch, cycle)]
        # re-assert last round's leases as running (the caller's round trip)
        sync_jobs.extend(running_states.values())
        s.apply_sync(jobs=sync_jobs)
        result = s.schedule_round(now_ns=now)
        sched = sorted(
            (job.id, run.node_id) for job, run in result.scheduled
        )
        pre = sorted(job.id for job, _ in result.preempted)
        rounds.append((sched, pre))
        for job, run in result.scheduled:
            running_states[job.id] = job_state_of(
                Job(
                    spec=job.spec,
                    queued=False,
                    validated=True,
                    runs=(
                        JobRun(
                            id=run.id,
                            job_id=job.id,
                            executor="ex1",
                            node_id=run.node_id,
                            node_name=run.node_id,
                            pool="default",
                            scheduled_at_priority=run.scheduled_at_priority,
                            running=True,
                            running_ns=now,
                        ),
                    ),
                )
            )
        for jid in pre:
            running_states.pop(jid, None)
        # a few completions go terminal (exercises the shadow sweep)
        done = sorted(running_states)[: max(0, len(running_states) - 10)]
        if done:
            term = []
            for jid in done:
                m = running_states.pop(jid)
                m.terminal = True
                term.append(m)
            s.apply_sync(jobs=term)
        now += 10**9

    final = sorted(
        (j.id, j.queued, j.in_terminal_state(), j.latest_run is None)
        for j in s.jobdb.read_txn().all_jobs()
    )
    return rounds, final


@pytest.mark.parametrize("incremental", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sidecar_pipelined_equals_sequential(monkeypatch, incremental, seed):
    a = _sidecar_scenario(monkeypatch, True, incremental, seed)
    b = _sidecar_scenario(monkeypatch, False, incremental, seed)
    assert a[0] == b[0], "per-round decisions diverged"
    assert a[1] == b[1], "final mirror state diverged"
    assert any(sched for sched, _ in a[0]), "scenario must schedule"


def _control_plane_scenario(tmp_path, monkeypatch, pipelined: bool, incremental: bool):
    """The in-process stack: submit -> cycles -> ordered event stream."""
    from armada_tpu.server import JobSubmitItem, QueueRecord
    from tests.control_plane import ControlPlane

    monkeypatch.setenv("ARMADA_PIPELINE", "1" if pipelined else "0")
    monkeypatch.setenv("ARMADA_PIPELINE_PREFETCH", "1" if pipelined else "0")
    plane = ControlPlane.build(
        tmp_path / ("p" if pipelined else "s"),
        config=SchedulingConfig(
            shape_bucket=32,
            enable_assertions=True,
            incremental_problem_build=incremental,
        ),
    )
    try:
        plane.server.create_queue(QueueRecord("tenant-a", weight=2.0))
        plane.server.create_queue(QueueRecord("tenant-b", weight=1.0))
        plane.server.submit_jobs(
            "tenant-a", "set1", [JobSubmitItem(resources={"cpu": "2", "memory": "2"})] * 4
        )
        plane.server.submit_jobs(
            "tenant-b", "set1", [JobSubmitItem(resources={"cpu": "2", "memory": "2"})] * 4
        )
        plane.run_until(
            lambda: len(plane.job_states()) == 8
            and all(s == "succeeded" for s in plane.job_states().values()),
            tick_s=3.0,
        )
        states = plane.job_states()
        kinds = {}
        for tenant in ("tenant-a", "tenant-b"):
            kinds[tenant] = [
                ev.WhichOneof("event")
                for e in plane.event_api.get_jobset_events(tenant, "set1")
                for ev in e.sequence.events
            ]
        # job ids are generated (ulid-style), so compare structure: the
        # multiset of terminal states and the ORDERED per-jobset event-kind
        # streams (id-free), which pin cycle-by-cycle behavior.
        return sorted(states.values()), kinds
    finally:
        plane.close()


@pytest.mark.parametrize("incremental", [False, True])
def test_inprocess_pipelined_equals_sequential(tmp_path, monkeypatch, incremental):
    a = _control_plane_scenario(tmp_path, monkeypatch, True, incremental)
    b = _control_plane_scenario(tmp_path, monkeypatch, False, incremental)
    assert a[0] == b[0], "final job states diverged"
    assert a[1] == b[1], "ordered event streams diverged"


# --- 4. sequential-path guard ------------------------------------------------


@pytest.mark.fast  # explicit: the fast tier must always exercise the
# ARMADA_PIPELINE=0 path (conftest's representative rule only takes the
# module's first picks)
def test_parity_scenario_under_sequential_escape_hatch(monkeypatch):
    """The ARMADA_PIPELINE=0 escape hatch must keep full wire parity: the
    sidecar round equals the in-process algo on the rich parity world from
    tests/test_sidecar.py -- the guard that keeps the sequential path from
    rotting while the default stays pipelined."""
    from tests.test_sidecar import build_world, config_for, run_in_process
    from armada_tpu.rpc.client import job_state_of
    from armada_tpu.scheduler.sidecar import ScheduleSidecar

    monkeypatch.setenv("ARMADA_PIPELINE", "0")
    config = config_for(incremental=True)
    nodes, queues, jobs, executors = build_world(config)
    inproc, _ = run_in_process(config, queues, jobs, executors)
    in_sched = {job.id: run.node_id for job, run in inproc.scheduled}
    in_pre = {job.id for job, _ in inproc.preempted}
    assert in_sched and in_pre

    sidecar = ScheduleSidecar(config, clock_ns=lambda: NOW_NS)
    sid = sidecar.create_session()
    s = sidecar.session(sid)
    s.apply_sync(
        jobs=[job_state_of(j) for j in jobs],
        executors=executors,
        queues=queues,
    )
    result = s.schedule_round(now_ns=NOW_NS)
    assert {job.id: run.node_id for job, run in result.scheduled} == in_sched
    assert {job.id for job, _ in result.preempted} == in_pre


# --- 5. device-loss mid-cycle -------------------------------------------------


def test_device_loss_mid_cycle_invalidates_prefetch(monkeypatch):
    """Device-loss resilience x the pipeline: an injected device loss
    mid-cycle provably invalidates the prefetch/scatter state (the replaced
    DeviceDeltaCache refuses stale scatters, the builder's shipped-row
    bookkeeping resets), and every cycle's decisions -- including the ones
    after the loss -- are bit-equal to the sequential ARMADA_PIPELINE=0
    path with no faults."""
    from armada_tpu.core import faults, watchdog
    from armada_tpu.models import run_round_on_device
    from armada_tpu.scheduler.incremental_algo import IncrementalProblemFeed

    saved_hooks = list(watchdog._reset_hooks)
    watchdog._reset_hooks.clear()

    def run_script(pipelined: bool, inject: bool):
        faults.reset_counters()
        watchdog.reset_supervisor()
        monkeypatch.setenv("ARMADA_REPROBE_INTERVAL_S", "0")
        monkeypatch.setenv("ARMADA_PIPELINE", "1" if pipelined else "0")
        monkeypatch.setenv(
            "ARMADA_PIPELINE_PREFETCH", "1" if pipelined else "0"
        )
        monkeypatch.setenv("ARMADA_WATCHDOG_S", "60")
        if inject:
            # after_n=1: the SECOND cycle's round dies -- after cycle 1's
            # tail already prefetched rows to the device cache
            monkeypatch.setenv("ARMADA_FAULT", "device_round:error:1")
        else:
            monkeypatch.delenv("ARMADA_FAULT", raising=False)
        cfg = make_config()
        F, nodes, queues = make_world(cfg)
        feed = IncrementalProblemFeed(cfg)
        b = feed.builder_for("default")
        b.set_queues(queues)
        b.set_nodes(nodes)
        spec_of = {}
        nid = [0]

        def submit(n, queue="q0"):
            specs = [make_job(F, nid[0] + i, queue) for i in range(n)]
            nid[0] += n
            for s in specs:
                spec_of[s.id] = s
            b.submit_many(specs)

        submit(16)
        decisions = []
        prefetched_before_loss = 0
        for cycle in range(4):
            bundle, ctx = b.assemble_delta()
            devcache = feed.devcache_for("default")
            _, outcome = run_round_on_device(
                bundle.stats_view(),
                ctx,
                cfg,
                device_problem=lambda dc=devcache, b_=bundle: dc.apply(b_),
                host_problem=bundle.materialize,
            )
            if inject and cycle == 1:
                # the loss just happened: supervisor degraded, cache
                # replaced (refuses any scatter), prefetch disarmed
                assert watchdog.supervisor().degraded
                assert feed.devcaches["default"]._prev is None
                assert b._last_sig is None and b._shipped_sg == 0
                assert b.prefetch_content(feed.devcaches["default"]) == 0
            decisions.append(
                (sorted(outcome.scheduled.items()), sorted(outcome.preempted))
            )
            apply_decisions(b, spec_of, outcome)
            submit(4, f"q{cycle % 3}")
            if pipelined:
                shipped = b.prefetch_content(feed.devcaches["default"])
                if inject and cycle == 0:
                    prefetched_before_loss = shipped
        if inject:
            assert prefetched_before_loss > 0, (
                "the loss must land AFTER a real prefetch shipped rows"
            )
        return decisions

    try:
        faulted = run_script(pipelined=True, inject=True)
        sequential = run_script(pipelined=False, inject=False)
        assert faulted == sequential, (
            "post-loss decisions must be bit-equal to the sequential path"
        )
        assert any(sched for sched, _ in sequential)
    finally:
        faults.reset_counters()
        watchdog.reset_supervisor()
        watchdog._reset_hooks[:] = saved_hooks


def test_sidecar_pipelined_equals_sequential_under_the_chip_body(monkeypatch):
    """The pipeline equality suite on the body an accelerator compiles (no
    fit cache; ARMADA_CACHE_SLOTS=0 here): pipelined vs sequential cycle
    order must stay bit-equal, AND its decisions must match those of
    XLA:CPU's cached body -- what a CPU failover would have decided."""
    runs = {}
    for body in ("chip", "cpu"):
        if body == "chip":
            monkeypatch.setenv("ARMADA_CACHE_SLOTS", "0")
        else:
            monkeypatch.delenv("ARMADA_CACHE_SLOTS")
        runs[body] = (
            _sidecar_scenario(monkeypatch, True, True, 0),
            _sidecar_scenario(monkeypatch, False, True, 0),
        )
    for body, (a, b) in runs.items():
        assert a[0] == b[0], f"{body}: per-round decisions diverged"
        assert a[1] == b[1], f"{body}: final mirror state diverged"
    assert runs["chip"][0][0] == runs["cpu"][0][0], "the two bodies decided differently"
    assert any(sched for sched, _ in runs["chip"][0][0]), "scenario must schedule"
