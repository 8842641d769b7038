"""Pool-parallel serving (round 17): parallel/stacked cycle == serial loop.

The non-negotiable contract: arming ARMADA_POOL_PARALLEL changes NOTHING
about decisions, event order, or mirror state -- the dispatch/fetch split
only reorders asynchronous device enqueues, and stacked launches are
jax.vmap lanes whose while_loop batching is bit-exact per lane.  Pinned
here:

1. *Multi-pool churn equality*: the same seeded submit/cancel/reprioritise
   /gang/preemption stream driven through P in {2, 4, 8} pool-restricted
   tenants yields identical per-cycle decisions, apply order (the event
   order), and final JobDb state with pool-parallel armed vs the serial
   loop -- both assemble modes, with verify armed, under the chip's
   body (no fit cache) and XLA:CPU's.
2. *Certification fallback*: a cycle that cannot certify pool
   independence (a multi-pool job queued, binding rate-limiter tokens)
   runs the serial order -- and stays bit-equal (the ledger shows the
   fallback, scheduler/pool_serving.py).
3. *Verification blast radius*: a RoundVerificationError in ONE pool's
   round walks the failover ladder for that pool alone -- its re-run is
   bit-equal, the other pools' decisions are untouched, exactly one
   fallback is recorded, and the quarantine scoreboard gets the strike.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np
import pytest

from armada_tpu.core import faults
from armada_tpu.core import watchdog
from armada_tpu.core.config import PoolConfig, PriorityClass, SchedulingConfig
from armada_tpu.core.types import JobSpec, NodeSpec, Queue
from armada_tpu.jobdb.job import Job
from armada_tpu.jobdb.jobdb import JobDb
from armada_tpu.models.verify import reset_verify_state
from armada_tpu.scheduler.algo import FairSchedulingAlgo
from armada_tpu.scheduler.executors import ExecutorSnapshot
from armada_tpu.scheduler.incremental_algo import IncrementalProblemFeed
from armada_tpu.scheduler.pool_serving import (
    pool_serving_stats,
    reset_pool_serving_stats,
)
from armada_tpu.scheduler.quarantine import reset_device_quarantine

NOW_NS = 1_000_000_000_000


@pytest.fixture(autouse=True)
def _fresh_state(monkeypatch):
    monkeypatch.delenv("ARMADA_POOL_PARALLEL", raising=False)
    monkeypatch.delenv("ARMADA_FAULT", raising=False)
    faults.reset_counters()
    reset_verify_state()
    reset_device_quarantine()
    reset_pool_serving_stats()
    watchdog.reset_supervisor()
    yield
    faults.reset_counters()
    reset_verify_state()
    reset_device_quarantine()
    reset_pool_serving_stats()
    watchdog.reset_supervisor()


def make_config(npools: int, incremental: bool, unlimited: bool = True):
    kw = {}
    if unlimited:
        # unlimited buckets: the frozen test clock never refills, so armed
        # defaults would drain mid-scenario and turn the equality run into
        # a nothing-schedules run (the certification-fallback test keeps
        # them armed deliberately)
        kw.update(
            maximum_scheduling_rate=0.0,
            maximum_per_queue_scheduling_rate=0.0,
        )
    return SchedulingConfig(
        shape_bucket=32,
        priority_classes={
            "low": PriorityClass("low", priority=100, preemptible=True),
            "high": PriorityClass("high", priority=1000, preemptible=False),
        },
        default_priority_class="high",
        maximum_scheduling_burst=1_000,
        incremental_problem_build=incremental,
        pools=tuple(PoolConfig(f"p{i}") for i in range(npools)),
        **kw,
    )


class MultiPoolWorld:
    """JobDb + feed + algo over P pool-restricted tenants, driven by a
    seeded churn script (submits with gangs, cancels, reprioritises; the
    capacity squeeze makes later high-priority submits preempt low ones)."""

    def __init__(self, npools: int, incremental: bool, seed: int,
                 unlimited: bool = True, multi_pool_job: bool = False):
        self.cfg = make_config(npools, incremental, unlimited)
        self.F = self.cfg.resource_list_factory()
        self.npools = npools
        self.jdb = JobDb(self.cfg)
        self.feed = None
        if incremental:
            self.feed = IncrementalProblemFeed(self.cfg)
            self.feed.attach(self.jdb)
        self.rng = np.random.default_rng(seed)
        self.seq = 0
        self.live: list = []
        self.multi_pool_job = multi_pool_job
        self.executors = [
            ExecutorSnapshot(
                id=f"ex{p}",
                pool=f"p{p}",
                last_update_ns=NOW_NS,
                nodes=tuple(
                    NodeSpec(
                        id=f"n{p}-{k}",
                        pool=f"p{p}",
                        total_resources=self.F.from_mapping(
                            {"cpu": "8", "memory": "32"}
                        ),
                    )
                    for k in range(3)
                ),
            )
            for p in range(npools)
        ]
        self.algo = FairSchedulingAlgo(
            self.cfg,
            queues=lambda: [Queue(f"q{i}", 1.0 + i) for i in range(3)],
            clock_ns=lambda: NOW_NS,
            feed=self.feed,
        )

    def _submit(self, txn, n: int, pc: str, gang_every: int = 0):
        for _ in range(n):
            i = self.seq
            self.seq += 1
            pool = f"p{i % self.npools}"
            pools = (pool,)
            if self.multi_pool_job and i == 7 and self.npools >= 2:
                pools = ("p0", "p1")  # breaks the independence certification
            gang_id = ""
            card = 0
            if gang_every and i % gang_every == 0:
                gang_id = f"g{i}"
                card = 2
            spec = JobSpec(
                id=f"j{i:05d}",
                queue=f"q{int(self.rng.integers(0, 3))}",
                priority_class=pc,
                submit_time=float(i),
                pools=pools,
                gang_id=gang_id,
                gang_cardinality=card,
                resources=self.F.from_mapping(
                    {
                        "cpu": str(1 + int(self.rng.integers(0, 3))),
                        "memory": "1",
                    }
                ),
            )
            txn.upsert(Job(spec=spec, queued=True, validated=True, pools=pools))
            self.live.append(spec.id)
            if card:
                # gang sibling, same pool/queue
                sib = dataclasses.replace(spec, id=f"{spec.id}s")
                txn.upsert(
                    Job(spec=sib, queued=True, validated=True, pools=pools)
                )
                self.live.append(sib.id)

    def run(self, cycles: int = 4):
        """Seeded churn; returns (per-cycle ordered decisions, final state)."""
        out = []
        for c in range(cycles):
            txn = self.jdb.write_txn()
            # churn: fill with preemptible work first, then high-priority
            # arrivals that must preempt; sprinkle cancels/reprioritises
            self._submit(
                txn,
                14 if c == 0 else 6,
                "low" if c < 2 else "high",
                gang_every=5,
            )
            if c >= 1 and len(self.live) > 4:
                for jid in self.live[2:4]:
                    job = txn.get(jid)
                    if job is not None and job.queued:
                        txn.upsert(dataclasses.replace(job, cancelled=True))
                jid = self.live[4]
                job = txn.get(jid)
                if job is not None and job.queued and not job.in_terminal_state():
                    txn.upsert(dataclasses.replace(job, priority=5000 + c))
            result = self.algo.schedule(txn, self.executors, NOW_NS)
            # event order == apply order: the per-pool sequence of
            # PoolStats AND the per-pool ordered decision lists
            out.append(
                (
                    [
                        (
                            ps.pool,
                            sorted(ps.outcome.scheduled.items()),
                            sorted(ps.outcome.preempted),
                        )
                        for ps in result.pools
                    ],
                    [(job.id, run.node_id) for job, run in result.scheduled],
                    sorted(job.id for job, _ in result.preempted),
                )
            )
            txn.commit()
        final = sorted(
            (
                j.id,
                j.queued,
                j.in_terminal_state(),
                None if j.latest_run is None else j.latest_run.node_id,
            )
            for j in self.jdb.read_txn().all_jobs()
        )
        return out, final


def run_scenario(parallel, *, npools=4, incremental=True, seed=0,
                 verify=False, unlimited=True, multi_pool_job=False,
                 monkeypatch=None):
    monkeypatch.setenv("ARMADA_POOL_PARALLEL", "1" if parallel else "0")
    monkeypatch.setenv("ARMADA_VERIFY", "1" if verify else "0")
    world = MultiPoolWorld(
        npools, incremental, seed, unlimited=unlimited,
        multi_pool_job=multi_pool_job,
    )
    return world.run()


# --- 1. multi-pool churn equality -------------------------------------------


@pytest.mark.parametrize("npools", [2, 4, 8])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pool_parallel_bit_equal_over_churn(monkeypatch, npools, seed):
    a = run_scenario(False, npools=npools, seed=seed, monkeypatch=monkeypatch)
    reset_pool_serving_stats()
    b = run_scenario(True, npools=npools, seed=seed, monkeypatch=monkeypatch)
    assert a == b, f"P={npools} seed={seed}: decisions/event order diverged"
    assert any(sched for _pools, sched, _pre in a[0]), "scenario must schedule"
    snap = pool_serving_stats().snapshot()
    assert snap["parallel_cycles"] > 0, "the parallel path never engaged"


def test_pool_parallel_bit_equal_with_verify_and_stacking(monkeypatch):
    """Verify armed end to end: every pool's round is certified (the
    stacked verify pass included) and decisions stay bit-equal; stacked
    launches actually happen."""
    a = run_scenario(False, npools=4, seed=3, verify=True,
                     monkeypatch=monkeypatch)
    reset_pool_serving_stats()
    b = run_scenario(True, npools=4, seed=3, verify=True,
                     monkeypatch=monkeypatch)
    assert a == b
    snap = pool_serving_stats().snapshot()
    assert snap["parallel_cycles"] > 0
    assert snap["stacked_launches"] > 0, "shape-matched pools must stack"
    from armada_tpu.models.verify import verify_state

    assert verify_state().rounds > 0 and verify_state().failures == 0


def test_pool_parallel_bit_equal_under_both_bodies(monkeypatch, round_body):
    """The stacked launch is a vmap of whichever body the platform compiles:
    the chip's (ARMADA_CACHE_SLOTS=0 here) and XLA:CPU's fit cache."""
    a = run_scenario(False, npools=4, seed=1, monkeypatch=monkeypatch)
    b = run_scenario(True, npools=4, seed=1, monkeypatch=monkeypatch)
    assert a == b


def test_pool_parallel_legacy_assemble_mode_equal(monkeypatch):
    """Non-incremental (legacy per-cycle build): pool-parallel has no
    incremental feed to certify against -- the flag must degrade to the
    serial order and change nothing."""
    a = run_scenario(False, npools=3, seed=0, incremental=False,
                     monkeypatch=monkeypatch)
    b = run_scenario(True, npools=3, seed=0, incremental=False,
                     monkeypatch=monkeypatch)
    assert a == b
    assert pool_serving_stats().snapshot()["parallel_cycles"] == 0


# --- 2. certification fallback ----------------------------------------------


def test_multi_pool_job_forces_serial_fallback(monkeypatch):
    """One queued job listing two pools makes the cycle order-dependent:
    the certification must fail, the cycle runs serially, decisions equal
    the serial loop exactly."""
    a = run_scenario(False, npools=3, seed=2, multi_pool_job=True,
                     monkeypatch=monkeypatch)
    reset_pool_serving_stats()
    b = run_scenario(True, npools=3, seed=2, multi_pool_job=True,
                     monkeypatch=monkeypatch)
    assert a == b
    snap = pool_serving_stats().snapshot()
    # the cycle with the multi-pool job queued fell back; once it leases,
    # independence is restored and LATER cycles may parallelize again
    assert snap["serial_fallback_cycles"] > 0


def test_binding_rate_limits_force_serial_fallback(monkeypatch):
    """Armed token buckets against the frozen test clock drain and become
    BINDING: the per-window token certification must refuse to overlap,
    and the fallback path hands every pool the exact post-consumption
    tokens the serial loop would have (the re-read after flush)."""
    a = run_scenario(False, npools=3, seed=0, unlimited=False,
                     monkeypatch=monkeypatch)
    reset_pool_serving_stats()
    b = run_scenario(True, npools=3, seed=0, unlimited=False,
                     monkeypatch=monkeypatch)
    assert a == b


def test_feed_independence_tracking():
    """pools_independent() follows the queued-job lifecycle: unrestricted
    and multi-pool jobs break it; leasing/terminating them restores it."""
    cfg = make_config(2, True)
    F = cfg.resource_list_factory()
    jdb = JobDb(cfg)
    feed = IncrementalProblemFeed(cfg)
    feed.attach(jdb)

    def upsert(job):
        txn = jdb.write_txn()
        txn.upsert(job)
        txn.commit()

    spec = JobSpec(
        id="a", queue="q0", priority_class="high", submit_time=0.0,
        pools=("p0",),
        resources=F.from_mapping({"cpu": "1", "memory": "1"}),
    )
    upsert(Job(spec=spec, queued=True, validated=True, pools=("p0",)))
    assert feed.pools_independent()
    # unrestricted job: sits in every builder
    free = dataclasses.replace(spec, id="b", pools=())
    upsert(Job(spec=free, queued=True, validated=True))
    assert not feed.pools_independent()
    upsert(Job(spec=free, queued=True, validated=True, cancelled=True))
    assert feed.pools_independent()
    # multi-pool job: sits in two builders
    both = dataclasses.replace(spec, id="c", pools=("p0", "p1"))
    upsert(Job(spec=both, queued=True, validated=True, pools=("p0", "p1")))
    assert not feed.pools_independent()
    upsert(Job(spec=both, queued=True, validated=True, pools=("p0", "p1"),
               cancelled=True))
    assert feed.pools_independent()


# --- 3. verification blast radius -------------------------------------------


def test_verify_failure_in_one_pool_walks_ladder_alone(monkeypatch):
    """round_corrupt drill against the pool-parallel cycle: the one-shot
    header corruption lands in exactly ONE pool's dispatched round; its
    finish raises RoundVerificationError and re-runs on the CPU rung
    bit-equal, the OTHER pools' decisions commit untouched, exactly one
    fallback is recorded, and the device gets a quarantine strike."""
    from armada_tpu.scheduler.quarantine import device_quarantine

    a = run_scenario(False, npools=4, seed=5, verify=True,
                     monkeypatch=monkeypatch)

    faults.reset_counters()
    reset_pool_serving_stats()
    watchdog.reset_supervisor()
    monkeypatch.setenv("ARMADA_POOL_PARALLEL", "1")
    monkeypatch.setenv("ARMADA_VERIFY", "1")
    monkeypatch.setenv("ARMADA_FAULT", "round_corrupt:header")
    world = MultiPoolWorld(4, True, 5)
    b = world.run()
    monkeypatch.delenv("ARMADA_FAULT")

    assert a == b, "the failed pool's ladder re-run must be bit-equal"
    from armada_tpu.models.verify import verify_state

    assert verify_state().failures == 1
    sup = watchdog.supervisor().snapshot()
    assert sup["fallbacks"] == 1, "exactly the corrupted pool fails over"
    assert sum(
        device_quarantine().snapshot()["strike_totals"].values()
    ) >= 1
