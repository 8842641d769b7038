"""Explain-pass attribution (models/explain.py) vs a sequential oracle.

The pass runs on device over the round-final slab; these tests pin its
reason codes against independent host-side recomputation (the parity
discipline):

  - every job attributed ``shape-infeasible`` must fit NO node even empty,
    and every job the oracle finds unfittable must be attributed exactly
    that (shape-infeasibility is static, so the counts must match BOTH
    ways);
  - every FAILED job attributed ``capacity-blocked`` must fit at least one
    empty node (it was blocked by allocations, not its shape);
  - per-reason failed counts must partition ``RoundOutcome.failed``
    exactly, and the reason total must cover every unplaced queued job;
  - the fragmentation forensics must equal the oracle's free-capacity
    arithmetic (quantised exactly like the builder: floor_units for node
    totals, ceil_units for requests).

Multi-seed, BOTH assemble modes (legacy dense build_problem and the
incremental builder's slab path), plus the cadence/transfer-economics
knobs and the reports/metrics/gateway/CLI integration surfaces.

The oracle fit checks deliberately mirror the builder's quantisation
(CLAUDE.md parity discipline); test worlds use node-bound resources only
and no selectors/taints, so empty-node fit is a pure totals comparison.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from armada_tpu.core.config import SchedulingConfig
from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob
from armada_tpu.models import explain as explain_mod
from armada_tpu.models import run_round_on_device, run_scheduling_round
from armada_tpu.models.explain import (
    FAILED_REASONS,
    REASON_NAMES,
    ExplainOutcome,
)

CFG = SchedulingConfig(
    shape_bucket=32,
    # lift the per-round fraction cap so every queued job is ATTEMPTED --
    # the shape/capacity oracle checks need the round to run to exhaustion
    maximum_resource_fraction_to_schedule={},
)
F = CFG.resource_list_factory()

FAILED_NAMES = {REASON_NAMES[r] for r in FAILED_REASONS}


@pytest.fixture(autouse=True)
def armed(monkeypatch):
    """Every round in this module runs the explain pass (interval 1)."""
    monkeypatch.setenv("ARMADA_EXPLAIN_INTERVAL", "1")
    explain_mod.reset_cadence()
    yield


def node(i, cpu=8, mem=32):
    return NodeSpec(
        id=f"n{i:03d}",
        pool="default",
        total_resources=F.from_mapping({"cpu": cpu, "memory": mem}),
    )


def job(i, queue="qa", cpu=2, mem=2, sub=None, **kw):
    return JobSpec(
        id=f"j{i:04d}",
        queue=queue,
        submit_time=float(i if sub is None else sub),
        resources=F.from_mapping({"cpu": cpu, "memory": mem}),
        **kw,
    )


# --- the oracle: quantised exactly like the builder --------------------------


def _req_units(j):
    return F.ceil_units(np.asarray([j.resources.atoms], dtype=np.int64))[0]


def _total_units(n):
    return F.floor_units(
        np.asarray([n.total_resources.atoms], dtype=np.int64)
    )[0]


def fits_some_node_empty(nodes, j):
    req = _req_units(j)
    return any(np.all(_total_units(n) >= req) for n in nodes)


def check_oracle_invariants(nodes, jobs, outcome):
    """The three ISSUE invariants + full coverage of the unplaced set."""
    exp = outcome.explain
    assert exp is not None
    by_id = {j.id: j for j in jobs}
    job_reasons = dict(exp.iter_job_reasons())

    # (a) the per-job reasons cover RoundOutcome.failed exactly, each with
    # a failed-set reason, and the count vector partitions it
    assert set(job_reasons) == set(outcome.failed)
    assert set(job_reasons.values()) <= FAILED_NAMES
    assert sum(exp.failed_counts.values()) == len(outcome.failed)
    for name in FAILED_NAMES:
        assert exp.failed_counts[name] == sum(
            1 for r in job_reasons.values() if r == name
        )

    # (b) shape-infeasible <=> fits no node even empty (static, so exact
    # in both directions across failed AND pending attribution)
    oracle_unfit = {
        j.id for j in jobs if not fits_some_node_empty(nodes, j)
    }
    assert exp.counts["shape-infeasible"] == len(oracle_unfit)
    for jid, reason in job_reasons.items():
        if reason == "shape-infeasible":
            assert jid in oracle_unfit
        # (c) capacity-blocked keys fit at least one empty node
        if reason == "capacity-blocked":
            assert fits_some_node_empty(nodes, by_id[jid])
    assert oracle_unfit.isdisjoint(outcome.scheduled)

    # (d) every unplaced queued job is attributed exactly once
    assert sum(exp.counts.values()) == len(jobs) - len(outcome.scheduled)

    # (e) pending attribution against ROUND-FINAL free capacity (these
    # worlds: no running jobs, no gangs): a pending job that fits no node
    # now is capacity-blocked; one that still fits somewhere was stopped by
    # the round, not by allocations.  Checked per queue, skipping queues
    # the kernel deactivated (a per-(queue, PC) cap trip reports
    # fairness-capped, which shadows the capacity/terminated split).
    free = {n.id: _total_units(n).astype(np.float64) for n in nodes}
    for jid, nid in outcome.scheduled.items():
        free[nid] -= _req_units(by_id[jid])

    def fits_now(j):
        req = _req_units(j)
        return any(np.all(f >= req) for f in free.values())

    pending = [
        j
        for j in jobs
        if j.id not in outcome.scheduled and j.id not in job_reasons
    ]
    for qname in {j.queue for j in jobs}:
        row = exp.queue_counts.get(qname, {})
        if row.get("fairness-capped", 0):
            continue  # killed queue: pending attribution is the kill
        q_pending = [j for j in pending if j.queue == qname]
        q_failed = [
            (jid, r)
            for jid, r in job_reasons.items()
            if by_id[jid].queue == qname
        ]
        expect = {
            "shape-infeasible": sum(
                1 for j in q_pending if not fits_some_node_empty(nodes, j)
            ),
            "capacity-blocked": sum(
                1
                for j in q_pending
                if fits_some_node_empty(nodes, j) and not fits_now(j)
            ),
            "round-terminated": sum(1 for j in q_pending if fits_now(j)),
        }
        for _, r in q_failed:
            expect[r] = expect.get(r, 0) + 1
        for reason, n in expect.items():
            assert row.get(reason, 0) == n, (qname, reason, row, expect)


def mixed_world(seed, num_nodes=8, num_jobs=40, num_queues=3):
    rng = np.random.default_rng(seed)
    nodes = [node(i) for i in range(num_nodes)]
    queues = [Queue(f"q{i}", float(rng.choice([1.0, 2.0]))) for i in range(num_queues)]
    jobs = []
    for i in range(num_jobs):
        big = rng.random() < 0.1
        jobs.append(
            job(
                i,
                queue=f"q{int(rng.integers(num_queues))}",
                cpu=64 if big else int(rng.choice([1, 2, 4, 8])),
                mem=int(rng.choice([1, 2, 4])),
            )
        )
    return nodes, queues, jobs


# --- 1. fast-tier representative: the oracle invariants ----------------------


def test_explain_oracle_invariants_representative():
    """One seed end to end: shape/capacity/partition/coverage oracle plus
    the fragmentation arithmetic (no running jobs: free = totals -
    scheduled)."""
    nodes, queues, jobs = mixed_world(seed=5)
    outcome = run_scheduling_round(
        CFG, pool="default", nodes=nodes, queues=queues, queued_jobs=jobs
    )
    check_oracle_invariants(nodes, jobs, outcome)

    # fragmentation forensics: free capacity oracle in atoms
    by_id = {j.id: j for j in jobs}
    free = {n.id: _total_units(n).astype(np.float64) for n in nodes}
    for jid, nid in outcome.scheduled.items():
        free[nid] -= _req_units(by_id[jid])
    free_mat = np.stack(list(free.values()))
    exp = outcome.explain
    for ri, name in enumerate(F.names):
        frag = exp.fragmentation[name]
        res = F.resolutions[ri]
        assert frag["free"] == int(round(free_mat[:, ri].sum() * res))
        assert frag["largest_request"] == int(
            round(free_mat[:, ri].max() * res)
        )
        if frag["free"] > 0:
            expect = 1.0 - free_mat[:, ri].max() / free_mat[:, ri].sum()
            assert frag["index"] == pytest.approx(expect, abs=1e-5)
        else:
            assert frag["index"] == 0.0


# --- 2. fast-tier representative: the serving-plane integration --------------


def test_explain_through_reports_and_metrics(tmp_path):
    """The full recording path: a real scheduling cycle with explain armed
    feeds job/queue/pool reports, the healthz summary, and the prometheus
    gauges (stale labels removed on the next pass)."""
    from prometheus_client import CollectorRegistry

    from armada_tpu.scheduler.metrics import SchedulerMetrics
    from armada_tpu.scheduler.reports import SchedulingReportsRepository
    from armada_tpu.server import JobSubmitItem, QueueRecord
    from tests.control_plane import ControlPlane

    cp = ControlPlane.build(
        tmp_path,
        # lift the per-round cap so the overflow is ATTEMPTED and lands in
        # the failed set (with per-job reports), not gated pending
        config=SchedulingConfig(
            shape_bucket=32,
            enable_assertions=True,
            maximum_resource_fraction_to_schedule={},
        ),
    )
    try:
        registry = CollectorRegistry()
        cp.scheduler.metrics = SchedulerMetrics(registry=registry)
        cp.scheduler.reports = SchedulingReportsRepository(max_job_reports=100)
        cp.server.create_queue(QueueRecord("heavy", weight=3.0))
        # 2 nodes x 8 cpu: 3-cpu jobs pack 2 per node (2 cpu stranded on
        # each), so the 5th is ATTEMPTED under every cap and fails the
        # per-node fit -- a genuine capacity-blocked failure with per-job
        # reports (statically unfittable shapes never reach a round:
        # SubmitChecker rejects them at admission, mirroring the reference)
        ids = cp.server.submit_jobs(
            "heavy",
            "m",
            [
                JobSubmitItem(resources={"cpu": "3", "memory": "2"})
                for _ in range(6)
            ],
        )
        for ex in cp.executors:
            ex.run_once()
        cp.ingest()
        cp.scheduler.cycle()
        reports = cp.scheduler.reports

        # job reports carry the catalogue reason code for the overflow
        failed_ids = [
            jid
            for jid in ids
            if (reports.job_report(jid) or {}).get("outcome") == "failed"
        ]
        assert failed_ids
        for jid in failed_ids:
            assert reports.job_report(jid)["reason"] == "capacity-blocked"

        # pool report + healthz summary carry the explain block
        pool = reports.pool_report("default")["default"]
        assert pool["explain"]["counts"]["capacity-blocked"] >= 1
        assert "fragmentation" in pool["explain"]
        summary = reports.explain_summary()
        assert "default" in summary and "time" in summary["default"]
        assert summary["default"]["counts"] == pool["explain"]["counts"]

        # queue report: per-reason counts + fairness headroom
        (qr,) = [
            r for r in reports.queue_report("heavy") if r["pool"] == "default"
        ]
        assert qr["unschedulable"].get("capacity-blocked", 0) >= 1
        assert qr["fairness_headroom"] >= 0.0

        # prometheus gauges, then stale-label removal on a later pass
        labels = {
            "pool": "default",
            "queue": "heavy",
            "reason": "capacity-blocked",
        }
        val = registry.get_sample_value(
            "armada_scheduler_unschedulable_jobs", labels
        )
        assert val is not None and val >= 1
        assert (
            registry.get_sample_value(
                "armada_scheduler_fragmentation_index",
                {"pool": "default", "resource": "cpu"},
            )
            is not None
        )
        # cancel the unplaced jobs; the next explain pass must drop the
        # (pool, queue, reason) series instead of exporting a stale count
        cp.server.cancel_jobs("heavy", "m", failed_ids)
        cp.ingest()
        cp.scheduler.cycle()
        assert (
            registry.get_sample_value(
                "armada_scheduler_unschedulable_jobs", labels
            )
            is None
        )
    finally:
        cp.close()


# --- multi-seed oracle, both assemble modes ----------------------------------


@pytest.mark.parametrize("seed", [1, 7, 13, 42])
def test_oracle_invariants_multi_seed(seed):
    nodes, queues, jobs = mixed_world(seed)
    outcome = run_scheduling_round(
        CFG, pool="default", nodes=nodes, queues=queues, queued_jobs=jobs
    )
    check_oracle_invariants(nodes, jobs, outcome)


def run_incremental_round(cfg, nodes, queues, jobs):
    """The slab path: incremental builder -> DeviceDeltaCache ->
    run_round_on_device (the serving plane's round entry, where the explain
    dispatch lives)."""
    from armada_tpu.models.incremental import IncrementalBuilder
    from armada_tpu.models.slab import DeviceDeltaCache

    builder = IncrementalBuilder(cfg, "default", queues)
    builder.set_nodes(nodes)
    builder.submit_many(jobs)
    cache = DeviceDeltaCache()
    bundle, ctx = builder.assemble_delta()
    _res, outcome = run_round_on_device(
        bundle.stats_view(),
        ctx,
        cfg,
        device_problem=lambda: cache.apply(bundle),
        host_problem=bundle.materialize,
    )
    return outcome


@pytest.mark.parametrize("seed", [3, 21])
def test_both_assemble_modes_agree(seed):
    """Legacy dense build vs the incremental slab path: identical reason
    counts and identical per-job failed attribution on the same world."""
    nodes, queues, jobs = mixed_world(seed)
    legacy = run_scheduling_round(
        CFG, pool="default", nodes=nodes, queues=queues, queued_jobs=jobs
    )
    explain_mod.reset_cadence()
    incr = run_incremental_round(CFG, nodes, queues, jobs)
    check_oracle_invariants(nodes, jobs, incr)
    assert incr.explain.counts == legacy.explain.counts
    assert incr.explain.failed_counts == legacy.explain.failed_counts
    assert dict(incr.explain.iter_job_reasons()) == dict(
        legacy.explain.iter_job_reasons()
    )
    assert incr.explain.queue_counts == legacy.explain.queue_counts


# --- reason-specific scenarios -----------------------------------------------


def test_gang_partial_attribution():
    """A gang that passes the per-queue caps but cannot place as a unit
    (free capacity fragmented across nodes) is attributed gang-partial for
    every member."""
    nodes = [node(i) for i in range(3)]
    queues = [Queue("qa", 1.0)]
    running = [
        RunningJob(
            job=job(100 + i, cpu=4, mem=4, sub=0),
            node_id=f"n{i:03d}",
        )
        for i in range(2)
    ]
    gang = [
        JobSpec(
            id=f"g{i}",
            queue="qa",
            submit_time=1.0,
            resources=F.from_mapping({"cpu": 5, "memory": 4}),
            gang_id="gang1",
            gang_cardinality=2,
        )
        for i in range(2)
    ]
    o = run_scheduling_round(
        CFG,
        pool="default",
        nodes=nodes,
        queues=queues,
        queued_jobs=gang,
        running=running,
    )
    assert sorted(o.failed) == ["g0", "g1"]
    assert o.explain.counts["gang-partial"] == 2
    assert dict(o.explain.iter_job_reasons()) == {
        "g0": "gang-partial",
        "g1": "gang-partial",
    }


def test_fairness_capped_attribution():
    """Jobs still pending when their queue trips its per-queue burst are
    fairness-capped (q_killed), not round-terminated -- and they are NOT in
    RoundOutcome.failed (they keep their chance next round)."""
    cfg = SchedulingConfig(
        shape_bucket=32,
        maximum_resource_fraction_to_schedule={},
        maximum_per_queue_scheduling_burst=2,
    )
    nodes = [node(i) for i in range(3)]
    queues = [Queue("qa", 1.0)]
    jobs = [job(i) for i in range(6)]
    o = run_scheduling_round(
        cfg, pool="default", nodes=nodes, queues=queues, queued_jobs=jobs
    )
    assert len(o.scheduled) == 2 and not list(o.failed)
    exp = o.explain
    assert exp.counts["fairness-capped"] == 4
    assert exp.pending_counts["fairness-capped"] == 4
    assert exp.failed_counts["fairness-capped"] == 0


def test_round_terminated_and_shape_dominance():
    """Pending attribution under a round-cap termination: the full-pool
    overflow reads capacity-blocked (nothing fits at round-final free
    capacity), an early stop with capacity left reads round-terminated,
    and statically unfittable jobs report shape-infeasible regardless of
    what stopped the round (shape-infeasibility is time-invariant)."""
    # default config: round cap fraction 1.0 trips exactly when the pool
    # fills -> the overflow is blocked by allocations, not an early stop
    cfg = SchedulingConfig(shape_bucket=32)
    nodes = [node(i) for i in range(4)]
    queues = [Queue("qa", 1.0), Queue("qb", 2.0)]
    jobs = [job(i, queue="qa" if i % 2 else "qb", cpu=4, mem=8) for i in range(20)]
    jobs.append(job(99, queue="qa", cpu=64, sub=99))
    o = run_scheduling_round(
        cfg, pool="default", nodes=nodes, queues=queues, queued_jobs=jobs
    )
    assert o.termination == "round_resource_cap"
    exp = o.explain
    assert exp.counts["shape-infeasible"] == 1
    assert exp.counts["capacity-blocked"] == 12  # 20 queued - 8 placed
    assert exp.counts["round-terminated"] == 0
    # the round never attempted them: pending, not failed
    assert sum(exp.failed_counts.values()) == len(list(o.failed))

    # a HALF-pool round cap stops with free capacity left: the same jobs
    # read round-terminated (a genuinely early stop)
    cfg_half = SchedulingConfig(
        shape_bucket=32,
        maximum_resource_fraction_to_schedule={"cpu": 0.5, "memory": 0.5},
    )
    o2 = run_scheduling_round(
        cfg_half,
        pool="default",
        nodes=nodes,
        queues=queues,
        queued_jobs=[j for j in jobs if j.id != "j0099"],
    )
    assert o2.termination == "round_resource_cap"
    exp2 = o2.explain
    assert exp2.counts["round-terminated"] == 20 - len(o2.scheduled)
    assert exp2.counts["capacity-blocked"] == 0


# --- cadence / transfer economics / truncation -------------------------------


def test_cadence_and_interval_resolution(monkeypatch):
    monkeypatch.setenv("ARMADA_EXPLAIN_INTERVAL", "2")
    explain_mod.reset_cadence()
    assert [explain_mod.explain_due() for _ in range(4)] == [
        True,
        False,
        True,
        False,
    ]
    # 0 and garbage disable; the process default fills in when unset
    monkeypatch.setenv("ARMADA_EXPLAIN_INTERVAL", "0")
    assert explain_mod.explain_interval() == 0
    assert not explain_mod.explain_due()
    monkeypatch.setenv("ARMADA_EXPLAIN_INTERVAL", "nope")
    assert explain_mod.explain_interval() == 0
    monkeypatch.delenv("ARMADA_EXPLAIN_INTERVAL")
    explain_mod.set_default_interval(7)
    try:
        assert explain_mod.explain_interval() == 7
        # env wins over the serve-wired default
        monkeypatch.setenv("ARMADA_EXPLAIN_INTERVAL", "3")
        assert explain_mod.explain_interval() == 3
        # ...but a MALFORMED env value falls back to the default rather
        # than silently disarming a serve-armed pass
        monkeypatch.setenv("ARMADA_EXPLAIN_INTERVAL", "10s")
        assert explain_mod.explain_interval() == 7
    finally:
        explain_mod.set_default_interval(0)


def test_cadence_per_pool_no_aliasing(monkeypatch):
    """Counters are PER POOL: a global counter ticking once per pool-round
    aliases whenever gcd(num_pools, interval) > 1 (2 pools at interval 2
    would attribute pool a forever and pool b never)."""
    monkeypatch.setenv("ARMADA_EXPLAIN_INTERVAL", "2")
    explain_mod.reset_cadence()
    seq = [
        (explain_mod.explain_due("a"), explain_mod.explain_due("b"))
        for _ in range(4)
    ]
    assert seq == [(True, True), (False, False), (True, True), (False, False)]


def test_arm_default_tokens_survive_overlap(monkeypatch):
    """Plane defaults are token-armed (the watchdog discipline): two
    overlapping planes and a non-LIFO stop never corrupt the default."""
    monkeypatch.delenv("ARMADA_EXPLAIN_INTERVAL", raising=False)
    t_a = explain_mod.arm_default(10)
    t_b = explain_mod.arm_default(5)
    try:
        assert explain_mod.explain_interval() == 5  # latest armed wins
        explain_mod.disarm_default(t_a)  # plane A stops FIRST
        assert explain_mod.explain_interval() == 5  # B keeps its cadence
    finally:
        explain_mod.disarm_default(t_a)
        explain_mod.disarm_default(t_b)
    assert explain_mod.explain_interval() == 0  # library default restored


def test_failover_round_keeps_attribution(monkeypatch):
    """A mid-kernel device loss must not consume an extra cadence tick:
    the cadence decision is made ONCE per scheduling round in
    run_round_on_device, so the committed (failed-over) re-run keeps the
    attribution the device attempt was armed for."""
    import armada_tpu.models as models_pkg
    from armada_tpu.core import watchdog

    from jax.errors import JaxRuntimeError as XlaError

    monkeypatch.setenv("ARMADA_EXPLAIN_INTERVAL", "2")
    monkeypatch.setenv("ARMADA_WATCHDOG_S", "60")
    explain_mod.reset_cadence()
    real = models_pkg.schedule_round
    fired = []

    def dying_kernel(*a, **kw):
        if not fired:
            fired.append(True)
            raise XlaError("injected mid-kernel device loss")
        return real(*a, **kw)

    monkeypatch.setattr(models_pkg, "schedule_round", dying_kernel)
    nodes_, queues_, jobs_ = mixed_world(3)
    sup = watchdog.supervisor()
    try:
        outcome = run_scheduling_round(
            CFG,
            pool="default",
            nodes=nodes_,
            queues=queues_,
            queued_jobs=jobs_,
        )
        assert fired and sup.degraded
        # tick 0 (due at interval 2) armed this round; the failover re-run
        # must carry its attribution, not consume tick 1
        assert outcome.explain is not None
        check_oracle_invariants(nodes_, jobs_, outcome)
    finally:
        sup.promote()


def test_reports_cover_unpaired_failed_jobs():
    """Explain cycles must never answer FEWER failed jobs than plain
    cycles: outcome.failed entries the pass did not pair (decode-time
    gang unwinds, gangs past the fcap) still get the generic report."""
    import types

    from armada_tpu.scheduler.reports import SchedulingReportsRepository

    reports = SchedulingReportsRepository()
    zero = {name: 0 for name in REASON_NAMES[1:]}
    exp = ExplainOutcome(
        counts=dict(zero, **{"capacity-blocked": 1}),
        failed_counts=dict(zero, **{"capacity-blocked": 1}),
        pending_counts=dict(zero),
        queue_counts={},
        key_reasons=[],
        fragmentation={},
        _failed_idx=np.array([0]),
        _failed_reason=np.array([explain_mod.REASON_CAPACITY]),
        _ctx=types.SimpleNamespace(members_of=lambda gi: ["j1"]),
    )
    o = types.SimpleNamespace(
        failed=["j1", "j2"],
        scheduled={},
        preempted=[],
        explain=exp,
        queue_stats={},
        num_iterations=1,
        termination="exhausted",
    )
    stats = types.SimpleNamespace(
        pool="default", outcome=o, num_nodes=1, num_queued=2, num_running=0
    )
    result = types.SimpleNamespace(scheduled=[], preempted=[], pools=[stats])
    reports.record_cycle(result, now=1.0)
    assert reports.job_report("j1")["reason"] == "capacity-blocked"
    assert reports.job_report("j2")["reason"].startswith("no node")


def test_disabled_pass_costs_nothing(monkeypatch):
    """Interval 0 (the library/test default): no explain outcome and no
    extra device->host transfer; armed, the pass adds EXACTLY ONE."""
    from armada_tpu.models.xfer import TRANSFER_STATS

    nodes, queues, jobs = mixed_world(seed=11)

    monkeypatch.setenv("ARMADA_EXPLAIN_INTERVAL", "0")
    TRANSFER_STATS.reset()
    o_off = run_scheduling_round(
        CFG, pool="default", nodes=nodes, queues=queues, queued_jobs=jobs
    )
    down_off = TRANSFER_STATS.snapshot()["down_transfers"]
    assert o_off.explain is None

    monkeypatch.setenv("ARMADA_EXPLAIN_INTERVAL", "1")
    explain_mod.reset_cadence()
    TRANSFER_STATS.reset()
    o_on = run_scheduling_round(
        CFG, pool="default", nodes=nodes, queues=queues, queued_jobs=jobs
    )
    down_on = TRANSFER_STATS.snapshot()["down_transfers"]
    assert o_on.explain is not None
    assert down_on == down_off + 1
    assert sorted(o_on.scheduled) == sorted(o_off.scheduled)


def test_truncation_flags(monkeypatch):
    """Shrunken packing caps trip the truncation flags instead of lying:
    more live keys than kcap -> truncated_keys; more failed gangs than
    fcap -> job_reasons_complete False (aggregate counts stay exact)."""
    monkeypatch.setattr(explain_mod, "_EXPLAIN_KCAP", 2)
    monkeypatch.setattr(explain_mod, "_EXPLAIN_FCAP", 3)
    nodes = [node(0, cpu=2, mem=4)]
    queues = [Queue("qa", 1.0)]
    # 6 distinct oversized shapes -> 6 live keys, all unplaced
    jobs = [job(i, cpu=4 + i, mem=8 + i) for i in range(6)]
    o = run_scheduling_round(
        CFG, pool="default", nodes=nodes, queues=queues, queued_jobs=jobs
    )
    exp = o.explain
    assert exp is not None
    assert exp.truncated_keys
    assert len(exp.key_reasons) == 2
    assert exp.counts["shape-infeasible"] == 6  # aggregates stay exact
    if len(list(o.failed)) > 3:
        assert not exp.job_reasons_complete


# --- gateway / lookout / CLI surfaces ----------------------------------------


def _fake_explain():
    return ExplainOutcome(
        counts={"capacity-blocked": 2},
        failed_counts={"capacity-blocked": 2},
        pending_counts={},
        queue_counts={"qa": {"capacity-blocked": 2}},
        key_reasons=[{"key": 0, "reason": "capacity-blocked", "jobs": 2}],
        fragmentation={"cpu": {"free": 8, "largest_request": 4, "index": 0.5}},
    )


def _record_fake_cycle(reports):
    """Populate a reports repo through its public recording API."""
    import types

    exp = _fake_explain()
    exp._failed_idx = np.array([0, 1])
    exp._failed_reason = np.array([2, 2])  # REASON_CAPACITY
    exp._ctx = types.SimpleNamespace(members_of=lambda gi: [f"jx{gi}"])
    outcome = types.SimpleNamespace(
        explain=exp,
        scheduled={},
        preempted=[],
        failed=["jx0", "jx1"],
        num_iterations=3,
        termination="exhausted",
        queue_stats={
            "qa": {
                "weight": 1.0,
                "fair_share": 0.5,
                "adjusted_fair_share": 0.5,
                "actual_share": 0.25,
                "demand_share": 0.9,
            }
        },
    )
    stats = types.SimpleNamespace(
        pool="default",
        outcome=outcome,
        num_nodes=1,
        num_queued=4,
        num_running=0,
    )
    result = types.SimpleNamespace(scheduled=[], preempted=[], pools=[stats])
    reports.record_cycle(result, now=123.0)


def test_gateway_explain_routes_and_lookout_details():
    """/v1/reports/explain[/{job}] + job details scheduling_report: the
    operator-reachable end of the reason codes."""
    from armada_tpu.scheduler.reports import SchedulingReportsRepository
    from armada_tpu.server.gateway import RestGateway

    class _Stub:
        pass

    class _StubQueries:
        def get_job_details(self, job_id):
            if job_id == "jx0":
                return {"job_id": "jx0", "state": "queued"}
            return None

    reports = SchedulingReportsRepository()
    _record_fake_cycle(reports)

    gw = RestGateway(
        _Stub(),
        _Stub(),
        port=0,
        lookout_queries=_StubQueries(),
        reports=reports,
    )
    try:
        base = f"http://127.0.0.1:{gw.port}"
        with urllib.request.urlopen(f"{base}/v1/reports/explain/jx0") as r:
            body = json.loads(r.read())
        assert body["reason"] == "capacity-blocked"
        assert body["outcome"] == "failed"

        with urllib.request.urlopen(f"{base}/v1/reports/explain") as r:
            pools = json.loads(r.read())
        assert pools["default"]["counts"] == {"capacity-blocked": 2}

        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"{base}/v1/reports/explain/zzz")
        assert e.value.code == 404

        # lookout job details carry the scheduling report alongside
        with urllib.request.urlopen(f"{base}/v1/job/jx0/details") as r:
            details = json.loads(r.read())
        assert details["scheduling_report"]["reason"] == "capacity-blocked"
    finally:
        gw.stop()


def test_lookout_webui_job_details_report():
    from armada_tpu.lookout.webui import LookoutWebUI
    from armada_tpu.scheduler.reports import SchedulingReportsRepository

    class _StubQueries:
        def get_job_details(self, job_id):
            return {"job_id": job_id} if job_id == "jx1" else None

    reports = SchedulingReportsRepository()
    _record_fake_cycle(reports)
    ui = LookoutWebUI(_StubQueries(), port=0, reports=reports)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{ui.port}/api/job/jx1"
        ) as r:
            details = json.loads(r.read())
        assert details["scheduling_report"]["reason"] == "capacity-blocked"
    finally:
        ui.stop()


def test_preemptor_attribution_in_reports():
    """Satellite: a preempted job's report names the preempting queue and
    priority when the same cycle scheduled onto the freed node."""
    import types

    from armada_tpu.scheduler.reports import SchedulingReportsRepository

    reports = SchedulingReportsRepository()
    pje = types.SimpleNamespace(id="victim", queue="low")
    prun = types.SimpleNamespace(node_id="n1")
    sje = types.SimpleNamespace(id="winner", queue="high")
    srun = types.SimpleNamespace(
        node_id="n1",
        scheduled_at_priority=900,
        executor="ex1",
        pool="default",
        priority=900,
    )
    result = types.SimpleNamespace(
        scheduled=[(sje, srun)], preempted=[(pje, prun)], pools=[]
    )
    reports.record_cycle(result, now=5.0)
    jr = reports.job_report("victim")
    assert jr["preemptor_job"] == "winner"
    assert jr["preemptor_queue"] == "high"
    assert jr["preemptor_priority"] == 900
    assert "high" in jr["reason"]
    # and the winner's own report is the usual scheduled record
    assert reports.job_report("winner")["outcome"] == "scheduled"


def test_armadactl_explain_cli(tmp_path, capsys, monkeypatch):
    """`armadactl explain` end to end against a live plane: job-level
    reason code and the pool forensics view."""
    import threading
    import time

    from armada_tpu.cli.armadactl import main
    from armada_tpu.cli.serve import run_fake_executor, start_control_plane
    from armada_tpu.server import JobSubmitItem

    monkeypatch.setenv("ARMADA_EXPLAIN_INTERVAL", "1")
    explain_mod.reset_cadence()
    cfg = SchedulingConfig(
        shape_bucket=32, maximum_resource_fraction_to_schedule={}
    )
    plane = start_control_plane(
        str(tmp_path / "data"),
        port=0,
        config=cfg,
        cycle_interval_s=0.05,
        schedule_interval_s=0.1,
    )
    stop = threading.Event()
    agent = threading.Thread(
        target=run_fake_executor,
        args=(f"127.0.0.1:{plane.port}",),
        kwargs={
            "executor_id": "t-ex",
            "num_nodes": 2,
            "cpu": "8",
            "memory": "32",
            "interval_s": 0.05,
            "stop": stop,
            "config": cfg,
        },
        daemon=True,
    )
    agent.start()
    try:
        url = f"127.0.0.1:{plane.port}"
        assert main(["--url", url, "queue", "create", "qa"]) == 0
        # 2 nodes x 8 cpu: 3-cpu jobs pack 2 per node, so the 5th is
        # attempted and fails the per-node fit -- capacity-blocked
        # (statically unfittable shapes are rejected at admission and
        # never reach a round)
        ids = plane.submit_server.submit_jobs(
            "qa",
            "s",
            [
                JobSubmitItem(resources={"cpu": "3", "memory": "2"})
                for _ in range(6)
            ],
        )
        # wait for the overflow to flow through ingest + a scheduling
        # cycle into a recorded failed report
        failed_id = None
        deadline = time.time() + 60
        while time.time() < deadline and failed_id is None:
            for jid in ids:
                r = plane.scheduler.reports.job_report(jid)
                if r is not None and r.get("outcome") == "failed":
                    failed_id = jid
                    break
            time.sleep(0.1)
        assert failed_id is not None, "no capacity-blocked overflow observed"
        capsys.readouterr()
        assert main(["--url", url, "explain", failed_id]) == 0
        out = capsys.readouterr().out
        assert "capacity-blocked" in out
        assert main(["--url", url, "explain"]) == 0
        out = capsys.readouterr().out
        assert "capacity-blocked" in out or "no explain pass" in out
    finally:
        stop.set()
        plane.stop()
    # the plane's serve-armed process default (10) must not leak into
    # library embedders in the same process: stop() restores the prior one
    monkeypatch.delenv("ARMADA_EXPLAIN_INTERVAL")
    assert explain_mod.explain_interval() == 0
