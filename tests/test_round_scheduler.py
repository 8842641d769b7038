"""Whole-round scenarios for the tensorised scheduling round.

Modeled on the reference's table-driven scheduler tests
(internal/scheduler/scheduling/preempting_queue_scheduler_test.go,
queue_scheduler_test.go, gang_scheduler_test.go): small clusters, explicit
expectations about which jobs schedule, fail, or get preempted.
"""

import dataclasses

import numpy as np
import pytest

from armada_tpu.core.config import PriorityClass, SchedulingConfig
from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob, Taint, Toleration
from armada_tpu.models import run_scheduling_round


def make_config(**overrides) -> SchedulingConfig:
    base = dict(
        supported_resource_types=(("memory", "1Mi"), ("cpu", "1m"), ("nvidia.com/gpu", "1")),
        priority_classes={
            "p0": PriorityClass("p0", priority=0, preemptible=True),
            "p1": PriorityClass("p1", priority=1, preemptible=True),
            "p2": PriorityClass("p2", priority=2, preemptible=False),
        },
        default_priority_class="p1",
        dominant_resource_fairness_resources=("cpu", "memory", "nvidia.com/gpu"),
        shape_bucket=8,
        maximum_scheduling_burst=1_000_000,
        maximum_per_queue_scheduling_burst=1_000_000,
        maximum_resource_fraction_to_schedule={},
    )
    base.update(overrides)
    return SchedulingConfig(**base)


_factory_cache = {}


def rl(config, **q):
    key = config.supported_resource_types
    f = _factory_cache.get(key)
    if f is None:
        f = config.resource_list_factory()
        _factory_cache[key] = f
    return f.from_mapping({k.replace("gpu", "nvidia.com/gpu") if k == "gpu" else k: v for k, v in q.items()})


def node(config, nid, cpu="1", memory="1Gi", **kw):
    return NodeSpec(nid, total_resources=rl(config, cpu=cpu, memory=memory, **kw.pop("extra", {})), **kw)


def job(config, jid, queue, cpu="1", memory="128Mi", pc="p1", **kw):
    return JobSpec(jid, queue, priority_class=pc, resources=rl(config, cpu=cpu, memory=memory), **kw)


def run_round(config, nodes, queues, jobs, running=()):
    return run_scheduling_round(
        config, pool="default", nodes=nodes, queues=queues, queued_jobs=jobs, running=running
    )


# ---------------------------------------------------------------------------


def test_single_queue_fifo_capacity():
    cfg = make_config()
    nodes = [node(cfg, "n0", cpu="2", memory="4Gi")]
    jobs = [job(cfg, f"j{i}", "A", cpu="1") for i in range(3)]
    out = run_round(cfg, nodes, [Queue("A")], jobs)
    assert len(out.scheduled) == 2
    # third identical job retired via the unfeasible scheduling key
    assert set(out.failed) == {"j2"} or len(out.failed) == 1
    assert out.preempted == []
    assert all(v == "n0" for v in out.scheduled.values())


def test_two_queue_fair_split():
    cfg = make_config()
    nodes = [node(cfg, f"n{i}", cpu="1", memory="2Gi") for i in range(10)]
    jobs = [job(cfg, f"a{i}", "A", cpu="1") for i in range(10)] + [
        job(cfg, f"b{i}", "B", cpu="1") for i in range(10)
    ]
    out = run_round(cfg, nodes, [Queue("A"), Queue("B")], jobs)
    a = sum(1 for j in out.scheduled if j.startswith("a"))
    b = sum(1 for j in out.scheduled if j.startswith("b"))
    assert a == 5 and b == 5


def test_weighted_fair_split():
    cfg = make_config()
    nodes = [node(cfg, f"n{i}", cpu="1", memory="2Gi") for i in range(12)]
    jobs = [job(cfg, f"a{i}", "A", cpu="1") for i in range(12)] + [
        job(cfg, f"b{i}", "B", cpu="1") for i in range(12)
    ]
    out = run_round(cfg, nodes, [Queue("A", weight=3.0), Queue("B", weight=1.0)], jobs)
    a = sum(1 for j in out.scheduled if j.startswith("a"))
    b = sum(1 for j in out.scheduled if j.startswith("b"))
    assert a == 9 and b == 3


def test_priority_class_order_within_queue():
    cfg = make_config()
    nodes = [node(cfg, "n0", cpu="1", memory="2Gi")]
    jobs = [
        job(cfg, "low", "A", cpu="1", pc="p0", submit_time=0.0),
        job(cfg, "high", "A", cpu="1", pc="p2", submit_time=1.0),
    ]
    out = run_round(cfg, nodes, [Queue("A")], jobs)
    assert "high" in out.scheduled and "low" not in out.scheduled


def test_job_priority_and_submit_time_order():
    cfg = make_config()
    nodes = [node(cfg, "n0", cpu="1", memory="2Gi")]
    jobs = [
        job(cfg, "later", "A", cpu="1", submit_time=5.0),
        job(cfg, "earlier", "A", cpu="1", submit_time=1.0),
        job(cfg, "urgent", "A", cpu="1", submit_time=9.0, priority=-5),
    ]
    out = run_round(cfg, nodes, [Queue("A")], jobs)
    assert list(out.scheduled) == ["urgent"]


def test_unfeasible_key_mass_skip():
    cfg = make_config()
    nodes = [node(cfg, "n0", cpu="4", memory="4Gi")]
    sel = {"zone": "mars"}
    jobs = [
        JobSpec(f"m{i}", "A", priority_class="p1", resources=rl(cfg, cpu="1", memory="128Mi"), node_selector=sel)
        for i in range(50)
    ] + [job(cfg, "ok", "A", cpu="1")]
    out = run_round(cfg, nodes, [Queue("A")], jobs)
    assert list(out.scheduled) == ["ok"]
    assert len(out.failed) == 50
    # one fit attempt retired all 50 identical jobs: far fewer iterations than jobs
    assert out.num_iterations <= 10


def test_gang_all_or_nothing():
    cfg = make_config()
    nodes = [node(cfg, f"n{i}", cpu="1", memory="2Gi") for i in range(2)]
    too_big = [
        job(cfg, f"g3-{i}", "A", cpu="1", gang_id="g3", gang_cardinality=3) for i in range(3)
    ]
    out = run_round(cfg, nodes, [Queue("A")], too_big)
    assert out.scheduled == {}
    fits = [job(cfg, f"g2-{i}", "A", cpu="1", gang_id="g2", gang_cardinality=2) for i in range(2)]
    out = run_round(cfg, nodes, [Queue("A")], fits)
    assert set(out.scheduled) == {"g2-0", "g2-1"}
    assert set(out.scheduled.values()) == {"n0", "n1"}


def test_gang_packs_multiple_members_per_node():
    cfg = make_config()
    nodes = [node(cfg, f"n{i}", cpu="2", memory="4Gi") for i in range(2)]
    gang = [job(cfg, f"g-{i}", "A", cpu="1", gang_id="g", gang_cardinality=4) for i in range(4)]
    out = run_round(cfg, nodes, [Queue("A")], gang)
    assert len(out.scheduled) == 4
    from collections import Counter

    counts = Counter(out.scheduled.values())
    assert counts["n0"] == 2 and counts["n1"] == 2


def test_fair_share_preemption_rebalances():
    cfg = make_config(protected_fraction_of_fair_share=0.5)
    nodes = [node(cfg, f"n{i}", cpu="1", memory="2Gi") for i in range(4)]
    running = [
        RunningJob(job(cfg, f"a{i}", "A", cpu="1", pc="p0"), node_id=f"n{i}") for i in range(4)
    ]
    newjobs = [job(cfg, f"b{i}", "B", cpu="1", pc="p0") for i in range(4)]
    out = run_round(cfg, nodes, [Queue("A"), Queue("B")], newjobs, running)
    b = [j for j in out.scheduled if j.startswith("b")]
    assert len(b) == 2
    assert len(out.preempted) == 2
    assert all(p.startswith("a") for p in out.preempted)


def test_protected_fair_share_blocks_eviction():
    cfg = make_config(protected_fraction_of_fair_share=100.0)
    nodes = [node(cfg, f"n{i}", cpu="1", memory="2Gi") for i in range(4)]
    running = [
        RunningJob(job(cfg, f"a{i}", "A", cpu="1", pc="p0"), node_id=f"n{i}") for i in range(4)
    ]
    newjobs = [job(cfg, f"b{i}", "B", cpu="1", pc="p0") for i in range(2)]
    out = run_round(cfg, nodes, [Queue("A"), Queue("B")], newjobs, running)
    assert out.scheduled == {}
    assert out.preempted == []


def test_urgency_preemption_displaces_lower_priority():
    cfg = make_config(protected_fraction_of_fair_share=100.0)
    nodes = [node(cfg, "n0", cpu="1", memory="2Gi")]
    running = [RunningJob(job(cfg, "victim", "A", cpu="1", pc="p0"), node_id="n0")]
    newjobs = [job(cfg, "urgent", "B", cpu="1", pc="p2")]
    out = run_round(cfg, nodes, [Queue("A"), Queue("B")], newjobs, running)
    assert out.scheduled == {"urgent": "n0"}
    assert out.preempted == ["victim"]


def test_urgency_preemption_prefers_clean_node():
    cfg = make_config(protected_fraction_of_fair_share=100.0)
    nodes = [node(cfg, "busy", cpu="1", memory="2Gi"), node(cfg, "free", cpu="1", memory="2Gi")]
    running = [RunningJob(job(cfg, "victim", "A", cpu="1", pc="p0"), node_id="busy")]
    newjobs = [job(cfg, "urgent", "B", cpu="1", pc="p2")]
    out = run_round(cfg, nodes, [Queue("A"), Queue("B")], newjobs, running)
    assert out.scheduled == {"urgent": "free"}
    assert out.preempted == []


def test_non_preemptible_running_job_survives():
    cfg = make_config(protected_fraction_of_fair_share=0.0)
    nodes = [node(cfg, "n0", cpu="1", memory="2Gi")]
    running = [RunningJob(job(cfg, "rock", "A", cpu="1", pc="p2"), node_id="n0")]
    newjobs = [job(cfg, "wish", "B", cpu="1", pc="p2")]
    out = run_round(cfg, nodes, [Queue("A"), Queue("B")], newjobs, running)
    assert out.scheduled == {}
    assert out.preempted == []


def test_node_selector_and_taints():
    cfg = make_config()
    tainted = NodeSpec(
        "gpu0",
        total_resources=rl(cfg, cpu="4", memory="8Gi"),
        taints=(Taint("gpu", "true", "NoSchedule"),),
        labels={"zone": "a"},
    )
    plain = NodeSpec("cpu0", total_resources=rl(cfg, cpu="4", memory="8Gi"), labels={"zone": "b"})
    jobs = [
        JobSpec(
            "gpu-job",
            "A",
            priority_class="p1",
            resources=rl(cfg, cpu="1", memory="128Mi"),
            tolerations=(Toleration("gpu", "Exists"),),
            node_selector={"zone": "a"},
        ),
        job(cfg, "cpu-job", "A", cpu="1"),
    ]
    out = run_round(cfg, [tainted, plain], [Queue("A")], jobs)
    assert out.scheduled["gpu-job"] == "gpu0"
    assert out.scheduled["cpu-job"] == "cpu0"  # taint repels the plain job


def test_global_burst_cap():
    cfg = make_config(maximum_scheduling_burst=2)
    nodes = [node(cfg, f"n{i}", cpu="1", memory="2Gi") for i in range(5)]
    jobs = [job(cfg, f"j{i}", "A", cpu="1") for i in range(5)]
    out = run_round(cfg, nodes, [Queue("A")], jobs)
    assert len(out.scheduled) == 2
    assert out.termination == "global_burst"
    assert out.failed == []  # remaining jobs were not attempted, not failed


def test_per_queue_resource_fraction_cap():
    pcs = {
        "p1": PriorityClass(
            "p1", priority=1, preemptible=True, maximum_resource_fraction_per_queue={"cpu": 0.5}
        )
    }
    cfg = make_config(priority_classes=pcs, default_priority_class="p1")
    nodes = [node(cfg, f"n{i}", cpu="1", memory="2Gi") for i in range(4)]
    jobs = [job(cfg, f"a{i}", "A", cpu="1", pc="p1") for i in range(4)] + [
        job(cfg, f"b{i}", "B", cpu="1", pc="p1") for i in range(4)
    ]
    out = run_round(cfg, nodes, [Queue("A"), Queue("B")], jobs)
    a = sum(1 for j in out.scheduled if j.startswith("a"))
    b = sum(1 for j in out.scheduled if j.startswith("b"))
    assert a == 2 and b == 2


def test_round_resource_fraction_cap():
    cfg = make_config(maximum_resource_fraction_to_schedule={"cpu": 0.25})
    nodes = [node(cfg, f"n{i}", cpu="1", memory="2Gi") for i in range(8)]
    jobs = [job(cfg, f"j{i}", "A", cpu="1") for i in range(8)]
    out = run_round(cfg, nodes, [Queue("A")], jobs)
    assert len(out.scheduled) == 2
    assert out.termination == "round_resource_cap"


def test_round_is_pure_and_repeatable():
    cfg = make_config()
    nodes = [node(cfg, f"n{i}", cpu="2", memory="4Gi") for i in range(3)]
    jobs = [job(cfg, f"j{i}", "A", cpu="1") for i in range(5)]
    out1 = run_round(cfg, nodes, [Queue("A")], jobs)
    out2 = run_round(cfg, nodes, [Queue("A")], jobs)
    assert out1.scheduled == out2.scheduled
    assert out1.preempted == out2.preempted


def test_prefer_large_job_ordering():
    """enablePreferLargeJobOrdering (queue_scheduler.go Less:598-626): on an
    empty farm (equal current costs) the larger gang goes first; the default
    ordering prefers the cheaper proposed cost instead."""
    import dataclasses

    from armada_tpu.core.config import SchedulingConfig
    from armada_tpu.core.types import JobSpec, NodeSpec, Queue
    from armada_tpu.models import run_scheduling_round

    # burst 1: only the FIRST candidate schedules, exposing the ordering.
    # Both queues stay within their budgets (4/8 and 2/8 vs fair 0.5/0.25).
    cfg = SchedulingConfig(shape_bucket=32, maximum_scheduling_burst=1)
    f = cfg.resource_list_factory()
    nodes = [
        NodeSpec(id="n0", pool="default",
                 total_resources=f.from_mapping({"cpu": "8", "memory": "32"}))
    ]
    queues = [Queue("big"), Queue("small")]
    jobs = [
        JobSpec(id="jb", queue="big",
                resources=f.from_mapping({"cpu": "4", "memory": "2"})),
        JobSpec(id="js", queue="small",
                resources=f.from_mapping({"cpu": "2", "memory": "2"})),
    ]
    # default: cheapest proposed cost first -> the small job goes first
    base = run_scheduling_round(
        cfg, pool="default", nodes=nodes, queues=queues, queued_jobs=jobs
    )
    assert "js" in base.scheduled and "jb" not in base.scheduled

    # prefer-large: equal current costs (empty farm), larger job first
    plcfg = dataclasses.replace(cfg, enable_prefer_large_job_ordering=True)
    pl = run_scheduling_round(
        plcfg, pool="default", nodes=nodes, queues=queues, queued_jobs=jobs
    )
    assert "jb" in pl.scheduled and "js" not in pl.scheduled


# --- the chip's body against XLA:CPU's ----------------------------------------
# schedule_round compiles one of two bodies from the platform: the uncached one
# on an accelerator (cache_slots=0), the per-key fit cache on XLA:CPU (what the
# watchdog's CPU failover serves).  The cache is exact memoisation, so the two
# must agree on EVERY RoundResult field, counters included.  One case a world.


def _synthetic_world(seed, gangs):
    from armada_tpu.models.synthetic import synthetic_problem

    problem, meta = synthetic_problem(
        num_nodes=400, num_gangs=4000, num_queues=16, num_runs=300,
        global_burst=250, perq_burst=60, seed=seed,
        max_gang_cardinality=gangs,
    )
    kw = dict(
        num_levels=meta["num_levels"], max_slots=meta["max_slots"],
        slot_width=meta["slot_width"],
    )
    return [(problem, kw, lambda r: None)]


def _built(cfg, nodes, queues, jobs, running=(), bid=None, check=lambda r: None):
    from armada_tpu.models import build_problem

    problem, ctx = build_problem(
        cfg, pool="default", nodes=nodes, queues=queues,
        queued_jobs=jobs, running=running, bid_price_of=bid,
    )
    kw = dict(
        num_levels=len(ctx.ladder) + 2, max_slots=ctx.max_slots,
        slot_width=ctx.slot_width,
    )
    return (problem, kw, check)


def _market_config(cfg):
    from armada_tpu.core.config import PoolConfig

    return dataclasses.replace(
        cfg,
        pools=(PoolConfig("default", market_driven=True, spot_price_cutoff=0.1),),
    )


def _crossed(r):
    assert float(r.spot_price) >= 0  # the spot-price crossing actually happened


def _mixed_world(market):
    """Evictee (pinned-node) and market (bid ordering, spot crossing) rounds:
    synthetic problems never produce evictee gangs or market pools, so these
    come from real builder worlds."""
    rng = np.random.default_rng(11)
    cfg = make_config()
    nodes = [
        node(cfg, f"n{i:03d}", cpu=str(int(rng.choice([4, 8]))), memory="32Gi")
        for i in range(40)
    ]
    queues = [Queue(f"q{i}", 1.0 + i % 2) for i in range(5)]
    jobs = [
        job(cfg, f"j{i:03d}", f"q{int(rng.integers(5))}",
            cpu=str(int(rng.choice([1, 2]))))
        for i in range(120)
    ]
    running = [
        RunningJob(
            job=job(cfg, f"r{i:03d}", f"q{int(rng.integers(5))}", cpu="2"),
            node_id=f"n{int(rng.integers(40)):03d}",
        )
        for i in range(40)
    ]
    if market:
        prices = {f"q{i}": float(1 + i) for i in range(5)}
        return [_built(_market_config(cfg), nodes, queues, jobs, running,
                       bid=lambda j: prices[j.queue], check=_crossed)]

    # protected_fraction 0 evicts every preemptible run; pinned
    # re-placements take the evictee path of both bodies
    def rescheduled(r):
        assert bool(np.asarray(r.run_rescheduled).any())

    evict_cfg = dataclasses.replace(cfg, protected_fraction_of_fair_share=0.0)
    return [_built(evict_cfg, nodes, queues, jobs, running, check=rescheduled)]


def _conflict_world(seed):
    """Conflict-heavy shapes: many jobs contending for ONE node (same-node
    stacking until it fills), one queue dominating the order (the DRF
    monopoly), gangs interleaved with singletons, with and without an
    eviction pass."""
    rng = np.random.default_rng(seed)
    cfg = make_config()
    # ONE big node + a handful of tiny ones: best-fit funnels every pick
    # onto the big node until it fills.
    nodes = [node(cfg, "big", cpu="64", memory="256Gi")] + [
        node(cfg, f"n{i}", cpu="2", memory="8Gi") for i in range(6)
    ]
    queues = [Queue(f"q{i}", 1.0) for i in range(4)]
    jobs = []
    for i in range(90):
        # queue 0 dominates: weight-equal but 3x the jobs, so the argmin
        # repeatedly returns to it
        qn = "q0" if i % 2 == 0 else f"q{int(rng.integers(1, 4))}"
        jobs.append(
            job(cfg, f"j{i:03d}", qn, cpu=str(int(rng.choice([1, 2]))),
                submit_time=float(i))
        )
    for g in range(6):
        for m in range(3):
            jobs.append(
                JobSpec(
                    f"g{g}m{m}", f"q{g % 4}", priority_class="p1",
                    submit_time=100.0 + g,
                    resources=rl(cfg, cpu="2", memory="128Mi"),
                    gang_id=f"gang{g}", gang_cardinality=3,
                )
            )
    running = [
        RunningJob(
            job=job(cfg, f"r{i:02d}", f"q{int(rng.integers(4))}", cpu="2",
                    pc="p0"),
            node_id="big" if i % 3 == 0 else f"n{int(rng.integers(6))}",
        )
        for i in range(12)
    ]

    def evicted(r):
        assert bool(np.asarray(r.run_evicted).any())

    evict_cfg = dataclasses.replace(cfg, protected_fraction_of_fair_share=0.0)
    return [
        _built(cfg, nodes, queues, jobs, running),
        _built(evict_cfg, nodes, queues, jobs, running, check=evicted),
    ]


def _market_world():
    cfg = _market_config(make_config())
    nodes = [node(cfg, f"n{i}", cpu="8", memory="32Gi") for i in range(8)]
    queues = [Queue(f"q{i}", 1.0) for i in range(4)]
    prices = {f"q{i}": float(1 + i) for i in range(4)}
    jobs = [
        job(cfg, f"j{i:03d}", f"q{i % 4}", cpu="1", submit_time=float(i))
        for i in range(60)
    ]
    return [_built(cfg, nodes, queues, jobs, bid=lambda j: prices[j.queue],
                   check=_crossed)]


@pytest.mark.parametrize(
    "world",
    [
        # Synthetic label keys are shared by different-shaped gangs: the fit
        # cache must verify (request, level), not trust the key alone, or it
        # reuses foreign fit rows and silently mis-places (found round 3).
        pytest.param(lambda: _synthetic_world(0, 1), id="synthetic-singles"),
        pytest.param(lambda: _synthetic_world(3, 3), id="synthetic-gangs-seed3"),
        pytest.param(lambda: _synthetic_world(0, 3), id="synthetic-gangs-seed0"),
        pytest.param(lambda: _mixed_world(market=False), id="evictees"),
        pytest.param(lambda: _mixed_world(market=True), id="market-mixed"),
        pytest.param(lambda: _conflict_world(0), id="conflicts-seed0"),
        pytest.param(lambda: _conflict_world(3), id="conflicts-seed3"),
        pytest.param(lambda: _conflict_world(11), id="conflicts-seed11"),
        pytest.param(_market_world, id="market-uniform"),
    ],
)
def test_chip_body_equals_cpu_body(world):
    import jax.numpy as jnp

    from armada_tpu.models.fair_scheduler import schedule_round as sr
    from armada_tpu.models.problem import SchedulingProblem

    for i, (problem, kw, check) in enumerate(world()):
        dev = SchedulingProblem(*(jnp.asarray(a) for a in problem))
        chip = sr(dev, **kw, cache_slots=0)
        cpu = sr(dev, **kw)  # the cache derived from the compat table
        for name in chip._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(chip, name)),
                np.asarray(getattr(cpu, name)),
                err_msg=f"round {i}: the two bodies diverged on {name}",
            )
        assert int(chip.kernel_iters) == int(chip.iterations)
        check(chip)


def test_outcome_carries_the_loop_counters():
    """The decoded RoundOutcome carries kernel_iters and window_refills (the
    compact buffer's ninth and tenth header slots) so bench/reports/spans
    read them without a transfer, and the sidecar's stats JSON keeps the
    keys perfbench reads."""
    cfg = make_config()
    nodes = [node(cfg, f"n{i}", cpu="8", memory="32Gi") for i in range(4)]
    queues = [Queue(f"q{i}", 1.0) for i in range(4)]
    jobs = [
        job(cfg, f"j{i:02d}", f"q{i % 4}", cpu="1", submit_time=float(i))
        for i in range(40)
    ]
    plain = run_round(cfg, nodes, queues, jobs)
    assert len(plain.scheduled) == 32
    assert 0 < plain.kernel_iters == plain.num_iterations
    # the 33rd job fits nowhere: its key retires all four queues' heads at
    # once, more cursors than a trip rebuilds by rows
    assert plain.window_refills == 1

    import json

    from armada_tpu.scheduler.algo import PoolStats, SchedulerResult
    from armada_tpu.scheduler.sidecar import _stats_of

    stats = PoolStats("default", plain, num_nodes=4, num_queued=40, num_running=0)
    (entry,) = json.loads(_stats_of(SchedulerResult(pools=[stats])))["pools"]
    assert entry["kernel_iters"] == entry["iterations"] == plain.kernel_iters
    assert entry["window_refills"] == 1
