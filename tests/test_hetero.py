"""Heterogeneity-aware scheduling, end to end (round 20).

The tentpole surfaces under one roof: the annotation parser and its
validation gate, key identity absorbing the type axis, SubmitChecker's
unknown-type rejection, the kernel's whitelist + throughput-bias placement
on hand-built worlds, bit-identity of single-type fleets with pre-hetero
decisions, fit-cache bit-equality on a type-sensitive synthetic
problem (the docs/lint.md ledger row), the explain pass's type-mismatch
attribution + per-type fragmentation, and a heterogeneous soak smoke.

The statistical parity legs (mixed fleets vs the independent sequential
oracle, scheduled AND preempted sets over many seeds) live in
tests/test_parity_full.py::test_hetero_*.
"""

import dataclasses

import numpy as np
import pytest

from armada_tpu.core.config import PoolConfig, SchedulingConfig
from armada_tpu.core.keys import (
    TYPE_BIAS_SCALE,
    NodeType,
    SchedulingKey,
    class_signature,
    static_fit_matrix,
    type_feasible,
    type_score_tables,
)
from armada_tpu.core.types import (
    NODE_TYPE_SCORES_ANNOTATION,
    JobSpec,
    NodeSpec,
    Queue,
    parse_node_type_scores,
)
from armada_tpu.models import explain as explain_mod
from armada_tpu.models import run_scheduling_round

# The lifted round-cap fraction mirrors test_explain: attribution tests
# need every queued job ATTEMPTED, and it is bit-neutral for worlds that
# never fill the pool.
CFG = SchedulingConfig(
    shape_bucket=32, maximum_resource_fraction_to_schedule={}
)
F = CFG.resource_list_factory()


def node(nid, cpu=8, mem=32, node_type=""):
    return NodeSpec(
        id=nid,
        pool="default",
        total_resources=F.from_mapping({"cpu": cpu, "memory": mem}),
        node_type=node_type,
    )


def job(jid, cpu=2, mem=2, sub=0.0, **kw):
    return JobSpec(
        id=jid,
        queue=kw.pop("queue", "qa"),
        submit_time=float(sub),
        resources=F.from_mapping({"cpu": cpu, "memory": mem}),
        **kw,
    )


def sched_key(**kw):
    kw.setdefault("priority", 0)
    return SchedulingKey(
        resources=(), node_selector=(), tolerations=(),
        priority_class="d", **kw,
    )


def hw(name):
    return NodeType(taints=(), indexed_labels=(), hw_type=name)


# --- the annotation parser ---------------------------------------------------


def test_parse_node_type_scores_canonical():
    got = parse_node_type_scores("v5e=2.0, v4=1 ,v6=4")
    assert got == (("v4", 1.0), ("v5e", 2.0), ("v6", 4.0))  # sorted
    assert parse_node_type_scores("") == ()
    assert parse_node_type_scores("  ") == ()


@pytest.mark.parametrize(
    "text",
    [
        "v5e",  # missing =
        "v5e=fast",  # non-numeric
        "v5e=0",  # throughput must be > 0
        "v5e=-1",
        "=2.0",  # empty type name
        "v5e=1,v5e=2",  # duplicate type
    ],
)
def test_parse_node_type_scores_rejects(text):
    with pytest.raises(ValueError):
        parse_node_type_scores(text)


def test_validation_rejects_malformed_annotation():
    from armada_tpu.server.submit import JobSubmitItem
    from armada_tpu.server.validation import ValidationError, validate_submission

    bad = JobSubmitItem(
        resources={"cpu": "1", "memory": "1"},
        annotations={NODE_TYPE_SCORES_ANNOTATION: "v5e=fast"},
    )
    with pytest.raises(ValidationError, match="item 0"):
        validate_submission([bad], CFG)
    ok = JobSubmitItem(
        resources={"cpu": "1", "memory": "1"},
        annotations={NODE_TYPE_SCORES_ANNOTATION: "v5e=2.0"},
    )
    validate_submission([ok], CFG)  # parses clean


# --- key identity + tables ---------------------------------------------------


def test_class_signature_absorbs_type_axis():
    a = job("j1")
    b = dataclasses.replace(a, node_type_scores=(("v5e", 2.0),))
    c = dataclasses.replace(a, node_type_scores=(("v5e", 4.0),))
    label = CFG.node_id_label
    assert class_signature(a, label) != class_signature(b, label)
    assert class_signature(b, label) != class_signature(c, label)  # weights
    assert class_signature(b, label) == class_signature(
        dataclasses.replace(b, id="other"), label
    )


def test_type_feasible_whitelist():
    insensitive = sched_key()
    sensitive = sched_key(type_scores=(("v5e", 2.0),))
    assert type_feasible(insensitive, hw("v5e"))
    assert type_feasible(insensitive, hw("v4"))
    assert type_feasible(sensitive, hw("v5e"))
    assert not type_feasible(sensitive, hw("v4"))  # whitelist excludes


def test_type_score_tables_row_interning_and_bias():
    types = [hw(""), hw("v4"), hw("v5e")]
    keys = [
        sched_key(),
        sched_key(type_scores=(("v4", 1.0), ("v5e", 2.0))),
        sched_key(priority=1, type_scores=(("v4", 1.0), ("v5e", 2.0))),
        sched_key(type_scores=(("v5e", 4.0),)),
    ]
    key_row, bias = type_score_tables(keys, types, len(keys), len(types))
    assert key_row[0] == 0  # insensitive keys share the all-zero row
    assert key_row[1] == key_row[2] != 0  # identical maps intern one row
    assert key_row[3] not in (0, key_row[1])
    assert np.all(bias[0] == 0.0)
    r1 = bias[key_row[1]]
    # thr=1 -> zero bias; thr=2 -> negative (preferred); a hardware type
    # the map does not name gets 0 (infeasibility is the compat gate's
    # job, never the bias row's)
    assert r1[1] == np.float32(0.0)
    assert r1[2] == np.float32((1.0 / 2.0 - 1.0) * TYPE_BIAS_SCALE)
    assert r1[0] == np.float32(0.0)
    # no sensitive key at all -> TR == 1 (the kernel's pre-hetero body)
    _, bias0 = type_score_tables(keys[:1], types, 1, len(types))
    assert bias0.shape[0] == 1


def test_static_fit_matrix_pre_type_skips_whitelist():
    types = [hw("v4"), hw("v5e")]
    sens = sched_key(type_scores=(("v5e", 2.0),))
    post = static_fit_matrix([sens], types)
    pre = static_fit_matrix([sens], types, pre_type=True)
    assert not post[0, 0] and post[0, 1]
    assert pre[0, 0] and pre[0, 1]  # pre-type: the whitelist is ignored


# --- SubmitChecker -----------------------------------------------------------


def test_submitcheck_unknown_type_rejected_with_words():
    from armada_tpu.scheduler.executors import ExecutorSnapshot
    from armada_tpu.scheduler.submitcheck import SubmitChecker

    cfg = SchedulingConfig(shape_bucket=32, pools=(PoolConfig("default"),))
    checker = SubmitChecker(cfg)
    checker.update_executors(
        [
            ExecutorSnapshot(
                id="ex1",
                pool="default",
                nodes=(
                    node("n0", node_type="v4"),
                    node("n1", node_type=""),
                ),
                last_update_ns=1,
            )
        ]
    )
    res = checker.check_gang([job("j1", node_type_scores=(("v9", 2.0),))])
    assert not res.ok
    assert "v9" in res.reason and "no such node exists" in res.reason
    # a map naming an existing type passes
    assert checker.check_gang(
        [job("j2", node_type_scores=(("v4", 2.0),))]
    ).ok
    # untyped jobs are untouched
    assert checker.check_gang([job("j3")]).ok


# --- kernel placement: whitelist + bias, hand-built --------------------------


def test_bias_steers_placement_to_fast_type():
    """Unbiased best-fit prefers the smaller (more packed) node; a 4x
    throughput on the bigger node's type must flip the pick -- the bias
    outweighs any packing-score difference by construction (scale 1024)."""
    nodes = [
        node("slow", cpu=8, mem=32, node_type="v4"),
        node("fast", cpu=32, mem=128, node_type="v6"),
    ]
    queues = [Queue("qa", 1.0)]
    plain = run_scheduling_round(
        CFG, pool="default", nodes=nodes, queues=queues,
        queued_jobs=[job("j1")], collect_stats=False,
    )
    assert plain.scheduled == {"j1": "slow"}  # best-fit baseline direction
    biased = run_scheduling_round(
        CFG, pool="default", nodes=nodes, queues=queues,
        queued_jobs=[
            job("j1", node_type_scores=(("v4", 1.0), ("v6", 4.0)))
        ],
        collect_stats=False,
    )
    assert biased.scheduled == {"j1": "fast"}


def test_whitelist_excludes_unnamed_types():
    nodes = [
        node("a", node_type="v4"),
        node("b", node_type="v6"),
        node("c", node_type=""),
    ]
    queues = [Queue("qa", 1.0)]
    out = run_scheduling_round(
        CFG, pool="default", nodes=nodes, queues=queues,
        queued_jobs=[
            job("j1", node_type_scores=(("v6", 1.0),)),
            job("j2", sub=1.0, node_type_scores=(("v9", 1.0),)),
        ],
        collect_stats=False,
    )
    assert out.scheduled.get("j1") == "b"
    assert "j2" in out.failed  # whitelists an absent type


def test_single_type_fleet_bit_identical_to_untyped():
    """Types without type-sensitive jobs change NOTHING: same decisions as
    the untyped fleet (TR == 1 compiles the pre-hetero body)."""
    rng = np.random.default_rng(5)
    untyped = [
        node(f"n{i}", cpu=int(rng.choice([8, 16]))) for i in range(12)
    ]
    typed = [dataclasses.replace(n, node_type="v5e") for n in untyped]
    queues = [Queue("qa", 1.0), Queue("qb", 2.0)]
    jobs = [
        job(f"j{i:03d}", cpu=int(rng.choice([1, 2, 4])),
            queue="qa" if i % 3 else "qb", sub=i)
        for i in range(40)
    ]
    a = run_scheduling_round(
        CFG, pool="default", nodes=untyped, queues=queues,
        queued_jobs=jobs, collect_stats=False,
    )
    b = run_scheduling_round(
        CFG, pool="default", nodes=typed, queues=queues,
        queued_jobs=jobs, collect_stats=False,
    )
    assert a.scheduled == b.scheduled
    assert a.preempted == b.preempted
    assert a.failed == b.failed


def test_hetero_cache_bit_equal():
    """The docs/lint.md ledger leg: on a type-sensitive synthetic problem
    the per-key fit cache (which refuses trow != 0 candidates) must stay
    bit-identical to the uncached body, the one the chip runs."""
    import jax.numpy as jnp

    from armada_tpu.models.fair_scheduler import schedule_round as sr
    from armada_tpu.models.problem import SchedulingProblem
    from armada_tpu.models.synthetic import synthetic_problem

    problem, meta = synthetic_problem(
        num_nodes=64, num_gangs=300, num_queues=8, num_runs=40,
        num_node_types=4, type_sensitive_frac=0.5,
        global_burst=200, perq_burst=60, seed=3, max_gang_cardinality=3,
    )
    assert problem.type_bias.shape[0] > 1  # the hetero body really compiled
    dev = SchedulingProblem(*(jnp.asarray(a) for a in problem))
    kw = dict(
        num_levels=meta["num_levels"], max_slots=meta["max_slots"],
        slot_width=meta["slot_width"],
    )
    base = sr(dev, **kw, cache_slots=0)
    got = sr(dev, **kw, cache_slots=8)
    for name in base._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(base, name)),
            np.asarray(getattr(got, name)),
            err_msg=f"cache_slots=8: diverged on {name}",
        )


# --- explain: type-mismatch + per-type fragmentation -------------------------


@pytest.fixture
def explain_armed(monkeypatch):
    monkeypatch.setenv("ARMADA_EXPLAIN_INTERVAL", "1")
    explain_mod.reset_cadence()
    yield


def test_explain_type_mismatch_partition(explain_armed):
    """Hand-built mixed fleet: the whitelisted-out job reads type-mismatch,
    the nowhere-fits job reads shape-infeasible (shape dominates type),
    and per-type fragmentation rows appear for every fleet type."""
    nodes = [
        node("a0", cpu=8, node_type="v4"),
        node("a1", cpu=8, node_type="v4"),
        node("b0", cpu=2, mem=4, node_type="v6"),
    ]
    queues = [Queue("qa", 1.0)]
    jobs = [
        job("fits", cpu=1, mem=1, sub=0),
        # needs cpu=4: fits a v4 node fine, but the whitelist only admits
        # v6 whose one node is too small -> type-mismatch
        job("typed-out", cpu=4, mem=4, sub=1,
            node_type_scores=(("v6", 2.0),)),
        # fits NO node even empty -- and carries a map, which must NOT
        # demote the dominant static reason
        job("too-big", cpu=99, mem=99, sub=2,
            node_type_scores=(("v4", 2.0),)),
    ]
    out = run_scheduling_round(
        CFG, pool="default", nodes=nodes, queues=queues,
        queued_jobs=jobs, collect_stats=False,
    )
    reasons = dict(out.explain.iter_job_reasons())
    assert "fits" in out.scheduled
    assert reasons["typed-out"] == "type-mismatch"
    assert out.explain.failed_counts["type-mismatch"] == 1
    # the nowhere-fits job is retired before any attempt (shape
    # infeasibility is static), so it reads shape-infeasible in the
    # PENDING vector -- shape dominated the type map it also carried
    assert out.explain.counts["shape-infeasible"] == 1
    assert out.explain.pending_counts["shape-infeasible"] == 1
    assert out.explain.counts["type-mismatch"] == 1
    # per-type fragmentation: one row per fleet type, every resource
    by_type = out.explain.fragmentation_by_type
    assert set(by_type) == {"v4", "v6"}
    for row in by_type.values():
        for rname in F.names:
            assert 0.0 <= row[rname]["index"] <= 1.0
    assert "fragmentation_by_type" in out.explain.summary()


def test_explain_single_type_fleet_skips_by_type(explain_armed):
    out = run_scheduling_round(
        CFG, pool="default", nodes=[node("n0"), node("n1")],
        queues=[Queue("qa", 1.0)], queued_jobs=[job("j1")],
        collect_stats=False,
    )
    assert out.explain is not None
    assert out.explain.fragmentation_by_type == {}
    assert "fragmentation_by_type" not in out.explain.summary()


def test_metrics_type_fragmentation_stale_label_removal():
    import prometheus_client

    from armada_tpu.scheduler.metrics import SchedulerMetrics

    m = SchedulerMetrics(registry=prometheus_client.CollectorRegistry())

    def fake_explain(by_type):
        return type(
            "E",
            (),
            {
                "queue_counts": {},
                "fragmentation": {},
                "fragmentation_by_type": by_type,
            },
        )()

    m._observe_explain(
        "default",
        fake_explain(
            {
                "v4": {"cpu": {"index": 0.5}},
                "v6": {"cpu": {"index": 0.25}},
            }
        ),
    )
    assert ("default", "v4", "cpu") in m._type_frag_labels
    assert ("default", "v6", "cpu") in m._type_frag_labels
    # the fleet went homogeneous: the per-type series must disappear
    m._observe_explain("default", fake_explain({}))
    assert not m._type_frag_labels


# --- loadgen / soak ----------------------------------------------------------


def test_workload_hetero_mix_deterministic_and_parsable():
    from armada_tpu.loadgen.workload import MixConfig, SubmitOp, WorkloadGenerator

    mix = MixConfig(
        node_types=("v4", "v5e"), type_sensitive_fraction=0.5,
        cancel_weight=0.0, reprioritize_weight=0.0,
    )
    a = WorkloadGenerator(mix, seed=11).next_ops(200)
    b = WorkloadGenerator(mix, seed=11).next_ops(200)
    seen = 0
    for op_a, op_b in zip(a, b):
        if not isinstance(op_a, SubmitOp):
            continue
        for it_a, it_b in zip(op_a.items, op_b.items):
            assert it_a.annotations == it_b.annotations  # seed-deterministic
            raw = it_a.annotations.get(NODE_TYPE_SCORES_ANNOTATION)
            if raw:
                seen += 1
                parsed = parse_node_type_scores(raw)
                assert parsed  # round-trips through the production parser
                assert {t for t, _ in parsed} <= {"v4", "v5e"}
    assert seen > 0


@pytest.mark.slow
def test_soak_hetero_fleet_smoke(tmp_path):
    """A short heterogeneous soak: typed fake nodes, type-sensitive
    submits riding the real annotation path, zero lifecycle violations."""
    from armada_tpu.loadgen.soak import SoakConfig, run_soak

    report = run_soak(
        SoakConfig(
            window_s=6.0,
            target_eps=30.0,
            num_nodes=4,
            num_queues=2,
            drain_s=2.0,
            cycle_interval_s=0.2,
            schedule_interval_s=0.5,
            seed=7,
            node_types=("v4", "v5e"),
            type_sensitive_fraction=0.4,
        ),
        str(tmp_path),
    )
    assert report["ok"], report
    assert report["violations"] == 0
    assert report["events"].get("type_sensitive", 0) > 0
    assert report["jobs"]["leased"] > 0
