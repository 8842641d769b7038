"""The compact single-transfer decode path must be indistinguishable from the
full-array pull.

decode_result's fast path (problem._fetch_compact) packs failed-gang indices,
preempted/rescheduled run indices and the placement slots into ONE device
buffer (fair_scheduler.compact_result) -- one transfer instead of seven.
These tests pin (a) outcome
equality between the compact path and the full pull on rounds exercising
scheduled + failed + preempted + rescheduled sets, and (b) the cap-overflow
fallback to the full pull.
"""

import jax.numpy as jnp
import pytest

from armada_tpu.core.config import PriorityClass, SchedulingConfig
from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob
from armada_tpu.models import (
    SchedulingProblem,
    build_problem,
    decode_result,
    schedule_round,
)
from armada_tpu.models import problem as problem_mod

CFG = SchedulingConfig(
    shape_bucket=32,
    priority_classes={
        "low": PriorityClass("low", priority=100, preemptible=True),
        "high": PriorityClass("high", priority=1000, preemptible=False),
    },
    default_priority_class="high",
)
F = CFG.resource_list_factory()


def _node(nid, cpu="8"):
    return NodeSpec(
        id=nid, pool="default",
        total_resources=F.from_mapping({"cpu": cpu, "memory": "32"}),
    )


def _job(jid, cpu="2", pc="high", sub=0.0, queue="q"):
    return JobSpec(
        id=jid, queue=queue, priority_class=pc, submit_time=sub,
        resources=F.from_mapping({"cpu": cpu, "memory": "1"}),
    )


def _evict_world():
    """Preemptible runners hogging the pool: the round schedules new jobs by
    evicting, re-places one evictee, preempts the rest."""
    nodes = [_node(f"n{i}", cpu="8") for i in range(4)]
    running = [
        RunningJob(job=_job(f"r{i}", cpu="8", pc="low", queue="hog"), node_id=f"n{i}")
        for i in range(4)
    ]
    jobs = [_job(f"j{i}", cpu="4", sub=i, queue="q") for i in range(4)]
    queues = [Queue("q"), Queue("hog")]
    return nodes, queues, jobs, running


def _fail_world():
    """Non-preemptible hogs leave no capacity: queued jobs are attempted,
    their scheduling key retires, and they decode as failed (g_state=2)."""
    nodes = [_node(f"n{i}", cpu="8") for i in range(2)]
    running = [
        RunningJob(job=_job(f"r{i}", cpu="8", pc="high", queue="hog"), node_id=f"n{i}")
        for i in range(2)
    ]
    jobs = [_job(f"j{i}", cpu="4", sub=i, queue="q") for i in range(3)]
    queues = [Queue("q"), Queue("hog")]
    return nodes, queues, jobs, running


def _round(world=_evict_world):
    nodes, queues, jobs, running = world()
    problem, ctx = build_problem(
        CFG, pool="default", nodes=nodes, queues=queues,
        queued_jobs=jobs, running=running,
    )
    dev = SchedulingProblem(*(jnp.asarray(a) for a in problem))
    result = schedule_round(
        dev,
        num_levels=len(ctx.ladder) + 2,
        max_slots=ctx.max_slots,
        slot_width=ctx.slot_width,
    )
    return result, ctx


def _assert_same(a, b):
    assert a.scheduled == b.scheduled
    assert sorted(a.preempted) == sorted(b.preempted)
    assert sorted(a.rescheduled) == sorted(b.rescheduled)
    assert sorted(a.failed) == sorted(b.failed)
    assert a.num_iterations == b.num_iterations
    assert a.termination == b.termination
    assert a.spot_price == b.spot_price
    assert a.unwound_groups == b.unwound_groups


@pytest.mark.parametrize("world", [_evict_world, _fail_world])
def test_compact_decode_matches_full_pull(world):
    result, ctx = _round(world)
    compact = decode_result(result, ctx)
    # Force the full pull by materializing the result to numpy (the compact
    # path only engages for device arrays).
    import numpy as np

    host = type(result)(*(np.asarray(x) for x in result))
    full = decode_result(host, ctx)
    if world is _evict_world:
        assert compact.scheduled, "scenario must schedule something"
        assert compact.preempted, "scenario must preempt"
    else:
        assert list(compact.failed), "scenario must fail the blocked jobs"
    _assert_same(compact, full)


def test_cap_overflow_falls_back_to_full_pull(monkeypatch):
    result, ctx = _round(_fail_world)
    baseline = decode_result(result, ctx)
    monkeypatch.setattr(problem_mod, "_COMPACT_FCAP", 1)
    monkeypatch.setattr(problem_mod, "_COMPACT_ECAP", 1)
    over = decode_result(result, ctx)
    _assert_same(baseline, over)


def test_compact_fetch_reports_overflow(monkeypatch):
    # _fail_world retires all three blocked jobs' gangs (n_failed=3 > cap).
    result, ctx = _round(_fail_world)
    monkeypatch.setattr(problem_mod, "_COMPACT_FCAP", 1)
    assert problem_mod._fetch_compact(result, ctx) is None


@pytest.mark.parametrize("world", [_evict_world, _fail_world])
def test_begin_decode_matches_blocking_decode(world):
    """The non-blocking begin_decode/finish pair (compaction + async
    device->host copy enqueued behind the kernel) must produce the same
    outcome as the blocking decode."""
    from armada_tpu.models import begin_decode

    result, ctx = _round(world)
    finish = begin_decode(result, ctx)
    overlapped = finish()
    blocking = decode_result(result, ctx)
    _assert_same(overlapped, blocking)


def test_begin_decode_overflow_falls_back(monkeypatch):
    monkeypatch.setattr(problem_mod, "_COMPACT_FCAP", 1)
    from armada_tpu.models import begin_decode

    result, ctx = _round(_fail_world)
    finish = begin_decode(result, ctx)
    overlapped = finish()
    blocking = decode_result(result, ctx)
    _assert_same(overlapped, blocking)
    assert len(list(overlapped.failed)) > 1  # the cap was genuinely exceeded
