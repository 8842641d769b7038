"""FairSchedulingAlgo's apply pass (scheduler/algo.py `_apply_outcome`): a
round's leases become jobdb objects in ONE pass over prototypes
(jobdb/job.py `with_new_runs`), held here to the one-lease-at-a-time
reference, JobRun(...) + Job.with_new_run, object for object."""

from __future__ import annotations

import copy
import dataclasses
import pickle
import uuid

import pytest

from armada_tpu.core.config import PriorityClass, SchedulingConfig
from armada_tpu.core.types import JobSpec
from armada_tpu.jobdb.job import Job, JobRun, with_new_runs
from armada_tpu.jobdb.jobdb import JobDb
from armada_tpu.models.problem import RoundOutcome
from armada_tpu.scheduler.algo import (
    FairSchedulingAlgo,
    SchedulerResult,
    _new_run_ids,
)
from tests.test_sidecar import _assert_fields_equal

_BASE = SchedulingConfig(shape_bucket=32, enable_assertions=True)
# every class of the default config, and one at another priority, so that a
# lease's priority tells the classes apart
CFG = dataclasses.replace(
    _BASE,
    priority_classes={
        **_BASE.priority_classes,
        "armada-low": PriorityClass("armada-low", priority=100, preemptible=True),
    },
)
F = CFG.resource_list_factory()
NOW_NS = 7_000_000_000
POOL = "default"
# n2 belongs to no executor: its leases name ""
EXECUTOR_OF_NODE = {"n0": "ex0", "n1": "ex1"}
CLASSES = ("", *CFG.priority_classes)  # "" resolves to the default class


def _spec(jid, pc, **kw):
    return JobSpec(
        id=jid,
        queue=f"q{len(jid) % 3}",
        priority_class=pc,
        submit_time=1.5 + len(jid),
        resources=F.from_mapping({"cpu": "1", "memory": "1"}),
        **kw,
    )


def _world() -> list[Job]:
    """Queued jobs of every class: fresh ones, retries with an earlier run
    and a bumped queued_version, the three members of a gang; and one job
    running on n0, for the preemption loop."""
    jobs = []
    for k, pc in enumerate(CLASSES):
        jobs.append(Job(spec=_spec(f"fresh{k}", pc), queued=True, validated=True, pools=(POOL,)))
        died = JobRun(
            id=f"old-{k}", job_id=f"retry{k}", node_id="n1", pool=POOL,
            failed=True, run_attempted=True, running_ns=5,
        )
        jobs.append(
            Job(
                spec=_spec(f"retry{k}", pc), priority=3, queued=True, queued_version=2,
                validated=True, runs=(died,),
            )
        )
        for m in range(3):
            jobs.append(
                Job(
                    spec=_spec(f"gang{k}-{m}", pc, gang_id=f"g{k}", gang_cardinality=3),
                    queued=True, validated=True,
                )
            )
    held = JobRun(id="held", job_id="victim", node_id="n0", pool=POOL, running=True)
    jobs.append(Job(spec=_spec("victim", "armada-preemptible"), queued=False, runs=(held,)))
    return jobs


def _outcome(form: str) -> RoundOutcome:
    ids = [j.id for j in _world() if j.id != "victim"]
    if form == "gang":
        ids = [i for i in ids if i.startswith("gang")]
    else:
        ids = [i for i in ids if not i.startswith("gang")]
    # leases in no sorted order, and one of a job the txn does not hold
    ids = ids[1::2] + ["ghost"] + ids[0::2]
    return RoundOutcome(
        scheduled={jid: f"n{k % 3}" for k, jid in enumerate(ids)},
        preempted=["victim"],
        failed=["stuck1", "stuck2"],
        num_iterations=len(ids),
        termination="global_burst",
    )


def _txn():
    db = JobDb(CFG)
    with db.write_txn() as txn:
        txn.upsert(_world())
    return db.write_txn()


def _reference_apply(txn, outcome, result, run_ids, away):
    """The pass as it was: one JobRun(...) and one with_new_run a lease."""
    away_priority = CFG.priority_ladder()[0]
    for job_id, node_id in outcome.scheduled.items():
        job = txn.get(job_id)
        if job is None:
            continue
        run = JobRun(
            id=next(run_ids),
            job_id=job_id,
            created_ns=NOW_NS,
            executor=EXECUTOR_OF_NODE.get(node_id, ""),
            node_id=node_id,
            node_name=node_id,
            pool=POOL,
            scheduled_at_priority=(
                away_priority if away else job.priority_class(CFG).priority
            ),
            pool_scheduled_away=away,
        )
        job = job.with_new_run(run)
        txn.upsert(job)
        result.scheduled.append((job, run))
    for job_id in outcome.preempted:
        job = txn.get(job_id)
        run = job.latest_run.with_preempted()
        job = job.with_updated_run(run).with_failed()
        txn.upsert(job)
        result.preempted.append((job, run))
    result.failed.extend(outcome.failed)


def _algo(run_ids=None):
    kw = {} if run_ids is None else {"run_ids": run_ids}
    return FairSchedulingAlgo(CFG, queues=lambda: [], clock_ns=lambda: NOW_NS, **kw)


def _apply(algo, outcome, away):
    txn, result = _txn(), SchedulerResult()
    classes = algo._apply_outcome(
        txn, outcome, POOL, EXECUTOR_OF_NODE, NOW_NS, result, away=away
    )
    return txn, result, classes


def _assert_same(got_txn, got, want_txn, want):
    assert list(got_txn._upserts) == list(want_txn._upserts)
    for job_id, job in want_txn._upserts.items():
        _assert_fields_equal(got_txn._upserts[job_id], job)
        assert got_txn._upserts[job_id] == job
    for name in ("scheduled", "preempted"):
        pairs, ref = getattr(got, name), getattr(want, name)
        assert len(pairs) == len(ref)
        for (job, run), (ref_job, ref_run) in zip(pairs, ref):
            _assert_fields_equal(job, ref_job)
            _assert_fields_equal(run, ref_run)
            assert (job, run) == (ref_job, ref_run)
            assert job is got_txn._upserts[job.id]
    assert list(got.failed) == list(want.failed)


@pytest.mark.parametrize("away", [False, True], ids=["home", "away"])
@pytest.mark.parametrize("form", ["one-lease-per-job", "gang"])
def test_batch_apply_equals_the_one_lease_reference(form, away):
    outcome = _outcome(form)
    drawn = []

    def factory(n):
        drawn.append([f"run-{k:04d}" for k in range(n)])
        return drawn[-1]

    got_txn, got, classes = _apply(_algo(factory), outcome, away)
    want_txn, want = _txn(), SchedulerResult()
    _reference_apply(want_txn, outcome, want, iter(f"run-{k:04d}" for k in range(99)), away)

    _assert_same(got_txn, got, want_txn, want)
    leased = [jid for jid in outcome.scheduled if jid != "ghost"]
    # the injected factory is asked once a round, its ids taken in lease order
    assert len(drawn) == 1
    assert [run.id for _, run in got.scheduled] == drawn[0]
    assert [job.id for job, _ in got.scheduled] == leased
    assert len(drawn[0]) == len(leased)
    assert classes == len(CLASSES)
    if away:
        assert {run.scheduled_at_priority for _, run in got.scheduled} == {100}
    else:
        assert {run.scheduled_at_priority for _, run in got.scheduled} == {100, 1000}

    # the leased jobs are ordinary instances that commit and index as the
    # reference's do
    job, run = got.scheduled[0]
    assert dataclasses.replace(job, priority=9).priority == 9
    assert job.with_queued(True).queued_version == job.queued_version + 1
    assert job.with_updated_run(run.with_running(running_ns=4)).latest_run.running
    assert pickle.loads(pickle.dumps(job)) == job and copy.deepcopy(run) == run
    got_txn.assert_invariants()
    got_txn.commit()
    want_txn.commit()
    got_db, want_db = got_txn._db.read_txn(), want_txn._db.read_txn()
    for queue in ("q0", "q1", "q2"):
        assert got_db.queued_jobs(queue) == want_db.queued_jobs(queue)
    assert got_db.gang_jobs("q1", "g1") == want_db.gang_jobs("q1", "g1")


def test_default_run_ids_are_uuid4_hex_drawn_once_a_round():
    outcome = _outcome("one-lease-per-job")
    got_txn, got, _ = _apply(_algo(), outcome, False)
    ids = [run.id for _, run in got.scheduled]
    assert len(set(ids)) == len(ids) == len(outcome.scheduled) - 1
    for run_id in ids:
        parsed = uuid.UUID(hex=run_id)
        assert parsed.hex == run_id
        assert parsed.version == 4 and parsed.variant == uuid.RFC_4122
    # everything but the random ids is the reference's
    want_txn, want = _txn(), SchedulerResult()
    _reference_apply(want_txn, outcome, want, iter(ids), False)
    _assert_same(got_txn, got, want_txn, want)


def test_new_run_ids_keep_uuid4_version_and_variant_bits():
    assert _new_run_ids(0) == []
    ids = _new_run_ids(4096)
    assert len(set(ids)) == 4096
    assert {len(i) for i in ids} == {32}
    assert {uuid.UUID(hex=i).version for i in ids} == {4}
    assert {uuid.UUID(hex=i).variant for i in ids} == {uuid.RFC_4122}
    # the other bits are random: every hex digit shows at the first position
    assert len({i[0] for i in ids}) == 16


@pytest.mark.parametrize("which", ["fresh0", "retry1", "gang2-1"])
def test_with_new_runs_equals_with_new_run(which):
    """The batch lease of jobdb/job.py makes what the one-lease reference
    makes: the run JobRun(job_id=job.id, **fields) and job.with_new_run(run),
    field for field, with the shared fields set on every run of the batch and
    the jobs it was given left as they were."""
    jobs = {j.id: j for j in _world()}
    job = jobs[which]
    shared = {"created_ns": NOW_NS, "pool": POOL, "pool_scheduled_away": True}
    fields = {
        "id": "r-new", "executor": "ex1", "node_id": "n1", "node_name": "n1",
        "scheduled_at_priority": 100,
    }
    was = dataclasses.replace(job)
    ((got_job, got_run), (other_job, _)) = with_new_runs(
        [(job, fields), (jobs["victim"], {**fields, "id": "r-other"})], **shared
    )
    want_run = JobRun(job_id=job.id, **shared, **fields)
    want_job = job.with_new_run(want_run)
    _assert_fields_equal(got_run, want_run)
    _assert_fields_equal(got_job, want_job)
    assert (got_job, got_run) == (want_job, want_run)
    assert got_job.latest_run is got_run and got_job.runs[:-1] == job.runs
    assert other_job.latest_run.job_id == "victim"
    _assert_fields_equal(job, was)
