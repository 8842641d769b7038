"""The id snapshot a HostContext holds (slab.SnapshotIds / IdSnapshot, PR 30).

A context taken at round n returns round n's ids however late it reads and
whatever the builder has done to the slots since, and a cycle pays for the
slots it overwrote, never for the vector's width.
"""

import json

import numpy as np
import pytest

from armada_tpu.core.config import SchedulingConfig
from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob
from armada_tpu.models import explain as explain_mod
from armada_tpu.models import decode_result, run_round_on_device, schedule_round
from armada_tpu.models.incremental import IncrementalBuilder
from armada_tpu.models.slab import DeviceDeltaCache, IdSnapshot, SnapshotIds

CFG = SchedulingConfig(shape_bucket=64, maximum_resource_fraction_to_schedule={})
F = CFG.resource_list_factory()


def _job(name, queue="q", cpu=1):
    return JobSpec(
        id=name, queue=queue, submit_time=float(sum(map(ord, name))),
        resources=F.from_mapping({"cpu": cpu, "memory": 1}),
    )


def _builder(nodes=2, queues=("q",)):
    b = IncrementalBuilder(CFG, "default", [Queue(q) for q in queues])
    b.set_nodes(
        [
            NodeSpec(id=f"n{i}", pool="default",
                     total_resources=F.from_mapping({"cpu": 8, "memory": 32}))
            for i in range(nodes)
        ]
    )
    return b


def _ids_now(b):
    """slot -> id of every queued single, from the table."""
    rows = b.jobs.live_rows()
    return {int(s): bytes(i) for s, i in zip(b.jobs.slot[rows], b.jobs.ids[rows])}


def _width(vec: IdSnapshot) -> int:
    return vec._live.shape[0]


def _whole(vec: IdSnapshot) -> np.ndarray:
    """Every id of the snapshot, the way decode reads: by an index array."""
    return vec[np.arange(_width(vec))]


def _assert_reads(ctx, want: dict):
    """`ctx.gang_ids_vec` gives `want` by int, by int array and whole."""
    vec = ctx.gang_ids_vec
    slots = sorted(want)
    for s in slots:
        assert vec[s] == want[s] and vec[np.int64(s)] == want[s]
    got = vec[np.asarray(slots, np.int64)]
    assert got.dtype == np.dtype("S48") and got.tolist() == [want[s] for s in slots]
    assert vec[np.flatnonzero(np.isin(np.arange(_width(vec)), slots))].tolist() == got.tolist()
    whole = _whole(vec)
    assert whole.shape == (_width(vec),)
    assert {s: bytes(whole[s]) for s in slots} == want
    assert not whole[[s for s in range(_width(vec)) if s not in want]].any()
    # members_of, decode's reader
    for s in slots:
        assert ctx.members_of(s) == [want[s].decode()]


def _mutate(b, how, names):
    """Overwrite the slots of `names` the way the feed does."""
    if how == "remove_many":
        b.remove_many(names)
    elif how == "single":
        for n in names:
            b.remove(n)
    elif how == "reuse":  # released and re-allocated to new jobs
        b.remove_many(names)
        b.submit_many([_job("re-" + n) for n in names])
    elif how == "resubmit":  # the same id leaves and comes back (reprioritise)
        for n in names:
            b.reprioritise(_job(n))
    else:
        raise AssertionError(how)


@pytest.mark.parametrize("how", ["remove_many", "single", "reuse", "resubmit"])
def test_a_context_keeps_its_rounds_ids(how):
    b = _builder()
    names = [f"job-{i}" for i in range(12)]
    b.submit_many([_job(n) for n in names])
    _, ctx = b.assemble_delta()
    want = _ids_now(b)
    assert len(want) == 12
    _assert_reads(ctx, want)
    _mutate(b, how, names[2:9])
    _assert_reads(ctx, want)
    b.submit_many([_job(f"later-{i}") for i in range(5)])
    _assert_reads(ctx, want)
    # the run axis is served by the same helper
    run = RunningJob(job=_job("run-0"), node_id="n0")
    b.lease(run)
    _, ctx2 = b.assemble_delta()
    rslot = int(b.runs.slot[b.runs._locate(b"run-0")])
    assert isinstance(ctx2.run_ids_vec, IdSnapshot) and ctx2.run_job_id(rslot) == "run-0"
    b.unlease("run-0")
    b.lease(RunningJob(job=_job("run-1"), node_id="n1"))
    assert ctx2.run_job_id(rslot) == "run-0"
    assert ctx2.run_ids_vec[np.asarray([rslot])].tolist() == [b"run-0"]


@pytest.mark.parametrize("outstanding", [2, 3])
def test_several_contexts_at_once_the_oldest_read_last(outstanding):
    b = _builder()
    b.submit_many([_job(f"a-{i}") for i in range(8)])
    held = []
    for r in range(outstanding):
        _, ctx = b.assemble_delta()
        held.append((ctx, _ids_now(b)))
        # every round overwrites slots the older contexts saw differently
        victims = sorted(n.decode() for n in _ids_now(b).values())[:3]
        b.remove_many(victims)
        b.submit_many([_job(f"r{r}-{i}") for i in range(4)])
    assert len({json.dumps(sorted(w.items()), default=repr) for _, w in held}) == outstanding
    for ctx, want in reversed(held):  # newest first, the oldest last
        _assert_reads(ctx, want)
    # a context that is dropped costs nothing more: the registry is weak
    del held, ctx
    assert not [r for r in b._g_ids._snaps if r() is not None]
    b.remove_many([n.decode() for n in _ids_now(b).values()][:2])
    assert b._g_ids.take_bytes_copied() > 0  # (the earlier rounds' images)
    b.submit_many([_job("after")])
    assert b._g_ids.take_bytes_copied() == 0  # nobody is looking: nothing copied


def test_growth_and_a_change_of_g_leave_the_old_array_to_the_old_context():
    b = _builder()
    b.submit_many([_job(f"a-{i}") for i in range(10)])
    _, ctx = b.assemble_delta()
    want = _ids_now(b)
    g0 = _width(ctx.gang_ids_vec)
    live0 = b._g_ids.live
    # the singles slab outgrows its capacity: _ensure_g_ids takes a new array
    b.submit_many([_job(f"grow-{i}") for i in range(g0 + 5)])
    assert b._g_ids.live is not live0 and len(b._g_ids.live) > g0
    b.remove_many([f"a-{i}" for i in range(10)])
    _assert_reads(ctx, want)
    assert _width(ctx.gang_ids_vec) == g0
    # a change of G in assemble (the run slab appears, the slabs grew)
    b.lease(RunningJob(job=_job("run-0"), node_id="n0"))
    _, ctx2 = b.assemble_delta()
    want2 = _ids_now(b)
    assert _width(ctx2.gang_ids_vec) != g0
    b.remove_many([n.decode() for n in list(want2.values())[:7]])
    _assert_reads(ctx2, want2)
    _assert_reads(ctx, want)


def _round(b, cache, mutate=None):
    """assemble -> kernel -> (the builder moves on) -> decode."""
    bundle, ctx = b.assemble_delta()
    dev = cache.apply(bundle)
    res = schedule_round(
        dev, num_levels=len(ctx.ladder) + 2, max_slots=ctx.max_slots,
        slot_width=ctx.slot_width,
    )
    if mutate is not None:
        mutate()
    return decode_result(res, ctx), ctx


def test_decodes_lazy_failed_ids_are_the_rounds_after_the_slots_moved_on():
    """The overlapped decode: the kernel's result is decoded AFTER the next
    sync's feed has reused slots; `failed` (lazy ids gathered by index
    array) and `scheduled` (members_of, by int) name round n's jobs."""
    b = _builder(nodes=2, queues=("q", "r"))
    fits = [_job(f"fits-{i}") for i in range(4)]
    # within the queue's share of the pool (16 cpu), wider than any node (8)
    hopeless = [_job(f"huge-{i}", queue="r", cpu=12) for i in range(5)]
    b.submit_many(fits + hopeless)
    cache = DeviceDeltaCache()

    def moves_on():
        # every job of the round leaves and other jobs take the slots
        b.remove_many([j.id for j in fits + hopeless])
        b.submit_many([_job(f"next-{i}") for i in range(9)])

    out, ctx = _round(b, cache, mutate=moves_on)
    assert sorted(out.scheduled) == sorted(j.id for j in fits)
    assert sorted(out.failed) == sorted(j.id for j in hopeless)
    assert len(out.failed) == 5 and "huge-3" in out.failed
    # and once more, later still
    b.remove_many([f"next-{i}" for i in range(9)])
    assert sorted(out.failed) == sorted(j.id for j in hopeless)
    assert ctx.members_of(_slot_of(ctx, b"huge-0")) == ["huge-0"]


def _slot_of(ctx, jid: bytes) -> int:
    (hit,) = np.flatnonzero(_whole(ctx.gang_ids_vec) == jid)
    return int(hit)


def test_the_explain_report_reads_members_of_after_apply(monkeypatch):
    """The explain report's per-job reasons are lazy (`iter_job_reasons` ->
    `members_of`): read after apply_outcome has released and reused the
    round's slots, they still name the round's jobs."""
    monkeypatch.setenv("ARMADA_EXPLAIN_INTERVAL", "1")
    explain_mod.reset_cadence()
    b = _builder(nodes=2, queues=("q", "r"))
    fits = [_job(f"fits-{i}") for i in range(3)]
    hopeless = [_job(f"huge-{i}", queue="r", cpu=12) for i in range(4)]
    b.submit_many(fits + hopeless)
    cache = DeviceDeltaCache()
    bundle, ctx = b.assemble_delta()
    _res, out = run_round_on_device(
        bundle.stats_view(), ctx, CFG,
        device_problem=lambda: cache.apply(bundle), host_problem=bundle.materialize,
    )
    assert out.explain is not None and sorted(out.scheduled) == sorted(j.id for j in fits)
    # apply: the leased leave the backlog, the hopeless are cancelled, and
    # the next sync's submits take their slots
    b.remove_many(list(out.scheduled))
    b.lease_many([RunningJob(job=j, node_id="n0") for j in fits])
    b.remove_many([j.id for j in hopeless])
    b.submit_many([_job(f"next-{i}") for i in range(7)])
    b.assemble_delta()
    reasons = dict(out.explain.iter_job_reasons())
    assert reasons == {j.id: "shape-infeasible" for j in hopeless}


def test_a_cycle_of_six_decisions_copies_bytes_not_the_vector(monkeypatch):
    """Through the served path (sidecar session, wire handlers): the
    `id_bytes_copied` of the round's stats JSON is the before-images of the
    slots the cycle overwrote -- a few hundred bytes, under 64 KB whatever
    the vector's width -- and the `g_ids_copy` span carries the same bytes."""
    from tests.test_trace import _find, _served_cycle, _served_session
    from armada_tpu.ops.trace import reset_recorder

    monkeypatch.delenv("ARMADA_TRACE", raising=False)
    reset_recorder()
    try:
        sidecar, sid, Fs = _served_session(maximum_scheduling_burst=1000)
        _served_cycle(sidecar, sid, Fs, 0, 6)
        for first in (100, 200, 300):
            _, rnd, resp = _served_cycle(sidecar, sid, Fs, first, 6)
            assert len(resp.scheduled) == 6
            pool = json.loads(resp.pool_stats_json)["pools"][0]
            assert 0 < pool["id_bytes_copied"] < 64 * 1024
            assert pool["assemble_rows_rebuilt"] == 0
            (assemble,) = _find(rnd, "assemble")
            assert assemble.args["id_bytes_copied"] == pool["id_bytes_copied"]
            assert assemble.args["assemble_rows_rebuilt"] == 0
            assert assemble.args["assemble_rebuild_reason"] == ""
            copies = _find(rnd, "g_ids_copy")
            assert len(copies) == 1 and 0 < copies[0].args["bytes"] <= 6 * 48 * 3
    finally:
        reset_recorder()


def test_snapshot_ids_unit():
    ids = SnapshotIds(8)
    ids.write(np.arange(8), [f"j{i}".encode() for i in range(8)])
    assert ids.take_bytes_copied() == 0  # no snapshot, nothing to keep
    s1 = ids.snapshot()
    ids.write(3, b"x3")
    ids.write(np.asarray([3, 4]), [b"y3", b"y4"])
    s2 = ids.snapshot()
    ids.write(np.asarray([4, 5]), b"")
    assert (s1[3], s1[4], s1[5]) == (b"j3", b"j4", b"j5")
    assert (s2[3], s2[4], s2[5]) == (b"y3", b"y4", b"j5")
    assert s1[[5, 3, 0]].tolist() == [b"j5", b"j3", b"j0"]
    assert s2[np.asarray([5, 3, 0])].tolist() == [b"j5", b"y3", b"j0"]
    assert _whole(s1).tolist() == [f"j{i}".encode() for i in range(8)]
    # slot 3 once for s1; slot 4 once for s1; then 4 and 5 for s2, 5 for s1
    assert ids.take_bytes_copied() == 48 * (1 + 1 + 2 + 1)
    old = ids.live
    ids.replace(np.zeros((16,), "S48"), 8)
    ids.write(3, b"z3")
    assert s1[3] == b"j3" and s2[3] == b"y3" and old[3] == b"y3"
    assert ids.take_bytes_copied() == 8 * 48
