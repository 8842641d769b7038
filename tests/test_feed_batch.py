"""A commit leaves the builders in ONE pass a builder (the feed's _flush:
remove_many + unlease_many, then submit_many and lease_many), and what it
leaves is what the commit path left that went job by job through the tables'
scalar calls.

That path is the reference here, written against the builders' one-job
methods (`remove`, `unlease`: a binary search a job through `_locate`): a
deleted or terminal job leaves every builder at once, a requeued or re-leased
job drops its old run at once, submits and leases wait for the end of the
commit.  The two feeds see the same seeded commits and are held equal after
every one: live rows of both tables in key order with every column (the slab
slot among them), both demand matrices, both slabs with their free lists and
dirty rows, the side tables, the feed's own sets, and the assembled delta and
problem bit for bit.  Only WHEN a table compacts inside one commit may differ
(once a batch, not once a job): physical row numbers are not compared.
"""

import dataclasses
import random

import numpy as np
import pytest

from armada_tpu.core.config import PoolConfig, PriorityClass, SchedulingConfig
from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob
from armada_tpu.jobdb.job import Job, JobRun
from armada_tpu.models.incremental import _SortedTable
from armada_tpu.ops.trace import recorder
from armada_tpu.scheduler.incremental_algo import IncrementalProblemFeed
from tests.test_trace import _find, _fresh_recorder  # noqa: F401  (a fixture)

CFG = SchedulingConfig(
    shape_bucket=32,
    priority_classes={
        "low": PriorityClass("low", priority=100, preemptible=True),
        "high": PriorityClass("high", priority=1000, preemptible=False),
    },
    default_priority_class="high",
)
MARKET = dataclasses.replace(
    CFG, pools=(PoolConfig("default", market_driven=True, spot_price_cutoff=0.5),)
)
TWO_POOLS = dataclasses.replace(CFG, pools=(PoolConfig("default"), PoolConfig("gpu")))
F = CFG.resource_list_factory()
QUEUES = [Queue(f"q{i}", weight=1.0 + i) for i in range(3)]
BANDS = ("", "low", "high")


def _nodes(pool, n=6):
    return [
        NodeSpec(
            id=f"{pool}-n{i}",
            pool=pool,
            total_resources=F.from_mapping({"cpu": "64", "memory": "256"}),
        )
        for i in range(n)
    ]


def _spec(i, rng, **kw):
    return JobSpec(
        id=f"j{i:05d}",
        queue=kw.pop("queue", f"q{rng.randrange(3)}"),
        priority_class=rng.choice(("low", "high")),
        priority=rng.randrange(3),
        submit_time=float(i),
        resources=F.from_mapping({"cpu": str(rng.choice((1, 2, 4))), "memory": "2"}),
        price_band=rng.choice(BANDS),
        **kw,
    )


def _leased(job, pool, node, attempt=0):
    run = JobRun(id=f"{job.id}-r{attempt}", job_id=job.id, node_id=node, pool=pool)
    return dataclasses.replace(job, queued=False, runs=job.runs + (run,))


def _requeued(job):
    run = dataclasses.replace(job.latest_run, returned=True, run_attempted=True)
    return dataclasses.replace(job, queued=True, runs=job.runs[:-1] + (run,))


# ---------------------------------------------------------- the reference ----


def _drop_run_if_held(b, job_id):
    if (
        job_id.encode() in b.runs
        or job_id in b._pending_runs
        or job_id in b.running_gang_specs
    ):
        b.unlease(job_id)


def per_job_commit(feed, upserts, deletes):
    """The commit path before the batch: removes and unleases one job at a
    time as the commit is read, submits and leases once per builder after."""

    def leave(job_id):
        feed.pool_restricted.discard(job_id)
        feed.unrestricted_queued.discard(job_id)
        feed.multi_pool_queued.discard(job_id)
        for b in feed.builders.values():
            b.remove(job_id)
            b.unlease(job_id)
        feed._forget_gang(job_id)

    submits, bans, leased, leases = {}, {}, [], {}
    for job_id in deletes:
        leave(job_id)
    for job in upserts.values():
        if job.in_terminal_state():
            leave(job.id)
        elif job.queued:
            if not job.validated:
                continue
            pools = job.pools or job.spec.pools
            spec = dataclasses.replace(job.spec, priority=job.priority, pools=pools)
            feed.pool_restricted.discard(job.id)
            feed.unrestricted_queued.discard(job.id)
            feed.multi_pool_queued.discard(job.id)
            if spec.pools:
                feed.pool_restricted.add(job.id)
                if len(spec.pools) >= 2:
                    feed.multi_pool_queued.add(job.id)
            else:
                feed.unrestricted_queued.add(job.id)
            for b in feed.builders.values():
                _drop_run_if_held(b, job.id)
            submits[job.id] = spec
            if job.anti_affinity_nodes():
                bans[job.id] = tuple(job.anti_affinity_nodes())
        else:
            feed.pool_restricted.discard(job.id)
            feed.unrestricted_queued.discard(job.id)
            feed.multi_pool_queued.discard(job.id)
            leased.append(job.id)
            run = job.latest_run
            if run is None or run.in_terminal_state():
                for b in feed.builders.values():
                    _drop_run_if_held(b, job.id)
                feed._forget_gang(job.id)
                continue
            pool = run.pool or "default"
            for name, b in feed.builders.items():
                if name != pool:
                    _drop_run_if_held(b, job.id)
            b = feed.builders.get(pool)
            if b is None:
                continue
            leases.setdefault(pool, []).append(
                RunningJob(
                    job=dataclasses.replace(job.spec, priority=job.priority),
                    node_id=run.node_id,
                    priority=run.scheduled_at_priority or 0,
                    away=run.pool_scheduled_away,
                )
            )
            if job.spec.gang_id:
                b.note_running_gang(job.queue, job.spec.gang_id, job.id)
                feed._gang_of[job.id] = (pool, job.queue, job.spec.gang_id)
    for pool, b in feed.builders.items():
        if leased:
            b.remove_many(leased)
        if submits:
            b.submit_many(list(submits.values()), bans or None)
        if leases.get(pool):
            b.lease_many(leases[pool])


# ------------------------------------------------------------- comparison ----


def _table_state(t):
    rows = t.live_rows()
    out = {c: getattr(t, c)[rows] for c in t._cols()}
    out["req"] = t.req[rows]
    if t.atoms is not None:
        out["atoms"] = t.atoms[rows]
    return out


def _slab_state(s):
    out = {c: getattr(s, c)[: s.hw] for c in s._columns}
    out.update(
        req=s.req[: s.hw],
        valid=s.valid[: s.hw],
        ids=s._ids.live[: s.hw],
        free=np.asarray(s.free, np.int64),
        dirty=np.asarray(s.dirty_log, np.int64),
        hw=np.asarray([s.hw, s.cap, s.epoch]),
    )
    return out


def _assert_same_arrays(got, want, where):
    assert got.keys() == want.keys(), where
    for name in got:
        np.testing.assert_array_equal(got[name], want[name], err_msg=f"{where}: {name}")


def assert_builders_equal(b, ref, where):
    for name in ("jobs", "runs"):
        t, r = getattr(b, name), getattr(ref, name)
        _assert_same_arrays(_table_state(t), _table_state(r), f"{where}: {name}")
        assert t.key_of_id == r.key_of_id, f"{where}: {name}.key_of_id"
        assert t.n - t.dead == r.n - r.dead, f"{where}: {name} live count"
    _assert_same_arrays(_slab_state(b._sg), _slab_state(ref._sg), f"{where}: singles slab")
    _assert_same_arrays(_slab_state(b._rr), _slab_state(ref._rr), f"{where}: run slab")
    np.testing.assert_array_equal(b._demand_sg, ref._demand_sg, err_msg=f"{where}: _demand_sg")
    np.testing.assert_array_equal(b._demand_run, ref._demand_run, err_msg=f"{where}: _demand_run")
    np.testing.assert_array_equal(b._g_ids.live, ref._g_ids.live, err_msg=f"{where}: g_ids")
    for side in ("gang_jobs", "banned", "_unknown_queue", "_pending_runs", "running_gang_specs"):
        assert list(getattr(b, side)) == list(getattr(ref, side)), f"{where}: {side}"
    assert b._running_gang_members == ref._running_gang_members, f"{where}: gang members"


def assert_bundles_equal(b, ref, where):
    got, _ = b.assemble_delta()
    want, _ = ref.assemble_delta()
    assert got.sig == want.sig and got.ev_base == want.ev_base, where
    for part in ("sg_cols", "rr_cols", "ev_cols", "fulls"):
        _assert_same_arrays(getattr(got, part), getattr(want, part), f"{where}: {part}")
    np.testing.assert_array_equal(got.sg_idx, want.sg_idx, err_msg=f"{where}: sg_idx")
    np.testing.assert_array_equal(got.rr_idx, want.rr_idx, err_msg=f"{where}: rr_idx")
    assert (got.gq_splice is None) == (want.gq_splice is None), f"{where}: splice"
    for a, w in zip(got.gq_splice or (), want.gq_splice or ()):
        np.testing.assert_array_equal(a, w, err_msg=f"{where}: gq_splice")
    truth, wanted = got.materialize(), want.materialize()
    for name, a, w in zip(truth._fields, truth, wanted):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(w), err_msg=f"{where}: problem field {name}"
        )


class Pair:
    """The feed under test and the reference, on the same commits."""

    def __init__(self, config):
        self.feed = IncrementalProblemFeed(config)
        self.ref = IncrementalProblemFeed(config)
        for feed in (self.feed, self.ref):
            for pool, b in feed.builders.items():
                b.set_queues(QUEUES)
                b.set_nodes(_nodes(pool))
                if b.market:
                    b.bid_price_of = lambda job: {"": 0.0, "low": 1.0, "high": 3.0}[job.price_band]
        self.commits = 0

    def commit(self, upserts, deletes=(), overlay=False):
        upserts = {j.id: j for j in upserts}
        deletes = set(deletes)
        if overlay:
            # the round's way: the open txn's buffer first, the commit's
            # re-fire of the same instances after (skipped by identity)
            self.feed.overlay(upserts, deletes)
        self.feed.on_delta(upserts, deletes)
        per_job_commit(self.ref, upserts, deletes)
        self.commits += 1
        where = f"commit {self.commits}"
        for name in ("pool_restricted", "unrestricted_queued", "multi_pool_queued", "_gang_of"):
            assert getattr(self.feed, name) == getattr(self.ref, name), f"{where}: {name}"
        for pool, b in self.feed.builders.items():
            assert_builders_equal(b, self.ref.builders[pool], f"{where}, pool {pool}")
        return where

    def assemble(self, where):
        for pool, b in self.feed.builders.items():
            assert_bundles_equal(b, self.ref.builders[pool], f"{where}, pool {pool}")


# -------------------------------------------------------------- scenarios ----


def _queued(n, rng, first=0, **kw):
    return [Job(spec=_spec(first + i, rng, **kw), validated=True) for i in range(n)]


def _lease_all(jobs, rng, pool="default", nodes=6):
    return [_leased(j, pool, f"{pool}-n{rng.randrange(nodes)}") for j in jobs]


def run_base(pair, rng):
    """Runs in the run table's base (its first insert IS the base)."""
    jobs = _queued(60, rng)
    pair.commit(jobs)
    running = _lease_all(jobs[:40], rng)
    pair.assemble(pair.commit(running, overlay=True))
    assert pair.feed.builders["default"].runs.n == pair.feed.builders["default"].runs.sorted_n
    done = [dataclasses.replace(j, succeeded=True) for j in rng.sample(running, 25)]
    pair.assemble(pair.commit(done + _queued(10, rng, first=100)))


def run_overlay(pair, rng):
    """Runs in the run table's overlay, and in both regions at once."""
    jobs = _queued(90, rng)
    pair.commit(jobs)
    first = _lease_all(jobs[:30], rng)
    pair.commit(first, overlay=True)
    second = _lease_all(jobs[30:70], rng)
    pair.assemble(pair.commit(second, overlay=True))
    t = pair.feed.builders["default"].runs
    assert t.n > t.sorted_n > 0
    done = rng.sample(first, 12) + rng.sample(second, 20)
    rng.shuffle(done)
    pair.assemble(pair.commit([dataclasses.replace(j, failed=True) for j in done]))


def run_pending(pair, rng):
    """Runs the builder cannot place yet (unknown node, unknown queue) wait
    in _pending_runs and leave from there."""
    jobs = _queued(20, rng) + _queued(6, rng, first=50, queue="nobody")
    pair.commit(jobs)
    running = _lease_all(jobs[:10], rng) + [
        _leased(j, "default", "default-n99") for j in jobs[10:16]
    ] + _lease_all(jobs[20:], rng)
    where = pair.commit(running, overlay=True)
    assert len(pair.feed.builders["default"]._pending_runs) == 12
    pair.assemble(where)
    done = [dataclasses.replace(j, succeeded=True) for j in running[5:13] + running[16:20]]
    pair.assemble(pair.commit(done))
    assert len(pair.feed.builders["default"]._pending_runs) == 5


def run_never_leased(pair, rng):
    """Queued jobs cancelled before any lease, unknown ids, deletes."""
    jobs = _queued(50, rng)
    pair.commit(jobs)
    cancelled = [dataclasses.replace(j, cancelled=True, queued=False) for j in jobs[5:25]]
    strangers = [
        dataclasses.replace(j, cancelled=True, queued=False) for j in _queued(5, rng, first=900)
    ]
    where = pair.commit(cancelled + strangers, deletes={jobs[30].id, jobs[31].id, "j99999"})
    pair.assemble(where)
    assert len(pair.feed.builders["default"].jobs.key_of_id) == 28


def run_deleted_and_upserted(pair, rng):
    """Ids both deleted and upserted in one commit: the delete goes first,
    whatever the upsert says."""
    jobs = _queued(40, rng)
    pair.commit(jobs)
    running = _lease_all(jobs[:20], rng)
    pair.assemble(pair.commit(running, overlay=True))
    again = (
        [_requeued(j) for j in running[:4]]  # deleted, back as queued
        + [_leased(_requeued(j), "default", "default-n1", 1) for j in running[4:8]]  # re-leased
        + [dataclasses.replace(j, succeeded=True) for j in running[8:12]]  # terminal
        + jobs[20:24]  # still queued
    )
    rng.shuffle(again)
    pair.assemble(pair.commit(again, deletes={j.id for j in again} | {jobs[30].id}))


def run_gangs(pair, rng):
    """Running gang members: the feed's _gang_of and the builder's members
    go with the job (a market pool keeps the specs too)."""
    gang = [
        Job(spec=_spec(i, rng, queue="q1", gang_id=f"g{i // 3}", gang_cardinality=3), validated=True)
        for i in range(12)
    ]
    singles = _queued(20, rng, first=100)
    pair.commit(gang + singles)
    running = _lease_all(gang + singles[:10], rng)
    pair.assemble(pair.commit(running, overlay=True))
    assert len(pair.feed._gang_of) == 12
    leave = running[0:3] + running[4:5] + running[14:18]
    done = [dataclasses.replace(j, failed=True) for j in leave] + [_requeued(running[6])]
    pair.assemble(pair.commit(done, overlay=True))
    # (a requeued member keeps its entry until it is terminal or leased again)
    assert len(pair.feed._gang_of) == 8


def run_two_pools(pair, rng):
    """Two builders: an unrestricted job sits in both backlogs, a run lives
    in one run table, and a job whose run moved pools inside one commit
    leaves the old table and joins the new."""
    free = _queued(30, rng)
    pinned = _queued(20, rng, first=100, pools=("gpu",))
    both = _queued(6, rng, first=200, pools=("default", "gpu"))
    pair.commit(free + pinned + both)
    running = (
        _lease_all(free[:12], rng)
        + _lease_all(pinned[:10], rng, pool="gpu")
        + _lease_all(both[:3], rng, pool="gpu")
    )
    pair.assemble(pair.commit(running, overlay=True))
    moved = [_leased(_requeued(j), "gpu", "gpu-n2", 1) for j in running[:4]]
    homeless = [_leased(free[20], "tpu", "tpu-n0")]  # a pool with no builder
    done = [dataclasses.replace(j, succeeded=True) for j in running[6:11] + running[14:24]]
    back = [_requeued(running[12])]
    mix = moved + homeless + done + back
    rng.shuffle(mix)
    pair.assemble(pair.commit(mix, deletes={free[25].id, pinned[15].id}))
    assert not pair.feed.pools_independent()


def run_compaction(pair, rng):
    """One commit takes the run table's tombstones past its threshold
    (1,024 and a quarter of the rows): the job-by-job path compacts in the
    middle of the commit, the batch at its end."""
    jobs = _queued(1500, rng)
    pair.commit(jobs)
    running = _lease_all(jobs[:1400], rng)
    pair.commit(running[:900], overlay=True)
    pair.assemble(pair.commit(running[900:], overlay=True))
    gens = [pair.feed.builders["default"].runs.gen, pair.ref.builders["default"].runs.gen]
    done = [dataclasses.replace(j, succeeded=True) for j in rng.sample(running, 1100)]
    pair.assemble(pair.commit(done + _queued(50, rng, first=2000)))
    for feed, gen in zip((pair.feed, pair.ref), gens):
        t = feed.builders["default"].runs
        assert t.gen > gen and t.dead < 1100  # both compacted inside the commit
    # and the next commit finds every survivor where the table says it is
    rest = [j for j in running if j.id.encode() in pair.feed.builders["default"].runs]
    pair.assemble(pair.commit([dataclasses.replace(j, failed=True) for j in rest[:200]]))


def run_steady(pair, rng):
    """Seeded steady traffic, every kind of transition in every commit."""
    queued, running, n = [], [], 0
    for cycle in range(8):
        fresh = _queued(30, rng, first=n)
        n += 30
        done = [dataclasses.replace(j, succeeded=True) for j in running[:8]]
        back = [_requeued(j) for j in running[8:11]]
        running = running[11:]
        deletes = {j.id for j in queued[:2]}
        queued = queued[2:]
        pair.commit(fresh + done + back, deletes=deletes)
        queued += fresh + back
        rng.shuffle(queued)
        leases = [
            _leased(j, "default", f"default-n{rng.randrange(6)}", len(j.runs))
            for j in queued[:14]
        ]
        queued = queued[14:]
        preempted = [dataclasses.replace(j, failed=True) for j in running[:2]]
        running = running[2:] + leases
        pair.assemble(pair.commit(leases + preempted, overlay=True))


SCENARIOS = [
    ("run-table-base", CFG, run_base),
    ("run-table-overlay", CFG, run_overlay),
    ("pending-runs", CFG, run_pending),
    ("never-leased-unknown-deleted", CFG, run_never_leased),
    ("deleted-and-upserted", CFG, run_deleted_and_upserted),
    ("gang-members", CFG, run_gangs),
    ("market-pool", MARKET, run_steady),
    ("market-gang-members", MARKET, run_gangs),
    ("two-pools", TWO_POOLS, run_two_pools),
    ("crosses-compaction", CFG, run_compaction),
    ("steady", CFG, run_steady),
]


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize(
    "config,scenario", [s[1:] for s in SCENARIOS], ids=[s[0] for s in SCENARIOS]
)
def test_a_batched_commit_leaves_what_the_job_by_job_path_left(config, scenario, seed):
    scenario(Pair(config), random.Random(3200 + seed))


# ------------------------------------------------------------- the counts ----


def test_a_thousand_completions_make_no_per_job_search_of_the_run_table(
    monkeypatch, _fresh_recorder
):
    """The engagement guard, `copied_rows`-style (a count, no timing): a
    commit of 1,000 completions and 1,000 submits never calls
    _SortedTable._locate on the run table, hands every builder one
    unlease_many, and says so in feed_apply's `terminal`."""
    rng = random.Random(32)
    feed = IncrementalProblemFeed(TWO_POOLS)
    for pool, b in feed.builders.items():
        b.set_queues(QUEUES)
        b.set_nodes(_nodes(pool))
    jobs = _queued(1500, rng)
    feed.on_delta({j.id: j for j in jobs}, set())
    running = _lease_all(jobs[:700], rng) + _lease_all(jobs[700:1400], rng, pool="gpu")
    feed.on_delta({j.id: j for j in running}, set())
    assert [len(b.runs.key_of_id) for b in feed.builders.values()] == [700, 700]

    run_tables = {id(b.runs) for b in feed.builders.values()}
    located = []
    locate = _SortedTable._locate

    def counting(self, jid):
        located.append(id(self) in run_tables)
        return locate(self, jid)

    monkeypatch.setattr(_SortedTable, "_locate", counting)
    commit = [dataclasses.replace(j, succeeded=True) for j in rng.sample(running, 1000)]
    commit += _queued(1000, rng, first=5000)
    rec = recorder()
    with rec.cycle("commit"):
        feed.on_delta({j.id: j for j in commit}, {"j99999"})
    assert not any(located)
    assert sum(len(b.runs.key_of_id) for b in feed.builders.values()) == 400
    root = rec.last()[-1].root
    (apply,) = _find(root, "feed_apply")
    assert apply.args["terminal"] == 1001 and apply.args["upserts"] == 2000
    spans = _find(root, "unlease_many")
    assert sorted(s.args["pool"] for s in spans) == ["default", "gpu"]
    assert [s.args["n"] for s in spans] == [2001, 2001]


def test_a_batch_of_one_is_the_same_code(_fresh_recorder):
    """apply_job one-shot: the same flush, a batch of one."""
    rng = random.Random(7)
    feed = IncrementalProblemFeed(CFG)
    b = feed.builders["default"]
    b.set_queues(QUEUES)
    b.set_nodes(_nodes("default"))
    job = _queued(1, rng)[0]
    feed.apply_job(job)
    assert list(b.jobs.key_of_id) == [job.id.encode()]
    run = _leased(job, "default", "default-n0")
    feed.apply_job(run)
    assert not b.jobs.key_of_id and list(b.runs.key_of_id) == [job.id.encode()]
    rec = recorder()
    with rec.cycle("one"):
        feed.apply_job(dataclasses.replace(run, succeeded=True))
    assert not b.runs.key_of_id and not b._demand_run.any()
    (span,) = _find(rec.last()[-1].root, "unlease_many")
    assert span.args["n"] == 1


def test_through_the_served_path_a_syncs_completions_take_the_batched_pass(_fresh_recorder):
    """What the benchmark's cycle does, at the tiny served size: the sync
    that reports a round's leases finished counts them in feed_apply's
    `terminal`, and the run table is searched once."""
    from armada_tpu.rpc import rpc_pb2 as pb
    from tests.test_pipeline import NOW_NS
    from tests.test_trace import _served_cycle, _served_session

    sidecar, sid, F = _served_session()
    _, _, resp = _served_cycle(sidecar, sid, F, 0, 8)
    assert len(resp.scheduled) == 8
    done = [
        pb.JobState(
            job_id=m.job_id, queue=m.queue, terminal=True,
            run=pb.JobRunState(run_id=m.run_id, node_id=m.node_id, pool=m.pool),
        )
        for m in resp.scheduled
    ]
    sidecar.handle_sync(pb.SyncStateRequest(session_id=sid, jobs=done))
    sidecar.handle_round(pb.ScheduleRoundRequest(session_id=sid, now_ns=NOW_NS))
    sync, rnd = (t.root for t in recorder().last()[-2:])
    (apply,) = _find(sync, "feed_apply")
    assert apply.args == {"upserts": 8, "deletes": 0, "overlay": False, "terminal": 8}
    (unlease,) = _find(sync, "unlease_many")
    assert unlease.args == {"pool": "default", "n": 8}
    builder = sidecar.session(sid).feed.builders["default"]
    assert not builder.runs.key_of_id and not builder._demand_run.any()
    # the round sweeps the terminal jobs it was told of out of the mirror:
    # eight deletes, which leave by the same pass and find nothing left
    assert sorted(s.args["terminal"] for s in _find(rnd, "feed_apply")) == [0, 8]
    (swept,) = _find(rnd, "unlease_many")
    assert swept.args == {"pool": "default", "n": 8}
