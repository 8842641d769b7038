"""The round kernel's carried skip window (models/fair_scheduler.py, PR 26).

The placement loop no longer gathers its per-queue skip window on every trip:
it carries the three [Q, W] tables and patches them by what a trip changed.
Checked here, on worlds built to hit the window's edges, for solo / stacked x
check_keys, on the chip's body (cache_slots = 0):

* the invariant itself: at the top of EVERY trip the carried tables equal a
  fresh gather (``_skip_window``) from the cursors, gang states and bad keys
  of that moment -- so everything the body derives from them is computed from
  the values the per-trip gather gave;
* every RoundResult field: what the sequential oracle (tests/
  test_parity_full.py) decides -- scheduled jobs and their count, preempted
  and rescheduled runs, per-queue allocation, termination -- against the
  oracle, and the stacked round's fields bit for bit against the solo round's;
* ``window_refills``, pinned per world;
* the same at Q = 512 (PR 28) and at Q = 1,024 (the bucket of the benchmark's
  925-queue configuration): hundreds of queues whose heads all hold one key, so
  that its registration retires every other head at once and the whole [Q, W]
  window is gathered again;
* a fleet with no room for most of what is queued: the round cannot fill its
  cap, fails every (queue, key) head in turn, gathers the window again on
  almost every trip and ends ``exhausted`` (the walk PR 27 met on the chip).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob
from armada_tpu.models import fair_scheduler as fs
from armada_tpu.models.problem import SchedulingProblem, build_problem, decode_result
from tests.test_parity_full import CFG, F, _Oracle, req_units

W = fs._SKIP_WINDOW
NQ = 10  # real queues; the queue axis pads to CFG.shape_bucket = 32
WHOLE = 16  # cores of a node: fits its shape, never its free capacity
GANGS = 64  # every world fills its gang axis exactly: the last window clips at G - 1


def _res(cpu, memory=1):
    return F.from_mapping({"cpu": cpu, "memory": memory})


class _World:
    """Eight 16-core nodes, each holding one non-preemptible 1-core run (so a
    whole-node job fits a node's shape and never its free capacity), ten
    queues, and exactly GANGS gang slots."""

    def __init__(self, config=CFG, nq=NQ, gangs=GANGS, nodes=8):
        self.config = config
        self.nq, self.gangs = nq, gangs
        self.nodes = [
            NodeSpec(id=f"n{i:02d}", pool="default", total_resources=F.from_mapping({"cpu": 16, "memory": 64}))
            for i in range(nodes)
        ]
        self.queues = [Queue(f"q{i}", 1.0) for i in range(nq)]
        self.jobs = []
        self.running = [self._run(f"pin{i}", "q9", f"n{i:02d}", "high") for i in range(nodes)]

    def _run(self, jid, queue, node, pc):
        spec = JobSpec(id=jid, queue=queue, priority_class=pc, submit_time=-100.0 + len(jid),
                       resources=_res(1))
        return RunningJob(job=spec, node_id=node, away=False)

    def add(self, queue, cpu, n=1, pc="high", memory=1):
        for _ in range(n):
            i = len(self.jobs)
            self.jobs.append(
                JobSpec(id=f"j{i:03d}", queue=f"q{queue}", priority_class=pc, submit_time=float(i),
                        resources=_res(cpu, memory))
            )
        return self

    def fill(self, queue=9):
        """1-core low jobs up to GANGS gang slots: every world has the same three keys
        (whole-node high, 1-core high, 1-core low) and so the same array shapes."""
        slots = len(self.jobs) + sum(r.job.priority_class == "low" for r in self.running)
        assert slots < self.gangs
        return self.add(queue, 1, self.gangs - slots, pc="low")


def _deep_skip():
    """q0 opens with 20 whole-node jobs that fit nowhere: the first failed fit registers
    their key, and the 19 behind it are skippable at once (nskip >= W: a window skipped
    whole, a hidden candidate behind it), then the rest."""
    w = _World().add(0, WHOLE, 20).add(0, 1, 4)
    for q in range(1, 8):
        w.add(q, 1, 4)
    return w.fill()


def _shared_key():
    """Every queue's head is the same whole-node job: ONE failed fit registers a key that
    the heads of nine other queues hold -- more cursors move than the body rebuilds by
    rows (one)."""
    w = _World()
    for q in range(NQ):
        w.add(q, WHOLE, 1)
    for q in range(NQ):
        w.add(q, 1, 3)
    return w.fill()


def _tails():
    """Tails inside the window from the first trip on (queues of 1 to 3 jobs), one long
    queue, and the last queues' windows clipped at G - 1."""
    w = _World().add(0, 1, 3).add(1, 1, 1).add(2, 1, 2).add(3, WHOLE, 2).add(4, 1, 30)
    return w.fill(queue=8)


def _evictees():
    """Fair-share eviction of every preemptible run: queues open with evictee heads
    (pinned re-placements), then whole-node and small jobs."""
    w = _World(dataclasses.replace(CFG, protected_fraction_of_fair_share=0.0))
    for i in range(12):
        w.running.append(w._run(f"low{i:02d}", f"q{i % 4}", f"n{i % 8:02d}", "low"))
    for q in range(6):
        w.add(q, WHOLE, 1).add(q, 1, 4)
    return w.fill()


def _exhausted():
    """Small jobs, all placed: the round ends on two dummy trips (the last cursor moves
    onto nothing, then nothing moves; no candidate, gang 0 as `g`), which must leave the
    window alone.  The 2-core job is the third key, for the shapes' sake."""
    w = _World().add(5, 2, 1)
    for q in range(5):
        w.add(q, 1, 2)
    return w.fill(queue=7)


# Under a shape bucket of 256: (real queues, gang slots, nodes) -> the queue axis.  512 is the
# axis past its first bucket; 1,024 is what the benchmark's 925 queues pad to.
WIDE = {512: (300, 768, 40), 1024: (800, 1792, 72)}


def _wide_shared_key(q_axis):
    """`_shared_key` on a queue axis of hundreds: every head the same whole-node job, so ONE
    failed fit retires every other queue's head and the carried [Q, W] window is gathered
    whole; behind each head one 1-core job, and room for every 1-core job, so that the set
    placed does not depend on the order of trips (without keys the heads fail one by one,
    between other queues' placements)."""
    nq, gangs, nodes = WIDE[q_axis]
    w = _World(dataclasses.replace(CFG, shape_bucket=256), nq=nq, gangs=gangs, nodes=nodes)
    for q in range(nq):
        w.add(q, WHOLE, 1)
    for q in range(nq):
        w.add(q, 1, 1)
    return w.fill(queue=nq - 1)


FULL_KEYS = 6  # whole-node requests a pair of queues holds, each its own key


def _full_fleet():
    """A fleet with room for four of the sixty-four jobs queued.  Every queue holds six
    whole-node jobs of six keys, and a pair of queues holds the same six: each failed fit
    registers a key that retires the partner's head too, so two cursors move on the next
    trip and the window is gathered whole -- on every trip until the last pair is through.
    The round leases the four 1-core jobs behind them and ends `exhausted`."""
    w = _World()
    for q in range(NQ):
        for j in range(FULL_KEYS):
            w.add(q, WHOLE, 1, memory=2 + (q // 2) * FULL_KEYS + j)
    return w.fill()


WORLDS = {
    "deep_skip": _deep_skip, "shared_key": _shared_key, "tails": _tails,
    "evictees": _evictees, "exhausted": _exhausted,
}
# other shapes: never stacked with WORLDS
OWN_SHAPES = {
    "wide_shared_key_512": functools.partial(_wide_shared_key, 512),
    "wide_shared_key_1024": functools.partial(_wide_shared_key, 1024),
    "full_fleet": _full_fleet,
}
# window_refills[world][check_keys].  A trip rebuilds one row.  Two cursors move at once
# only after a key registration: one that retires another queue's head too, or one whose
# queue is still skipping (its window skipped whole) while the next decided queue moves.
# Without keys nothing is registered, and never more than one cursor moves.
REFILLS = {
    "deep_skip": {True: 1, False: 0},
    "shared_key": {True: 1, False: 0},
    "tails": {True: 0, False: 0},
    "evictees": {True: 1, False: 0},
    "exhausted": {True: 0, False: 0},
    "wide_shared_key_512": {True: 1, False: 0},
    "wide_shared_key_1024": {True: 1, False: 0},
    "full_fleet": {True: (NQ // 2) * FULL_KEYS},  # one for every key there is
}


@functools.lru_cache(maxsize=None)
def _built(name):
    w = {**WORLDS, **OWN_SHAPES}[name]()
    problem, ctx = build_problem(
        w.config, pool="default", nodes=w.nodes, queues=w.queues, queued_jobs=w.jobs, running=w.running
    )
    assert ctx.num_real_gangs == w.gangs == problem.g_req.shape[0], name
    oracle = _Oracle(w.config, w.nodes, w.queues, w.jobs, w.running)
    scheduled, preempted, rescheduled = oracle.run()
    q_alloc = np.stack([oracle.alloc[q.name] for q in sorted(w.queues, key=lambda q: q.name)])
    dev = SchedulingProblem(*(jnp.asarray(a) for a in problem))
    return dev, ctx, (scheduled, preempted, rescheduled, q_alloc)


def _statics(dev, ctx):
    return dict(
        num_levels=len(ctx.ladder) + 2, max_slots=ctx.max_slots, slot_width=ctx.slot_width,
        **fs._resolve_round_statics(
            compat_rows=dev.compat.shape[0], G=dev.g_req.shape[0], Q=dev.q_weight.shape[0], max_iterations=0,
            prefer_large=False, cache_slots=0,
        ),
    )


@functools.lru_cache(maxsize=None)
def _round(stacked, check_keys):
    """The round as served, its body built with `check_keys` and, solo, watched: before
    every trip the carried window is compared with a fresh gather."""
    bare = fs._schedule_round_jit.__wrapped__  # under jit: the named function
    make = fs._make_place_iteration
    trips = []

    def make_watched(p, *args, **kw):
        kw["check_keys"] = check_keys
        body = make(p, *args, **kw)
        if stacked:
            return body

        def watched(c):
            fresh = fs._skip_window(p, p.q_start, c.q_head, c.g_state, c.key_bad, check_keys)
            same = jnp.stack([
                jnp.all(a == b) for a, b in zip(fresh, (c.w_gang, c.w_key, c.w_skip))
            ])
            jax.debug.callback(lambda s: trips.append(np.asarray(s)), same)
            return body(c)

        return watched

    def run(p, **statics):
        fs._make_place_iteration = make_watched  # read while tracing, below
        try:
            if stacked:
                return jax.vmap(lambda lane: bare(lane, **statics))(p)
            return bare(p, **statics)
        finally:
            fs._make_place_iteration = make

    jitted = jax.jit(run, static_argnames=tuple(_statics(*_built("tails")[:2])))
    return jitted, trips


def _assert_oracle(name, result, ctx, expected):
    scheduled, preempted, rescheduled, q_alloc = expected
    outcome = decode_result(result, ctx)
    assert set(outcome.scheduled) == set(scheduled), name
    assert int(result.scheduled_count) == len(scheduled), name
    assert set(outcome.preempted) == preempted and set(outcome.rescheduled) == rescheduled, name
    np.testing.assert_array_equal(np.asarray(result.q_alloc)[: len(q_alloc)], q_alloc, err_msg=name)
    assert outcome.termination == "exhausted", name


def _watched_round(name, check_keys):
    """The solo round on `name`, the invariant checked at the top of every trip (the dummy
    ones included), the oracle's decisions and the pinned refills."""
    dev, ctx, expected = _built(name)
    run, trips = _round(False, check_keys)
    del trips[:]
    got = jax.block_until_ready(run(dev, **_statics(dev, ctx)))
    jax.effects_barrier()
    assert len(trips) == int(got.kernel_iters) == int(got.iterations) > 0, name
    assert np.all(trips), (name, np.argwhere(~np.stack(trips)))
    _assert_oracle(name, got, ctx, expected)
    assert int(got.window_refills) == REFILLS[name][check_keys], name
    return got


@pytest.mark.parametrize("check_keys", [True, False], ids=["keys", "nokeys"])
@pytest.mark.parametrize("stacked", [False, True], ids=["solo", "stacked"])
def test_carried_window_is_the_gathered_window(stacked, check_keys):
    if not stacked:
        for name in WORLDS:
            _watched_round(name, check_keys)
        return
    built = {name: _built(name) for name in WORLDS}
    run, _ = _round(True, check_keys)
    lanes = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *(built[n][0] for n in WORLDS))
    dev, ctx, _ = built["tails"]
    out = run(lanes, **_statics(dev, ctx))
    solo_run, _ = _round(False, check_keys)
    for i, (name, (dev, ctx, expected)) in enumerate(built.items()):
        got = jax.tree_util.tree_map(lambda a: a[i], out)
        _assert_oracle(name, got, ctx, expected)
        solo = solo_run(dev, **_statics(dev, ctx))
        for field in got._fields:
            if field in ("kernel_iters", "window_refills"):
                continue  # lanes run in lockstep: a lane that is done still counts trips
            np.testing.assert_array_equal(
                np.asarray(getattr(got, field)), np.asarray(getattr(solo, field)),
                err_msg=f"{name}: {field} differs from the solo round",
            )
        assert int(got.window_refills) == REFILLS[name][check_keys], name
        assert int(got.window_refills) <= int(got.kernel_iters)


@pytest.mark.parametrize("check_keys", [True, False], ids=["keys", "nokeys"])
@pytest.mark.parametrize("q_axis", sorted(WIDE), ids=lambda q: f"q{q}")
def test_carried_window_is_the_gathered_window_on_a_wide_queue_axis(q_axis, check_keys):
    """The invariant and the oracle's decisions on a queue axis of 512 (300 real queues) and
    of 1,024 (800), through refills that gather all Q x W entries again."""
    name = f"wide_shared_key_{q_axis}"
    nq, gangs, _ = WIDE[q_axis]
    dev, ctx, _ = _built(name)
    assert (ctx.num_real_queues, dev.q_weight.shape[0]) == (nq, q_axis)
    assert dev.g_req.shape[0] == gangs
    got = _watched_round(name, check_keys)
    assert int(got.scheduled_count) == gangs - nq  # every 1-core job, no whole-node job
    # with keys, a registration moves hundreds of cursors at once: the window is refilled
    assert (int(got.window_refills) > 0) == check_keys


def test_a_round_that_cannot_fill_its_cap_refills_on_every_failed_head():
    got = _watched_round("full_fleet", True)
    placed, trips, refills = int(got.scheduled_count), int(got.kernel_iters), int(got.window_refills)
    assert placed == GANGS - NQ * FULL_KEYS == 4  # far under any cap: the round ran out of heads
    # a failed fit for each key, the trips that place, two dummy trips at the end
    assert trips == refills + placed + 2
    assert refills / trips > 0.8


def test_the_worlds_reach_the_edges():
    """The worlds do what their docstrings say, read off the solo round with keys."""
    run, _ = _round(False, True)
    seen = {}
    for name in WORLDS:
        dev, ctx, _ = _built(name)
        seen[name] = (dev, run(dev, **_statics(dev, ctx)))
    dev, r = seen["deep_skip"]
    whole = np.asarray(dev.g_req)[:, 0] == req_units(_res(WHOLE))[0]
    assert np.sum(np.asarray(r.g_state)[whole] == 2) == 20
    # 20 failures for ONE attempt: 19 were skipped (a whole window, then its rest) and
    # retired by the sweep after the loop; two dummy trips end the round
    assert int(r.iterations) == GANGS - 19 + 2
    dev, r = seen["evictees"]
    assert np.sum(np.asarray(r.run_evicted)) == 12
    assert np.asarray(dev.q_start)[NQ] + W > GANGS - 1 == np.asarray(dev.gq_gang).shape[0] - 1
    dev, r = seen["exhausted"]
    assert int(r.scheduled_count) == GANGS and int(r.iterations) == GANGS + 2
