"""The queue axis past its first 256-bucket: the round kernel at Q = 512 and
Q = 1,024 against the repo's independent sequential oracle, and the
fair-share water-filling at 925 queues against a plain transcription.

Every world the suite had before this file holds under 100 queues, so the
kernel's queue axis ran padded to one bucket (`models/incremental.py`:
`qbucket = min(bucket, 256)`) wherever it was compared with anything.  An
organisation that gives every user, team and project a queue has about a
thousand (PERF.md section 4, `tenants-925q-1m-50k`).  The comparisons here
are exact: job sets and counts, no tolerance.
"""

import collections

import numpy as np
import pytest

import test_parity_full as parity
from armada_tpu.core.config import scheduling_config_from_dict
from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob
from armada_tpu.models import run_round_on_device
from armada_tpu.models.incremental import IncrementalBuilder
from armada_tpu.models.slab import DeviceDeltaCache
from armada_tpu.ops.fairness import fair_shares

SIZES = (500, 1000, 2000)  # cpu thousandths: few sizes, so that costs tie


def _config(burst):
    return scheduling_config_from_dict(
        {
            "priorityClasses": {
                "batch": {"priority": 100, "preemptible": True},
                "prod": {"priority": 1000, "preemptible": False},
            },
            "defaultPriorityClassName": "batch",
            "pools": [{"name": "default"}],
            # 256: the served path's bucket for the queue axis (min(bucket, 256))
            "shapeBucket": 256,
            "maximumSchedulingBurst": burst,
            "maximumPerQueueSchedulingBurst": burst,
        }
    )


def tenants_world(cfg, n_queues, n_jobs, n_running, seed, n_nodes=160):
    """An organisation's tenants at test size: equal weights, 1/k demand over
    the queue index (the last queues hold no job at all), an initial running
    set in the first third of the queues only, and three job sizes, so that
    hundreds of queues stand at exactly the same allocation.  Queue names are
    NOT zero-padded: their sorted order ("t10" < "t2") is not their numeric
    order, which is what the tie-break below is about."""
    rng = np.random.default_rng(seed)
    f = cfg.resource_list_factory()
    rl = lambda milli, mem: f.from_mapping({"cpu": f"{milli}m", "memory": str(mem)})  # noqa: E731
    nodes = [
        NodeSpec(id=f"n{i:04d}", pool="default", total_resources=rl(c * 1000, c * 4))
        for i, c in enumerate(rng.choice([16, 32], n_nodes))
    ]
    queues = [Queue(f"t{i}", 1.0) for i in range(n_queues)]
    share = 1.0 / np.arange(1, n_queues + 1)
    counts = np.floor(n_jobs * share / share.sum()).astype(int)  # the tail rounds to 0
    jobs = []
    for qi, n in enumerate(counts):
        for _ in range(n):
            milli = int(rng.choice(SIZES))
            jobs.append(
                JobSpec(
                    id=f"j{len(jobs):06d}", queue=f"t{qi}",
                    priority_class="batch" if rng.random() < 0.7 else "prod",
                    submit_time=float(len(jobs)) + float(rng.random()),  # unique
                    resources=rl(milli, max(1, milli // 500)),
                )
            )
    running = [
        RunningJob(
            job=JobSpec(
                id=f"r{i:05d}", queue=f"t{int(rng.integers(n_queues // 3))}",
                priority_class="batch" if i % 2 else "prod", submit_time=-1.0,
                resources=rl(1000, 4),
            ),
            node_id=nodes[i % n_nodes].id,
        )
        for i in range(n_running)
    ]
    return nodes, queues, jobs, running, counts


def served_round(cfg, nodes, queues, jobs, running):
    """The serving plane's round: incremental builder -> slab cache ->
    run_round_on_device; returns (outcome, HostContext)."""
    builder = IncrementalBuilder(cfg, "default", queues)
    builder.set_nodes(nodes)
    builder.submit_many(jobs)
    builder.lease_many(running)
    cache = DeviceDeltaCache()
    bundle, ctx = builder.assemble_delta()
    _, outcome = run_round_on_device(
        bundle.stats_view(), ctx, cfg,
        device_problem=lambda: cache.apply(bundle), host_problem=bundle.materialize,
    )
    return outcome, ctx


@pytest.mark.parametrize(
    "n_queues,padded,n_jobs,burst,seed",
    [(300, 512, 1500, 250, 5), (300, 512, 1500, 250, 11), (925, 1024, 4000, 600, 3)],
)
def test_round_at_hundreds_of_queues_agrees_with_the_sequential_oracle(
    n_queues, padded, n_jobs, burst, seed
):
    """Scheduled set, per-queue lease counts and the preempted set equal the
    oracle's, exactly (node ids may differ on exact packing-score ties, as in
    tests/test_parity_full.py).

    The tie-break.  With equal weights, queues that hold no running job stand
    at allocation zero, and those whose next job has the same size propose
    the same cost.  The reference takes the lower cost first and breaks a tie
    by QUEUE NAME (queue_scheduler.go, QueueCandidateGangIteratorPQ.Less:
    "Tie-break by queue name"); the oracle sorts its candidates by
    (cost, name); the kernel's `select` takes the first minimum over its queue
    axis, whose order is the sorted names'.  Were the kernel's order the
    queues' numeric order ("t2" before "t10"), the round would end having
    taken the burst's last leases from other queues, and the per-queue counts
    below would differ."""
    cfg = _config(burst)
    nodes, queues, jobs, running, counts = tenants_world(cfg, n_queues, n_jobs, 120, seed)
    assert (counts == 0).sum() > n_queues // 10, "some queues hold no job"
    assert len({r.job.queue for r in running}) < n_queues // 3, "most queues run nothing"
    outcome, ctx = served_round(cfg, nodes, queues, jobs, running)
    assert (ctx.num_real_queues, ctx.queues_padded) == (n_queues, padded)
    assert ctx.queue_names == sorted(q.name for q in queues)
    assert ctx.queues_pending == int((counts > 0).sum())
    o_sched, o_preempted, _ = parity._Oracle(cfg, nodes, queues, jobs, running).run()

    assert len(outcome.scheduled) == burst  # the burst binds: the last leases are ties
    assert set(outcome.scheduled) == set(o_sched), (
        sorted(set(outcome.scheduled) - set(o_sched))[:5], sorted(set(o_sched) - set(outcome.scheduled))[:5],
    )
    assert set(outcome.preempted) == set(o_preempted)
    queue_of = {j.id: j.queue for j in jobs}
    per_queue = collections.Counter(queue_of[j] for j in outcome.scheduled)
    assert per_queue == collections.Counter(queue_of[j] for j in o_sched)
    # what the round looks like: about one job a queue from hundreds of queues
    # (64 queues give a burst of 1,000 sixteen each), and ties everywhere
    assert len(per_queue) > 0.7 * (counts > 0).sum() and max(per_queue.values()) <= 4
    # the tie-break is exercised: some queue that sorts early by name and late
    # by number got a lease that a numeric order would have given elsewhere
    by_name = sorted(q.name for q in queues)
    leased_rank = sorted(by_name.index(q) for q in per_queue)
    assert leased_rank != sorted(int(q[1:]) for q in per_queue)


# --- the fair-share water-filling ---------------------------------------------


def plain_fair_shares(weights, cds, max_iterations=10):
    """updateFairShares (context/scheduling.go:220-300) as plain numpy loops,
    written from the description in ops/fairness.py's docstring and the Go
    source's order of steps, with no jit and no jax: returns (fair share,
    demand-capped adjusted share, uncapped adjusted share, iterations).  f32
    scalars, because shares are f32 everywhere in the repo."""
    f = np.float32
    w = np.asarray(weights, f)
    cds = np.asarray(cds, f)
    q = len(w)
    total = w.sum(dtype=f)
    fair = np.where(total > 0, w / (total if total > 0 else f(1)), f(0)).astype(f)
    achieved = np.zeros(q, bool)
    spare = np.zeros(q, f)
    dcafs = np.zeros(q, f)
    ucafs = np.zeros(q, f)
    unallocated = f(1.0)
    iterations = 0
    while iterations < max_iterations and unallocated > f(0.01):
        iterations += 1
        total_weight = w[~achieved].sum(dtype=f)
        for i in range(q):  # the share if this queue alone wanted everything
            denom = total_weight + (w[i] if achieved[i] else f(0))
            if denom > 0:
                ucafs[i] = ucafs[i] + f(w[i] / denom) * f(unallocated - spare[i])
        if total_weight <= 0:  # every queue has what it asked for
            break
        for i in range(q):
            if not achieved[i]:
                dcafs[i] = dcafs[i] + f(w[i] / total_weight) * unallocated
        unallocated = f(0)
        spare = np.zeros(q, f)
        for i in range(q):  # clip to demand; what spills over is shared next time
            over = f(dcafs[i] - cds[i])
            if over > 0 and not achieved[i]:
                dcafs[i] = cds[i]
                spare[i] = over
                achieved[i] = True
                unallocated = f(unallocated + over)
    return fair, dcafs, ucafs, iterations


@pytest.mark.parametrize("n_queues,elephants", [(925, 12), (925, 60), (300, 5), (64, 64)])
def test_fair_shares_at_925_queues_against_the_plain_transcription(n_queues, elephants):
    """Most of an organisation's queues ask for less than an equal share: the
    mice hand capacity on, so the loop runs more than one trip (one, where
    every queue wants its share or more).  Padding queues (weight 0) take
    nothing.  Shares are f32 sums over up to a thousand queues in another
    order than the plain loop's: equal to 1e-6 absolute (a share is at most 1
    and f32 carries 7 digits), the trip count exactly."""
    rng = np.random.default_rng(n_queues + elephants)
    padded = -(-n_queues // 256) * 256
    w = np.zeros(padded, np.float32)
    w[:n_queues] = 1.0
    cds = np.zeros(padded, np.float32)
    cds[:n_queues] = rng.uniform(0.0, 0.4 / n_queues, n_queues)  # mice: under 1/n
    cds[:elephants] = rng.uniform(0.05, 1.0, elephants)  # elephants: far over it
    got = fair_shares(w, cds)
    fair, dcafs, ucafs, iterations = plain_fair_shares(w, cds)
    np.testing.assert_allclose(np.asarray(got.fair_share), fair, rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got.demand_capped_adjusted_fair_share), dcafs, rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got.uncapped_adjusted_fair_share), ucafs, rtol=0, atol=1e-6)
    assert int(got.iterations) == iterations
    assert (iterations > 1) == (elephants < n_queues)
    assert not np.asarray(got.demand_capped_adjusted_fair_share)[n_queues:].any()
    # a mouse gets what it asks for, an elephant more than an equal share
    if elephants < n_queues:
        assert np.allclose(dcafs[elephants:n_queues], cds[elephants:n_queues], atol=1e-7)
        assert (dcafs[:elephants] > 1.0 / n_queues).all()
