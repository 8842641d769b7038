"""The carried candidate order against the from-scratch order (PR 30).

`IncrementalBuilder._assemble_delta` patches last cycle's order by the
tables' delta where its own state says the delta is expressible, and
rebuilds from scratch otherwise.  The check here is installed by the TEST
(no branch in the served code): around every `_assemble_delta`, a deep copy
of the builder whose carried state was dropped rebuilds from scratch, and
every bundle field, every context field and the builder's own slabs are
compared bit for bit.  It runs over the whole scenario of
tests/test_slab_delta.py and over worlds built for the patch's edges; each
reason for a rebuild is hit by name.
"""

import contextlib
import copy
import dataclasses
import json
import os
import subprocess
import sys
import tracemalloc

import numpy as np
import pytest

from armada_tpu.core.config import PoolConfig, PriorityClass, SchedulingConfig
from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob
from armada_tpu.models.incremental import IncrementalBuilder
from armada_tpu.models.slab import DeviceDeltaCache

import tests.test_slab_delta as slab_delta

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _same_bits(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, (what, a.dtype, b.dtype, a.shape, b.shape)
    assert a.tobytes() == b.tobytes(), f"{what}: patched and rebuilt differ"


def _same_cols(a: dict, b: dict, what):
    assert list(a) == list(b), (what, list(a), list(b))
    for name in a:
        _same_bits(a[name], b[name], f"{what}.{name}")


# context fields that are plain values; the id vectors are read whole below
_CTX_PLAIN = (
    "pool", "queue_names", "node_ids", "num_real_nodes", "num_real_queues",
    "num_real_gangs", "num_real_runs", "ladder", "pc_names", "max_slots",
    "slot_width", "q_demand_raw", "pool_total_atoms", "queues_padded",
    "queues_pending", "gang_members_over", "type_names",
)


def _compare(builder, twin, got, want):
    (bundle, ctx), (tb, tc) = got, want
    assert (bundle.sig, bundle.seq, bundle.ev_base) == (tb.sig, tb.seq, tb.ev_base)
    _same_bits(bundle.sg_idx, tb.sg_idx, "sg_idx")
    _same_bits(bundle.rr_idx, tb.rr_idx, "rr_idx")
    _same_cols(bundle.sg_cols, tb.sg_cols, "sg_cols")
    _same_cols(bundle.rr_cols, tb.rr_cols, "rr_cols")
    _same_cols(bundle.ev_cols, tb.ev_cols, "ev_cols")
    _same_cols(bundle.fulls, tb.fulls, "fulls")
    assert (bundle.gq_splice is None) == (tb.gq_splice is None), "one side has no splice"
    if bundle.gq_splice is not None:
        for part, a, b in zip(("rem", "ins", "vals"), bundle.gq_splice, tb.gq_splice):
            _same_bits(a, b, f"gq_splice.{part}")
    for name, a, b in zip(bundle.materialize()._fields, bundle.materialize(), tb.materialize()):
        _same_bits(a, b, f"materialize.{name}")
    for name in _CTX_PLAIN:
        assert getattr(ctx, name) == getattr(tc, name), name
    assert ctx.gang_group._d == tc.gang_group._d
    for name in ("gang_ids_vec", "run_ids_vec"):  # snapshots: read whole, by index array
        a, b = getattr(ctx, name), getattr(tc, name)
        assert a._live.shape == b._live.shape, name
        every = np.arange(a._live.shape[0])
        _same_bits(a[every], b[every], name)
    assert ctx.assemble_stats["id_bytes_copied"] == tc.assemble_stats["id_bytes_copied"]
    # what the next cycle starts from
    for slab in ("_sg", "_rr"):
        _same_bits(getattr(builder, slab).valid, getattr(twin, slab).valid, slab + ".valid")
        assert getattr(builder, slab).dirty_log == getattr(twin, slab).dirty_log == []
    _same_bits(builder._demand_sg, twin._demand_sg, "demand_sg")
    _same_bits(builder._demand_run, twin._demand_run, "demand_run")
    _same_bits(builder._prev_gq, twin._prev_gq, "prev_gq")
    assert builder._prev_gq_real == twin._prev_gq_real


@contextlib.contextmanager
def checked_against_rebuild():
    """Every `_assemble_delta` inside is compared with a from-scratch twin;
    yields the list of (rebuild reason or "", rows rebuilt) a cycle."""
    orig = IncrementalBuilder._assemble_delta
    seen = []

    def checked(self, **kw):
        twin = copy.deepcopy(self)
        twin._order_carry = None
        want = orig(twin, **kw)
        assert twin._rebuild_reason in ("no_previous", "market", "gang_units")
        got = orig(self, **kw)
        _compare(self, twin, got, want)
        assert (self._rows_rebuilt == 0) == (self._rebuild_reason == "")
        seen.append((self._rebuild_reason, self._rows_rebuilt))
        return got

    IncrementalBuilder._assemble_delta = checked
    try:
        yield seen
    finally:
        IncrementalBuilder._assemble_delta = orig


# ------------------------------------------------ the slab-delta scenarios ----


@pytest.mark.parametrize(
    "scenario",
    [
        "test_slab_delta_matches_legacy_over_cycles",
        "test_slab_delta_lookback_truncation",
        "test_bundle_seq_gap_forces_full_upload",
        "test_slab_delta_market_pool",
        "test_ctx_id_snapshots_survive_post_assemble_mutations",
        "test_running_gang_cascade_on_slab_path",
    ],
)
def test_slab_delta_scenario_patched_equals_rebuilt(scenario):
    with checked_against_rebuild() as seen:
        getattr(slab_delta, scenario)()
    assert seen, "the scenario never assembled"
    if scenario == "test_slab_delta_lookback_truncation":
        # a queue over its lookback, drained from the head: patched, not rebuilt
        assert [why for why, _ in seen[1:]] == [""] * (len(seen) - 1), seen
    if scenario == "test_slab_delta_market_pool":
        assert {why for why, _ in seen} == {"market"}


# ------------------------------------------------------------ edge worlds ----


def _config(lookback=100_000, market=False):
    cfg = SchedulingConfig(
        shape_bucket=64,
        priority_classes={
            "batch": PriorityClass("batch", priority=100, preemptible=True),
            "prod": PriorityClass("prod", priority=1000, preemptible=False),
        },
        default_priority_class="batch",
        max_queue_lookback=lookback,
        maximum_scheduling_burst=1000,
    )
    if market:
        cfg = dataclasses.replace(
            cfg, pools=(PoolConfig("default", market_driven=True, spot_price_cutoff=0.5),)
        )
    return cfg


class World:
    """A builder driven like the feed drives it: jobs leave from the head of
    their queue and become runs, runs end, new jobs arrive (`prod` ones sort
    into the middle of their queue), some are cancelled mid-queue."""

    def __init__(self, cfg, queues=3, nodes=8, seed=0):
        self.cfg = cfg
        self.F = cfg.resource_list_factory()
        self.rng = np.random.default_rng(seed)
        self.queues = [Queue(f"q{i}") for i in range(queues)]
        self.b = IncrementalBuilder(cfg, "default", self.queues)
        self.nodes = [
            NodeSpec(
                id=f"n{i}", pool="default",
                total_resources=self.F.from_mapping({"cpu": "64", "memory": "256"}),
            )
            for i in range(nodes)
        ]
        self.b.set_nodes(self.nodes)
        self.next_id = 0
        self.queued = {}  # job id -> spec
        self.running = []

    def spec(self, queue, pc="batch", gang=""):
        i = self.next_id
        self.next_id += 1
        return JobSpec(
            id=f"job-{i:07d}", queue=queue, priority_class=pc, submit_time=float(i),
            resources=self.F.from_mapping({"cpu": "1", "memory": "1"}),
            gang_id=gang, gang_cardinality=2 if gang else 0,
        )

    def submit(self, n, queue, pc="batch"):
        specs = [self.spec(queue, pc) for _ in range(n)]
        for s in specs:
            self.queued[s.id] = s
        self.b.submit_many(specs)
        return specs

    def heads(self, queue, n):
        """The first `n` queued jobs of `queue` in scheduling order."""
        mine = [s for s in self.queued.values() if s.queue == queue]
        prio = {name: pc.priority for name, pc in self.cfg.priority_classes.items()}
        mine.sort(key=lambda s: (-prio[s.priority_class], s.priority, s.submit_time, s.id))
        return mine[:n]

    def lease(self, specs):
        self.b.remove_many([s.id for s in specs])
        runs = [
            RunningJob(job=s, node_id=self.nodes[int(self.rng.integers(len(self.nodes)))].id)
            for s in specs
        ]
        self.b.lease_many(runs)
        for s in specs:
            self.queued.pop(s.id)
        self.running.extend(specs)

    def finish(self, n):
        done, self.running = self.running[:n], self.running[n:]
        for s in done:
            self.b.unlease(s.id)

    def cancel(self, specs):
        self.b.remove_many([s.id for s in specs])
        for s in specs:
            self.queued.pop(s.id)

    def assemble(self):
        return self.b.assemble_delta()

    def steady(self, leases=2, submits=2, finishes=2):
        for q in self.queues:
            self.lease(self.heads(q.name, leases))
            self.submit(submits, q.name, "prod" if self.rng.random() < 0.3 else "batch")
        self.finish(finishes)
        return self.assemble()


def _reasons(seen):
    return [why for why, _ in seen]


@pytest.mark.parametrize("extra", [-1, 0, 1, 5], ids=["one-under", "at", "one-over", "five-over"])
def test_queue_around_its_lookback(extra):
    """Head removals pull rows in over the lookback boundary, mid-queue
    (`prod`) inserts push rows out, with the queue one under, at and over it."""
    L = 12
    with checked_against_rebuild() as seen:
        w = World(_config(lookback=L), queues=2)
        w.submit(L + extra, "q0")
        w.submit(4, "q1")
        w.assemble()
        for step in range(6):
            w.lease(w.heads("q0", 1 + step % 2))  # the head leaves: one comes in
            w.assemble()
            w.submit(2, "q0", "prod")  # sorts before every batch job: two go out
            w.assemble()
            w.cancel(w.heads("q0", 5)[3:5])  # mid-queue
            w.submit(1, "q0")  # the tail, beyond the lookback when over it
            w.assemble()
    assert _reasons(seen)[1:] == [""] * (len(seen) - 1), seen


def test_a_cycle_that_crosses_a_table_merge_and_one_a_compaction():
    with checked_against_rebuild() as seen:
        w = World(_config(lookback=300), queues=3)
        for q in w.queues:
            w.submit(400, q.name)
        w.assemble()
        gens = [w.b.jobs.gen]
        # the overlay outgrows 2,048 rows: a merge; then the tombstones pass a
        # quarter of the table: a compaction
        for _ in range(9):
            for q in w.queues:
                w.submit(90, q.name, "prod" if w.rng.random() < 0.5 else "batch")
            w.steady(leases=3, submits=0)
            gens.append(w.b.jobs.gen)
        merges = sum(a != b for a, b in zip(gens, gens[1:]))
        assert merges >= 1, gens
        for _ in range(30):
            for q in w.queues:
                w.lease(w.heads(q.name, 40))
            w.finish(60)
            w.assemble()
            gens.append(w.b.jobs.gen)
        assert w.b.jobs.dead < 120 * 30, "no compaction ran"
    whys = _reasons(seen)
    assert whys[0] == "no_previous"
    # ("caps": the backlog grows through a slab capacity on the way)
    assert {"", "table_renumbered"} <= set(whys[1:]) <= {"", "table_renumbered", "caps"}
    renumbered = sum(a != b for a, b in zip(gens, gens[1:]))
    assert whys.count("table_renumbered") == renumbered >= 2
    # a renumbering costs one rebuild; the cycle after it is patched again
    for i, why in enumerate(whys[1:-1], 1):
        if why == "table_renumbered":
            assert whys[i + 1] in ("", "table_renumbered", "caps")
    assert whys.count("") > len(whys) // 2


def test_an_empty_delta_and_evictees_arriving_and_leaving():
    with checked_against_rebuild() as seen:
        w = World(_config(), queues=3)
        for q in w.queues:
            w.submit(20, q.name)
        w.assemble()
        bundle, _ = w.assemble()  # nothing happened in between
        assert bundle.gq_splice is not None and all(p.size == 0 for p in bundle.gq_splice)
        assert bundle.sg_idx.size == 0 and bundle.rr_idx.size == 0
        # evictees arrive (batch is preemptible) ...
        for q in w.queues:
            w.lease(w.heads(q.name, 3))
        b2, _ = w.assemble()
        assert b2.gq_splice[1].size == 9  # nine evictee slots came in
        # ... stay put over a cycle that only submits ...
        w.submit(2, "q1", "prod")
        w.assemble()
        # ... and leave, all of one queue's and some of another's
        w.finish(4)
        w.assemble()
        w.finish(5)
        w.assemble()
    assert _reasons(seen)[1:] == [""] * (len(seen) - 1), seen


def test_a_slot_freed_and_reused_in_one_cycle_and_a_prefetched_slot_redirtied():
    with checked_against_rebuild() as seen:
        w = World(_config(), queues=2)
        w.submit(10, "q0")
        w.submit(10, "q1")
        cache = DeviceDeltaCache()
        bundle, _ = w.assemble()
        cache.apply(bundle)
        # freed and reused between two assembles: same slot, another place
        victim = w.heads("q0", 1)[0]
        slot = int(w.b.jobs.slot[w.b.jobs._locate(victim.id.encode())])
        w.cancel([victim])
        (fresh,) = w.submit(1, "q1", "prod")
        assert int(w.b.jobs.slot[w.b.jobs._locate(fresh.id.encode())]) == slot
        bundle, _ = w.assemble()
        cache.apply(bundle)
        # a submit prefetched to the device, then its slot dirtied again (the
        # job cancelled, another takes the slot) before the next assemble
        (early,) = w.submit(1, "q0")
        assert w.b.prefetch_content(cache) == 1
        w.cancel([early])
        w.submit(1, "q1")
        bundle, _ = w.assemble()
        dev = cache.apply(bundle)
        for name, a, b in zip(dev._fields, dev, bundle.materialize()):
            _same_bits(np.asarray(a), b, name)
        # inserted and removed between two assembles: never in any order
        (ghost,) = w.submit(1, "q0", "prod")
        w.cancel([ghost])
        w.assemble()
    assert _reasons(seen)[1:] == [""] * (len(seen) - 1), seen


def test_each_reason_for_a_rebuild_by_name():
    with checked_against_rebuild() as seen:
        w = World(_config(lookback=50), queues=3)
        for q in w.queues:
            w.submit(30, q.name)
        w.assemble()  # no_previous
        w.steady()  # patched
        # a gang unit arrives, stays a cycle, and leaves
        gang = [w.spec("q1", gang="g1") for _ in range(2)]
        w.b.submit_many(gang)
        w.assemble()  # gang_units
        w.assemble()  # gang_units: still there
        for s in gang:
            w.b.remove(s.id)
        w.assemble()  # gang_units: last cycle's unit slots are dirty
        w.assemble()  # patched
        w.steady()  # patched
        # a retry-banned single rides the same slow path
        banned = w.spec("q2")
        w.b.submit(banned, banned_nodes=["n0"])
        w.assemble()  # gang_units
        w.b.remove(banned.id)
        w.assemble()
        w.assemble()
        w.steady()  # patched again
        # a queue made unknown, then known again, then a new one
        w.b.set_queues(w.queues[:2])
        w.assemble()  # queues
        w.steady()  # patched, with q2's rows out of the order
        w.b.set_queues(w.queues)
        w.assemble()  # queues
        w.b.set_queues(w.queues + [Queue("q9")])
        w.assemble()  # queues
        w.steady()
        # the singles slab outgrows its capacity
        w.submit(w.b._sg.cap, "q0")
        w.assemble()  # caps
        w.steady()
        # a node leaves: no reason to rebuild, its runs' evictee slots flip
        w.b.set_nodes(w.nodes[1:])
        w.assemble()
        w.b.set_nodes(w.nodes)
        w.assemble()
    whys = _reasons(seen)
    assert whys == [
        "no_previous", "",
        # the gang: arrives, stays, leaves (its unit slots are still dirty
        # the cycle after), and the order is patched again
        "gang_units", "gang_units", "gang_units", "", "",
        # the retry-banned single, likewise
        "gang_units", "gang_units", "", "",
        "queues", "", "queues", "queues", "",
        "caps", "",
        "", "",
    ], whys

    with checked_against_rebuild() as seen:
        w = World(_config(market=True), queues=2)
        w.b.bid_price_of = lambda job: 1.0
        w.submit(5, "q0")
        w.assemble()
        w.steady(leases=1, submits=1, finishes=0)
    assert _reasons(seen) == ["market", "market"]

    # "table_renumbered" is hit in the merge and compaction test; "mismatch"
    # is the patch's own cross-check and must never fire on a sound builder
    assert "mismatch" not in whys


def test_a_mismatch_rebuilds_and_mutates_nothing_first():
    """The patch's cross-check: a slot dirtied behind the builder's back (a
    live base row's slot, which no delta explains) makes the cycle rebuild,
    and the rebuilt cycle equals the twin's."""
    with checked_against_rebuild() as seen:
        w = World(_config(), queues=2)
        w.submit(10, "q0")
        w.submit(10, "q1")
        w.assemble()
        w.steady()
        row = w.b.jobs._locate(w.heads("q0", 4)[3].id.encode())
        assert row < w.b.jobs.sorted_n  # a base row: no delta will explain it
        w.b._sg.dirty_log.append(int(w.b.jobs.slot[row]))
        w.assemble()
        w.steady()
    assert _reasons(seen) == ["no_previous", "", "mismatch", ""]


def test_random_churn_patched_equals_rebuilt():
    """A seeded random walk over every operation, tight lookback."""
    for seed in range(3):
        with checked_against_rebuild() as seen:
            w = World(_config(lookback=25), queues=4, seed=seed)
            for q in w.queues:
                w.submit(int(w.rng.integers(5, 60)), q.name)
            w.assemble()
            for _ in range(25):
                for q in w.queues:
                    r = w.rng.random()
                    if r < 0.6:
                        w.lease(w.heads(q.name, int(w.rng.integers(0, 4))))
                    if r > 0.3:
                        w.submit(int(w.rng.integers(0, 5)), q.name, "prod" if r > 0.8 else "batch")
                    if 0.45 < r < 0.55:
                        mine = w.heads(q.name, 1000)
                        if len(mine) > 6:
                            w.cancel([mine[int(w.rng.integers(len(mine)))]])
                w.finish(int(w.rng.integers(0, 6)))
                w.assemble()
        # ("caps": the run slab grows as the first leases arrive)
        assert set(_reasons(seen)[1:]) <= {"", "caps"}, seen
        assert _reasons(seen).count("") >= 22, seen


# --------------------------------------------- counts a CPU run can pin ----


def _steady_cycles(backlog: int, cycles: int = 40):
    """(rows rebuilt, table generation, bytes allocated at once inside
    `assemble_delta`) of each of `cycles` steady cycles over `backlog` jobs in
    8 queues: 104 leases, 96 submits and 104 completions a cycle."""
    w = World(_config(lookback=100_000), queues=8, nodes=64)
    specs = []
    for k, q in enumerate(w.queues):
        specs += [w.spec(q.name) for _ in range(backlog // (k + 1) // 3 + 500)]
    for s in specs:
        w.queued[s.id] = s
    w.b.submit_many(specs)  # one batch: the whole backlog is the table's base
    heads = {q.name: w.heads(q.name, (cycles + 1) * 13) for q in w.queues}
    for q in w.queues:  # a standing set of runs: the run slab has its size
        batch, heads[q.name] = heads[q.name][:13], heads[q.name][13:]
        w.lease(batch)
    w.assemble()
    assert w.b._prev_gq.shape[0] >= w.b.jobs.n >= backlog // 2
    rebuilt, gens, peaks = [], [], []
    for cycle in range(cycles):
        w.finish(104)
        for q in w.queues:
            batch, heads[q.name] = heads[q.name][:13], heads[q.name][13:]
            w.lease(batch)
            w.submit(12, q.name, "prod" if cycle % 3 == 0 else "batch")
        tracemalloc.start()
        tracemalloc.reset_peak()
        before = tracemalloc.get_traced_memory()[0]
        _, ctx = w.assemble()
        peaks.append(tracemalloc.get_traced_memory()[1] - before)
        tracemalloc.stop()
        rebuilt.append(ctx.assemble_stats["assemble_rows_rebuilt"])
        gens.append(w.b.jobs.gen)
    return w, rebuilt, gens, peaks


def test_forty_steady_cycles_rebuild_nothing_and_allocate_nothing_wide():
    """20k jobs x 8 queues: no cycle without a table merge rebuilds a row,
    and the number of arrays of the backlog's or the gang axis' width that a
    patched cycle allocates inside `assemble_delta` is ZERO: the order, the
    splice's scratch, the flags and the prefix count live in buffers the
    builder keeps.  Pinned two ways: all that a patched cycle holds at once
    is less than ONE int64 vector of the backlog's width (a rebuilt cycle
    holds two dozen; what a patched one holds is as wide as the table's
    overlay, here up to 2,048 rows), and it does not grow when the backlog
    triples under the same traffic (one flag a row would show)."""
    w, rebuilt, gens, peaks = _steady_cycles(20_000)
    backlog = len(w.queued)
    merged = [i for i in range(40) if gens[i] != (gens[i - 1] if i else 1)]
    patched = [i for i in range(40) if i not in merged]
    assert len(merged) <= 2, gens
    assert all(rebuilt[i] == 0 for i in patched), rebuilt
    assert all(rebuilt[i] > 0.9 * backlog for i in merged), rebuilt
    assert max(peaks[i] for i in patched) < 8 * backlog, (peaks, backlog)
    assert min(peaks[i] for i in merged) > 100 * backlog, (peaks, backlog)
    w3, rebuilt3, gens3, peaks3 = _steady_cycles(60_000, cycles=12)
    assert rebuilt3 == [0] * 12 and set(gens3) == {1}
    assert all(i in patched for i in range(12))
    for a, b in zip(peaks[:12], peaks3):
        assert b < 1.25 * a, (peaks[:12], peaks3)


def test_the_tiny_cell_reports_both_counters_and_is_correct(tmp_path):
    """The benchmark's tiny cell on the CPU, through run.py: both new
    per-layer metrics are on the result line (files only: the two layer files
    and their BENCHMARK.json entries) and the run is `correct`."""
    sys.path.insert(0, os.path.join(REPO, "tests", "perfbench"))
    try:
        from perfbench_tiny import RUN, make_tiny
    finally:
        sys.path.pop(0)
    bench = make_tiny(tmp_path)
    out = os.path.join(tmp_path, "out")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONHASHSEED", None)
    proc = subprocess.run(
        [
            sys.executable, RUN, "--workload", "tiny.steady-40", "--seed", "2900000011",
            "--seconds", "2", "--trace", "1", "--allow-cpu", "--benchmark", bench, "--out", out,
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, result["checks"]
    metrics = result["metrics"]
    record = json.load(open(os.path.join(out, "tiny.steady-40.seed2900000011.trace1.0.json")))
    window = [c for c in record["per_cycle"] if c["phase"] == "window"]
    assert len(window) >= 3
    rebuilt = [c["assemble_rows_rebuilt"] for c in window]
    assert metrics["assemble_rows_rebuilt_per_cycle"]["value"] == pytest.approx(np.mean(rebuilt))
    # a steady window patches: at most a table merge's cycle rebuilds
    assert sum(1 for r in rebuilt if r) <= 1 + len(rebuilt) // 8, rebuilt
    copied = [c["id_bytes_copied"] for c in window]
    assert metrics["id_bytes_copied_per_cycle"]["value"] == np.median(copied)
    # 40 leases, 40 submits, 40 completions a cycle, under the round's and
    # the last round's snapshots: kilobytes, whatever the vectors' width
    assert 40 * 48 <= np.median(copied) < 64 * 1024
