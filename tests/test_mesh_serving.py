"""Mesh serving plane (round 12): sharded steady cycle == single chip.

The non-negotiable contract: node-axis-sharding the slab and running the
round kernel SPMD over the conftest's 8-device virtual mesh changes
NOTHING about decisions or mirror state -- sharding only distributes
reductions.  Pinned here:

1. *Steady-cycle equality over loadgen churn*: the same seeded
   submit/cancel/reprioritise/gang op stream (loadgen/workload.py) driven
   through a MeshDeviceDeltaCache world and a plain DeviceDeltaCache
   world yields bit-equal decisions AND bit-equal materialized problems
   (mirror state) every cycle, across 3 seeds, including a slab-growing
   burst cycle (full re-upload re-shards) and the shadow pipeline's
   content prefetch.
2. *Degrade ladder*: a mid-cycle device_round fault under an armed
   watchdog steps the mesh 8 -> 4 (never to CPU: the supervisor stays on
   "device", zero fallbacks), the SAME round re-runs on the smaller mesh
   with identical decisions, later cycles re-shard through the reset-hook
   cache replacement, and restore() returns to the full mesh.
3. *Divisibility padding*: pad_problem/shard_problem pad non-divisible
   axes with inert lanes (decisions identical, padded gang lanes absent,
   padded run lanes never evicted); the builders' node bucket aligns to
   the mesh multiple so slab growth never trips _check_divisible.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from armada_tpu.core.config import PriorityClass, SchedulingConfig
from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob
from armada_tpu.models import decode_result, run_round_on_device, schedule_round
from armada_tpu.models.incremental import IncrementalBuilder, _node_bucket
from armada_tpu.models.slab import DeviceDeltaCache
from armada_tpu.loadgen.workload import (
    CancelOp,
    MixConfig,
    ReprioritizeOp,
    SubmitOp,
    WorkloadGenerator,
)
from armada_tpu.parallel.mesh import make_mesh, pad_problem, shard_problem
from armada_tpu.parallel.mesh_slab import MeshDeviceDeltaCache
from armada_tpu.parallel.serving import mesh_serving, reset_mesh_serving

NOW_NS = 1_000_000_000_000


@pytest.fixture(autouse=True)
def _fresh_mesh_state():
    """Mesh serving is process-global (like the watchdog supervisor):
    every test starts and leaves disarmed."""
    reset_mesh_serving()
    yield
    reset_mesh_serving()


def make_config(**kw) -> SchedulingConfig:
    return SchedulingConfig(
        shape_bucket=64,
        priority_classes={
            "low": PriorityClass("low", priority=100, preemptible=True),
            "high": PriorityClass("high", priority=1000, preemptible=False),
        },
        default_priority_class="high",
        maximum_scheduling_burst=16,
        **kw,
    )


def make_world(cfg, num_nodes=12, num_queues=3):
    F = cfg.resource_list_factory()
    nodes = [
        NodeSpec(
            id=f"n{i}",
            pool="default",
            total_resources=F.from_mapping({"cpu": "16", "memory": "64"}),
        )
        for i in range(num_nodes)
    ]
    queues = [Queue(f"q{i}", weight=1.0 + i) for i in range(num_queues)]
    return F, nodes, queues


class ChurnWorld:
    """One builder+cache arm of the A/B, driven by shared loadgen ops."""

    def __init__(self, cfg, F, nodes, queues, cache):
        self.cfg = cfg
        self.F = F
        self.builder = IncrementalBuilder(cfg, "default", queues)
        self.builder.set_nodes(nodes)
        self.cache = cache
        self.spec_of = {}
        self.leased = set()

    def submit_specs(self, specs):
        for s in specs:
            self.spec_of[s.id] = s
        self.builder.submit_many(specs)

    def cancel(self, jid):
        self.builder.remove(jid)
        self.builder.unlease(jid)
        self.spec_of.pop(jid, None)
        self.leased.discard(jid)

    def reprioritize(self, jid, priority):
        spec = self.spec_of.get(jid)
        if spec is None or jid in self.leased:
            return  # queued-only churn in this harness
        spec = dataclasses.replace(spec, priority=priority)
        self.spec_of[jid] = spec
        self.builder.remove(jid)
        self.builder.submit_many([spec])

    def cycle(self):
        bundle, ctx = self.builder.assemble_delta()
        dev = self.cache.apply(bundle)
        res = schedule_round(
            dev,
            num_levels=len(ctx.ladder) + 2,
            max_slots=ctx.max_slots,
            slot_width=ctx.slot_width,
        )
        outcome = decode_result(res, ctx)
        return bundle, dev, outcome

    def apply(self, outcome):
        self.builder.remove_many(outcome.scheduled.keys())
        leases = []
        for jid, nid in outcome.scheduled.items():
            spec = self.spec_of.get(jid)
            if spec is not None:
                leases.append(RunningJob(job=spec, node_id=nid))
                self.leased.add(jid)
        self.builder.lease_many(leases)
        for jid in outcome.preempted:
            self.builder.unlease(jid)
            self.leased.discard(jid)


def _specs_from_ops(F, gen, ops, seq, tick):
    """Deterministic JobSpecs from a WorkloadGenerator op batch (ids are
    ours -- the server assigns them in production; here both arms must see
    IDENTICAL streams, so the test owns the id space).  Submitted ids feed
    back into the generator's live pool, so later cancels/reprioritises
    really target them."""
    submits, cancels, reprios = [], [], []
    for op in ops:
        if isinstance(op, SubmitOp):
            ids = []
            for item in op.items:
                i = seq[0]
                seq[0] += 1
                spec = JobSpec(
                    id=f"lg{i:06d}",
                    queue=op.queue,
                    priority=item.priority,
                    priority_class="low" if item.priority % 2 else "high",
                    submit_time=float(tick * 1000 + i % 1000),
                    resources=F.from_mapping(
                        {"cpu": item.resources["cpu"], "memory": "1"}
                    ),
                    gang_id=item.gang_id,
                    gang_cardinality=item.gang_cardinality,
                )
                submits.append(spec)
                ids.append(spec.id)
            gen.note_submitted(op.queue, ids)
        elif isinstance(op, CancelOp):
            cancels.extend(op.job_ids)
        elif isinstance(op, ReprioritizeOp):
            reprios.append((op.job_ids, op.priority))
    return submits, cancels, reprios


def assert_mirror_state_equal(bundle_a, bundle_b):
    """Mirror-state bit-equality: both arms assemble the identical dense
    problem (field by field) -- the whole cycle state, not just decisions."""
    pa, pb = bundle_a.materialize(), bundle_b.materialize()
    for name, a, b in zip(pa._fields, pa, pb):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"mirror drift in {name}"
        )


def assert_device_equals_materialize(bundle, dev):
    truth = bundle.materialize()
    for name, d, h in zip(dev._fields, dev, truth):
        np.testing.assert_array_equal(
            np.asarray(d), np.asarray(h), err_msg=f"device drift in {name}"
        )


def run_churn_ab(seed, cycles=5, burst_at=3, prefetch_at=2):
    """Drive both arms through seeded loadgen churn; assert equality every
    cycle.  Returns total scheduled."""
    mesh_serving().configure(8)
    cfg = make_config()
    F, _nodes, _queues = make_world(cfg)
    # queue names follow the generator's own naming (queue_prefix-i)
    nodes = [
        NodeSpec(
            id=f"n{i}",
            pool="default",
            total_resources=F.from_mapping({"cpu": "16", "memory": "64"}),
        )
        for i in range(12)
    ]
    queues = [Queue(f"q-{i}", weight=1.0 + i) for i in range(3)]
    single = ChurnWorld(cfg, F, nodes, queues, DeviceDeltaCache())
    mesh = ChurnWorld(cfg, F, nodes, queues, MeshDeviceDeltaCache())
    gen = WorkloadGenerator(
        MixConfig(num_queues=3, queue_prefix="q", gang_fraction=0.2), seed=seed
    )
    total = 0
    seq = [0]
    for cyc in range(cycles):
        ops = gen.next_ops(14 if cyc != burst_at else 90)
        submits, cancels, reprios = _specs_from_ops(F, gen, ops, seq, cyc)
        if cyc == burst_at:
            # slab-growing burst: blow past the 64-slot bucket so the sig
            # changes and the mesh arm pays a full sharded re-upload
            extra = [
                JobSpec(
                    id=f"burst{seed}-{i}",
                    queue=f"q-{i % 3}",
                    priority_class="high",
                    submit_time=float(5000 + i),
                    resources=F.from_mapping({"cpu": "1", "memory": "1"}),
                )
                for i in range(80)
            ]
            submits = submits + extra
        for w in (single, mesh):
            w.submit_specs(submits)
            for jid in cancels:
                w.cancel(jid)
            for jids, prio in reprios:
                for jid in jids:
                    w.reprioritize(jid, prio)
        bundle_a, _dev_a, out_a = single.cycle()
        bundle_b, dev_b, out_b = mesh.cycle()
        assert_mirror_state_equal(bundle_a, bundle_b)
        assert_device_equals_materialize(bundle_b, dev_b)
        assert out_a.scheduled == out_b.scheduled, f"cycle {cyc} diverged"
        assert out_a.preempted == out_b.preempted
        assert sorted(out_a.failed) == sorted(out_b.failed)
        single.apply(out_a)
        mesh.apply(out_b)
        total += len(out_a.scheduled)
        if cyc == prefetch_at:
            # shadow-pipeline stage (b) on the sharded slab: content rows
            # ship early, next cycle stays bit-equal (asserted above)
            mesh.builder.prefetch_content(mesh.cache)
            single.builder.prefetch_content(single.cache)
    assert mesh.cache.mesh_devices == 8
    return total


# --- 1. steady-cycle equality over loadgen churn (fast pick: seed 0) --------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mesh_steady_cycle_bit_equal_over_churn(seed):
    total = run_churn_ab(seed)
    assert total > 10  # the churn actually scheduled work


def test_mesh_churn_bit_equal_under_the_chip_body(monkeypatch):
    """The mesh equality suite on the body an accelerator compiles (no fit
    cache; ARMADA_CACHE_SLOTS=0 here): sharded vs single-device cycles stay
    bit-equal cycle-by-cycle -- the full [N] fit masks and argmin of every
    trip ride the node-axis sharding, where the cached body reads rows."""
    monkeypatch.setenv("ARMADA_CACHE_SLOTS", "0")
    total = run_churn_ab(0)
    assert total > 10


# --- 2. the degrade ladder ---------------------------------------------------


def test_mesh_degrades_to_smaller_mesh_on_device_fault(monkeypatch):
    """device_round fault mid-cycle: the ladder steps 8 -> 4, the SAME
    round re-runs on the smaller mesh bit-equal, the supervisor never
    leaves the device backend (zero CPU fallbacks), later cycles re-shard
    through the reset-hook cache replacement, restore() returns to 8."""
    from armada_tpu.core import faults
    from armada_tpu.core.watchdog import add_reset_hook, reset_supervisor

    mesh_serving().configure(8)
    sup = reset_supervisor()
    sup.configure(deadline_s=120.0, reprobe_interval_s=0)
    cfg = make_config()
    F, nodes, queues = make_world(cfg)
    single = ChurnWorld(cfg, F, nodes, queues, DeviceDeltaCache())
    mesh = ChurnWorld(cfg, F, nodes, queues, MeshDeviceDeltaCache())
    specs = [
        JobSpec(
            id=f"d{i}",
            queue=f"q{i % 3}",
            priority_class="high",
            submit_time=float(i),
            resources=F.from_mapping({"cpu": "2", "memory": "1"}),
        )
        for i in range(30)
    ]
    for w in (single, mesh):
        w.submit_specs(specs)

    # what the feed's reset hook does in serve: replace the cache
    def replace_cache():
        mesh.cache = MeshDeviceDeltaCache()

    add_reset_hook(replace_cache)

    _bundle_a, _dev_a, out_a = single.cycle()

    bundle_b, ctx_b = mesh.builder.assemble_delta()
    faults.reset_counters()
    monkeypatch.setenv("ARMADA_FAULT", "device_round:error")
    _res, out_b = run_round_on_device(
        bundle_b.stats_view(),
        ctx_b,
        cfg,
        device_problem=lambda: mesh.cache.apply(bundle_b),
        host_problem=bundle_b.materialize,
    )
    monkeypatch.delenv("ARMADA_FAULT")

    snap = mesh_serving().snapshot()
    assert snap["devices"] == 4 and snap["degrades"] == 1
    # never CPU: the supervisor stayed on the device backend
    assert sup.snapshot()["backend"] == "device"
    assert sup.snapshot()["fallbacks"] == 0
    assert out_a.scheduled == out_b.scheduled
    assert out_a.preempted == out_b.preempted

    # zero dropped / double-leased: every id placed exactly once
    assert len(out_b.scheduled) == len(set(out_b.scheduled))
    single.apply(out_a)
    mesh.apply(out_b)

    # next cycle re-shards onto the 4-device mesh via the replaced cache
    bundle_a2, _dev_a2, out_a2 = single.cycle()
    bundle_b2, dev_b2, out_b2 = mesh.cycle()
    assert mesh.cache.mesh_devices == 4
    assert_mirror_state_equal(bundle_a2, bundle_b2)
    assert_device_equals_materialize(bundle_b2, dev_b2)
    assert out_a2.scheduled == out_b2.scheduled

    # restore to the full mesh (the re-probe path calls this)
    mesh_serving().restore()
    assert mesh_serving().snapshot()["devices"] == 8
    assert mesh_serving().snapshot()["restores"] == 1
    single.apply(out_a2)
    mesh.apply(out_b2)
    _a3, _d3, out_a3 = single.cycle()
    _b3, dev_b3, out_b3 = mesh.cycle()
    assert mesh.cache.mesh_devices == 8
    assert out_a3.scheduled == out_b3.scheduled


def test_mesh_ladder_walks_and_exhausts():
    ms = mesh_serving()
    ms.configure(8)
    assert ms.device_count() == 8 and ms.axis_multiple() == 8
    assert ms.degrade("t1") is not None  # 4
    assert ms.degrade("t2") is not None  # 2
    assert ms.degrade("t3") is None  # 1: exhausted -> caller goes to CPU
    snap = ms.snapshot()
    assert snap["degrades"] == 3 and snap["devices"] == 0
    # alignment stays the CONFIGURED size through the whole ladder
    assert ms.axis_multiple() == 8
    ms.restore()
    assert ms.snapshot()["devices"] == 8


# --- 3. divisibility padding -------------------------------------------------


def test_pad_problem_lanes_inert():
    """Padding node/gang/run axes to awkward multiples changes NOTHING the
    kernel decides: padded gang lanes end absent (state 3), padded run
    lanes never evict, slot placements identical."""
    from armada_tpu.models.synthetic import synthetic_problem

    problem, meta = synthetic_problem(
        num_nodes=24, num_gangs=40, num_queues=4, num_runs=10, seed=3
    )
    kw = dict(
        num_levels=meta["num_levels"],
        max_slots=meta["max_slots"],
        slot_width=meta["slot_width"],
    )
    G = problem.g_req.shape[0]
    RJ = problem.run_req.shape[0]
    padded = pad_problem(problem, node_multiple=7, job_multiple=6)
    assert padded.node_total.shape[0] % 7 == 0
    assert padded.g_req.shape[0] % 6 == 0
    assert padded.run_req.shape[0] % 6 == 0
    base = schedule_round(problem, **kw)
    pad = schedule_round(padded, **kw)
    for name in ("slot_gang", "slot_nodes", "slot_counts", "n_slots",
                 "q_alloc", "iterations", "termination", "scheduled_count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(base, name)),
            np.asarray(getattr(pad, name)),
            err_msg=name,
        )
    np.testing.assert_array_equal(
        np.asarray(base.g_state), np.asarray(pad.g_state)[:G]
    )
    np.testing.assert_array_equal(
        np.asarray(base.run_evicted), np.asarray(pad.run_evicted)[:RJ]
    )
    # the padded lanes stayed inert
    assert (np.asarray(pad.g_state)[G:] == 3).all()  # absent
    assert not np.asarray(pad.run_evicted)[RJ:].any()


def test_shard_problem_autopads_non_divisible():
    """A 3-device mesh over bucket-256 axes (256 % 3 != 0) pads instead of
    raising mid-serve -- and the sharded round still matches single."""
    from armada_tpu.models.synthetic import synthetic_problem

    problem, meta = synthetic_problem(
        num_nodes=20, num_gangs=32, num_queues=3, num_runs=8, seed=5
    )
    kw = dict(
        num_levels=meta["num_levels"],
        max_slots=meta["max_slots"],
        slot_width=meta["slot_width"],
    )
    mesh = make_mesh(jax.devices()[:3], node_shards=3, job_shards=1)
    assert problem.node_total.shape[0] % 3 != 0  # really needs the pad
    sharded_in = shard_problem(problem, mesh)
    assert sharded_in.node_total.shape[0] % 3 == 0
    single = schedule_round(problem, **kw)
    sharded = schedule_round(sharded_in, **kw)
    for name in ("slot_gang", "slot_nodes", "slot_counts", "n_slots",
                 "q_alloc", "scheduled_count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(single, name)),
            np.asarray(getattr(sharded, name)),
            err_msg=name,
        )


def test_node_bucket_aligns_to_mesh_multiple():
    assert _node_bucket(64) == 64  # mesh off: unchanged
    mesh_serving().configure(8)
    assert _node_bucket(64) == 64
    assert _node_bucket(60) == 64  # rounded up to the 8-multiple
    mesh_serving().configure(6)
    assert _node_bucket(64) % 6 == 0
    # and the builder's assembled node axis honours it
    cfg = SchedulingConfig(
        shape_bucket=60,
        priority_classes={
            "high": PriorityClass("high", priority=1000, preemptible=False)
        },
        default_priority_class="high",
        maximum_scheduling_burst=16,
    )
    mesh_serving().configure(8)
    F, nodes, queues = make_world(cfg, num_nodes=5)
    b = IncrementalBuilder(cfg, "default", queues)
    b.set_nodes(nodes)
    b.submit_many(
        [
            JobSpec(
                id="a1",
                queue="q0",
                priority_class="high",
                submit_time=0.0,
                resources=F.from_mapping({"cpu": "1", "memory": "1"}),
            )
        ]
    )
    bundle, _ctx = b.assemble_delta()
    assert bundle.materialize().node_total.shape[0] % 8 == 0


def test_serve_wires_mesh_block_into_healthz(tmp_path, monkeypatch):
    """The serve-level surface (cli/serve.py): `--mesh N` arms the
    process-global MeshServing before the feed builds its caches, and
    /healthz embeds the mesh block -- requested/devices from the ladder --
    only when mesh serving is enabled.  ARMADA_MESH is the env fallback
    (a malformed value disarms rather than crashing serve)."""
    import json as _json
    import urllib.request

    from armada_tpu.cli.serve import start_control_plane

    cfg = SchedulingConfig(shape_bucket=32)
    p = start_control_plane(
        str(tmp_path / "mesh-data"), port=0, config=cfg,
        cycle_interval_s=0.05, schedule_interval_s=0.5, health_port=0,
        mesh_devices=8,
    )
    try:
        sv = mesh_serving()
        assert sv.enabled() and sv.snapshot()["requested"] == 8
        body = _json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{p.health_server.port}/healthz", timeout=5
            ).read()
        )
        assert body["mesh"]["requested"] == 8
        assert body["mesh"]["devices"] == 8
        assert body["mesh"]["degrades"] == 0
    finally:
        p.stop()

    # env fallback: ARMADA_MESH resolves when --mesh is not given; a
    # malformed value means "off" (serve must start, block absent).
    for env_val, want_enabled in (("8", True), ("not-a-number", False)):
        monkeypatch.setenv("ARMADA_MESH", env_val)
        reset_mesh_serving()
        p = start_control_plane(
            str(tmp_path / f"mesh-env-{want_enabled}"), port=0, config=cfg,
            cycle_interval_s=0.05, schedule_interval_s=0.5, health_port=0,
        )
        try:
            assert mesh_serving().enabled() is want_enabled
            body = _json.loads(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{p.health_server.port}/healthz",
                    timeout=5,
                ).read()
            )
            assert ("mesh" in body) is want_enabled
        finally:
            p.stop()
