"""Round-output verification (models/verify.py + scheduler/quarantine.py).

The certification layer's contract, pinned four ways:

1. *No false positives*: clean rounds verify green, multi-seed, in BOTH
   assemble modes (legacy dense build_problem and the incremental slab
   path), with running jobs/evictions in play, under the chip's body
   and XLA:CPU's, pipelined and sequential -- and an armed plane's DECISIONS are
   bit-identical to a disarmed one's (the pass only reads).
2. *Oracle cross-check of every invariant*: tampering with exactly one of
   the kernel's redundant encodings (header scalar, slot record, gang
   state, accumulators, evictee masks, fetched bytes) fires exactly the
   site that cross-checks it -- including the round-12 GSPMD class (a
   whole accumulator multiplied by the shard count).
3. *The corruption drill end to end*: every ARMADA_FAULT=round_corrupt
   mode is detected BEFORE decode commits anything, the failover re-run
   is bit-equal to an uncorrupted round, and the device quarantine blocks
   re-promotion until operator clear.
4. *Transfer economics*: exactly ONE extra device->host transfer per
   verified round; the disabled path adds zero transfers and zero state.
"""

from __future__ import annotations

import numpy as np
import pytest

from armada_tpu.core import faults, watchdog
from armada_tpu.core.config import SchedulingConfig
from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob
from armada_tpu.models import run_round_on_device, run_scheduling_round
from armada_tpu.models import verify as verify_mod
from armada_tpu.models.fair_scheduler import schedule_round
from armada_tpu.models.problem import (
    SchedulingProblem,
    begin_decode,
    build_problem,
)
from armada_tpu.models.verify import (
    RoundVerificationError,
    reset_verify_state,
    verify_state,
)
from armada_tpu.models.xfer import TRANSFER_STATS
from armada_tpu.scheduler.quarantine import (
    DeviceQuarantine,
    device_quarantine,
    reset_device_quarantine,
)

CFG = SchedulingConfig(shape_bucket=32)
F = CFG.resource_list_factory()


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    """Fresh verification ledger / quarantine / supervisor per test; the
    pass armed (individual tests disarm to pin the off path)."""
    monkeypatch.setenv("ARMADA_VERIFY", "1")
    monkeypatch.delenv("ARMADA_FAULT", raising=False)
    monkeypatch.setenv("ARMADA_REPROBE_INTERVAL_S", "0")
    faults.reset_counters()
    reset_verify_state()
    reset_device_quarantine()
    watchdog.reset_supervisor()
    saved_hooks = list(watchdog._reset_hooks)
    watchdog._reset_hooks.clear()
    yield
    faults.reset_counters()
    reset_verify_state()
    reset_device_quarantine()
    watchdog.set_promotion_gate(None)
    watchdog.reset_supervisor()
    watchdog._reset_hooks[:] = saved_hooks


def node(i, cpu=8, mem=32):
    return NodeSpec(
        id=f"n{i:03d}",
        pool="default",
        total_resources=F.from_mapping({"cpu": cpu, "memory": mem}),
    )


def job(i, queue="qa", cpu=2, mem=2, **kw):
    return JobSpec(
        id=f"j{i:04d}",
        queue=queue,
        submit_time=float(i),
        resources=F.from_mapping({"cpu": cpu, "memory": mem}),
        **kw,
    )


def mixed_world(seed, num_nodes=8, num_jobs=40, num_queues=3, runs=3):
    """Queued backlog + preemptible running jobs, so the invariants see
    evictions (the `holds` algebra) and not just fresh placements."""
    rng = np.random.default_rng(seed)
    nodes = [node(i) for i in range(num_nodes)]
    queues = [
        Queue(f"q{i}", float(rng.choice([1.0, 2.0]))) for i in range(num_queues)
    ]
    jobs = [
        job(
            i,
            queue=f"q{int(rng.integers(num_queues))}",
            cpu=int(rng.choice([1, 2, 4, 8])),
            mem=int(rng.choice([1, 2, 4])),
        )
        for i in range(num_jobs)
    ]
    running = [
        RunningJob(
            job=job(1000 + r, queue=f"q{r % num_queues}", cpu=4, mem=4),
            node_id=nodes[r % num_nodes].id,
        )
        for r in range(runs)
    ]
    return nodes, queues, jobs, running


def world_kwargs(seed):
    nodes, queues, jobs, running = mixed_world(seed)
    return dict(
        pool="default",
        nodes=nodes,
        queues=queues,
        queued_jobs=jobs,
        running=running,
    )


def decisions(outcome):
    return (
        sorted(outcome.scheduled.items()),
        sorted(outcome.preempted),
        sorted(outcome.failed),
    )


# --- 0. fast-tier representative (conftest picks the first tests) ------------


def test_verify_representative(monkeypatch):
    """End to end in one compile: a clean armed round verifies green with
    exactly ONE extra transfer, and an injected header corruption is
    caught before decode, fails over bit-equal, and takes a quarantine
    strike -- the acceptance contract in miniature."""
    monkeypatch.delenv("ARMADA_VERIFY", raising=False)
    baseline = run_scheduling_round(CFG, **world_kwargs(9))
    monkeypatch.setenv("ARMADA_VERIFY", "1")
    TRANSFER_STATS.reset()
    armed = run_scheduling_round(CFG, **world_kwargs(9))
    assert decisions(armed) == decisions(baseline)
    snap = verify_state().snapshot()
    assert snap["failures"] == 0 and snap["rounds_verified"] == 1
    # compact fetch + verification buffer = the one allowed extra
    assert TRANSFER_STATS.snapshot()["down_transfers"] == 2

    faults.reset_counters()
    monkeypatch.setenv("ARMADA_FAULT", "round_corrupt:header")
    out = run_scheduling_round(CFG, **world_kwargs(9))
    assert decisions(out) == decisions(baseline)
    snap = verify_state().snapshot()
    assert snap["failures"] == 1
    assert "slot-count" in snap["failures_by_site"]
    assert watchdog.supervisor().fallbacks == 1
    assert sum(
        device_quarantine().snapshot()["strike_totals"].values()
    ) >= 1


# --- 1. no false positives ---------------------------------------------------


@pytest.mark.parametrize("seed", [1, 7, 13, 42])
def test_clean_rounds_verify_green_multi_seed(seed, monkeypatch):
    monkeypatch.delenv("ARMADA_VERIFY", raising=False)
    baseline = run_scheduling_round(CFG, **world_kwargs(seed))
    monkeypatch.setenv("ARMADA_VERIFY", "1")
    armed = run_scheduling_round(CFG, **world_kwargs(seed))
    snap = verify_state().snapshot()
    assert snap["failures"] == 0
    assert snap["rounds_verified"] >= 1
    assert snap["last_verdict"]["ok"]
    # the pass only READS: armed decisions identical to disarmed
    assert decisions(armed) == decisions(baseline)


def run_incremental_cycles(cfg, seed, cycles=3, pipeline="1"):
    """The slab path (IncrementalProblemFeed -> DeviceDeltaCache ->
    run_round_on_device), multiple cycles so prefetch/lease churn is in
    play; returns per-cycle decisions."""
    import os

    from armada_tpu.scheduler.incremental_algo import IncrementalProblemFeed

    os.environ["ARMADA_PIPELINE"] = pipeline
    try:
        nodes, queues, jobs, _running = mixed_world(seed)
        feed = IncrementalProblemFeed(cfg)
        b = feed.builder_for("default")
        b.set_queues(queues)
        b.set_nodes(nodes)
        b.submit_many(jobs)
        spec_of = {j.id: j for j in jobs}
        out = []
        for _ in range(cycles):
            bundle, ctx = b.assemble_delta()
            devcache = feed.devcache_for("default")
            _res, outcome = run_round_on_device(
                bundle.stats_view(),
                ctx,
                cfg,
                device_problem=lambda dc=devcache, b_=bundle: dc.apply(b_),
                host_problem=bundle.materialize,
            )
            out.append(
                (sorted(outcome.scheduled.items()), sorted(outcome.preempted))
            )
            b.remove_many(outcome.scheduled.keys())
            b.lease_many(
                [
                    RunningJob(job=spec_of[jid], node_id=nid)
                    for jid, nid in outcome.scheduled.items()
                ]
            )
        return out
    finally:
        os.environ.pop("ARMADA_PIPELINE", None)


@pytest.mark.parametrize("seed", [3, 21])
def test_incremental_mode_verifies_green(seed):
    run_incremental_cycles(CFG, seed)
    snap = verify_state().snapshot()
    assert snap["failures"] == 0
    assert snap["rounds_verified"] >= 3


def test_verification_armed_parity_under_both_bodies(round_body, monkeypatch):
    """The armed plane's decisions are bit-identical to the disarmed one's
    under the chip's body (no fit cache) and XLA:CPU's, pipelined AND
    sequential -- and round verification passes the chip's body on the CPU."""
    monkeypatch.delenv("ARMADA_VERIFY", raising=False)
    base = run_incremental_cycles(CFG, seed=11, pipeline="1")
    monkeypatch.setenv("ARMADA_VERIFY", "1")
    reset_verify_state()
    armed = run_incremental_cycles(CFG, seed=11, pipeline="1")
    armed_seq = run_incremental_cycles(CFG, seed=11, pipeline="0")
    assert armed == base
    assert armed_seq == base
    snap = verify_state().snapshot()
    assert snap["failures"] == 0
    assert snap["rounds_verified"] >= 6


# --- 2. oracle cross-check: each tampered encoding fires its site ------------


def device_round(seed):
    import jax.numpy as jnp

    nodes, queues, jobs, running = mixed_world(seed)
    problem, ctx = build_problem(
        CFG,
        pool="default",
        nodes=nodes,
        queues=queues,
        queued_jobs=jobs,
        running=running,
    )
    dev = SchedulingProblem(*(jnp.asarray(a) for a in problem))
    result = schedule_round(
        dev,
        num_levels=len(ctx.ladder) + 2,
        max_slots=ctx.max_slots,
        slot_width=ctx.slot_width,
    )
    return dev, result, ctx


def sites_of(dev, result, ctx, corrupt_bytes=False):
    """Dispatch + fetch + verdict on (possibly tampered) state; returns the
    failed site tuple ( () = verified green)."""
    fin = begin_decode(result, ctx)
    vd = verify_mod.dispatch_verify(dev, result, fin.dispatched, ctx)
    assert vd is not None
    fin.fetch()
    if corrupt_bytes:
        buf = ctx.last_compact_np.copy()
        buf[3] ^= np.int32(1 << 19)
        ctx.last_compact_np = buf
    try:
        verify_mod.finish_verify(vd, ctx)
    except RoundVerificationError as e:
        return e.sites
    return ()


def test_tampered_encodings_fire_their_sites():
    import jax.numpy as jnp

    dev, result, ctx = device_round(5)
    assert sites_of(dev, result, ctx) == ()
    n_slots = int(result.n_slots)
    assert n_slots >= 2, "tamper world must place"
    N = int(dev.node_total.shape[0])

    # header scalar (the round_corrupt `header` class)
    sites = sites_of(
        dev,
        result._replace(scheduled_count=result.scheduled_count + jnp.int32(5)),
        ctx,
    )
    assert {"slot-count", "gang-count"} <= set(sites)

    # placement lane -> out-of-range node (the `lane` class)
    sites = sites_of(
        dev,
        result._replace(slot_nodes=result.slot_nodes.at[0, 0].set(N)),
        ctx,
    )
    assert "lane" in sites and "node-capacity" in sites

    # slot member count drifts from the gang's cardinality
    sites = sites_of(
        dev,
        result._replace(
            slot_counts=result.slot_counts.at[0, 0].add(jnp.int32(1))
        ),
        ctx,
    )
    assert {"slot-count", "gang-card"} <= set(sites)

    # slot record vs g_state (duplicate slot / missing slot)
    sites = sites_of(
        dev,
        result._replace(slot_gang=result.slot_gang.at[0].set(result.slot_gang[1])),
        ctx,
    )
    assert "slot-state" in sites

    # truncated slot record
    sites = sites_of(
        dev, result._replace(n_slots=result.n_slots - jnp.int32(1)), ctx
    )
    assert "slot-count" in sites and "slot-state" in sites

    # the round-12 GSPMD miscompile class: a whole accumulator x2
    sites = sites_of(dev, result._replace(q_alloc=result.q_alloc * 2.0), ctx)
    assert sites == ("queue-alloc",)
    sites = sites_of(
        dev, result._replace(alloc=result.alloc.at[0].mul(2.0)), ctx
    )
    assert "node-capacity" in sites

    # rescheduled-without-evicted (needs a valid non-evicted run)
    ev = np.asarray(result.run_evicted)
    rv = np.asarray(dev.run_valid)
    free = np.flatnonzero(rv & ~ev)
    assert free.size, "tamper world must retain a run"
    sites = sites_of(
        dev,
        result._replace(
            run_rescheduled=result.run_rescheduled.at[int(free[0])].set(True)
        ),
        ctx,
    )
    assert sites == ("evictee",)

    # transfer corruption: flipped bit in the FETCHED bytes (the `bytes`
    # class -- only the fingerprint cross-check can see it)
    sites = sites_of(dev, result, ctx, corrupt_bytes=True)
    assert sites == ("fingerprint",)


def test_corrupt_verify_buffer_is_a_failure():
    """A corrupted VERIFICATION transfer must fail closed, not pass open."""
    _dev, _result, ctx = device_round(5)
    with pytest.raises(RoundVerificationError) as ei:
        verify_mod.finish_verify(np.zeros(16, np.int32), ctx)
    assert ei.value.sites == (verify_mod.SITE_BUFFER,)


# --- 3. the corruption drill end to end --------------------------------------


@pytest.mark.parametrize("mode", ["header", "lane", "bytes"])
def test_round_corrupt_drill_detected_and_bit_equal(mode, monkeypatch):
    """Injected corruption at every round_corrupt site: detected BEFORE
    decode commits any decision, the ladder re-runs the SAME round on the
    CPU rung bit-equal to an uncorrupted round, the supervisor records the
    fallback, and the device takes a quarantine strike."""
    monkeypatch.delenv("ARMADA_VERIFY", raising=False)
    baseline = run_scheduling_round(CFG, **world_kwargs(9))
    monkeypatch.setenv("ARMADA_VERIFY", "1")
    reset_verify_state()
    faults.reset_counters()
    monkeypatch.setenv("ARMADA_FAULT", f"round_corrupt:{mode}")
    out = run_scheduling_round(CFG, **world_kwargs(9))
    assert decisions(out) == decisions(baseline)
    snap = verify_state().snapshot()
    assert snap["failures"] == 1
    sup = watchdog.supervisor()
    assert sup.fallbacks == 1 and sup.degraded
    q = device_quarantine().snapshot()
    assert sum(q["strike_totals"].values()) >= 1
    expected_site = {
        "header": "slot-count",
        "lane": "lane",
        "bytes": "fingerprint",
    }[mode]
    assert expected_site in snap["failures_by_site"]


def test_quarantine_blocks_promotion_until_clear(monkeypatch):
    """N strikes -> the re-probe's promote() is vetoed until operator
    clear (the armadactl quarantine --clear flow)."""
    reset_device_quarantine(strikes=1)
    monkeypatch.setenv("ARMADA_FAULT", "round_corrupt:header")
    run_scheduling_round(CFG, **world_kwargs(9))
    sup = watchdog.supervisor()
    assert sup.degraded
    assert watchdog.promotion_blocked() is not None
    assert not sup.promote()
    assert sup.degraded
    cleared = device_quarantine().clear()
    assert cleared
    assert watchdog.promotion_blocked() is None
    assert sup.promote()
    assert not sup.degraded


def test_quarantine_blocks_mesh_restore_until_clear():
    from armada_tpu.parallel.serving import reset_mesh_serving

    ms = reset_mesh_serving()
    ms.configure(4)
    assert ms.degrade("drill") is not None
    assert ms.device_count() == 2
    dq = reset_device_quarantine(strikes=1)
    dq.record_strikes(["chip0"], "drill")
    assert ms.restore() is False
    assert ms.device_count() == 2
    dq.clear()
    assert ms.restore() is True
    assert ms.device_count() == 4
    ms.configure(0)


def test_cpu_rung_verification_failure_escalates(monkeypatch):
    """A verification failure while ALREADY degraded to the CPU rung
    propagates loudly instead of looping the ladder."""
    sup = watchdog.supervisor()
    sup.record_failure("prior loss")
    assert sup.degraded
    faults.reset_counters()
    monkeypatch.setenv("ARMADA_FAULT", "round_corrupt:header")
    with pytest.raises(RoundVerificationError):
        run_scheduling_round(CFG, **world_kwargs(9))


def test_one_shot_arming_and_mode_filter(monkeypatch):
    """round_corrupt entries are one-shot per entry, and each check point
    consumes ONLY its own modes -- the bytes check must not burn a pending
    header entry (core/faults.active modes filter)."""
    monkeypatch.setenv(
        "ARMADA_FAULT", "round_corrupt:header,round_corrupt:bytes"
    )
    faults.reset_counters()
    # the bytes-site check point skips the header entry entirely
    assert faults.active("round_corrupt", modes=("bytes",)) == "bytes"
    assert faults.active("round_corrupt", modes=("bytes",)) is None  # one-shot
    assert faults.active("round_corrupt", modes=("header", "lane")) == "header"
    assert faults.active("round_corrupt", modes=("header", "lane")) is None


# --- 4. transfer economics ---------------------------------------------------


def _one_round_transfer_count(monkeypatch, armed: bool) -> int:
    if armed:
        monkeypatch.setenv("ARMADA_VERIFY", "1")
    else:
        monkeypatch.delenv("ARMADA_VERIFY", raising=False)
    nodes, queues, jobs, running = mixed_world(17)
    problem, ctx = build_problem(
        CFG,
        pool="default",
        nodes=nodes,
        queues=queues,
        queued_jobs=jobs,
        running=running,
    )
    TRANSFER_STATS.reset()
    _res, outcome = run_round_on_device(problem, ctx, CFG)
    assert outcome.scheduled
    return TRANSFER_STATS.snapshot()["down_transfers"]


def test_exactly_one_extra_transfer(monkeypatch):
    disarmed = _one_round_transfer_count(monkeypatch, armed=False)
    reset_verify_state()
    armed = _one_round_transfer_count(monkeypatch, armed=True)
    assert armed == disarmed + 1
    assert verify_state().snapshot()["rounds_verified"] == 1


def test_disabled_path_costs_nothing(monkeypatch):
    _one_round_transfer_count(monkeypatch, armed=False)
    snap = verify_state().snapshot()
    assert snap["rounds_verified"] == 0 and snap["failures"] == 0
    assert not snap["enabled"]


def test_arm_default_tokens_survive_overlap(monkeypatch):
    monkeypatch.delenv("ARMADA_VERIFY", raising=False)
    assert not verify_mod.verify_enabled()
    t1 = verify_mod.arm_default(True)
    t2 = verify_mod.arm_default(False)
    assert not verify_mod.verify_enabled()  # latest armed plane wins
    verify_mod.disarm_default(t2)
    assert verify_mod.verify_enabled()
    verify_mod.disarm_default(t1)
    assert not verify_mod.verify_enabled()
    # malformed env falls back to the armed default, not silently off
    t3 = verify_mod.arm_default(True)
    monkeypatch.setenv("ARMADA_VERIFY", "garbage")
    assert verify_mod.verify_enabled()
    verify_mod.disarm_default(t3)


# --- quarantine scoreboard unit ----------------------------------------------


def test_device_quarantine_window_and_clear():
    q = DeviceQuarantine(strikes=2, window_s=600.0)
    assert q.record_strikes(["d0"], "r1") == []
    assert q.record_strikes(["d0"], "r2") == ["d0"]
    assert "d0" in q.quarantined()
    assert q.promotion_blocked() and "d0" in q.promotion_blocked()
    # second quarantine of the same device does not re-fire
    assert q.record_strikes(["d0"], "r3") == []
    snap = q.snapshot()
    assert snap["strike_totals"]["d0"] == 3
    assert q.clear("d0") == ["d0"]
    assert q.quarantined() == {}
    assert q.promotion_blocked() is None
    # clear-all resets BOTH maps: a device mid-window (struck, not yet
    # quarantined) gets a fresh slate too, alongside the quarantined one
    q.record_strikes(["d0"], "r4")
    q.record_strikes(["d0"], "r5")
    q.record_strikes(["d1"], "r6")
    assert sorted(q.clear()) == ["d0", "d1"]
    assert q.record_strikes(["d1"], "r7") == []  # strike window restarted


def test_device_quarantine_disabled_threshold():
    q = DeviceQuarantine(strikes=0)
    assert q.record_strikes(["d0"], "r") == []
    assert q.quarantined() == {}
    assert q.promotion_blocked() is None
    assert q.snapshot()["strike_totals"] == {"d0": 1}


# --- observability surfaces --------------------------------------------------


def test_healthz_block_and_metrics(monkeypatch):
    from prometheus_client import CollectorRegistry

    from armada_tpu.scheduler.metrics import SchedulerMetrics

    reset_device_quarantine(strikes=1)
    faults.reset_counters()
    monkeypatch.setenv("ARMADA_FAULT", "round_corrupt:lane")
    run_scheduling_round(CFG, **world_kwargs(9))
    block = verify_mod.healthz_block()
    assert block["failures"] == 1
    assert block["last_verdict"] is not None
    assert block["quarantine"]["quarantined"]

    registry = CollectorRegistry()
    metrics = SchedulerMetrics(registry=registry)
    metrics.observe_verify(block)
    assert (
        registry.get_sample_value(
            "armada_round_verification_failures_total", {"site": "lane"}
        )
        == 1.0
    )
    device = next(iter(block["quarantine"]["quarantined"]))
    assert (
        registry.get_sample_value(
            "armada_device_quarantined", {"device": device}
        )
        == 1.0
    )
    # stale-label removal: a cleared device stops exporting
    device_quarantine().clear()
    metrics.observe_verify(verify_mod.healthz_block())
    assert (
        registry.get_sample_value(
            "armada_device_quarantined", {"device": device}
        )
        is None
    )


def test_controlplane_quarantine_verbs():
    """armadactl quarantine rides ExecutorAdmin: status returns the
    healthz block, clear re-admits (plane-local like checkpoints)."""
    from armada_tpu.server.controlplane import ControlPlaneServer

    cp = ControlPlaneServer(publisher=None)
    dq = reset_device_quarantine(strikes=1)
    dq.record_strikes(["chipX"], "drill")
    status = cp.quarantine_status()
    assert "chipX" in status["quarantine"]["quarantined"]
    out = cp.quarantine_clear("chipX")
    assert out == {"cleared": ["chipX"]}
    assert cp.quarantine_status()["quarantine"]["quarantined"] == {}
