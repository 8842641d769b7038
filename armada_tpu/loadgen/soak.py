"""The soak driver: sustained open-loop traffic against a real control plane.

Builds the full in-process serving stack (SubmitServer -> eventlog ->
ingestion -> scheduler with the incremental feed -> fake executor fleet --
the same wiring `armadactl serve` runs, minus sockets), then drives it for a
wall-clock window at a target event rate while the streaming SLO layer
(scheduler/slo.py) accumulates cycle-latency / time-to-first-lease /
ingest-lag distributions.  Optionally arms an ``ARMADA_FAULT`` site mid-soak
(chaos-under-load): the device-loss failover then shows up as the
``cycle_latency_degraded_s`` histogram -- degradation measured as a latency
distribution, not a pass/fail drill.

One entry point: :func:`run_soak` -> the report dict `tools/soak.py` and
``armadactl soak`` print as ONE JSON line (same contract as bench.py), and
the keys bench.py merges under ``soak_*``.

Env downscale knobs (CPU hosts; mirror ARMADA_BENCH_*): ARMADA_SOAK_WINDOW_S,
ARMADA_SOAK_RATE, ARMADA_SOAK_NODES, ARMADA_SOAK_QUEUES, ARMADA_SOAK_DSN
(route the scheduler DB through pgwire against a real PostgreSQL).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Optional

from armada_tpu.core.config import SchedulingConfig
from armada_tpu.core.logging import get_logger
from armada_tpu.core.types import NodeSpec
from armada_tpu.loadgen.arrivals import make_arrivals
from armada_tpu.loadgen.lifecycle import LifecycleTracker
from armada_tpu.loadgen.workload import (
    CancelOp,
    MixConfig,
    ReprioritizeOp,
    SubmitOp,
    WorkloadGenerator,
)
from armada_tpu.ops.metrics import mono_now

_log = get_logger(__name__)


@dataclasses.dataclass
class SoakConfig:
    window_s: float = 120.0
    target_eps: float = 500.0  # arrival events per second
    process: str = "poisson"  # poisson | bursty | ramp
    seed: int = 0
    num_queues: int = 4
    num_nodes: int = 8
    node_cpu: str = "16"
    node_memory: str = "64"
    job_runtime_s: float = 3.0
    cycle_interval_s: float = 0.25
    schedule_interval_s: float = 1.0
    drain_s: float = 5.0
    gang_fraction: float = 0.05
    # chaos-under-load: an ARMADA_FAULT entry ("site:mode", e.g.
    # "device_round:hang") armed at `fault_at_frac` of the window.
    fault: Optional[str] = None
    fault_at_frac: float = 0.5
    watchdog_s: float = 5.0  # round deadline while a fault is configured
    db_url: Optional[str] = None  # external scheduler DB (pgwire DSN)
    # Mid-soak kill/restart leg (crash-under-load): at this fraction of the
    # window, checkpoint, fire the ingest_ack crash window (a batch commits
    # but its in-memory ack dies), abandon the whole serving world WITHOUT
    # drain, and rebuild it from the data dir (checkpoint restore + log
    # suffix replay).  Recovery time lands in the restart_recovery_s SLO
    # histogram (RTO); LifecycleTracker then pins zero dropped/double-leased
    # jobs ACROSS the restart.  None = no crash leg.
    crash_at_frac: Optional[float] = None
    # Partition-parallel ingestion (ingest/shards.py): run the soak world's
    # ingesters as this many shard workers.  None = ARMADA_INGEST_SHARDS
    # (the serve knob) or 1; the run's save/restore carries the armed value
    # through the fault/crash legs.
    ingest_shards: Optional[int] = None
    # Sharded materialized store (ingest/storeunion.py): each ingest shard
    # leg writes its own SQLite file behind the union reader.  None =
    # ARMADA_STORE_SHARDS (the serve knob) or 1; >1 forces file-backed
    # storage and rounds the ingest width up to a multiple (each worker's
    # partition set must live in one store shard).
    store_shards: Optional[int] = None
    # Heterogeneous fleet: hardware types assigned round-robin across the
    # soak nodes (() = every node untyped, the pre-heterogeneity world) and
    # the fraction of submits carrying a node-type throughput map over
    # those types (loadgen/workload.MixConfig.type_sensitive_fraction).
    node_types: tuple = ()
    type_sensitive_fraction: float = 0.3

    @staticmethod
    def from_env(**overrides) -> "SoakConfig":
        """Env-downscaled config (the bench/CI shape)."""
        kw = dict(
            window_s=float(os.environ.get("ARMADA_SOAK_WINDOW_S", 120.0)),
            target_eps=float(os.environ.get("ARMADA_SOAK_RATE", 500.0)),
            num_nodes=int(os.environ.get("ARMADA_SOAK_NODES", 8)),
            num_queues=int(os.environ.get("ARMADA_SOAK_QUEUES", 4)),
            db_url=os.environ.get("ARMADA_SOAK_DSN") or None,
            node_types=tuple(
                t.strip()
                for t in os.environ.get("ARMADA_SOAK_NODE_TYPES", "").split(",")
                if t.strip()
            ),
        )
        kw.update(overrides)
        return SoakConfig(**kw)


def run_soak_cli(cfg: "SoakConfig") -> dict:
    """The shared driver behind `tools/soak.py` and `armadactl soak`:
    compilation cache on (a cold kernel compile inside the measured window
    would dominate a downscaled run), temp data dir, and the backend
    platform stamped into the report so a CPU run is labelled as one.
    Returns the report; callers print it as ONE JSON line and map `ok` to
    the exit code."""
    import tempfile

    from armada_tpu.core.platform import enable_compilation_cache

    enable_compilation_cache()
    with tempfile.TemporaryDirectory(prefix="armada-soak-") as d:
        report = run_soak(cfg, d)
    import jax

    report["platform"] = jax.devices()[0].platform
    return report


class SoakWorld:
    """The in-process serving stack (tests/control_plane.py wiring, real
    clocks).  Owned by run_soak; close() releases the stores.

    `resume=True` rebuilds the plane from the SAME data dir after a kill:
    the scheduler store restores from the newest checkpoint when it is
    behind the fence, the ingestion pipelines resume from the store's
    committed consumer positions (bounded suffix replay), and queue
    creation skips queues the restored store already holds."""

    def __init__(self, cfg: SoakConfig, data_dir: str, resume: bool = False):
        from armada_tpu.eventlog import EventLog
        from armada_tpu.eventlog.publisher import Publisher
        from armada_tpu.executor import ExecutorService, FakeClusterContext
        from armada_tpu.ingest.converter import convert_sequences
        from armada_tpu.ingest.pipeline import IngestionPipeline
        from armada_tpu.ingest.schedulerdb import SchedulerDb
        from armada_tpu.jobdb.jobdb import JobDb
        from armada_tpu.scheduler import (
            FairSchedulingAlgo,
            Scheduler,
            StandaloneLeaderController,
        )
        from armada_tpu.scheduler.api import ExecutorApi
        from armada_tpu.scheduler.incremental_algo import IncrementalProblemFeed
        from armada_tpu.server import (
            EventApi,
            EventDb,
            QueueRepository,
            SubmitServer,
            event_sink_converter,
        )
        from armada_tpu.server.queues import QueueRecord

        from armada_tpu.ingest import resolve_num_shards

        self.config = SchedulingConfig(
            shape_bucket=64,
            incremental_problem_build=True,
            enable_assertions=False,  # soak measures latency, not invariants
        )
        factory = self.config.resource_list_factory()
        os.makedirs(data_dir, exist_ok=True)
        self.ingest_shards = resolve_num_shards(cfg.ingest_shards)
        store_shards = cfg.store_shards
        if store_shards is None:
            try:
                store_shards = int(os.environ.get("ARMADA_STORE_SHARDS", "0"))
            except ValueError:
                store_shards = 0
        self.store_shards = store_shards if store_shards and store_shards > 1 else 1
        if self.store_shards > 1:
            # store shard = partition % W, ingest shard = partition % N:
            # round the ingest width up so W divides N (each worker's
            # partition set must land in ONE store file).
            self.ingest_shards = max(self.ingest_shards, self.store_shards)
            self.ingest_shards += (-self.ingest_shards) % self.store_shards
        # The partition count is permanent per data dir (crash legs reopen
        # it): widen only when sharding is requested from the start.
        self.log = EventLog(
            os.path.join(data_dir, "log"),
            num_partitions=max(2, self.ingest_shards),
        )
        self.ingest_shards = min(self.ingest_shards, self.log.num_partitions)
        # The crash leg needs a store that SURVIVES the kill: file-backed
        # SQLite in the data dir (the event log already is).  Plain soaks
        # keep the in-memory default -- durability is not what they measure.
        durable = cfg.crash_at_frac is not None
        if self.store_shards > 1:
            # Per-shard store files live in the data dir (always file-
            # backed -- the union reader has no :memory: form), so the
            # crash leg's kill/rebuild reopens the same width.
            from armada_tpu.ingest.storeunion import ShardedSchedulerDb

            self.db = ShardedSchedulerDb(
                cfg.db_url or os.path.join(data_dir, "store-shards"),
                num_shards=self.store_shards,
                num_partitions=self.log.num_partitions,
            )
        else:
            self.db = SchedulerDb(
                cfg.db_url
                or (
                    os.path.join(data_dir, "scheduler.db")
                    if durable
                    else ":memory:"
                )
            )
        self.checkpoints = None
        if durable:
            from armada_tpu.scheduler.checkpoint import (
                CheckpointManager,
                maybe_restore,
            )

            self.checkpoints = CheckpointManager(
                os.path.join(data_dir, "checkpoints")
            )
            self.restore_info = (
                maybe_restore(self.db, self.checkpoints) if resume else None
            )
        self.eventdb = EventDb(":memory:")
        self.publisher = Publisher(self.log)
        if self.ingest_shards > 1:
            from armada_tpu.ingest import PartitionedIngestionPipeline

            self.scheduler_pipeline = PartitionedIngestionPipeline(
                self.log,
                self.db,
                convert_sequences,
                consumer_name="scheduler",
                num_shards=self.ingest_shards,
                start_positions=self.db.positions("scheduler"),
            )
            self.event_pipeline = PartitionedIngestionPipeline(
                self.log,
                self.eventdb,
                event_sink_converter,
                consumer_name="events",
                num_shards=self.ingest_shards,
            )
        else:
            self.scheduler_pipeline = IngestionPipeline(
                self.log,
                self.db,
                convert_sequences,
                consumer_name="scheduler",
                start_positions=self.db.positions("scheduler"),
            )
            self.event_pipeline = IngestionPipeline(
                self.log, self.eventdb, event_sink_converter, consumer_name="events"
            )
        self.queues = QueueRepository(self.db)
        self.server = SubmitServer(self.db, self.publisher, self.queues, self.config)
        self.event_api = EventApi(self.eventdb)
        self.jobdb = JobDb(self.config)
        self.feed = IncrementalProblemFeed(self.config)
        self.feed.attach(self.jobdb)
        self.scheduler = Scheduler(
            self.db,
            self.jobdb,
            FairSchedulingAlgo(
                self.config,
                queues=self.queues.scheduling_queues,
                # The plane's LOGICAL time (event timestamps, lease ages) --
                # not an SLO latency clock, which all ride mono_now().
                # lint: allow(slo-wallclock) -- plane logical time, same clock serve wires
                clock_ns=lambda: int(time.time() * 1e9),
                feed=self.feed,
            ),
            self.publisher,
            StandaloneLeaderController(),
            self.config,
            ingest_step=self.scheduler_pipeline.run_until_caught_up,
        )
        self.executor_api = ExecutorApi(self.db, self.publisher, factory)
        nodes = [
            NodeSpec(
                id=f"soak-n{i}",
                pool="default",
                executor="soak-ex",
                total_resources=factory.from_mapping(
                    {"cpu": cfg.node_cpu, "memory": cfg.node_memory}
                ),
                # round-robin so every configured type has capacity (the
                # rebuild after a crash leg recreates the same assignment)
                node_type=(
                    cfg.node_types[i % len(cfg.node_types)]
                    if cfg.node_types
                    else ""
                ),
            )
            for i in range(cfg.num_nodes)
        ]
        self.cluster = FakeClusterContext(
            nodes, factory, runtime_of=lambda s, r=cfg.job_runtime_s: r
        )
        self.executor = ExecutorService(
            "soak-ex", "default", self.cluster, self.executor_api, factory
        )
        if self.checkpoints is not None:
            self.scheduler.checkpointer = self.checkpoints
        existing = (
            {r["name"] for r in self.db.list_queues()} if resume else set()
        )
        for i in range(cfg.num_queues):
            if f"soak-{i}" not in existing:
                self.server.create_queue(QueueRecord(f"soak-{i}", weight=1.0))

    def ingest(self) -> None:
        self.scheduler_pipeline.run_until_caught_up()
        self.event_pipeline.run_until_caught_up()

    def job_states(self) -> dict:
        rows, _ = self.db.fetch_job_updates(0, 0)
        out = {}
        for r in rows:
            if r["succeeded"]:
                s = "succeeded"
            elif r["failed"]:
                s = "failed"
            elif r["cancelled"]:
                s = "cancelled"
            elif r["queued"]:
                s = "queued"
            else:
                s = "leased"
            out[r["job_id"]] = s
        return out

    def close(self) -> None:
        self.db.close()
        self.eventdb.close()
        self.log.close()


def _apply_ops(world: SoakWorld, gen: WorkloadGenerator, tracker: LifecycleTracker, ops, jobset: str) -> int:
    """Apply generated ops through the submit surface; returns jobs submitted."""
    submitted = 0
    for op in ops:
        if isinstance(op, SubmitOp):
            t0 = mono_now()
            ids = world.server.submit_jobs(op.queue, jobset, op.items)
            gen.note_submitted(op.queue, ids)
            tracker.note_submitted(op.queue, ids, t=t0)
            submitted += len(ids)
        elif isinstance(op, CancelOp):
            world.server.cancel_jobs(op.queue, jobset, op.job_ids, reason="soak")
        elif isinstance(op, ReprioritizeOp):
            world.server.reprioritize_jobs(
                op.queue, jobset, op.priority, job_ids=op.job_ids
            )
    return submitted


def _crash_restart(cfg: SoakConfig, data_dir: str, world: SoakWorld, rec):
    """The kill/restart leg: checkpoint, fire the committed-but-unacked
    ingest crash window, abandon the world without drain, rebuild from the
    data dir (snapshot restore + bounded suffix replay), and record
    kill -> first-completed-scheduling-cycle as an RTO sample.  Returns
    (new_world, rto_s, sequences_replayed)."""
    from armada_tpu.core import faults as _faults

    world.scheduler.checkpoint()
    # Crash window drill under load: the next ingestion batch COMMITS (data
    # + cursor in one txn) and dies before the in-memory ack -- exactly the
    # window the exactly-once design covers.  One-shot; restored below.
    prev_fault = os.environ.get("ARMADA_FAULT")
    os.environ["ARMADA_FAULT"] = "ingest_ack:error"
    try:
        world.ingest()
    except _faults.FaultInjected:
        pass
    finally:
        if prev_fault is None:
            os.environ.pop("ARMADA_FAULT", None)
        else:
            os.environ["ARMADA_FAULT"] = prev_fault
    t_kill = mono_now()
    # "Kill": no drain, no final cycle -- everything durable is already on
    # disk (the log fsyncs per publish, the store commits per batch);
    # close() just releases handles so the drill does not leak fds.
    world.close()
    # The kill takes the MATERIALIZED VIEW with it (the cliff checkpoints
    # exist for: a wiped/corrupt store used to mean full-log replay).  The
    # event log survives; the rebuilt plane must restore the snapshot and
    # replay only the suffix past its fence.
    for suffix in ("", "-wal", "-shm"):
        try:
            os.remove(os.path.join(data_dir, "scheduler.db" + suffix))
        except FileNotFoundError:
            pass
    # Sharded store: wipe the per-shard files too (the rebuild recreates
    # the dir at the same width -- cfg carries it through the restart).
    shard_dir = os.path.join(data_dir, "store-shards")
    if os.path.isdir(shard_dir):
        import shutil

        shutil.rmtree(shard_dir)
    new_world = SoakWorld(cfg, data_dir, resume=True)
    new_world.executor.run_once()
    replayed = new_world.scheduler_pipeline.run_until_caught_up()
    new_world.event_pipeline.run_until_caught_up()
    new_world.scheduler.cycle(schedule=True)
    rto_s = mono_now() - t_kill
    rec.observe_restart(rto_s)
    return new_world, rto_s, replayed


def run_soak(cfg: SoakConfig, data_dir: str, stub_probe: bool = True) -> dict:
    """Run one soak window; returns the JSON-able report.

    `stub_probe`: when a fault is configured, stub the device supervisor's
    re-probe healthy (this host's default backend IS the device under test,
    and the real probe refuses a CPU -- same stub chaos_cycle uses) so
    re-promotion is part of the measured window.
    """
    from armada_tpu.analysis import tsan
    from armada_tpu.core import faults, watchdog
    from armada_tpu.scheduler import slo

    if cfg.crash_at_frac is not None and cfg.db_url:
        # The kill/restart leg wipes the local scheduler.db to exercise
        # snapshot restore; an external store would survive the "kill" with
        # its cursors intact, maybe_restore would (correctly) skip, and the
        # drill would fail spuriously while testing nothing.  Refuse, like
        # serve refuses --replicate-log with external DBs.
        raise ValueError(
            "crash_at_frac cannot be combined with db_url: the kill/restart "
            "drill wipes the embedded scheduler store to exercise "
            "checkpoint restore; an external database survives the kill"
        )
    rec = slo.reset_recorder()
    faults.reset_counters()
    sup = watchdog.reset_supervisor()
    # Everything this driver touches is saved and RESTORED on exit -- a
    # leaked drill knob (50ms re-probe, stubbed-healthy probe, armed
    # fault) turns every later test in the process order-dependent.
    saved_env = {
        k: os.environ.get(k)
        for k in (
            "ARMADA_FAULT",
            "ARMADA_WATCHDOG_S",
            "ARMADA_TSAN",
            "ARMADA_FAULT_HANG_S",
            "ARMADA_REPROBE_INTERVAL_S",
            # The armed ingest-shard count rides through the drill (and its
            # kill/restart resume) untouched: the rebuilt post-crash world
            # must re-shard identically.
            "ARMADA_INGEST_SHARDS",
            # ... and the store-shard width (permanent per store dir -- a
            # post-crash rebuild at a different width would be refused).
            "ARMADA_STORE_SHARDS",
        )
    }
    os.environ.pop("ARMADA_FAULT", None)
    # The round deadline arms only WITH the fault (warm-up cycles compile
    # and legitimately run long); clear any caller-armed drill deadline
    # until then.
    os.environ.pop("ARMADA_WATCHDOG_S", None)
    tsan_was_enabled = tsan.enabled()
    chaos = bool(cfg.fault) or cfg.crash_at_frac is not None
    if chaos:
        # Both chaos legs (device fault, kill/restart) run with the race
        # harness armed: failover and restart are where zombie-writer races
        # live.
        os.environ["ARMADA_TSAN"] = "1"
        tsan.enable()
        tsan.reset()
    if cfg.fault:
        os.environ.setdefault("ARMADA_FAULT_HANG_S", "60")
        os.environ.setdefault("ARMADA_REPROBE_INTERVAL_S", "0.05")
        if stub_probe:
            sup._probe = lambda timeout_s: (True, "soak-stub")

    world = SoakWorld(cfg, data_dir)
    jobset = f"soak-{cfg.seed}"
    arrivals = make_arrivals(cfg.process, cfg.target_eps, seed=cfg.seed)
    mix = MixConfig(
        num_queues=cfg.num_queues,
        gang_fraction=cfg.gang_fraction,
        jobset=jobset,
        node_types=cfg.node_types,
        type_sensitive_fraction=(
            cfg.type_sensitive_fraction if cfg.node_types else 0.0
        ),
    )
    gen = WorkloadGenerator(mix, seed=cfg.seed)
    tracker = LifecycleTracker()
    event_cursors = {q: 0 for q in gen.queues}

    def consume_events():
        for q in gen.queues:
            batch = world.event_api.get_jobset_events(
                q, jobset, from_idx=event_cursors[q], limit=10_000
            )
            for item in batch:
                tracker.observe_sequence(item.sequence)
            if batch:
                event_cursors[q] = batch[-1].idx + 1

    # Fleet must exist before traffic: validation judges against it and the
    # first scheduling round needs node snapshots.
    world.executor.run_once()
    world.ingest()

    try:
        t0 = mono_now()
        fault_at = cfg.fault_at_frac * cfg.window_s
        fault_armed = False
        crash_at_s = (cfg.crash_at_frac or 0.0) * cfg.window_s
        crashed = False
        rto_s = None
        replayed_after_crash = 0
        next_cycle = 0.0
        last_schedule = -1e9
        last_tick = 0.0
        cycles = sched_cycles = 0
        while True:
            now_rel = mono_now() - t0
            if now_rel >= cfg.window_s:
                break
            if (
                cfg.crash_at_frac is not None
                and not crashed
                and now_rel >= crash_at_s
            ):
                world, rto_s, replayed_after_crash = _crash_restart(
                    cfg, data_dir, world, rec
                )
                crashed = True
                cycles += 1
                sched_cycles += 1
                last_tick = mono_now() - t0
                _log.info(
                    "soak: kill/restart at t=%.1fs, RTO %.3fs (%d sequences "
                    "replayed past the fence)",
                    now_rel,
                    rto_s,
                    replayed_after_crash,
                )
            if cfg.fault and not fault_armed and now_rel >= fault_at:
                # One-shot entry; fires on the next device-round check.  The
                # round deadline arms WITH the fault: a soak's warm-up cycles
                # legitimately exceed a drill-sized deadline while XLA
                # compiles, and a spurious pre-fault fallback would pollute
                # the failover-window measurement.
                os.environ["ARMADA_WATCHDOG_S"] = str(cfg.watchdog_s)
                os.environ["ARMADA_FAULT"] = cfg.fault
                fault_armed = True
                _log.info("soak: armed fault %s at t=%.1fs", cfg.fault, now_rel)
            n_due = arrivals.due_until(now_rel)
            if n_due:
                _apply_ops(world, gen, tracker, gen.next_ops(n_due), jobset)
            if now_rel >= next_cycle:
                world.ingest()
                do_schedule = now_rel - last_schedule >= cfg.schedule_interval_s
                world.scheduler.cycle(schedule=do_schedule)
                cycles += 1
                if do_schedule:
                    sched_cycles += 1
                    last_schedule = now_rel
                world.ingest()
                world.cluster.tick(max(0.0, (mono_now() - t0) - last_tick))
                last_tick = mono_now() - t0
                world.executor.run_once()
                consume_events()
                next_cycle = (mono_now() - t0) + cfg.cycle_interval_s
            else:
                time.sleep(
                    min(0.002, max(0.0, min(next_cycle, arrivals.peek()) - now_rel))
                )
        window_wall_s = mono_now() - t0

        # Drain: no new traffic, a few more scheduling cycles so in-flight
        # submits get their shot at a lease before the drop check.
        drain_deadline = mono_now() + cfg.drain_s
        while mono_now() < drain_deadline:
            world.ingest()
            world.scheduler.cycle(schedule=True)
            sched_cycles += 1
            cycles += 1
            world.ingest()
            world.cluster.tick(cfg.cycle_interval_s)
            world.executor.run_once()
            consume_events()
            time.sleep(cfg.cycle_interval_s / 4)

        promoted = None
        if cfg.fault:
            # convergence: the (stubbed-healthy) re-probe promotes back
            deadline = mono_now() + 10.0
            while sup.degraded and mono_now() < deadline:
                time.sleep(0.05)
            promoted = not sup.degraded

        tracker.check_dropped(world.job_states())
        tsan_found = tsan.take_violations() if chaos else []

        slo_snap = rec.snapshot()
        events_total = sum(gen.counts.values()) - gen.counts["gang_jobs"]
        report = {
            "tool": "soak",
            "window_s": round(window_wall_s, 2),
            "process": cfg.process,
            "seed": cfg.seed,
            "target_eps": cfg.target_eps,
            "achieved_eps": round(events_total / max(window_wall_s, 1e-9), 1),
            "events": dict(gen.counts),
            "cycles": cycles,
            "schedule_cycles": sched_cycles,
            "nodes": cfg.num_nodes,
            "queues": cfg.num_queues,
            "slo": slo_snap,
            "jobs": tracker.summary(),
            "violations": len(tracker.violations),
            "device_state": {
                k: sup.snapshot()[k]
                for k in ("backend", "fallbacks", "promotions")
            },
        }
        report["ingest_shards"] = world.ingest_shards
        report["store_shards"] = world.store_shards
        # Flat headline keys (the bench-JSON soak_* shape).
        for name, src in (
            ("cycle", slo_snap.get("cycle_latency_s", {})),
            ("ttfl", slo_snap.get("time_to_first_lease_s", {})),
            ("ingest_lag", slo_snap.get("ingest_visible_lag_s", {})),
        ):
            for p in ("p50_s", "p95_s", "p99_s"):
                if p in src:
                    report[f"{name}_{p}"] = src[p]
        if cfg.fault:
            report["fault"] = cfg.fault
            report["fault_at_s"] = round(fault_at, 1)
            report["promoted"] = promoted
            report["degraded_cycles"] = slo_snap.get(
                "cycle_latency_degraded_s", {}
            ).get("count", 0)
            report["slo_degraded"] = slo_snap.get("cycle_latency_degraded_s", {})
            report["tsan_violations"] = len(tsan_found)
            if tsan_found:
                report["tsan_detail"] = tsan_found[:5]
        if cfg.crash_at_frac is not None:
            report["crash"] = {
                "at_s": round(crash_at_s, 1),
                "rto_s": round(rto_s, 3) if rto_s is not None else None,
                "replayed_sequences": replayed_after_crash,
                "restored_from_checkpoint": bool(
                    (getattr(world, "restore_info", None) or {}).get(
                        "restored"
                    )
                ),
            }
            restart_hist = slo_snap.get("restart_recovery_s", {})
            for p in ("p50_s", "p95_s", "p99_s"):
                if p in restart_hist:
                    report[f"restart_{p}"] = restart_hist[p]
            report.setdefault("tsan_violations", len(tsan_found))
            if tsan_found:
                report.setdefault("tsan_detail", tsan_found[:5])
        if tracker.violations:
            report["violation_detail"] = tracker.violations[:10]
        report["ok"] = bool(
            not tracker.violations
            and not tsan_found
            and report["jobs"]["leased"] > 0
            and slo_snap.get("cycle_latency_s", {}).get("count", 0) > 0
            # a configured fault must actually FIRE (>=1 fallback), fail
            # over without an SLO gap, and re-promote
            and (
                not cfg.fault
                or (report["device_state"]["fallbacks"] >= 1 and promoted)
            )
            # a configured kill/restart leg must actually restart (RTO
            # recorded) AND restore from the checkpoint it wrote
            and (
                cfg.crash_at_frac is None
                or (crashed and report["crash"]["restored_from_checkpoint"])
            )
        )
        return report
    finally:
        world.close()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if cfg.fault and cfg.fault.startswith("convert_record"):
            # The poison drill's latch is STICKY by design (the fault is
            # one-shot, the latched payload keeps failing); a soak that
            # armed it must not leak it into later tests.
            from armada_tpu.ingest import dlq as _dlq

            _dlq.reset_poison()
        if chaos and not tsan_was_enabled:
            # Leave the race harness the way we found it: an armed-but-
            # unharvested tsan would change every later test's behavior.
            tsan.disable()
        if cfg.fault and stub_probe:
            # Drop the always-healthy probe stub with the supervisor it
            # was installed on; later device-loss tests must pay real
            # probes again.
            watchdog.reset_supervisor()
