"""Chaos driver: N steady scheduling cycles with one randomly injected
fault, asserting convergence.

The drill the device-loss resilience work exists for, runnable anywhere
(no TPU needed -- the "device" is whatever jax's default backend is):

  1. build a steady-state incremental world (builder + device delta cache),
  2. pick a random cycle and a random fault (`device_round:hang` or
     `device_round:error`, via ARMADA_FAULT) and arm the round watchdog,
  3. run N cycles through models.run_round_on_device -- the faulted cycle
     must complete on the CPU failover within the deadline,
  4. re-run the identical cycle script fault-free and assert every cycle's
     scheduled/preempted decisions are BIT-EQUAL,
  5. let the (stubbed-healthy) re-probe promote back to the device and
     assert the post-promotion cycles also match.

Exit code 0 + one JSON line on success; non-zero with the mismatch on
failure.  Knobs: --cycles, --seed, --burst, --jobs/--nodes (world size),
--prefetch (exercise the pipeline's scatter prefetch around the loss).

    python tools/chaos_cycle.py --cycles 8 --seed 3
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_world(cfg, num_nodes, num_queues):
    from armada_tpu.core.types import NodeSpec, Queue

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


CORRUPT_MODES = ("header", "lane", "bytes")


def run_script(
    *, cycles, seed, jobs0, burst, num_nodes, num_queues, fault, fault_cycle,
    prefetch, deadline_s=30.0, mesh=0,
):
    """One deterministic multi-cycle run; returns per-cycle decision lists.
    `fault` is None (clean replay), "hang"/"error" (device loss) or a
    round_corrupt mode ("header"/"lane"/"bytes": silent corruption, which
    ONLY round verification can catch -- ARMADA_VERIFY is armed and the
    device quarantine threshold drops to 1 strike so the drill also
    exercises the promotion gate), injected at `fault_cycle`.  `mesh` >= 2
    arms the mesh serving plane (the chip-loss drill: the faulted cycle
    must degrade to a SMALLER mesh, never CPU)."""
    from armada_tpu.analysis import tsan
    from armada_tpu.core import faults, watchdog
    from armada_tpu.core.config import PriorityClass, SchedulingConfig
    from armada_tpu.core.types import JobSpec, RunningJob
    from armada_tpu.models import run_round_on_device
    from armada_tpu.models.verify import reset_verify_state
    from armada_tpu.parallel.serving import reset_mesh_serving
    from armada_tpu.scheduler.incremental_algo import IncrementalProblemFeed
    from armada_tpu.scheduler.quarantine import reset_device_quarantine

    # The FAULTED leg arms the race harness (analysis/tsan): the watchdog
    # failover is exactly where zombie-worker races live.  The harness then
    # STAYS armed through the promoted-wait and the clean replay -- an
    # abandoned hang-mode worker can unwedge long after its own leg, and a
    # late generation-stale scatter must still be recorded; main() harvests
    # violations only after both legs.
    if fault:
        os.environ["ARMADA_TSAN"] = "1"
        tsan.enable()
        tsan.reset()
    faults.reset_counters()
    sup = watchdog.reset_supervisor()
    os.environ["ARMADA_REPROBE_INTERVAL_S"] = "0.05"
    os.environ["ARMADA_WATCHDOG_S"] = str(deadline_s)
    os.environ["ARMADA_FAULT_HANG_S"] = "60"
    # the re-probe must see a healthy backend: this host's default jax
    # platform IS the device under test, and the real probe refuses a CPU
    sup._probe = lambda timeout_s: (True, "chaos-stub")
    ms = reset_mesh_serving()
    if mesh:
        ms.configure(mesh)
        ms._probe = lambda timeout_s: (True, "chaos-stub")
    corrupt = fault in CORRUPT_MODES
    if corrupt or os.environ.get("ARMADA_CHAOS_VERIFY"):
        # The corruption drill's whole point: verification armed for BOTH
        # legs (the clean replay certifies green), 1 strike quarantines so
        # the drill exercises the promotion gate too.
        os.environ["ARMADA_VERIFY"] = "1"
        reset_verify_state()
        reset_device_quarantine(strikes=1)
    if corrupt:
        # after_n counts that site's checks: one per cycle for the
        # device-side legs (maybe_corrupt_result) AND for the fetched-bytes
        # leg (one compact fetch per cycle in this gang-free world).
        os.environ["ARMADA_FAULT"] = f"round_corrupt:{fault}:{fault_cycle}"
    elif fault:
        # after_n = number of device-round checks before the injected cycle
        os.environ["ARMADA_FAULT"] = f"device_round:{fault}:{fault_cycle}"
    else:
        os.environ.pop("ARMADA_FAULT", None)
    os.environ["ARMADA_PIPELINE_PREFETCH"] = "1" if prefetch else "0"

    cfg = SchedulingConfig(
        shape_bucket=64,
        priority_classes={
            "low": PriorityClass("low", priority=100, preemptible=True),
            "high": PriorityClass("high", priority=1000, preemptible=False),
        },
        default_priority_class="high",
        maximum_scheduling_burst=max(burst, 8),
    )
    F, nodes, queues = build_world(cfg, num_nodes, num_queues)
    feed = IncrementalProblemFeed(cfg)
    b = feed.builder_for("default")
    b.set_queues(queues)
    b.set_nodes(nodes)
    rng = random.Random(seed)
    spec_of = {}
    nid = [0]

    def submit(n):
        specs = []
        for _ in range(n):
            i = nid[0]
            nid[0] += 1
            specs.append(
                JobSpec(
                    id=f"j{i}",
                    queue=f"q{rng.randrange(num_queues)}",
                    priority_class="low" if rng.random() < 0.4 else "high",
                    submit_time=float(i),
                    resources=F.from_mapping(
                        {"cpu": str(rng.randrange(1, 5)), "memory": "1"}
                    ),
                )
            )
        for s in specs:
            spec_of[s.id] = s
        b.submit_many(specs)

    submit(jobs0)
    decisions = []
    for _cycle in range(cycles):
        bundle, ctx = b.assemble_delta()
        devcache = feed.devcache_for("default")
        _, outcome = run_round_on_device(
            bundle.stats_view(),
            ctx,
            cfg,
            device_problem=lambda dc=devcache, b_=bundle: dc.apply(b_),
            host_problem=bundle.materialize,
        )
        decisions.append(
            (sorted(outcome.scheduled.items()), sorted(outcome.preempted))
        )
        b.remove_many(outcome.scheduled.keys())
        b.lease_many(
            [
                RunningJob(job=spec_of[jid], node_id=node)
                for jid, node in outcome.scheduled.items()
            ]
        )
        for jid in outcome.preempted:
            b.unlease(jid)
        submit(burst)
        if prefetch:
            b.prefetch_content(feed.devcaches["default"])
    return decisions, sup, ms


def run_pool_script(
    *, cycles, seed, pools, jobs0, burst, fault, fault_cycle,
    deadline_s=30.0, parallel=True,
):
    """The pool-parallel drill leg (round 17): a P-tenant world driven
    through FairSchedulingAlgo with ARMADA_POOL_PARALLEL armed, one
    injected device fault mid-window -- the faulted pool must walk the
    failover ladder ALONE, every cycle's decisions must equal the serial
    clean replay, and no job may lease twice."""
    from armada_tpu.analysis import tsan
    from armada_tpu.core import faults, watchdog
    from armada_tpu.core.config import PoolConfig, PriorityClass, SchedulingConfig
    from armada_tpu.core.types import JobSpec, NodeSpec, Queue
    from armada_tpu.jobdb.job import Job
    from armada_tpu.jobdb.jobdb import JobDb
    from armada_tpu.scheduler.algo import FairSchedulingAlgo
    from armada_tpu.scheduler.executors import ExecutorSnapshot
    from armada_tpu.scheduler.incremental_algo import IncrementalProblemFeed
    from armada_tpu.scheduler.pool_serving import (
        pool_serving_stats,
        reset_pool_serving_stats,
    )

    if fault:
        os.environ["ARMADA_TSAN"] = "1"
        tsan.enable()
        tsan.reset()
    faults.reset_counters()
    sup = watchdog.reset_supervisor()
    reset_pool_serving_stats()
    os.environ["ARMADA_REPROBE_INTERVAL_S"] = "0.05"
    os.environ["ARMADA_WATCHDOG_S"] = str(deadline_s)
    sup._probe = lambda timeout_s: (True, "chaos-stub")
    os.environ["ARMADA_POOL_PARALLEL"] = "1" if parallel else "0"
    if fault:
        os.environ["ARMADA_FAULT"] = f"device_round:{fault}:{fault_cycle}"
    else:
        os.environ.pop("ARMADA_FAULT", None)

    now_ns = 1_000_000_000_000
    cfg = SchedulingConfig(
        shape_bucket=32,
        priority_classes={
            "low": PriorityClass("low", priority=100, preemptible=True),
            "high": PriorityClass("high", priority=1000, preemptible=False),
        },
        default_priority_class="high",
        maximum_scheduling_burst=max(burst, 8),
        incremental_problem_build=True,
        pools=tuple(PoolConfig(f"cp{i}") for i in range(pools)),
        maximum_scheduling_rate=0.0,
        maximum_per_queue_scheduling_rate=0.0,
    )
    F = cfg.resource_list_factory()
    jdb = JobDb(cfg)
    feed = IncrementalProblemFeed(cfg)
    feed.attach(jdb)
    executors = [
        ExecutorSnapshot(
            id=f"cex{p}",
            pool=f"cp{p}",
            last_update_ns=now_ns,
            nodes=tuple(
                NodeSpec(
                    id=f"cn{p}-{k}",
                    pool=f"cp{p}",
                    total_resources=F.from_mapping(
                        {"cpu": "8", "memory": "32"}
                    ),
                )
                for k in range(3)
            ),
        )
        for p in range(pools)
    ]
    algo = FairSchedulingAlgo(
        cfg,
        queues=lambda: [Queue(f"cq{i}", 1.0 + i) for i in range(3)],
        clock_ns=lambda: now_ns,
        feed=feed,
    )
    rng = random.Random(seed)
    nid = [0]

    def submit(txn, n):
        for _ in range(n):
            i = nid[0]
            nid[0] += 1
            pool = f"cp{i % pools}"
            spec = JobSpec(
                id=f"cj{i:05d}",
                queue=f"cq{rng.randrange(3)}",
                priority_class="low" if rng.random() < 0.4 else "high",
                submit_time=float(i),
                pools=(pool,),
                resources=F.from_mapping(
                    {"cpu": str(rng.randrange(1, 4)), "memory": "1"}
                ),
            )
            txn.upsert(
                Job(spec=spec, queued=True, validated=True, pools=(pool,))
            )

    decisions = []
    leased_ever: set = set()
    violations = 0
    for _cycle in range(cycles):
        txn = jdb.write_txn()
        submit(txn, jobs0 if _cycle == 0 else burst)
        result = algo.schedule(txn, executors, now_ns)
        txn.commit()
        cycle_dec = [
            (
                ps.pool,
                sorted(ps.outcome.scheduled.items()),
                sorted(ps.outcome.preempted),
            )
            for ps in result.pools
        ]
        decisions.append(cycle_dec)
        for job, _run in result.scheduled:
            if job.id in leased_ever:
                violations += 1  # double-lease: the drill's hard failure
            leased_ever.add(job.id)
    return decisions, sup, pool_serving_stats().snapshot(), violations


def _dlq_materialized(db) -> dict:
    """Materialized-state equality surface for the poison drill.

    dead_letters is EXCLUDED (the poisoned arm carries 'replayed' rows the
    clean arm never saw), consumer_positions too (the replay appends the
    raw record back to the log, so the poisoned cursor ends further), and
    serials/serial columns as everywhere (batching differs)."""
    from armada_tpu.ingest.schedulerdb import SNAPSHOT_TABLES

    snap = db.export_snapshot()
    out = {}
    for table, cols in SNAPSHOT_TABLES.items():
        if table in ("serials", "dead_letters", "consumer_positions"):
            continue
        rows = snap.get(table, [])
        if "serial" in cols:
            i = cols.index("serial")
            rows = [r[:i] + r[i + 1 :] for r in rows]
        out[table] = sorted(rows)
    return out


def _poison_world(log, rng) -> None:
    """Publish a deterministic churny mix across queues/jobsets."""
    from armada_tpu.eventlog.publisher import Publisher
    from armada_tpu.events import events_pb2 as pb

    pub = Publisher(log)
    jid = 0
    for i in range(40):
        events = []
        for _ in range(rng.randrange(1, 4)):
            events.append(
                pb.Event(
                    created_ns=i + 1,
                    submit_job=pb.SubmitJob(
                        job_id=f"pz-{jid:05d}", spec=pb.JobSpec()
                    ),
                )
            )
            jid += 1
        pub.publish(
            [
                pb.EventSequence(
                    queue=f"pq{rng.randrange(3)}",
                    jobset=f"pjs{rng.randrange(4)}",
                    events=events,
                )
            ]
        )


def _poison_arm(d, log, rng, sharded: bool) -> dict:
    """One arm of the poison drill: clean drain -> poisoned drain (fault
    armed, bounded retries escalate to bisection) -> operator replay ->
    suffix drain -> bit-equality against the never-poisoned state."""
    from armada_tpu.core import faults
    from armada_tpu.ingest import dlq
    from armada_tpu.ingest.converter import convert_sequences
    from armada_tpu.ingest.pipeline import IngestionPipeline
    from armada_tpu.ingest.schedulerdb import SchedulerDb
    from armada_tpu.ingest.shards import PartitionedIngestionPipeline
    from armada_tpu.ingest.storeunion import ShardedSchedulerDb

    tag = "sharded" if sharded else "serial"
    parts = log.num_partitions

    def caught_up(store, timeout_s: float = 60.0) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            pos = store.positions("scheduler")
            if all(pos.get(p, 0) >= log.end_offset(p) for p in range(parts)):
                return True
            time.sleep(0.02)
        return False

    # Clean arm FIRST (the fault env is still disarmed): the never-poisoned
    # ground truth over the original log contents.
    clean = SchedulerDb(os.path.join(d, f"clean-{tag}.sqlite"))
    IngestionPipeline(
        log, clean, convert_sequences, "scheduler"
    ).run_until_caught_up()
    want = _dlq_materialized(clean)

    # Store-shard width rides the env (--store-shards); only the sharded
    # ingest arm can drive the union store (serial store() raises on it by
    # design), and the width must divide the ingest width.
    store_w = 1
    if sharded:
        try:
            store_w = max(1, int(os.environ.get("ARMADA_STORE_SHARDS", "1")))
        except ValueError:
            store_w = 1
        store_w = max(w for w in (1, 2, 4) if w <= min(store_w, parts))
    if store_w > 1:
        poisoned = ShardedSchedulerDb(
            os.path.join(d, f"poisoned-{tag}"),
            num_shards=store_w,
            num_partitions=parts,
        )
    else:
        poisoned = SchedulerDb(os.path.join(d, f"poisoned-{tag}.sqlite"))

    dlq.reset_poison()
    faults.reset_counters()
    os.environ["ARMADA_FAULT"] = "convert_record:raise"
    try:
        if sharded:
            pipe = PartitionedIngestionPipeline(
                log,
                poisoned,
                convert_sequences,
                "scheduler",
                num_shards=parts,
                convert_mode="inline",
                poll_interval=0.02,
            )
        else:
            pipe = IngestionPipeline(
                log, poisoned, convert_sequences, "scheduler",
                poll_interval=0.02,
            )
        pipe.start()
        # Wedge-proof half: with the poison latched, bounded retries must
        # escalate to bisection and the shard drains PAST the poison
        # offset to the log end.
        drained = caught_up(poisoned)
        dead = poisoned.list_dead_letters(consumer="scheduler", status="dead")

        # Operator fix: disarm the fault, clear the latch, replay the
        # quarantined raw bytes back through the log.
        os.environ.pop("ARMADA_FAULT", None)
        dlq.reset_poison()
        replay = dlq.DlqAdmin(log, {"scheduler": poisoned}).replay("scheduler")
        redrained = caught_up(poisoned)
        pipe.stop()
    finally:
        os.environ.pop("ARMADA_FAULT", None)
        dlq.reset_poison()

    got = _dlq_materialized(poisoned)
    equal = got == want
    return {
        "ok": bool(
            drained
            and redrained
            and len(dead) >= 1
            and replay.get("replayed", 0) >= 1
            and equal
        ),
        "arm": tag,
        "store_shards": store_w,
        "drained_past_poison": drained,
        "dead_letters": len(dead),
        "replayed": replay.get("replayed", 0),
        "state_equal_after_replay": equal,
    }


def run_poison_drill(seed: int) -> dict:
    """The --poison leg: 3 seeds x (serial + sharded ingest), under tsan.

    Asserts per seed/arm: the pipeline never wedges on a poison record
    (bounded retries -> bisection -> per-record quarantine, cursor past the
    poison), >=1 dead letter lands, `dlq replay` + a suffix drain restores
    bit-equality with a never-poisoned drain of the same log."""
    import tempfile

    from armada_tpu.analysis import tsan
    from armada_tpu.eventlog.log import EventLog
    from armada_tpu.ingest import dlq

    save = {
        k: os.environ.get(k)
        for k in ("ARMADA_FAULT", "ARMADA_INGEST_RETRIES")
    }
    os.environ["ARMADA_INGEST_RETRIES"] = "2"
    tsan.enable()
    tsan.reset()
    dlq.reset_registry()
    arms = []
    try:
        for s in (seed, seed + 1, seed + 2):
            with tempfile.TemporaryDirectory(prefix="chaos-poison-") as d:
                log = EventLog(os.path.join(d, "log"), num_partitions=4)
                _poison_world(log, random.Random(s))
                for sharded in (False, True):
                    rep = _poison_arm(d, log, random.Random(s), sharded)
                    rep["seed"] = s
                    arms.append(rep)
                log.close()
    finally:
        for k, v in save.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        dlq.reset_poison()
    violations = tsan.take_violations()
    tsan.disable()
    reg = dlq.registry().snapshot()
    return {
        "ok": bool(arms)
        and all(a["ok"] for a in arms)
        and not violations,
        "seeds": 3,
        "dead_letters_total": reg["dead_letters_total"],
        "batch_retries": sum((reg.get("batch_retries") or {}).values()),
        "tsan_violations": len(violations),
        "arms": arms,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cycles", type=int, default=8)
    ap.add_argument("--seed", type=int, default=int(time.time()) % 10_000)
    ap.add_argument("--jobs", type=int, default=40, help="initial backlog")
    ap.add_argument("--burst", type=int, default=8, help="submits per cycle")
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--queues", type=int, default=3)
    ap.add_argument(
        "--prefetch",
        action="store_true",
        help="exercise the pipeline's content prefetch around the loss",
    )
    ap.add_argument(
        "--corrupt",
        action="store_true",
        help="the silent-corruption drill (ISSUE 13): inject a random "
        "round_corrupt fault (header scalar / placement lane / fetched "
        "bytes) mid-drill with round verification armed -- verification "
        "must catch it before decode, the failover re-run must be "
        "bit-equal to the clean replay, the 1-strike quarantine must "
        "BLOCK re-promotion until cleared, and the post-clear probe must "
        "promote (docs/operations.md silent-corruption runbook)",
    )
    ap.add_argument(
        "--soak",
        action="store_true",
        help="additionally run a short soak window with the same fault "
        "armed mid-window (failover measured UNDER LOAD as a latency "
        "distribution; armada_tpu/loadgen/soak.py; ARMADA_SOAK_WINDOW_S "
        "downscales)",
    )
    ap.add_argument(
        "--crash",
        action="store_true",
        help="additionally run the kill/restart drill under load: mid-soak "
        "checkpoint -> wipe the materialized store -> rebuild from snapshot "
        "+ log-suffix replay; asserts zero dropped/double-leased jobs, zero "
        "tsan violations, and reports RTO (restart_recovery_s)",
    )
    ap.add_argument(
        "--pools",
        type=int,
        default=0,
        dest="pools",
        help="additionally run the pool-parallel drill leg (round 17): an "
        "N-tenant world through FairSchedulingAlgo with "
        "ARMADA_POOL_PARALLEL armed and a device fault injected into one "
        "pool's round mid-window -- the faulted pool walks the failover "
        "ladder alone, decisions must be bit-equal to a SERIAL clean "
        "replay, zero dropped/double-leased jobs, zero tsan violations "
        "(docs/operations.md pool-parallel runbook)",
    )
    ap.add_argument(
        "--mesh",
        type=int,
        default=0,
        help="arm the mesh serving plane over N (virtual) devices: the "
        "chip-loss drill -- the faulted cycle must degrade to a SMALLER "
        "mesh (never CPU: supervisor fallbacks stay 0), re-shard, restore "
        "to the full mesh, and every cycle's decisions must stay bit-equal "
        "to the clean replay (docs/multichip.md runbook)",
    )
    ap.add_argument(
        "--ingest-shards",
        type=int,
        default=None,
        dest="ingest_shards",
        help="arm the partition-parallel ingest plane (ARMADA_INGEST_SHARDS, "
        "ingest/shards.py) for EVERY leg -- faulted run, clean replay, and "
        "the soak/crash legs (their env save/restore keeps it armed) -- so "
        "convergence is exercised against the sharded ingesters, not a "
        "silent serial pipeline (default: inherit the environment)",
    )
    ap.add_argument(
        "--store-shards",
        type=int,
        default=None,
        dest="store_shards",
        help="arm the sharded materialized store (ARMADA_STORE_SHARDS, "
        "ingest/storeunion.py) for EVERY leg -- per-shard SQLite files "
        "behind the union reader; the ingest width rounds up to a "
        "multiple (default: inherit the environment)",
    )
    ap.add_argument(
        "--poison",
        action="store_true",
        help="additionally run the poison-record drill (ISSUE 19): arm "
        "ARMADA_FAULT=convert_record with bounded retries "
        "(ARMADA_INGEST_RETRIES=2) over 3 seeded synthetic logs, serial "
        "AND sharded ingest arms, under tsan -- the pipeline must drain "
        "PAST the poison (bisection quarantines exactly the bad record, "
        "cursor advances, no wedge), and `dlq replay` + a suffix drain "
        "must restore bit-equality with a never-poisoned drain "
        "(docs/operations.md dead-letter runbook)",
    )
    ap.add_argument(
        "--node-types",
        default=None,
        dest="node_types",
        metavar="T1,T2,...",
        help="run the soak/crash legs on a heterogeneous fleet: "
        "comma-separated node types round-robined across the fake nodes, "
        "with type-sensitive submits in the mix (ARMADA_SOAK_NODE_TYPES; "
        "default: inherit the environment)",
    )
    args = ap.parse_args()

    if args.ingest_shards is not None:
        os.environ["ARMADA_INGEST_SHARDS"] = str(args.ingest_shards)
    if args.store_shards is not None:
        # Width is permanent per store dir; setting it here means every
        # leg's fresh temp world builds at the armed width.
        os.environ["ARMADA_STORE_SHARDS"] = str(args.store_shards)
    if args.node_types is not None:
        # The soak/crash legs read SoakConfig.from_env; their env
        # save/restore keeps the heterogeneous fleet armed across restarts.
        os.environ["ARMADA_SOAK_NODE_TYPES"] = args.node_types

    if args.mesh:
        # The drill must run anywhere: give the CPU platform enough virtual
        # devices to host the mesh (only effective before the first jax
        # import; harmless when a real accelerator backend is the default).
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={args.mesh}"
            ).strip()

    rng = random.Random(args.seed)
    if args.corrupt and args.mesh:
        print("--corrupt and --mesh are separate drills; pick one", file=sys.stderr)
        return 2
    if args.corrupt:
        fault = rng.choice(list(CORRUPT_MODES))
        # both legs arm verification (the clean replay certifies green)
        os.environ["ARMADA_CHAOS_VERIFY"] = "1"
    else:
        fault = rng.choice(["error", "hang"])
    fault_cycle = rng.randrange(1, max(2, args.cycles - 1))
    common = dict(
        # hang drills ride a tight deadline so the drill stays fast; it
        # still dwarfs any legit CPU round at this world size.  Mesh mode
        # keeps the full deadline: the degrade rerun compiles a fresh
        # sharded kernel, which a 3s deadline would misread as a second
        # loss and walk the whole ladder down to CPU.
        deadline_s=3.0 if fault == "hang" and not args.mesh else 30.0,
        cycles=args.cycles,
        seed=args.seed,
        jobs0=args.jobs,
        burst=args.burst,
        num_nodes=args.nodes,
        num_queues=args.queues,
        prefetch=args.prefetch,
        mesh=args.mesh,
    )
    t0 = time.monotonic()
    chaotic, sup, ms = run_script(fault=fault, fault_cycle=fault_cycle, **common)
    chaos_s = time.monotonic() - t0
    snap = sup.snapshot()
    mesh_snap = ms.snapshot()
    if args.mesh:
        # convergence half 1 (mesh mode): the faulted cycle stepped DOWN the
        # mesh ladder (never to CPU) and the stubbed-healthy probe restores
        # the full mesh.
        deadline = time.monotonic() + 10.0
        while (
            ms.snapshot()["devices"] < args.mesh
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        promoted = ms.snapshot()["devices"] == args.mesh
        mesh_ok = (
            mesh_snap["degrades"] >= 1
            and snap["fallbacks"] == 0
            and not sup.degraded
        )
    elif args.corrupt:
        # convergence half 1 (corruption drill): verification caught the
        # silently-wrong round (fallbacks >= 1 via the ladder), the
        # 1-strike quarantine must HOLD the stubbed-healthy re-probe down,
        # and only the operator clear releases promotion.
        from armada_tpu.core.watchdog import promotion_blocked
        from armada_tpu.models.verify import verify_state
        from armada_tpu.scheduler.quarantine import device_quarantine

        verify_snap = verify_state().snapshot()
        time.sleep(0.5)  # ~10 stub-probe cycles: promotion must NOT happen
        held = sup.degraded and promotion_blocked() is not None
        quarantined = sorted(device_quarantine().quarantined())
        device_quarantine().clear()
        deadline = time.monotonic() + 10.0
        while sup.degraded and time.monotonic() < deadline:
            time.sleep(0.05)
        promoted = not sup.degraded
        mesh_ok = (
            verify_snap["failures"] >= 1 and held and bool(quarantined)
        )
    else:
        # convergence half 1: the supervisor recovered (stubbed-healthy probe)
        deadline = time.monotonic() + 10.0
        while sup.degraded and time.monotonic() < deadline:
            time.sleep(0.05)
        promoted = not sup.degraded
        mesh_ok = True

    clean, _, _ = run_script(fault=None, fault_cycle=0, **common)

    # Harvest AFTER both legs: the harness stayed armed, so a zombie worker
    # unwedging during the promoted-wait or the clean replay still lands in
    # the gate (tsan is record-only -- it cannot perturb the clean leg).
    from armada_tpu.analysis import tsan

    tsan_found = tsan.take_violations()
    tsan.disable()

    soak_report = None
    if args.soak:
        # The soak leg runs AFTER tsan harvest state is captured for the
        # replay legs: run_soak re-arms/reset the harness itself for its
        # own fault window and reports its own tsan_violations.
        import tempfile

        from armada_tpu.loadgen.soak import SoakConfig, run_soak

        # A corrupt-mode string is not a device_round MODE: the soak leg
        # always drills a real device fault (the corruption drill itself
        # is the replay legs' job above).
        soak_fault = "error" if args.corrupt else fault
        cfg = SoakConfig.from_env(
            window_s=float(os.environ.get("ARMADA_SOAK_WINDOW_S", 30.0)),
            target_eps=float(os.environ.get("ARMADA_SOAK_RATE", 100.0)),
            seed=args.seed,
            fault=f"device_round:{soak_fault}",
            watchdog_s=8.0,
        )
        with tempfile.TemporaryDirectory(prefix="chaos-soak-") as d:
            soak_report = run_soak(cfg, d)

    crash_report = None
    if args.crash:
        import tempfile

        from armada_tpu.loadgen.soak import SoakConfig, run_soak

        ccfg = SoakConfig.from_env(
            window_s=float(os.environ.get("ARMADA_SOAK_WINDOW_S", 20.0)),
            target_eps=float(os.environ.get("ARMADA_SOAK_RATE", 100.0)),
            seed=args.seed,
            crash_at_frac=0.5,
        )
        with tempfile.TemporaryDirectory(prefix="chaos-crash-") as d:
            crash_report = run_soak(ccfg, d)

    pool_report = None
    if args.pools:
        pfc = rng.randrange(1, max(2, args.cycles * args.pools - 1))
        pool_common = dict(
            cycles=args.cycles,
            seed=args.seed,
            pools=args.pools,
            jobs0=args.jobs,
            burst=args.burst,
        )
        chaotic_p, psup, pstats, pviol = run_pool_script(
            fault="error", fault_cycle=pfc, parallel=True, **pool_common
        )
        deadline = time.monotonic() + 10.0
        while psup.degraded and time.monotonic() < deadline:
            time.sleep(0.05)
        p_promoted = not psup.degraded
        clean_p, _, _, cviol = run_pool_script(
            fault=None, fault_cycle=0, parallel=False, **pool_common
        )
        pool_tsan = tsan.take_violations()
        tsan.disable()
        pool_report = {
            "ok": (
                chaotic_p == clean_p
                and psup.snapshot()["fallbacks"] >= 1
                and p_promoted
                and pviol == 0
                and cviol == 0
                and not pool_tsan
                and pstats["parallel_cycles"] >= 1
            ),
            "pools": args.pools,
            "decisions_equal_serial": chaotic_p == clean_p,
            "fallbacks": psup.snapshot()["fallbacks"],
            "promoted": p_promoted,
            "double_leased": pviol + cviol,
            "parallel_cycles": pstats["parallel_cycles"],
            "stacked_launches": pstats["stacked_launches"],
            "tsan_violations": len(pool_tsan),
        }

    poison_report = None
    if args.poison:
        poison_report = run_poison_drill(args.seed)

    ok = (
        chaotic == clean
        and (snap["fallbacks"] >= 1 if not args.mesh else mesh_ok)
        and (not args.corrupt or mesh_ok)
        and promoted
        and not tsan_found
        and (soak_report is None or soak_report["ok"])
        and (crash_report is None or crash_report["ok"])
        and (pool_report is None or pool_report["ok"])
        and (poison_report is None or poison_report["ok"])
    )
    fault_site = "round_corrupt" if args.corrupt else "device_round"
    line = {
        "tool": "chaos_cycle",
        "ok": ok,
        "seed": args.seed,
        "cycles": args.cycles,
        "fault": f"{fault_site}:{fault}@cycle{fault_cycle}",
        "prefetch": bool(args.prefetch),
        "fallbacks": snap["fallbacks"],
        "promoted": promoted,
        "decisions_equal": chaotic == clean,
        "scheduled_total": sum(len(s) for s, _ in clean),
        "chaos_run_s": round(chaos_s, 2),
        "tsan_violations": len(tsan_found),
    }
    from armada_tpu.ingest import resolve_num_shards

    # the ingest-shard width every leg ran with (--ingest-shards / env)
    line["ingest_shards"] = resolve_num_shards()
    # the store-shard width (0/absent env = the single shared writer)
    try:
        line["store_shards"] = max(
            1, int(os.environ.get("ARMADA_STORE_SHARDS", "1"))
        )
    except ValueError:
        line["store_shards"] = 1
    if args.mesh:
        line["mesh"] = {
            "requested": args.mesh,
            "degrades": mesh_snap["degrades"],
            "restored": promoted,
            "cpu_fallbacks": snap["fallbacks"],
        }
    if args.corrupt:
        line["corrupt"] = {
            "caught": verify_snap["failures"] >= 1,
            "sites": sorted(verify_snap["failures_by_site"]),
            "quarantined": quarantined,
            "promotion_held": held,
            "promoted_after_clear": promoted,
        }
    if tsan_found:
        line["tsan_detail"] = tsan_found[:5]
    if soak_report is not None:
        line["soak"] = {
            k: soak_report[k]
            for k in (
                "ok",
                "window_s",
                "achieved_eps",
                "violations",
                "degraded_cycles",
                "cycle_p50_s",
                "cycle_p99_s",
            )
            if k in soak_report
        }
        line["soak"]["degraded_p99_s"] = soak_report.get("slo_degraded", {}).get(
            "p99_s"
        )
    if crash_report is not None:
        line["crash"] = {
            "ok": crash_report["ok"],
            "violations": crash_report["violations"],
            "tsan_violations": crash_report.get("tsan_violations", 0),
            **(crash_report.get("crash") or {}),
        }
    if pool_report is not None:
        line["pools"] = pool_report
    if poison_report is not None:
        line["poison"] = poison_report
    if not ok and chaotic != clean:
        for i, (a, b) in enumerate(zip(chaotic, clean)):
            if a != b:
                line["first_divergent_cycle"] = i
                break
    print(json.dumps(line))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
