"""`armadactl serve`: the whole control plane in one process.

Equivalent of the reference's `mage localdev minimal` development topology
(server + scheduler + ingesters + Pulsar + Postgres + Redis in docker,
docs/developer_guide.md:88-105) collapsed onto the native event log + SQLite:
event log, scheduler DB ingester, event-stream ingester, the scheduler loop,
and the gRPC services, all under one roof.  State lives in --data-dir and
survives restarts (event-sourced recovery).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from typing import Optional

from armada_tpu.core.config import SchedulingConfig
from armada_tpu.eventlog import EventLog
from armada_tpu.eventlog.publisher import Publisher
from armada_tpu.ingest.converter import convert_sequences
from armada_tpu.ingest.pipeline import IngestionPipeline
from armada_tpu.ingest.schedulerdb import SchedulerDb
from armada_tpu.jobdb.jobdb import JobDb
from armada_tpu.lookout import LookoutDb, LookoutQueries, lookout_converter
from armada_tpu.scheduler import (
    FairSchedulingAlgo,
    FileLeaseLeaderController,
    Scheduler,
    StandaloneLeaderController,
)
from armada_tpu.scheduler.api import ExecutorApi
from armada_tpu.server import (
    EventApi,
    EventDb,
    QueueRepository,
    SubmitServer,
    event_sink_converter,
)


@dataclasses.dataclass
class ControlPlaneProcess:
    """A running control plane; stop() shuts everything down cleanly."""

    port: int
    scheduler: Scheduler
    submit_server: SubmitServer
    event_api: EventApi
    _grpc_server: object
    _pipelines: list
    _stop: threading.Event
    _scheduler_thread: threading.Thread
    _log: EventLog
    _db: SchedulerDb
    _eventdb: EventDb
    _lookoutdb: LookoutDb
    _metrics_server: object = None
    health_server: object = None
    lookout_web: object = None
    rest_gateway: object = None
    algo_port: Optional[int] = None
    # The ScheduleSidecar behind algo_port (None when the port is off).
    sidecar: object = None
    _algo_server: object = None
    replicator: object = None
    checkpoint_manager: object = None
    restore_info: object = None
    # This plane's watchdog arming token; disarmed on stop() (see
    # start_control_plane).
    _watchdog_token: object = None
    # This plane's explain-default arming token (models/explain.py
    # arm_default); disarmed on stop() so in-process embedders/tests after
    # the plane keep the library default (0 = off), and overlapping plane
    # lifetimes never corrupt each other's cadence.
    _explain_token: Optional[int] = None
    # This plane's round-verification arming token (models/verify.py
    # arm_default); disarmed on stop() like the explain token above.
    _verify_token: Optional[int] = None
    # This plane's arming of the cycle recorder's garbage-collection hook
    # (ops/trace.py arm_gc): collections inside a cycle become gc_collect
    # spans while a plane runs; disarmed on stop() like the tokens above.
    _gc_token: Optional[int] = None
    _stopped: bool = False

    def stop(self, grace_s: float = 1.0) -> None:
        """grace_s: gRPC drain window -- in-flight RPCs (an executor's lease
        call, a sidecar round) get this long to complete before the sockets
        close; new RPCs are rejected immediately either way.  SIGTERM
        shutdown (armadactl serve) passes a longer drain than tests do.
        Idempotent: a Ctrl-C landing mid-drain re-enters harmlessly."""
        if self._stopped:
            return
        self._stopped = True
        self._stop.set()
        self._scheduler_thread.join(timeout=10)
        if self._watchdog_token is not None:
            from armada_tpu.core.watchdog import supervisor as _supervisor

            _supervisor().disarm(self._watchdog_token)
        if self._explain_token is not None:
            from armada_tpu.models import explain as _explain

            _explain.disarm_default(self._explain_token)
        if self._verify_token is not None:
            from armada_tpu.models import verify as _verify

            _verify.disarm_default(self._verify_token)
        if self._gc_token is not None:
            from armada_tpu.ops.trace import disarm_gc

            disarm_gc(self._gc_token)
        if self.replicator is not None:
            self.replicator.stop()
        for p in self._pipelines:
            p.stop()
        self._grpc_server.stop(grace_s).wait()
        if self._algo_server is not None:
            self._algo_server.stop(grace_s).wait()
        if self.health_server is not None:
            self.health_server.stop()
        if self.lookout_web is not None:
            self.lookout_web.stop()
        if self.rest_gateway is not None:
            self.rest_gateway.stop()
        if self._metrics_server is not None:
            # prometheus_client >= 0.17 returns (server, thread)
            try:
                server, thread = self._metrics_server
                server.shutdown()
                thread.join(timeout=5)
            except (TypeError, ValueError):
                pass
        self._db.close()
        self._eventdb.close()
        self._lookoutdb.close()
        self._log.close()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Join the scheduler loop (forever when timeout is None); returns
        True once it has exited."""
        self._scheduler_thread.join(timeout)
        return not self._scheduler_thread.is_alive()


def start_control_plane(
    data_dir: str,
    port: int = 0,
    config: Optional[SchedulingConfig] = None,
    cycle_interval_s: float = 1.0,
    schedule_interval_s: float = 5.0,
    leader_id: Optional[str] = None,
    num_partitions: Optional[int] = None,
    metrics_port: Optional[int] = None,
    health_port: Optional[int] = None,
    profiling: bool = False,
    lookout_port: Optional[int] = None,
    binoculars_url: Optional[str] = None,
    rest_port: Optional[int] = None,
    kube_lease_url: Optional[str] = None,
    kube_lease_namespace: str = "default",
    bind_host: str = "127.0.0.1",
    authenticator=None,
    lookout_oidc=None,
    lookout_trust_proxy: bool = False,
    advertised_address: Optional[str] = None,
    proxy_bearer_token: Optional[str] = None,
    algo_port: Optional[int] = None,
    replicate_log: bool = False,
    database_url: Optional[str] = None,
    lookout_database_url: Optional[str] = None,
    watchdog_s: Optional[float] = None,
    checkpoint_interval_s: Optional[float] = None,
    mesh_devices: Optional[int] = None,
    explain_interval: Optional[int] = None,
    verify_rounds: Optional[bool] = None,
    ingest_shards: Optional[int] = None,
    store_shards: Optional[int] = None,
) -> ControlPlaneProcess:
    """health_port: serve /health liveness (+ /debug/pprof/* when
    `profiling`) on this port, 0 = pick a free one (common/health,
    common/profiling/http.go).  lookout_port: host the lookout web UI
    (internal/lookoutui equivalent) on this port; binoculars_url: a
    cluster's binoculars gRPC address -- wires the UI's live log viewer
    (lookoutui job log view via binoculars logs.go).  authenticator: the
    server/authn.py chain gating the gRPC services and REST gateway; None =
    dev chain (trusted headers + anonymous)."""
    if replicate_log and (database_url or lookout_database_url):
        # Each replica ingests its own copy of the log into its own view;
        # two replicas sharing one external database would fight over the
        # same exactly-once consumer cursor (consumer_positions) and each
        # silently skip the batches the other acked.  Refuse rather than
        # corrupt -- replicated mode uses per-replica embedded views (or
        # point each replica at its OWN database via separate configs).
        raise ValueError(
            "--database-url cannot be combined with --replicate-log: "
            "replicas would share one consumer cursor and silently miss "
            "event batches; give each replica its own database (or use "
            "the embedded per-replica default)"
        )
    os.makedirs(data_dir, exist_ok=True)
    config = config or SchedulingConfig()
    factory = config.resource_list_factory()

    # Device-loss watchdog (core/watchdog): the PRODUCTION paths arm a
    # round deadline by default -- an unarmed serve whose device hangs
    # wedges mid-round while holding leadership (a zombie leader).  A
    # timed-out or erroring device round fails over to the CPU backend from
    # host tables; a background in-process re-probe re-promotes.
    # watchdog_s: None = env ARMADA_WATCHDOG_S or 120s; 0 disables.
    from armada_tpu.core.watchdog import supervisor

    if watchdog_s is None:
        try:
            watchdog_s = float(os.environ.get("ARMADA_WATCHDOG_S", 120.0))
        except ValueError:
            watchdog_s = 120.0

    # Mesh serving plane (serve --mesh N / ARMADA_MESH; parallel/serving.py):
    # armed BEFORE the feed builds its device caches, so every slab is
    # node-axis-sharded from the first upload and the builders align their
    # pad buckets to the mesh multiple.  Chip loss degrades to a smaller
    # mesh (one slab re-shard) before the watchdog's CPU failover rung.
    from armada_tpu.parallel.serving import mesh_serving

    if mesh_devices is None:
        try:
            mesh_devices = int(os.environ.get("ARMADA_MESH", "0"))
        except ValueError:
            mesh_devices = 0
    mesh_serving().configure(mesh_devices)
    # A mesh that cannot come up at the requested size fails the START, not
    # a round: serving_mesh() raises when fewer devices are visible.
    mesh_serving().serving_mesh()

    # Persist XLA compilations so a restarted replica does not re-pay the
    # round's compile: where JAX_COMPILATION_CACHE_DIR says, else
    # <checkout>/.jax_cache (core/platform.py) -- never under data_dir,
    # which moves between runs and would never hit.
    from armada_tpu.core.platform import enable_compilation_cache

    enable_compilation_cache()

    # Log width (serve --log-partitions / ARMADA_LOG_PARTITIONS): a PERMANENT
    # property of a log directory -- EventLog persists it in META on first
    # create, adopts it when unspecified, and refuses a mismatch (the
    # jobset->partition routing would silently change otherwise).
    if num_partitions is None:
        try:
            num_partitions = (
                int(os.environ["ARMADA_LOG_PARTITIONS"])
                if "ARMADA_LOG_PARTITIONS" in os.environ
                else None
            )
        except ValueError:
            num_partitions = None
    log = EventLog(os.path.join(data_dir, "eventlog"), num_partitions=num_partitions)
    num_partitions = log.num_partitions
    # Sharded materialized stores (serve --store-shards /
    # ARMADA_STORE_SHARDS; ingest/storeunion.py): W store legs -- one
    # SQLite file (or PG schema) per store shard, each owning a disjoint
    # partition set -- behind one union read surface.  Width is PERMANENT
    # per store directory (STORE_META adoption); 0/1 keeps the plain
    # single-writer stores.  The event store (events.db) is partition-keyed
    # already and stays single-file.
    if store_shards is None:
        try:
            store_shards = int(os.environ.get("ARMADA_STORE_SHARDS", "0"))
        except ValueError:
            store_shards = 0
    store_shards = max(0, store_shards)
    if store_shards > num_partitions:
        # Refuse BEFORE creating shard files -- width is permanent per
        # store directory, and partitions route p % W, so W > P would
        # leave shards that can never own a partition.
        raise ValueError(
            f"--store-shards {store_shards} exceeds the log's "
            f"{num_partitions} partitions"
        )
    # External DBs (postgres:// via the pure-python wire driver,
    # ingest/pgwire.py) or the embedded per-replica SQLite defaults.
    if store_shards > 1:
        from armada_tpu.ingest.storeunion import (
            ShardedLookoutDb,
            ShardedSchedulerDb,
        )

        db = ShardedSchedulerDb(
            database_url or os.path.join(data_dir, "store-shards"),
            num_shards=store_shards,
            num_partitions=num_partitions,
        )
        lookoutdb = ShardedLookoutDb(
            lookout_database_url
            or os.path.join(data_dir, "lookout-shards"),
            num_shards=store_shards,
            num_partitions=num_partitions,
        )
    else:
        db = SchedulerDb(database_url or os.path.join(data_dir, "scheduler.db"))
        lookoutdb = LookoutDb(
            lookout_database_url or os.path.join(data_dir, "lookout.db")
        )
    eventdb = EventDb(os.path.join(data_dir, "events.db"))
    # Bounded-replay restart (scheduler/checkpoint.py): load the newest
    # valid snapshot into the scheduler store BEFORE the ingestion pipelines
    # read their start positions, so they replay only the log suffix past
    # the snapshot fence.  Fast-forward only -- a store already at/past the
    # fence keeps its own (newer) state; corrupt snapshots fall back to the
    # previous one, then to full replay.
    from armada_tpu.scheduler.checkpoint import CheckpointManager, maybe_restore

    checkpointer = CheckpointManager(os.path.join(data_dir, "checkpoints"))
    restore_info = maybe_restore(db, checkpointer)
    if restore_info.get("restored"):
        logging.getLogger("armada.serve").info(
            "restored scheduler store from checkpoint %s",
            restore_info.get("path"),
        )
    if checkpoint_interval_s is None:
        try:
            checkpoint_interval_s = float(
                os.environ.get("ARMADA_CHECKPOINT_S", 0.0)
            )
        except ValueError:
            checkpoint_interval_s = 0.0
    publisher = Publisher(log)

    # Partition-parallel ingestion (serve --ingest-shards /
    # ARMADA_INGEST_SHARDS; ingest/shards.py): N shard workers per view,
    # each owning a disjoint partition set with its own consumer cursor
    # rows and store leg.  1 (the default) keeps the serial pipeline.
    from armada_tpu.ingest import PartitionedIngestionPipeline, resolve_num_shards

    ingest_shards_explicit = (
        ingest_shards is not None or "ARMADA_INGEST_SHARDS" in os.environ
    )
    ingest_shards = min(resolve_num_shards(ingest_shards), num_partitions)
    if store_shards > 1:
        # Store shard = partition % W, ingest shard = partition % N: an
        # ingest shard's partitions all land in ONE store file only when W
        # divides N (the batch must stay one transaction).  An unspecified
        # ingest width follows the store width.
        if not ingest_shards_explicit:
            ingest_shards = store_shards
        if ingest_shards % store_shards != 0:
            raise ValueError(
                f"--ingest-shards {ingest_shards} must be a multiple of "
                f"--store-shards {store_shards} (each ingest shard's "
                "partition set must live in one store shard)"
            )

    def _pipeline(sink, converter, consumer):
        if ingest_shards > 1:
            return PartitionedIngestionPipeline(
                log,
                sink,
                converter,
                consumer_name=consumer,
                num_shards=ingest_shards,
                start_positions=sink.positions(consumer),
            )
        return IngestionPipeline(
            log,
            sink,
            converter,
            consumer_name=consumer,
            start_positions=sink.positions(consumer),
        )

    scheduler_pipeline = _pipeline(db, convert_sequences, "scheduler")
    event_pipeline = _pipeline(eventdb, event_sink_converter, "events")
    lookout_pipeline = _pipeline(lookoutdb, lookout_converter, "lookout")
    # Publish wakeups: idle pipelines sleep until their partitions get data
    # instead of burning the fixed 0.05s poll.
    for _p in (scheduler_pipeline, event_pipeline, lookout_pipeline):
        publisher.add_wakeup(_p.notify)

    # Queue CRUD is event-sourced onto "$control-plane" so replicated
    # deployments converge on queue config by replay (cross-host HA).
    queues = QueueRepository(db, publisher=publisher)
    # Cross-host HA write gate: None = we may write (we hold the log of
    # record), else the leader's address -> UNAVAILABLE.  `leader` is
    # constructed below; the closure binds late.  The SAME gate sits on the
    # Publisher itself (the choke point every append path shares -- submit,
    # queue CRUD, ExecutorApi reports, ExecutorAdmin events); SubmitServer
    # additionally checks it first so followers answer UNAVAILABLE before
    # any local-state error.
    _write_gate = (lambda: leader.leader_address()) if replicate_log else None
    submit_server = SubmitServer(
        db, publisher, queues, config, write_gate=_write_gate
    )
    event_api = EventApi(eventdb)
    from armada_tpu.server.controlplane import ControlPlaneServer

    control_plane = ControlPlaneServer(publisher)
    jobdb = JobDb(config)
    if kube_lease_url and not leader_id:
        # Silent fallback to always-leader here would be split-brain with two
        # replicas: requesting kube election without a holder id is an error.
        raise ValueError("--kube-lease-url requires --leader-id (the holder identity)")
    if leader_id and kube_lease_url:
        # Replicated deployment on Kubernetes: coordination/v1 Lease election
        # (leader.go:112-186); falls back to the file lease off-cluster.
        from armada_tpu.scheduler.kube_leader import KubernetesLeaseLeaderController

        # In-cluster credentials: the standard service-account mount
        # (rest.InClusterConfig's sources); without them the apiserver answers
        # 401/TLS failure and no replica would ever lead.  The token FILE is
        # passed (not its contents): bound tokens rotate ~hourly and the
        # controller re-reads per request.
        sa = "/var/run/secrets/kubernetes.io/serviceaccount"
        sa_token_file = f"{sa}/token" if os.path.exists(f"{sa}/token") else None
        sa_ca = f"{sa}/ca.crt" if os.path.exists(f"{sa}/ca.crt") else None
        leader = KubernetesLeaseLeaderController(
            kube_lease_url,
            leader_id,
            namespace=kube_lease_namespace,
            token_file=sa_token_file,
            ca_file=sa_ca,
        )
    else:
        leader = (
            FileLeaseLeaderController(
                os.path.join(data_dir, "leader.lease"), leader_id
            )
            if leader_id
            else StandaloneLeaderController()
        )
    if replicate_log:
        publisher.write_gate = _write_gate
    if leader_id:
        # Epoch fence on the single append choke point: a deposed leader's
        # publish is rejected the moment the election record carries a
        # higher generation, independent of how stale its own leadership
        # view is.  The scheduler stamps the held epoch each leader cycle.
        gen_peek = getattr(leader, "current_generation", None)
        if gen_peek is not None:
            publisher.epoch_source = gen_peek
    from armada_tpu.scheduler.metrics import SchedulerMetrics
    from armada_tpu.scheduler.reports import (
        LeaderProxyingReports,
        SchedulingReportsRepository,
    )

    reports = SchedulingReportsRepository(
        max_job_reports=config.max_job_scheduling_contexts_per_executor
    )

    # Queries go through the proxying wrapper: followers forward to the
    # leader's advertised address from the election record
    # (leader_proxying_reports_server.go) instead of answering NOT_FOUND
    # from their empty local repository.  Recording stays on the plain
    # repository (only the leader runs cycles).
    def _reports_client(address: str):
        from armada_tpu.rpc.client import ArmadaClient

        # Follower-to-leader hop: the leader's chain sees this replica, not
        # the original caller.  Dev chains ride the trusted header; strict
        # deployments configure a service credential.
        return ArmadaClient(
            address,
            principal=leader_id or "scheduler-follower",
            bearer_token=proxy_bearer_token,
        )

    reports_query = LeaderProxyingReports(reports, leader, _reports_client)
    metrics = None
    metrics_server = None
    if metrics_port is not None:
        from prometheus_client import CollectorRegistry, start_http_server

        # Own registry: a restarted plane in the same process must not
        # collide with the previous instance's collectors on the global one.
        registry = CollectorRegistry()
        metrics_server = start_http_server(metrics_port, registry=registry)
        metrics = SchedulerMetrics(
            registry=registry,
            state_reset_interval_s=config.job_state_metrics_reset_interval_s,
        )
    feed = None
    if config.incremental_problem_build:
        from armada_tpu.scheduler.incremental_algo import IncrementalProblemFeed

        feed = IncrementalProblemFeed(config)
        feed.attach(jobdb)
    scheduler = Scheduler(
        db,
        jobdb,
        FairSchedulingAlgo(
            config,
            queues=queues.scheduling_queues,
            clock_ns=lambda: int(time.time() * 1e9),
            # reports are always on in serve; metrics when exposed
            collect_stats=True,
            feed=feed,
        ),
        publisher,
        leader,
        config,
        metrics=metrics,
        reports=reports,
    )
    scheduler.checkpointer = checkpointer
    scheduler.checkpoint_interval_s = checkpoint_interval_s or 0.0
    # armadactl checkpoint rides the ExecutorAdmin surface: trigger + status
    # resolve against THIS plane's scheduler (plane-local state, not
    # event-sourced -- a snapshot of a replica is that replica's affair).
    control_plane.checkpoint_trigger = scheduler.checkpoint
    control_plane.checkpoint_status = scheduler.durability_status
    # armadactl dlq rides the same plane-local surface: the dead-letter
    # tables live in THIS replica's materialized stores; replay re-publishes
    # through the shared log (idempotent re-application makes that safe).
    from armada_tpu.ingest.dlq import DlqAdmin

    dlq_admin = DlqAdmin(
        log, {"scheduler": db, "events": eventdb, "lookout": lookoutdb}
    )
    control_plane.dlq_admin = dlq_admin
    executor_api = ExecutorApi(db, publisher, factory)

    from armada_tpu.rpc.server import make_server

    grpc_server, bound_port = make_server(
        submit_server=submit_server,
        event_api=event_api,
        executor_api=executor_api,
        factory=factory,
        lookout_queries=LookoutQueries(lookoutdb),
        reports=reports_query,
        control_plane=control_plane,
        replication_log=log if replicate_log else None,
        address=f"{bind_host}:{port}",
        authenticator=authenticator,
    )

    # Now the port is bound: advertise this replica's address through the
    # election record so followers can proxy leader-local queries.
    if hasattr(leader, "set_advertised_address"):
        if advertised_address is None:
            import socket as _socket

            advertise_host = (
                bind_host
                if bind_host not in ("0.0.0.0", "::")
                else _socket.gethostname()
            )
            advertised_address = f"{advertise_host}:{bound_port}"
        leader.set_advertised_address(advertised_address)
        reports_query.set_self_address(advertised_address)

    replicator = None
    if replicate_log:
        from armada_tpu.eventlog.replicator import LogReplicator
        from armada_tpu.rpc.client import ReplicationClient

        def _replication_client(addr: str):
            # same credential the reports proxy uses for follower->leader
            # hops (tokens come from config, never argv)
            return ReplicationClient(
                addr,
                principal=f"replica:{leader_id or 'standalone'}",
                bearer_token=proxy_bearer_token,
            )

        def _min_acked() -> dict:
            # The LOWEST committed consumer position per partition across
            # every local materialized view: the safety bound for
            # divergence truncation (a suffix no view has read can be
            # dropped without orphaning state).
            out = {p: None for p in range(num_partitions)}
            for positions in (
                db.positions("scheduler"),
                eventdb.positions("events"),
                lookoutdb.positions("lookout"),
            ):
                for p in range(num_partitions):
                    pos = positions.get(p, 0)
                    out[p] = pos if out[p] is None else min(out[p], pos)
            return {p: (v or 0) for p, v in out.items()}

        replicator = LogReplicator(
            log,
            leader_address=leader.leader_address,
            client_factory=_replication_client,
            min_acked=_min_acked,
        )
        replicator.start()
        scheduler.replication_status = replicator.status

    scheduler_pipeline.start()
    event_pipeline.start()
    lookout_pipeline.start()

    # Recovery fencing happens inside the scheduler's first leader cycle
    # (ensure_db_up_to_date on leadership acquisition); the background
    # ingesters above make the marker wait progress.

    stop = threading.Event()
    scheduler_thread = threading.Thread(
        target=scheduler.run,
        args=(stop,),
        kwargs={
            "cycle_interval_s": cycle_interval_s,
            "schedule_interval_s": schedule_interval_s,
        },
        daemon=True,
    )
    scheduler_thread.start()

    if profiling and health_port is None:
        # --profiling alone must not be a silent no-op: the profiling
        # endpoints live on the health server.
        health_port = 0
    health_server = None
    if health_port is not None:
        from armada_tpu.core.health import (
            FunctionChecker,
            HealthServer,
            StartupCompleteChecker,
        )

        health_server = HealthServer(health_port, profiling=profiling, host=bind_host)
        # /healthz embeds the device-degradation block (backend,
        # consecutive failures, last fallback reason) next to liveness,
        # plus the streaming SLO percentiles (cycle latency, TTFL,
        # ingest->visible lag -- scheduler/slo.py).
        health_server.device_status = supervisor().snapshot
        if mesh_serving().enabled():
            health_server.mesh_status = mesh_serving().snapshot
        from armada_tpu.scheduler.slo import recorder as _slo_recorder

        health_server.slo_status = _slo_recorder().snapshot
        health_server.durability_status = scheduler.durability_status
        from armada_tpu.ops.trace import recorder as _trace_recorder

        health_server.trace_status = _trace_recorder().healthz_block
        # Last explain-pass attribution per pool (models/explain.py via the
        # reports repository): reason counts + fragmentation forensics.
        health_server.explain_status = reports.explain_summary
        # Round-verification block (models/verify.py): last verdict,
        # per-site failure census, device quarantine scoreboard.
        from armada_tpu.models.verify import healthz_block as _verify_block

        health_server.verify_status = _verify_block
        # Pool-parallel serving scoreboard (scheduler/pool_serving.py):
        # parallel vs serial-fallback cycles, stacked launches, per-pool
        # round seconds -- wired unconditionally (the block reports
        # enabled=false under the serial default, which is itself signal).
        from armada_tpu.scheduler.pool_serving import pool_serving_stats

        health_server.pools_status = lambda: pool_serving_stats().snapshot()
        # Ingest-plane block (ingest/stats.py): per-consumer events/s +
        # per-partition lag, shard counts, abandoned-thread census.
        from armada_tpu.ingest.stats import registry as _ingest_stats

        health_server.ingest_status = lambda: {
            "shards_configured": ingest_shards,
            "store_shards": store_shards if store_shards > 1 else 1,
            "log_partitions": num_partitions,
            "consumers": _ingest_stats().snapshot(),
        }
        # Dead-letter block (ingest/dlq.py): quarantine census, batch
        # retries, pending control-plane halts, per-store row counts.
        health_server.dlq_status = dlq_admin.status
        startup = StartupCompleteChecker()
        health_server.checker.add(startup)
        health_server.checker.add(
            FunctionChecker(
                lambda: None if scheduler_thread.is_alive() else "scheduler loop dead",
                "scheduler",
            )
        )
        for p, pname in (
            (scheduler_pipeline, "scheduler-ingester"),
            (event_pipeline, "event-ingester"),
            (lookout_pipeline, "lookout-ingester"),
        ):
            health_server.checker.add(
                FunctionChecker(
                    lambda p=p, pname=pname: (
                        None if p.alive() else f"{pname} pipeline dead"
                    ),
                    pname,
                )
            )
        if replicate_log:
            # /ready gates on leadership: followers are healthy but NOT
            # ready, so the k8s Service only routes to the log of record
            # (the manifest's readinessProbe; liveness stays /health).
            def _ready():
                addr = leader.leader_address()
                return (
                    None
                    if addr is None
                    else f"follower (leader at {addr or 'unknown'})"
                )

            health_server.ready_checker = _ready
        startup.mark_complete()

    lookout_web = None
    if lookout_port is not None:
        from armada_tpu.lookout.webui import LookoutWebUI

        logs_of = None
        if binoculars_url:
            from armada_tpu.rpc.client import BinocularsClient

            logs_of = BinocularsClient(binoculars_url).logs
        oidc = lookout_oidc
        if isinstance(oidc, dict):
            from armada_tpu.lookout.oidc import web_config_from_dict

            try:
                oidc = web_config_from_dict(oidc)
            except ValueError:
                raise  # misconfiguration: fail loudly
            except Exception as e:
                # Issuer discovery is a network fetch; an IdP outage at boot
                # must not take the scheduler down.  The UI still gates on
                # the authn chain -- only the browser login flow is lost
                # until a restart (operators wanting boot-time certainty
                # configure explicit endpoints).
                logging.getLogger("armada.serve").warning(
                    "lookoutOidc discovery failed (%s); serving the UI "
                    "without the browser login flow",
                    e,
                )
                oidc = None
        lookout_web = LookoutWebUI(
            LookoutQueries(lookoutdb),
            lookout_port,
            host=bind_host,
            logs_of=logs_of,
            # the UI gates on the SAME chain as the gRPC/REST transports: a
            # strict operator config (serve --config authn:) locks the page,
            # the dev default (trusted headers + anonymous) keeps it open
            authenticator=authenticator,
            # serve: lookoutOidc: enables the browser login flow
            oidc=oidc,
            # serve: lookoutTrustProxy: honour X-Forwarded-* (reverse-proxy
            # deployments only; client-controlled when exposed directly)
            trust_proxy=lookout_trust_proxy,
            # cancel/reprioritise from the UI ride the same SubmitServer
            # (and therefore the same queue ACLs) as the gRPC verbs
            submit=submit_server,
            # job details carry the scheduler's why-(not)-scheduled report
            # (explain reason codes); follower replicas proxy to the leader
            reports=reports_query,
        )

    rest_gateway = None
    if rest_port is not None:
        from armada_tpu.server.gateway import RestGateway

        rest_gateway = RestGateway(
            submit_server,
            event_api,
            rest_port,
            host=bind_host,
            authenticator=authenticator,
            lookout_queries=LookoutQueries(lookoutdb),
            reports=reports_query,
        )

    # Scheduling sidecar (SURVEY §7 step 5): the round kernel as a gRPC
    # backend for EXTERNAL control planes (scheduling_algo.go:36-41).  A
    # dedicated port because its callers (a colocated Go scheduler) are a
    # different trust/deployment surface from job submitters.
    algo_server = None
    algo_bound = None
    sidecar = None
    if algo_port is not None:
        from armada_tpu.scheduler.sidecar import ScheduleSidecar

        sidecar = ScheduleSidecar(config)
        algo_server, algo_bound = make_server(
            schedule_sidecar=sidecar,
            address=f"{bind_host}:{algo_port}",
            authenticator=authenticator,
        )

    # Reference-counted watchdog arming, LAST -- after every fallible
    # startup step (DB connect, port binds): a failed start_control_plane
    # must not leak a process-global deadline no stop() will ever disarm.
    # Rounds before this point (the scheduler thread is already ticking)
    # just run unarmed for the few ms of remaining setup.  Planes overlap
    # and stop in any order (HA tests kill the leader while the follower
    # serves on); stop() disarms only THIS plane's registration.
    _watchdog_token = supervisor().arm(watchdog_s)
    # Unschedulable-reason attribution (models/explain.py): serve arms the
    # explain pass on a cadence by default (every 10th round of EACH
    # pool -- per-pool counters, so no pool aliases out of attribution) so
    # every deployment answers "why wasn't my job scheduled" with a reason
    # code; 0 disables.  ARMADA_EXPLAIN_INTERVAL (the drill/test override)
    # wins over this default inside explain_interval().  Armed LAST --
    # after every fallible startup step -- so a failed start never leaks
    # the serve default into a library embedder (stop() disarms it).
    from armada_tpu.models import explain as _explain

    _explain_token = _explain.arm_default(
        10 if explain_interval is None else explain_interval
    )
    # Round-output verification (models/verify.py): serve arms it ON by
    # default -- the serving plane is exactly where a silently-corrupted
    # round becomes a durable fact (event-sourcing makes decisions
    # irreversible once published).  ARMADA_VERIFY (the drill/test
    # override) wins over this default inside verify_enabled();
    # --no-verify disarms for planes that cannot afford the extra
    # transfer.  Token-armed LAST like the explain default above.
    from armada_tpu.models import verify as _verify

    _verify_token = _verify.arm_default(
        True if verify_rounds is None else bool(verify_rounds)
    )
    from armada_tpu.ops.trace import arm_gc

    _gc_token = arm_gc()

    return ControlPlaneProcess(
        port=bound_port,
        scheduler=scheduler,
        submit_server=submit_server,
        event_api=event_api,
        _grpc_server=grpc_server,
        _pipelines=[scheduler_pipeline, event_pipeline, lookout_pipeline],
        _stop=stop,
        _scheduler_thread=scheduler_thread,
        _log=log,
        _db=db,
        _eventdb=eventdb,
        _lookoutdb=lookoutdb,
        _metrics_server=metrics_server,
        health_server=health_server,
        lookout_web=lookout_web,
        rest_gateway=rest_gateway,
        algo_port=algo_bound,
        sidecar=sidecar,
        _algo_server=algo_server,
        replicator=replicator,
        checkpoint_manager=checkpointer,
        restore_info=restore_info,
        _watchdog_token=_watchdog_token,
        _explain_token=_explain_token,
        _verify_token=_verify_token,
        _gc_token=_gc_token,
    )


def run_fake_executor(
    server_address: str,
    executor_id: str = "fake-1",
    pool: str = "default",
    num_nodes: int = 4,
    cpu: str = "16",
    memory: str = "64Gi",
    interval_s: float = 1.0,
    stop: Optional[threading.Event] = None,
    config: Optional[SchedulingConfig] = None,
    default_runtime_s: float = 10.0,
    binoculars_port: Optional[int] = None,
    cordon_labels: Optional[dict] = None,
    metrics_port: Optional[int] = None,
    kubernetes_url: Optional[str] = None,
    kubernetes_in_cluster: bool = False,
    kube_token_file: Optional[str] = None,
    kube_ca_file: Optional[str] = None,
    kube_insecure: bool = False,
    pod_checks_file: Optional[str] = None,
    auth_token: Optional[str] = None,
    auth_token_file: Optional[str] = None,
    auth_basic: Optional[str] = None,
) -> None:
    """`armadactl executor`: a cluster agent against a remote control plane.
    Default is the fake in-memory cluster (cmd/fakeexecutor); kubernetes_url
    or kubernetes_in_cluster drives a real Kubernetes cluster via
    KubernetesClusterContext (cmd/executor).

    auth_token / auth_token_file / auth_basic ("user:pass") present
    credentials to a control plane running a non-dev auth chain
    (server/authn.py); without them only trusted-header/anonymous chains
    accept the lease stream."""
    import time

    from armada_tpu.core.types import NodeSpec
    from armada_tpu.executor import ExecutorService, FakeClusterContext
    from armada_tpu.rpc.client import ExecutorApiClient

    config = config or SchedulingConfig()
    factory = config.resource_list_factory()
    submit_brake = None
    if kubernetes_url or kubernetes_in_cluster:
        from armada_tpu.executor.kubernetes import (
            KubernetesClusterContext,
            etcd_health_brake,
        )

        if kubernetes_in_cluster:
            cluster = KubernetesClusterContext.in_cluster(
                factory, node_id_label=config.node_id_label, executor_id=executor_id
            )
        else:
            token = None
            if kube_token_file:
                with open(kube_token_file) as f:
                    token = f.read().strip()
            cluster = KubernetesClusterContext(
                kubernetes_url,
                factory,
                token=token,
                ca_file=kube_ca_file,
                insecure=kube_insecure,
                node_id_label=config.node_id_label,
                executor_id=executor_id,
            )
        # Real clusters get the etcd-health submission brake by default
        # (executor/application.go:63-103); the fake cluster has no etcd.
        submit_brake = etcd_health_brake(cluster)
    else:
        nodes = [
            NodeSpec(
                id=f"{executor_id}-n{i}",
                pool=pool,
                executor=executor_id,
                total_resources=factory.from_mapping({"cpu": cpu, "memory": memory}),
            )
            for i in range(num_nodes)
        ]
        cluster = FakeClusterContext(
            nodes, factory, runtime_of=lambda s: default_runtime_s
        )
    pod_check_rules, failed_pod_checker = (), None
    if pod_checks_file:
        import yaml

        from armada_tpu.executor.podchecks import checks_from_config

        with open(pod_checks_file) as f:
            pod_check_rules, failed_pod_checker = checks_from_config(
                yaml.safe_load(f)
            )
    bearer = auth_token
    if auth_token_file:
        with open(auth_token_file) as f:
            bearer = f.read().strip()
    basic = None
    if auth_basic:
        user, _, password = auth_basic.partition(":")
        basic = (user, password)
    api = ExecutorApiClient(
        server_address, factory=factory, bearer_token=bearer, basic_auth=basic
    )
    agent = ExecutorService(
        executor_id,
        pool,
        cluster,
        api,
        factory,
        pod_check_rules=pod_check_rules,
        failed_pod_checker=failed_pod_checker,
        submit_brake=submit_brake,
    )
    binoculars_server = None
    if binoculars_port is not None:
        from armada_tpu.executor.binoculars import Binoculars
        from armada_tpu.rpc.server import make_server

        binoculars_server, bport = make_server(
            binoculars=Binoculars(cluster, cordon_labels=cordon_labels),
            address=f"127.0.0.1:{binoculars_port}",
        )
        print(f"binoculars (logs/cordon) on 127.0.0.1:{bport}")
    metrics = None
    _metrics_handle = None
    if metrics_port is not None:
        from armada_tpu.executor.metrics import start_executor_metrics

        metrics, _metrics_handle = start_executor_metrics(metrics_port)
        print(f"executor metrics on :{metrics_port}/metrics")
    stop = stop or threading.Event()
    last = time.monotonic()
    tick = getattr(cluster, "tick", None)  # fake-cluster virtual time only
    errors_in_a_row = 0
    try:
        while not stop.is_set():
            now = time.monotonic()
            if tick is not None:
                tick(now - last)
            last = now
            try:
                agent.run_once()
                errors_in_a_row = 0
            except Exception as exc:
                # A transient apiserver / control-plane blip must not kill a
                # long-running agent (the reference's task loops retry); back
                # off up to 30s and keep reconciling.
                errors_in_a_row += 1
                backoff = min(interval_s * (2**errors_in_a_row), 30.0)
                print(f"executor {executor_id}: cycle failed ({exc}); retrying in {backoff:.1f}s")
                stop.wait(backoff)
                continue
            if metrics is not None:
                # observability must never throttle reconciliation: a
                # metrics bug outside this try would read as a cluster
                # failure and pin the loop in backoff
                try:
                    metrics.observe(agent)
                except Exception:  # noqa: BLE001
                    pass
            stop.wait(interval_s)
    finally:
        if binoculars_server is not None:
            binoculars_server.stop(1)
        if metrics is not None and _metrics_handle is not None:
            try:
                server, thread = _metrics_handle
                server.shutdown()
                thread.join(timeout=5)
            except (TypeError, ValueError):
                pass
        api.close()
