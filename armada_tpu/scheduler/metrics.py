"""Prometheus metrics for the scheduler.

Equivalent of the reference's cycle + state metrics
(internal/scheduler/metrics/cycle_metrics.go:71-170, state_metrics.go), with
the same metric names where the concept carries over, so dashboards written
for the reference read against this framework:

  armada_scheduler_fair_share{pool,queue}
  armada_scheduler_adjusted_fair_share{pool,queue}
  armada_scheduler_actual_share{pool,queue}
  armada_scheduler_demand{pool,queue}
  armada_scheduler_queue_weight{pool,queue}
  armada_scheduler_fairness_error{pool}
  armada_scheduler_scheduled_jobs{pool,queue}        (counter)
  armada_scheduler_premptied_jobs{pool,queue}        (counter; [sic] the
      reference's historical spelling is kept for dashboard compatibility)
  armada_scheduler_schedule_cycle_times (histogram)
  armada_scheduler_reconcile_cycle_times (histogram)
  armada_scheduler_job_state_counter_by_queue{queue,state} (counter)
"""

from __future__ import annotations

import time
from typing import Optional

from prometheus_client import Counter, Gauge, Histogram, REGISTRY


class SchedulerMetrics:
    def __init__(self, registry=REGISTRY, state_reset_interval_s: float = 0.0):
        """state_reset_interval_s: clear the job-state counter vector this
        often (state_metrics.go:157,307 jobStateMetricsResetInterval) to
        bound label-series churn; 0 = never reset."""
        self._state_reset_interval_s = state_reset_interval_s
        self._last_state_reset: Optional[float] = None
        self._used_labels: set = set()
        g = lambda name, doc, labels: Gauge(  # noqa: E731
            name, doc, labels, registry=registry
        )
        self.fair_share = g(
            "armada_scheduler_fair_share", "Fair share of each queue", ["pool", "queue"]
        )
        self.adjusted_fair_share = g(
            "armada_scheduler_adjusted_fair_share",
            "Adjusted fair share of each queue",
            ["pool", "queue"],
        )
        self.actual_share = g(
            "armada_scheduler_actual_share", "Actual share of each queue", ["pool", "queue"]
        )
        self.demand = g(
            "armada_scheduler_demand", "Demand share of each queue", ["pool", "queue"]
        )
        self.queue_weight = g(
            "armada_scheduler_queue_weight", "Weight of each queue", ["pool", "queue"]
        )
        self.short_job_penalty = g(
            "armada_scheduler_short_job_penalty",
            "Resource share charged for jobs that exited soon after starting",
            ["pool", "queue"],
        )
        # Market-pool gauges (cycle_metrics.go:231,279,295).
        self.spot_price = g(
            "armada_scheduler_spot_price",
            "Spot price of each market-driven pool",
            ["pool"],
        )
        self.indicative_price = g(
            "armada_scheduler_indicative_price",
            "Indicative price for configured job shapes in pool",
            ["pool", "name"],
        )
        self.indicative_price_schedulable = g(
            "armada_scheduler_indicative_price_schedulable",
            "Whether the configured job shape could schedule",
            ["pool", "name", "reason"],
        )
        self.idealised_scheduled_value = g(
            "armada_scheduler_idealised_scheduled_value",
            "Value each queue would realise on a boundary-less cluster",
            ["pool", "queue"],
        )
        self.realised_scheduled_value = g(
            "armada_scheduler_realised_scheduled_value",
            "Value each queue actually realised this cycle",
            ["pool", "queue"],
        )
        self.indicative_share = g(
            "armada_scheduler_indicative_share",
            "Share a new queue at the base priority would receive",
            ["pool", "priority"],
        )
        self.quarantined_nodes = Gauge(
            "armada_scheduler_quarantined_nodes",
            "Nodes currently excluded for high failure rates",
            registry=registry,
        )
        # Explain-pass attribution (models/explain.py): per-queue
        # unschedulable-job counts by dominant reason, refreshed on explain
        # cycles (ARMADA_EXPLAIN_INTERVAL); label sets not reported by the
        # latest pass are removed so a drained queue stops exporting.
        self.unschedulable_jobs = g(
            "armada_scheduler_unschedulable_jobs",
            "Jobs a scheduling round left unplaced, by dominant reason "
            "(shape-infeasible / capacity-blocked / fairness-capped / "
            "gang-partial / round-terminated / type-mismatch)",
            ["pool", "queue", "reason"],
        )
        self.fragmentation_index = g(
            "armada_scheduler_fragmentation_index",
            "1 - largest single-node free block / total free capacity, "
            "per resource (0 = one node could absorb all free capacity)",
            ["pool", "resource"],
        )
        # Per-hardware-type split of the same index; only exported on
        # mixed fleets (a shattered accelerator tier hides inside healthy
        # aggregate numbers when the CPU tier holds most free capacity).
        self.type_fragmentation_index = g(
            "armada_scheduler_type_fragmentation_index",
            "Fragmentation index split by hardware node type "
            "(armada-tpu.io/node-type); exported on mixed fleets only",
            ["pool", "node_type", "resource"],
        )
        self._unsched_labels: set = set()
        self._frag_labels: set = set()
        self._type_frag_labels: set = set()
        # Round-output verification (models/verify.py): cumulative failure
        # counts per invariant/fingerprint site, and the device quarantine
        # scoreboard (scheduler/quarantine.py).  Quarantine label sets no
        # longer present (operator clear) are removed, like the explain
        # series above -- a cleared device must stop exporting its gauge.
        self.round_verification_failures = g(
            "armada_round_verification_failures_total",
            "Scheduling rounds that failed output verification, by the "
            "invariant or fingerprint site that caught them (monotonic)",
            ["site"],
        )
        self.device_quarantined = g(
            "armada_device_quarantined",
            "1 while the device is quarantined by round verification "
            "(excluded from re-promotion until `armadactl quarantine "
            "--clear`)",
            ["device"],
        )
        self._quarantine_labels: set = set()
        # Per-pool round latency (round 17, pool-parallel serving): the
        # slow-tenant gauge -- labelled quantiles from the SLO recorder's
        # per-pool histograms (scheduler/slo.py observe_pool_round).  Label
        # sets for pools the recorder no longer reports are removed, like
        # the explain series above.
        self.pool_cycle_seconds = g(
            "armada_scheduler_pool_cycle_seconds",
            "Per-pool scheduling-round latency percentiles (one pool's "
            "dispatch through apply within a cycle)",
            ["pool", "quantile"],
        )
        self._pool_cycle_labels: set = set()
        # Device-loss degradation state (core/watchdog): dashboards alert on
        # device_healthy == 0 (rounds running on the CPU failover) and on
        # device_fallbacks increasing (each is one lost round re-run).
        self.device_healthy = Gauge(
            "armada_scheduler_device_healthy",
            "1 while scheduling rounds target the accelerator backend, "
            "0 while degraded to the CPU failover",
            registry=registry,
        )
        self.device_consecutive_failures = Gauge(
            "armada_scheduler_device_consecutive_failures",
            "Device round failures since the last healthy round",
            registry=registry,
        )
        self.device_fallbacks = Gauge(
            "armada_scheduler_device_fallbacks",
            "Device rounds that failed over to the CPU backend (monotonic)",
            registry=registry,
        )
        self.device_promotions = Gauge(
            "armada_scheduler_device_promotions",
            "Re-promotions back to the accelerator backend (monotonic)",
            registry=registry,
        )
        # Executor-reported ACTUAL usage (reference metrics.go:387-395 +
        # commonmetrics QueueUsedDesc "queue_resource_used"): what pods are
        # consuming, as opposed to what the scheduler allocated.
        self.queue_resource_used = g(
            "armada_scheduler_queue_resource_used",
            "Resource usage of non-terminal pods per queue, as reported by executors",
            ["cluster", "pool", "queue", "resource"],
        )
        self.fairness_error = g(
            "armada_scheduler_fairness_error",
            "Cumulative delta between adjusted fair share and actual share",
            ["pool"],
        )
        self.scheduled_jobs = Counter(
            "armada_scheduler_scheduled_jobs",
            "Number of jobs scheduled",
            ["pool", "queue"],
            registry=registry,
        )
        self.preempted_jobs = Counter(
            "armada_scheduler_premptied_jobs",
            "Number of jobs preempted",
            ["pool", "queue"],
            registry=registry,
        )
        self.schedule_cycle_time = Histogram(
            "armada_scheduler_schedule_cycle_times",
            "Cycle time when scheduling",
            registry=registry,
            buckets=[0.01, 0.05, 0.1, 0.5, 1, 2, 5, 10, 30],
        )
        self.reconcile_cycle_time = Histogram(
            "armada_scheduler_reconcile_cycle_times",
            "Cycle time when reconciling state only",
            registry=registry,
            buckets=[0.001, 0.01, 0.05, 0.1, 0.5, 1, 5],
        )
        self.job_state_counter = Counter(
            "armada_scheduler_job_state_counter_by_queue",
            "Job state transitions observed",
            ["queue", "state"],
            registry=registry,
        )
        # Streaming SLO percentiles (scheduler/slo.py LogHistograms): the
        # standing-load latency distributions -- cycle latency (split by
        # device degradation state), time-to-first-lease, ingest->visible
        # lag -- as labelled quantile gauges, refreshed every cycle.
        self.slo_latency = g(
            "armada_scheduler_slo_latency_seconds",
            "Streaming SLO latency percentiles (log-bucketed histograms)",
            ["metric", "quantile"],
        )
        self.slo_count = g(
            "armada_scheduler_slo_observations",
            "Sample count behind each SLO latency histogram",
            ["metric"],
        )
        # Per-stage cycle latency (ops/trace.py stage histograms): where a
        # cycle's time goes, as the same labelled-quantile-gauge shape as
        # the SLO block, so dashboards attribute a cycle_latency regression
        # to a stage without a trace capture.  A stage = a DIRECT child of
        # a cycle root: sync_state/transitions/schedule/event_publish/
        # commit for scheduler cycles (assemble/round/kernel spans nest
        # INSIDE schedule -- the watchdog worker adopts the caller's span,
        # so they never double-count as stages); for sidecar cycles the
        # direct children of the two roots: session_lock_wait/
        # job_from_state/mirror_upsert/mirror_commit/prefetch_content
        # under sidecar_sync, session_lock_wait/fleet_scan/feed_apply (the
        # overlay)/pool_nodes/pool_prepare/assemble/round/apply_outcome/
        # away_prepare/mirror_commit/slo_feed under sidecar_round.  The
        # commit's feed_apply nests in mirror_commit since PR 25, so
        # sidecar_sync has no feed_apply stage any more
        # (docs/observability.md has the catalogue).
        self.cycle_stage_latency = g(
            "armada_cycle_stage_seconds",
            "Per-stage cycle latency percentiles (trace-span histograms)",
            ["stage", "quantile"],
        )
        # Durability gauges (scheduler/checkpoint.py + eventlog/replicator):
        # dashboards alert on snapshot age past the cadence (RPO drifting),
        # replication lag growing (takeover would lose that window), and an
        # epoch bump (a failover happened).
        self.snapshot_age = Gauge(
            "armada_durability_snapshot_age_seconds",
            "Age of the newest valid checkpoint snapshot",
            registry=registry,
        )
        self.snapshot_fenced_offset = Gauge(
            "armada_durability_fenced_offset_total",
            "Sum of the newest snapshot's eventlog fence offsets (restart "
            "replays only the suffix past this)",
            registry=registry,
        )
        self.durability_epoch = Gauge(
            "armada_durability_epoch",
            "Current leader-election fencing generation (monotonic epoch)",
            registry=registry,
        )
        self.replication_lag_bytes = Gauge(
            "armada_replication_lag_bytes",
            "Event-log bytes the local replica trails the leader by",
            registry=registry,
        )
        self.replication_lag_seconds = Gauge(
            "armada_replication_lag_seconds",
            "Seconds since every partition was last caught up to the leader",
            registry=registry,
        )
        self.replication_records = Gauge(
            "armada_replication_records_replicated_total",
            "Event-log records replicated from leaders (monotonic)",
            registry=registry,
        )
        # Ingest-plane gauges (round 18, ingest/stats.py): per-consumer
        # apply rate and per-partition lag.  Lag is in log BYTES -- the
        # honest unit (positions are byte offsets); bytes track events 1:1
        # for a steady record-size mix.  Stale label sets (a stopped view,
        # a shrunk partition set) are removed like the explain series.
        self.ingest_lag = g(
            "armada_ingest_lag_bytes",
            "Unapplied event-log backlog per consumer view and partition "
            "(bytes of log the view's committed cursor trails by)",
            ["consumer", "partition"],
        )
        self.ingest_rate = g(
            "armada_ingest_events_per_second",
            "Events applied per second by each consumer view "
            "(exponentially decayed rate)",
            ["consumer"],
        )
        # Per-shard store-leg write latency (round 19, sharded materialized
        # stores): the spread across shards is what distinguishes a
        # single-writer convoy (every shard reports the same queueing
        # latency) from genuinely parallel store legs.
        self.ingest_store_write = g(
            "armada_ingest_store_write_seconds",
            "Average store-transaction latency per consumer view and "
            "ingest shard (the shard's transactional store leg)",
            ["consumer", "shard"],
        )
        self._ingest_lag_labels: set = set()
        self._ingest_rate_labels: set = set()
        self._ingest_store_labels: set = set()
        # Poison-record quarantine (round 21, ingest/dlq.py): dead-letter
        # and batch-retry counts are process-cumulative registry totals
        # exported as gauges (the registry is the source of truth; a
        # restart legitimately resets them, like verification failures).
        self.ingest_dead_letters = g(
            "armada_ingest_dead_letters_total",
            "Records quarantined to the dead-letter store per consumer "
            "view and partition (process-cumulative)",
            ["consumer", "partition"],
        )
        self.ingest_batch_retries = g(
            "armada_ingest_batch_retries_total",
            "Failed ingest batch attempts per consumer view "
            "(process-cumulative; spikes precede poison isolation)",
            ["consumer"],
        )
        self._ingest_dead_labels: set = set()
        self._ingest_retry_labels: set = set()

    # --- hooks called by the Scheduler --------------------------------------

    def observe_device(self, snapshot: dict) -> None:
        """Publish the watchdog supervisor's degradation state
        (core/watchdog.DeviceSupervisor.snapshot), once per cycle."""
        self.device_healthy.set(0.0 if snapshot.get("backend") == "cpu" else 1.0)
        self.device_consecutive_failures.set(
            float(snapshot.get("consecutive_failures", 0))
        )
        self.device_fallbacks.set(float(snapshot.get("fallbacks", 0)))
        self.device_promotions.set(float(snapshot.get("promotions", 0)))

    def observe_slo(self, snapshot: dict) -> None:
        """Publish the SLO recorder's histogram snapshot
        (scheduler/slo.SLORecorder.snapshot), once per cycle.  The "pools"
        sub-block (per-pool round histograms, round 17) exports as
        armada_scheduler_pool_cycle_seconds{pool,quantile}; stale pool
        label sets are removed."""
        pools = snapshot.get("pools")
        if isinstance(pools, dict):
            seen = set()
            for pool, summary in pools.items():
                if not isinstance(summary, dict) or not summary.get("count"):
                    continue
                for q in ("p50", "p90", "p95", "p99"):
                    v = summary.get(q + "_s")
                    if v is not None:
                        seen.add((pool, q))
                        self.pool_cycle_seconds.labels(pool, q).set(v)
            for labels in self._pool_cycle_labels - seen:
                try:
                    self.pool_cycle_seconds.remove(*labels)
                except KeyError:
                    pass
            self._pool_cycle_labels = seen
        for metric, summary in snapshot.items():
            if not isinstance(summary, dict) or not summary.get("count"):
                continue
            self.slo_count.labels(metric).set(float(summary["count"]))
            for q in ("p50", "p90", "p95", "p99"):
                v = summary.get(q + "_s")
                if v is not None:
                    self.slo_latency.labels(metric, q).set(v)

    def observe_ingest(self, consumers: dict) -> None:
        """Publish the ingest stats registry's snapshot
        (ingest/stats.registry().snapshot), once per cycle; stale
        consumer/partition label sets are removed."""
        lag_seen = set()
        rate_seen = set()
        store_seen = set()
        for consumer, snap in consumers.items():
            if not isinstance(snap, dict) or "events_per_s" not in snap:
                continue
            rate_seen.add((consumer,))
            self.ingest_rate.labels(consumer).set(float(snap["events_per_s"]))
            for part, lag in (snap.get("lag_bytes") or {}).items():
                lag_seen.add((consumer, str(part)))
                self.ingest_lag.labels(consumer, str(part)).set(float(lag))
            for shard, stats in (snap.get("store_write") or {}).items():
                if not isinstance(stats, dict) or not stats.get("writes"):
                    continue
                store_seen.add((consumer, str(shard)))
                self.ingest_store_write.labels(consumer, str(shard)).set(
                    float(stats.get("avg_s", 0.0))
                )
        for labels in self._ingest_lag_labels - lag_seen:
            try:
                self.ingest_lag.remove(*labels)
            except KeyError:
                pass
        for labels in self._ingest_rate_labels - rate_seen:
            try:
                self.ingest_rate.remove(*labels)
            except KeyError:
                pass
        for labels in self._ingest_store_labels - store_seen:
            try:
                self.ingest_store_write.remove(*labels)
            except KeyError:
                pass
        self._ingest_lag_labels = lag_seen
        self._ingest_rate_labels = rate_seen
        self._ingest_store_labels = store_seen

    def observe_dlq(self, snapshot: dict) -> None:
        """Publish the dead-letter registry's snapshot
        (ingest/dlq.registry().snapshot), once per cycle; stale label sets
        (a reset registry) are removed like the ingest series."""
        dead_seen = set()
        retry_seen = set()
        by_part = snapshot.get("dead_letters_by_partition") or {}
        for consumer, parts in by_part.items():
            for part, n in parts.items():
                labels = (consumer, str(part))
                dead_seen.add(labels)
                self.ingest_dead_letters.labels(*labels).set(float(n))
        for consumer, n in (snapshot.get("batch_retries") or {}).items():
            retry_seen.add((consumer,))
            self.ingest_batch_retries.labels(consumer).set(float(n))
        for labels in self._ingest_dead_labels - dead_seen:
            try:
                self.ingest_dead_letters.remove(*labels)
            except KeyError:
                pass
        for labels in self._ingest_retry_labels - retry_seen:
            try:
                self.ingest_batch_retries.remove(*labels)
            except KeyError:
                pass
        self._ingest_dead_labels = dead_seen
        self._ingest_retry_labels = retry_seen

    def observe_trace(self, stage_snapshot: dict) -> None:
        """Publish the trace recorder's per-stage latency snapshot
        (ops/trace.TraceRecorder.stage_snapshot), once per cycle.  Keys
        arrive as ``stage.<name>`` (plus the whole-cycle ``cycle``)."""
        for key, summary in stage_snapshot.items():
            if not isinstance(summary, dict) or not summary.get("count"):
                continue
            stage = key.split(".", 1)[1] if key.startswith("stage.") else key
            for q in ("p50", "p90", "p95", "p99"):
                v = summary.get(q + "_s")
                if v is not None:
                    self.cycle_stage_latency.labels(stage, q).set(v)

    def observe_verify(self, block: dict) -> None:
        """Publish the round-verification ledger + quarantine scoreboard
        (models/verify.healthz_block), once per cycle.  Failure counters
        are cumulative process totals exported as-is; quarantine gauges
        for devices no longer on the scoreboard are removed."""
        for site, n in (block.get("failures_by_site") or {}).items():
            self.round_verification_failures.labels(site).set(float(n))
        seen = set()
        quarantined = (block.get("quarantine") or {}).get("quarantined") or {}
        for device in quarantined:
            labels = (device,)
            seen.add(labels)
            self.device_quarantined.labels(*labels).set(1.0)
        for labels in self._quarantine_labels - seen:
            try:
                self.device_quarantined.remove(*labels)
            except KeyError:
                pass
        self._quarantine_labels = seen

    def observe_durability(self, status: dict) -> None:
        """Publish the scheduler's durability block
        (Scheduler.durability_status), once per cycle."""
        self.durability_epoch.set(float(status.get("epoch", 0)))
        snap = (status.get("checkpoint") or {}).get("snapshot")
        if snap:
            self.snapshot_age.set(float(snap.get("age_s", 0.0)))
            self.snapshot_fenced_offset.set(
                float(snap.get("fenced_offset_total", 0))
            )
        rep = status.get("replication")
        if isinstance(rep, dict) and "lag_bytes" in rep:
            self.replication_lag_bytes.set(float(rep["lag_bytes"]))
            self.replication_lag_seconds.set(float(rep["lag_s"]))
            self.replication_records.set(
                float(rep.get("records_replicated", 0))
            )

    def observe_executor_usage(self, executors, factory) -> None:
        """Publish executor-reported per-queue usage (metrics.go:387-395).
        Values are in resource base units (atoms).  Label sets not reported
        this round are REMOVED -- a queue whose pods all finished must not
        keep exporting its last nonzero usage forever."""
        seen = set()
        for ex in executors:
            for queue, atoms in ex.queue_usage.items():
                for i, name in enumerate(factory.names):
                    if i < len(atoms):
                        labels = (ex.id, ex.pool, queue, name)
                        seen.add(labels)
                        self.queue_resource_used.labels(*labels).set(
                            float(atoms[i])
                        )
        for labels in self._used_labels - seen:
            try:
                self.queue_resource_used.remove(*labels)
            except KeyError:
                pass
        self._used_labels = seen

    def _observe_explain(self, pool: str, explain) -> None:
        """Publish one pool's explain attribution (models/explain.py):
        per-(queue, reason) unschedulable counts + per-resource
        fragmentation indices.  Stale (pool, queue, reason) series from a
        previous pass are removed, mirroring observe_executor_usage."""
        seen = set()
        for qname, reasons in explain.queue_counts.items():
            for reason, n in reasons.items():
                labels = (pool, qname, reason)
                seen.add(labels)
                self.unschedulable_jobs.labels(*labels).set(float(n))
        for labels in {
            l for l in self._unsched_labels if l[0] == pool
        } - seen:
            try:
                self.unschedulable_jobs.remove(*labels)
            except KeyError:
                pass
        self._unsched_labels = {
            l for l in self._unsched_labels if l[0] != pool
        } | seen
        fseen = set()
        for resource, frag in explain.fragmentation.items():
            fseen.add((pool, resource))
            self.fragmentation_index.labels(pool, resource).set(
                float(frag.get("index", 0.0))
            )
        for labels in {
            l for l in self._frag_labels if l[0] == pool
        } - fseen:
            try:
                self.fragmentation_index.remove(*labels)
            except KeyError:
                pass
        self._frag_labels = {
            l for l in self._frag_labels if l[0] != pool
        } | fseen
        tseen = set()
        for tname, row in getattr(
            explain, "fragmentation_by_type", {}
        ).items():
            for resource, frag in row.items():
                labels = (pool, tname, resource)
                tseen.add(labels)
                self.type_fragmentation_index.labels(*labels).set(
                    float(frag.get("index", 0.0))
                )
        for labels in {
            l for l in self._type_frag_labels if l[0] == pool
        } - tseen:
            try:
                self.type_fragmentation_index.remove(*labels)
            except KeyError:
                pass
        self._type_frag_labels = {
            l for l in self._type_frag_labels if l[0] != pool
        } | tseen

    def observe_cycle(self, result, duration_s: float, now: Optional[float] = None) -> None:
        """`result` is a CycleResult; records cycle time + decisions + shares."""
        if self._state_reset_interval_s > 0:
            now = time.time() if now is None else now
            if self._last_state_reset is None:
                self._last_state_reset = now
            elif now - self._last_state_reset > self._state_reset_interval_s:
                self.job_state_counter.clear()
                self._last_state_reset = now
        if result.scheduled:
            self.schedule_cycle_time.observe(duration_s)
        else:
            self.reconcile_cycle_time.observe(duration_s)

        for seq in result.published:
            for ev in seq.events:
                kind = ev.WhichOneof("event")
                state = {
                    "submit_job": "queued",
                    "job_run_leased": "leased",
                    "job_run_running": "running",
                    "job_succeeded": "succeeded",
                    "job_errors": "failed",
                    "cancelled_job": "cancelled",
                    "job_run_preempted": "preempted",
                    "job_requeued": "requeued",
                }.get(kind)
                if state:
                    self.job_state_counter.labels(seq.queue, state).inc()

        sched = result.scheduler_result
        if sched is None:
            return
        for job, run in sched.scheduled:
            self.scheduled_jobs.labels(run.pool, job.queue).inc()
        for job, run in sched.preempted:
            self.preempted_jobs.labels(run.pool or "", job.queue).inc()
        for stats in sched.pools:
            error = 0.0
            for qname, qs in stats.outcome.queue_stats.items():
                self.fair_share.labels(stats.pool, qname).set(qs["fair_share"])
                self.adjusted_fair_share.labels(stats.pool, qname).set(
                    qs["adjusted_fair_share"]
                )
                self.actual_share.labels(stats.pool, qname).set(qs["actual_share"])
                self.demand.labels(stats.pool, qname).set(qs["demand_share"])
                self.queue_weight.labels(stats.pool, qname).set(qs["weight"])
                self.short_job_penalty.labels(stats.pool, qname).set(
                    qs.get("short_job_penalty", 0.0)
                )
                error += abs(qs["adjusted_fair_share"] - qs["actual_share"])
            self.fairness_error.labels(stats.pool).set(error)
            explain = getattr(stats.outcome, "explain", None)
            if explain is not None:
                self._observe_explain(stats.pool, explain)
            for prio, share in stats.outcome.indicative_shares.items():
                self.indicative_share.labels(stats.pool, str(prio)).set(share)
            if stats.market:
                # Set every cycle -- 0 when no crossing happened -- so a stale
                # previous-round price never lingers (context/scheduling.go
                # GetSpotPrice returns 0 when unset).
                self.spot_price.labels(stats.pool).set(
                    stats.outcome.spot_price or 0.0
                )
            for name, pr in stats.indicative_prices.items():
                if pr.evaluated:
                    self.indicative_price.labels(stats.pool, name).set(pr.price)
                    self.indicative_price_schedulable.labels(
                        stats.pool, name, pr.unschedulable_reason
                    ).set(1.0 if pr.schedulable else 0.0)
            if stats.market:
                # Per-cycle flow values: set 0 for queues with no placements
                # this cycle, like spot_price above, so stale values never
                # linger on a quiet queue.
                for qname in stats.outcome.queue_stats:
                    self.idealised_scheduled_value.labels(stats.pool, qname).set(
                        stats.idealised_values.get(qname, 0.0)
                    )
                    self.realised_scheduled_value.labels(stats.pool, qname).set(
                        stats.realised_values.get(qname, 0.0)
                    )
