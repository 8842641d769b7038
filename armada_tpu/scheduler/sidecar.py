"""The scheduling sidecar: the TPU round kernel as a gRPC-callable backend.

Mirrors the reference's SchedulingAlgo boundary (internal/scheduler/
scheduling/scheduling_algo.go:36-41 -- Schedule(ctx, txn) -> SchedulerResult)
so an EXTERNAL control plane (the build plan's "colocate with the reference's
Go scheduler" deployment, SURVEY.md north star) can use this repo's kernel
without adopting its Python control plane:

  caller owns job truth  --SyncState deltas-->  session's JobDb mirror
  caller's cycle         --ScheduleRound----->  FairSchedulingAlgo.schedule
  response               <--leases/preemptions  (caller applies to ITS jobDb)

The session keeps the full incremental machinery server-side (JobDb mirror,
per-pool IncrementalBuilders, device-resident slabs), so a steady-state call
carries O(cycle delta) bytes: the state-transfer economics the reference gets
from Schedule() being an in-process call are preserved across the boundary.

The sidecar's decisions are applied to its own mirror when the round commits,
exactly like the in-process scheduler -- the caller's subsequent SyncState
deltas are idempotent re-assertions (latest state wins), so an accepted lease
round-trips as a no-op and a rejected one (caller failed to publish) is
corrected by the next sync.
"""

from __future__ import annotations

import contextlib
import json
import time
import uuid
from typing import Optional, Sequence

from armada_tpu.core.config import SchedulingConfig
from armada_tpu.core.logging import get_logger
from armada_tpu.core.pipeline import pipeline_enabled, prefetch_worthwhile
from armada_tpu.core.types import JobSpec, Queue
from armada_tpu.events.convert import SpecTemplates, job_spec_from_proto
from armada_tpu.jobdb.job import JOB_DEFAULTS, RUN_DEFAULTS, Job, JobRun, blank
from armada_tpu.jobdb.jobdb import JobDb
from armada_tpu.ops.trace import recorder as _trace
from armada_tpu.scheduler.algo import FairSchedulingAlgo, SchedulerResult
from armada_tpu.scheduler.providers import most_specific_bid
from armada_tpu.scheduler.executors import ExecutorSnapshot

from armada_tpu.analysis.tsan import make_lock

FAILED_SAMPLE_CAP = 1000

_log = get_logger(__name__)


class UnknownSession(KeyError):
    """No session with this id -- maps to gRPC NOT_FOUND.  A dedicated type
    so an incidental KeyError inside a round can never masquerade as a
    missing session (the caller would wrongly rebuild its mirror)."""


class SessionExists(ValueError):
    """Caller-chosen session id already live -- maps to ALREADY_EXISTS.
    Silently replacing the session would discard a mirror another caller
    (or a retried CreateSession) is still feeding."""


class SessionBids:
    """Latest synced market bid prices, (queue, band, pool)-keyed.

    Stands in for the polling BidPriceProvider (scheduler/
    external_providers.py): the CALLER refreshes prices by syncing a new
    table; lookups between syncs serve the cached one, matching the
    reference's bid-price cache semantics (pricing/bid_price.go).
    """

    def __init__(self):
        self._prices: dict[tuple[str, str, str], float] = {}

    def update(self, prices: dict[tuple[str, str, str], float]) -> None:
        self._prices = dict(prices)

    def price(self, queue: str, band: str = "", pool: str = "") -> float:
        return most_specific_bid(self._prices, queue, band, pool)


def _job_from_state(msg, factory) -> Job:
    """JobState wire message -> jobdb Job (the mirror's view of the caller's
    job).  Ban nodes ride as synthetic terminal attempted runs so
    Job.anti_affinity_nodes derives them exactly like a native retry."""
    spec = job_spec_from_proto(
        msg.job_id,
        msg.queue,
        msg.jobset,
        msg.spec,
        factory,
        submit_time=msg.submit_time,
    )
    runs = []
    for node_id in msg.banned_nodes:
        runs.append(
            JobRun(
                id=f"ban/{msg.job_id}/{node_id}",
                job_id=msg.job_id,
                node_id=node_id,
                node_name=node_id,
                failed=True,
                run_attempted=True,
            )
        )
    has_run = bool(msg.run.run_id or msg.run.node_id)
    if has_run:
        r = msg.run
        runs.append(
            JobRun(
                id=r.run_id or uuid.uuid4().hex,
                job_id=msg.job_id,
                executor=r.executor,
                node_id=r.node_id,
                node_name=r.node_name or r.node_id,
                pool=r.pool or "default",
                scheduled_at_priority=(
                    int(r.scheduled_at_priority)
                    if r.has_scheduled_at_priority
                    else None
                ),
                pool_scheduled_away=r.away,
                running=r.running,
                running_ns=int(r.running_ns),
                run_attempted=r.running or bool(r.running_ns),
                # A terminal job's run is over (resources free); the job row
                # is retained only for the short-job penalty window, which
                # exempts preempted runs.
                failed=bool(msg.terminal) and not r.preempted,
                preempted=bool(msg.terminal) and r.preempted,
            )
        )
    return Job(
        spec=spec,
        priority=int(msg.priority),
        queued=bool(msg.queued) and not msg.terminal,
        validated=bool(msg.validated),
        pools=tuple(msg.pools),
        failed=bool(msg.terminal),
        runs=tuple(runs),
    )


def _jobs_from_states(msgs, templates: SpecTemplates, terminal_synced: dict):
    """A SyncState's JobStates -> jobdb Jobs, in ONE pass: each Job equal,
    field for field, to `_job_from_state(msg, factory)` (the one-message
    reference; tests/test_sidecar.py holds the pass to it), with the session's
    terminal bookkeeping done on the way.  Returns (jobs, spec_hits).

    What a `spec` sub-message converts to comes from `templates` (derived
    once per distinct spec, its containers shared between the jobs of it),
    and each frozen dataclass is filled from a prototype field dict instead
    of through its generated __init__ (an object.__setattr__ a field, twenty
    fields a JobSpec, and Job's __post_init__): the instances are ordinary
    ones, ==, dataclasses.replace and the with_* methods work on them.
    """
    lookup = templates.lookup
    out = []
    misses = 0
    for m in msgs:
        spec_msg = m.spec
        key = spec_msg.SerializeToString()
        fields = lookup(key)
        if fields is None:
            fields = templates.intern(key, spec_msg)
            misses += 1
        job_id = m.job_id
        submit_time = m.submit_time
        terminal = m.terminal
        r = m.run

        spec = blank(JobSpec)
        d = spec.__dict__
        d.update(fields)
        d["id"] = job_id
        d["queue"] = m.queue
        d["jobset"] = m.jobset
        d["submit_time"] = submit_time

        # len()-guarded, like the spec's collections: iterating an empty
        # repeated field costs as much as a JobRun.  Bans are rare (a retry):
        # they take the plain constructor, as the reference does.
        runs = []
        if len(m.banned_nodes):
            for node_id in m.banned_nodes:
                runs.append(
                    JobRun(
                        id=f"ban/{job_id}/{node_id}",
                        job_id=job_id,
                        node_id=node_id,
                        node_name=node_id,
                        failed=True,
                        run_attempted=True,
                    )
                )
        if r.run_id or r.node_id:
            run = blank(JobRun)
            d = run.__dict__
            d.update(RUN_DEFAULTS)
            d["id"] = r.run_id or uuid.uuid4().hex
            d["job_id"] = job_id
            d["executor"] = r.executor
            d["node_id"] = r.node_id
            d["node_name"] = r.node_name or r.node_id
            d["pool"] = r.pool or "default"
            d["scheduled_at_priority"] = (
                int(r.scheduled_at_priority)
                if r.has_scheduled_at_priority
                else None
            )
            d["pool_scheduled_away"] = r.away
            d["running"] = r.running
            d["running_ns"] = int(r.running_ns)
            d["run_attempted"] = r.running or bool(r.running_ns)
            d["failed"] = terminal and not r.preempted
            d["preempted"] = terminal and r.preempted
            runs.append(run)

        if terminal:
            # 0 = never ran: the penalty can't apply
            # (ShortJobPenalty.applies needs running_ns > 0), so the sweep
            # drops it at the next round -- and never mixes the sidecar wall
            # clock with the caller's logical now_ns.
            terminal_synced[job_id] = int(r.running_ns)
        else:
            terminal_synced.pop(job_id, None)

        job = blank(Job)
        d = job.__dict__
        d.update(JOB_DEFAULTS)
        d["spec"] = spec
        # what Job.__post_init__ derives from a priority that is given
        d["priority"] = d["requested_priority"] = int(m.priority)
        d["submitted_ns"] = int(submit_time * 1e9)
        d["queued"] = m.queued and not terminal
        d["validated"] = m.validated
        d["pools"] = tuple(m.pools) if len(m.pools) else ()
        d["failed"] = terminal
        d["runs"] = tuple(runs)
        out.append(job)
    return out, len(out) - misses


class ScheduleSession:
    """One caller's mirrored world + algo; serialized rounds."""

    def __init__(
        self,
        session_id: str,
        config: SchedulingConfig,
        clock_ns=lambda: int(time.time() * 1e9),
    ):
        self.id = session_id
        self.config = config
        self._clock_ns = clock_ns
        # Terminal jobs the caller synced (retained only for the short-job
        # penalty window): job id -> running_ns (or sync time when the run
        # never ran).  Swept at round end so a long-lived session's mirror
        # cannot grow without bound on a caller that deletes lazily -- the
        # in-process scheduler's _retained_terminal sweep equivalent.
        self._terminal_synced: dict[str, int] = {}
        self.factory = config.resource_list_factory()
        self._spec_templates = SpecTemplates(self.factory)
        self.jobdb = JobDb(config)
        self.queues: list[Queue] = []
        self.executors: list[ExecutorSnapshot] = []
        self.bids = SessionBids()
        self.feed = None
        if config.incremental_problem_build:
            from armada_tpu.scheduler.incremental_algo import (
                IncrementalProblemFeed,
            )

            self.feed = IncrementalProblemFeed(config)
            self.feed.attach(self.jobdb)
        market = any(p.market_driven for p in config.pools)
        self.algo = FairSchedulingAlgo(
            config,
            queues=lambda: self.queues,
            clock_ns=clock_ns,
            collect_stats=False,
            bid_prices=self.bids if market else None,
            feed=self.feed,
        )
        self._lock = make_lock("sidecar.session")

    # ----------------------------------------------------------- syncing ----
    # One SyncState request applies ATOMICALLY with respect to rounds: the
    # session lock is held across all its parts, so a concurrent
    # ScheduleRound can never see (say) this request's jobs against the
    # executor set the same request replaces.

    def apply_sync(
        self,
        jobs: Sequence = (),
        deletes: Sequence[str] = (),
        executors: Optional[Sequence[ExecutorSnapshot]] = None,
        queues: Optional[Sequence[Queue]] = None,
        bids: Optional[dict] = None,
        trace_id: str = "",
    ) -> None:
        # The caller's cycle is sync + round: the sync half gets its own
        # ring entry (kind "sync") under the caller's trace id so the two
        # stitch by id in a dump (perfbench's sync_s and round spans read
        # the split from exactly these entries).
        with _trace().cycle(
            "sidecar_sync",
            trace_id=trace_id,
            kind="sync",
            jobs=len(jobs),
            deletes=len(deletes),
        ):
            self._apply_sync_locked(jobs, deletes, executors, queues, bids)

    @contextlib.contextmanager
    def _locked(self):
        """The session lock; the wait for it is a span of the open cycle
        (a sync queued behind a round, or the reverse, shows as such)."""
        with _trace().span("session_lock_wait"):
            self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()

    def _apply_sync_locked(
        self, jobs, deletes, executors, queues, bids
    ) -> None:
        # One span per boundary per request, never one per job: the counts
        # ride as span args (perfbench/layers/ reads these by name).
        trace = _trace()
        with self._locked():
            if jobs or deletes:
                txn = self.jobdb.write_txn()
                if deletes:
                    txn.delete(list(deletes))
                if jobs:
                    # the conversion AND the terminal bookkeeping of the
                    # request's jobs: one pass over the messages
                    with trace.span("job_from_state", n=len(jobs)) as span:
                        converted, spec_hits = _jobs_from_states(
                            jobs, self._spec_templates, self._terminal_synced
                        )
                        span.annotate(
                            templates=len(self._spec_templates),
                            spec_hits=spec_hits,
                        )
                    with trace.span("mirror_upsert", n=len(converted)):
                        txn.upsert(converted)
                for jid in deletes:
                    self._terminal_synced.pop(jid, None)
                # the commit publishes to the mirror's indexes (mirror_index)
                # and fires the feed's subscription (feed_apply with its
                # submit_many / remove_many / lease_many nest in here)
                with trace.span("mirror_commit"):
                    txn.commit()
                if (
                    self.feed is not None
                    and pipeline_enabled()
                    and prefetch_worthwhile()
                ):
                    # Shadow-pipeline stage (b): the commit just landed these
                    # caller-asserted rows in the builders -- start their
                    # slab upload NOW, so the transfer overlaps the
                    # rest of the sync and the next round's assemble instead
                    # of serializing inside its device apply.  Best-effort:
                    # the mirror COMMITTED, so a device error here must not
                    # fail the sync (the caller would wrongly retry state
                    # that applied); the rows just ride the next bundle.
                    try:
                        self.feed.prefetch_content()
                    except Exception:
                        _log.warning(
                            "sync content prefetch failed", exc_info=True
                        )
            if executors is not None:
                self.executors = list(executors)
            if queues is not None:
                self.queues = list(queues)
            if bids is not None:
                self.bids.update(bids)

    def sync_jobs(self, jobs: Sequence, deletes: Sequence[str] = ()) -> None:
        self.apply_sync(jobs=jobs, deletes=deletes)

    def set_executors(self, executors: Sequence[ExecutorSnapshot]) -> None:
        self.apply_sync(executors=executors)

    def set_queues(self, queues: Sequence[Queue]) -> None:
        self.apply_sync(queues=queues)

    def set_bids(self, prices: dict) -> None:
        self.apply_sync(bids=prices)

    # ------------------------------------------------------------ rounds ----

    def schedule_round(
        self,
        now_ns: Optional[int] = None,
        quarantined=frozenset(),
        trace_id: str = "",
    ) -> SchedulerResult:
        from armada_tpu.core.watchdog import supervisor
        from armada_tpu.ops.metrics import mono_now
        from armada_tpu.scheduler.slo import recorder as slo_recorder

        t_start = mono_now()
        sup0 = supervisor()
        fallbacks0 = sup0.snapshot()["fallbacks"]
        degraded0 = sup0.degraded
        # The round's cycle trace carries the CALLER's trace id when one
        # arrived over the gRPC metadata (rpc/server.py): the caller grafts
        # the returned spans under its RPC span, yielding one stitched
        # cross-process tree (tests/test_trace.py pins it).
        trace = _trace()
        with trace.cycle(
            "sidecar_round", trace_id=trace_id, kind="round", session=self.id
        ), self._locked():
            txn = self.jobdb.write_txn()
            now = now_ns or self._clock_ns()

            def sweep():
                # Sweep synced terminal jobs once they leave the short-job
                # penalty window (immediately when no penalty is
                # configured): only ids from _terminal_synced, O(tracked),
                # never a backlog scan.  Decision-independent (terminal
                # jobs can neither schedule nor preempt, and builders only
                # see txn deletes at commit), so the pipelined round runs
                # it in the kernel shadow; final mirror state is identical
                # either way (tests/test_pipeline.py).
                with trace.span(
                    "sweep", tracked=len(self._terminal_synced)
                ):
                    window = int(
                        max(
                            self.config.short_job_penalty_cutoffs().values(),
                            default=0.0,
                        )
                        * 1e9
                    )
                    expired = [
                        jid
                        for jid, ns in self._terminal_synced.items()
                        if ns == 0 or now - ns >= window
                    ]
                    if expired:
                        txn.delete(expired)
                        for jid in expired:
                            self._terminal_synced.pop(jid, None)

            pipelined = pipeline_enabled()
            result = self.algo.schedule(
                txn,
                self.executors,
                now_ns=now_ns or None,
                quarantined_nodes=frozenset(quarantined),
                shadow_work=[sweep] if pipelined else None,
            )
            if not pipelined:
                sweep()
            # Commit the mirror like the in-process scheduler commits its
            # jobDb: later rounds must see this round's leases.  The caller
            # re-asserting job state via SyncState is idempotent on top.
            with trace.span("mirror_commit"):
                txn.commit()
            # Sidecar rounds feed the same streaming cycle-latency SLO as
            # the in-process scheduler (TTFL/ingest-lag stay caller-side:
            # the caller owns submit timing across the boundary).  Degraded
            # = before OR fallback-delta OR after: a drill-speed re-probe
            # can promote back before the failed-over round returns, and a
            # promotion can land mid-round (scheduler.cycle's rule).
            with trace.span("slo_feed"):
                sup = supervisor()
                slo_recorder().observe_cycle(
                    mono_now() - t_start,
                    degraded=degraded0
                    or sup.degraded
                    or sup.snapshot()["fallbacks"] > fallbacks0,
                )
                # Per-pool round latency rides the same recorder (round
                # 17): the algo stamps each PoolStats with its round seconds
                # + the per-round fallback-delta degraded flag.
                for ps in result.pools:
                    if ps.round_s:
                        slo_recorder().observe_pool_round(
                            ps.pool, ps.round_s, degraded=ps.degraded
                        )
            # The response's stats JSON is encoded HERE, inside the round's
            # trace, so that its cost (it grows with the queue count once
            # queue_stats is collected) is a span of the cycle and not
            # untraced time between the root and the wire.
            with trace.span("stats_encode", pools=len(result.pools)) as enc:
                result.stats_json = _stats_of(result)
                enc.annotate(bytes=len(result.stats_json))
            return result


def _stats_of(result: SchedulerResult, trace: Optional[dict] = None) -> str:
    pools = []
    for s in result.pools:
        entry = {
            "pool": s.pool,
            "num_nodes": s.num_nodes,
            "num_queued": s.num_queued,
            "num_running": s.num_running,
            "scheduled": len(s.outcome.scheduled),
            "preempted": len(s.outcome.preempted),
            "termination": s.outcome.termination,
            "iterations": s.outcome.num_iterations,
            # trips of the placement loop; == iterations
            "kernel_iters": getattr(s.outcome, "kernel_iters", 0),
            # of those, the trips that gathered the whole skip window again
            "window_refills": getattr(s.outcome, "window_refills", 0),
            "queue_stats": s.outcome.queue_stats,
            # queues, queues_padded, queues_pending, queues_scheduled and,
            # where queue_stats is collected, fair_share_iterations
            **s.outcome.queue_axis,
            # assemble_rows_rebuilt, id_bytes_copied (the slab path)
            **s.outcome.assemble,
        }
        if s.market:
            entry["indicative_prices"] = s.indicative_prices
            entry["idealised_values"] = s.idealised_values
            entry["realised_values"] = s.realised_values
        pools.append(entry)
    # Degradation state rides the stats JSON so an EXTERNAL control plane
    # (the sidecar's whole audience) sees a CPU-failover round without
    # scraping this process's /healthz: backend, consecutive failures,
    # last fallback reason (core/watchdog).
    from armada_tpu.core.watchdog import supervisor
    from armada_tpu.scheduler.slo import recorder as slo_recorder

    doc = {
        "pools": pools,
        "device": supervisor().snapshot(),
        # Streaming SLO percentiles (cycle latency split healthy/
        # degraded): the external control plane reads its scheduling
        # tail latency from the same response it already parses.
        "slo": slo_recorder().snapshot(),
    }
    if trace is not None:
        # The round's span tree (offset form, ops/trace.Span.to_dict): the
        # caller grafts it under its RPC span for one stitched timeline.
        doc["trace"] = trace
    return json.dumps(doc, default=float)


class ScheduleSidecar:
    """Session registry behind the armada_tpu.api.Schedule service."""

    def __init__(self, default_config: SchedulingConfig, clock_ns=None):
        self.default_config = default_config
        self._clock_ns = clock_ns or (lambda: int(time.time() * 1e9))
        self._sessions: dict[str, ScheduleSession] = {}
        self._lock = make_lock("sidecar.service")

    def create_session(
        self, session_id: str = "", config_yaml: str = ""
    ) -> str:
        config = self.default_config
        if config_yaml:
            import yaml

            from armada_tpu.core.config import scheduling_config_from_dict

            try:
                doc = yaml.safe_load(config_yaml) or {}
                if "scheduling" in doc:
                    doc = doc["scheduling"]
                config = scheduling_config_from_dict(doc)
            except (yaml.YAMLError, TypeError, KeyError) as e:
                # caller data -> INVALID_ARGUMENT, never a server traceback
                raise ValueError(f"bad session config_yaml: {e}") from e
        sid = session_id or uuid.uuid4().hex
        # Construct outside the registry lock (JobDb + feed + algo setup is
        # not instant; other sessions' lookups must not stall behind it),
        # then publish under it.
        session = ScheduleSession(sid, config, clock_ns=self._clock_ns)
        with self._lock:
            if sid in self._sessions:
                raise SessionExists(sid)
            self._sessions[sid] = session
        return sid

    def session(self, session_id: str) -> ScheduleSession:
        with self._lock:
            s = self._sessions.get(session_id)
        if s is None:
            raise UnknownSession(session_id)
        return s

    def close_session(self, session_id: str) -> None:
        with self._lock:
            self._sessions.pop(session_id, None)

    # ----------------------------------------------------- wire handling ----
    # (proto-level entry points used by the gRPC service; kept here so the
    # service class in rpc/server.py stays a thin auth + status-code shim)

    def handle_sync(self, msg, trace_id: str = "") -> None:
        s = self.session(msg.session_id)
        executors = None
        if msg.set_executors:
            from armada_tpu.rpc.convert import snapshot_from_proto

            executors = [
                snapshot_from_proto(e, s.factory) for e in msg.executors
            ]
        queues = None
        if msg.set_queues:
            queues = [Queue(q.name, q.weight or 1.0) for q in msg.queues]
        bids = None
        if msg.set_bids:
            bids = {}
            for qb in msg.bids.queues:
                for bid in qb.bids:
                    bids[(qb.queue, bid.band, bid.pool)] = bid.price
        s.apply_sync(
            jobs=list(msg.jobs),
            deletes=list(msg.deleted_job_ids),
            executors=executors,
            queues=queues,
            bids=bids,
            trace_id=trace_id,
        )

    def handle_round(self, msg, trace_id: str = ""):
        from armada_tpu.rpc import rpc_pb2 as pb

        s = self.session(msg.session_id)
        result = s.schedule_round(
            now_ns=int(msg.now_ns) or None,
            quarantined=frozenset(msg.quarantined_node_ids),
            trace_id=trace_id,
        )
        # The round's finished trace (it just closed): ship its span tree
        # back only when the caller ASKED to stitch (sent a trace id) --
        # an untraced caller pays zero response bytes for it.
        trace_doc = None
        if trace_id:
            for t in reversed(_trace().last()):
                if t.trace_id == trace_id and t.kind == "round":
                    d = t.root.to_dict(t.root.t0)
                    d.setdefault("args", {})["pid"] = t.pid
                    trace_doc = d
                    break
        # encoded inside the round's trace (schedule_round); only a caller
        # that stitches pays a second dump, with the span tree in it
        resp = pb.ScheduleRoundResponse(
            pool_stats_json=(
                _stats_of(result, trace=trace_doc)
                if trace_doc is not None
                else result.stats_json
            )
        )
        for job, run in result.scheduled:
            resp.scheduled.append(
                pb.RoundLease(
                    job_id=job.id,
                    run_id=run.id,
                    queue=job.queue,
                    node_id=run.node_id,
                    executor=run.executor,
                    pool=run.pool,
                    scheduled_at_priority=run.scheduled_at_priority or 0,
                    away=run.pool_scheduled_away,
                )
            )
        for job, run in result.preempted:
            resp.preempted.append(
                pb.RoundPreemption(job_id=job.id, run_id=run.id)
            )
        for jid in result.failed:
            if len(resp.failed_sample) >= FAILED_SAMPLE_CAP:
                break
            resp.failed_sample.append(jid)
        return resp
