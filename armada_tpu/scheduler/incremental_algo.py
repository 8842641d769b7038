"""Feed JobDb deltas into cycle-persistent incremental problem builders.

The reference's scheduler keeps its jobDb between cycles and only applies
event deltas (internal/scheduler/scheduler.go:240-246); the tensor analog is
models/incremental.IncrementalBuilder, and THIS module is the glue: a JobDb
commit subscriber that translates job-state changes into builder deltas, so
FairSchedulingAlgo can assemble a 1M-job pool problem in O(delta) Python +
O(G) numpy instead of re-reading a million Job objects every second.

Mapping (idempotent -- the same delta may arrive twice: once from the open
txn's overlay at schedule time and again at commit):

  queued+validated  -> submit(spec @ current priority, retry bans) per pool
  running           -> remove from backlogs; lease(run) on the run's pool
  terminal/deleted  -> remove + unlease everywhere

Away-pass candidates (jobs restricted to specific pools) are tracked in a
side set so the away rounds never need a full backlog scan.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from armada_tpu.core.config import SchedulingConfig
from armada_tpu.core.types import RunningJob
from armada_tpu.jobdb.job import Job
from armada_tpu.models.incremental import IncrementalBuilder
from armada_tpu.models.slab import DeviceDeltaCache
from armada_tpu.ops.trace import recorder as _trace


def new_device_cache() -> DeviceDeltaCache:
    """The feed's device-cache factory: a node-axis-sharded mesh cache when
    the mesh serving plane is armed (serve --mesh / ARMADA_MESH;
    parallel/serving.py), else the plain single-device DeviceDeltaCache.
    Consulted at EVERY cache (re)build -- feed init, resync, late pool
    discovery, and the watchdog/mesh reset hooks -- so a ladder step
    (degrade to a smaller mesh, CPU failover, re-promotion) re-shards the
    next full upload onto whatever the supervisor currently targets."""
    from armada_tpu.parallel.serving import mesh_serving

    if mesh_serving().enabled():
        from armada_tpu.parallel.mesh_slab import MeshDeviceDeltaCache

        return MeshDeviceDeltaCache()
    return DeviceDeltaCache()


class _Batch:
    """One commit's builder work, gathered job by job in commit order and
    handed to each builder in one call a kind (IncrementalProblemFeed._flush).

    gone     ids leaving every builder: deletes and terminal upserts
    leased   ids leaving every backlog because they run now
    ended    (id, pool where its run lives on, or None): every builder but
             that pool's drops the id's run, if it holds one
    submits  id -> spec at its current priority; bans: id -> banned nodes
    leases   pool -> the RunningJobs joining that pool's run table"""

    __slots__ = ("gone", "leased", "ended", "submits", "bans", "leases")

    def __init__(self):
        self.gone: list = []
        self.leased: list = []
        self.ended: list = []
        self.submits: dict = {}
        self.bans: dict = {}
        self.leases: dict = {}


class IncrementalProblemFeed:
    """Per-pool IncrementalBuilders + device caches, fed from JobDb commits.

    Market-driven pools ride the same tables, stored in (queue, band,
    submit, id) order; the per-cycle bid re-sort is a slice permutation
    inside the builder (models/incremental._market_perm).  The scheduling
    algo refreshes each market builder's `bid_price_of` before assembling
    (prices come from the provider, re-read every cycle).
    """

    def __init__(self, config: SchedulingConfig):
        self.config = config
        self.builders: dict[str, IncrementalBuilder] = {}
        self.devcaches: dict[str, DeviceDeltaCache] = {}
        # queued job ids with an explicit pools restriction: the away pass's
        # candidate set (scheduling_algo.go:216-283) without a backlog scan.
        self.pool_restricted: set[str] = set()
        # Pool-parallel certification sets (round 17): a queued job with NO
        # pools restriction sits in EVERY builder's backlog, and one listing
        # >= 2 pools sits in each of them -- either makes two pools' rounds
        # order-dependent (pool A scheduling it changes pool B's problem),
        # so the cycle must stay serial.  Both empty <=> every queued job
        # is restricted to exactly one pool <=> all backlogs are pairwise
        # disjoint <=> dispatching pool B before pool A's apply is
        # bit-neutral (pools_independent()).  Same lifecycle as
        # pool_restricted: queued adds, lease/terminal removes.
        self.unrestricted_queued: set[str] = set()
        self.multi_pool_queued: set[str] = set()
        # running gang membership: job id -> (pool, queue, gang id), so gang
        # domain pins can be forgotten when the run ends (else the
        # note_running_gang sets grow forever).
        self._gang_of: dict[str, tuple] = {}
        # Open-txn overlay registry: job id -> the exact (immutable) Job
        # instance already applied mid-txn via overlay(), plus overlaid
        # deletes.  The commit's subscriber re-fire passes the same
        # instances, so identity lets it skip the idempotent second apply --
        # which profiling showed was ~half the sidecar cycle's feed cost
        # (lease_many/remove_many/apply_job all ran twice per cycle, and the
        # per-pool overlay re-applied every earlier pool's upserts).  A job
        # re-upserted after its overlay is a NEW instance (jobdb Jobs are
        # immutable), so it misses the registry and re-applies correctly.
        self._overlaid: dict[str, Job] = {}
        self._overlaid_deletes: set[str] = set()
        self._jobdb = None
        # Builders must exist BEFORE the first delta arrives or it is lost --
        # the feed retains no job state of its own.  Configured pools are
        # eager; pools discovered later from node snapshots are backfilled
        # from the JobDb in builder_for.
        for p in config.pools:
            self.builders[p.name] = IncrementalBuilder(config, p.name)
            self.devcaches[p.name] = new_device_cache()
        # Device-loss resilience (core/watchdog): a backend transition
        # (failover to CPU, re-promotion to the device) must drop every
        # device-resident cache this feed owns.  Held weakly -- a closed
        # control plane's feed is garbage, not a leak in the hook registry.
        from armada_tpu.core.watchdog import add_reset_hook

        add_reset_hook(self.reset_device_state)

    def reset_device_state(self) -> None:
        """Drop device-resident problem state after a device loss or
        re-promotion: REPLACE each pool's DeviceDeltaCache and invalidate
        the builders' prefetch bookkeeping so already-shipped rows re-enter
        the next bundle.  Replacement, never mutation of the live object:
        this hook can fire from the RE-PROBE thread (promotion) while a
        round is mid-apply in the scheduler thread, and from a watchdog
        worker that unwedges later -- both still hold the OLD cache, which
        stays internally consistent and simply becomes garbage; every
        future cycle fetches the fresh cache, whose empty state forces the
        full-upload fallback to the supervisor's current backend.  Host
        tables are untouched."""
        for pool in list(self.devcaches):
            self.devcaches[pool] = new_device_cache()
        for b in self.builders.values():
            b.invalidate_prefetch()

    def attach(self, jobdb) -> None:
        self._jobdb = jobdb
        jobdb.subscribe(self.on_delta)
        # schedule() overlays the OPEN txn's buffer onto the builders; if
        # that txn aborts (publish failure, leadership fencing), builder
        # state has run ahead of the JobDb with nothing to correct it --
        # CLAUDE.md's "state only advances with a committed txn" invariant.
        # Aborts are rare, so the remedy is a full resync.
        jobdb.subscribe_abort(self.resync)

    def resync(self) -> None:
        """Discard all builder state and rebuild from committed JobDb state."""
        self.builders = {}
        self.devcaches = {}
        self.pool_restricted = set()
        self.unrestricted_queued = set()
        self.multi_pool_queued = set()
        self._gang_of = {}
        self._overlaid = {}
        self._overlaid_deletes = set()
        for p in self.config.pools:
            self.builders[p.name] = IncrementalBuilder(self.config, p.name)
            self.devcaches[p.name] = new_device_cache()
        if self._jobdb is not None:
            batch = _Batch()
            for job in self._jobdb.read_txn().all_jobs():
                self.apply_job(job, batch)
            self._flush(batch)

    def builder_for(self, pool: str, txn=None) -> Optional[IncrementalBuilder]:
        b = self.builders.get(pool)
        if b is None:
            b = IncrementalBuilder(self.config, pool)
            self.builders[pool] = b
            self.devcaches[pool] = new_device_cache()
            if txn is not None:
                # Late pool discovery (a node snapshot introduced a pool not
                # in config): one-time backfill scan.
                batch = _Batch()
                for job in txn.all_jobs():
                    self.apply_job(job, batch)
                self._flush(batch)
        return b

    def devcache_for(self, pool: str) -> DeviceDeltaCache:
        return self.devcaches[pool]

    def prefetch_content(self, skip_pool: str = None) -> int:
        """Shadow-pipeline stage (b): ship every builder's decision-
        independent dirty rows to its device cache now (see
        IncrementalBuilder.prefetch_content for the soundness boundary and
        skip conditions).  Called from a kernel shadow (the running pool is
        skipped -- its bundle already applied) or right after a commit so
        the upload overlaps the caller's inter-cycle work."""
        shipped = 0
        for pool, b in self.builders.items():
            if pool == skip_pool:
                continue
            cache = self.devcaches.get(pool)
            if cache is not None:
                shipped += b.prefetch_content(cache)
        return shipped

    # ------------------------------------------------------------ deltas ----

    def on_delta(self, upserts: dict, deletes: set) -> None:
        # The commit subscriber: skip anything overlay() already applied
        # within the committing txn, then drop the registry (it is only
        # meaningful inside that txn).
        self._apply_delta(upserts, deletes, record=False)
        self._overlaid.clear()
        self._overlaid_deletes.clear()

    def overlay(self, upserts: dict, deletes: set = frozenset()) -> None:
        """Mid-txn application (the schedule-time overlay of the OPEN txn's
        buffer): applies like on_delta but records each applied instance so
        neither a later per-pool overlay nor the commit re-fire pays for it
        again."""
        self._apply_delta(upserts, deletes, record=True)

    def _apply_delta(self, upserts: dict, deletes, record: bool) -> None:
        # A per-job table call is a binary search through numpy's dispatch
        # wrappers (remove / unlease) or one np.insert PER COLUMN (submit /
        # lease, O(table) each): a K-job commit would pay K of them a
        # builder.  So the commit is gathered into ONE batch -- the ids
        # that leave, the runs that ended, the submits, the leases -- and
        # _flush hands each builder one call a kind.  `terminal` counts the
        # ids (deletes and terminal upserts) that left through that pass.
        with _trace().span(
            "feed_apply",
            upserts=len(upserts),
            deletes=len(deletes),
            overlay=record,
        ) as span:
            batch = _Batch()
            for job_id in deletes:
                if job_id in self._overlaid_deletes:
                    continue
                if record:
                    self._overlaid_deletes.add(job_id)
                self._gone(job_id, batch)
            overlaid = self._overlaid
            for job in upserts.values():
                if overlaid.get(job.id) is job:
                    continue
                if record:
                    overlaid[job.id] = job
                self.apply_job(job, batch)
            span.annotate(terminal=len(batch.gone))
            self._flush(batch)

    def _flush(self, batch: _Batch) -> None:
        # Per-op spans (remove_many/unlease_many/submit_many/lease_many)
        # live inside the builder methods themselves, so the trace
        # attributes this cost wherever the feed runs -- serve, sidecar, or
        # bench.  Order a builder: ids out of the backlog and ended runs out
        # of the run table first (their slab slots return to the free lists
        # in the batch's order), then the submits and leases that reuse
        # them.
        leaving = batch.gone + batch.leased
        for pool, b in self.builders.items():
            if leaving:
                # one table pass + one demand update for a commit's
                # terminal jobs and a round's ~1k scheduled jobs alike
                b.remove_many(leaving)
            ended = [j for j, stays in batch.ended if stays != pool]
            if ended:
                b.unlease_many(ended)
            if batch.submits:
                b.submit_many(
                    list(batch.submits.values()), batch.bans or None
                )
            leases = batch.leases.get(pool)
            if leases:
                b.lease_many(leases)

    def _gone(self, job_id: str, batch: _Batch) -> None:
        """A deleted or terminal job: out of the feed's own sets now, out of
        every builder's backlog and run table at the flush."""
        self.pool_restricted.discard(job_id)
        self.unrestricted_queued.discard(job_id)
        self.multi_pool_queued.discard(job_id)
        batch.gone.append(job_id)
        batch.ended.append((job_id, None))
        self._forget_gang(job_id)

    def _forget_gang(self, job_id: str) -> None:
        entry = self._gang_of.pop(job_id, None)
        if entry is not None:
            pool, queue, gang_id = entry
            b = self.builders.get(pool)
            if b is not None:
                b.forget_running_gang(queue, gang_id, job_id)

    def apply_job(self, job: Job, batch: Optional[_Batch] = None) -> None:
        """Translate one job's state into builder deltas.  Nothing touches a
        builder's tables here: the job is classified into `batch` (what
        leaves, whose run ended, submits, leases), which the caller flushes
        once per builder, or which flushes inline -- a batch of one, the
        same code -- when called one-shot.  A batch holds an id once: a
        commit's upserts are keyed by id, as the JobDb's jobs are."""
        flush_here = batch is None
        if batch is None:
            batch = _Batch()
        if job.in_terminal_state():
            self._gone(job.id, batch)
        elif job.queued:
            if not job.validated:
                return
            pools = job.pools or job.spec.pools
            if job.priority == job.spec.priority and pools == job.spec.pools:
                spec = job.spec
            else:
                spec = dataclasses.replace(
                    job.spec, priority=job.priority, pools=pools
                )
            bans = job.anti_affinity_nodes()
            if spec.pools:
                self.pool_restricted.add(job.id)
                self.unrestricted_queued.discard(job.id)
                if len(spec.pools) >= 2:
                    self.multi_pool_queued.add(job.id)
                else:
                    self.multi_pool_queued.discard(job.id)
            else:
                self.pool_restricted.discard(job.id)
                self.multi_pool_queued.discard(job.id)
                self.unrestricted_queued.add(job.id)
            # a requeued job's run ended; a fresh submit never had one
            batch.ended.append((job.id, None))
            batch.submits[spec.id] = spec
            if bans:
                batch.bans[spec.id] = tuple(bans)
        else:
            self._leased(job, batch)
        if flush_here:
            self._flush(batch)

    def _leased(self, job: Job, batch: _Batch) -> None:
        """A leased or running job: out of every backlog, and into the run
        table of its run's pool while that run lives."""
        self.pool_restricted.discard(job.id)
        self.unrestricted_queued.discard(job.id)
        self.multi_pool_queued.discard(job.id)
        batch.leased.append(job.id)
        run = job.latest_run
        if run is None or run.in_terminal_state():
            batch.ended.append((job.id, None))
            self._forget_gang(job.id)
            return
        pool = run.pool or "default"
        # the run lives on in its own pool's table (lease_many replaces the
        # row); any other builder's row for this job is a run that ended
        batch.ended.append((job.id, pool))
        # Existing builders only: creating one here would skip builder_for's
        # one-time JobDb backfill and permanently hide the queued backlog
        # from a late-discovered pool (the algo creates builders WITH a txn).
        b = self.builders.get(pool)
        if b is None:
            return
        batch.leases.setdefault(pool, []).append(
            RunningJob(
                job=(
                    job.spec
                    if job.priority == job.spec.priority
                    else dataclasses.replace(job.spec, priority=job.priority)
                ),
                node_id=run.node_id,
                priority=run.scheduled_at_priority or 0,
                away=run.pool_scheduled_away,
            )
        )
        if job.spec.gang_id:
            b.note_running_gang(job.queue, job.spec.gang_id, job.id)
            self._gang_of[job.id] = (pool, job.queue, job.spec.gang_id)

    # ------------------------------------------------------------ queries ---

    def pools_independent(self) -> bool:
        """Every queued job restricted to exactly ONE pool -- all builders'
        backlogs pairwise disjoint, so the pools' rounds commute: pool A's
        apply only removes ids pool B never held (its overlay is a no-op on
        B's tables) and preemptions only touch A's own run table.  The
        pool-parallel cycle (scheduler/algo.py) requires this to dispatch
        pool B before pool A's decisions land; two O(1) set checks per
        cycle."""
        return not self.unrestricted_queued and not self.multi_pool_queued

    def running_of(self, pool: str, txn) -> list[RunningJob]:
        """RunningJob views of the pool's leased set, reconstructed from the
        builder's run table + txn specs -- for the away rounds, which go
        through the per-cycle builder and need host objects.  O(runs in
        pool), not O(all jobs)."""
        b = self.builders.get(pool)
        if b is None:
            return []
        out = []
        for row in b.runs.live_rows():
            jid = b.runs.ids[row].tobytes().rstrip(b"\0").decode()
            job = txn.get(jid)
            if job is None:
                continue
            run = job.latest_run
            if run is None or run.in_terminal_state():
                continue
            out.append(
                RunningJob(
                    job=dataclasses.replace(job.spec, priority=job.priority),
                    node_id=run.node_id,
                    priority=run.scheduled_at_priority or 0,
                    away=run.pool_scheduled_away,
                )
            )
        return out

    def away_candidates(self, txn) -> list:
        """Still-queued specs with an explicit pools restriction."""
        out = []
        for jid in sorted(self.pool_restricted):
            job = txn.get(jid)
            if job is None or not job.queued or not job.validated:
                continue
            out.append(
                dataclasses.replace(
                    job.spec,
                    priority=job.priority,
                    pools=job.pools or job.spec.pools,
                )
            )
        return out
