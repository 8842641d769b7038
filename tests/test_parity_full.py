"""Full-semantics placement parity: kernel vs an independent sequential
oracle covering the phases the singleton oracle (test_parity.py) skips --
fair-share eviction + reschedule + preemption, gang all-or-nothing
placement, home/away level preemption with oversubscription repair, and
market bid ordering (VERDICT round-2 "what's weak" #2; reference semantics:
preempting_queue_scheduler.go:108-300, queue_scheduler.go:87-270,
gang_scheduler.go:100-247, market_iterator.go:245).

The oracle shares NO code with the kernel: it walks plain dicts one gang at
a time.  Properties asserted per random world (>=20 seeds, hundreds of
nodes): identical scheduled JOB sets, identical preempted run sets, and
identical per-queue scheduled counts (node ids may differ only on exact
score ties; submit times are unique to keep ordering deterministic).

Eviction coverage spans the whole protected-fraction range: 0.0 (any usage
evicts every preemptible run), INTERMEDIATE fractions (the reference's
production shape -- the oracle independently reimplements the water-filling
fair-share redistribution of context/scheduling.go updateFairShares and the
pqs.go:146-156 gate, cross-checking the kernel's ops/fairness.fair_shares),
and high (no eviction).  Per-(queue, pc) allocation caps
(maximumResourceFractionPerQueue) are modeled too: the gate runs BEFORE the
fit check, a trip does not place the candidate and KILLS the queue for the
round (new candidates stop, evictees keep re-placing) -- the semantics
tests/test_market_columnar.py pinned for the mega round, now cross-checked
for the real round against this oracle.
"""

import numpy as np
import pytest

from armada_tpu.core.config import PoolConfig, PriorityClass, SchedulingConfig
from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob
from armada_tpu.models import run_scheduling_round

CFG = SchedulingConfig(
    shape_bucket=32,
    priority_classes={
        "low": PriorityClass("low", priority=100, preemptible=True),
        "high": PriorityClass("high", priority=1000, preemptible=False),
    },
    default_priority_class="high",
    protected_fraction_of_fair_share=1e9,  # no fair-share eviction by default
)
F = CFG.resource_list_factory()
RES = list(F.names)


def cap_units(spec_res):
    """Node capacity in the factory's floored resolution units -- the same
    quantisation the problem builder applies (floor for capacity), which
    keeps every score/cost a small exact dyadic in f32."""
    return np.asarray(F.floor_units(spec_res.atoms), dtype=float)


def req_units(spec_res):
    """Request in ceiled resolution units (builder: ceil for requests)."""
    return np.asarray(F.ceil_units(spec_res.atoms), dtype=float)


# --- the oracle --------------------------------------------------------------


class _Oracle:
    """Sequential greedy re-implementation of the round semantics."""

    def __init__(self, config, nodes, queues, jobs, running, prices=None):
        self.config = config
        self.market = prices is not None
        self.prices = prices or {}
        ladder = sorted({pc.priority for pc in config.priority_classes.values()})
        self.level_of = {p: i + 2 for i, p in enumerate(ladder)}
        self.num_levels = len(ladder) + 2
        self.nodes = [n for n in nodes]
        self.node_idx = {n.id: i for i, n in enumerate(nodes)}
        self.total = {n.id: cap_units(n.total_resources) for n in nodes}
        # usage[node_id][level] = summed request vectors bound at that level
        self.usage = {
            n.id: [np.zeros(len(RES)) for _ in range(self.num_levels)]
            for n in nodes
        }
        self.queues = {q.name: q for q in queues}
        self.qorder = sorted(self.queues)
        self.alloc = {q.name: np.zeros(len(RES)) for q in queues}
        self.total_pool = (
            sum(self.total.values()) if nodes else np.zeros(len(RES))
        )
        scale = (
            np.maximum.reduce([self.total[n.id] for n in nodes])
            if nodes
            else np.ones(len(RES))
        )
        # same arithmetic as the problem builder: f64 reciprocal, cast f32
        scale32 = scale.astype(np.float32)
        self.inv_scale32 = np.where(
            scale32 > 0, 1.0 / np.maximum(scale32, 1e-9), 0.0
        ).astype(np.float32)
        self.drf32 = np.array(
            [
                1.0 if name in config.dominant_resource_fairness_resources else 0.0
                for name in RES
            ],
            np.float32,
        )
        self.jobs = list(jobs)
        self.running = list(running)
        # per-(queue, pc) allocation + the f32 cap thresholds
        # (maximumResourceFractionPerQueue, constraints.go): the gate
        # compares f32, but unit-quantised requests are integral so the
        # running sums stay exact; only the THRESHOLD rounds (frac x f32
        # total, transcribed from the config semantics, not the builder).
        # RESTRICTION: the threshold derives from NODE capacity only --
        # floating totals join total_pool for caps in the builder
        # (problem.py:1026-1041), so cap worlds with floating resources
        # would need float totals added here first.
        self.alloc_pc = {
            q.name: {pc: np.zeros(len(RES)) for pc in config.priority_classes}
            for q in queues
        }
        self.pc_cap = {}
        tp32 = self.total_pool.astype(np.float32)
        for pc_name, pc in config.priority_classes.items():
            cap = np.full(len(RES), np.inf, np.float32)
            for rname, frac in pc.maximum_resource_fraction_per_queue.items():
                if rname in RES:
                    cap[RES.index(rname)] = np.float32(frac * tp32[RES.index(rname)])
            self.pc_cap[pc_name] = cap
        for r in running:
            lvl = self._run_level(r)
            self.usage[r.node_id][lvl] += req_units(r.job.resources)
            self.alloc[r.job.queue] += req_units(r.job.resources)
            self.alloc_pc[r.job.queue][r.job.priority_class] += req_units(
                r.job.resources
            )

    def _run_level(self, r: RunningJob) -> int:
        if r.away:
            return 1
        pc = self.config.priority_class(r.job.priority_class)
        return self.level_of[pc.priority]

    def _allocatable(self, nid: str, level: int) -> np.ndarray:
        u = self.usage[nid]
        return self.total[nid] - sum(u[lv] for lv in range(level, self.num_levels))

    def _cost(self, qname: str, extra: np.ndarray) -> float:
        # float32 like the kernel: scores/costs are the only inexact
        # quantities, and x64 would break near-ties the other way (the
        # integral capacity/fit arithmetic is exact in either precision).
        alloc32 = (self.alloc[qname] + extra).astype(np.float32)
        total32 = self.total_pool.astype(np.float32)
        with np.errstate(divide="ignore", invalid="ignore"):
            frac = np.where(
                total32 > 0, alloc32 / np.maximum(total32, np.float32(1e-9)), 0.0
            ).astype(np.float32)
        cost = np.float32(max(np.float32(0.0), (frac * self.drf32).max()))
        return float(cost / np.float32(self.queues[qname].weight))

    def _score(self, nid: str, level: int) -> float:
        free32 = self._allocatable(nid, level).astype(np.float32)
        return float((free32 * self.inv_scale32).sum(dtype=np.float32))

    # --- protected fair share (pqs.go:146-156 + scheduling.go:220-300) ------
    def _water_fill_shares(self, weights, cds, max_iterations=10):
        """Scalar per-queue transcription of the REFERENCE's updateFairShares
        loop (context/scheduling.go:220-300): queues capped at their
        constrained demand re-share spare capacity by weight until it is
        gone.  Structured after the Go per-queue loops -- NOT after the
        kernel's vectorized ops/fairness op -- so a transcription error in
        the kernel cannot hide here.  f32 scalars because scores/costs are
        f32-canonical everywhere (this file's parity discipline); the
        gate consumes only fair_share and the demand-capped share."""
        f = np.float32
        qs = [
            {"w": f(w), "cds": f(c), "dcafs": f(0.0), "achieved": False}
            for w, c in zip(weights, cds)
        ]
        weight_sum = f(0.0)
        for q in qs:
            weight_sum = f(weight_sum + q["w"])
        fair_share = np.array(
            [f(q["w"] / weight_sum) if weight_sum > 0 else f(0.0) for q in qs],
            np.float32,
        )
        unallocated = f(1.0)  # proportion of the cluster shared each pass
        for _ in range(max_iterations):
            if not (unallocated > 0.01):
                break
            total_weight = f(0.0)
            for q in qs:
                if not q["achieved"]:
                    total_weight = f(total_weight + q["w"])
            if total_weight <= 0.0:
                break
            for q in qs:
                if not q["achieved"]:
                    q["dcafs"] = f(
                        q["dcafs"] + f(q["w"] / total_weight) * unallocated
                    )
            unallocated = f(0.0)
            for q in qs:
                spare = f(q["dcafs"] - q["cds"])
                if spare > 0:
                    q["dcafs"] = q["cds"]
                    q["achieved"] = True
                    unallocated = f(unallocated + spare)
        return fair_share, np.array([q["dcafs"] for q in qs], np.float32)

    def _protected_over(self) -> dict:
        """queue -> 'allocation exceeds protected fraction of fair share'
        (the eviction gate).  Demand/shares follow the reference: constrained
        demand = queued + running request sums capped at the pool total; the
        fair share each queue is measured against is
        max(demand-capped-adjusted, plain weight share)."""
        cfg = self.config
        assert not any(
            pc.maximum_resource_fraction_per_queue
            for pc in cfg.priority_classes.values()
        ), "oracle does not model per-(queue,pc) demand caps"
        qnames = self.qorder
        w = np.array(
            [self.queues[q].weight for q in qnames], np.float32
        )
        demand = {q: np.zeros(len(RES), np.float64) for q in qnames}
        for j in self.jobs:
            if j.queue in demand:
                demand[j.queue] += req_units(j.resources).astype(np.float64)
        for r in self.running:
            if r.job.queue in demand:
                demand[r.job.queue] += req_units(r.job.resources).astype(
                    np.float64
                )
        total64 = self.total_pool.astype(np.float64)
        cds = np.zeros(len(qnames), np.float32)
        for i, q in enumerate(qnames):
            capped = np.minimum(demand[q], total64)
            with np.errstate(divide="ignore", invalid="ignore"):
                frac = np.where(total64 > 0, capped / np.maximum(total64, 1e-9), 0.0)
            cds[i] = max(0.0, float((frac * self.drf32.astype(np.float64)).max()))
        fair_share, dcafs = self._water_fill_shares(w, cds)
        out = {}
        for i, q in enumerate(qnames):
            # unweighted DRF cost of the CURRENT allocation (f32, kernel's
            # unweighted_drf_cost arithmetic)
            alloc32 = self.alloc[q].astype(np.float32)
            total32 = self.total_pool.astype(np.float32)
            frac32 = (
                np.where(total32 > 0, alloc32 / np.where(total32 > 0, total32, 1), 0)
                .astype(np.float32)
                * self.drf32
            )
            actual = np.float32(max(np.float32(0), frac32.max()))
            fairsh = np.float32(max(dcafs[i], fair_share[i]))
            frac = actual / fairsh if fairsh > 0 else np.inf
            out[q] = bool(
                frac > np.float32(cfg.protected_fraction_of_fair_share)
                and w[i] > 0
            )
        return out

    def run(self):
        cfg = self.config
        # --- phase A: fair-share eviction (pqs.go:117-160) -------------------
        # The water-fill (and its no-per-(queue,pc)-caps assert) only runs
        # when the gate can conceivably trip: actual/fairsh is bounded by
        # ~1/min_fair_share, so a sentinel-huge protected fraction (the
        # default CFG's 1e9) means no queue ever evicts.
        if cfg.protected_fraction_of_fair_share < 1e6:
            over_by_queue = self._protected_over()
        else:
            over_by_queue = {}
        evicted = []  # list of (RunningJob, level)
        for r in self.running:
            pc = cfg.priority_class(r.job.priority_class)
            preemptible = True if r.away else pc.preemptible
            over = over_by_queue.get(r.job.queue, False)
            if preemptible and over:
                lvl = self._run_level(r)
                req = req_units(r.job.resources)
                self.usage[r.node_id][lvl] -= req
                self.usage[r.node_id][0] += req  # evicted marker
                self.alloc[r.job.queue] -= req
                self.alloc_pc[r.job.queue][r.job.priority_class] -= req
                evicted.append((r, lvl))

        # --- candidate streams per queue -------------------------------------
        def qkey(j):
            pc = cfg.priority_class(j.priority_class)
            return (-pc.priority, j.priority, j.submit_time, j.id)

        # gangs group into one unit (uniform members; lead = sort-first)
        by_gang, singles = {}, []
        for j in self.jobs:
            if j.gang_id:
                by_gang.setdefault((j.queue, j.gang_id), []).append(j)
            else:
                singles.append(j)
        units = []
        for members in by_gang.values():
            members.sort(key=qkey)
            units.append((members[0], members))
        for j in singles:
            units.append((j, [j]))
        per_queue = {q: [] for q in self.queues}
        for lead, members in units:
            per_queue[lead.queue].append((qkey(lead), "new", lead, members))
        for r, lvl in evicted:
            pc = cfg.priority_class(r.job.priority_class)
            ladder_prio = (
                sorted({p.priority for p in cfg.priority_classes.values()})[
                    max(lvl - 2, 0)
                ]
            )
            per_queue[r.job.queue].append(
                (
                    (-ladder_prio, r.job.priority, r.job.submit_time, r.job.id),
                    "evictee",
                    r,
                    lvl,
                )
            )
        for q in per_queue:
            # evictees precede queued units of the same queue (incremental.py
            # gq layout); both sub-streams sort by their own keys
            ev = sorted([e for e in per_queue[q] if e[1] == "evictee"])
            new = sorted([e for e in per_queue[q] if e[1] == "new"])
            per_queue[q] = ev + new
        heads = {q: 0 for q in self.queues}

        scheduled = {}
        rescheduled = set()
        dead_keys = set()
        sched_members = 0
        burst = cfg.maximum_scheduling_burst
        perq_burst = cfg.maximum_per_queue_scheduling_burst
        q_sched = {q: 0 for q in self.queues}
        q_blocked = set()
        new_blocked = False

        def job_key(j):
            pc = cfg.priority_class(j.priority_class)
            return (
                tuple(req_units(j.resources)),
                tuple(sorted(j.node_selector.items())),
                pc.name,
                # the type axis is part of key identity (core/keys lesson:
                # a type-sensitive job must never retire/share a class with
                # an insensitive twin)
                tuple(j.node_type_scores),
            )

        def fit_nodes(req, level, card, clean, tscores=()):
            """(feasible, [(node_id, count)]): best-fit spread at `level`
            against clean (level-0) or urgency allocatable.  A nonempty
            type-score map is a whitelist (nodes of unnamed/<=0 types are
            infeasible) and biases the packing score by
            (1/throughput - 1) * 1024 per admitted node -- an independent
            transcription of the Gavel-style semantics, in the same f32
            arithmetic the kernel's precomputed bias tables use."""
            fit_level = 0 if clean else level
            thr_of = dict(tscores)
            caps = []
            for n in self.nodes:
                if tscores:
                    thr = thr_of.get(n.node_type)
                    if thr is None or thr <= 0:
                        continue  # whitelist: type not admitted
                free = self._allocatable(n.id, fit_level)
                if np.all(free >= req):
                    per = int(
                        min(
                            np.floor(free[r] / req[r])
                            for r in range(len(RES))
                            if req[r] > 0
                        )
                        if np.any(req > 0)
                        else card
                    )
                    score = self._score(n.id, fit_level)
                    if tscores:
                        bias = np.float32(
                            (
                                np.float32(1.0) / np.float32(thr_of[n.node_type])
                                - np.float32(1.0)
                            )
                            * np.float32(1024.0)
                        )
                        score = float(np.float32(score) + bias)
                    caps.append((score, self.node_idx[n.id], n.id, min(per, card)))
            if sum(c[3] for c in caps) < card:
                return False, []
            caps.sort()
            out, left = [], card
            for _, _, nid, per in caps:
                take = min(per, left)
                out.append((nid, take))
                left -= take
                if left == 0:
                    break
            return True, out

        while True:
            candidates = []
            for q in self.qorder:
                lst = per_queue[q]
                while heads[q] < len(lst):
                    entry = lst[heads[q]]
                    if entry[1] == "new" and job_key(entry[2]) in dead_keys:
                        heads[q] += 1
                        continue
                    break
                if heads[q] >= len(lst):
                    continue
                entry = lst[heads[q]]
                if entry[1] == "new" and (new_blocked or q in q_blocked):
                    continue
                if entry[1] == "evictee":
                    req_tot = req_units(entry[2].job.resources)
                    price = self.prices.get(q, 0.0)
                else:
                    req_tot = req_units(entry[2].resources) * len(entry[3])
                    price = self.prices.get(q, 0.0)
                order = -price if self.market else self._cost(q, req_tot)
                candidates.append((order, q, entry))
            if not candidates:
                break
            candidates.sort(key=lambda c: (c[0], c[1]))
            _, q, entry = candidates[0]

            if entry[1] == "evictee":
                _, _, r, lvl = entry
                req = req_units(r.job.resources)
                free = self._allocatable(r.node_id, lvl)
                if np.all(free >= req):
                    self.usage[r.node_id][0] -= req
                    self.usage[r.node_id][lvl] += req
                    self.alloc[q] += req
                    self.alloc_pc[q][r.job.priority_class] += req
                    rescheduled.add(r.job.id)
                heads[q] += 1
                continue

            _, _, lead, members = entry
            card = len(members)
            req = req_units(lead.resources)
            # constraint gates (new jobs only)
            if sched_members + card > burst:
                new_blocked = True
                continue
            pc = cfg.priority_class(lead.priority_class)
            hit_q_cap = bool(
                np.any(
                    (self.alloc_pc[q][pc.name] + req * card).astype(np.float32)
                    > self.pc_cap[pc.name]
                )
            )
            if q_sched[q] + card > perq_burst or hit_q_cap:
                # per-queue gate (kernel gate_queue -> q_killed): the
                # tripping candidate does NOT place and the queue stops
                # producing NEW candidates; evictees keep re-placing.
                q_blocked.add(q)
                continue
            level = self.level_of[pc.priority]
            tscores = lead.node_type_scores
            feasible, spread = fit_nodes(req, level, card, clean=True,
                                         tscores=tscores)
            if not feasible:
                feasible, spread = fit_nodes(req, level, card, clean=False,
                                             tscores=tscores)
            if not feasible:
                if card == 1:
                    dead_keys.add(job_key(lead))
                heads[q] += 1
                continue
            mi = 0
            for nid, count in spread:
                for _ in range(count):
                    scheduled[members[mi].id] = nid
                    mi += 1
                self.usage[nid][level] += req * count
            self.alloc[q] += req * card
            self.alloc_pc[q][pc.name] += req * card
            sched_members += card
            q_sched[q] += card
            heads[q] += 1

        # --- phase B: oversubscription repair (eviction.go:130-180) ----------
        # The kernel flags every oversubscribed run from ONE snapshot of the
        # post-placement state and evicts them simultaneously; a sequential
        # walk would stop evicting once the first eviction clears the node.
        phase_a_ids = {e[0].job.id for e in evicted}
        flagged = []
        for r in self.running:
            if r.job.id in phase_a_ids and r.job.id not in rescheduled:
                continue  # no slot held: already evicted and not back
            pc = cfg.priority_class(r.job.priority_class)
            preemptible = True if r.away else pc.preemptible
            if not preemptible:
                continue
            lvl = self._run_level(r)
            if np.any(self._allocatable(r.node_id, lvl) < 0):
                flagged.append((r, lvl))
        over_evicted = []
        for r, lvl in flagged:
            req = req_units(r.job.resources)
            self.usage[r.node_id][lvl] -= req
            self.usage[r.node_id][0] += req
            self.alloc[r.job.queue] -= req
            self.alloc_pc[r.job.queue][r.job.priority_class] -= req
            rescheduled.discard(r.job.id)
            over_evicted.append((r, lvl))
        # pinned re-schedule fixed point (pqs.go:222-247): per iteration each
        # node admits its (cost, run-table-order) minimal fitting evictee --
        # the kernel breaks cost ties by run row index, whose table sorts on
        # (queue, evictee priority, job priority, submit, id).
        qidx = {q: i for i, q in enumerate(self.qorder)}
        ladder = sorted({p.priority for p in cfg.priority_classes.values()})

        def run_order(r, lvl):
            return (
                qidx[r.job.queue],
                -ladder[max(lvl - 2, 0)],
                r.job.priority,
                r.job.submit_time,
                r.job.id,
            )

        pending = list(over_evicted)
        progress = True
        while pending and progress:
            progress = False
            by_node = {}
            for r, lvl in pending:
                req = req_units(r.job.resources)
                if np.all(self._allocatable(r.node_id, lvl) >= req):
                    cand = (self._cost(r.job.queue, req), run_order(r, lvl), r, lvl)
                    cur = by_node.get(r.node_id)
                    if cur is None or cand[:2] < cur[:2]:
                        by_node[r.node_id] = cand
            for _, _, r, lvl in by_node.values():
                req = req_units(r.job.resources)
                self.usage[r.node_id][0] -= req
                self.usage[r.node_id][lvl] += req
                self.alloc[r.job.queue] += req
                self.alloc_pc[r.job.queue][r.job.priority_class] += req
                rescheduled.add(r.job.id)
                pending = [(p, pl) for p, pl in pending if p.job.id != r.job.id]
                progress = True

        preempted = set()
        for r, _ in evicted + over_evicted:
            if r.job.id not in rescheduled:
                preempted.add(r.job.id)
        return scheduled, preempted, rescheduled


# --- worlds ------------------------------------------------------------------


def world(seed, num_nodes=200, num_jobs=300, num_queues=5, gangs=6,
          num_running=40, away_frac=0.0):
    rng = np.random.default_rng(seed)
    nodes = [
        NodeSpec(
            id=f"n{i:04d}",
            pool="default",
            total_resources=F.from_mapping(
                {"cpu": int(rng.choice([8, 16, 32])), "memory": int(rng.choice([32, 64]))}
            ),
        )
        for i in range(num_nodes)
    ]
    queues = [Queue(f"q{i}", float(rng.choice([1.0, 2.0]))) for i in range(num_queues)]
    jobs = []
    t = 0.0
    for i in range(num_jobs):
        t += 0.001 + float(rng.random()) * 0.01  # unique submit times
        jobs.append(
            JobSpec(
                id=f"j{i:05d}",
                queue=f"q{int(rng.integers(num_queues))}",
                priority_class=str(rng.choice(["low", "high"])),
                submit_time=t,
                resources=F.from_mapping(
                    {"cpu": int(rng.choice([1, 2, 4])), "memory": int(rng.choice([2, 4]))}
                ),
            )
        )
    for g in range(gangs):
        t += 0.01
        card = int(rng.choice([2, 3, 4]))
        for m in range(card):
            jobs.append(
                JobSpec(
                    id=f"g{g}m{m}",
                    queue=f"q{int(rng.integers(num_queues))}"
                    if False
                    else f"q{g % num_queues}",
                    priority_class="high",
                    submit_time=t,
                    resources=F.from_mapping({"cpu": 2, "memory": 2}),
                    gang_id=f"gang{g}",
                    gang_cardinality=card,
                )
            )
    running = []
    for i in range(num_running):
        t += 0.01
        away = bool(rng.random() < away_frac)
        running.append(
            RunningJob(
                job=JobSpec(
                    id=f"r{i:04d}",
                    queue=f"q{int(rng.integers(num_queues))}",
                    priority_class="low" if (away or rng.random() < 0.7) else "high",
                    submit_time=-100.0 + t,
                    resources=F.from_mapping({"cpu": 2, "memory": 2}),
                ),
                node_id=f"n{int(rng.integers(num_nodes)):04d}",
                away=away,
            )
        )
    return nodes, queues, jobs, running


def hetero_world(seed, types=("v4", "v5e", "v6"), sensitive_frac=0.4, **kw):
    """world() re-dressed as a mixed fleet: nodes carry hardware types
    (plus some untyped ""), and a fraction of units -- gangs uniformly --
    carry per-type throughput maps, including the occasional map naming
    only a type the fleet lacks (whitelist-infeasible on both sides)."""
    import dataclasses

    nodes, queues, jobs, running = world(seed, **kw)
    rng = np.random.default_rng(seed + 77)
    pool = list(types) + [""]
    nodes = [
        dataclasses.replace(n, node_type=pool[int(rng.integers(len(pool)))])
        for n in nodes
    ]

    def draw_map():
        if rng.random() < 0.05:
            return (("v9", 2.0),)  # names no fleet type: never places
        k = 1 + int(rng.integers(len(types)))
        chosen = rng.choice(len(types), size=k, replace=False)
        return tuple(
            sorted(
                (types[int(c)], float(rng.choice([0.5, 1.0, 2.0, 4.0])))
                for c in chosen
            )
        )

    gang_maps: dict = {}
    out_jobs = []
    for j in jobs:
        if j.gang_id:
            # members must stay uniform (one key class per gang)
            if j.gang_id not in gang_maps:
                gang_maps[j.gang_id] = (
                    draw_map() if rng.random() < sensitive_frac else ()
                )
            ts = gang_maps[j.gang_id]
        else:
            ts = draw_map() if rng.random() < sensitive_frac else ()
        out_jobs.append(
            dataclasses.replace(j, node_type_scores=ts) if ts else j
        )
    out_running = []
    for r in running:
        if rng.random() < sensitive_frac / 2:
            r = dataclasses.replace(
                r, job=dataclasses.replace(r.job, node_type_scores=draw_map())
            )
        out_running.append(r)
    return nodes, queues, out_jobs, out_running


def _compare(cfg, nodes, queues, jobs, running, prices=None, seed=None):
    oracle = _Oracle(cfg, nodes, queues, jobs, running, prices=prices)
    o_sched, o_preempted, _ = oracle.run()
    bid = None
    if prices is not None:
        bid = lambda job: prices.get(job.queue, 0.0)  # noqa: E731
    outcome = run_scheduling_round(
        cfg, pool="default", nodes=nodes, queues=queues, queued_jobs=jobs,
        running=running, bid_price_of=bid, collect_stats=False,
    )
    label = f"seed {seed}"
    assert set(outcome.scheduled) == set(o_sched), (
        f"{label}: kernel-only={set(outcome.scheduled) - set(o_sched)} "
        f"oracle-only={set(o_sched) - set(outcome.scheduled)}"
    )
    assert sorted(outcome.preempted) == sorted(o_preempted), (
        f"{label}: kernel={sorted(outcome.preempted)} oracle={sorted(o_preempted)}"
    )
    jq = {j.id: j.queue for j in jobs}
    def by_queue(ids):
        out = {}
        for jid in ids:
            out[jq[jid]] = out.get(jq[jid], 0) + 1
        return out
    assert by_queue(outcome.scheduled) == by_queue(o_sched), label
    return outcome


@pytest.mark.parametrize("seed", list(range(1, 21)))
def test_gangs_and_runs_without_eviction(seed):
    """Gangs + running jobs + mixed PCs at hundreds of nodes: scheduled-set
    and per-queue-count parity with the independent oracle."""
    nodes, queues, jobs, running = world(seed)
    _compare(CFG, nodes, queues, jobs, running, seed=seed)


@pytest.mark.parametrize("seed", [2, 5, 9, 13, 17, 23, 31, 41])
def test_fair_share_eviction_and_preemption(seed):
    """protected_fraction=0: every preemptible run evicts; each either
    reschedules (usually onto its pinned node) or is preempted."""
    import dataclasses

    cfg = dataclasses.replace(CFG, protected_fraction_of_fair_share=0.0)
    nodes, queues, jobs, running = world(
        seed, num_nodes=120, num_jobs=150, num_running=60, gangs=0
    )
    outcome = _compare(cfg, nodes, queues, jobs, running, seed=seed)
    # sanity: the scenario actually exercises eviction machinery
    assert outcome.rescheduled or outcome.preempted


@pytest.mark.parametrize("seed", [3, 7, 11, 19])
def test_away_runs_preempted_by_home_jobs(seed):
    """Away runs (level 1) are urgency-preempted when home jobs need the
    capacity; the repair pass preempts what cannot re-fit."""
    nodes, queues, jobs, running = world(
        seed, num_nodes=60, num_jobs=400, num_running=80, gangs=0,
        away_frac=1.0,
    )
    _compare(CFG, nodes, queues, jobs, running, seed=seed)


@pytest.mark.parametrize("seed", list(range(1, 11)))
def test_hetero_type_bias_parity(seed):
    """Mixed fleet at hundreds of nodes: whitelists gate feasibility and
    the (1/throughput - 1) * 1024 bias re-ranks nodes; the oracle carries
    its own transcription of both, so scheduled/preempted set equality
    cross-checks the kernel's precomputed [TR,N] bias-table gather."""
    nodes, queues, jobs, running = hetero_world(seed)
    assert any(j.node_type_scores for j in jobs)  # the axis is exercised
    outcome = _compare(CFG, nodes, queues, jobs, running, seed=seed)
    sensitive = {j.id for j in jobs if j.node_type_scores}
    assert sensitive & set(outcome.scheduled), (
        "no type-sensitive job placed -- the biased path never ran"
    )


@pytest.mark.parametrize("seed", [2, 9, 17, 31])
def test_hetero_eviction_preemption_parity(seed):
    """Fair-share eviction over a mixed fleet: evictees take the pinned
    bias-free path, new sensitive units the biased path -- the preempted
    set must still match the oracle exactly."""
    import dataclasses

    cfg = dataclasses.replace(CFG, protected_fraction_of_fair_share=0.0)
    nodes, queues, jobs, running = hetero_world(
        seed, num_nodes=120, num_jobs=150, num_running=60, gangs=0
    )
    outcome = _compare(cfg, nodes, queues, jobs, running, seed=seed)
    assert outcome.rescheduled or outcome.preempted


@pytest.mark.parametrize("seed", [4, 8, 15, 16])
def test_market_bid_ordering(seed):
    """Market pools order queues by bid price, not DRF cost."""
    import dataclasses

    cfg = dataclasses.replace(
        CFG, pools=(PoolConfig("default", market_driven=True),)
    )
    rng = np.random.default_rng(seed)
    nodes, queues, jobs, running = world(
        seed, num_nodes=40, num_jobs=200, num_running=0, gangs=0
    )
    prices = {q.name: float(rng.integers(1, 10)) for q in queues}
    _compare(cfg, nodes, queues, jobs, running, prices=prices, seed=seed)


@pytest.mark.parametrize("seed,protected", [
    (2, 0.25), (5, 0.25), (9, 0.5), (13, 0.5), (17, 0.5),
    (23, 1.0), (31, 1.0), (41, 2.0),
])
def test_protected_fair_share_intermediate(seed, protected):
    """INTERMEDIATE protected fractions (the reference's production shape,
    pqs.go:146-156): only queues whose allocation exceeds `protected` x
    max(demand-capped-adjusted fair share, weight share) evict -- the oracle
    independently reimplements the water-filling share computation
    (context/scheduling.go updateFairShares), so the kernel's fair_shares op
    is cross-checked, not mirrored."""
    import dataclasses

    cfg = dataclasses.replace(
        CFG, protected_fraction_of_fair_share=protected
    )
    nodes, queues, jobs, running = world(
        seed, num_nodes=120, num_jobs=150, num_running=60, gangs=0
    )
    _compare(cfg, nodes, queues, jobs, running, seed=seed)


def test_protected_fraction_gates_eviction_directionally():
    """Deterministic sanity around the gate: an over-allocated queue evicts
    at a low protected fraction and is protected at a high one."""
    import dataclasses

    nodes = [
        NodeSpec(
            id=f"n{i}",
            pool="default",
            total_resources=F.from_mapping({"cpu": "8", "memory": "32"}),
        )
        for i in range(4)
    ]
    queues = [Queue("hog", 1.0), Queue("starved", 1.0)]
    # hog runs 4 full nodes; starved wants one job
    running = [
        RunningJob(
            job=JobSpec(
                id=f"r{i}", queue="hog", priority_class="low",
                submit_time=-1.0 - i,
                resources=F.from_mapping({"cpu": "8", "memory": "8"}),
            ),
            node_id=f"n{i}",
        )
        for i in range(4)
    ]
    jobs = [
        JobSpec(
            id="j0", queue="starved", priority_class="low", submit_time=0.0,
            resources=F.from_mapping({"cpu": "8", "memory": "8"}),
        )
    ]
    # hog's actual share ~1.0.  Water-filling raises hog's demand-capped
    # fair share to 0.75 (starved's capped demand is only 0.25; its spare
    # re-shares to hog), so frac = 1.0/0.75 ~ 1.33.  protected=1: evicts
    # (1.33 > 1), starved schedules.  protected=4: protected, nothing moves.
    lo = dataclasses.replace(CFG, protected_fraction_of_fair_share=1.0)
    hi = dataclasses.replace(CFG, protected_fraction_of_fair_share=4.0)
    out_lo = _compare(lo, nodes, queues, jobs, running, seed=0)
    out_hi = _compare(hi, nodes, queues, jobs, running, seed=1)
    assert "j0" in out_lo.scheduled and len(out_lo.preempted) == 1
    assert not out_hi.preempted and not out_hi.scheduled


CAP_CFG = SchedulingConfig(
    shape_bucket=32,
    priority_classes={
        "low": PriorityClass(
            "low", priority=100, preemptible=True,
            maximum_resource_fraction_per_queue={"cpu": 0.01},
        ),
        "high": PriorityClass("high", priority=1000, preemptible=False),
    },
    default_priority_class="high",
    protected_fraction_of_fair_share=1e9,
)


@pytest.mark.parametrize("seed", [6, 12, 21, 34, 47])
def test_per_queue_pc_caps_kill_queues_midround(seed):
    """maximumResourceFractionPerQueue (constraints.go CheckJobConstraints):
    a candidate whose (queue, pc) allocation would cross the cap trips the
    per-queue gate, does NOT place, and KILLS its queue for the round (new
    candidates stop; evictees still re-place).  Random worlds where the
    'low' class's 1% cpu cap trips mid-round in most queues."""
    nodes, queues, jobs, running = world(
        seed, num_nodes=60, num_jobs=250, num_running=30, gangs=0
    )
    outcome = _compare(CAP_CFG, nodes, queues, jobs, running, seed=seed)
    # sanity: the cap actually bit -- fewer low jobs scheduled than capacity
    # alone would admit
    low_sched = sum(
        1 for j in jobs
        if j.id in outcome.scheduled and j.priority_class == "low"
    )
    low_total = sum(1 for j in jobs if j.priority_class == "low")
    assert low_sched < low_total, "cap never tripped; scenario too loose"


def test_pc_cap_trip_is_a_kill_not_a_skip():
    """Deterministic: the 3rd low job crosses the cap -> it does not place
    AND the queue's later (smaller!) low job is dead too -- the reference
    kills the queue, it does not skip past the tripping candidate."""
    import dataclasses

    cfg = dataclasses.replace(
        CAP_CFG,
        priority_classes={
            "low": PriorityClass(
                "low", priority=100, preemptible=True,
                # pool = 4 nodes x 8 cpu = 32; cap = 0.1 x 32 = 3.2 cpu
                maximum_resource_fraction_per_queue={"cpu": 0.1},
            ),
            "high": PriorityClass("high", priority=1000, preemptible=False),
        },
    )
    nodes = [
        NodeSpec(
            id=f"n{i}", pool="default",
            total_resources=F.from_mapping({"cpu": "8", "memory": "32"}),
        )
        for i in range(4)
    ]
    queues = [Queue("qa", 1.0), Queue("qb", 1.0)]
    jobs = [
        _mkjob("a1", "qa", 2, 0.1),
        # a2 takes qa/low to 2+2=4 cpu > 3.2: trips, kills qa
        _mkjob("a2", "qa", 2, 0.2),
        # a3 WOULD pass the cap arithmetic on its own (2+1=3 <= 3.2) -- under
        # skip-the-tripping-candidate semantics it places; under the
        # reference's queue-kill it is dead.  This is the discriminating
        # candidate that makes the test able to catch a kill->skip
        # regression even if applied to kernel and oracle alike.
        _mkjob("a3", "qa", 1, 0.3),
        _mkjob("b1", "qb", 2, 0.5),
    ]
    outcome = _compare(cfg, nodes, queues, jobs, [], seed="kill-not-skip")
    assert set(outcome.scheduled) == {"a1", "b1"}


def _mkjob(jid, q, cpu, sub):
    return JobSpec(
        id=jid, queue=q, priority_class="low", submit_time=sub,
        resources=F.from_mapping({"cpu": str(cpu), "memory": "1"}),
    )


# --- the chip's body and XLA:CPU's, each against the oracle ------------------
# (conftest's `round_body`: schedule_round compiles the uncached body on an
# accelerator and the per-key fit cache on XLA:CPU)


@pytest.mark.parametrize("seed", [6, 14, 27])
def test_conflict_heavy_parity(seed, round_body):
    """Both bodies against the independent oracle on conflict-heavy worlds:
    few nodes (every pick contends for the same best-fit targets, so the
    cached fit rows of a node are re-derived after almost every commit),
    gangs interleaved with singletons.  _compare asserts scheduled-set,
    preempted-set and per-queue-count equality; each body matching the
    oracle pins their equality transitively."""
    nodes, queues, jobs, running = world(
        seed, num_nodes=30, num_jobs=250, num_running=0, gangs=4
    )
    _compare(CFG, nodes, queues, jobs, running, seed=seed)


@pytest.mark.parametrize("seed", [5, 17])
def test_eviction_preempted_set_parity(seed, round_body):
    """Eviction rounds under both bodies: evictee slots take the pinned path
    (never the fit cache), and the preempted / rescheduled sets must match
    the oracle exactly."""
    import dataclasses

    cfg = dataclasses.replace(CFG, protected_fraction_of_fair_share=0.0)
    nodes, queues, jobs, running = world(
        seed, num_nodes=120, num_jobs=150, num_running=60, gangs=0
    )
    outcome = _compare(cfg, nodes, queues, jobs, running, seed=seed)
    assert outcome.rescheduled or outcome.preempted
