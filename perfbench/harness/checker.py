"""The benchmark's plain reference: straight numpy and Python over the
recorded answers, independent of `armada_tpu`, run after the window.

It replays what the client sent and what the scheduler answered, cycle by
cycle, against the world's own tables, and holds the answers to what the
reference scheduler's semantics guarantee, no more (an order of leases within
a queue is not among them: a job that does not fit is skipped; which of two
equally good nodes wins is not either).  A violation is a string; none means
the run's decisions are sound.

Invariants (README.md lists them for users):
  1. every leased job is one the client submitted, is queued, and was not
     leased before (a lease is durable: no job is leased twice in a run);
  2. every lease names a node of the fleet;
  3. after each round no node holds more than its capacity, in any resource,
     counting the initial running set, every lease so far, minus every run
     the client has reported finished and every run the scheduler preempted;
     checked on every node that a lease or a preemption of the round touched;
  4. a round leases at most the per-round cap, and at most the per-queue cap
     from any one queue, and says which queue each job belongs to correctly;
  5. the scheduler's own count of running jobs moves by exactly
     leases - completions - preemptions from round to round, and its count of
     queued jobs by submits - leases (what it acknowledged, it keeps; a
     preempted job does not come back to the backlog);
  6. a preempted job is terminal, as it is for the program (it fails the job)
     and for the reference scheduler: it held a live lease, or is a live run
     of the initial running set; its node's room is freed; it is never leased
     again, never preempted again, and the client never reports it finished;
  7. only a job of a preemptible priority class (the configuration's
     `priorityClasses`) is preempted, and no job is both leased and preempted
     in one round;
  8. the initial running jobs are known by id, node, shape and class: a
     preemption of `r00000017` frees that run's room on that run's node;
  9. a round that gives up has nothing left to place: where a round preempted
     nothing, leased fewer jobs than the per-round cap and reports
     `termination` "exhausted", then after its leases no job that some queue
     under its per-queue cap held among its first `maxQueueLookback` queued
     jobs at the round's start (the queue's order: priority class, submit
     time, id) fits, in every resource, into what any node that admits it
     (invariant 11's rule) has left.  For a gang it holds what can be said
     plainly and exactly, its members being of one shape: the nodes that admit
     the shape have room between them for fewer members than the gang has
     (a node's room being the least, over the resources asked, of free over
     request, rounded down; within ONE value of the kind's `uniformity_label`
     where it states one); a gang that is not whole within the lookback, or
     has more members than the round's or its queue's cap has left, is left
     out.  Rounds that preempt are left alone: the reference's second pass
     may free room that nothing retries.  A configuration whose rounds can
     give up states its `maxQueueLookback`: a round that gives up without one
     is reported.
 10. a gang is leased whole in one round or not at all (its members count one
     each toward invariant 4's caps); where its kind states a
     `uniformity_label`, the members' nodes carry one value of that label;
 11. a lease names a node that admits the job: the node's labels satisfy the
     job's `node_selector`, and the job tolerates every NoSchedule /
     NoExecute taint of the node (`world.admits`); a lease that does not is
     reported and not booked;
 12. a preempted member takes its whole gang with it in the same round: every
     member of its gang that holds a lease is preempted in that round too.
"""

from __future__ import annotations

import numpy as np

QUEUED, LEASED, DONE, PREEMPTED = 0, 1, 2, 3
MAX_REPORTED = 20


class Checker:
    def __init__(
        self, world, cap: int, queue_cap: int, priority_classes: dict, lookback: int | None = None
    ):
        """`priority_classes` is the configuration's `priorityClasses` block
        (class name -> {"preemptible": bool, "priority": int, ...}); `lookback`
        its `maxQueueLookback`, which a configuration whose rounds can give up
        has to state (invariant 9 reads it, and takes no default of the
        program's for it)."""
        self.world = world
        self.cap = cap
        self.queue_cap = queue_cap
        self.lookback = None if lookback is None else int(lookback)
        self.class_priority = {
            flag: int(priority_classes[world.class_name(flag)].get("priority", 0))
            for flag in (True, False)
        }
        self._order = None  # see `_heads`: made once for the world's tables as they stand
        self.used = np.zeros_like(world.node_total)
        np.add.at(self.used, world.run_node, world.run_shape_req[world.run_shape])
        self.violations: list = []
        self.bad_cycles: set = set()
        over = (self.used > world.node_total).any(axis=1)
        if over.any():
            self._bad(0, f"the initial running set overfills {int(over.sum())} nodes")
        self.status = np.full(world.num_jobs, QUEUED, np.int8)
        self.submitted = int(world.sizes["queued_jobs"])  # numbers below are known
        self.node_of = {}  # leased job number -> node index
        self.run_live = np.ones(len(world.run_shape), bool)  # initial runs not preempted
        self.prev_counts = None

        def may_preempt(flag: bool) -> bool:
            return bool(priority_classes[world.class_name(flag)].get("preemptible", False))

        self.shape_preemptible = np.array([may_preempt(s[2]) for s in world.shapes], bool)
        self.run_shape_preemptible = np.array([may_preempt(s[2]) for s in world.run_shapes], bool)

    def _bad(self, n: int, msg: str) -> None:
        self.bad_cycles.add(n)
        msg = f"cycle {n}: {msg}"
        if len(self.violations) < MAX_REPORTED:
            self.violations.append(msg)
        elif len(self.violations) == MAX_REPORTED:
            self.violations.append("... more violations not listed")

    def _preempt(self, n: int, job_id: str):
        """One preemption: the node whose room it freed, or None when it broke
        invariant 6 or 7 (reported)."""
        w = self.world
        try:
            i, initial = w.job_number(job_id), False
        except KeyError:
            try:
                i, initial = w.run_number(job_id), True
            except KeyError:
                self._bad(n, f"preempted unknown job {job_id!r}")
                return None
        if initial:
            if not self.run_live[i]:
                self._bad(n, f"preempted initial run {job_id} twice")
                return None
            shape, node = w.run_shape[i], int(w.run_node[i])
            if not self.run_shape_preemptible[shape]:
                self._bad(n, f"preempted {job_id} of a class that is not preemptible")
                return None
            self.run_live[i] = False
            self.used[node] -= w.run_shape_req[shape]
            return node
        if self.status[i] != LEASED:
            what = "twice" if self.status[i] == PREEMPTED else "that holds no lease"
            self._bad(n, f"preempted job {job_id} {what}")
            return None
        if not self.shape_preemptible[w.job_shape[i]]:
            self._bad(n, f"preempted {job_id} of a class that is not preemptible")
            return None
        self.status[i] = PREEMPTED
        node = self.node_of.pop(i)
        self.used[node] -= w.shape_req[w.job_shape[i]]
        return node

    def cycle(self, n: int, record: dict) -> None:
        """One cycle's record: `submitted` (job numbers the SyncState carried),
        `completed` (job numbers it reported finished), `leases` ((job id,
        node id, queue) the round returned), `preempted` (job ids), and the
        scheduler's own `num_queued` / `num_running` before the round."""
        w = self.world
        if len(self.status) < w.num_jobs:
            self.status = np.concatenate(
                [self.status, np.full(w.num_jobs - len(self.status), QUEUED, np.int8)]
            )
        self.submitted = max(self.submitted, max(record["submitted"], default=-1) + 1)
        for i in record["completed"]:
            if self.status[i] != LEASED:
                what = "that was preempted" if self.status[i] == PREEMPTED else "that holds no lease"
                self._bad(n, f"the client completed job {i} {what}")
                continue
            self.status[i] = DONE
            self.used[self.node_of.pop(i)] -= w.shape_req[w.job_shape[i]]

        leases = record["leases"]
        heads = None
        if self.gave_up(record):
            if self.lookback is None:
                self._bad(n, "the round gave up (exhausted) and the configuration states no "
                          "maxQueueLookback: \"nothing left fits\" cannot be held")
            else:
                heads = self._heads()  # before the leases leave the backlog
        # the round evicts, then places: its preemptions free room first
        touched = []
        leased_now = {job_id for job_id, _, _ in leases}
        for job_id in record["preempted"]:
            if job_id in leased_now:
                self._bad(n, f"job {job_id} leased and preempted in one round")
                continue
            node = self._preempt(n, job_id)
            if node is not None:
                touched.append(node)
        if len(w.gang_size):
            self._gangs_whole(n, leases, record["preempted"])
        if len(leases) > self.cap:
            self._bad(n, f"{len(leases)} leases, over the per-round cap {self.cap}")
        per_queue: dict = {}
        for job_id, node_id, queue in leases:
            try:
                i = w.job_number(job_id)
            except KeyError:
                self._bad(n, f"leased unknown job {job_id!r}")
                continue
            if i >= self.submitted:
                self._bad(n, f"leased job {job_id} before it was submitted")
                continue
            if self.status[i] != QUEUED:
                what = {LEASED: "twice", DONE: "after it finished"}.get(
                    int(self.status[i]), "after it was preempted"
                )
                self._bad(n, f"job {job_id} leased {what}")
                continue
            node = w.node_index.get(node_id)
            if node is None:
                self._bad(n, f"job {job_id} leased to unknown node {node_id!r}")
                continue
            if queue != w.queue_names[w.job_queue[i]]:
                self._bad(n, f"job {job_id} reported in queue {queue!r}")
            per_queue[queue] = per_queue.get(queue, 0) + 1
            if not w.shape_admits[w.job_shape[i], w.node_kind[node]]:
                kind = w.node_kinds[w.node_kind[node]]
                self._bad(
                    n,
                    f"job {job_id} leased to node {node_id}, which does not admit it: selector "
                    f"{w.shape_selector[w.job_shape[i]]} against labels {kind['labels']}, taints {kind['taints']} "
                    f"against tolerations {w.shape_tolerations[w.job_shape[i]]}"
                )
                continue
            self.status[i] = LEASED
            self.node_of[i] = node
            self.used[node] += w.shape_req[w.job_shape[i]]
            touched.append(node)
        for queue, k in per_queue.items():
            if k > self.queue_cap:
                self._bad(n, f"{k} leases from {queue}, over the per-queue cap")
        if touched:
            idx = np.unique(np.asarray(touched))
            over = (self.used[idx] > w.node_total[idx]).any(axis=1)
            for node in idx[over][:3]:
                self._bad(
                    n,
                    f"node {w.node_ids[node]} holds {self.used[node].tolist()} "
                    f"of {w.node_total[node].tolist()} (thousandths of {', '.join(w.resources)})"
                )

        if heads is not None:
            self._nothing_left_fits(n, heads, per_queue)

        # 5: the scheduler's own counts, as the round saw them on entry
        counts = (record.get("num_queued"), record.get("num_running"))
        if None not in counts and self.prev_counts is not None:
            (q0, r0), prev = self.prev_counts
            want_q = q0 + len(record["submitted"]) - prev["leased"]
            want_r = r0 + prev["leased"] - prev["preempted"] - len(record["completed"])
            if counts != (want_q, want_r):
                self._bad(
                    n,
                    f"scheduler counts queued/running {counts}, "
                    f"the mirror should hold {(want_q, want_r)}"
                )
        if None not in counts:
            self.prev_counts = (
                counts,
                {"leased": len(leases), "preempted": len(record["preempted"])},
            )

    # ---- 10, 12: a gang is one unit ----

    def _gangs_whole(self, n: int, leases: list, preempted: list) -> None:
        """10: every gang with a member among the round's leases has all of
        them there, on nodes of one value of its uniformity label; 12: every
        gang with a member among the round's preemptions has every member that
        held a lease at the round's start there.  Called after the round's
        preemptions are booked and before its leases are."""
        w = self.world
        by_gang: dict = {}
        for job_id, node_id, _ in leases:
            try:
                i = w.job_number(job_id)
            except KeyError:
                continue  # reported where the lease is booked
            if w.job_gang[i] >= 0:
                by_gang.setdefault(int(w.job_gang[i]), {})[i] = node_id
        for g, got in by_gang.items():
            size = int(w.gang_size[g])
            if len(got) != size:
                self._bad(n, f"gang {w.gang_id(g)} leased in part: {len(got)} of its {size} members")
            label = w.shape_uniformity[w.job_shape[next(iter(got))]]
            if label:
                # of the members on nodes that admit them (11 reports the others)
                kinds = {int(w.node_kind[w.node_index[x]]) for x in got.values() if x in w.node_index}
                shape = w.job_shape[next(iter(got))]
                values = {w.node_kinds[k]["labels"].get(label) for k in kinds if w.shape_admits[shape, k]}
                if len(values) > 1 or None in values:
                    self._bad(n, f"gang {w.gang_id(g)} leased across values {sorted(map(str, values))} of its uniformity label {label!r}")
        gone = set()
        for job_id in preempted:
            try:
                gone.add(w.job_number(job_id))
            except KeyError:
                continue  # an initial run (no gang), or reported by `_preempt`
        for g in {int(w.job_gang[i]) for i in gone if w.job_gang[i] >= 0}:
            members = np.arange(w.gang_start[g], w.gang_start[g] + w.gang_size[g])
            left = members[self.status[members] == LEASED]  # the preempted are PREEMPTED by now
            if len(left):
                self._bad(
                    n,
                    f"gang {w.gang_id(g)} preempted in part: {len(left)} of its members keep their lease "
                    f"(first {w.job_id(int(left[0]))})"
                )

    # ---- 9: a round that gives up ----

    def gave_up(self, record: dict) -> bool:
        """Whether invariant 9 holds this round to "nothing left fits"."""
        return (
            record.get("termination") == "exhausted"
            and not record["preempted"]
            and len(record["leases"]) < self.cap
        )

    def _heads(self) -> np.ndarray:
        """Job numbers of every queue's first `lookback` queued jobs, in the
        queue's own order (priority class, highest first; submit time; id)."""
        w = self.world
        if self._order is None or self._order[0] != w.num_jobs:
            flags = np.array([s[2] for s in w.shapes], bool)[w.job_shape]
            rank = np.where(flags, -self.class_priority[True], -self.class_priority[False])
            order = np.lexsort((np.arange(w.num_jobs), w.job_submit, rank, w.job_queue))
            queue = w.job_queue[order]
            starts = np.flatnonzero(np.r_[True, queue[1:] != queue[:-1]])  # each queue's first place
            self._order = (w.num_jobs, order, starts, np.diff(np.r_[starts, len(order)]))
        _, order, starts, lengths = self._order
        queued = (self.status[order] == QUEUED) & (order < self.submitted)
        before = np.cumsum(queued) - queued  # queued jobs ahead of each, over all queues
        ahead_of_queue = np.repeat(before[starts], lengths)
        return order[queued & (before - ahead_of_queue < self.lookback)]

    def _nothing_left_fits(self, n: int, heads: np.ndarray, leased_per_queue: dict) -> None:
        """`heads` less what the round leased, queue by queue: no single job
        left in a queue still under its cap fits the free room of a node that
        admits it, and no gang left whole there has room for all its members
        (the docstring's rule)."""
        w = self.world
        left = heads[self.status[heads] == QUEUED]
        capped = [
            w.queue_index[q] for q, k in leased_per_queue.items() if k >= self.queue_cap
        ]
        left = left[~np.isin(w.job_queue[left], capped)]
        free = w.node_total - self.used
        leased = sum(leased_per_queue.values())
        told = (
            f"the round gave up (exhausted, {leased} leases under the cap {self.cap}, nothing preempted) while "
        )
        singles = left[w.job_gang[left] < 0]
        shapes = np.unique(w.job_shape[singles])
        fits = (w.shape_req[shapes][:, None, :] <= free[None, :, :]).all(axis=2) & w.shape_admits[shapes][:, w.node_kind]
        if fits.any():
            s, node = (int(x) for x in np.argwhere(fits)[0])
            shape = int(shapes[s])
            cpu, mem, preemptible = w.shapes[shape]
            holder = w.queue_names[int(w.job_queue[singles[w.job_shape[singles] == shape][0]])]
            self._bad(
                n,
                told + f"a job still fits: {holder} holds a "
                f"{w.class_name(preemptible)} job of {cpu} cpu thousandths and {mem} memory within "
                f"its lookback, node {w.node_ids[node]} has {free[node].tolist()} free; "
                f"{int(fits.any(axis=0).sum())} nodes have room for one of {len(shapes)} shapes",
            )
            return
        gangs, seen = np.unique(w.job_gang[left[w.job_gang[left] >= 0]], return_counts=True)
        for g in gangs[seen == w.gang_size[gangs]]:  # whole within the lookback
            size, first = int(w.gang_size[g]), int(w.gang_start[g])
            queue = w.queue_names[int(w.job_queue[first])]
            if size > min(self.cap - leased, self.queue_cap - leased_per_queue.get(queue, 0)):
                continue
            shape = int(w.job_shape[first])
            req = w.shape_req[shape]
            room = np.where(w.shape_admits[shape][w.node_kind], (free[:, req > 0] // req[req > 0]).min(axis=1), 0)
            room = np.minimum(np.maximum(room, 0), size)
            label = w.shape_uniformity[shape]
            domains = [np.ones(len(room), bool)]
            if label:
                value = np.array([k["labels"].get(label) for k in w.node_kinds], object)[w.node_kind]
                domains = [value == v for v in set(value.tolist()) - {None}]
            if any(room[d].sum() >= size for d in domains):
                self._bad(
                    n,
                    told + f"a gang still fits: {queue} holds gang {w.gang_id(int(g))} of {size} members "
                    f"(each {req.tolist()} thousandths of {', '.join(w.resources)}) whole within its lookback, and "
                    f"the nodes that admit it have room for {int(room.sum())} such members",
                )
                return
