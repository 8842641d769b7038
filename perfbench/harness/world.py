"""The seeded world: a fleet, queues, a backlog and a running set, as plain
numpy tables plus the wire messages a mirroring control plane would send.

A copy, for the yardstick, of the distribution `armada_tpu/models/synthetic.py`
draws (`synthetic_world`, `synthetic_mirror`, `synthetic_job_state`), with two
differences that make runs repeat:

* every categorical draw is STRATIFIED: exact counts per category (largest
  remainder), then a seeded permutation.  Two seeds give the same histogram of
  node shapes, job shapes, queues and priority classes, in another order, so a
  seed changes which job sits where and never how much work there is;
* the wire messages are built straight from the tables (one `JobSpec` template
  per distinct shape), with no intermediate object per job.

The initial running set is `running_jobs` spread evenly over the nodes, or,
where the `world` block has `running_fill`, as many as fill every node to that
share of its own size (`_fill`); `running_queue_demand` "1/k" gives the first
queues more of it than their fair share.

Three kinds of key are optional (README.md, "A configuration"), and a
configuration that states none of them draws bit for bit what it drew before
they existed:

* `resources` may name more than cpu and memory (`nvidia.com/gpu`): every
  `[*, R]` table has a column a resource, in that order;
* `node_types` lists the fleet's node shapes with their extra resources,
  labels and taints, in place of the short form `node_cores` x
  `memory_per_core`;
* `job_kinds` lists kinds of job beside the grid `job_cpu_milli` x
  `job_memory_factor`: a share of the submits each, with their own size,
  `node_selector`, `tolerations` and, with `gang`, members that are submitted,
  leased and preempted together.  A batch is drawn in UNITS (a single job, or a
  gang): every batch of one size holds the same number of jobs and the same
  number of gangs of every cardinality; a seed moves them between queues'
  places and never changes the amount of work.

Nothing here imports the scheduler: the tables are also what the plain checker
(`checker.py`) holds the program's answers against, and whether a node ADMITS
a job (`admits`: selector against labels, taints against tolerations) is
worked out here, from the configuration's own words.
"""

from __future__ import annotations

import numpy as np

from perfbench.harness.cell import CellError

JOBSET = "bench"
POOL = "default"
MILLI = 1000  # the wire carries resources in thousandths of a unit


def exact_counts(n: int, weights) -> np.ndarray:
    """n split over categories in proportion to `weights`, exactly: floors,
    then the largest remainders get the rest (ties to the lower index)."""
    w = np.asarray(weights, dtype=np.float64)
    ideal = n * w / w.sum()
    counts = np.floor(ideal).astype(np.int64)
    rest = int(n - counts.sum())
    if rest:
        order = np.argsort(-(ideal - counts), kind="stable")
        counts[order[:rest]] += 1
    return counts


def stratified(rng: np.random.Generator, n: int, weights) -> np.ndarray:
    """Category index per item: exact counts, seeded order."""
    counts = exact_counts(n, weights)
    return rng.permutation(np.repeat(np.arange(len(counts)), counts))


BLOCKING = ("NoSchedule", "NoExecute")  # a taint of another effect keeps no job off a node


def admits(selector: dict, tolerations: list, labels: dict, taints: list) -> bool:
    """Whether a node with `labels` and `taints` may take a job with
    `selector` and `tolerations`, by Kubernetes' rules: every selector entry
    equals the node's label; every NoSchedule / NoExecute taint is tolerated
    (a toleration names the taint's key and value, or with operator "Exists"
    its key alone, or no key at all; one that states an effect tolerates only
    that effect)."""
    if any(labels.get(k) != v for k, v in selector.items()):
        return False
    for taint in taints:
        if taint.get("effect", "NoSchedule") not in BLOCKING:
            continue
        for tol in tolerations:
            if tol.get("effect") and tol["effect"] != taint.get("effect", "NoSchedule"):
                continue
            if tol.get("operator", "Equal") == "Exists":
                if tol.get("key", "") in ("", taint["key"]):
                    break
            elif tol.get("key") == taint["key"] and tol.get("value", "") == taint.get("value", ""):
                break
        else:
            return False
    return True


def gangs_for(members: int, sizes, weights):
    """How many gangs of each of `sizes` (in the proportions `weights`, exact
    counts) have `members` members between them: the counts, or None and the
    nearest member counts below and above that some whole number of gangs
    does make."""
    sizes = np.asarray(sizes, dtype=np.int64)
    below = 0
    for gangs in range(members + 1):
        counts = exact_counts(gangs, weights)
        made = int((counts * sizes).sum())
        if made == members:
            return counts, None
        if made > members:
            return None, (below, made)
        below = made
    return None, (below, None)


class World:
    """Tables of one deployment, drawn from `seed`.

    Jobs are numbered: 0..queued-1 is the initial backlog, later numbers are
    the submits of later cycles (`extend`).  Job `i` is `j{i:09d}` on the wire;
    initial running job `i` is `r{i:08d}`, node `i` is `n{i:06d}`.
    """

    def __init__(self, sizes: dict, seed: int):
        self.sizes = sizes
        # any whole number up to a little over 2**31 is a valid seed
        self.rng = np.random.default_rng(int(seed))
        rng = self.rng
        n_nodes = int(sizes["nodes"])
        n_queues = int(sizes["queues"])
        self.resources = tuple(sizes["resources"])  # ("cpu", "memory"[, "nvidia.com/gpu"])
        if self.resources[:2] != ("cpu", "memory"):
            raise CellError(f"resources {list(self.resources)}: cpu and memory come first, others after them")
        if sizes.get("queue_demand", "1/k") != "1/k":
            raise CellError(
                f"queue_demand {sizes['queue_demand']!r}: \"1/k\" is the one demand over the queues this "
                "generator draws (the k-th queue holds 1/k of the first's jobs)"
            )

        self._node_tables(rng, n_nodes)
        self.node_ids = [f"n{i:06d}" for i in range(n_nodes)]
        self.node_index = {nid: i for i, nid in enumerate(self.node_ids)}
        self.queue_names = [f"q{i:03d}" for i in range(n_queues)]
        self.queue_index = {q: i for i, q in enumerate(self.queue_names)}
        self.queue_weights = 1.0 / np.arange(1, n_queues + 1)  # 1/k demand

        self._job_tables()
        self.job_queue = np.zeros(0, np.int32)
        self.job_shape = np.zeros(0, np.int32)
        self.job_submit = np.zeros(0, np.float64)
        self.job_gang = np.zeros(0, np.int64)  # the gang's number, -1 for a single job
        self.gang_start = np.zeros(0, np.int64)  # gang number -> its first member's job number
        self.gang_size = np.zeros(0, np.int64)
        self._gang_specs: dict = {}
        self.extend(int(sizes["queued_jobs"]), 0.0)

        # the initial running set
        run_shapes, run_w = [], []
        rp = float(sizes["running_preemptible_share"])
        for cpu in sizes["running_cpu_milli"]:
            for preemptible, share in ((True, rp), (False, 1.0 - rp)):
                run_shapes.append((int(cpu), int(sizes["running_memory"]), preemptible))
                run_w.append(share)
        self.run_shapes = run_shapes
        self.run_shape_req = self._req([(cpu, mem, {}) for cpu, mem, _ in run_shapes])
        # a running job of the initial set states no selector and tolerates nothing
        open_kinds = np.array([admits({}, [], k["labels"], k["taints"]) for k in self.node_kinds], bool)
        self.run_shape_admits = np.tile(open_kinds, (len(run_shapes), 1))
        demand = sizes.get("running_queue_demand", "uniform")
        if demand not in ("uniform", "1/k"):
            raise ValueError(f"running_queue_demand {demand!r}: 'uniform' or '1/k'")
        fill = sizes.get("running_fill")
        if fill is None:
            self.run_shape = stratified(rng, int(sizes["running_jobs"]), run_w).astype(np.int32)
        elif "running_jobs" in sizes:
            raise ValueError("running_fill derives running_jobs: give one of the two")
        elif "node_types" in sizes:
            raise CellError("running_fill fills the short form's nodes (node_cores x memory_per_core): not node_types yet")
        else:
            self.run_shape, self.run_node = self._fill(rng, float(fill), rp)
        n_runs = len(self.run_shape)
        self.run_queue = stratified(
            rng, n_runs, self.queue_weights if demand == "1/k" else np.ones(n_queues)
        ).astype(np.int32)
        if fill is None:
            # spread over the fleet: a seeded order of the nodes, wrapped (drawn
            # after the queues: the accepted configurations' order of draws);
            # where a node type's taint keeps the runs off, over the others
            order = rng.permutation(n_nodes)
            if not open_kinds.all():
                order = order[open_kinds[self.node_kind[order]]]
                if not len(order) and n_runs:
                    raise CellError("no node type admits the initial running set (every one is tainted)")
            self.run_node = np.resize(order, n_runs).astype(np.int64)

        self._spec_templates = None
        self._run_spec_templates = None

    def _req(self, rows) -> np.ndarray:
        """[len(rows), R] in wire units (thousandths) from (cpu thousandths,
        memory units, {extra resource: units}) rows."""
        out = np.zeros((len(rows), len(self.resources)), dtype=np.int64)
        for i, (cpu, mem, extra) in enumerate(rows):
            out[i, 0], out[i, 1] = int(cpu), int(mem) * MILLI
            for name, amount in extra.items():
                if name not in self.resources[2:]:
                    raise CellError(f"resource {name!r} is not among the configuration's `resources` {list(self.resources)}")
                out[i, self.resources.index(name)] = int(amount) * MILLI
        return out

    def _node_tables(self, rng, n_nodes: int) -> None:
        """`node_kinds` (name, labels, taints a node type), `node_kind` [N],
        `node_cores` [N] and `node_total` [N, R]: the short form's sizes in
        equal parts, or `node_types` by `count` (which add up to `nodes`) or by
        `share`; exact counts, seeded order."""
        sizes = self.sizes
        if ("node_types" in sizes) == ("node_cores" in sizes):
            raise CellError("a world states its nodes once: `node_cores` x `memory_per_core`, or `node_types`")
        if "node_cores" in sizes:
            per_core = int(sizes["memory_per_core"])
            types = [{"name": f"c{c}", "cores": int(c), "memory": int(c) * per_core} for c in sizes["node_cores"]]
            counts = exact_counts(n_nodes, np.ones(len(types)))
        else:
            types = sizes["node_types"]
            if all("count" in t for t in types):
                counts = np.asarray([int(t["count"]) for t in types], dtype=np.int64)
                if counts.sum() != n_nodes:
                    raise CellError(f"node_types counts add up to {int(counts.sum())}, `nodes` says {n_nodes}")
            elif all("share" in t and "count" not in t for t in types):
                counts = exact_counts(n_nodes, [float(t["share"]) for t in types])
            else:
                raise CellError("node_types: every entry a `count`, or every entry a `share`")
        self.node_kinds = [
            {"name": str(t["name"]), "labels": dict(t.get("labels", {})), "taints": [dict(x) for x in t.get("taints", [])]}
            for t in types
        ]
        self.node_kind = rng.permutation(np.repeat(np.arange(len(counts)), counts))
        self.node_cores = np.asarray([int(t["cores"]) for t in types], dtype=np.int64)[self.node_kind]
        self.kind_total = self._req([(int(t["cores"]) * MILLI, int(t["memory"]), t.get("resources", {})) for t in types])
        self.node_total = self.kind_total[self.node_kind]  # [N, R] in wire units (thousandths)

    def _job_tables(self) -> None:
        """The job shapes: the grid's (cpu thousandths, memory units,
        preemptible) and two a kind of `job_kinds` (one a class), with
        `shape_req` [S, R], `shape_kind` (the kind's name; "grid"),
        `shape_selector`, `shape_tolerations`, `shape_uniformity`,
        `shape_admits` [S, node kinds], and the UNIT types a batch is made of:
        (shape, cardinality), a single job being a unit of one."""
        sizes = self.sizes
        p = float(sizes["preemptible_share"])
        shapes, weights = [], []
        for cpu in sizes.get("job_cpu_milli", ()):
            for factor in sizes["job_memory_factor"]:
                for preemptible, share in ((True, p), (False, 1.0 - p)):
                    shapes.append((int(cpu), int(cpu) // 1000 * int(factor) + 1, preemptible))
                    weights.append(share)
        grid = len(shapes)
        rows = [(cpu, mem, {}) for cpu, mem, _ in shapes]
        self.shape_kind = ["grid"] * grid
        self.shape_selector = [{} for _ in range(grid)]
        self.shape_tolerations = [[] for _ in range(grid)]
        self.shape_uniformity = [""] * grid
        self.shape_weights = np.asarray(weights)  # of the grid's shapes, among themselves
        # unit types: (shape, cardinality); a kind's come after the grid's
        self.unit_types = [(s, 1) for s in range(grid)]
        self.job_kinds = []  # (name, share, [(sizes, weights) of its gangs], its two shapes, preemptible share)
        for n, kind in enumerate(sizes.get("job_kinds", ())):
            name = str(kind.get("name", f"kind{n}"))
            gang = kind.get("gang") or {}
            cardinality = gang.get("cardinality", 1)
            by_size = {int(c): float(w) for c, w in cardinality.items()} if isinstance(cardinality, dict) else {int(cardinality): 1.0}
            if not by_size or min(by_size) < 1 or min(by_size.values()) <= 0:
                raise CellError(f"job_kinds {name}: a gang's cardinality is a whole number from 1, each with a weight over 0")
            mine = []
            for preemptible in (True, False):
                mine.append(len(shapes))
                shapes.append((int(kind["cpu_milli"]), int(kind["memory"]), preemptible))
                rows.append((int(kind["cpu_milli"]), int(kind["memory"]), kind.get("resources", {})))
                self.shape_kind.append(name)
                self.shape_selector.append(dict(kind.get("node_selector", {})))
                self.shape_tolerations.append([dict(t) for t in kind.get("tolerations", [])])
                self.shape_uniformity.append(str(gang.get("uniformity_label", "")))
                self.unit_types += [(mine[-1], c) for c in sorted(by_size)]
            self.job_kinds.append(
                (name, float(kind["share"]), sorted(by_size.items()), mine, float(kind.get("preemptible_share", p)))
            )
        self.grid_share = 1.0 - sum(k[1] for k in self.job_kinds)
        if self.grid_share < -1e-9 or (self.grid_share > 1e-9 and not grid):
            raise CellError("job_kinds: the shares add up to at most 1, and what they leave is the grid's (job_cpu_milli x job_memory_factor)")
        self.shapes = shapes
        self.shape_req = self._req(rows)
        self.shape_admits = np.array(
            [
                [admits(sel, tol, k["labels"], k["taints"]) for k in self.node_kinds]
                for sel, tol in zip(self.shape_selector, self.shape_tolerations)
            ],
            bool,
        ).reshape(len(shapes), len(self.node_kinds))
        shut_out = np.flatnonzero(~self.shape_admits.any(axis=1))
        if len(shut_out):
            raise CellError(
                f"job kind {self.shape_kind[shut_out[0]]}: no node type satisfies its node_selector "
                f"{self.shape_selector[shut_out[0]]} and carries only taints it tolerates"
            )
        # a batch's blocks, single jobs first: (cardinality, its unit types' places, their shapes)
        self.blocks = []
        for c in sorted({c for _, c in self.unit_types} | {1}):
            mine = [i for i, (_, size) in enumerate(self.unit_types) if size == c]
            self.blocks.append((c, mine, np.asarray([self.unit_types[i][0] for i in mine], np.int64)))
        self._unit_counts: dict = {}

    def unit_counts(self, n: int) -> np.ndarray:
        """How many units of each of `unit_types` a batch of `n` jobs holds:
        the same for every batch of that size, whatever the seed.  The kinds
        share `n` by exact counts; the grid's part goes over its shapes as it
        always did; a kind's part is made of whole gangs in the proportions of
        its cardinalities, each size split over the two classes; a part that
        no whole number of gangs makes is a CellError that says which would."""
        if n not in self._unit_counts:
            counts = np.zeros(len(self.unit_types), dtype=np.int64)
            parts = exact_counts(n, [max(self.grid_share, 0.0)] + [k[1] for k in self.job_kinds])
            grid = len(self.shape_weights)
            if grid:
                counts[:grid] = exact_counts(int(parts[0]), self.shape_weights)
            index = {t: i for i, t in enumerate(self.unit_types)}
            for (name, share, by_size, (batch, prod), p), members in zip(self.job_kinds, parts[1:]):
                gangs, nearest = gangs_for(int(members), [c for c, _ in by_size], [w for _, w in by_size])
                if gangs is None:
                    raise CellError(
                        f"job_kinds {name}: a batch of {n} jobs gives it {int(members)} members (share {share}), which no "
                        f"whole number of gangs of cardinality {dict(by_size)} makes; {nearest[0]} or {nearest[1]} members "
                        f"would: choose the share, or the batch (queued_jobs, submits_per_cycle), so that it comes to one of them"
                    )
                for (c, _), g in zip(by_size, gangs):
                    counts[index[(batch, c)]], counts[index[(prod, c)]] = exact_counts(int(g), [p, 1.0 - p])
            self._unit_counts[n] = counts
        return self._unit_counts[n]

    def _fill(self, rng, fill: float, preemptible_share: float) -> tuple:
        """The running set of a fleet filled by capacity: (run_shape, run_node).

        Every node of one size carries the same running jobs, whatever the
        seed: the running cpu sizes taken in turn, largest first, each added
        while it fits into `fill` of the node's cores and memory, until the
        smallest no longer does (so a node is within one smallest job of its
        share, and never over it).  The seed decides which node has which size
        (`node_cores`) and, per cpu size with exact counts, which of the runs
        are preemptible."""
        if not 0.0 <= fill <= 1.0:
            raise ValueError(f"running_fill {fill}: a share from 0 to 1")
        cpus = sorted({cpu for cpu, _, _ in self.run_shapes}, reverse=True)
        mem = self.run_shape_req[0, 1]  # every running shape has `running_memory`
        per_size = {}  # cores -> the cpu sizes one such node carries
        for cores in np.unique(self.node_cores):
            room = np.floor(fill * np.array([cores * MILLI, cores * int(self.sizes["memory_per_core"]) * MILLI]))
            carried, turn = [], 0
            while min(cpus) <= room[0] and mem <= room[1]:
                cpu = cpus[turn % len(cpus)]
                turn += 1
                if cpu <= room[0]:
                    carried.append(cpu)
                    room -= (cpu, mem)  # cpu and memory: a running job holds nothing else
            per_size[int(cores)] = carried
        counts = np.array([len(per_size[int(c)]) for c in self.node_cores], dtype=np.int64)
        run_node = np.repeat(np.arange(len(self.node_cores), dtype=np.int64), counts)
        run_cpu = np.array(
            [cpu for c in self.node_cores for cpu in per_size[int(c)]], dtype=np.int64
        )
        # shape index of (cpu, preemptible): the order the shapes were listed in
        index = {(cpu, pre): i for i, (cpu, _, pre) in enumerate(self.run_shapes)}
        run_shape = np.zeros(len(run_cpu), np.int32)
        for cpu in sorted(cpus):
            mine = np.flatnonzero(run_cpu == cpu)
            pre = stratified(rng, len(mine), [preemptible_share, 1.0 - preemptible_share]) == 0
            run_shape[mine] = np.where(pre, index[(cpu, True)], index[(cpu, False)])
        return run_shape, run_node

    # ---------------------------------------------------------- tables ----

    @property
    def num_jobs(self) -> int:
        return int(self.job_queue.shape[0])

    def extend(self, n: int, t0: float) -> range:
        """n more queued jobs submitted in [t0, t0 + 1): the same histogram of
        queues and shapes every time, in a seeded order."""
        return self.extend_batches(n, [t0])[0]

    def extend_batches(self, n: int, t0s) -> list:
        """One batch of n more queued jobs per submit time in `t0s`, the tables
        grown once for all of them; returns each batch's job numbers.

        A batch is laid out by cardinality: its single jobs first, then its
        gangs of each size, a gang's members on consecutive numbers with one
        queue and one submit time.  Each block draws its units' queues (1/k,
        exact counts: the gangs of a batch fall to the same queues' share
        every time), their shapes (exact counts a unit type) and their submit
        times, in that order, as the single jobs always did."""
        rng = self.rng
        counts = self.unit_counts(n)
        queues, shapes, submits, gangs = [self.job_queue], [self.job_shape], [self.job_submit], [self.job_gang]
        starts, sizes = [self.gang_start], [self.gang_size]
        at, n_gangs = self.num_jobs, len(self.gang_size)
        for t0 in t0s:
            for c, mine, block_shapes in self.blocks:
                per_shape = counts[mine]
                units = int(per_shape.sum())
                queue = stratified(rng, units, self.queue_weights).astype(np.int32)
                shape = rng.permutation(np.repeat(block_shapes, per_shape))
                submit = t0 + rng.random(units)
                queues.append(np.repeat(queue, c))
                shapes.append(np.repeat(shape.astype(np.int32), c))
                submits.append(np.repeat(submit, c))
                if c == 1:
                    gangs.append(np.full(units, -1, np.int64))
                else:
                    gangs.append(np.repeat(np.arange(n_gangs, n_gangs + units), c))
                    starts.append(at + c * np.arange(units))
                    sizes.append(np.full(units, c, np.int64))
                    n_gangs += units
                at += c * units
        lo = self.num_jobs
        self.job_queue = np.concatenate(queues)
        self.job_shape = np.concatenate(shapes)
        self.job_submit = np.concatenate(submits)
        self.job_gang = np.concatenate(gangs)
        self.gang_start = np.concatenate(starts)
        self.gang_size = np.concatenate(sizes)
        assert self.num_jobs == lo + n * len(t0s), "a batch holds the jobs asked for"
        return [range(lo + b * n, lo + (b + 1) * n) for b in range(len(t0s))]

    def chunks(self, n: int, chunk: int):
        """Ranges of about `chunk` job numbers that cover 0..n-1 and split no
        gang: a gang's members leave in one SyncState."""
        lo = 0
        while lo < n:
            hi = min(lo + chunk, n)
            if hi < n and self.job_gang[hi] >= 0 and self.job_gang[hi] == self.job_gang[hi - 1]:
                g = self.job_gang[hi]
                hi = int(self.gang_start[g] + self.gang_size[g])
            yield range(lo, hi)
            lo = hi

    def members(self, i: int) -> range:
        """The job numbers of job `i`'s gang (of `i` alone, for a single job)."""
        g = self.job_gang[i]
        return range(i, i + 1) if g < 0 else range(int(self.gang_start[g]), int(self.gang_start[g] + self.gang_size[g]))

    @staticmethod
    def job_id(i: int) -> str:
        return f"j{i:09d}"

    def job_number(self, job_id: str) -> int:
        """The number of a backlog job, or KeyError for an id this world never
        made (an initial running job is not a backlog job: `run_number`)."""
        if len(job_id) == 10 and job_id[0] == "j" and job_id[1:].isdigit():
            i = int(job_id[1:])
            if i < self.num_jobs:
                return i
        raise KeyError(job_id)

    def run_number(self, job_id: str) -> int:
        """The number of an initial running job, or KeyError."""
        if len(job_id) == 9 and job_id[0] == "r" and job_id[1:].isdigit():
            i = int(job_id[1:])
            if i < len(self.run_shape):
                return i
        raise KeyError(job_id)

    @staticmethod
    def class_name(preemptible: bool) -> str:
        """The priority class a shape's `preemptible` flag stands for."""
        return "batch" if preemptible else "prod"

    def histograms(self) -> dict:
        """What a seed may not change: counts per category."""
        n0 = int(self.sizes["queued_jobs"])
        per_node = np.bincount(self.run_node, minlength=len(self.node_cores))
        out = {
            "node_cores": np.bincount(self.node_cores).tolist(),
            "job_queue": np.bincount(self.job_queue[:n0], minlength=len(self.queue_names)).tolist(),
            "job_shape": np.bincount(self.job_shape[:n0], minlength=len(self.shapes)).tolist(),
            "run_shape": np.bincount(self.run_shape, minlength=len(self.run_shapes)).tolist(),
            "run_queue": np.bincount(self.run_queue, minlength=len(self.queue_names)).tolist(),
            "runs_per_node_max": int(per_node.max()) if len(per_node) else 0,
            "running_jobs": len(self.run_shape),
            # nodes by how many running jobs they carry and, in a fleet filled
            # by capacity, by the share of their cores those jobs hold (whole %)
            "runs_per_node": np.bincount(per_node).tolist(),
        }
        if "running_fill" in self.sizes:
            used_cpu = np.bincount(
                self.run_node, weights=self.run_shape_req[self.run_shape, 0], minlength=len(per_node)
            ).astype(np.int64)
            out["node_fill_pct"] = np.bincount(100 * used_cpu // self.node_total[:, 0]).tolist()
        if "node_types" in self.sizes:
            out["node_kind"] = dict(zip((k["name"] for k in self.node_kinds), np.bincount(self.node_kind).tolist()))
        if self.job_kinds:
            # of the initial backlog: gangs by cardinality, jobs by kind
            sizes = self.gang_size[self.gang_start < n0]
            out["gangs_by_size"] = {int(c): int((sizes == c).sum()) for c in np.unique(sizes)}
            kind = np.asarray(self.shape_kind)[self.job_shape[:n0]]
            out["jobs_by_kind"] = {str(k): int((kind == k).sum()) for k in dict.fromkeys(self.shape_kind)}
        return out

    # ------------------------------------------------------------ wire ----
    # Only these methods touch the program's protobuf modules (the wire
    # format IS the system's interface); they import lazily so the tables,
    # the checker and their tests need no generated code.

    def _milli(self, req) -> dict:
        """A row of a `[*, R]` table as the wire's map: cpu and memory always,
        another resource where the row has any."""
        return {name: int(a) for r, (name, a) in enumerate(zip(self.resources, req)) if r < 2 or a}

    def _templates(self):
        if self._spec_templates is None:
            from armada_tpu.events import events_pb2 as epb

            def spec(preemptible, req, selector=(), tolerations=()):
                return epb.JobSpec(
                    priority_class=self.class_name(preemptible),
                    resources=epb.Resources(milli=self._milli(req)),
                    node_selector=dict(selector),
                    tolerations=[epb.Toleration(**t) for t in tolerations],
                )

            self._spec_templates = [
                spec(s[2], req, sel, tol)
                for s, req, sel, tol in zip(self.shapes, self.shape_req, self.shape_selector, self.shape_tolerations)
            ]
            self._run_spec_templates = [spec(s[2], req) for s, req in zip(self.run_shapes, self.run_shape_req)]
        return self._spec_templates, self._run_spec_templates

    @staticmethod
    def gang_id(g: int) -> str:
        return f"g{g:08d}"

    def _spec_of(self, i: int, specs):
        """Job `i`'s JobSpec: its shape's template; for a gang's member the
        template with the gang's id, cardinality and uniformity label, one
        message a gang."""
        g = self.job_gang[i]
        if g < 0:
            return specs[self.job_shape[i]]
        spec = self._gang_specs.get(g)
        if spec is None:
            spec = type(specs[0])()
            spec.CopyFrom(specs[self.job_shape[i]])
            spec.gang_id = self.gang_id(g)
            spec.gang_cardinality = int(self.gang_size[g])
            spec.gang_node_uniformity_label = self.shape_uniformity[self.job_shape[i]]
            self._gang_specs[g] = spec
        return spec

    def job_state(self, i: int):
        """Backlog job `i` as the queued, validated JobState of a SyncState."""
        return self.job_states((i,))[0]

    def job_states(self, numbers) -> list:
        from armada_tpu.rpc import rpc_pb2 as pb

        specs, _ = self._templates()
        names, queue, shape, submit = (
            self.queue_names, self.job_queue, self.job_shape, self.job_submit,
        )
        spec_of = (lambda i: self._spec_of(i, specs)) if len(self.gang_size) else (lambda i: specs[shape[i]])
        JobState = pb.JobState
        return [
            JobState(
                job_id=f"j{i:09d}",
                queue=names[queue[i]],
                jobset=JOBSET,
                spec=spec_of(i),
                queued=True,
                validated=True,
                submit_time=float(submit[i]),
            )
            for i in numbers
        ]

    def terminal_state(self, i: int, lease, running_ns: int):
        """Backlog job `i`, leased by `lease` (a RoundLease), now finished: the
        terminal JobState a mirroring control plane sends (its run is over, its
        resources are free)."""
        from armada_tpu.rpc import rpc_pb2 as pb

        m = self.job_state(i)
        m.queued = False
        m.terminal = True
        run = m.run
        run.run_id = lease.run_id
        run.node_id = lease.node_id
        run.node_name = lease.node_id
        run.executor = lease.executor
        run.pool = lease.pool or POOL
        run.scheduled_at_priority = lease.scheduled_at_priority
        run.has_scheduled_at_priority = True
        run.running_ns = int(running_ns)
        return m

    def running_states(self, numbers, now_ns: int, priorities: dict) -> list:
        """Initial running jobs as JobStates with a live run bound to a node."""
        from armada_tpu.rpc import rpc_pb2 as pb

        _, specs = self._templates()
        out = []
        for i in numbers:
            preemptible = self.run_shapes[self.run_shape[i]][2]
            node = self.node_ids[self.run_node[i]]
            out.append(
                pb.JobState(
                    job_id=f"r{i:08d}",
                    queue=self.queue_names[self.run_queue[i]],
                    jobset=JOBSET,
                    spec=specs[self.run_shape[i]],
                    queued=False,
                    validated=True,
                    submit_time=-1.0,
                    run=pb.JobRunState(
                        run_id=f"run{i:08d}",
                        node_id=node,
                        node_name=node,
                        pool=POOL,
                        scheduled_at_priority=priorities[self.class_name(preemptible)],
                        has_scheduled_at_priority=True,
                        running=True,
                        running_ns=now_ns - 10**9,
                    ),
                )
            )
        return out

    def executor_snapshots(self, now_ns: int) -> list:
        """The fleet as ExecutorSnapshot messages (one giant snapshot is not
        what real callers send)."""
        from armada_tpu.events import events_pb2 as epb
        from armada_tpu.rpc import rpc_pb2 as pb

        n_ex = int(self.sizes["executors"])
        per = (len(self.node_ids) + n_ex - 1) // n_ex
        # one message a node type: its resources (cpu and memory, and what else it has), labels, taints
        kinds = [
            (epb.Resources(milli=self._milli(total)), kind["labels"], [epb.Taint(**t) for t in kind["taints"]])
            for kind, total in zip(self.node_kinds, self.kind_total)
        ]
        out = []
        for e in range(n_ex):
            ex = f"ex{e}"
            out.append(
                pb.ExecutorSnapshot(
                    id=ex,
                    pool=POOL,
                    last_update_ns=now_ns,
                    nodes=[
                        pb.Node(
                            id=self.node_ids[i],
                            pool=POOL,
                            executor=ex,
                            resources=kinds[k][0],
                            labels=kinds[k][1],
                            taints=kinds[k][2],
                        )
                        for i, k in enumerate(self.node_kind[e * per : (e + 1) * per].tolist(), e * per)
                    ],
                )
            )
        return out
