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

Nothing here imports the scheduler: the tables are also what the plain checker
(`checker.py`) holds the program's answers against.
"""

from __future__ import annotations

import numpy as np

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
        self.resources = tuple(sizes["resources"])  # ("cpu", "memory")

        cores = np.asarray(sizes["node_cores"], dtype=np.int64)
        self.node_cores = cores[stratified(rng, n_nodes, np.ones(len(cores)))]
        mem_per_core = int(sizes["memory_per_core"])
        # [N, 2] in wire units (thousandths): cpu, memory
        self.node_total = np.stack(
            [self.node_cores * MILLI, self.node_cores * mem_per_core * MILLI], axis=1
        )
        self.node_ids = [f"n{i:06d}" for i in range(n_nodes)]
        self.node_index = {nid: i for i, nid in enumerate(self.node_ids)}
        self.queue_names = [f"q{i:03d}" for i in range(n_queues)]
        self.queue_index = {q: i for i, q in enumerate(self.queue_names)}
        self.queue_weights = 1.0 / np.arange(1, n_queues + 1)  # 1/k demand

        # job shapes: (cpu thousandths, memory units, preemptible)
        shapes, weights = [], []
        p = float(sizes["preemptible_share"])
        for cpu in sizes["job_cpu_milli"]:
            for factor in sizes["job_memory_factor"]:
                for preemptible, share in ((True, p), (False, 1.0 - p)):
                    shapes.append((int(cpu), int(cpu) // 1000 * int(factor) + 1, preemptible))
                    weights.append(share)
        self.shapes = shapes
        self.shape_weights = np.asarray(weights)
        self.shape_req = np.asarray(
            [(cpu, mem * MILLI) for cpu, mem, _ in shapes], dtype=np.int64
        )

        self.job_queue = np.zeros(0, np.int32)
        self.job_shape = np.zeros(0, np.int32)
        self.job_submit = np.zeros(0, np.float64)
        self.extend(int(sizes["queued_jobs"]), 0.0)

        # the initial running set
        run_shapes, run_w = [], []
        rp = float(sizes["running_preemptible_share"])
        for cpu in sizes["running_cpu_milli"]:
            for preemptible, share in ((True, rp), (False, 1.0 - rp)):
                run_shapes.append((int(cpu), int(sizes["running_memory"]), preemptible))
                run_w.append(share)
        self.run_shapes = run_shapes
        self.run_shape_req = np.asarray(
            [(cpu, mem * MILLI) for cpu, mem, _ in run_shapes], dtype=np.int64
        )
        demand = sizes.get("running_queue_demand", "uniform")
        if demand not in ("uniform", "1/k"):
            raise ValueError(f"running_queue_demand {demand!r}: 'uniform' or '1/k'")
        fill = sizes.get("running_fill")
        if fill is None:
            self.run_shape = stratified(rng, int(sizes["running_jobs"]), run_w).astype(np.int32)
        elif "running_jobs" in sizes:
            raise ValueError("running_fill derives running_jobs: give one of the two")
        else:
            self.run_shape, self.run_node = self._fill(rng, float(fill), rp)
        n_runs = len(self.run_shape)
        self.run_queue = stratified(
            rng, n_runs, self.queue_weights if demand == "1/k" else np.ones(n_queues)
        ).astype(np.int32)
        if fill is None:
            # spread over the fleet: a seeded order of the nodes, wrapped (drawn
            # after the queues: the accepted configurations' order of draws)
            self.run_node = np.resize(rng.permutation(n_nodes), n_runs).astype(np.int64)

        self._spec_templates = None
        self._run_spec_templates = None

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
                    room -= (cpu, mem)
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
        grown once for all of them; returns each batch's job numbers."""
        rng = self.rng
        queues, shapes, submits = [self.job_queue], [self.job_shape], [self.job_submit]
        for t0 in t0s:
            queues.append(stratified(rng, n, self.queue_weights).astype(np.int32))
            shapes.append(stratified(rng, n, self.shape_weights).astype(np.int32))
            submits.append(t0 + rng.random(n))
        lo = self.num_jobs
        self.job_queue = np.concatenate(queues)
        self.job_shape = np.concatenate(shapes)
        self.job_submit = np.concatenate(submits)
        return [range(lo + b * n, lo + (b + 1) * n) for b in range(len(t0s))]

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
        return out

    # ------------------------------------------------------------ wire ----
    # Only these methods touch the program's protobuf modules (the wire
    # format IS the system's interface); they import lazily so the tables,
    # the checker and their tests need no generated code.

    def _templates(self):
        if self._spec_templates is None:
            from armada_tpu.events import events_pb2 as epb

            def spec(cpu, mem, preemptible):
                return epb.JobSpec(
                    priority_class=self.class_name(preemptible),
                    resources=epb.Resources(
                        milli={"cpu": int(cpu), "memory": int(mem) * MILLI}
                    ),
                )

            self._spec_templates = [spec(*s) for s in self.shapes]
            self._run_spec_templates = [spec(*s) for s in self.run_shapes]
        return self._spec_templates, self._run_spec_templates

    def job_state(self, i: int):
        """Backlog job `i` as the queued, validated JobState of a SyncState."""
        return self.job_states((i,))[0]

    def job_states(self, numbers) -> list:
        from armada_tpu.rpc import rpc_pb2 as pb

        specs, _ = self._templates()
        names, queue, shape, submit = (
            self.queue_names, self.job_queue, self.job_shape, self.job_submit,
        )
        JobState = pb.JobState
        return [
            JobState(
                job_id=f"j{i:09d}",
                queue=names[queue[i]],
                jobset=JOBSET,
                spec=specs[shape[i]],
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
        by_cores = {
            int(c): epb.Resources(
                milli={
                    "cpu": int(c) * MILLI,
                    "memory": int(c) * int(self.sizes["memory_per_core"]) * MILLI,
                }
            )
            for c in self.sizes["node_cores"]
        }
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
                            resources=by_cores[int(self.node_cores[i])],
                        )
                        for i in range(e * per, min((e + 1) * per, len(self.node_ids)))
                    ],
                )
            )
        return out
