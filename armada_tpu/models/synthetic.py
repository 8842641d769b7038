"""Synthetic dense scheduling problems, built straight as tensors.

For benchmarks and compile checks at reference scale (1M queued jobs x 50k
nodes, BASELINE.json) the host-object path (core.types -> models.problem
build_problem) would spend minutes materialising Python dataclasses; production
rounds keep state device-resident between cycles anyway (the reference's jobDb
cache, scheduler.go:240-246), so scale testing goes straight to the dense form.
Shapes/semantics are identical to build_problem's output.

`synthetic_world` is the spec-level twin: JobSpec/NodeSpec objects feeding the
incremental builder for END-TO-END cycle benchmarks (delta apply + assemble +
upload + kernel + decode), the number the reference's 5s round budget is
actually comparable to.
"""

from __future__ import annotations

import numpy as np

from armada_tpu.models.problem import SchedulingProblem, queue_ordered_gang_index

_INF = np.float32(3.0e38)


# bidstore-style price bands for market benchmarks (pkg/bidstore enumerates
# a small fixed band set; prices are per (queue, band))
SYNTHETIC_BANDS = tuple(f"band{i}" for i in range(8))


def synthetic_bid_price(job) -> float:
    """Deterministic (queue, band) pricer for market benchmarks: stable
    across runs/cycles, spreads bands across queues so the serving
    permutation is non-trivial."""
    import zlib

    h = zlib.crc32(f"{job.queue}/{job.price_band}".encode())
    return 1.0 + (h % 97) / 10.0


def synthetic_world(
    *,
    num_nodes: int,
    num_jobs: int,
    num_queues: int,
    num_runs: int = 0,
    seed: int = 0,
    shape_bucket: int = 8192,
    market: bool = False,
):
    """(config, nodes, queues, specs, running, spec_factory): a JobSpec-level
    world mirroring synthetic_problem's distribution.

    `spec_factory(n, t0)` mints n fresh queued specs with submit times after
    t0 -- the per-cycle arrival delta for steady-state benchmarks.  ResourceList
    instances are shared across jobs of the same shape so 1M specs stay cheap.
    shape_bucket defaults high so +-1000-job deltas never change the padded
    tensor shapes (one compile serves every measured cycle).

    `market=True` marks the pool market-driven and stamps every spec with one
    of 8 price bands (pkg/bidstore-style); pair with a (queue, band) pricer
    such as `synthetic_bid_price`.
    """
    from armada_tpu.core.config import PoolConfig, PriorityClass, SchedulingConfig
    from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob

    rng = np.random.default_rng(seed)
    config = SchedulingConfig(
        shape_bucket=shape_bucket,
        priority_classes={
            "batch": PriorityClass("batch", priority=100, preemptible=True),
            "prod": PriorityClass("prod", priority=1000, preemptible=False),
        },
        default_priority_class="batch",
        pools=(
            PoolConfig("default", market_driven=market, spot_price_cutoff=0.9),
        ),
    )
    factory = config.resource_list_factory()

    queues = [Queue(f"q{i:03d}", 1.0) for i in range(num_queues)]
    nodes = []
    node_shapes = {}
    for i in range(num_nodes):
        cores = int(rng.choice([16, 32, 64, 96]))
        rl = node_shapes.get(cores)
        if rl is None:
            rl = factory.from_mapping({"cpu": str(cores), "memory": str(cores * 4)})
            node_shapes[cores] = rl
        nodes.append(NodeSpec(id=f"n{i:06d}", pool="default", total_resources=rl))

    job_shapes = {}

    def _req(cpu_m: int, mem: int):
        rl = job_shapes.get((cpu_m, mem))
        if rl is None:
            rl = factory.from_mapping({"cpu": f"{cpu_m}m", "memory": str(mem)})
            job_shapes[(cpu_m, mem)] = rl
        return rl

    probs = 1.0 / np.arange(1, num_queues + 1)
    probs /= probs.sum()
    counter = [0]

    def spec_factory(n: int, t0: float) -> list:
        qs = rng.choice(num_queues, size=n, p=probs)
        cpus = rng.choice([500, 1000, 2000, 4000], size=n)
        memm = rng.choice([2, 4, 8], size=n)
        pcs = rng.random(n) < 0.7
        subs = t0 + rng.random(n)
        bands = rng.integers(0, len(SYNTHETIC_BANDS), n) if market else None
        out = []
        base = counter[0]
        counter[0] += n
        for i in range(n):
            out.append(
                JobSpec(
                    id=f"j{base + i:09d}",
                    queue=f"q{qs[i]:03d}",
                    priority_class="batch" if pcs[i] else "prod",
                    submit_time=float(subs[i]),
                    resources=_req(int(cpus[i]), int(cpus[i] // 1000 * memm[i] + 1)),
                    price_band=SYNTHETIC_BANDS[bands[i]] if market else "",
                )
            )
        return out

    specs = spec_factory(num_jobs, 0.0)
    running = []
    if num_runs:
        run_nodes = rng.integers(0, num_nodes, num_runs)
        run_cpus = rng.choice([500, 1000, 2000], size=num_runs)
        run_pc = rng.random(num_runs) < 0.5
        run_q = rng.integers(0, num_queues, num_runs)
        for i in range(num_runs):
            running.append(
                RunningJob(
                    job=JobSpec(
                        id=f"r{i:08d}",
                        queue=f"q{run_q[i]:03d}",
                        priority_class="batch" if run_pc[i] else "prod",
                        submit_time=-1.0,
                        resources=_req(int(run_cpus[i]), 4),
                    ),
                    node_id=f"n{run_nodes[i]:06d}",
                )
            )
    return config, nodes, queues, specs, running, spec_factory


def synthetic_serving_config(config, burst: int):
    """A synthetic_world config as the served-cycle harnesses run it: the
    incremental build on, no rate limiting in the measured cycle, `burst`
    as the per-cycle placement cap."""
    import dataclasses

    return dataclasses.replace(
        config,
        incremental_problem_build=True,
        maximum_scheduling_rate=1e9,
        maximum_per_queue_scheduling_rate=1e9,
        maximum_scheduling_burst=burst,
        maximum_per_queue_scheduling_burst=burst,
    )


def synthetic_job_state(spec, jobset: str = "bench"):
    """One queued, validated synthetic JobSpec as the JobState wire message
    a mirroring control plane sends in SyncState."""
    from armada_tpu.events.convert import job_spec_to_proto
    from armada_tpu.rpc import rpc_pb2 as pb

    return pb.JobState(
        job_id=spec.id,
        queue=spec.queue,
        jobset=jobset,
        spec=job_spec_to_proto(spec),
        priority=spec.priority,
        queued=True,
        validated=True,
        submit_time=spec.submit_time,
    )


def synthetic_mirror(config, nodes, specs, running, now_ns: int, chunk: int = 50_000):
    """(executors, job_state_chunks): the SyncState payload mirroring a
    synthetic_world into a sidecar session.  Nodes ride in 10 executor
    snapshots (one giant snapshot is not what real callers send); the
    queued specs, then the running jobs, come as a GENERATOR of
    `chunk`-sized JobState lists, so a million messages are never alive at
    once and each list fits one gRPC message."""
    from armada_tpu.rpc import rpc_pb2 as pb
    from armada_tpu.scheduler.executors import ExecutorSnapshot

    n_ex = 10
    per = (len(nodes) + n_ex - 1) // n_ex
    executors = [
        ExecutorSnapshot(
            id=f"ex{e}",
            pool="default",
            nodes=tuple(nodes[e * per : (e + 1) * per]),
            last_update_ns=now_ns,
        )
        for e in range(n_ex)
    ]

    def running_state(r, i):
        m = synthetic_job_state(r.job)
        m.queued = False
        m.run.MergeFrom(
            pb.JobRunState(
                run_id=f"run{i:08d}",
                node_id=r.node_id,
                node_name=r.node_id,
                pool="default",
                scheduled_at_priority=config.priority_class(
                    r.job.priority_class
                ).priority,
                has_scheduled_at_priority=True,
                running=True,
                running_ns=now_ns - 10**9,
            )
        )
        return m

    def chunks():
        for lo in range(0, len(specs), chunk):
            yield [synthetic_job_state(s) for s in specs[lo : lo + chunk]]
        for lo in range(0, len(running), chunk):
            yield [
                running_state(r, lo + i)
                for i, r in enumerate(running[lo : lo + chunk])
            ]

    return executors, chunks()


def synthetic_problem(
    *,
    num_nodes: int,
    num_gangs: int,
    num_queues: int,
    num_runs: int = 0,
    num_resources: int = 4,
    num_keys: int = 16,
    num_node_types: int = 8,
    type_sensitive_frac: float = 0.0,
    max_gang_cardinality: int = 1,
    global_burst: int = 1_000,
    perq_burst: int = 1_000,
    node_pad_to: int = 1,
    gang_pad_to: int = 1,
    seed: int = 0,
) -> tuple[SchedulingProblem, dict]:
    """A realistic mixed workload: heterogeneous nodes, skewed queue demand.

    Returns (problem, meta) where meta carries the kernel's static shape args
    (num_levels, max_slots, slot_width).
    """
    rng = np.random.default_rng(seed)
    R = num_resources

    def pad(n, to):
        return max(to, ((n + to - 1) // to) * to)

    N = pad(num_nodes, node_pad_to)
    G = pad(num_gangs, gang_pad_to)
    RJ = pad(max(num_runs, 1), gang_pad_to)
    Q = num_queues

    # Nodes: capacity vectors like (cpu cores*1000m, memory GiB, gpu, storage).
    base = np.array([16_000, 64, 0, 500], np.float32)[:R]
    node_total = np.zeros((N, R), np.float32)
    mult = rng.choice([1.0, 2.0, 4.0, 6.0], size=(num_nodes, 1)).astype(np.float32)
    node_total[:num_nodes] = base[None, :] * mult
    has_gpu = rng.random(num_nodes) < 0.2
    if R >= 3:
        node_total[:num_nodes, 2] = np.where(has_gpu, 8.0, 0.0)
    node_type = np.zeros((N,), np.int32)
    node_type[:num_nodes] = rng.integers(0, num_node_types, num_nodes)
    node_ok = np.zeros((N,), bool)
    node_ok[:num_nodes] = True

    # Static fit: most keys fit most types; a few restrictive keys.
    compat = rng.random((num_keys, num_node_types)) < 0.9
    compat[0] = True  # the common key
    # Heterogeneity (type_sensitive_frac > 0): a fraction of keys declare a
    # per-type throughput profile -- their compat additionally whitelists the
    # profiled types and their bias row tiers them by 1/throughput (the
    # builder-side semantics of core/keys.type_score_tables, synthesized
    # directly in table form here).  key 0 stays the insensitive common key.
    compat_pre_type = compat.copy()
    key_type_row = np.zeros((num_keys,), np.int32)
    type_bias = np.zeros((1, num_node_types), np.float32)
    if type_sensitive_frac > 0 and num_keys > 1 and num_node_types > 1:
        sens = np.where(rng.random(num_keys - 1) < type_sensitive_frac)[0] + 1
        if sens.shape[0]:
            TR = int(sens.shape[0]) + 1
            type_bias = np.zeros((TR, num_node_types), np.float32)
            for row, ki in enumerate(sens, start=1):
                key_type_row[ki] = row
                admits = rng.random(num_node_types) < 0.6
                admits[rng.integers(0, num_node_types)] = True
                thr = rng.choice([0.5, 1.0, 2.0, 4.0], size=num_node_types)
                type_bias[row] = np.where(
                    admits,
                    (
                        (np.float32(1.0) / thr.astype(np.float32) - np.float32(1.0))
                        * np.float32(1024.0)
                    ),
                    0.0,
                ).astype(np.float32)
                compat[ki] &= admits

    # Gangs: skewed queue popularity (zipf-ish), small requests.
    g_queue = np.zeros((G,), np.int32)
    probs = 1.0 / np.arange(1, Q + 1)
    probs /= probs.sum()
    g_queue[:num_gangs] = rng.choice(Q, size=num_gangs, p=probs)
    g_req = np.zeros((G, R), np.float32)
    cpu = rng.choice([500, 1000, 2000, 4000], size=num_gangs).astype(np.float32)
    memf = cpu / 1000.0 * rng.choice([2, 4, 8], size=num_gangs)
    g_req[:num_gangs, 0] = cpu
    if R >= 2:
        g_req[:num_gangs, 1] = memf
    if R >= 3:
        g_req[:num_gangs, 2] = (rng.random(num_gangs) < 0.05).astype(np.float32)
    g_card = np.zeros((G,), np.int32)
    g_card[:num_gangs] = (
        rng.integers(1, max_gang_cardinality + 1, num_gangs)
        if max_gang_cardinality > 1
        else 1
    )
    g_level = np.zeros((G,), np.int32)
    g_level[:num_gangs] = rng.integers(1, 3, num_gangs)
    g_key = np.full((G,), -1, np.int32)
    g_key[:num_gangs] = rng.integers(0, num_keys, num_gangs)
    g_pc = np.zeros((G,), np.int32)
    g_pc[:num_gangs] = g_level[:num_gangs] - 1
    # per-queue FIFO order
    g_order = np.zeros((G,), np.int32)
    order_all = np.argsort(g_queue[:num_gangs], kind="stable")
    rank = np.empty(num_gangs, np.int64)
    counts = np.bincount(g_queue[:num_gangs], minlength=Q)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank[order_all] = np.arange(num_gangs) - starts[g_queue[:num_gangs][order_all]]
    g_order[:num_gangs] = rank
    g_run = np.full((G,), -1, np.int32)
    g_valid = np.zeros((G,), bool)
    g_valid[:num_gangs] = True

    # Running jobs (optional): bound to random nodes at level >= 1.
    run_req = np.zeros((RJ, R), np.float32)
    run_node = np.zeros((RJ,), np.int32)
    run_level = np.ones((RJ,), np.int32)
    run_queue = np.zeros((RJ,), np.int32)
    run_pc = np.zeros((RJ,), np.int32)
    run_preemptible = np.zeros((RJ,), bool)
    run_gang = np.full((RJ,), -1, np.int32)
    run_valid = np.zeros((RJ,), bool)
    if num_runs:
        run_req[:num_runs, 0] = rng.choice([500, 1000, 2000], size=num_runs)
        if R >= 2:
            run_req[:num_runs, 1] = run_req[:num_runs, 0] / 250.0
        run_node[:num_runs] = rng.integers(0, num_nodes, num_runs)
        run_level[:num_runs] = rng.integers(1, 3, num_runs)
        run_queue[:num_runs] = rng.integers(0, Q, num_runs)
        run_pc[:num_runs] = run_level[:num_runs] - 1
        run_preemptible[:num_runs] = rng.random(num_runs) < 0.5
        run_valid[:num_runs] = True

    gq_gang, q_start, q_len = queue_ordered_gang_index(g_queue, g_order, num_gangs, G, Q)

    total_pool = node_total[:num_nodes].sum(axis=0, dtype=np.float64).astype(np.float32)
    drf_mult = np.ones((R,), np.float32)
    scale = node_total.max(axis=0)
    inv_scale = np.where(scale > 0, 1.0 / np.maximum(scale, 1e-9), 0.0).astype(np.float32)

    C = 2
    q_weight = np.ones((Q,), np.float32)
    # constrained demand share ~ demand / total (uncapped)
    demand = np.zeros((Q, R), np.float64)
    np.add.at(demand, g_queue[:num_gangs], (g_req[:num_gangs] * g_card[:num_gangs, None]).astype(np.float64))
    with np.errstate(divide="ignore", invalid="ignore"):
        frac = np.where(total_pool > 0, demand / np.maximum(total_pool, 1e-9), 0.0)
    q_cds = np.clip(frac.max(axis=1), 0.0, None).astype(np.float32)

    problem = SchedulingProblem(
        node_total=node_total,
        node_type=node_type,
        node_ok=node_ok,
        run_req=run_req,
        run_node=run_node,
        run_level=run_level,
        run_queue=run_queue,
        run_pc=run_pc,
        run_preemptible=run_preemptible,
        run_gang=run_gang,
        run_valid=run_valid,
        g_req=g_req,
        g_card=g_card,
        g_level=g_level,
        g_queue=g_queue,
        g_key=g_key,
        g_pc=g_pc,
        g_order=g_order,
        g_run=g_run,
        g_valid=g_valid,
        g_absent=np.zeros_like(g_valid),
        g_price=np.zeros((G,), np.float32),
        g_spot_price=np.zeros((G,), np.float32),
        gq_gang=gq_gang,
        q_start=q_start,
        q_len=q_len,
        q_weight=q_weight,
        q_cds=q_cds,
        q_penalty=np.zeros((Q, R), np.float32),
        compat=compat,
        total_pool=total_pool,
        drf_mult=drf_mult,
        inv_scale=inv_scale,
        round_cap=np.full((R,), _INF, np.float32),
        pc_queue_cap=np.full((C, R), _INF, np.float32),
        protected_fraction=np.float32(1.0),
        global_burst=np.int32(global_burst),
        perq_burst=np.full((Q,), perq_burst, np.int32),
        node_axes=np.ones((R,), np.float32),
        float_total=np.zeros((R,), np.float32),
        market=np.bool_(False),
        spot_cutoff=np.float32(_INF),
        ban_mask=np.zeros((1, N), bool),
        g_ban_row=np.zeros((G,), np.int32),
        type_bias=type_bias,
        key_type_row=key_type_row,
        compat_pre_type=compat_pre_type,
    )
    meta = dict(
        num_levels=3,
        max_slots=max(1, min(num_gangs, global_burst)),
        slot_width=max(1, min(max_gang_cardinality, N)),
    )
    return problem, meta
