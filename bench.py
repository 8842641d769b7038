"""Headline benchmark: one END-TO-END scheduling cycle at reference scale.

Metric (BASELINE.json): wall-clock of a full steady-state cycle over 1M
queued jobs x 50k nodes -- apply the cycle's event deltas (new submits, last
round's leases) to the incremental state, assemble the dense problem, upload,
run the round kernel, decode the decisions back to job/node ids.  The
reference budgets maxSchedulingDuration=5s per round (config.yaml:3) -- that
is the baseline; the north star is <1s.  The kernel-only number (host prep
excluded) is reported alongside as `kernel_s`.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
vs_baseline = 5.0 / value  (x times faster than the reference's round budget).

The process asks `jax.devices()` itself and owns the chip from start to
finish (no probe child: a chip belongs to one process at a time).  With no
accelerator it exits non-zero and prints no metric -- unless the caller
pinned JAX_PLATFORMS=cpu, in which case the metric name and the line say
CPU run.  A round that fell back to the CPU backend mid-run
(device_state.fallbacks > 0) fails the run.

Env knobs for local runs: ARMADA_BENCH_JOBS, ARMADA_BENCH_NODES,
ARMADA_BENCH_QUEUES, ARMADA_BENCH_REPEATS, ARMADA_BENCH_RUNS,
ARMADA_BENCH_BURST (per-cycle placement cap + arrival count -- the
mass-placement datapoint, docs/bench.md); ARMADA_BENCH_POOLS=N sizes the
multi-tenant pool-parallel A/B arm (default 8; =0 skips; _JOBS/_NODES
per-pool knobs); ARMADA_BENCH_EXPLAIN=0 skips
the explain-pass measurement (explain_s + explain_counts keys);
ARMADA_BENCH_VERIFY=0 skips the round-verification measurement
(verify_s + verify_transfers keys -- the extra transfer count the
certification pass is allowed, models/verify.py);
ARMADA_BENCH_HETERO=0 skips the heterogeneous-fleet kernel A/B
(hetero_* keys: 4 node types, ~30% type-sensitive keys, per-iteration
cost vs the insensitive body -- the type-bias gather must stay off the
sequential chain).
The JSON carries the trip counters (kernel_iters / round_iters /
burst10k_iters).

The JSON carries host-load context (loadavg / cpu_count): the host-side
slices (assemble, decode/apply) degrade roughly linearly with CPU
competition -- a headline is only interpretable next to the load it was
measured under.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from armada_tpu.models.fair_scheduler import schedule_round
from armada_tpu.models.problem import SchedulingProblem
from armada_tpu.models.synthetic import synthetic_problem

BASELINE_ROUND_BUDGET_S = 5.0


def bench_devices():
    """The devices this run measures, as jax reports them.  No accelerator
    is a failure (non-zero exit, no metric) unless the caller asked for a
    CPU run by pinning JAX_PLATFORMS=cpu -- a CPU time must never appear
    under the chip's metric name by accident."""
    devices = jax.devices()
    if (
        devices[0].platform == "cpu"
        and os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu"
    ):
        print(
            "bench: jax found no accelerator (jax.devices() -> "
            f"{devices[0].device_kind}); set JAX_PLATFORMS=cpu for a "
            "labelled CPU run",
            file=sys.stderr,
        )
        sys.exit(2)
    return devices


def _arm_watchdog():
    """Last-resort guarantee of the one-JSON-line contract: if the measurement
    stalls, emit a structured failure line and exit before the driver's own
    timeout hits."""
    import threading

    budget = float(os.environ.get("ARMADA_BENCH_WATCHDOG_S", 1200))

    def fire():
        print(
            json.dumps(
                {
                    "metric": "scheduling_round_wall_clock",
                    "value": None,
                    "unit": "s",
                    "vs_baseline": None,
                    "error": f"watchdog: bench stalled >{budget:.0f}s",
                }
            ),
            flush=True,
        )
        os._exit(3)

    t = threading.Timer(budget, fire)
    t.daemon = True
    t.start()
    return t


def _kernel_bench(num_gangs, num_nodes, num_queues, repeats, burst=1_000):
    """Kernel-only round time on pre-built device tensors (round 1's
    headline; kept as the `kernel_s` extra).

    ARMADA_BENCH_SHARDED=1 runs the same round SPMD over ALL visible devices
    (parallel/mesh.py: nodes-axis sharding, XLA collectives over ICI) -- the
    multi-chip path needs zero new code, just more chips visible."""
    problem, meta = synthetic_problem(
        num_nodes=num_nodes,
        num_gangs=num_gangs,
        num_queues=num_queues,
        num_runs=num_nodes // 2,
        global_burst=burst,
        perq_burst=burst,
        seed=7,
        node_pad_to=len(jax.devices()),
    )
    kw = dict(
        num_levels=meta["num_levels"],
        max_slots=meta["max_slots"],
        slot_width=meta["slot_width"],
    )
    if os.environ.get("ARMADA_BENCH_SHARDED") == "1":
        from armada_tpu.parallel import make_mesh, shard_problem, sharded_schedule_round

        mesh = make_mesh()
        print(
            f"bench: sharded kernel over {mesh.devices.size} devices",
            file=sys.stderr,
        )
        # Pre-shard once: the timed repeats must measure the round, not the
        # host->device transfer (sharded_schedule_round's internal
        # device_put is a no-op on already-correctly-sharded arrays).
        problem = shard_problem(problem, mesh)

        def run():
            return sharded_schedule_round(problem, mesh, **kw)

        result = run()
        jax.block_until_ready(result)
        scheduled = int(result.scheduled_count)
        assert scheduled > 0, "sharded round scheduled nothing"
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(run())
            times.append(time.perf_counter() - t0)
        return min(times)
    dev = jax.device_put(SchedulingProblem(*(jnp.asarray(a) for a in problem)))
    # compile + warm up
    result = schedule_round(dev, **kw)
    jax.block_until_ready(result)
    scheduled = int(result.scheduled_count)
    iters = int(result.iterations)
    assert scheduled > 0, f"kernel round scheduled nothing ({iters} iterations)"
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        r = schedule_round(dev, **kw)
        jax.block_until_ready(r)
        times.append(time.perf_counter() - t0)
    return min(times)


def _e2e_bench(
    num_jobs, num_nodes, num_queues, num_runs, repeats, burst, mesh=False,
    measure_explain=True,
):
    """Full steady-state cycle: deltas -> assemble -> upload -> kernel ->
    decode, over the incremental builder (models/incremental.py).  Returns
    (cycle_s, breakdown dict, scheduled count).  mesh=True runs the SAME
    cycle on the mesh serving plane (node-axis-sharded slab +
    MeshDeviceDeltaCache; caller must have armed parallel/serving first)."""
    import dataclasses

    from armada_tpu.core.types import RunningJob
    from armada_tpu.models import begin_decode, decode_result
    from armada_tpu.models.incremental import DeviceProblemCache, IncrementalBuilder
    from armada_tpu.models.slab import DeviceDeltaCache
    from armada_tpu.models.synthetic import synthetic_bid_price, synthetic_world

    # ARMADA_BENCH_MARKET=1: same cycle over a market-driven pool (bid-price
    # candidate order; the incremental tables store (queue, band, submit, id)
    # and permute band slices by price per cycle).
    market = os.environ.get("ARMADA_BENCH_MARKET") == "1"
    config, nodes, queues, specs, running, spec_factory = synthetic_world(
        num_nodes=num_nodes,
        num_jobs=num_jobs,
        num_queues=num_queues,
        num_runs=num_runs,
        seed=7,
        market=market,
        # The pad bucket must swallow a whole cycle's backlog swing, or the
        # job-axis shape oscillates across bucket boundaries and EVERY cycle
        # pays a TPU recompile (measured: 37s/cycle at burst=10k with the
        # default 8k bucket).
        shape_bucket=max(8192, 4 * burst),
    )
    if burst != 1000:
        # Mass-placement shape (post-drain / failover recovery): kernel cost
        # scales with PLACEMENTS, not backlog -- this is the cycle an
        # operator cares about after an outage (burst semantics:
        # ref config/scheduler/config.yaml:99-107).
        config = dataclasses.replace(
            config,
            maximum_scheduling_burst=burst,
            maximum_per_queue_scheduling_burst=burst,
        )
    t0 = time.perf_counter()
    builder = IncrementalBuilder(
        config, "default", queues,
        bid_price_of=synthetic_bid_price if market else None,
    )
    builder.set_nodes(nodes)
    builder.submit_many(specs)
    for r in running:
        builder.lease(r)
    print(
        f"bench: e2e setup (one-time backlog load) {time.perf_counter() - t0:.1f}s",
        file=sys.stderr,
    )
    spec_of = {s.id: s for s in specs}
    kw = None
    # Slot-stable slab deltas by default (O(deltas) upload per cycle); the
    # legacy dense rebuild+full-upload path stays behind a knob for A/B.
    legacy_build = os.environ.get("ARMADA_BENCH_LEGACY_BUILD") == "1"
    if mesh:
        from armada_tpu.parallel.mesh_slab import MeshDeviceDeltaCache

        devcache = MeshDeviceDeltaCache()
    else:
        devcache = DeviceProblemCache() if legacy_build else DeviceDeltaCache()

    from armada_tpu.core.pipeline import pipeline_enabled, prefetch_worthwhile
    from armada_tpu.models.xfer import TRANSFER_STATS
    from armada_tpu.ops.trace import recorder as trace_recorder

    do_prefetch = not legacy_build and prefetch_worthwhile()
    # Trace-derived stage splits (ops/trace.py): armed by default so the
    # headline JSON carries stage_*_s keys -- the "legible without a TPU"
    # per-stage regression surface; ARMADA_BENCH_TRACE=0 disarms both the
    # spans and the keys.
    stages_on = os.environ.get("ARMADA_BENCH_TRACE", "") != "0"
    rec = trace_recorder()
    _last_round: dict = {}

    def cycle(t_now):
        """One measured cycle; the trace cycle wraps _cycle_body via a
        real `with` so an exception can never leak an open cycle trace."""
        if not stages_on:
            return _cycle_body(t_now)
        with rec.cycle("bench_cycle", kind="bench"):
            total, parts, n_sched = _cycle_body(t_now)
        # Trace-derived per-stage splits (ops/trace.py): the SAME span
        # names the serving plane records, so a bench stage regression
        # maps 1:1 onto a production trace (ARMADA_BENCH_TRACE=0 drops
        # these keys).
        parts = dict(parts)
        parts.update(
            {
                f"stage_{name}_s": round(dur, 4)
                for name, dur in rec.last_stages().items()
            }
        )
        return total, parts, n_sched

    def _cycle_body(t_now):
        nonlocal kw
        TRANSFER_STATS.reset()
        t_start = time.perf_counter()
        trace = os.environ.get("ARMADA_BENCH_TRACE") == "1"
        if legacy_build:
            problem, ctx = builder.assemble()
            t_asm = time.perf_counter()
            with rec.span("devcache_apply", full_upload=True):
                dev = devcache.put(problem)
        else:
            bundle, ctx = builder.assemble_delta()
            t_asm = time.perf_counter()
            dev = devcache.apply(bundle)
        if trace:
            t_up = time.perf_counter()
            print(
                f"bench-trace: devapply={t_up - t_asm:.4f}", file=sys.stderr
            )
        kw = dict(
            num_levels=len(ctx.ladder) + 2,
            max_slots=ctx.max_slots,
            slot_width=ctx.slot_width,
        )
        with rec.span("kernel_dispatch"):
            result = schedule_round(dev, **kw)
        # Overlapped decode (default): the compaction + its device->host copy
        # are enqueued BEHIND the kernel without a host sync, and the cycle's
        # decision-independent work (next submits + their slab prefetch)
        # runs while kernel + transfer are in flight (what a sync/fetch
        # round trip costs on this host is not measured).
        # ARMADA_BENCH_NO_OVERLAP=1 (or the global ARMADA_PIPELINE=0
        # escape hatch) restores the blocking sequential flow for A/B (its
        # keys split upload+kernel vs decode).
        overlap = (
            pipeline_enabled()
            and os.environ.get("ARMADA_BENCH_NO_OVERLAP") != "1"
        )
        if overlap:
            t_disp0 = time.perf_counter()
            with rec.span("decode_dispatch"):
                finish = begin_decode(result, ctx)
            t_disp = time.perf_counter()
            fresh = spec_factory(burst, t_now)
            for s in fresh:
                spec_of[s.id] = s
            builder.submit_many(fresh)  # carries its own trace span
            # Shadow-pipeline stage (b): ship the fresh submits' slab rows
            # while the kernel + result transfer are in flight, so the
            # next cycle's device apply only carries lease/evict rows.
            prefetched = (
                builder.prefetch_content(devcache) if do_prefetch else 0
            )
            t_kernel = time.perf_counter()  # dispatch + overlapped submits
            if trace:
                print(
                    f"bench-trace: dispatch={t_disp - t_disp0:.4f} "
                    f"submits={t_kernel - t_disp:.4f} "
                    f"prefetched_rows={prefetched}",
                    file=sys.stderr,
                )
            if trace:
                # Split finish() into its device wait (kernel drain + the
                # async device->host copy) and the host-side decode, and
                # time the builder apply separately (decode_apply is the
                # largest host slice of the cycle).  The barrier is a
                # scalar FETCH, the repo-wide fetch-not-barrier rule; it
                # adds one transfer, so the traced cycle is slightly
                # slower than the untraced one.
                with rec.span("fetch_decode", scalar_barrier=True):
                    int(result.n_slots)
                    t_drain = time.perf_counter()
                    outcome = finish()
                t_decode = time.perf_counter()
                print(
                    f"bench-trace: drain={t_drain - t_kernel:.4f} "
                    f"fetch+decode={t_decode - t_drain:.4f}",
                    file=sys.stderr,
                )
            else:
                with rec.span("fetch_decode"):
                    outcome = finish()
        else:
            with rec.span("fetch_decode"):
                jax.block_until_ready(result)
                t_kernel = time.perf_counter()
                outcome = decode_result(result, ctx)
        # Feed the decisions back (part of the measured cycle: the reference
        # applies SchedulerResult to the jobDb inside its 5s budget too).
        t_apply0 = time.perf_counter()
        with rec.span("apply", scheduled=len(outcome.scheduled)):
            builder.remove_many(outcome.scheduled.keys())
            leases = []
            for jid, nid in outcome.scheduled.items():
                spec = spec_of.pop(jid, None)
                if spec is not None:
                    leases.append(RunningJob(job=spec, node_id=nid))
            builder.lease_many(leases)
            for jid in outcome.preempted:
                builder.unlease(jid)
        if trace:
            print(
                f"bench-trace: apply={time.perf_counter() - t_apply0:.4f}",
                file=sys.stderr,
            )
        if not overlap:
            # same outcome-independent count as the overlapped arm, so the
            # A/B times identical host work and neither backlog drifts
            fresh = spec_factory(burst, t_now)
            for s in fresh:
                spec_of[s.id] = s
            builder.submit_many(fresh)  # carries its own trace span
        t_end = time.perf_counter()
        # Kept for the post-loop explain-pass measurement (outside the
        # timed cycle): round-final device tensors + decode ctx.
        _last_round.update(dev=dev, result=result, ctx=ctx)
        return (
            t_end - t_start,
            {
                "assemble_s": round(t_asm - t_start, 4),
                "upload_kernel_s": round(t_kernel - t_asm, 4),
                "decode_apply_s": round(t_end - t_kernel, 4),
                # Trips of the placement loop: an exact count on any
                # backend.  Rides the compact decode buffer: free.
                "kernel_iters": outcome.kernel_iters,
                "round_iters": outcome.num_iterations,
                # Per-cycle device-transfer counters (models/xfer.py):
                # counts and bytes are exact on any backend, so payload
                # regressions stay legible without a TPU (the cost per
                # transfer on this host is not measured).
                **TRANSFER_STATS.snapshot(),
            },
            len(outcome.scheduled),
        )

    # warm-up cycle compiles the kernel at these shapes
    cycle(100.0)
    # The warm-up cycle carries the ONE full sharded slab upload (steady
    # cycles scatter replicated delta rows, counted shards=1), so the
    # per-chip upload-pressure keys only exist in ITS stats -- capture them
    # before the first measured cycle's reset wipes them.
    warm_chip_xfer = {
        k: v
        for k, v in TRANSFER_STATS.snapshot().items()
        if k in ("up_chip_bytes", "up_sharded_transfers")
    }
    best, best_parts, scheduled = None, None, 0
    for rep in range(repeats):
        total, parts, n_sched = cycle(200.0 + rep)
        if best is None or total < best:
            best, best_parts, scheduled = total, parts, n_sched
    assert scheduled > 0, "e2e cycle scheduled nothing"
    for k, v in warm_chip_xfer.items():
        best_parts.setdefault(k, v)
    # Explain pass (models/explain.py; ARMADA_BENCH_EXPLAIN=0 skips): the
    # unschedulable-reason attribution over the LAST measured round's slab,
    # timed dispatch->fetch at steady state (first run pays the one-off jit
    # compile) -- explain_s is the full off-critical-path cost of an
    # explain-cadence round, and explain_transfers pins the ONE extra
    # device->host transfer the pass is allowed.
    if (
        measure_explain
        and os.environ.get("ARMADA_BENCH_EXPLAIN", "1") != "0"
        and _last_round
    ):
        from armada_tpu.models import explain as _explain

        t_explain, out = None, None
        for _ in range(2):
            TRANSFER_STATS.reset()
            t0 = time.perf_counter()
            out = _explain.finish_explain(
                _explain.dispatch_explain(
                    _last_round["dev"], _last_round["result"],
                    _last_round["ctx"],
                ),
                _last_round["ctx"],
            )
            t_explain = time.perf_counter() - t0
        if out is not None:
            best_parts["explain_s"] = round(t_explain, 4)
            best_parts["explain_counts"] = {
                k: v for k, v in out.counts.items() if v
            }
            best_parts["explain_transfers"] = TRANSFER_STATS.snapshot()[
                "down_transfers"
            ]
    # Round verification (models/verify.py; ARMADA_BENCH_VERIFY=0 skips):
    # the conservation-invariant + fingerprint certification over the LAST
    # measured round's slab, timed dispatch->verdict at steady state (first
    # run pays the one-off jit compile).  verify_s is the full cost an
    # armed round adds off the critical path, and verify_transfers pins
    # the ONE extra device->host transfer the pass is allowed -- the
    # compact fetch it cross-checks is the round's own, fetched OUTSIDE
    # the timed window here exactly as it is in production.
    if (
        measure_explain
        and os.environ.get("ARMADA_BENCH_VERIFY", "1") != "0"
        and _last_round
    ):
        from armada_tpu.models import verify as _verify
        from armada_tpu.models.problem import _dispatch_compact, _fetch_compact

        t_verify, verdict = None, None
        for _ in range(2):
            d = _dispatch_compact(
                _last_round["result"], _last_round["ctx"]
            )
            if d is None:
                break
            _fetch_compact(
                _last_round["result"], _last_round["ctx"], dispatched=d
            )
            TRANSFER_STATS.reset()
            t0 = time.perf_counter()
            vd = _verify.dispatch_verify(
                _last_round["dev"], _last_round["result"], d,
                _last_round["ctx"],
            )
            if vd is None:
                break
            verdict = _verify.finish_verify(vd, _last_round["ctx"])
            t_verify = time.perf_counter() - t0
        if verdict is not None:
            best_parts["verify_s"] = round(t_verify, 4)
            best_parts["verify_transfers"] = TRANSFER_STATS.snapshot()[
                "down_transfers"
            ]
    return best, best_parts, scheduled


def _sidecar_bench(num_jobs, num_nodes, num_queues, num_runs, repeats, burst):
    """ARMADA_BENCH_SIDECAR=1: the same steady-state cycle driven through
    the scheduling sidecar (armada_tpu.api.Schedule) -- the Go-interop
    boundary.  The 1M-job mirror + incremental builders + device slabs live
    SERVER-side (loaded once); each measured cycle ships only the delta
    (burst fresh submits in, the round's leases out).

    Two arms against the SAME live session: `direct` invokes the service
    handlers in-process (proto in/proto out, no sockets), `wire` goes
    through real gRPC on localhost.  wire - direct isolates the boundary
    cost; wire itself is the full sidecar cycle an external control plane
    would see.  Returns a dict of sidecar_* keys for the JSON line.
    """
    from armada_tpu.models.synthetic import (
        synthetic_job_state,
        synthetic_mirror,
        synthetic_serving_config,
        synthetic_world,
    )
    from armada_tpu.rpc import rpc_pb2 as pb
    from armada_tpu.rpc.client import ScheduleClient
    from armada_tpu.rpc.server import make_server
    from armada_tpu.scheduler.sidecar import ScheduleSidecar

    t0 = time.perf_counter()
    config, nodes, queues, specs, running, spec_factory = synthetic_world(
        num_nodes=num_nodes,
        num_jobs=num_jobs,
        num_queues=num_queues,
        num_runs=num_runs,
        seed=7,
        shape_bucket=max(8192, 4 * burst),
    )
    config = synthetic_serving_config(config, burst)
    now0 = 10**12
    clock = [now0]
    sidecar = ScheduleSidecar(config, clock_ns=lambda: clock[0])
    server, port = make_server(schedule_sidecar=sidecar)
    client = ScheduleClient(f"127.0.0.1:{port}")
    sid = client.create_session("bench")

    # One-time mirror load through the service handlers (in-process: the
    # boundary claim is about the per-cycle path, and 100+ full-size gRPC
    # messages would only measure localhost socket throughput).
    session = sidecar.session(sid)
    executors, job_chunks = synthetic_mirror(
        config, nodes, specs, running, now0
    )
    session.apply_sync(executors=executors, queues=queues)
    for states in job_chunks:
        sidecar.handle_sync(pb.SyncStateRequest(session_id=sid, jobs=states))
    setup_s = time.perf_counter() - t0
    print(f"bench: sidecar mirror load {setup_s:.1f}s", file=sys.stderr)

    def cycle(wire: bool):
        clock[0] += 10**9
        fresh = spec_factory(burst, clock[0] / 1e9)
        states = [synthetic_job_state(s) for s in fresh]
        t_start = time.perf_counter()
        if wire:
            client.sync_state(sid, jobs=states)
            resp = client.schedule_round(sid, now_ns=clock[0])
        else:
            sidecar.handle_sync(
                pb.SyncStateRequest(session_id=sid, jobs=states)
            )
            resp = sidecar.handle_round(
                pb.ScheduleRoundRequest(session_id=sid, now_ns=clock[0])
            )
        dt = time.perf_counter() - t_start
        return dt, len(resp.scheduled)

    cycle(wire=False)  # warm-up: compiles the kernel at these shapes
    direct_times, wire_times, scheduled = [], [], 0
    for _ in range(repeats):
        dt, _n = cycle(wire=False)
        direct_times.append(dt)
        dt, n = cycle(wire=True)
        wire_times.append(dt)
        scheduled = n
    assert scheduled > 0, "sidecar cycle scheduled nothing"
    server.stop(0)
    client.close()
    return {
        "sidecar_cycle_s": round(min(wire_times), 4),
        "sidecar_direct_s": round(min(direct_times), 4),
        "sidecar_boundary_s": round(min(wire_times) - min(direct_times), 4),
        "sidecar_setup_s": round(setup_s, 1),
        "sidecar_scheduled_per_cycle": scheduled,
    }


def _mesh_bench(num_jobs, num_nodes, num_queues, num_runs, repeats, burst, platform):
    """ARMADA_BENCH_MESH=N: the e2e steady cycle on the mesh serving plane
    (node-axis-sharded slab, sharded kernel round, compact decode from
    sharded outputs) over N devices (fewer visible is an error).  Adds mesh_cycle_s /
    mesh_devices to the one-line JSON; a 5M-jobs x 200k-nodes scale axis --
    the backlog a single chip's slab cannot hold -- runs only on a REAL
    mesh (accelerator platform; ARMADA_BENCH_MESH_SCALE=0 skips it)."""
    import jax as _jax

    try:
        n = int(os.environ.get("ARMADA_BENCH_MESH", "0"))
    except ValueError:
        n = 0
    avail = len(_jax.devices())
    if n < 2 or n > avail:
        raise RuntimeError(
            f"mesh arm requested {n} devices, {avail} visible"
        )
    from armada_tpu.parallel.serving import mesh_serving

    mesh_serving().configure(n)
    out = {"mesh_devices": n}
    try:
        print(f"bench: mesh arm over {n} devices", file=sys.stderr)
        cycle_s, parts, scheduled = _e2e_bench(
            num_jobs, num_nodes, num_queues, num_runs, repeats, burst,
            mesh=True, measure_explain=False,
        )
        out["mesh_cycle_s"] = round(cycle_s, 4)
        out["mesh_scheduled_per_cycle"] = scheduled
        for key in ("up_chip_bytes", "up_sharded_transfers"):
            if key in parts:
                out[f"mesh_{key}"] = parts[key]
        if (
            platform != "cpu"
            and os.environ.get("ARMADA_BENCH_MESH_SCALE", "1") != "0"
        ):
            # The scale axis only a mesh can represent: 4x nodes, 5x jobs.
            # Virtual CPU "meshes" share one socket and would measure
            # nothing but collective overhead at a 40x bigger problem, so
            # this leg is real-accelerator only.
            scale_jobs = int(os.environ.get("ARMADA_BENCH_MESH_SCALE_JOBS", 5_000_000))
            scale_nodes = int(os.environ.get("ARMADA_BENCH_MESH_SCALE_NODES", 200_000))
            print(
                f"bench: mesh scale axis {scale_jobs} x {scale_nodes}",
                file=sys.stderr,
            )
            scale_s, _, scale_sched = _e2e_bench(
                scale_jobs,
                scale_nodes,
                num_queues,
                scale_nodes // 2,
                repeats=max(1, repeats // 3),
                burst=burst,
                mesh=True,
                measure_explain=False,
            )
            out["mesh_scale_cycle_s"] = round(scale_s, 4)
            out["mesh_scale_jobs"] = scale_jobs
            out["mesh_scale_nodes"] = scale_nodes
            out["mesh_scale_scheduled_per_cycle"] = scale_sched
    finally:
        mesh_serving().configure(0)
    return out


def _soak_bench() -> dict:
    """ARMADA_BENCH_SOAK (default on; =0 skips): a short sustained-traffic
    window through the full serving stack (armada_tpu/loadgen/soak.py) --
    submit/cancel/reprioritise churn via SubmitServer -> eventlog -> ingest
    -> scheduler -> fake executors -- with the streaming SLO layer's
    p50/p95/p99 cycle latency, time-to-first-lease and ingest->visible lag
    folded into the bench line as soak_* keys.  The soak world is small and
    independent of the 1M-row arms above (it measures the SERVING loop's
    latency distribution, not peak problem scale); ARMADA_BENCH_SOAK_S /
    ARMADA_BENCH_SOAK_RATE downscale further for CPU hosts."""
    import tempfile

    from armada_tpu.loadgen.soak import SoakConfig, run_soak

    window_s = float(os.environ.get("ARMADA_BENCH_SOAK_S", 45.0))
    rate = float(os.environ.get("ARMADA_BENCH_SOAK_RATE", 200.0))
    print(
        f"bench: soak arm ({window_s:.0f}s window @ {rate:.0f} events/s)",
        file=sys.stderr,
    )
    cfg = SoakConfig(
        window_s=window_s,
        target_eps=rate,
        drain_s=min(10.0, window_s / 4),
        seed=7,
    )
    with tempfile.TemporaryDirectory(prefix="armada-bench-soak-") as d:
        report = run_soak(cfg, d)
    out = {
        "soak_window_s": report["window_s"],
        "soak_eps": report["achieved_eps"],
        "soak_target_eps": report["target_eps"],
        "soak_cycles": report["schedule_cycles"],
        "soak_ok": report["ok"],
    }
    for key in (
        "cycle_p50_s",
        "cycle_p95_s",
        "cycle_p99_s",
        "ttfl_p50_s",
        "ttfl_p95_s",
        "ttfl_p99_s",
        "ingest_lag_p99_s",
    ):
        if key in report:
            out["soak_" + key] = report[key]
    return out


def _pools_bench() -> dict:
    """ARMADA_BENCH_POOLS=N (default 8; =0 skips): the multi-tenant cycle
    A/B (round 17).  Splits one small world into N pools -- every job
    restricted to exactly one pool, identical node fleets, so the cycle
    certifies independence and the pool-parallel path engages -- and times
    the SAME FairSchedulingAlgo.schedule cycle serial vs pool-parallel.
    Shape-identical pools stack into one kernel launch, so this measures
    the dispatch-count/trip-count economics (P launches -> 1).  The world is deliberately small (the "hundreds of small
    tenants" shape, ARMADA_BENCH_POOLS_JOBS/NODES per pool); decisions are
    asserted identical between the arms, not just timed."""
    import dataclasses as _dc

    import numpy as _np

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

    n_pools = int(os.environ.get("ARMADA_BENCH_POOLS", 8))
    jobs_per_pool = int(os.environ.get("ARMADA_BENCH_POOLS_JOBS", 192))
    nodes_per_pool = int(os.environ.get("ARMADA_BENCH_POOLS_NODES", 4))
    num_queues = int(os.environ.get("ARMADA_BENCH_POOLS_QUEUES", 16))
    repeats = int(os.environ.get("ARMADA_BENCH_POOLS_REPEATS", 5))
    now_ns = 10**12
    print(
        f"bench: pools arm ({n_pools} pools x {jobs_per_pool} jobs / "
        f"{nodes_per_pool} nodes)",
        file=sys.stderr,
    )

    cfg = SchedulingConfig(
        shape_bucket=32,
        priority_classes={
            "high": PriorityClass("high", priority=1000, preemptible=False)
        },
        default_priority_class="high",
        incremental_problem_build=True,
        pools=tuple(PoolConfig(f"bp{i}") for i in range(n_pools)),
        # Unlimited rate buckets: the arm replays the SAME cycle (txn
        # aborts between repeats) against a frozen clock, so armed buckets
        # would drain on the warm-up and the measured repeats would
        # schedule nothing.
        maximum_scheduling_rate=0.0,
        maximum_per_queue_scheduling_rate=0.0,
    )
    F = cfg.resource_list_factory()

    def make_world():
        jdb = JobDb(cfg)
        feed = IncrementalProblemFeed(cfg)
        feed.attach(jdb)
        txn = jdb.write_txn()
        for p in range(n_pools):
            # per-POOL seed: tenants are statistically identical, so every
            # pool lands in the same padded buckets and the whole window
            # stacks into one launch -- the shape-matching scenario the
            # mechanism exists for (real fleets get there via shape_bucket
            # quantization)
            rng = _np.random.default_rng(17)
            pool = f"bp{p}"
            for j in range(jobs_per_pool):
                txn.upsert(
                    Job(
                        spec=JobSpec(
                            id=f"bp{p}-{j:05d}",
                            queue=f"bq{j % num_queues}",
                            priority_class="high",
                            submit_time=float(j),
                            pools=(pool,),
                            resources=F.from_mapping(
                                {
                                    "cpu": str(1 + int(rng.integers(0, 8))),
                                    "memory": "1",
                                }
                            ),
                        ),
                        queued=True,
                        validated=True,
                        pools=(pool,),
                    )
                )
        txn.commit()
        executors = [
            ExecutorSnapshot(
                id=f"bex{p}",
                pool=f"bp{p}",
                last_update_ns=now_ns,
                nodes=tuple(
                    NodeSpec(
                        id=f"bn{p}-{k}",
                        pool=f"bp{p}",
                        # 12 cpu x 4 nodes: the fill leases ~24 runs/pool,
                        # safely inside one run-axis pad bucket, so steady
                        # cycles keep every pool shape-identical
                        total_resources=F.from_mapping(
                            {"cpu": "12", "memory": "64"}
                        ),
                    )
                    for k in range(nodes_per_pool)
                ),
            )
            for p in range(n_pools)
        ]
        algo = FairSchedulingAlgo(
            cfg,
            queues=lambda: [Queue(f"bq{i}", 1.0 + i) for i in range(num_queues)],
            clock_ns=lambda: now_ns,
            feed=feed,
            collect_stats=False,
        )
        return jdb, algo, executors

    def run_arm(parallel: bool):
        prev = os.environ.get("ARMADA_POOL_PARALLEL")
        os.environ["ARMADA_POOL_PARALLEL"] = "1" if parallel else "0"
        try:
            jdb, algo, executors = make_world()
            # Fill cycle (committed): tenants lease up to capacity, the
            # rest stays pending -- the many-mostly-full-tenant STEADY
            # state the pool-parallel claim is about.  Measured cycles
            # then pay each pool's full round (assemble, upload, kernel,
            # compact fetch, decode) with few decisions -- exactly the
            # per-pool fixed costs the dispatch/fetch split and the
            # stacked launch amortize.
            decisions = []
            txn = jdb.write_txn()
            res = algo.schedule(txn, executors, now_ns)
            txn.commit()
            decisions.append(
                sorted((job.id, run.node_id) for job, run in res.scheduled)
            )
            best = None
            for r in range(repeats + 1):
                txn = jdb.write_txn()
                t0 = time.perf_counter()
                res = algo.schedule(txn, executors, now_ns)
                dt = time.perf_counter() - t0
                decisions.append(
                    sorted((job.id, run.node_id) for job, run in res.scheduled)
                )
                txn.commit()
                if r > 0:  # r=0 warms the steady-shape compiles
                    best = dt if best is None else min(best, dt)
            return best, decisions
        finally:
            if prev is None:
                os.environ.pop("ARMADA_POOL_PARALLEL", None)
            else:
                os.environ["ARMADA_POOL_PARALLEL"] = prev

    serial_s, serial_decisions = run_arm(False)
    reset_pool_serving_stats()
    parallel_s, parallel_decisions = run_arm(True)
    snap = pool_serving_stats().snapshot()
    decisions_equal = parallel_decisions == serial_decisions
    if not decisions_equal:
        # Report, never crash the headline: the equality CONTRACT is pinned
        # by tests/test_pool_parallel.py; here it rides the JSON so a
        # TPU-host divergence is legible without killing the bench line.
        print(
            "bench: POOLS ARM DIVERGED (pools_decisions_equal=false)",
            file=sys.stderr,
        )
    print(
        f"bench: pools x{n_pools} steady cycle serial {serial_s:.4f}s -> "
        f"parallel {parallel_s:.4f}s ({snap['stacked_launches']} stacked "
        f"launches, overlap ratio {snap['last_overlap_ratio']})",
        file=sys.stderr,
    )
    return {
        "pools_n": n_pools,
        "pools_serial_s": round(serial_s, 4),
        "pools_parallel_s": round(parallel_s, 4),
        "pools_speedup": round(serial_s / max(parallel_s, 1e-9), 2),
        "pools_decisions_equal": decisions_equal,
        "pools_stacked_launches": snap["stacked_launches"],
        "pools_stacked_pools": snap["stacked_pools"],
        "pools_overlap_ratio": snap["last_overlap_ratio"],
        "pools_scheduled_fill": len(serial_decisions[0]),
        "pools_scheduled_steady": sum(len(d) for d in serial_decisions[1:]),
    }


def _restart_bench() -> dict:
    """ARMADA_BENCH_RESTART (default on; =0 skips): bounded-replay restart
    cost (scheduler/checkpoint.py).  Builds a serving store from a synthetic
    event backlog, checkpoints, appends a suffix of further events, wipes
    the store, and times snapshot-restore + suffix-only replay -- the RTO
    path `serve` runs after a crash.  Replayed-sequence counts ride along
    so a regression in the FENCE (replaying more than the suffix) is
    legible without timing.  ARMADA_BENCH_RESTART_EVENTS downscales."""
    import tempfile
    import uuid

    from armada_tpu.eventlog import EventLog
    from armada_tpu.eventlog.publisher import Publisher
    from armada_tpu.events import events_pb2 as pb
    from armada_tpu.ingest.converter import convert_sequences
    from armada_tpu.ingest.pipeline import IngestionPipeline
    from armada_tpu.ingest.schedulerdb import SchedulerDb
    from armada_tpu.scheduler.checkpoint import (
        CheckpointManager,
        maybe_restore,
        snapshot_plane,
    )

    n_base = int(os.environ.get("ARMADA_BENCH_RESTART_EVENTS", 20_000))
    n_suffix = max(1, n_base // 10)

    def _submit_batch(publisher, lo, n):
        seqs = []
        for i in range(lo, lo + n):
            seqs.append(
                pb.EventSequence(
                    queue=f"rq{i % 8}",
                    jobset="restart-bench",
                    events=[
                        pb.Event(
                            created_ns=i + 1,
                            submit_job=pb.SubmitJob(
                                job_id=uuid.uuid4().hex,
                                spec=pb.JobSpec(priority_class="default"),
                            ),
                        )
                    ],
                )
            )
        publisher.publish(seqs)

    with tempfile.TemporaryDirectory(prefix="armada-bench-restart-") as d:
        log = EventLog(os.path.join(d, "log"), num_partitions=2)
        db = SchedulerDb(os.path.join(d, "scheduler.db"))
        publisher = Publisher(log)
        pipe = IngestionPipeline(
            log, db, convert_sequences, consumer_name="scheduler"
        )
        _submit_batch(publisher, 0, n_base)
        pipe.run_until_caught_up()
        mgr = CheckpointManager(os.path.join(d, "checkpoints"))
        t0 = time.perf_counter()
        mgr.write(snapshot_plane(db))
        snapshot_s = time.perf_counter() - t0
        _submit_batch(publisher, n_base, n_suffix)
        db.close()
        os.remove(os.path.join(d, "scheduler.db"))
        t0 = time.perf_counter()
        db2 = SchedulerDb(os.path.join(d, "scheduler.db"))
        restored = maybe_restore(db2, mgr)
        pipe2 = IngestionPipeline(
            log,
            db2,
            convert_sequences,
            consumer_name="scheduler",
            start_positions=db2.positions("scheduler"),
        )
        replayed = pipe2.run_until_caught_up()
        restart_s = time.perf_counter() - t0
        jobs_after = len(db2.fetch_job_updates(0, 0)[0])
        db2.close()
        log.close()
    print(
        f"bench: restart arm snapshot {snapshot_s:.3f}s, restore+replay "
        f"{restart_s:.3f}s ({replayed}/{n_base + n_suffix} sequences "
        f"replayed)",
        file=sys.stderr,
    )
    return {
        "restart_replay_s": round(restart_s, 4),
        "restart_snapshot_s": round(snapshot_s, 4),
        "restart_replayed_sequences": replayed,
        "restart_total_sequences": n_base + n_suffix,
        "restart_restored": bool(restored.get("restored")),
        "restart_jobs": jobs_after,
    }


def _hetero_bench(num_gangs, num_nodes, num_queues, repeats, burst) -> dict:
    """ARMADA_BENCH_HETERO (default on; =0 skips): heterogeneity-aware
    kernel A/B at the headline shape -- the SAME synthetic round with 4
    node types, ~30% of scheduling keys carrying a per-type throughput
    profile (type_bias rows gathered in-loop, models/fair_scheduler.py),
    vs the type-insensitive baseline at identical array shapes.  The
    per-iteration ratio is the evidence that the bias gather stays OFF the
    sequential chain (precomputed [TR,T] table + one row gather, the
    ban_mask pattern); a regression here means in-loop compute crept onto
    a gathered row.  ARMADA_BENCH_HETERO_TYPES / _FRAC reshape the fleet."""
    n_types = int(os.environ.get("ARMADA_BENCH_HETERO_TYPES", 4))
    frac = float(os.environ.get("ARMADA_BENCH_HETERO_FRAC", 0.3))

    def _arm(sensitive_frac: float):
        problem, meta = synthetic_problem(
            num_nodes=num_nodes,
            num_gangs=num_gangs,
            num_queues=num_queues,
            num_runs=num_nodes // 2,
            num_node_types=n_types,
            type_sensitive_frac=sensitive_frac,
            global_burst=burst,
            perq_burst=burst,
            seed=7,
            node_pad_to=len(jax.devices()),
        )
        kw = dict(
            num_levels=meta["num_levels"],
            max_slots=meta["max_slots"],
            slot_width=meta["slot_width"],
        )
        dev = jax.device_put(
            SchedulingProblem(*(jnp.asarray(a) for a in problem))
        )
        result = schedule_round(dev, **kw)  # compile + warm up
        jax.block_until_ready(result)
        scheduled = int(result.scheduled_count)
        iters = int(result.kernel_iters)
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(schedule_round(dev, **kw))
            times.append(time.perf_counter() - t0)
        return min(times), scheduled, iters, int(problem.type_bias.shape[0])

    base_s, base_sched, base_iters, base_tr = _arm(0.0)
    het_s, het_sched, het_iters, het_tr = _arm(frac)
    assert base_tr == 1, "baseline arm unexpectedly carries bias rows"
    assert het_tr > 1, (
        "hetero arm compiled the insensitive body -- no sensitive keys drawn"
    )
    assert het_sched > 0, "hetero round scheduled nothing"
    out = {
        "hetero_kernel_s": round(het_s, 4),
        "hetero_base_kernel_s": round(base_s, 4),
        "hetero_scheduled": het_sched,
        "hetero_types": n_types,
        "hetero_bias_rows": het_tr,
    }
    # Normalize by trip count: the arms place different sets (the bias
    # re-ranks nodes and the whitelist narrows feasibility), so wall-clock
    # alone conflates per-iteration cost with trip count.
    if base_iters and het_iters:
        out["hetero_per_iter_ratio"] = round(
            (het_s / het_iters) / (base_s / base_iters), 3
        )
    print(
        f"bench: hetero kernel {het_s:.4f}s vs base {base_s:.4f}s "
        f"(per-iter ratio {out.get('hetero_per_iter_ratio')})",
        file=sys.stderr,
    )
    return out


def _ingest_bench() -> dict:
    """ARMADA_BENCH_INGEST (default on; =0 skips): ingest-throughput A/B --
    the serial IngestionPipeline vs the partition-parallel plane
    (ingest/shards.py, ARMADA_BENCH_INGEST_SHARDS workers, default 8) over
    the same pre-published full-lifecycle backlog (submit/validate/lease/
    assign/run/succeed per job: the steady serving mix, production-shaped
    time-ordered job ids).  Drained state is checked bit-equal (serials
    excluded, as everywhere).  Best of ARMADA_BENCH_INGEST_REPEATS sharded
    drains rides the record (page-cache variance; the serial leg is flat).
    A third arm repeats the sharded drain with the STORE sharded too
    (ARMADA_BENCH_INGEST_STORE_SHARDS, default = worker count; =0 skips;
    must divide the worker count) -- the ingest_store_* keys are the
    shared-writer-vs-per-shard-file A/B.  ARMADA_BENCH_INGEST_JOBS
    downscales.  NOTE: the speedup needs real cores -- a 1-CPU host
    reports ~1x by construction."""
    import tempfile
    import uuid

    from armada_tpu.eventlog import EventLog
    from armada_tpu.eventlog.publisher import Publisher
    from armada_tpu.events import events_pb2 as pb
    from armada_tpu.ingest import (
        IngestionPipeline,
        PartitionedIngestionPipeline,
        SchedulerDb,
        convert_sequences,
    )

    n_jobs = int(os.environ.get("ARMADA_BENCH_INGEST_JOBS", 60_000))
    shards = int(os.environ.get("ARMADA_BENCH_INGEST_SHARDS", 8))
    repeats = int(os.environ.get("ARMADA_BENCH_INGEST_REPEATS", 2))
    partitions = max(shards, 8)
    base_ms = int(time.time() * 1e3)

    def _id(i: int) -> str:
        # the production job-id shape (server/submit.py): time-prefixed,
        # so PK b-tree inserts are append-ish instead of random
        return f"{base_ms + i:013x}-{uuid.uuid4().hex[:12]}"

    def _seqs():
        out = []
        for i in range(n_jobs):
            jid, rid = _id(i), _id(i)
            out.append(
                pb.EventSequence(
                    queue=f"iq{i % 8}",
                    jobset=f"ijs{i % 512}",
                    events=[
                        pb.Event(
                            created_ns=i + 1,
                            submit_job=pb.SubmitJob(
                                job_id=jid,
                                spec=pb.JobSpec(priority_class="default"),
                            ),
                        ),
                        pb.Event(
                            job_validated=pb.JobValidated(
                                job_id=jid, pools=["default"]
                            )
                        ),
                        pb.Event(
                            job_run_leased=pb.JobRunLeased(
                                job_id=jid,
                                run_id=rid,
                                executor_id="e1",
                                node_id="n1",
                                pool="default",
                                scheduled_at_priority=1000,
                                update_sequence_number=1,
                            )
                        ),
                        pb.Event(
                            job_run_assigned=pb.JobRunAssigned(
                                job_id=jid, run_id=rid
                            )
                        ),
                        pb.Event(
                            job_run_running=pb.JobRunRunning(
                                job_id=jid, run_id=rid
                            )
                        ),
                        pb.Event(
                            job_run_succeeded=pb.JobRunSucceeded(
                                job_id=jid, run_id=rid
                            )
                        ),
                        pb.Event(job_succeeded=pb.JobSucceeded(job_id=jid)),
                    ],
                )
            )
        return out

    def _canon(db):
        jobs, runs = db.fetch_job_updates(0, 0)
        return (
            sorted(
                tuple(r[c] for c in r.keys() if c != "serial") for r in jobs
            ),
            sorted(
                tuple(r[c] for c in r.keys() if c != "serial") for r in runs
            ),
        )

    total_events = n_jobs * 7
    with tempfile.TemporaryDirectory(prefix="armada-bench-ingest-") as d:
        log = EventLog(os.path.join(d, "log"), num_partitions=partitions)
        Publisher(log).publish(_seqs())

        db_serial = SchedulerDb(os.path.join(d, "serial.db"))
        t0 = time.perf_counter()
        IngestionPipeline(
            log, db_serial, convert_sequences, consumer_name="scheduler"
        ).run_until_caught_up()
        serial_s = time.perf_counter() - t0

        # Warm the converter pool OUTSIDE the measurement (one-time spawn).
        warm = SchedulerDb(":memory:")
        PartitionedIngestionPipeline(
            log, warm, convert_sequences, "scheduler", num_shards=shards
        ).run_until_caught_up()
        warm.close()

        best_s = None
        db_sharded = None
        for trial in range(max(1, repeats)):
            if db_sharded is not None:
                db_sharded.close()
            db_sharded = SchedulerDb(os.path.join(d, f"sharded{trial}.db"))
            pipe = PartitionedIngestionPipeline(
                log,
                db_sharded,
                convert_sequences,
                "scheduler",
                num_shards=shards,
            )
            pipe.start()
            t0 = time.perf_counter()
            while sum(pipe.lag().values()):
                time.sleep(0.003)
            t = time.perf_counter() - t0
            pipe.stop()
            best_s = t if best_s is None else min(best_s, t)
        equal = _canon(db_serial) == _canon(db_sharded)

        # Third arm: shard the STORE too (ingest/storeunion.py) -- each
        # pipeline worker drains into its own SQLite file instead of
        # funnelling every batch through the one shared writer.  Same
        # log, same worker count; the delta is purely the store leg.
        store_shards = int(
            os.environ.get("ARMADA_BENCH_INGEST_STORE_SHARDS", shards)
        )
        if store_shards > 1 and shards % store_shards:
            # each worker's partition set must land in ONE store file
            print(
                f"bench: ingest store arm needs store shards to divide the "
                f"{shards} workers; using {shards}",
                file=sys.stderr,
            )
            store_shards = shards
        store_s = None
        store_equal = None
        if store_shards > 1:
            from armada_tpu.ingest.storeunion import ShardedSchedulerDb

            db_store = None
            for trial in range(max(1, repeats)):
                if db_store is not None:
                    db_store.close()
                # fresh dir per trial: width is permanent per store dir,
                # and a re-drain over a populated store would measure the
                # exactly-once skip, not the write path
                db_store = ShardedSchedulerDb(
                    os.path.join(d, f"store{trial}"),
                    num_shards=store_shards,
                    num_partitions=partitions,
                )
                pipe = PartitionedIngestionPipeline(
                    log,
                    db_store,
                    convert_sequences,
                    "scheduler",
                    num_shards=shards,
                )
                pipe.start()
                t0 = time.perf_counter()
                while sum(pipe.lag().values()):
                    time.sleep(0.003)
                t = time.perf_counter() - t0
                pipe.stop()
                store_s = t if store_s is None else min(store_s, t)
            store_equal = _canon(db_serial) == _canon(db_store)
            db_store.close()
        db_serial.close()
        db_sharded.close()
        log.close()
    serial_eps = total_events / serial_s
    sharded_eps = total_events / best_s
    if not equal:
        print(
            "bench: INGEST ARM DIVERGED (ingest_equal=false)", file=sys.stderr
        )
    print(
        f"bench: ingest x{shards} shards {serial_eps:,.0f} -> "
        f"{sharded_eps:,.0f} events/s ({serial_s:.2f}s -> {best_s:.2f}s, "
        f"{sharded_eps / serial_eps:.2f}x, {total_events} events)",
        file=sys.stderr,
    )
    out = {
        "ingest_events_per_s": round(sharded_eps),
        "ingest_serial_events_per_s": round(serial_eps),
        "ingest_speedup": round(sharded_eps / serial_eps, 2),
        "ingest_shards": shards,
        "ingest_events": total_events,
        "ingest_equal": equal,
    }
    if store_s is not None:
        store_eps = total_events / store_s
        if not store_equal:
            print(
                "bench: INGEST STORE ARM DIVERGED (ingest_store_equal=false)",
                file=sys.stderr,
            )
        print(
            f"bench: ingest x{store_shards} STORE shards "
            f"{sharded_eps:,.0f} -> {store_eps:,.0f} events/s "
            f"({best_s:.2f}s -> {store_s:.2f}s, "
            f"{store_eps / sharded_eps:.2f}x over the shared writer)",
            file=sys.stderr,
        )
        out.update(
            {
                "ingest_store_events_per_s": round(store_eps),
                "ingest_store_shards": store_shards,
                "ingest_store_speedup": round(store_eps / sharded_eps, 2),
                "ingest_store_equal": store_equal,
            }
        )
    return out


def main():
    from armada_tpu.core.pipeline import pipeline_enabled as _pipeline_enabled

    watchdog = _arm_watchdog()
    devices = bench_devices()
    platform = devices[0].platform
    # Persistent XLA cache (JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache): the measured repeats are post-warm-up either
    # way; this only shortens wall-clock to first cycle.
    from armada_tpu.core.platform import enable_compilation_cache

    enable_compilation_cache()
    num_jobs = int(os.environ.get("ARMADA_BENCH_JOBS", 1_000_000))
    num_nodes = int(os.environ.get("ARMADA_BENCH_NODES", 50_000))
    num_queues = int(os.environ.get("ARMADA_BENCH_QUEUES", 64))
    num_runs = int(os.environ.get("ARMADA_BENCH_RUNS", num_nodes // 2))
    repeats = int(os.environ.get("ARMADA_BENCH_REPEATS", 3))
    burst = int(os.environ.get("ARMADA_BENCH_BURST", 1_000))

    kernel_s = _kernel_bench(num_jobs, num_nodes, num_queues, repeats, burst)
    print(f"bench: kernel-only round {kernel_s:.4f}s", file=sys.stderr)
    load_start = os.getloadavg()
    e2e_s, parts, scheduled = _e2e_bench(
        num_jobs, num_nodes, num_queues, num_runs, repeats, burst
    )
    load_end = os.getloadavg()

    # Placement-throughput datapoint: the burst-10k cycle --
    # the post-outage/failover drain shape, where kernel cost scales with
    # PLACEMENTS (10k iterations), measured every round instead of ad hoc.
    # Default-on ONLY at full scale: a downscaled local run (ARMADA_BENCH_
    # JOBS/NODES set) must not silently pay a fresh 40960-slot kernel
    # compile that dwarfs the run it was downscaled for -- there the arm is
    # opt-in via ARMADA_BENCH_BURST10K=1 (scale it with
    # ARMADA_BENCH_BURST10K_N).  =0 always skips; a main run that already
    # overrode the burst skips too (the two would measure the same thing).
    burst10k_s = None
    downscaled = bool(
        os.environ.get("ARMADA_BENCH_JOBS")
        or os.environ.get("ARMADA_BENCH_NODES")
    )
    b10k_env = os.environ.get("ARMADA_BENCH_BURST10K", "" if downscaled else "1")
    if b10k_env not in ("", "0") and burst == 1_000:
        b10k = int(os.environ.get("ARMADA_BENCH_BURST10K_N", 10_000))
        print(f"bench: burst-{b10k} placement-throughput arm", file=sys.stderr)
        burst10k_s, b10k_parts, b10k_sched = _e2e_bench(
            num_jobs,
            num_nodes,
            num_queues,
            num_runs,
            repeats=max(1, repeats // 3),
            burst=b10k,
            measure_explain=False,  # the headline arm already measured it
        )
        print(
            f"bench: burst10k cycle {burst10k_s:.4f}s "
            f"({b10k_sched} placed)",
            file=sys.stderr,
        )

    market_tag = "_market" if os.environ.get("ARMADA_BENCH_MARKET") == "1" else ""
    if platform == "cpu":
        # an explicit JAX_PLATFORMS=cpu run: never under the chip's name
        market_tag += "_cpu_run"
    line = {
        "metric": f"e2e_cycle_wall_clock_{num_jobs//1000}kjobs_x_{num_nodes//1000}knodes{market_tag}",
        "value": round(e2e_s, 4),
        "unit": "s",
        "vs_baseline": round(BASELINE_ROUND_BUDGET_S / e2e_s, 2),
        "kernel_s": round(kernel_s, 4),
        "scheduled_per_cycle": scheduled,
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        # Host-load context: assemble/decode degrade with CPU competition
        # (one early headline was captured against a rogue CPU hog).
        # loadavg_1m >> cpu busy on an otherwise-idle host means the number
        # is inflated.
        "loadavg_1m": round(load_end[0], 2),
        "loadavg_1m_before_e2e": round(load_start[0], 2),
        "cpu_count": os.cpu_count(),
        # ARMADA_PIPELINE=0 is the sequential A/B arm (shadow pipeline off).
        "pipeline": int(_pipeline_enabled()),
        **parts,
    }
    if burst != 1_000:
        line["burst"] = burst
    if burst10k_s is not None:
        line["burst10k_cycle_s"] = round(burst10k_s, 4)
        # The burst arm is where the trip count dominates (10k placements):
        # burst10k_iters is the headline evidence for the multi-commit
        # kernel, an exact count on any backend.
        if b10k_parts and b10k_parts.get("kernel_iters"):
            line["burst10k_iters"] = b10k_parts["kernel_iters"]
            line["burst10k_round_iters"] = b10k_parts["round_iters"]
    if os.environ.get("ARMADA_BENCH_SIDECAR") == "1":
        line.update(
            _sidecar_bench(
                num_jobs, num_nodes, num_queues, num_runs, repeats, burst
            )
        )
    if os.environ.get("ARMADA_BENCH_MESH", "0") not in ("", "0"):
        line.update(
            _mesh_bench(
                num_jobs, num_nodes, num_queues, num_runs, repeats, burst,
                platform,
            )
        )
    # Device-loss degradation state (core/watchdog), read after every arm
    # that runs rounds through the served path and before the soak (which
    # starts from a fresh supervisor).  A round that fell back to the CPU
    # backend fails the run: its time is not the chip's.
    from armada_tpu.core.watchdog import supervisor as _supervisor

    _snap = _supervisor().snapshot()
    if _snap["fallbacks"]:
        raise RuntimeError(
            f"{_snap['fallbacks']} round(s) fell back to the CPU backend: "
            f"{_snap['last_fallback_reason']}"
        )
    line["device_state"] = {
        k: _snap[k]
        for k in ("backend", "consecutive_failures", "fallbacks", "promotions")
    }
    if os.environ.get("ARMADA_BENCH_SOAK", "1") != "0":
        line.update(_soak_bench())
    if os.environ.get("ARMADA_BENCH_POOLS", "8") not in ("", "0"):
        line.update(_pools_bench())
    if os.environ.get("ARMADA_BENCH_RESTART", "1") != "0":
        line.update(_restart_bench())
    if os.environ.get("ARMADA_BENCH_INGEST", "1") != "0":
        line.update(_ingest_bench())
    if os.environ.get("ARMADA_BENCH_HETERO", "1") != "0":
        line.update(
            _hetero_bench(num_jobs, num_nodes, num_queues, repeats, burst)
        )
    watchdog.cancel()
    print(json.dumps(line))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # always emit exactly one JSON line for the driver
        import traceback

        traceback.print_exc()
        print(
            json.dumps(
                {
                    "metric": "scheduling_round_wall_clock",
                    "value": None,
                    "unit": "s",
                    "vs_baseline": None,
                    "error": f"{type(e).__name__}: {e}"[:500],
                }
            )
        )
        sys.exit(1)
