"""Profile the sidecar's steady direct cycle on CPU: sync/round splits over
warmed cycles, then a cProfile of 3 more -- the methodology behind
docs/bench.md's round-6 host-side ablation (whole-cycle differencing is
useless when the CPU kernel's variance exceeds the host-side trim being
measured).  Scale knobs: PJOBS, PNODES, PQUEUES, PRUNS, PBURST; e.g.
PJOBS=1000000 PNODES=50000 PRUNS=25000 python tools/sidecar_profile.py.

The sync/round split is read from the CYCLE TRACE ring (ops/trace.py):
each handle_sync/handle_round records a cycle tree rooted at the SESSION
methods (apply_sync/schedule_round), and this tool reports those root
durations -- the same stage-split source of truth bench.py's stage_*_s
keys, /healthz's trace block and `armadactl trace` read, instead of a
second set of ad-hoc timers that could drift from it.  Scope note: the
session roots exclude the thin wire shims around them -- handle_sync's
executor/queue/bid proto parsing (jobs convert INSIDE apply_sync, which
dominates) and handle_round's response assembly (~1k RoundLease appends
+ stats JSON).  Those slices still show in the cProfile section below;
the r6-era perf_counter numbers included them, so per-cycle totals here
read a few ms lower than that baseline at equal cost."""
import cProfile
import io
import os
import pstats
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def main():
    jobs = int(os.environ.get("PJOBS", 200_000))
    nodes = int(os.environ.get("PNODES", 10_000))
    queues = int(os.environ.get("PQUEUES", 64))
    runs = int(os.environ.get("PRUNS", nodes // 2))
    burst = int(os.environ.get("PBURST", 1_000))

    from armada_tpu.models.synthetic import (
        synthetic_job_state,
        synthetic_mirror,
        synthetic_serving_config,
        synthetic_world,
    )
    from armada_tpu.rpc import rpc_pb2 as pb
    from armada_tpu.scheduler.sidecar import ScheduleSidecar

    t0 = time.perf_counter()
    config, nodes_l, queues_l, specs, running, spec_factory = (
        bench.synthetic_world(
            num_nodes=nodes,
            num_jobs=jobs,
            num_queues=queues,
            num_runs=runs,
            seed=7,
            shape_bucket=max(8192, 4 * burst),
        )
        if hasattr(bench, "synthetic_world")
        else synthetic_world(
            num_nodes=nodes,
            num_jobs=jobs,
            num_queues=queues,
            num_runs=runs,
            seed=7,
            shape_bucket=max(8192, 4 * burst),
        )
    )
    config = synthetic_serving_config(config, burst)
    now0 = 10**12
    clock = [now0]
    sidecar = ScheduleSidecar(config, clock_ns=lambda: clock[0])
    sid = sidecar.create_session("prof")
    session = sidecar.session(sid)

    executors, job_chunks = synthetic_mirror(
        config, nodes_l, specs, running, now0
    )
    session.apply_sync(executors=executors, queues=queues_l)
    for states in job_chunks:
        sidecar.handle_sync(pb.SyncStateRequest(session_id=sid, jobs=states))
    print(f"setup {time.perf_counter()-t0:.1f}s", file=sys.stderr)

    from armada_tpu.models.xfer import TRANSFER_STATS
    from armada_tpu.ops.trace import recorder as trace_recorder

    rec = trace_recorder()
    if not rec.enabled:
        print(
            "warning: ARMADA_TRACE=0 disables cycle tracing -- the "
            "sync/round splits below will read 0",
            file=sys.stderr,
        )

    def _ring_duration(kind: str) -> float:
        """Root duration of the newest ring entry of this kind -- the
        trace-span timing of the call that just returned."""
        for t in reversed(rec.last()):
            if t.kind == kind:
                return t.root.dur_s
        return 0.0

    def cycle():
        clock[0] += 10**9
        fresh = spec_factory(burst, clock[0] / 1e9)
        states = [synthetic_job_state(s) for s in fresh]
        TRANSFER_STATS.reset()
        sidecar.handle_sync(pb.SyncStateRequest(session_id=sid, jobs=states))
        t_sync = _ring_duration("sync")
        xs_sync = TRANSFER_STATS.snapshot()
        resp = sidecar.handle_round(
            pb.ScheduleRoundRequest(session_id=sid, now_ns=clock[0])
        )
        t_round = _ring_duration("round")
        xs = TRANSFER_STATS.snapshot()
        xs["sync_up_transfers"] = xs_sync["up_transfers"]
        xs["sync_up_bytes"] = xs_sync["up_bytes"]
        return t_sync, t_round, len(resp.scheduled), xs

    # Pipeline A/B over the SAME live session (the sidecar reads
    # ARMADA_PIPELINE / ARMADA_PIPELINE_PREFETCH per call): warmed cycles
    # per arm, with per-cycle device-transfer counters split by phase -- on
    # an accelerator, upload work counted in the SYNC phase overlaps the
    # caller's cycle instead of the round's critical path, so the
    # sync-vs-round split is the number to watch even on a CPU host.
    # Arms: pipelined+prefetch (the TPU-shaped config, scatter forced on),
    # pipelined (CPU default: shadow order only), sequential.  The
    # operator's own env is restored afterwards so the cProfile below
    # measures the configuration that was asked for.
    env0 = {
        k: os.environ.get(k)
        for k in ("ARMADA_PIPELINE", "ARMADA_PIPELINE_PREFETCH")
    }
    for _ in range(2):
        cycle()
    for arm, label in (
        (("1", "1"), "pipelined+prefetch"),
        (("1", None), "pipelined"),
        (("0", "0"), "sequential"),
    ):
        os.environ["ARMADA_PIPELINE"] = arm[0]
        if arm[1] is None:
            os.environ.pop("ARMADA_PIPELINE_PREFETCH", None)
        else:
            os.environ["ARMADA_PIPELINE_PREFETCH"] = arm[1]
        cycle()  # settle the arm (first cycle pays any carried-over state)
        for _ in range(3):
            ts, tr, n, xs = cycle()
            print(
                f"[{label}] sync {ts:.3f}s round {tr:.3f}s total "
                f"{ts+tr:.3f}s sched {n} | sync-up "
                f"{xs['sync_up_transfers']}x/{xs['sync_up_bytes']/1e6:.2f}MB "
                f"cycle-up {xs['up_transfers']}x/{xs['up_bytes']/1e6:.2f}MB "
                f"down {xs['down_transfers']}x/{xs['down_bytes']/1e6:.3f}MB",
                file=sys.stderr,
            )
    for k, v in env0.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v

    pr = cProfile.Profile()
    pr.enable()
    for _ in range(3):
        cycle()
    pr.disable()
    s = io.StringIO()
    ps = pstats.Stats(pr, stream=s).sort_stats("cumulative")
    ps.print_stats(45)
    print(s.getvalue())


if __name__ == "__main__":
    main()
