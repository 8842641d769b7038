#!/usr/bin/env python3
"""chip_smoke.py -- the quickest proof that the served scheduling round
still starts on the chip.

One process; it owns the chip from start to finish.  Through the entry
points a user calls -- `armada_tpu.cli.serve.start_control_plane` (what
`armadactl serve` calls; watchdog, round verification and the explain
cadence left at serve's defaults), `run_fake_executor`, and the
`armada_tpu.rpc.client` clients over real gRPC on localhost -- it runs:

* the full-size leg, the sidecar (the `SchedulingAlgo` boundary):
  CreateSession, SyncState of the repo's headline world (50,000 nodes in
  10 executor snapshots, 64 queues, 1,000,000 queued jobs, 25,000 running;
  source: the reference's README.md:13,18 and
  config/scheduler/config.yaml:99-107, via BASELINE.md), then
  SyncState(1,000 fresh submits) + ScheduleRound cycles over the wire until
  three in a row compile nothing.  One round's assembled host problem is
  also run on `jax.devices("cpu")[0]` in this process (the CPU failover
  rung): both answers must pass round verification and decide the same
  number of leases and preemptions.  Which NODE a job lands on may differ
  between the two compilers among nodes whose f32 packing scores tie
  (docs/operations.md, "Device-loss degradation"); the smoke prints how
  many did;
* the whole-stack leg, small: a queue, ~100 jobs including one gang through
  SubmitServer, one fake executor, and the event stream watched until
  every job is leased and succeeds.

Every check is a hard failure: the script exits non-zero at the first one
that does not hold, and prints the result line only when all held.  With
no TPU (`JAX_PLATFORMS=cpu`, or a machine without one) it exits non-zero
at the first check, in seconds.

    python chip_smoke.py              # one chip
    python chip_smoke.py --mesh 4     # the mesh serving plane over 4 chips

The last line of standard output is one JSON object:
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

WORLD = dict(
    num_nodes=50_000,
    num_jobs=1_000_000,
    num_queues=64,
    num_runs=25_000,
    seed=7,
    shape_bucket=8192,
)
BURST = 1_000
CLEAN_CYCLES = 3  # consecutive wire cycles that must compile nothing
MAX_CYCLES = 10
STACK_JOBS = 96  # singles; one gang of STACK_GANG rides on top
STACK_GANG = 4
DEADLINE_S = 1150.0  # the contract is 1200 s, compilation included

_T0 = time.monotonic()


class SmokeFailure(AssertionError):
    """A hard check did not hold."""


def log(msg: str) -> None:
    print(f"[smoke {time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)
    log(f"ok: {msg}")


def _arm_deadline() -> None:
    def fire():
        print(
            f"chip_smoke: exceeded {DEADLINE_S:.0f}s; aborting",
            file=sys.stderr,
            flush=True,
        )
        os._exit(3)

    t = threading.Timer(DEADLINE_S, fire)
    t.daemon = True
    t.start()


def require_tpu(mesh: int) -> dict:
    """First check, before any world is built: every visible device is a
    TPU (and there are enough of them for the mesh asked for)."""
    from importlib import metadata

    import jax
    import jaxlib

    devices = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(
        f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu}; "
        f"devices: {[str(d) for d in devices]}"
    )
    not_tpu = [str(d) for d in devices if d.platform != "tpu"]
    if not_tpu:
        print(
            f"chip_smoke: no TPU: jax.devices() reports {not_tpu} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
            file=sys.stderr,
        )
        sys.exit(2)
    if mesh and len(devices) < mesh:
        print(
            f"chip_smoke: --mesh {mesh} needs {mesh} chips, "
            f"{len(devices)} visible",
            file=sys.stderr,
        )
        sys.exit(2)
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


class CompileLog:
    """Every XLA backend compile in this process, by program name, plus the
    persistent cache's hit and write counts (jax.monitoring)."""

    def __init__(self):
        import jax

        self.compiles: list = []  # (program, seconds)
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((str(kw.get("fun_name")), float(secs)))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def since(self, mark: int) -> list:
        return self.compiles[mark:]


def memory_by_chip() -> list:
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append(
            {
                "device": str(d),
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            }
        )
    return out


def _digest(resp) -> str:
    """Decisions of one round, independent of run ids: sha256 over the
    sorted (job, node) leases and the sorted preempted job ids."""
    h = hashlib.sha256()
    for job_id, node_id in sorted((l.job_id, l.node_id) for l in resp.scheduled):
        h.update(f"{job_id}@{node_id};".encode())
    h.update(b"|")
    for job_id in sorted(p.job_id for p in resp.preempted):
        h.update(f"{job_id};".encode())
    return h.hexdigest()


class CpuTwin:
    """Run ONE served round twice: on the device as served, then from the
    same assembled host tables on `jax.devices("cpu")[0]` -- exactly what
    the failover ladder's CPU rung does -- and keep both outcomes."""

    def __init__(self):
        import armada_tpu.scheduler.algo as algo

        self._algo = algo
        self._real = algo.run_round_on_device
        self.armed = False
        self.device = None
        self.cpu = None
        self.cpu_s = None
        self.cpu_verified = False
        algo.run_round_on_device = self._run

    def restore(self) -> None:
        self._algo.run_round_on_device = self._real

    def _run(self, problem, ctx, config, **kw):
        res, outcome = self._real(problem, ctx, config, **kw)
        if self.armed:
            self.armed = False
            import armada_tpu.models as models
            from armada_tpu.models.verify import verify_state

            t0 = time.monotonic()
            host_problem = kw["host_problem"]()
            kernel_kwargs, shadow, _, _, _ = models._round_env(
                problem, ctx, config, (), False
            )
            before = verify_state().snapshot()
            # a verification failure on this rung raises out of the round
            _, cpu_outcome = models._run_round_cpu_failover(
                host_problem, ctx, config, kernel_kwargs, shadow, False
            )
            after = verify_state().snapshot()
            self.cpu_verified = (
                after["rounds_verified"] > before["rounds_verified"]
                and after["failures"] == before["failures"]
            )
            self.cpu_s = time.monotonic() - t0
            self.device = (dict(outcome.scheduled), sorted(outcome.preempted))
            self.cpu = (dict(cpu_outcome.scheduled), sorted(cpu_outcome.preempted))
            # The SERVED round is the device's: the twin must not leave its
            # devices behind in what the supervisor and /healthz report.
            models._note_round_devices(res)
        return res, outcome


def mesh_layout(plane, session_id: str, mesh: int) -> dict:
    """Where the session's resident slab lives: every node-axis field must
    have `mesh` addressable shards on `mesh` distinct devices, each a
    `1/mesh` slice of the padded node axis.  Returns bytes held per chip
    (sharded fields count their shard, replicated fields their full copy)."""
    from armada_tpu.parallel.mesh import problem_shardings
    from armada_tpu.parallel.serving import mesh_serving

    serving_mesh = mesh_serving().serving_mesh()
    check(serving_mesh is not None, "mesh serving plane is up")
    shardings = problem_shardings(serving_mesh)
    session = plane.sidecar.session(session_id)
    resident = session.feed.devcache_for("default")._prev
    per_chip: dict = {}
    node_fields = []
    for name, sharding, arr in zip(resident._fields, shardings, resident):
        spec = tuple(sharding.spec)
        for shard in arr.addressable_shards:
            key = str(shard.device)
            per_chip[key] = per_chip.get(key, 0) + shard.data.nbytes
        if "nodes" not in spec:
            continue
        axis = spec.index("nodes")
        shards = arr.addressable_shards
        devices = {str(s.device) for s in shards}
        widths = {s.data.shape[axis] for s in shards}
        if (
            len(shards) != mesh
            or len(devices) != mesh
            or widths != {arr.shape[axis] // mesh}
        ):
            raise SmokeFailure(
                f"slab field {name}: {len(shards)} shards on "
                f"{len(devices)} devices, widths {widths} of {arr.shape}"
            )
        node_fields.append(name)
    check(
        bool(node_fields),
        f"{len(node_fields)} node-axis slab fields each hold {mesh} shards "
        f"on {mesh} distinct devices, a 1/{mesh} slice of the node axis",
    )
    return {"node_axis_fields": node_fields, "slab_bytes_per_chip": per_chip}


def full_size_leg(plane, config, world, compiles: CompileLog, mesh: int) -> dict:
    """CreateSession + SyncState of the whole world + wire cycles, over real
    gRPC against the plane's algo port."""
    from armada_tpu.models.synthetic import synthetic_job_state, synthetic_mirror
    from armada_tpu.rpc.client import ScheduleClient

    _, nodes, queues, specs, running, spec_factory = world
    now0 = 10**12
    clock = now0
    client = ScheduleClient(f"127.0.0.1:{plane.algo_port}")
    out: dict = {"cycles": []}
    twin = CpuTwin()
    try:
        sid = client.create_session("chip-smoke")
        t0 = time.monotonic()
        executors, job_chunks = synthetic_mirror(
            config, nodes, specs, running, now0
        )
        client.sync_state(
            sid,
            executors=executors,
            queues=queues,
            factory=config.resource_list_factory(),
        )
        synced = 0
        for states in job_chunks:
            client.sync_state(sid, jobs=states)
            synced += len(states)
        out["mirror_load_s"] = time.monotonic() - t0
        log(
            f"mirror loaded over the wire: {len(nodes)} nodes in "
            f"{len(executors)} executors, {len(queues)} queues, {synced} job "
            f"states in {out['mirror_load_s']:.1f}s"
        )
        out["memory_after_load"] = memory_by_chip()

        clean = 0
        while clean < CLEAN_CYCLES:
            n = len(out["cycles"])
            if n >= MAX_CYCLES:
                raise SmokeFailure(
                    f"{MAX_CYCLES} cycles and still compiling: "
                    f"{[c['compiled'] for c in out['cycles']]}"
                )
            # Cycle 0 compiles every program at these shapes; cycle 1 is the
            # first delta-scatter round and carries the CPU twin.
            twin.armed = n == 1
            clock += 10**9
            states = [
                synthetic_job_state(s) for s in spec_factory(BURST, clock / 1e9)
            ]
            mark = len(compiles.compiles)
            t0 = time.monotonic()
            client.sync_state(sid, jobs=states)
            resp = client.schedule_round(sid, now_ns=clock)
            dt = time.monotonic() - t0
            compiled = compiles.since(mark)
            stats = json.loads(resp.pool_stats_json)
            pool = stats["pools"][0]
            cycle = {
                "wall_s": dt,
                "scheduled": len(resp.scheduled),
                "preempted": len(resp.preempted),
                "iterations": pool["iterations"],
                "kernel_iters": pool["kernel_iters"],
                "compiled": [(name, round(s, 3)) for name, s in compiled],
                "digest": _digest(resp),
                "cpu_twin": n == 1,
            }
            out["cycles"].append(cycle)
            log(
                f"cycle {n}: {dt:.3f}s wall, scheduled {cycle['scheduled']}, "
                f"preempted {cycle['preempted']}, kernel trips "
                f"{cycle['kernel_iters']}, compiled {len(compiled)} program(s)"
                + (" [+ CPU twin]" if n == 1 else "")
            )
            for name, secs in compiled:
                log(f"    compiled {name}: {secs:.2f}s")
            check(cycle["scheduled"] > 0, f"cycle {n} scheduled > 0 jobs")
            dev = stats["device"]
            check(
                dev["platform"] == "tpu" and dev["backend"] == "device",
                f"cycle {n} round outputs live on {dev['device_count']} x "
                f"{dev['device_kind']} ({dev['platform']}), read from the arrays",
            )
            check(dev["fallbacks"] == 0, f"cycle {n}: no CPU fallback")
            if n == 1:
                check(twin.cpu is not None, "the CPU twin of cycle 1 ran")
                (dev_sched, dev_pre), (cpu_sched, cpu_pre) = twin.device, twin.cpu
                moved = [
                    j for j in dev_sched.keys() & cpu_sched.keys()
                    if dev_sched[j] != cpu_sched[j]
                ]
                out["cpu_twin"] = {
                    "seconds": twin.cpu_s,
                    "scheduled": [len(dev_sched), len(cpu_sched)],
                    "preempted": [len(dev_pre), len(cpu_pre)],
                    "same_jobs": dev_sched.keys() == cpu_sched.keys(),
                    "same_preempted": dev_pre == cpu_pre,
                    "jobs_on_a_different_node": len(moved),
                }
                check(
                    twin.cpu_verified
                    and len(dev_sched) == len(cpu_sched)
                    and len(dev_pre) == len(cpu_pre),
                    f"chip and XLA:CPU rounds over cycle 1's host problem both "
                    f"passed round verification and decided {len(cpu_sched)} "
                    f"leases, {len(cpu_pre)} preemptions (CPU rung took "
                    f"{twin.cpu_s:.1f}s)",
                )
                log(
                    f"    same jobs: {out['cpu_twin']['same_jobs']}, same "
                    f"preempted: {out['cpu_twin']['same_preempted']}, "
                    f"{len(moved)} job(s) on a different node (f32 "
                    f"packing-score ties round differently under the two "
                    f"compilers)"
                )
            clean = clean + 1 if n >= 2 and not compiled else 0
        steady = [c["wall_s"] for c in out["cycles"][-CLEAN_CYCLES:]]
        out["first_cycle_s"] = out["cycles"][0]["wall_s"]
        out["steady_cycle_s"] = steady
        log(
            f"first cycle {out['first_cycle_s']:.2f}s; last {CLEAN_CYCLES} "
            f"cycles compiled nothing: {[round(s, 3) for s in steady]} s"
        )
        out["memory_after_rounds"] = memory_by_chip()
        if mesh:
            out["mesh_layout"] = mesh_layout(plane, sid, mesh)
        client.close_session(sid)
    finally:
        twin.restore()
        client.close()
    return out


def whole_stack_leg(plane, config) -> dict:
    """Queue + jobs (one gang) through SubmitServer, one fake executor, the
    event stream watched until everything leased and succeeded: the native
    event log, both protobuf modules, ingest and the lease path all ran."""
    from armada_tpu.cli.serve import run_fake_executor
    from armada_tpu.rpc.client import ArmadaClient
    from armada_tpu.server.queues import QueueRecord
    from armada_tpu.server.submit import JobSubmitItem

    address = f"127.0.0.1:{plane.port}"
    client = ArmadaClient(address)
    stop = threading.Event()
    agent = threading.Thread(
        target=run_fake_executor,
        args=(address,),
        kwargs=dict(
            executor_id="smoke-ex",
            num_nodes=8,
            cpu="16",
            memory="64",
            interval_s=0.2,
            stop=stop,
            config=config,
            default_runtime_s=0.5,
        ),
        daemon=True,
    )
    t0 = time.monotonic()
    try:
        client.create_queue(QueueRecord("smoke", 1.0))
        agent.start()
        singles = [
            JobSubmitItem(resources={"cpu": "1", "memory": "1"})
            for _ in range(STACK_JOBS)
        ]
        gang = [
            JobSubmitItem(
                resources={"cpu": "2", "memory": "2"},
                gang_id="smoke-gang",
                gang_cardinality=STACK_GANG,
            )
            for _ in range(STACK_GANG)
        ]
        ids = client.submit_jobs("smoke", "chip-smoke", singles + gang)
        gang_ids = set(ids[-STACK_GANG:])
        check(len(ids) == STACK_JOBS + STACK_GANG, f"submitted {len(ids)} jobs")
        leased: dict = {}
        succeeded: set = set()
        cursor = 0
        deadline = time.monotonic() + 300
        while len(succeeded) < len(ids):
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"only {len(succeeded)}/{len(ids)} jobs succeeded, "
                    f"{len(leased)} leased, after 300s"
                )
            for item in client.watch(
                "smoke", "chip-smoke", from_idx=cursor, idle_timeout_s=1.0
            ):
                cursor = item.idx + 1
                for ev in item.sequence.events:
                    kind = ev.WhichOneof("event")
                    body = getattr(ev, kind)
                    if kind == "job_run_leased":
                        leased[body.job_id] = body.node_id
                    elif kind == "job_succeeded":
                        succeeded.add(body.job_id)
                    elif kind in ("job_errors", "job_run_errors", "job_run_preempted"):
                        raise SmokeFailure(f"job event {kind}: {body}")
        check(
            set(leased) == set(ids) and succeeded == set(ids),
            f"all {len(ids)} jobs leased to the fake executor and succeeded",
        )
        check(
            gang_ids <= set(leased),
            f"the {STACK_GANG}-member gang leased whole",
        )
    finally:
        stop.set()
        agent.join(timeout=10)
        client.close()
    return {"jobs": len(ids), "wall_s": time.monotonic() - t0}


def final_checks(plane, mesh: int) -> dict:
    """After the last round: supervisor, mesh, quarantine and verification
    state, in process AND as /healthz reports it over HTTP."""
    from armada_tpu.core.watchdog import supervisor
    from armada_tpu.models.verify import healthz_block
    from armada_tpu.parallel.serving import mesh_serving

    snap = supervisor().snapshot()
    check(
        snap["backend"] == "device"
        and snap["fallbacks"] == 0
        and snap["consecutive_failures"] == 0,
        f"supervisor: backend={snap['backend']} fallbacks={snap['fallbacks']} "
        f"consecutive_failures={snap['consecutive_failures']}",
    )
    verify = healthz_block()
    check(
        verify["enabled"] and verify["rounds_verified"] > 0 and verify["failures"] == 0,
        f"round verification armed: {verify['rounds_verified']} rounds "
        f"verified, {verify['failures']} failures",
    )
    check(
        not verify["quarantine"]["strike_totals"],
        "no device quarantine strike",
    )
    mesh_snap = mesh_serving().snapshot()
    check(
        mesh_snap["degrades"] == 0 and mesh_snap["devices"] == (mesh if mesh >= 2 else 0),
        f"mesh: devices={mesh_snap['devices']} degrades={mesh_snap['degrades']}",
    )
    url = f"http://127.0.0.1:{plane.health_server.port}/healthz"
    with urllib.request.urlopen(url, timeout=30) as r:
        body = json.loads(r.read())
    dev = body["device"]
    check(
        body["healthy"]
        and dev["backend"] == "device"
        and dev["fallbacks"] == 0
        and dev["consecutive_failures"] == 0
        and dev["platform"] == "tpu"
        and dev["device_count"] == max(mesh, 1),
        f"/healthz over HTTP: healthy, device block names "
        f"{dev['device_count']} x {dev['device_kind']} ({dev['platform']}), "
        f"fallbacks {dev['fallbacks']}",
    )
    check(
        body["verify"]["failures"] == 0
        and not body["verify"]["quarantine"]["strike_totals"]
        and (not mesh or body["mesh"]["degrades"] == 0),
        "/healthz verify and mesh blocks say the same",
    )
    return {"supervisor": snap, "healthz_device": dev, "mesh": mesh_snap}


def run(mesh: int, device: dict) -> dict:
    from armada_tpu.cli.serve import start_control_plane
    from armada_tpu.models.synthetic import (
        synthetic_serving_config,
        synthetic_world,
    )

    compiles = CompileLog()
    report: dict = {"device": device, "mesh": mesh, "world": dict(WORLD, burst=BURST)}
    t0 = time.monotonic()
    world = synthetic_world(**WORLD)
    config = synthetic_serving_config(world[0], BURST)
    report["world_build_s"] = time.monotonic() - t0
    log(f"synthetic world built in {report['world_build_s']:.1f}s: {WORLD}")
    with tempfile.TemporaryDirectory(prefix="armada-chip-smoke-") as data_dir:
        plane = start_control_plane(
            data_dir,
            port=0,
            config=config,
            algo_port=0,
            health_port=0,
            cycle_interval_s=0.25,
            schedule_interval_s=0.5,
            mesh_devices=mesh,
        )
        try:
            from armada_tpu.core.platform import compilation_cache_dir

            log(
                f"control plane up: grpc :{plane.port}, sidecar :{plane.algo_port}, "
                f"health :{plane.health_server.port}; compile cache at "
                f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or compilation_cache_dir()}"
            )
            report["full_size"] = full_size_leg(plane, config, world, compiles, mesh)
            del world
            report["whole_stack"] = whole_stack_leg(plane, config)
            report["final"] = final_checks(plane, mesh)
        finally:
            plane.stop()
    report["compiles"] = [(name, round(s, 3)) for name, s in compiles.compiles]
    report["compile_cache"] = {
        "dir": os.environ.get("JAX_COMPILATION_CACHE_DIR") or "<checkout>/.jax_cache",
        "hits": compiles.cache_hits,
        "writes": compiles.cache_writes,
    }
    log(
        f"persistent compile cache: {compiles.cache_hits} hits, "
        f"{compiles.cache_writes} writes, "
        f"{sum(s for _, s in compiles.compiles):.1f}s in "
        f"{len(compiles.compiles)} backend compiles"
    )
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--mesh", type=int, default=0,
        help="arm the mesh serving plane over N chips (the four-chip leg)",
    )
    ap.add_argument("--report", help="also write the full report as JSON here")
    args = ap.parse_args(argv)
    _arm_deadline()
    device = require_tpu(args.mesh)
    report = run(args.mesh, device)
    report["total_s"] = time.monotonic() - _T0
    if args.report:
        os.makedirs(os.path.dirname(os.path.abspath(args.report)), exist_ok=True)
        with open(args.report, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1, default=str)
    log(f"all checks held in {report['total_s']:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
