"""One run of one cell: set up the served path, warm it, measure a window of
back-to-back cycles from the client's side of the wire, check the answers.

The system under test is started the way `armadactl serve` starts it
(`start_control_plane`, serve's defaults: 120 s watchdog, round verification,
explain cadence) in this process, which owns the chip; the client is a gRPC
channel to its sidecar port.  From the program the harness takes the control
plane, the wire format, its span trees, its transfer counters and its
verification state; traffic, timing, reduction and checking are the
benchmark's own.
"""

from __future__ import annotations

import gc
import glob
import json
import math
import os
import statistics
import sys
import tempfile
import time

import numpy as np

from perfbench.harness import layers, probes, tracered
from perfbench.harness.cell import Cell
from perfbench.harness.checker import Checker
from perfbench.harness.world import World

NOW0_NS = 10**12
CLEAN_CYCLES = 3  # consecutive warm cycles that must compile nothing
EXTRA_WARM_CYCLES = 12  # beyond the mix's lifetime, before giving up on "clean"
TRACED_FROM = 2  # the profiler covers window cycles [TRACED_FROM, TRACED_FROM + n)
SYNC = "/armada_tpu.api.Schedule/SyncState"
ROUND = "/armada_tpu.api.Schedule/ScheduleRound"
CREATE = "/armada_tpu.api.Schedule/CreateSession"
CLOSE = "/armada_tpu.api.Schedule/CloseSession"


def say(msg: str) -> None:
    print(f"[perfbench] {msg}", flush=True)


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device_block(chips: int, allow_cpu: bool) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        raise NoAccelerator(
            f"no TPU: jax.devices() reports {[str(d) for d in devices]} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    if platform == "tpu" and len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, {len(devices)} visible")
    return {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": chips if platform == "tpu" else len(devices),
    }


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()]
    return int(max(peaks, default=0))


class Wire:
    """The client: one gRPC channel to the sidecar, the four calls of the
    `Schedule` service, and the serialized size of what crossed."""

    def __init__(self, port: int):
        import grpc

        from armada_tpu.rpc import rpc_pb2 as pb
        from armada_tpu.rpc.transport import channel_options

        self.pb = pb
        self.channel = grpc.insecure_channel(f"127.0.0.1:{port}", options=channel_options())

        def call(path, req_cls, resp_cls):
            return self.channel.unary_unary(
                path,
                request_serializer=req_cls.SerializeToString,
                response_deserializer=resp_cls.FromString,
            )

        self.sync = call(SYNC, pb.SyncStateRequest, pb.Empty)
        self.round = call(ROUND, pb.ScheduleRoundRequest, pb.ScheduleRoundResponse)
        self.create = call(CREATE, pb.ScheduleSessionConfig, pb.ScheduleSessionHandle)
        self.close_session = call(CLOSE, pb.ScheduleSessionHandle, pb.Empty)

    def close(self) -> None:
        self.channel.close()


class Run:
    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.traffic = cell.traffic
        self.submits = int(self.traffic["submits_per_cycle"])
        self.lifetime = int(self.traffic["lifetime_cycles"])
        # a fleet that finishes K jobs a cycle, whatever an earlier round leased
        rate = self.traffic.get("completions_per_cycle")
        self.service_rate = None if rate is None else int(rate)
        self.step_ns = int(float(self.traffic["logical_cycle_s"]) * 1e9)
        self.compiles = probes.CompileLog()
        self.gclog = probes.GcLog()
        self.seen_traces: set = set()
        self.cycles: list = []  # every cycle's record, warm-ups included
        self.requests: dict = {}  # cycle number -> prebuilt SyncStateRequest
        self.leased: dict = {}  # cycle number -> {job number: RoundLease}, live leases only
        self.leased_at: dict = {}  # job number -> the cycle that leased it
        self.k = 0
        self.diag: dict = {}

    # ------------------------------------------------------------ set-up ----

    def start(self, data_dir: str) -> None:
        from armada_tpu.cli.serve import start_control_plane
        from armada_tpu.core.config import scheduling_config_from_dict

        t = time.perf_counter()
        self.world = World(self.cell.config["world"], self.seed)
        self.diag["world_build_s"] = time.perf_counter() - t
        block = self.cell.scheduling()
        self.plane = start_control_plane(
            data_dir, port=0, config=scheduling_config_from_dict(block), algo_port=0
        )
        self.wire = Wire(self.plane.algo_port)
        pb = self.wire.pb
        self.sid = self.wire.create(
            pb.ScheduleSessionConfig(
                session_id="perfbench", config_yaml=json.dumps({"scheduling": block})
            )
        ).session_id
        self.checker = Checker(
            self.world,
            cap=int(block["maximumSchedulingBurst"]),
            queue_cap=int(block["maximumPerQueueSchedulingBurst"]),
            priority_classes=block["priorityClasses"],
            lookback=block.get("maxQueueLookback"),
        )

    def load_mirror(self) -> None:
        """The whole world over the wire, as a mirroring control plane would
        send it on start: the fleet and the queues, then every job state."""
        pb, w = self.wire.pb, self.world
        t = time.perf_counter()
        self.wire.sync(
            pb.SyncStateRequest(
                session_id=self.sid,
                set_executors=True,
                executors=w.executor_snapshots(NOW0_NS),
                set_queues=True,
                queues=[pb.Queue(name=q, weight=1.0) for q in w.queue_names],
            )
        )
        chunk = int(w.sizes["mirror_chunk"])
        sent = 0
        for numbers in w.chunks(w.num_jobs, chunk):  # a gang's members leave in one SyncState
            states = w.job_states(numbers)
            self.wire.sync(pb.SyncStateRequest(session_id=self.sid, jobs=states))
            sent += len(states)
        priorities = {
            name: int(pc["priority"])
            for name, pc in self.cell.config["scheduling"]["priorityClasses"].items()
        }
        n_runs = len(w.run_shape)
        for lo in range(0, n_runs, chunk):
            states = w.running_states(range(lo, min(lo + chunk, n_runs)), NOW0_NS, priorities)
            self.wire.sync(pb.SyncStateRequest(session_id=self.sid, jobs=states))
            sent += len(states)
        self.diag["mirror_load_s"] = time.perf_counter() - t
        self.diag["mirror_job_states"] = sent

    def prebuild(self, n: int) -> None:
        """Submit messages of the next `n` cycles not built yet."""
        pb = self.wire.pb
        first = max(self.requests, default=self.k - 1) + 1
        times = [(NOW0_NS + (k + 1) * self.step_ns) / 1e9 for k in range(first, first + n)]
        for k, numbers in enumerate(self.world.extend_batches(self.submits, times), first):
            req = pb.SyncStateRequest(session_id=self.sid, jobs=self.world.job_states(numbers))
            self.requests[k] = (req, list(numbers))

    # ------------------------------------------------------------- cycle ----

    def prepare(self, k: int):
        """Between cycles: cycle k's request, with the terminal states of the
        jobs that finished appended: every job leased `lifetime` cycles earlier
        and still running (a gang's members were leased together, so they
        finish together) or, where the mix gives `completions_per_cycle`, the
        K oldest live leases of the books (`finishing`).  A job the scheduler
        preempted since has left the books: `forget`."""
        if k not in self.requests:
            self.prebuild(1)  # the estimate of cycles per window fell short
        req, submitted = self.requests.pop(k)
        completed = []
        for at, i, lease in self.finishing(k):
            del self.leased_at[i]
            ran_from = NOW0_NS + (at + 1) * self.step_ns
            req.jobs.append(self.world.terminal_state(i, lease, ran_from))
            completed.append(i)
        return req, submitted, completed

    def finishing(self, k: int) -> list:
        """(cycle leased, job number, lease) of the jobs that finish before
        cycle k, taken out of `leased`.  With `completions_per_cycle` K the
        fleet has a service rate: from cycle `lifetime` on, the K oldest live
        leases (by the cycle that leased them, then by their place in that
        round's response), or all of them if fewer are live; what one thin
        round leased does not come back as one thin round of completions.  A
        gang finishes whole: where the next oldest lease is a gang's member
        and its live members no longer fit under K, the cycle finishes fewer
        than K (K is a ceiling; `drift` reckons with what really finished)."""
        if self.service_rate is None:
            at = k - self.lifetime
            return [(at, i, lease) for i, lease in self.leased.pop(at, {}).items()]
        out, want = [], self.service_rate if k >= self.lifetime else 0
        w = self.world
        for at in sorted(self.leased):
            live = self.leased[at]
            for i in list(live):
                if i not in live:
                    continue  # left with its gang
                unit = [j for j in w.members(i) if j in live]
                if len(out) + len(unit) > want:
                    return out
                out += [(at, j, live.pop(j)) for j in unit]
            if not live:
                del self.leased[at]
        return out

    def forget(self, job_id: str) -> None:
        """A job the round reports preempted: the scheduler ended its run and
        failed the job in its own mirror, so the client owes it no terminal
        state and sends nothing more for it.  (An initial running job was
        never in these books: the client completes none of them.)"""
        try:
            i = self.world.job_number(job_id)
        except KeyError:
            return  # an initial run, or an id the checker reports
        k = self.leased_at.pop(i, None)
        if k is not None:
            del self.leased[k][i]

    def cycle(self, traced: bool = False) -> dict:
        """One SyncState + one ScheduleRound, timed from the client's side
        until the response is parsed; everything else is between cycles."""
        import grpc

        pb = self.wire.pb
        k = self.k
        req, submitted, completed = self.prepare(k)
        round_req = pb.ScheduleRoundRequest(
            session_id=self.sid, now_ns=NOW0_NS + (k + 1) * self.step_ns
        )
        compile_mark = self.compiles.mark()
        xfer0 = probes.transfer_counts()
        annotation = None
        if traced:
            from jax.profiler import TraceAnnotation

            annotation = TraceAnnotation(tracered.CYCLE_MARKER)
            annotation.__enter__()
        gc_mark = self.gclog.mark()
        rec = {"k": k, "traced": traced, "error": None}
        resp = None
        t0 = time.perf_counter()
        try:
            self.wire.sync(req)
            t1 = time.perf_counter()
            resp = self.wire.round(round_req)
        except grpc.RpcError as e:
            rec["error"] = f"{e.code().name}: {e.details()}"
            t1 = time.perf_counter()
        t2 = time.perf_counter()
        if annotation is not None:
            annotation.__exit__(None, None, None)
        rec.update(self.gclog.since(gc_mark))
        rec["t_start"], rec["t_end"] = t0, t2
        rec["wall_s"], rec["sync_wall_s"], rec["round_wall_s"] = t2 - t0, t1 - t0, t2 - t1
        rec["compiles"] = self.compiles.since(compile_mark)
        xfer1 = probes.transfer_counts()
        rec.update({key: xfer1[key] - xfer0[key] for key in xfer1})
        rec["request_bytes"] = req.ByteSize() + round_req.ByteSize()
        rec["submitted"], rec["completed"] = submitted, completed
        rec["leases"], rec["preempted"] = [], []
        self.k += 1
        if resp is None:
            return rec
        rec["response_bytes"] = resp.ByteSize()
        rec["wire_bytes"] = rec["request_bytes"] + rec["response_bytes"]
        rec["leases"] = [(m.job_id, m.node_id, m.queue) for m in resp.scheduled]
        rec["preempted"] = [m.job_id for m in resp.preempted]
        stats = json.loads(resp.pool_stats_json)
        pool = stats["pools"][0] if stats.get("pools") else {}
        # every scalar of the round's own stats: together as `pool`, and each
        # under its own name where the record has not used it (`preempted` is
        # the list of ids; a `cycle_field` reads the count as `pool.preempted`)
        rec["pool"] = {key: v for key, v in pool.items() if not isinstance(v, (dict, list))}
        for key, v in rec["pool"].items():
            rec.setdefault(key, v)
        rec["device"] = {
            key: stats["device"].get(key)
            for key in ("backend", "platform", "device_kind", "device_count", "fallbacks")
        }
        known = self.leased[k] = {}
        for m in resp.scheduled:
            try:
                known[self.world.job_number(m.job_id)] = m
            except KeyError:
                pass  # the checker reports it
        self.leased_at.update(dict.fromkeys(known, k))
        # counted by the harness from the leases and the world's tables: the
        # leases that are a gang's members, and those that ask a resource
        # beyond cpu and memory (the GPUs of a fleet that has them)
        rec["gang_members_leased"] = rec["gpu_leases"] = 0
        if known and (len(self.world.gang_size) or len(self.world.resources) > 2):
            numbers = np.fromiter(known, np.int64, len(known))
            rec["gang_members_leased"] = int((self.world.job_gang[numbers] >= 0).sum())
            rec["gpu_leases"] = int(self.world.shape_req[self.world.job_shape[numbers], 2:].any(axis=1).sum())
        for job_id in rec["preempted"]:
            self.forget(job_id)
        # the sidecar's two roots of this cycle (the plane's own idle
        # scheduler loop also leaves traces in the ring: not this cycle's)
        rec["spans"] = [
            t
            for t in probes.span_trees_since(self.seen_traces)
            if t["name"] in ("sidecar_sync", "sidecar_round")
        ]
        server = 0.0
        for tree in rec["spans"]:
            server += tree["dur_s"]
            apply_ = probes.find_span(tree, "devcache_apply")
            if apply_ is not None:
                rec["scatter_rows"] = {
                    key: apply_.get("args", {}).get(key) for key in ("sg_rows", "rr_rows", "splice")
                }
        rec["server_s"] = server
        rec["wire_s"] = rec["wall_s"] - server if server else None
        return rec

    def failed_reason(self, rec: dict, need_tpu: bool):
        if rec["error"]:
            return rec["error"]
        dev = rec.get("device")
        if dev is None:
            return "no response"
        if dev["fallbacks"] or dev["backend"] != "device":
            return f"CPU fallback: {dev}"
        if need_tpu and dev["platform"] != "tpu":
            return f"the round's arrays live on {dev['platform']}"
        return None

    def warm(self) -> None:
        """Cycles before the window: the first compiles (or reads the cache),
        then at least the mix's lifetime, so that completions flow and backlog
        and running set stand still, at least the mix's `min_warm_cycles`, and
        until three in a row compile nothing."""
        clean = 0
        least = max(self.lifetime, int(self.traffic.get("min_warm_cycles", 0)))
        most = least + EXTRA_WARM_CYCLES
        while self.k < most and not (clean >= CLEAN_CYCLES and self.k > least):
            rec = self.cycle()
            rec["phase"] = "warm"
            self.cycles.append(rec)
            if rec["error"]:
                raise RuntimeError(f"warm cycle {rec['k']} failed: {rec['error']}")
            clean = clean + 1 if not rec["compiles"] else 0
            say(
                f"warm cycle {rec['k']}: {rec['wall_s']:.3f}s, {len(rec['leases'])} leases, "
                f"{len(rec['completed'])} completions, trips {rec.get('kernel_iters')}, "
                f"compiled/fetched {rec['compiles']}, scatter {rec.get('scatter_rows')}"
            )
        self.diag["warm_cycles"] = self.k
        self.diag["warm_clean"] = clean >= CLEAN_CYCLES

    def window(self, trace_dir: str) -> None:
        import jax

        walls = [c["wall_s"] for c in self.cycles[-CLEAN_CYCLES:]]
        expect = max(1, math.ceil(self.seconds / (0.6 * statistics.median(walls))) + 3)
        self.prebuild(expect)
        traced_n = int(self.traffic.get("traced_cycles", 3)) if self.trace else 0
        gc.collect()
        self.first_window_k = self.k
        self.t_window = time.perf_counter()
        self.setup_end = time.time()
        n = 0
        while True:
            if self.trace and n == TRACED_FROM:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 2
                options.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=options)
            rec = self.cycle(traced=self.trace and TRACED_FROM <= n < TRACED_FROM + traced_n)
            rec["phase"] = "window"
            self.cycles.append(rec)
            n += 1
            if self.trace and n == TRACED_FROM + traced_n:
                jax.profiler.stop_trace()
            if rec["error"] or rec["t_end"] - self.t_window >= self.seconds:
                break
        if self.trace and n < TRACED_FROM + traced_n and n > TRACED_FROM:
            jax.profiler.stop_trace()
        self.window_s = self.cycles[-1]["t_end"] - self.t_window
        starts = [c["t_start"] for c in self.cycles[-n:]] + [self.cycles[-1]["t_end"]]
        for c, nxt in zip(self.cycles[-n:], starts[1:]):
            c["generator_s"] = max(0.0, nxt - c["t_end"])

    def stop(self) -> None:
        from armada_tpu.models.verify import healthz_block

        self.verify = healthz_block()  # while the plane still arms it
        try:
            self.wire.close_session(self.wire.pb.ScheduleSessionHandle(session_id=self.sid))
        except Exception as e:  # noqa: BLE001 - shutting down; the result stands
            say(f"CloseSession failed: {e}")
        self.wire.close()
        self.plane.stop()
        self.gclog.close()


def histogram(values, bins: int = 12) -> list:
    lo, hi = min(values), max(values)
    width = (hi - lo) / bins or 1.0
    counts = [0] * bins
    for v in values:
        counts[min(bins - 1, int((v - lo) / width))] += 1
    return [[round(lo + i * width, 4), c] for i, c in enumerate(counts)]


def drift(window: list, traffic: dict | None = None, whole_gangs: bool = False) -> tuple:
    """How far `num_queued` and `num_running` moved between the window's first
    and last cycle, each as {"value", "limit"}, and the problem if one moved by
    more than its limit.  The running count is held to where it stood; the
    queued count to where the mix's own flows put it: with a service rate
    (`completions_per_cycle`) under the arrivals the backlog grows by
    `submits_per_cycle` - `completions_per_cycle` every cycle BY DESIGN (in a
    world with gangs, `whole_gangs`, by the submits less what each record says
    really finished: gangs finish whole, so the rate is a ceiling), and
    what is compared is how far it is from that.  The limit is 0 (exactly)
    unless the mix states `stationary_slack_per_cycle` S, jobs a cycle: then it
    is floor(S x the window's cycles after its first), for both counts, so a
    window of faster cycles is held as tightly as one of slower ones."""
    traffic = traffic or {}
    first, last = ((c.get("num_queued"), c.get("num_running")) for c in (window[0], window[-1]))
    moved = f"not stationary: queued, running {first} -> {last}"
    if None in first or None in last:
        return {}, moved
    steps = len(window) - 1
    grows = due = 0
    if "completions_per_cycle" in traffic:
        rate = int(traffic["completions_per_cycle"])
        grows = int(traffic["submits_per_cycle"]) - rate
        finished = sum(len(c["completed"]) if whole_gangs else rate for c in window[1:])
        due = int(traffic["submits_per_cycle"]) * steps - finished
    limit = math.floor(float(traffic.get("stationary_slack_per_cycle", 0)) * steps)
    out = {
        "queued_drift": {"value": abs(last[0] - first[0] - due), "limit": limit},
        "running_drift": {"value": abs(last[1] - first[1]), "limit": limit},
    }
    if all(c["value"] <= limit for c in out.values()):
        return out, None
    return out, moved + f" over {steps} cycles, the backlog due to grow {grows} a cycle, limit {limit}"


def verdict(run: Run, window: list, on_tpu: bool) -> tuple:
    """(failed cycles {k: why}, problems that make the whole run incorrect,
    every number compared with its limit {name: {"value", "limit"}})."""
    for n, c in enumerate(run.cycles):
        run.checker.cycle(n, c)
    failed = {}
    for c in window:
        reason = run.failed_reason(c, on_tpu)
        if reason:
            failed[c["k"]] = reason
    for n in run.checker.bad_cycles:
        if n >= run.first_window_k:
            failed.setdefault(n, "checker")
    verify = run.verify
    problems = list(run.checker.violations)
    checks = {"checker_violations": {"value": len(problems), "limit": 0}}
    compiled = sum(c["compiles"] for c in window)
    if compiled:
        problems.append(f"{compiled} programs compiled or fetched inside the window")
    if not (verify["enabled"] and verify["failures"] == 0):
        problems.append(f"round verification: {verify}")
    if verify["rounds_verified"] < len(run.cycles):
        problems.append(
            f"round verification covered {verify['rounds_verified']} of {len(run.cycles)} rounds"
        )
    if not run.diag["warm_clean"]:
        problems.append("warm-up never reached three cycles in a row without a compile")
    drifts, moved = drift(window, run.traffic, whole_gangs=bool(len(run.world.gang_size)))
    if moved:
        problems.append(moved)
    checks.update(
        failed_cycles={"value": len(failed), "limit": 0},
        compiles_in_window={"value": compiled, "limit": 0},
        verify_failures={"value": verify["failures"], "limit": 0},
        rounds_unverified={"value": max(0, len(run.cycles) - verify["rounds_verified"]), "limit": 0},
        **drifts,
    )
    return failed, problems, checks


def write_record(run: Run, summary: dict, out_dir: str, on_tpu: bool) -> str:
    """The run's diagnostics: a few lines now, everything in a file."""
    for key in ("wall_histogram", "wall_prefixes", "gc", "problems", "failed_cycles"):
        say(f"{key}: {json.dumps(summary[key])}")
    cache = {k: v for k, v in summary["compile_cache"].items() if k != "backend_compiles"}
    say(f"setup {json.dumps(summary['setup'])}; compile cache {json.dumps(cache)}")
    drop = ("spans", "leases", "preempted", "submitted", "completed", "t_start", "t_end", "pool")
    per_cycle = []
    for c in run.cycles:
        row = {key: v for key, v in c.items() if key not in drop}
        row["leases"] = len(c["leases"])
        row["completions"] = len(c["completed"])
        row["preempted"] = len(c["preempted"])
        row["at_s"] = c["t_start"] - run.t_window
        row["span_s"] = {}
        for tree in c.get("spans", ()):
            _flatten(tree, row["span_s"])
        if not on_tpu:
            for key in [k for k in row if k.endswith("_s")]:
                row.pop(key)  # a CPU run's seconds are not device numbers
        per_cycle.append(row)
    summary["per_cycle"] = per_cycle
    n = 0
    stem = f"{summary['workload']}.seed{summary['seed']}.trace{summary['trace']}"
    while os.path.exists(os.path.join(out_dir, f"{stem}.{n}.json")):
        n += 1
    path = os.path.join(out_dir, f"{stem}.{n}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(summary, f, indent=1, default=str)
    say(f"per-cycle record: {path}")
    return path


def run_cell(args, t0: float) -> tuple:
    """(exit code, result line or None)."""
    cell = Cell(args.benchmark, args.workload)
    try:
        device = device_block(cell.chips, args.allow_cpu)
    except NoAccelerator as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2, None
    on_tpu = device["platform"] == "tpu"
    say(
        f"cell {cell.name} seed {args.seed} seconds {args.seconds} trace {args.trace} on "
        f"{device['count']} x {device['kind']}; PYTHONHASHSEED={os.environ.get('PYTHONHASHSEED')}"
    )
    out_dir = args.out or os.path.join(cell.root, "perfbench_out")
    os.makedirs(out_dir, exist_ok=True)
    run = Run(cell, args.seed, args.seconds, bool(args.trace))
    raw_trace = None
    with tempfile.TemporaryDirectory(prefix="perfbench-") as data_dir:
        trace_dir = os.path.join(data_dir, "profile")  # large: read here, then gone
        run.start(data_dir)
        try:
            run.load_mirror()
            say(
                f"world {run.diag['world_build_s']:.1f}s, mirror of "
                f"{run.diag['mirror_job_states']} job states over the wire "
                f"{run.diag['mirror_load_s']:.1f}s"
            )
            run.warm()
            run.window(trace_dir)
        finally:
            run.stop()
        files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if files:
            names: set = set()
            for c in run.cycles:
                for tree in c.get("spans", ()):
                    probes.span_names(tree, names)
            raw_trace = tracered.load_xplane(files[-1], names)
    setup_s = run.setup_end - t0
    window = [c for c in run.cycles if c["phase"] == "window"]
    failed, problems, checks = verdict(run, window, on_tpu)

    trace = {}
    if args.trace:
        if raw_trace is not None:
            trace = tracered.reduce_trace(raw_trace)
            if args.keep_trace:
                with open(os.path.join(out_dir, f"{cell.name}.trace.json"), "w") as f:
                    json.dump(raw_trace, f)
        if trace:
            trace["device_kind"] = device["kind"]
        elif on_tpu:
            problems.append("the traced window holds no device operation")

    walls = [c["wall_s"] for c in window if not c["error"]]
    leases = sum(len(c["leases"]) for c in window)
    values = {
        "setup_s": setup_s,
        "cycle_p50_s": layers.percentile(walls, 50.0) if walls else None,
        "cycle_p75_s": layers.percentile(walls, 75.0) if walls else None,
        "placements_per_s": leases / run.window_s,
    }
    peak_bytes = memory_peak_bytes()
    ctx = {
        "cycles": window,
        "trace": trace,
        "shapes": {
            "nodes": int(cell.config["world"]["nodes"]),
            "queues": int(cell.config["world"]["queues"]),
            "resources": len(cell.config["world"]["resources"]),
        },
        "run": {"memory_peak_bytes": peak_bytes, "window_s": run.window_s},
    }
    metrics = {}
    if args.trace:
        for declared, reader in cell.per_layer():
            if not on_tpu and declared["source"] != "program_counter":
                continue  # a CPU run gives counts, never a time or a share
            value = layers.read(reader["read"], ctx)
            if value is not None:
                metrics[declared["name"]] = {"value": value, "unit": declared["unit"]}
    elif on_tpu:
        for declared in cell.end_to_end():
            if values.get(declared["name"]) is not None:
                metrics[declared["name"]] = {
                    "value": values[declared["name"]],
                    "unit": declared["unit"],
                }
    dev_out = dict(device, memory_peak_bytes=peak_bytes)
    result = {
        "correct": not failed and not problems,
        "attempted": len(window),
        "failed": len(failed),
        "metrics": metrics,
        "device": dev_out,
    }
    if trace and on_tpu:
        dev_out["busy_s"] = trace["busy_s"]
        dev_out["window_s"] = trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace["device_ops"],
            "idle_gaps": trace["idle_gaps"],
        }
    result["checks"] = checks  # every number compared, beside its limit: the line's last key

    def prefix_stats(limit):
        xs = [c["wall_s"] for c in window if c["t_end"] - run.t_window <= limit]
        if len(xs) < 4:
            return None
        return {"n": len(xs), "p50": layers.percentile(xs, 50), "p75": layers.percentile(xs, 75)}

    summary = {
        "workload": cell.name,
        "seed": args.seed,
        "seconds": args.seconds,
        "trace": int(args.trace),
        "hash_seed": os.environ.get("PYTHONHASHSEED"),
        "device": dev_out,
        "setup": dict(run.diag, setup_s=setup_s),
        "window_s": run.window_s,
        "cycles_in_window": len(window),
        "leases_in_window": leases,
        "values": values if on_tpu else None,
        "wall_histogram": histogram(walls) if walls and on_tpu else None,
        "wall_prefixes": {str(t): prefix_stats(t) for t in (20, 35, 51)} if on_tpu else None,
        "gc": {
            "counts_in_window": [sum(c["gc_counts"][g] for c in window) for g in range(3)],
            "pause_s_in_cycles": sum(c["gc_pause_s"] for c in window) if on_tpu else None,
            "counts_in_run": run.gclog.counts,
            "pause_s_in_run": run.gclog.pause_s if on_tpu else None,
        },
        "compile_cache": {
            "hits": run.compiles.cache_hits,
            "misses_written": run.compiles.cache_misses,
            "backend_compiles": [name for name, _ in run.compiles.compiles],
            "in_window": sum(c["compiles"] for c in window),
        },
        "scatter_rows_seen": sorted(
            {json.dumps(c.get("scatter_rows")) for c in window if c.get("scatter_rows")}
        )[:40],
        "verify": run.verify,
        "failed_cycles": failed,
        "problems": problems,
        "books": {
            "live_leases": len(run.leased_at),
            "initial_runs_live": int(run.checker.run_live.sum()),
            "preempted_in_window": sum(len(c["preempted"]) for c in window),
        },
        "histograms": run.world.histograms(),
        "trace_reduction": trace,
        "result": result,
    }
    write_record(run, summary, out_dir, on_tpu)
    return 0, result


def _flatten(tree: dict, into: dict) -> None:
    into[tree["name"]] = into.get(tree["name"], 0.0) + tree.get("dur_s", 0.0)
    for c in tree.get("children", ()):
        _flatten(c, into)
