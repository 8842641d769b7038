"""Health checkers + the debug/profiling HTTP endpoint.

Equivalent of the reference's common runtime surface:
- internal/common/health: `Checker` (checker.go), `MultiChecker`
  (multi_checker.go), `StartupCompleteChecker` (startup_complete_checker.go),
  and the HTTP handler semantics (http_handler.go: 204 when healthy, 503 +
  error text when not; mounted at /health, http_mux_setup.go).
- internal/common/profiling/http.go: an on-demand profiling server.  Go gets
  net/http/pprof for free; the Python-native analogues here are
  /debug/pprof/profile?seconds=N (process-wide statistical sampler over
  sys._current_frames -- every thread, not just the handler's), /debug/pprof/
  heap (tracemalloc snapshot, started on first use) and /debug/pprof/threads
  (stack dump of every live thread).

One ThreadingHTTPServer serves both surfaces; components register checkers.
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
from collections import Counter
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional
from urllib.parse import parse_qs, urlparse

from armada_tpu.analysis.tsan import make_lock


def sample_profile(seconds: float, interval_s: float = 0.01) -> str:
    """Statistical profile of EVERY thread in the process: sample
    sys._current_frames at `interval_s` for `seconds`, report the hottest
    (function, file:line) entries by inclusive sample count.  The py-spy-style
    answer to Go's process-wide net/http/pprof CPU profile."""
    own = threading.get_ident()
    leaf = Counter()
    inclusive = Counter()
    samples = 0
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        for tid, frame in sys._current_frames().items():
            if tid == own:
                continue
            samples += 1
            first = True
            seen = set()
            while frame is not None:
                code = frame.f_code
                key = f"{code.co_name} ({code.co_filename}:{code.co_firstlineno})"
                if first:
                    leaf[key] += 1
                    first = False
                if key not in seen:  # count once per stack for inclusive
                    inclusive[key] += 1
                    seen.add(key)
                frame = frame.f_back
        time.sleep(interval_s)
    out = [f"{samples} samples over {seconds:.2f}s ({interval_s * 1000:.0f}ms interval)\n"]
    out.append("--- inclusive (on stack) ---")
    for key, n in inclusive.most_common(60):
        out.append(f"{n:8d}  {key}")
    out.append("--- self (leaf frame) ---")
    for key, n in leaf.most_common(40):
        out.append(f"{n:8d}  {key}")
    return "\n".join(out) + "\n"


class StartupCompleteChecker:
    """Healthy once the component finished starting (startup_complete_checker.go)."""

    def __init__(self):
        self._complete = False

    def mark_complete(self) -> None:
        self._complete = True

    def check(self) -> Optional[str]:
        return None if self._complete else "startup not complete yet"


class FunctionChecker:
    """Wraps a callable returning None (healthy) or an error string."""

    def __init__(self, fn: Callable[[], Optional[str]], name: str = ""):
        self._fn = fn
        self.name = name

    def check(self) -> Optional[str]:
        return self._fn()


class MultiChecker:
    """Joins constituent checkers; unhealthy if any is (multi_checker.go)."""

    def __init__(self, *checkers):
        self._lock = make_lock("health.multi_checker")
        self._checkers = list(checkers)

    def add(self, checker) -> None:
        with self._lock:
            self._checkers.append(checker)

    def check(self) -> Optional[str]:
        with self._lock:
            checkers = list(self._checkers)
        if not checkers:
            return "no checkers registered"
        errors = []
        for c in checkers:
            try:
                e = c.check()
            except Exception as exc:  # a broken checker is unhealthy, not a 500
                e = f"checker {getattr(c, 'name', type(c).__name__)!r} raised: {exc}"
            if e:
                errors.append(e)
        return "\n".join(errors) if errors else None


class _Handler(BaseHTTPRequestHandler):
    server_version = "armada-tpu-health/1"

    def log_message(self, fmt, *args):  # quiet by default
        pass

    def _respond(self, status: int, body: bytes = b"", ctype="text/plain") -> None:
        self.send_response(status)
        if body:
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        if body:
            self.wfile.write(body)

    def do_GET(self):  # noqa: N802 (stdlib naming)
        parsed = urlparse(self.path)
        path = parsed.path.rstrip("/") or "/"
        srv: "HealthServer" = self.server.owner  # type: ignore[attr-defined]
        if path == "/health":
            err = srv.checker.check()
            if err is None:
                self._respond(204)
            else:
                self._respond(503, err.encode())
        elif path == "/healthz":
            # Structured liveness: the same checker verdict as /health plus
            # the DEGRADATION state (device backend, consecutive failures,
            # last fallback reason -- core/watchdog).  A plane running on
            # the CPU failover is degraded-but-HEALTHY: liveness must not
            # flip (restarting it would not fix the device), the operator
            # reads the device block instead (docs/operations.md runbook):
            # backend, fallbacks, and the platform / device kind / count
            # the last round's output arrays lived on.
            import json

            err = srv.checker.check()
            body = {"healthy": err is None, "error": err}
            if srv.device_status is not None:
                try:
                    body["device"] = srv.device_status()
                except Exception as exc:  # noqa: BLE001
                    body["device"] = {"error": str(exc)}
            if srv.mesh_status is not None:
                # Mesh serving block (parallel/serving.py): configured vs
                # currently-served device count, degrade-ladder history --
                # a plane serving on a halved mesh is degraded-but-healthy
                # exactly like the CPU-failover rung below it.
                try:
                    body["mesh"] = srv.mesh_status()
                except Exception as exc:  # noqa: BLE001
                    body["mesh"] = {"error": str(exc)}
            if srv.slo_status is not None:
                # Streaming SLO block (scheduler/slo.py): cycle-latency /
                # TTFL / ingest-lag percentiles, so an operator reads tail
                # latency from the same endpoint that reports degradation
                # (docs/operations.md soak runbook).
                try:
                    body["slo"] = srv.slo_status()
                except Exception as exc:  # noqa: BLE001
                    body["slo"] = {"error": str(exc)}
            if srv.durability_status is not None:
                # Durability block (scheduler/checkpoint.py + replicator):
                # snapshot age/fence, current epoch, replication lag --
                # the RPO/RTO signals of the recovery runbook.
                try:
                    body["durability"] = srv.durability_status()
                except Exception as exc:  # noqa: BLE001
                    body["durability"] = {"error": str(exc)}
            if srv.trace_status is not None:
                # Trace block (ops/trace.py): the last cycle's identity +
                # top spans -- the at-a-glance "where did the cycle go"
                # before reaching for armadactl trace + Perfetto.
                try:
                    body["trace"] = srv.trace_status()
                except Exception as exc:  # noqa: BLE001
                    body["trace"] = {"error": str(exc)}
            if srv.explain_status is not None:
                # Explain block (models/explain.py via the reports repo):
                # last unschedulable-reason attribution per pool -- reason
                # counts, fragmentation indices, per-key table.
                try:
                    body["explain"] = srv.explain_status()
                except Exception as exc:  # noqa: BLE001
                    body["explain"] = {"error": str(exc)}
            if srv.verify_status is not None:
                # Round-verification block (models/verify.py +
                # scheduler/quarantine.py): last verdict, per-site failure
                # census, the device quarantine scoreboard.  A plane with
                # quarantined devices is degraded-but-HEALTHY like the CPU
                # failover below it -- the operator reads this block and
                # clears via `armadactl quarantine --clear`.
                try:
                    body["verify"] = srv.verify_status()
                except Exception as exc:  # noqa: BLE001
                    body["verify"] = {"error": str(exc)}
            if srv.pools_status is not None:
                # Pool-parallel serving block (scheduler/pool_serving.py):
                # parallel vs serial-fallback cycle counts, stacked-launch
                # totals, last overlap ratio and per-pool round seconds --
                # how the multi-tenant cycle is actually being served.
                try:
                    body["pools"] = srv.pools_status()
                except Exception as exc:  # noqa: BLE001
                    body["pools"] = {"error": str(exc)}
            if srv.ingest_status is not None:
                # Ingest-plane block (ingest/stats.py): per-consumer
                # events/s and per-partition lag, shard counts, abandoned
                # threads, and per-shard store-leg write latency
                # (`store_write`, round 19 sharded stores) -- whether the
                # materialized views keep up with the log, per view, and
                # whether the store legs commit in parallel or convoy.
                try:
                    body["ingest"] = srv.ingest_status()
                except Exception as exc:  # noqa: BLE001
                    body["ingest"] = {"error": str(exc)}
            if srv.dlq_status is not None:
                # Dead-letter block (ingest/dlq.py): quarantined-record
                # and batch-retry census plus pending control-plane halts.
                # A nonzero control_halts entry means a shard is parked
                # waiting for an operator verdict (`armadactl dlq`) -- the
                # plane is degraded-but-HEALTHY, like quarantine above.
                try:
                    body["dlq"] = srv.dlq_status()
                except Exception as exc:  # noqa: BLE001
                    body["dlq"] = {"error": str(exc)}
            self._respond(
                200 if err is None else 503,
                (json.dumps(body) + "\n").encode(),
                ctype="application/json",
            )
        elif path == "/ready":
            # Readiness is liveness + the optional gate (e.g. leadership in
            # replicated deployments: followers stay out of the k8s Service
            # so writes only ever reach the log of record).
            err = srv.checker.check()
            if err is None and srv.ready_checker is not None:
                err = srv.ready_checker()
            if err is None:
                self._respond(204)
            else:
                self._respond(503, err.encode())
        elif path == "/debug/pprof/profile" and srv.profiling:
            qs = parse_qs(parsed.query)
            try:
                seconds = float(qs.get("seconds", ["5"])[0])
            except ValueError:
                self._respond(400, b"bad seconds parameter\n")
                return
            seconds = min(max(seconds, 0.01), 120.0)
            self._respond(200, sample_profile(seconds).encode())
        elif path == "/debug/pprof/heap" and srv.profiling:
            import tracemalloc

            if not tracemalloc.is_tracing():
                tracemalloc.start()
                self._respond(
                    200,
                    b"tracemalloc started; call again for a snapshot\n",
                )
                return
            snap = tracemalloc.take_snapshot()
            lines = [
                str(stat) for stat in snap.statistics("lineno")[:80]
            ]
            self._respond(200, ("\n".join(lines) + "\n").encode())
        elif path == "/debug/pprof/threads" and srv.profiling:
            out = []
            for tid, frame in sys._current_frames().items():
                name = next(
                    (t.name for t in threading.enumerate() if t.ident == tid),
                    str(tid),
                )
                out.append(f"--- thread {name} ({tid}) ---")
                out.extend(traceback.format_stack(frame))
            self._respond(200, "".join(f"{l}\n" if not l.endswith("\n") else l for l in out).encode())
        else:
            self._respond(404)


class HealthServer:
    """Serves /health (+ /debug/pprof/* when profiling=True) on `port`."""

    def __init__(self, port: int = 0, profiling: bool = False, host: str = "127.0.0.1"):
        self.checker = MultiChecker()
        # Optional () -> error-or-None gate behind /ready (readiness can be
        # stricter than liveness: a healthy follower is alive but not ready).
        self.ready_checker = None
        # Optional () -> dict: the device-degradation block /healthz embeds
        # (serve wires core/watchdog.supervisor().snapshot here).
        self.device_status = None
        # Optional () -> dict: the mesh serving block (serve --mesh wires
        # parallel/serving.mesh_serving().snapshot here).
        self.mesh_status = None
        # Optional () -> dict: the streaming SLO block (serve wires
        # scheduler/slo.recorder().snapshot here).
        self.slo_status = None
        # Optional () -> dict: the durability block (serve wires
        # Scheduler.durability_status: snapshot age/fence, epoch,
        # replication lag).
        self.durability_status = None
        # Optional () -> dict: the cycle-trace block (serve wires
        # ops/trace.recorder().healthz_block: last cycle's top spans).
        self.trace_status = None
        # Optional () -> dict: last explain-pass attribution per pool
        # (serve wires SchedulingReportsRepository.explain_summary).
        self.explain_status = None
        # Optional () -> dict: the round-verification block (serve wires
        # models/verify.healthz_block: last verdict, failure census,
        # device quarantine scoreboard).
        self.verify_status = None
        # Optional () -> dict: pool-parallel serving scoreboard (serve
        # wires scheduler/pool_serving.pool_serving_stats().snapshot).
        self.pools_status = None
        # Optional () -> dict: ingest-plane block (serve wires
        # ingest/stats.registry().snapshot plus shard/partition config).
        self.ingest_status = None
        # Optional () -> dict: dead-letter block (serve wires
        # ingest/dlq.DlqAdmin.status: quarantine census, batch retries,
        # pending control-plane halts, per-store row counts).
        self.dlq_status = None
        self.profiling = profiling
        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.owner = self  # type: ignore[attr-defined]
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._thread.join(timeout=5)
        self._httpd.server_close()
