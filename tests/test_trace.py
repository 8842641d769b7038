"""Cycle tracing (ops/trace.py): the correlated span timeline, pinned.

1. *Recorder mechanics*: span nesting, ring eviction, the zero-allocation
   off path, cross-thread attachment, per-thread active cycles.
2. *Chrome export*: every emitted event carries the fields Perfetto's JSON
   importer requires; instants/completes/metadata all appear; offset-form
   (wire) dumps convert identically.
3. *Cross-process stitching*: a traced caller driving the sidecar over
   REAL gRPC gets one tree -- its RPC span with the server's round spans
   grafted beneath, same trace id on both sides' ring entries.
4. *Failover attribution*: an injected device_round hang is attributed to
   the cycle that paid it (root tagged degraded + failover_reason, a
   cpu_failover span present) -- the trace answer to "which cycle was the
   failover window".
5. *Bit-neutrality*: the pipeline bit-equality scenario runs with tracing
   explicitly ARMED and stays bit-equal (the recorder only reads clocks).
"""

from __future__ import annotations

import dataclasses
import json
import threading

import pytest

from armada_tpu.ops import trace as trace_mod
from armada_tpu.ops.trace import chrome_trace, reset_recorder


@pytest.fixture(autouse=True)
def _fresh_recorder(monkeypatch):
    monkeypatch.delenv("ARMADA_TRACE", raising=False)
    rec = reset_recorder()
    yield rec
    reset_recorder()


# --- 1. recorder mechanics ---------------------------------------------------


def test_span_nesting_and_args(_fresh_recorder):
    rec = _fresh_recorder
    with rec.cycle("cyc", seq=7):
        with rec.span("outer", pool="default"):
            with rec.span("inner"):
                pass
            rec.note("tick", bytes=42)
        with rec.span("second"):
            pass
    (t,) = rec.last()
    assert t.root.name == "cyc" and t.root.args == {"seq": 7}
    assert [c.name for c in t.root.children] == ["outer", "second"]
    outer = t.root.children[0]
    assert [c.name for c in outer.children] == ["inner", "tick"]
    assert outer.children[1].args == {"bytes": 42}
    assert outer.dur_s >= outer.children[0].dur_s >= 0.0


def test_ring_eviction(monkeypatch):
    rec = reset_recorder(ring=3)
    for i in range(5):
        with rec.cycle("cyc", n=i):
            pass
    assert [t.root.args["n"] for t in rec.last()] == [2, 3, 4]


def test_disabled_and_idle_are_shared_noop(monkeypatch, _fresh_recorder):
    rec = _fresh_recorder
    # no active cycle: spans are the SHARED no-op object (zero allocation)
    assert rec.span("x") is trace_mod._NOOP
    monkeypatch.setenv("ARMADA_TRACE", "0")
    assert rec.cycle("x") is trace_mod._NOOP
    with rec.cycle("x"):
        assert rec.span("y") is trace_mod._NOOP
    assert not rec.last()


def test_cross_thread_spans_attach_to_cycle_root(_fresh_recorder):
    rec = _fresh_recorder
    with rec.cycle("cyc"):

        def worker():
            with rec.span("worker_span"):
                pass

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        t.join()
    (trace,) = rec.last()
    names = [c.name for c in trace.root.children]
    assert "worker_span" in names


def test_zombie_worker_spans_dropped_after_finalize(_fresh_recorder):
    """The recorder's zombie guard (the devcache GenerationGuard idea): a
    watchdog-abandoned worker that unwedges AFTER its cycle finalized must
    neither grow the finalized ring entry nor charge span counts to
    whatever unrelated cycle is primary by then."""
    rec = _fresh_recorder
    handle = []
    with rec.cycle("cyc"):
        with rec.span("round"):
            handle.append(rec.capture())  # what run_with_deadline captures
    (old,) = rec.last()
    n0, round_children0 = old.span_count, len(old.root.children[0].children)

    def zombie():
        rec.adopt(handle[0])
        with rec.span("late_kernel"):
            pass
        rec.note("late_xfer", bytes=1)

    # ...while a NEW unrelated cycle is live
    with rec.cycle("next_cycle") as fresh:
        t = threading.Thread(target=zombie, daemon=True)
        t.start()
        t.join()
        assert fresh.span_count == 1, "zombie must not charge the new cycle"
    assert old.span_count == n0
    assert len(old.root.children[0].children) == round_children0
    names = {c.name for c in rec.last()[-1].root.children}
    assert "late_kernel" not in names and "late_xfer" not in names


def test_nested_cycle_degrades_to_span(_fresh_recorder):
    rec = _fresh_recorder
    with rec.cycle("outer"):
        with rec.cycle("inner"):  # same thread: degrades to a span
            pass
    assert [t.root.name for t in rec.last()] == ["outer"]
    (t,) = rec.last()
    assert [c.name for c in t.root.children] == ["inner"]
    assert rec.nested_cycles == 1


def test_stage_histograms_and_last_stages(_fresh_recorder):
    rec = _fresh_recorder
    with rec.cycle("cyc"):
        with rec.span("stage_a"):
            pass
        with rec.span("stage_a"):  # same stage twice: accumulates
            pass
        with rec.span("stage_b"):
            pass
    stages = rec.last_stages()
    assert set(stages) == {"stage_a", "stage_b"}
    snap = rec.stage_snapshot()
    assert snap["stage.stage_a"]["count"] == 1  # one cycle's accumulation
    assert snap["cycle"]["count"] == 1
    block = rec.healthz_block()
    assert block["cycles"] == 1 and block["kind"] == "cyc"
    assert {s["name"] for s in block["top_spans"]} == {"stage_a", "stage_b"}


def test_annotate_tags_active_root(_fresh_recorder):
    rec = _fresh_recorder
    with rec.cycle("cyc"):
        with rec.span("deep"):
            rec.annotate(degraded=True, failover_reason="drill")
    (t,) = rec.last()
    assert t.root.args["degraded"] is True
    assert t.root.args["failover_reason"] == "drill"


def test_transfer_counters_ride_the_trace(_fresh_recorder):
    from armada_tpu.models.xfer import TRANSFER_STATS

    rec = _fresh_recorder
    TRANSFER_STATS.reset()
    with rec.cycle("cyc"):
        TRANSFER_STATS.count_up(1234)
        TRANSFER_STATS.count_down(99)
    (t,) = rec.last()
    notes = {c.name: c.args for c in t.root.children}
    assert notes["xfer_up"] == {"bytes": 1234}
    assert notes["xfer_down"] == {"bytes": 99}
    # counters themselves are unchanged by the trace ride-along
    assert TRANSFER_STATS.up_bytes == 1234 and TRANSFER_STATS.down_bytes == 99


# --- 2. Chrome trace-event export -------------------------------------------


def _assert_perfetto_schema(doc: dict) -> None:
    assert "traceEvents" in doc
    assert doc["traceEvents"], "export must emit events"
    for ev in doc["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(ev), ev
        if ev["ph"] == "X":
            assert "ts" in ev and "dur" in ev and ev["dur"] > 0
        elif ev["ph"] == "i":
            assert "ts" in ev and ev.get("s") == "t"
        else:
            assert ev["ph"] == "M", f"unexpected phase {ev['ph']}"
    json.dumps(doc)  # JSON-serializable end to end


def test_chrome_trace_schema(_fresh_recorder):
    rec = _fresh_recorder
    for i in range(2):
        with rec.cycle("cyc", n=i):
            with rec.span("stage"):
                rec.note("instant", bytes=1)
    doc = chrome_trace(rec.last())
    _assert_perfetto_schema(doc)
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert phases == {"X", "i", "M"}
    # both cycles share the timeline, separated by the gutter
    xs = [e for e in doc["traceEvents"] if e["name"] == "cyc"]
    assert len(xs) == 2 and xs[1]["ts"] > xs[0]["ts"] + xs[0]["dur"]
    # every non-metadata event is trace-id-labelled for correlation
    for ev in doc["traceEvents"]:
        if ev["ph"] != "M":
            assert ev["args"]["trace_id"]


def test_chrome_trace_from_wire_form(_fresh_recorder):
    """The offset-form dump (armadactl trace --raw, the RPC shape) converts
    through the SAME exporter as live CycleTrace objects."""
    rec = _fresh_recorder
    with rec.cycle("cyc"):
        with rec.span("stage"):
            pass
    dump = json.loads(json.dumps(rec.dump()))  # wire round trip
    doc = chrome_trace(dump["traces"])
    _assert_perfetto_schema(doc)
    assert {"cyc", "stage"} <= {e["name"] for e in doc["traceEvents"]}


# --- 3. cross-process stitching over the sidecar boundary --------------------


def test_sidecar_round_stitches_one_tree(_fresh_recorder):
    from tests.test_pipeline import NOW_NS, make_config, make_job, make_world
    from armada_tpu.jobdb.job import Job
    from armada_tpu.rpc.client import ScheduleClient, job_state_of
    from armada_tpu.rpc.server import make_server
    from armada_tpu.scheduler.executors import ExecutorSnapshot
    from armada_tpu.scheduler.sidecar import ScheduleSidecar

    cfg = make_config(incremental_problem_build=True)
    F, nodes, queues = make_world(cfg)
    sidecar = ScheduleSidecar(cfg, clock_ns=lambda: NOW_NS)
    server, port = make_server(schedule_sidecar=sidecar)
    client = ScheduleClient(f"127.0.0.1:{port}")
    rec = _fresh_recorder
    try:
        sid = client.create_session("t")
        with rec.cycle("caller_cycle"):
            client.sync_state(
                sid,
                jobs=[
                    job_state_of(
                        Job(spec=make_job(F, i, "q0"), queued=True, validated=True)
                    )
                    for i in range(6)
                ],
                executors=[
                    ExecutorSnapshot(
                        id="ex1",
                        pool="default",
                        nodes=tuple(nodes),
                        last_update_ns=NOW_NS,
                    )
                ],
                queues=queues,
                factory=F,
            )
            resp = client.schedule_round(sid, now_ns=NOW_NS)
        assert len(resp.scheduled) > 0
    finally:
        server.stop(0)
        client.close()

    caller = rec.last()[-1]
    assert caller.root.name == "caller_cycle"
    # the caller's tree: exactly the two RPC spans at the top level -- the
    # server's cycles did NOT nest as siblings (per-thread active cycles)
    assert [c.name for c in caller.root.children] == [
        "rpc_sync_state",
        "rpc_schedule_round",
    ]
    rpc = caller.root.children[1]
    # ...with the server's round spans grafted BENEATH the RPC span
    (grafted,) = rpc.children
    assert grafted.name == "sidecar_round" and grafted.args.get("remote")
    sub = set()

    def walk(s):
        sub.add(s.name)
        for c in s.children:
            walk(c)

    walk(grafted)
    assert {"round", "kernel_dispatch", "fetch_decode", "apply_outcome"} <= sub
    # remote spans sit INSIDE the RPC span's window after re-basing
    assert grafted.t0 >= rpc.t0 and grafted.dur_s <= rpc.dur_s + 1e-6

    # both sides' ring entries carry the SAME trace id (the stitch key)
    kinds = {(t.kind, t.trace_id) for t in rec.last()}
    assert ("round", caller.trace_id) in kinds
    assert ("sync", caller.trace_id) in kinds

    # and the whole stitched tree exports as valid Perfetto JSON (client
    # and server share a pid in this in-process topology, so the track
    # split itself is pinned by test_grafted_remote_gets_own_track)
    doc = chrome_trace([caller])
    _assert_perfetto_schema(doc)
    assert grafted.args.get("pid") == caller.pid


def test_grafted_remote_gets_own_track(_fresh_recorder):
    """A grafted subtree from a genuinely different process (distinct pid)
    renders on its own Perfetto process track, descendants included."""
    rec = _fresh_recorder
    remote_pid = 424242
    with rec.cycle("client"):
        with rec.span("rpc"):
            rec.graft(
                {
                    "name": "server_round",
                    "off_s": 0.001,
                    "dur_s": 0.002,
                    "args": {"pid": remote_pid},
                    "children": [
                        {"name": "kernel", "off_s": 0.0015, "dur_s": 0.0005}
                    ],
                }
            )
    doc = chrome_trace(rec.last())
    _assert_perfetto_schema(doc)
    by_name = {
        e["name"]: e for e in doc["traceEvents"] if e["ph"] != "M"
    }
    assert by_name["client"]["pid"] == by_name["rpc"]["pid"] != remote_pid
    assert by_name["server_round"]["pid"] == remote_pid
    assert by_name["kernel"]["pid"] == remote_pid, "descendants inherit"
    meta = {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert f"armada-remote-{remote_pid}" in meta


# --- 4. failover-cycle attribution -------------------------------------------


def test_failover_cycle_attribution(monkeypatch, _fresh_recorder):
    """Under ARMADA_FAULT=device_round:hang, the cycle that paid the
    watchdog deadline + CPU re-run carries the attribution: root tagged
    degraded with the reason, a cpu_failover span in its tree."""
    from tests.test_faults import make_config, make_job, make_world
    from armada_tpu.core import faults, watchdog
    from armada_tpu.models import run_scheduling_round

    faults.reset_counters()
    watchdog.reset_supervisor()
    saved_hooks = list(watchdog._reset_hooks)
    watchdog._reset_hooks.clear()
    monkeypatch.setenv("ARMADA_REPROBE_INTERVAL_S", "0")
    monkeypatch.setenv("ARMADA_WATCHDOG_S", "1.0")
    monkeypatch.setenv("ARMADA_FAULT", "device_round:hang")
    monkeypatch.setenv("ARMADA_FAULT_HANG_S", "8")
    try:
        cfg = make_config()
        F, nodes, queues = make_world(cfg)
        jobs = [make_job(F, i) for i in range(8)]
        rec = _fresh_recorder
        with rec.cycle("drill_cycle"):
            out = run_scheduling_round(
                cfg,
                pool="default",
                nodes=nodes,
                queues=queues,
                queued_jobs=jobs,
                collect_stats=False,
            )
        assert out.scheduled, "failover round must still schedule"
        (t,) = rec.last()
        assert t.root.args["degraded"] is True
        assert "RoundTimeout" in t.root.args["failover_reason"]
        names = set()

        def walk(s):
            names.add(s.name)
            for c in s.children:
                walk(c)

        walk(t.root)
        assert "cpu_failover" in names
        # the re-run's kernel spans sit under the failover span
        failover = next(
            c for c in t.root.children if c.name == "cpu_failover"
        )
        sub = set()
        walk2 = lambda s: (sub.add(s.name), [walk2(c) for c in s.children])  # noqa: E731
        walk2(failover)
        assert "kernel_dispatch" in sub and "fetch_decode" in sub
    finally:
        faults.reset_counters()
        watchdog.reset_supervisor()
        watchdog._reset_hooks[:] = saved_hooks


# --- 5. tracing-armed bit-equality -------------------------------------------


@pytest.mark.fast
def test_pipeline_bit_equality_with_tracing_armed(monkeypatch):
    """The pipeline bit-equality scenario with tracing explicitly ARMED:
    the recorder must be decision-neutral (it only reads clocks and
    appends spans), so pipelined == sequential still holds span-for-span
    instrumented."""
    from tests.test_pipeline import _sidecar_scenario

    monkeypatch.setenv("ARMADA_TRACE", "1")
    reset_recorder()
    a = _sidecar_scenario(monkeypatch, True, True, seed=1)
    b = _sidecar_scenario(monkeypatch, False, True, seed=1)
    assert a[0] == b[0], "per-round decisions diverged under tracing"
    assert a[1] == b[1], "final mirror state diverged under tracing"
    assert any(sched for sched, _ in a[0]), "scenario must schedule"
    # ...and the armed run actually recorded round cycles
    rec = trace_mod.recorder()
    assert any(t.kind == "round" for t in rec.last())


# --- 6. the served cycle's tree is closed (PR 25) ------------------------------


def _names(span, into=None):
    """Span names under `span` (itself excluded), with multiplicity."""
    into = [] if into is None else into
    for c in span.children:
        into.append(c.name)
        _names(c, into)
    return into


def _find(span, name):
    return [c for c in _walk(span) if c.name == name]


def _walk(span):
    for c in span.children:
        yield c
        yield from _walk(c)


def _served_session(maximum_scheduling_burst=16, num_nodes=12, num_queues=3, **config):
    """An in-process sidecar session at the tiny served size, with the
    fleet and the queues synced: (sidecar, session id, factory)."""
    from tests.test_pipeline import NOW_NS, make_config, make_world
    from armada_tpu.rpc import rpc_pb2 as pb  # noqa: F401 - builds the wire module
    from armada_tpu.scheduler.executors import ExecutorSnapshot
    from armada_tpu.scheduler.sidecar import ScheduleSidecar

    cfg = dataclasses.replace(
        make_config(incremental_problem_build=True, **config),
        maximum_scheduling_burst=maximum_scheduling_burst,
    )
    F, nodes, queues = make_world(cfg, num_nodes=num_nodes, num_queues=num_queues)
    sidecar = ScheduleSidecar(cfg, clock_ns=lambda: NOW_NS)
    sid = sidecar.create_session("t")
    sidecar.session(sid).apply_sync(
        executors=[
            ExecutorSnapshot(
                id="ex1", pool="default", nodes=tuple(nodes), last_update_ns=NOW_NS
            )
        ],
        queues=queues,
    )
    return sidecar, sid, F


def _served_cycle(sidecar, sid, F, first, n):
    """One SyncState (n fresh jobs) + one ScheduleRound through the wire
    handlers; returns the cycle's (sync root, round root, response)."""
    from tests.test_pipeline import NOW_NS, make_job
    from armada_tpu.jobdb.job import Job
    from armada_tpu.ops.trace import recorder
    from armada_tpu.rpc import rpc_pb2 as pb
    from armada_tpu.rpc.client import job_state_of

    states = [
        job_state_of(
            Job(spec=make_job(F, first + i, f"q{i % 3}", cpu=1), queued=True, validated=True)
        )
        for i in range(n)
    ]
    sidecar.handle_sync(pb.SyncStateRequest(session_id=sid, jobs=states))
    resp = sidecar.handle_round(pb.ScheduleRoundRequest(session_id=sid, now_ns=NOW_NS))
    sync, rnd = recorder().last()[-2:]
    assert (sync.kind, rnd.kind) == ("sync", "round")
    return sync.root, rnd.root, resp


def test_served_cycle_roots_are_covered_by_named_children(_fresh_recorder):
    sidecar, sid, F = _served_session()
    sync, rnd, resp = _served_cycle(sidecar, sid, F, 0, 6)
    assert len(resp.scheduled) == 6
    # the sync half: lock, convert, upsert, commit -- the feed nests in the commit
    assert [c.name for c in sync.children if c.name != "gc_collect"] == [
        "session_lock_wait", "job_from_state", "mirror_upsert", "mirror_commit",
    ]
    by_name = {c.name: c for c in sync.children}
    # six queued jobs of one shape: one spec template, interned by the first
    # message and serving the other five (the terminal bookkeeping of the
    # request's jobs runs inside this span too)
    assert by_name["job_from_state"].args == {"n": 6, "templates": 1, "spec_hits": 5}
    assert by_name["mirror_upsert"].args == {"n": 6}
    commit = [c.name for c in by_name["mirror_commit"].children if c.name != "gc_collect"]
    assert commit == ["mirror_index", "feed_apply"]
    assert "submit_many" in _names(by_name["mirror_commit"])
    # the round half
    top = [c.name for c in rnd.children if c.name != "gc_collect"]
    assert top[0] == "session_lock_wait"
    # the response's stats JSON is dumped inside the root (PR 28), last
    assert top[-3:] == ["mirror_commit", "slo_feed", "stats_encode"]
    assert {
        "fleet_scan", "pool_nodes", "pool_prepare", "assemble", "round", "apply_outcome",
    } <= set(top)
    (assemble,) = _find(rnd, "assemble")
    assert [c.name for c in assemble.children if c.name != "gc_collect"] == [
        "assemble_order", "assemble_splice", "assemble_bundle",
    ]
    (thunk,) = _find(rnd, "shadow_thunk")
    assert [c.name for c in thunk.children if c.name != "gc_collect"] == ["sweep"]
    (fetch,) = _find(rnd, "fetch_decode")
    inside = [c.name for c in fetch.children if c.name not in ("gc_collect", "xfer_down")]
    # verification is armed by serve, not by a bare sidecar: no verify_fetch here
    assert inside == ["device_wait", "decode"]
    wait, decode = (c for c in fetch.children if c.name in ("device_wait", "decode"))
    assert wait.t1 <= decode.t0 and fetch.t0 <= wait.t0 and decode.t1 <= fetch.t1
    (apply_,) = _find(rnd, "devcache_apply")
    assert apply_.args["full_upload"] is True  # the first round uploads whole


def test_scatter_spans_name_the_compiled_variant(_fresh_recorder):
    sidecar, sid, F = _served_session()
    _served_cycle(sidecar, sid, F, 0, 6)
    _, rnd, _ = _served_cycle(sidecar, sid, F, 100, 6)
    (apply_,) = _find(rnd, "devcache_apply")
    assert apply_.args["program"] in ("apply_delta", "apply_delta.splice")
    rows, runs, splice, fulls = (int(v) for v in apply_.args["bucket"].split("/"))
    assert rows >= apply_.args["sg_rows"] and runs >= apply_.args["rr_rows"]
    assert (splice > 0) == apply_.args["splice"] and fulls >= 0


def test_g_ids_copy_only_on_a_cycle_that_copies(_fresh_recorder):
    sidecar, sid, F = _served_session()
    sync1, round1, _ = _served_cycle(sidecar, sid, F, 0, 6)
    # before any round nothing holds a snapshot of the id vector: the first
    # sync's submits write in place; the round's assemble hands its context a
    # snapshot, and its own leases leaving the backlog pay the one copy: the
    # before-images of the six slots they clear (PR 30), not the vector
    assert not _find(sync1, "g_ids_copy")
    copies = _find(round1, "g_ids_copy")
    assert len(copies) == 1 and copies[0].args["bytes"] == 6 * 48
    sync2, round2, resp2 = _served_cycle(sidecar, sid, F, 100, 6)
    # the sync's submits take the slots the round just cleared, whose
    # before-images the snapshot already holds: nothing more to copy
    assert not _find(sync2, "g_ids_copy")
    assert len(resp2.scheduled) == 6 and len(_find(round2, "g_ids_copy")) == 1
    # six slots under at most the two rounds' snapshots, whatever the width
    assert 6 * 48 <= _find(round2, "g_ids_copy")[0].args["bytes"] <= 2 * 6 * 48
    # a sync and a round that change nothing copy nothing
    from tests.test_pipeline import NOW_NS
    from armada_tpu.ops.trace import recorder
    from armada_tpu.rpc import rpc_pb2 as pb

    for _ in range(2):  # the second round finds the snapshots still held
        sidecar.handle_round(pb.ScheduleRoundRequest(session_id=sid, now_ns=NOW_NS))
    assert not _find(recorder().last()[-1].root, "g_ids_copy")


@pytest.mark.parametrize("burst", [10, 100])
def test_spans_per_cycle_do_not_depend_on_the_burst(_fresh_recorder, burst):
    """Nothing is recorded per job: a cycle of 10 jobs and one of 100 leave
    the same spans (gc_collect apart: one per collection, which follows the
    allocator)."""
    import collections

    # a burst cap above both cycles together: the rate limiter never binds
    sidecar, sid, F = _served_session(maximum_scheduling_burst=1000, num_nodes=40)
    _served_cycle(sidecar, sid, F, 0, burst)  # full upload, first compiles
    sync, rnd, resp = _served_cycle(sidecar, sid, F, 1000, burst)
    assert len(resp.scheduled) == burst
    got = collections.Counter(n for n in _names(sync) + _names(rnd) if n != "gc_collect")
    # one note per array the scatter ships (index vectors, columns, the full
    # fields that changed): a count of fields, never of rows
    assert 30 <= got.pop("xfer_up") <= 45
    assert got == _EXPECTED_STEADY_SPANS, got


# The steady cycle's spans at the tiny served size on the CPU (no prefetch
# there, no verification without serve), whatever the burst.
_EXPECTED_STEADY_SPANS = {
    "session_lock_wait": 2, "job_from_state": 1, "mirror_upsert": 1, "mirror_commit": 2,
    "mirror_index": 2, "feed_apply": 4, "submit_many": 1, "fleet_scan": 1, "pool_nodes": 1,
    "pool_prepare": 1, "assemble": 1, "assemble_order": 1, "assemble_splice": 1,
    "assemble_bundle": 1, "round": 1, "devcache_apply": 1, "kernel_dispatch": 1,
    "decode_dispatch": 1, "shadow": 1, "shadow_thunk": 1, "sweep": 1, "fetch_decode": 1,
    "device_wait": 1, "decode": 1, "apply_outcome": 1, "remove_many": 1, "table_remove": 1,
    "g_ids_copy": 1, "lease_many": 1, "slo_feed": 1, "xfer_down": 1,
    # the queue axis (PR 28): one span a boundary, whatever the queue count
    "queue_tokens": 1, "queue_caps": 1, "stats_encode": 1,
    # the sync's commit (PR 32): the run table asked once for every id that
    # may have left a run behind (here the fresh submits, which hold none)
    "unlease_many": 1,
}


def test_a_served_cycle_builds_no_away_views(_fresh_recorder):
    """One pool with no away pools, on the incremental feed: nothing reads
    the away pass's views of the cycle's own decisions, so none is built and
    no `away_prepare` span opens.  The apply pass counts the priority classes
    it resolved (`classes`) beside the leases it filled (`scheduled`)."""
    sidecar, sid, F = _served_session()
    for first in (0, 100):
        _sync, rnd, resp = _served_cycle(sidecar, sid, F, first, 6)
        assert len(resp.scheduled) == 6
        assert not _find(rnd, "away_prepare")
        (apply_,) = _find(rnd, "apply_outcome")
        assert apply_.args == {
            "pool": "default", "scheduled": 6, "preempted": 0, "classes": 1,
        }


@pytest.mark.parametrize("stats", [False, True], ids=["served", "stats-collected"])
@pytest.mark.parametrize("queues", [8, 300])
def test_queue_axis_spans_once_a_cycle_whatever_the_queue_count(_fresh_recorder, queues, stats):
    """The O(queues) host work of a round has one span a boundary and never
    one a queue: `queue_tokens` (the rate limiters' per-queue tokens; the
    priority overrides too where a provider is set), `queue_caps` (assemble's
    per-queue burst caps), `stats_encode` (the response's stats JSON) and,
    only where the session collects them (a sidecar session does not, unless
    its config publishes metric events or runs the optimiser), `queue_stats`.
    The round's counters ride the `round` span and the stats JSON alike."""
    import collections

    sidecar, sid, F = _served_session(
        num_queues=queues, maximum_scheduling_burst=1000, publish_metric_events=stats
    )
    _served_cycle(sidecar, sid, F, 0, 6)
    _, rnd, resp = _served_cycle(sidecar, sid, F, 100, 6)
    got = collections.Counter(_names(rnd))
    assert {n: got[n] for n in ("queue_tokens", "queue_caps", "stats_encode", "queue_stats")} == {
        "queue_tokens": 1, "queue_caps": 1, "stats_encode": 1, "queue_stats": int(stats),
    }
    (caps,), (tokens,), (encode,) = (_find(rnd, n) for n in ("queue_caps", "queue_tokens", "stats_encode"))
    assert caps.args == {"queues": queues} and tokens.args == {"queues": queues}
    assert encode.args == {"pools": 1, "bytes": len(resp.pool_stats_json)}
    pool = json.loads(resp.pool_stats_json)["pools"][0]
    # _served_cycle submits to q0..q2 only: three queues hold jobs, three are leased from
    # the queue axis pads to min(shape_bucket, 256): 64 with make_config's bucket
    axis = {"queues": queues, "queues_padded": -(-queues // 64) * 64,
            "queues_pending": 3, "queues_scheduled": 3}
    if stats:
        (span,) = _find(rnd, "queue_stats")
        assert span.args == {"queues": queues}
        assert set(pool["queue_stats"]) == {f"q{i}" for i in range(queues)}
        assert 1 <= pool["fair_share_iterations"] <= 10
        axis["fair_share_iterations"] = pool["fair_share_iterations"]
    else:
        assert pool["queue_stats"] == {} and "fair_share_iterations" not in pool
    assert {k: pool[k] for k in axis} == axis
    (round_span,) = _find(rnd, "round")
    assert {k: round_span.args[k] for k in axis} == axis


def test_gc_collect_is_charged_to_the_span_that_paid(_fresh_recorder):
    import gc

    from armada_tpu.ops.trace import arm_gc, disarm_gc

    rec = _fresh_recorder
    before = len(gc.callbacks)
    token = arm_gc()
    other = arm_gc()  # a second plane: still one hook
    assert len(gc.callbacks) == before + 1
    try:
        gc.collect()  # no cycle open anywhere: dropped
        assert not rec.last()
        with rec.cycle("cyc"):
            with rec.span("work"):
                gc.collect()
                gc.collect()
                gc.collect(0)
            t = threading.Thread(target=gc.collect)  # a thread with no cycle
            t.start()
            t.join()
        (trace,) = rec.last()
        (work,) = trace.root.children  # nothing landed on the root
        # one span per collection, over the interval in which it ran
        assert [c.name for c in work.children] == ["gc_collect"] * 3
        assert [c.args["generation"] for c in work.children] == [2, 2, 0]
        first, second, young = work.children
        assert work.t0 <= first.t0 < first.t1 <= second.t0 < second.t1 <= young.t0
        assert young.t1 <= work.t1 and "collected" in first.args
        gc.collect()  # the cycle is finished: dropped
        assert len(work.children) == 3
    finally:
        disarm_gc(token)
        assert len(gc.callbacks) == before + 1
        disarm_gc(other)
    assert len(gc.callbacks) == before


def test_self_time_of_a_parent_with_overlapping_children_on_two_threads():
    from armada_tpu.ops.trace import Span, self_seconds, top_spans

    parent = Span("round", 10.0, 1, None)
    parent.t1 = 11.0
    for name, t0, t1, tid in (
        ("kernel_dispatch", 10.1, 10.4, 2),  # the watchdog's worker, adopted
        ("shadow", 10.3, 10.6, 1),  # overlaps it on the waiting thread
        ("fetch_decode", 10.9, 11.5, 2),  # runs past the parent: clipped
        ("xfer_down", 10.7, 10.7, 1),  # a note covers nothing
    ):
        child = Span(name, t0, tid, None)
        child.t1 = t1
        parent.children.append(child)
    doc = parent.to_dict(10.0)
    # 1.0 - ([.1,.6] + [.9,1.0]) = 0.4
    assert self_seconds(doc) == pytest.approx(0.4)
    assert self_seconds(doc["children"][0]) == pytest.approx(0.3)  # a leaf: all of it
    root = Span("cyc", 10.0, 1, None)
    root.t1 = 11.0
    root.children.append(parent)
    ranked = {s["name"]: s for s in top_spans(root.to_dict(10.0))}
    assert ranked["round"]["self_s"] == pytest.approx(0.4)
    assert ranked["round"]["dur_s"] == pytest.approx(1.0)


def test_notes_and_collections_pass_through_the_profiler_bridge(monkeypatch):
    import gc

    from armada_tpu.ops.trace import arm_gc, disarm_gc

    monkeypatch.setenv("ARMADA_TRACE_JAX", "1")
    rec = reset_recorder()
    seen = []

    class _Ctx:
        def __init__(self, name):
            self.name = name

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    def enter(name, args=None):
        seen.append(("enter", name, dict(args or {})))
        return _Ctx(name)

    monkeypatch.setattr(rec, "_enter_jax", enter)
    token = arm_gc()
    try:
        with rec.cycle("cyc"):
            rec.note("xfer_up", bytes=42)
            gc.collect()
    finally:
        disarm_gc(token)
    assert ("enter", "xfer_up", {"bytes": 42}) in seen
    assert seen.index(("exit", "xfer_up")) == seen.index(("enter", "xfer_up", {"bytes": 42})) + 1
    assert ("enter", "gc_collect", {}) in seen and ("exit", "gc_collect") in seen


# --- 7. the device programs carry names, and only names (PR 25) ----------------


def _canonical_hlo(text: str) -> str:
    """Optimized HLO with operation metadata and the source tables (four
    blocks between the module's header and its computations) dropped and
    every instruction renamed by order of appearance (named scopes shift the
    compiler's name counters)."""
    import re

    text = re.sub(r",?\s*metadata=\{[^}]*\}", "", text)
    text, tables = re.subn(
        r"\n(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*", "\n", text
    )
    assert tables == 4 and "ENTRY" in text and "while(" in text
    names: dict = {}

    def rename(m):
        return names.setdefault(m.group(0), f"%{len(names)}")

    return re.sub(r"%[\w.\-]+", rename, text)


def _served_round_call(monkeypatch):
    """The round program of a served cycle at the tiny size: (the jitted
    function, its problem, its statics)."""
    from armada_tpu.models import fair_scheduler as fs

    captured = {}
    real = fs._schedule_round_jit

    def spy(p, **statics):
        captured.setdefault("call", (p, statics))
        return real(p, **statics)

    monkeypatch.setattr(fs, "_schedule_round_jit", spy)
    sidecar, sid, F = _served_session()
    _served_cycle(sidecar, sid, F, 0, 6)
    return (real,) + captured["call"]


def test_named_scopes_change_only_metadata(_fresh_recorder, monkeypatch):
    import contextlib

    import jax

    real, p, statics = _served_round_call(monkeypatch)
    named = real.lower(p, **statics).compile().as_text()
    for scope in (
        "armada.round/armada.round.evict", "armada.round/armada.round.loop/while",
        "armada.round.loop/while/body/select", "armada.round.loop/while/body/fit",
        "armada.round.loop/while/body/commit", "armada.round/armada.round.repair",
    ):
        assert scope in named, scope
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())

    # under jit's wrapper sits named_scope("armada.round")'s, then the function
    bare_round = real.__wrapped__.__wrapped__

    def unnamed(p, **kw):  # a fresh function: nothing traced under the names is reused
        return bare_round(p, **kw)

    unnamed.__name__ = bare_round.__name__  # the module is named after it
    bare_fn = jax.jit(unnamed, static_argnames=tuple(statics))
    # compiled afresh: a persistent compilation cache that an earlier test of
    # this process enabled keys the program without its names, and would hand
    # back the named executable
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        bare = bare_fn.lower(p, **statics).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert "armada." not in bare
    assert _canonical_hlo(named) == _canonical_hlo(bare)


def _hlo_computations(text: str) -> dict:
    """name -> the instruction lines of each computation of an HLO module."""
    import re

    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            cur.append(line)
    return comps


def _gathers(comps: dict, root: str, skip=()) -> list:
    """(operand's leading dimension, result elements) of every gather in
    computation `root` and in what it calls (fusions, calls, branches,
    nested loops), `skip` aside."""
    import math
    import re

    dims = r"\w+\[([\d,]*)\]"
    found, seen, todo = [], set(skip), [root]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        shape_of = {}
        for line in comps[name]:
            m = re.match(rf"\s*(?:ROOT )?(%[\w.\-]+) = {dims}", line)
            if m:
                shape_of[m.group(1)] = [int(d) for d in m.group(2).split(",") if d]
            m = re.match(rf"\s*(?:ROOT )?%[\w.\-]+ = {dims}\S* gather\((%[\w.\-]+),", line)
            if m:
                result = math.prod(int(d) for d in m.group(1).split(",") if d)
                found.append((shape_of[m.group(2)][0], result))
            todo.extend(re.findall(r"(?:calls|to_apply|body|condition|\w+_computation)=(%[\w.\-]+)", line))
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo.extend(group.split(", "))
    return found


def test_loop_body_gathers_the_skip_window_only_in_the_refill_branch(_fresh_recorder, monkeypatch):
    """PR 26: the placement loop carries its [Q, W] skip window.  In the
    compiled round the loop's body holds no gather from a [G] table with a
    [Q, W] result, but in ONE branch of the `window` conditional: the refill,
    which is the three gathers every trip used to make."""
    import re

    from armada_tpu.models import fair_scheduler as fs

    real, p, statics = _served_round_call(monkeypatch)
    comps = _hlo_computations(real.lower(p, **statics).compile().as_text())
    window = (p.g_req.shape[0], p.q_weight.shape[0] * fs._SKIP_WINDOW)
    assert window[0] not in (window[1], p.compat.shape[0])  # [G] is not mistaken
    (body,) = {
        re.search(r"body=(%[\w.\-]+)", line).group(1)
        for name in comps
        for line in comps[name]
        if " while(" in line and 'armada.round.loop/while"' in line
    }
    (cond,) = [
        line for line in comps[body] if " conditional(" in line and "/body/window/cond" in line
    ]
    branches = re.findall(r"(?:true|false)_computation=(%[\w.\-]+)", cond) or re.findall(
        r"%[\w.\-]+", cond.split("branch_computations={")[1].split("}")[0]
    )
    assert len(branches) == 2
    assert sorted(_gathers(comps, b).count(window) for b in branches) == [0, 3]
    outside = _gathers(comps, body, skip=branches)
    assert len(outside) > 10 and window not in outside


# The profiler names a call of a device program "jit_" + the jitted function
# (its `XLA Modules` line) and an operation by its HLO text alone, so a trace
# reader finds programs by the first and scopes through the compiled text.
_DEVICE_PROGRAMS = {
    "armada.round": "_schedule_round_jit",
    "armada.compact": "compact_result",
    "armada.verify": "_verify_kernel_impl",
    "armada.explain": "_explain_kernel_impl",
    "armada.scatter": "apply_delta",
}


@pytest.mark.parametrize("scope", sorted(_DEVICE_PROGRAMS))
def test_device_programs_keep_their_names(scope, _fresh_recorder, monkeypatch):
    """Each program of the serving path is one jitted function under one
    scope: the function's name is what the profiler calls the program."""
    import jax

    from armada_tpu.models import explain, fair_scheduler as fs, slab, verify

    name = _DEVICE_PROGRAMS[scope]
    if scope in ("armada.round", "armada.compact"):
        # wrapped at import: read the scope off the program a served cycle ran
        real = getattr(fs, name)
        calls = []

        def spy(*args, **kw):
            calls.append((args, kw))
            return real(*args, **kw)

        monkeypatch.setattr(fs, name, spy)
        sidecar, sid, F = _served_session()
        _served_cycle(sidecar, sid, F, 0, 6)
        args, kw = calls[0]
        text = real.lower(*args, **kw).compile().as_text()
        assert text.startswith(f"HloModule jit_{name}")
        assert f'op_name="jit({name})/{scope}/' in text
        return
    seen = []
    named_scope = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope", lambda name: seen.append(name) or named_scope(name)
    )
    if scope == "armada.scatter":
        fn = slab._make_apply()
    else:
        module = verify if scope == "armada.verify" else explain
        monkeypatch.setattr(module, "_KERNEL", None)
        fn = module._kernel()
    assert seen == [scope]
    assert fn.__name__ == name


# What a device trace is read by (perfbench/harness/scopes.py): the op_name
# path of each operation, which must name the program and, in the round's
# loop, the trip's phase.
_SCOPES_IN_OP_NAME = {
    "_schedule_round_jit": (
        "armada.round/armada.round.evict/", "armada.round.loop/while/body/select/",
        "armada.round.loop/while/body/fit/", "armada.round.loop/while/body/commit/",
        "armada.round.loop/while/body/window/", "armada.round/armada.round.repair/",
    ),
    "compact_result": ("/armada.compact/",),
    "_verify_kernel_impl": ("/armada.verify/",),
    "apply_delta": ("/armada.scatter/",),
}


def test_served_programs_carry_their_scopes_in_op_name(_fresh_recorder, monkeypatch):
    """Two served cycles at the tiny size; each program they ran, lowered
    (not compiled) with the arguments it ran with, names its scopes in the
    `op_name` metadata of its operations."""
    from armada_tpu.models import fair_scheduler as fs, slab, verify

    calls: dict = {}

    def spy(real):
        def call(*args, **kw):
            calls.setdefault(real.__name__, (real, args, kw))
            return real(*args, **kw)

        return call

    for name in ("_schedule_round_jit", "compact_result"):
        monkeypatch.setattr(fs, name, spy(getattr(fs, name)))
    monkeypatch.setattr(verify, "_KERNEL", spy(verify._kernel()))
    monkeypatch.setattr(slab, "_APPLY", spy(slab._make_apply()))
    monkeypatch.setenv("ARMADA_VERIFY", "1")
    sidecar, sid, F = _served_session()
    _served_cycle(sidecar, sid, F, 0, 6)
    _served_cycle(sidecar, sid, F, 6, 6)
    assert sorted(calls) == sorted(_SCOPES_IN_OP_NAME)
    for name, scopes in _SCOPES_IN_OP_NAME.items():
        real, args, kw = calls[name]
        text = real.lower(*args, **kw).as_text(debug_info=True)
        for scope in scopes:
            assert f'"jit({name})/' in text and scope in text, (name, scope)
