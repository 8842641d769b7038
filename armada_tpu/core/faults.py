"""Fault-injection harness for resilience drills and tests.

The production failure modes this repo must survive -- a device round
hanging or erroring mid-round, a severed pgwire socket,
an event-log publish failure, an executor pod-submit rejection -- are all
rare and environment-dependent, so the code paths that handle them rot
unless they can be triggered on demand.  ``ARMADA_FAULT`` injects them:

    ARMADA_FAULT=<site>:<mode>[:<after_n>][,<site>:<mode>[:<after_n>]...]

* ``site``  -- an injection point name (see the catalogue below).
* ``mode``  -- ``error`` (raise), ``hang`` (block, bounded by
  ``ARMADA_FAULT_HANG_S``, default 120s -- long enough that only a watchdog
  recovers, short enough that abandoned test threads drain), or ``exit``
  (``os._exit(137)``: a REAL crash, no atexit/finally -- only meaningful in
  subprocess drills, where the parent observes the kill and restarts).
* ``after_n`` -- skip the first N checks of that site, fire on check N+1.
  Each entry fires ONCE and then disarms (counters are process-global), so
  a drill injects a deterministic single fault and the system's recovery is
  observable: ``chaos_cycle.py`` and the tests assert convergence after it.

Sites wired in this repo (docs/operations.md has the operator catalogue):

    device_round     the device scheduling round (models.run_round_on_device
                     worker: dispatch + fetch) -- hang simulates a device
                     wedge, error simulates an XLA failure
    pgwire           the external-PostgreSQL adapter's statement path
                     (ingest/sqladapter.py) -- fires as a severed socket
    eventlog_publish the event-log publisher (eventlog/publisher.py), before
                     any append so the failure is all-or-nothing
    executor_submit  the executor's pod submission (executor/service.py)
    ingest_ack       the ingestion pipeline, between the batch's
                     transactional commit and the in-memory cursor ack
                     (ingest/pipeline.py) -- the crash window the
                     exactly-once design exists for
    snapshot_write   the checkpoint writer, before any file is written
                     (scheduler/checkpoint.py) -- a crash mid-snapshot must
                     leave recovery falling back to the previous snapshot
    leader_promote   the scheduler's promotion branch, after winning the
                     election and before the recovery fence completes
                     (scheduler/scheduler.py) -- promotion must re-run
                     idempotently on the next cycle
    convert_record   a poison RECORD in the ingest plane (ingest/dlq.py):
                     the first fire latches the triggering batch's first
                     raw payload as STICKY poison -- every later convert
                     of that payload raises deterministically, modelling a
                     record that fails on every retry (a one-shot fault
                     would succeed on retry and never exercise the
                     dead-letter path).  ``dlq.reset_poison()`` clears the
                     latch; ``after_n`` counts conversion batches.
    round_corrupt    SILENT device corruption of a scheduling round, with
                     the corruption class as the mode: ``header`` perturbs
                     the compact header's scheduled_count scalar on
                     device, ``lane`` overwrites a placement lane with an
                     out-of-range node (models/verify.maybe_corrupt_result),
                     ``bytes`` flips a bit in the FETCHED compact buffer
                     (models/problem._fetch_compact -- transfer
                     corruption).  Only observable when round verification
                     is armed (ARMADA_VERIFY): the whole point of the
                     drill is that an unverified plane would commit it.

Checks are env-driven per call (monkeypatch-friendly) and cost one dict
lookup when ``ARMADA_FAULT`` is unset.
"""

from __future__ import annotations

import os
import time

from armada_tpu.analysis.tsan import make_lock


class FaultInjected(RuntimeError):
    """An ``error``-mode injected fault.  Subclasses RuntimeError so device
    sites are handled exactly like a real XLA runtime error."""


_lock = make_lock("faults.state")
# (site, mode, after_n) -> number of checks seen / whether it already fired.
_counts: dict[tuple, int] = {}
_fired: set[tuple] = set()


def reset_counters() -> None:
    """Forget check counts and fired state (tests/drills re-arm)."""
    with _lock:
        _counts.clear()
        _fired.clear()


def _parse(spec: str):
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        parts = entry.split(":")
        if len(parts) < 2:
            continue  # malformed entries are ignored, not fatal
        site, mode = parts[0], parts[1]
        try:
            after_n = int(parts[2]) if len(parts) > 2 else 0
        except ValueError:
            continue
        yield site, mode, after_n


def armed(site: str) -> bool:
    """True when ANY entry for `site` is present in ARMADA_FAULT, without
    advancing counters or consuming one-shot state.  The cheap outer gate
    for sites whose check itself has a cost (ingest/dlq.py re-serializes
    payloads only when the poison drill is armed)."""
    spec = os.environ.get("ARMADA_FAULT")
    if not spec:
        return False
    return any(s == site for s, _mode, _n in _parse(spec))


def active(site: str, modes=None):
    """The mode to fire for `site` on THIS check, or None.  Advances the
    per-entry check counter; one-shot (an entry never fires twice).

    `modes` restricts which entry modes THIS check point consumes: sites
    whose modes live at different code points (round_corrupt's `header`/
    `lane` fire device-side in models/__init__, `bytes` fires at the
    fetched-transfer boundary in models/problem.py) must not advance or
    burn each other's entries -- a filtered-out entry is left untouched
    for its own check point."""
    spec = os.environ.get("ARMADA_FAULT")
    if not spec:
        return None
    for s, mode, after_n in _parse(spec):
        if s != site:
            continue
        if modes is not None and mode not in modes:
            continue
        key = (s, mode, after_n)
        with _lock:
            if key in _fired:
                continue
            n = _counts.get(key, 0)
            _counts[key] = n + 1
            if n < after_n:
                continue
            _fired.add(key)
        return mode
    return None


def check(site: str, exc: type = FaultInjected) -> None:
    """Fire the armed fault for `site`, if any: mode ``error`` raises
    ``exc`` (default FaultInjected), mode ``hang`` blocks for
    ARMADA_FAULT_HANG_S seconds (a bounded stand-in for a device wedge:
    only an external watchdog observes it as a timeout; the hung thread
    eventually drains so tests do not leak forever-threads)."""
    mode = active(site)
    if mode is None:
        return
    if mode == "hang":
        budget = float(os.environ.get("ARMADA_FAULT_HANG_S", 120.0))
        deadline = time.monotonic() + budget
        while time.monotonic() < deadline:
            time.sleep(min(0.05, budget))
        return
    if mode == "exit":
        # A real kill: no exception handlers, no finally blocks, no atexit
        # -- exactly what a power loss looks like to the durable state on
        # disk.  137 = SIGKILL's conventional exit status.
        os._exit(137)
    raise exc(f"injected fault at {site!r}")
