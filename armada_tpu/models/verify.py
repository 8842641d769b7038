"""Round-output verification: catch silent device corruption before decode
commits a poisoned round.

The robustness ladder so far only fires on LOUD failures: a hung device
trips the watchdog (core/watchdog), a raised XLA error walks the mesh
degrade ladder (parallel/serving).  A silently-wrong device result has no
defense -- the round-12 GSPMD reduction miscompile returned every
compact-header scalar multiplied by the shard count and was only caught by
a failing test.  Armada's
event-sourcing discipline makes decisions durable facts once published, so
one corrupted round poisons the JobDb, the mirror and every downstream
view; the cheapest place to stop it is between fetch and decode.

This module is the third small jitted pass over the round-final slab (the
explain-pass dispatch economics, models/explain.py: ONE i32 buffer, ONE
extra device->host transfer, dispatched in the decode shadow and fetched
after the outcome).  It certifies the round two independent ways:

* *Conservation invariants*, each a redundancy cross-check between two
  encodings of the same decision set the kernel maintains separately --
  corruption of either side breaks the agreement:

    slot-count      sum of live slot member counts == header sched_count
    gang-count      sum of placed queue-gang cardinalities == sched_count
    slot-state      per-gang slot occurrences match g_state == 1 exactly
                    (no double slot, no placed gang without a slot)
    gang-card       every live slot's member count == its gang's g_card
    lane            live placement lanes target in-range, node_ok nodes
    node-capacity   clean-level allocatable == node_total - retained run
                    usage - new placements (per node, per resource)
    queue-alloc     q_alloc == retained run usage + placed gang requests
                    (per queue, per resource; the f32 accumulator check)
    evictee         run_rescheduled implies run_evicted

  The two alloc checks re-derive the kernel's accumulators with vectorized
  scatter-adds over the FINAL masks (the exact algebra is pinned in
  tests/test_verify.py's sequential oracle): a retained run is
  ``valid & (~evicted | rescheduled)`` -- evicted-and-rescheduled runs keep
  ONE copy of their usage (the level-0 marker; the re-placement at levels
  >= 1 never touches the clean level), preempted runs' markers are dropped
  by the kernel's final unbind.  f32 association differs from the kernel's
  sequential adds, so both compare under a tolerance that still catches
  every corruption class that matters (flipped exponent/high-mantissa
  bits, the xN shard miscompile) -- resolution units are integral, so the
  slack is pure headroom until sums cross 2^24.

* A *fingerprint* (XOR + wrapping-sum fold) of the compact result buffer,
  computed ON DEVICE over the exact i32 buffer the decode transfer
  carries.  Host-side decode stashes the bytes it actually received
  (HostContext.last_compact_np) and ``finish_verify`` re-derives the folds
  from them: transfer truncation or bit-flips are detected independently
  of the invariant pass (which sees only device-resident state).

Any violation raises ``RoundVerificationError``; models.run_round_on_device
treats it like a device fault -- reset hooks fire, the SAME round re-runs
(mesh ladder first if armed, then the CPU rung; the re-run PASSING there
is the evidence the corruption was device-side -- bit-equal to a clean
replay on one backend, while XLA:TPU and XLA:CPU may break f32
packing-score ties differently, docs/operations.md -- and a CPU-side
failure escalates loudly instead of looping) -- and feeds the per-device
quarantine score (scheduler/quarantine.DeviceQuarantine: N strikes within
a window stop the re-probe loops from re-promoting that device until
``armadactl quarantine --clear``).

Arming: ``ARMADA_VERIFY`` (1/0) wins, else the latest armed plane default
(serve arms 1 via --verify/--no-verify through arm_default/disarm_default
tokens), else the library default 0 -- tests and embedders never pay the
extra compile or transfer unless they arm it.  Unlike explain there is no
cadence: a correctness gate that skips rounds is not a gate.

Drills: ``ARMADA_FAULT=round_corrupt:{header,lane,bytes}[:after_n]``
(core/faults; ``maybe_corrupt_result`` + the fetched-bytes flip in
problem._fetch_compact) inject each corruption class without a broken
chip; tools/chaos_cycle.py --corrupt is the standing drill.
"""

from __future__ import annotations

import itertools
import os
import time
from typing import Optional

import numpy as np

from armada_tpu.analysis.tsan import make_lock

_VERSION = 1
_VHEADER = 16  # i32 slots; layout below (append, never reorder)
# header slot indices
_H_VERSION = 0
_H_FLAGS = 1
_H_FP_XOR = 2
_H_FP_SUM = 3
_H_N_SLOTS = 4
_H_SLOT_MEMBERS = 5
_H_SCHED_COUNT = 6
_H_PLACED_GANGS = 7
_H_PLACED_MEMBERS = 8
_H_NODE_DIFF_BITS = 9
_H_QUEUE_DIFF_BITS = 10
_H_COMPACT_LEN = 11
_H_N_EVICTED = 12
_H_N_RESCHEDULED = 13

# Invariant bit order is part of the wire layout AND the metrics `site`
# label vocabulary: append, never reorder.  The two host-side sites
# ("fingerprint", "buffer") follow the device bits.
CHECK_NAMES = (
    "slot-count",
    "gang-count",
    "slot-state",
    "gang-card",
    "lane",
    "node-capacity",
    "queue-alloc",
    "evictee",
)
SITE_FINGERPRINT = "fingerprint"
SITE_BUFFER = "buffer"
ALL_SITES = CHECK_NAMES + (SITE_FINGERPRINT, SITE_BUFFER)


class RoundVerificationError(RuntimeError):
    """A scheduling round failed output verification: one or more
    conservation invariants were violated on device, or the fetched compact
    buffer's fingerprint did not match the device-computed one.  Carries
    the failed site names; run_round_on_device treats it like a device
    fault (reset hooks + ladder re-run + quarantine strike)."""

    def __init__(self, sites, detail: str = ""):
        self.sites = tuple(sites)
        msg = f"round verification failed: {', '.join(self.sites)}"
        if detail:
            msg += f" ({detail})"
        super().__init__(msg)


# ---------------------------------------------------------------- arming ----


def verify_enabled() -> bool:
    """Round verification armed?  ``ARMADA_VERIFY`` wins (1/0), else the
    most recently armed still-running plane default (arm_default), else the
    library default (0).  A malformed env value falls back to the armed
    default -- a wrapper exporting garbage must not silently disarm a
    serve-armed gate (the ARMADA_WATCHDOG_S parse discipline)."""
    env = os.environ.get("ARMADA_VERIFY")
    if env is not None:
        try:
            return int(env) != 0
        except ValueError:
            pass
    if _ARMED:
        return bool(next(reversed(_ARMED.values())))
    return _DEFAULT


_DEFAULT = False
# Token-ordered armed plane defaults (the explain/watchdog discipline:
# overlapping plane lifetimes never corrupt the default).
_ARMED: dict = {}
_next_token = itertools.count(1)


def set_default(enabled: bool) -> bool:
    """Process LIBRARY default (embedders); returns the previous value."""
    global _DEFAULT
    prev = _DEFAULT
    _DEFAULT = bool(enabled)
    return prev


def arm_default(enabled: bool = True) -> int:
    token = next(_next_token)
    _ARMED[token] = bool(enabled)
    return token


def disarm_default(token: int) -> None:
    _ARMED.pop(token, None)


# ----------------------------------------------------------------- state ----


class VerifyState:
    """Process-global verification ledger: per-site failure counts + the
    last verdict, feeding /healthz, prometheus and the pool reports.  Like
    the watchdog supervisor, ONE per process -- every pool's rounds share
    the device under test."""

    def __init__(self):
        self._lock = make_lock("verify.state")
        self.rounds = 0  # rounds that ran the verification pass
        self.failures = 0  # rounds that failed it
        self.failures_by_site: dict = {}
        self.last_verdict: Optional[dict] = None

    def record_pass(self, pool: str = "") -> None:
        with self._lock:
            self.rounds += 1
            self.last_verdict = {"ok": True, "pool": pool, "ts": time.time()}

    def record_failure(self, sites, pool: str = "", detail: str = "") -> None:
        with self._lock:
            self.rounds += 1
            self.failures += 1
            for s in sites:
                self.failures_by_site[s] = self.failures_by_site.get(s, 0) + 1
            self.last_verdict = {
                "ok": False,
                "pool": pool,
                "sites": list(sites),
                "detail": detail[:300],
                "ts": time.time(),
            }

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "enabled": verify_enabled(),
                "rounds_verified": self.rounds,
                "failures": self.failures,
                "failures_by_site": dict(self.failures_by_site),
                "last_verdict": (
                    dict(self.last_verdict) if self.last_verdict else None
                ),
            }


_STATE = VerifyState()


def verify_state() -> VerifyState:
    return _STATE


def reset_verify_state() -> VerifyState:
    """Fresh ledger (tests)."""
    global _STATE
    _STATE = VerifyState()
    return _STATE


def healthz_block() -> dict:
    """The /healthz `verify` block: last verdict + failure census + the
    device quarantine scoreboard (scheduler/quarantine.py)."""
    block = verify_state().snapshot()
    from armada_tpu.scheduler.quarantine import device_quarantine

    block["quarantine"] = device_quarantine().snapshot()
    return block


# ---------------------------------------------------------------- kernel ----

_KERNEL = None


def _kernel():
    """Build the jitted verification program on first use: the module must
    stay importable without initializing a jax backend (CLI/metrics/health
    read only the constants and the state ledger)."""
    global _KERNEL
    if _KERNEL is None:
        import jax

        _KERNEL = jax.jit(jax.named_scope("armada.verify")(_verify_kernel_impl))
    return _KERNEL


def _verify_kernel_impl(
    node_total,
    node_ok,
    node_axes,
    run_req,
    run_node,
    run_queue,
    run_valid,
    g_req,
    g_card,
    g_queue,
    g_run,
    g_state,
    slot_gang,
    slot_nodes,
    slot_counts,
    n_slots,
    run_evicted,
    run_rescheduled,
    alloc0,
    q_alloc,
    scheduled_count,
    compact_buf,
    num_real_gangs,
):
    """Dense conservation invariants + compact-buffer fingerprint over the
    round-final state; ONE i32[_VHEADER] buffer out.

    Everything is a single dense pass (no while_loop), so the in-loop
    kernel economics rules do not arise -- the explain-pass precedent.
    O(S*W*R + G + RJ*R + N*R) work, negligible next to the round kernel.
    """
    import jax
    import jax.numpy as jnp

    G = g_state.shape[0]
    N, R = node_total.shape
    S, W = slot_nodes.shape

    real_g = jnp.arange(G, dtype=jnp.int32) < num_real_gangs
    placed = real_g & (g_state == 1)
    placed_q = placed & (g_run < 0)  # queue gangs own slots; evictees do not

    ns = n_slots.astype(jnp.int32)
    live_slot = jnp.arange(S, dtype=jnp.int32) < ns
    livef = live_slot.astype(jnp.int32)
    mc = jnp.sum(slot_counts, axis=1) * livef  # members per live slot

    # slot-count: the header accumulator vs the slot record (two encodings
    # the kernel maintains independently).  Also bounds n_slots and counts.
    slot_members = jnp.sum(mc)
    bad_slot_count = (
        (slot_members != scheduled_count)
        | (ns < 0)
        | (ns > S)
        | jnp.any(live_slot[:, None] & (slot_counts < 0))
    )

    # gang-count: the g_state encoding of the same total.
    placed_members = jnp.sum(g_card * placed_q.astype(jnp.int32))
    bad_gang_count = placed_members != scheduled_count

    # slot-state: per-gang slot occurrences must match g_state == 1 exactly
    # (dead slots default to gang 0 -- masked by the live weight).
    occ = jnp.zeros((G,), jnp.int32).at[slot_gang].add(livef, mode="drop")
    bad_slot_state = jnp.any(occ != placed_q.astype(jnp.int32))

    # gang-card: a live slot's member count is its gang's cardinality.
    sg_safe = jnp.clip(slot_gang, 0, G - 1)
    bad_gang_card = jnp.any(live_slot & (mc != g_card[sg_safe] * livef))

    # lane: live placement lanes target in-range, schedulable nodes.
    lane_live = live_slot[:, None] & (slot_counts > 0)
    node_in_range = (slot_nodes >= 0) & (slot_nodes < N)
    lane_ok = node_in_range & node_ok[jnp.clip(slot_nodes, 0, N - 1)]
    bad_lane = jnp.any(lane_live & ~lane_ok)

    # evictee: a rescheduled run must have been evicted first.
    bad_evictee = jnp.any(run_valid & run_rescheduled & ~run_evicted)

    # node-capacity: clean-level allocatable re-derived from the FINAL
    # masks.  A retained run (valid & (~evicted | rescheduled)) counts ONE
    # copy of its usage at the clean level -- the evicted marker stays at
    # level 0 and the re-placement at levels >= 1 never touches it; a
    # preempted run's marker was dropped by the kernel's final unbind.
    holds = run_valid & (~run_evicted | run_rescheduled)
    run_req_node = run_req * node_axes[None, :]
    used = jnp.zeros((N, R), jnp.float32).at[run_node].add(
        run_req_node * holds.astype(jnp.float32)[:, None], mode="drop"
    )
    g_req_node = g_req * node_axes[None, :]
    lane_members = (slot_counts * lane_live).astype(jnp.float32)  # [S, W]
    lane_req = lane_members[:, :, None] * g_req_node[sg_safe][:, None, :]
    used = used.at[slot_nodes.reshape(-1)].add(
        lane_req.reshape(S * W, R), mode="drop"
    )
    expected_free0 = node_total - used
    # Per-ELEMENT tolerance: resolutions differ by orders of magnitude
    # across the resource axis (cpu in milli-units, memory in bytes), so a
    # global scalar tolerance would let the largest resource's headroom
    # swallow real corruption in the smallest.
    node_diff_e = jnp.abs(alloc0 - expected_free0)
    node_diff = jnp.max(node_diff_e)
    bad_node = jnp.any(node_diff_e > 0.5 + 1e-3 * node_total)

    # queue-alloc: the kernel's f32 per-queue accumulator vs the same
    # retained-runs + placed-gangs algebra (evictee re-placements ride the
    # run-side `holds` mask; queue gangs ride the slot-side g_state mask).
    Q = q_alloc.shape[0]
    expected_q = jnp.zeros((Q, R), jnp.float32).at[run_queue].add(
        run_req * holds.astype(jnp.float32)[:, None], mode="drop"
    )
    gang_tot = g_req * (
        g_card.astype(jnp.float32) * placed_q.astype(jnp.float32)
    )[:, None]
    expected_q = expected_q.at[g_queue].add(gang_tot, mode="drop")
    queue_diff_e = jnp.abs(q_alloc - expected_q)
    queue_diff = jnp.max(queue_diff_e)
    bad_queue = jnp.any(
        queue_diff_e
        > 1.0 + 1e-3 * jnp.maximum(jnp.abs(expected_q), jnp.abs(q_alloc))
    )

    flags = (
        bad_slot_count.astype(jnp.int32) * (1 << 0)
        + bad_gang_count.astype(jnp.int32) * (1 << 1)
        + bad_slot_state.astype(jnp.int32) * (1 << 2)
        + bad_gang_card.astype(jnp.int32) * (1 << 3)
        + bad_lane.astype(jnp.int32) * (1 << 4)
        + bad_node.astype(jnp.int32) * (1 << 5)
        + bad_queue.astype(jnp.int32) * (1 << 6)
        + bad_evictee.astype(jnp.int32) * (1 << 7)
    )

    # Fingerprint of the compact decode buffer, folded ON DEVICE over the
    # exact i32 lanes the transfer carries: XOR (order-free, catches any
    # odd set of flipped bits) + wrapping sum (catches paired flips and
    # truncation-with-zero-fill XOR misses at zero lanes).
    fp_xor = jax.lax.reduce(
        compact_buf, jnp.int32(0), jax.lax.bitwise_xor, (0,)
    )
    fp_sum = jnp.sum(compact_buf, dtype=jnp.int32)

    bits = lambda v: jax.lax.bitcast_convert_type(  # noqa: E731
        v.astype(jnp.float32), jnp.int32
    )
    out = jnp.zeros((_VHEADER,), jnp.int32)
    out = out.at[_H_VERSION].set(_VERSION)
    out = out.at[_H_FLAGS].set(flags)
    out = out.at[_H_FP_XOR].set(fp_xor)
    out = out.at[_H_FP_SUM].set(fp_sum)
    out = out.at[_H_N_SLOTS].set(ns)
    out = out.at[_H_SLOT_MEMBERS].set(slot_members)
    out = out.at[_H_SCHED_COUNT].set(scheduled_count.astype(jnp.int32))
    out = out.at[_H_PLACED_GANGS].set(jnp.sum(placed_q.astype(jnp.int32)))
    out = out.at[_H_PLACED_MEMBERS].set(placed_members)
    out = out.at[_H_NODE_DIFF_BITS].set(bits(node_diff))
    out = out.at[_H_QUEUE_DIFF_BITS].set(bits(queue_diff))
    out = out.at[_H_COMPACT_LEN].set(jnp.int32(compact_buf.shape[0]))
    out = out.at[_H_N_EVICTED].set(
        jnp.sum((run_valid & run_evicted).astype(jnp.int32))
    )
    out = out.at[_H_N_RESCHEDULED].set(
        jnp.sum((run_valid & run_rescheduled).astype(jnp.int32))
    )
    return out


def dispatch_verify(device_problem, result, compact_dispatched, ctx):
    """Enqueue the verification kernel behind the round + the compact
    dispatch WITHOUT reading it back; returns the device buffer or None
    (pass unavailable: host-array result, or no compact
    buffer to fingerprint -- the full-pull fallback already reads every
    array, so a truncated compact transfer cannot reach it).  Mirrors
    explain.dispatch_explain: the dispatch/fetch split lets the device
    compute and its device->host copy ride the decode shadow."""
    import jax

    if not isinstance(result.g_state, jax.Array):
        return None
    if compact_dispatched is None:
        return None
    compact_buf = compact_dispatched[0]
    buf = _kernel()(
        device_problem.node_total,
        device_problem.node_ok,
        device_problem.node_axes,
        device_problem.run_req,
        device_problem.run_node,
        device_problem.run_queue,
        device_problem.run_valid,
        device_problem.g_req,
        device_problem.g_card,
        device_problem.g_queue,
        device_problem.g_run,
        result.g_state,
        result.slot_gang,
        result.slot_nodes,
        result.slot_counts,
        result.n_slots,
        result.run_evicted,
        result.run_rescheduled,
        result.alloc[0],
        result.q_alloc,
        result.scheduled_count,
        compact_buf,
        np.int32(ctx.num_real_gangs),
    )
    try:
        buf.copy_to_host_async()
    except (AttributeError, RuntimeError):
        pass  # backend without async copies: the fetch blocks normally
    return buf


_KERNEL_STACKED = None


def _kernel_stacked():
    global _KERNEL_STACKED
    if _KERNEL_STACKED is None:
        import jax

        _KERNEL_STACKED = jax.jit(
            jax.named_scope("armada.verify")(jax.vmap(_verify_kernel_impl))
        )
    return _KERNEL_STACKED


def dispatch_verify_stacked(device_problem, result, compact_buf, ctxs):
    """Verification for a STACKED round (pool-parallel serving, round 17):
    vmap the invariant kernel over the pool lanes of the stacked problem /
    result / compact buffer -- ONE [P, _VHEADER] buffer, ONE extra
    device->host transfer for the whole stack (the begin_decode_stacked
    economics).  `compact_buf` is the stacked [P, L] compact device buffer;
    each lane's fingerprint folds over exactly the row its decode transfer
    carries.  Returns the device buffer or None (host-array result / no
    compact buffer).  Per-lane verdicts come from ``finish_verify`` on the
    fetched rows (models.__init__ fetches once and verdicts per pool)."""
    import jax

    if not isinstance(result.g_state, jax.Array):
        return None
    if compact_buf is None:
        return None
    buf = _kernel_stacked()(
        device_problem.node_total,
        device_problem.node_ok,
        device_problem.node_axes,
        device_problem.run_req,
        device_problem.run_node,
        device_problem.run_queue,
        device_problem.run_valid,
        device_problem.g_req,
        device_problem.g_card,
        device_problem.g_queue,
        device_problem.g_run,
        result.g_state,
        result.slot_gang,
        result.slot_nodes,
        result.slot_counts,
        result.n_slots,
        result.run_evicted,
        result.run_rescheduled,
        result.alloc[:, 0],
        result.q_alloc,
        result.scheduled_count,
        compact_buf,
        np.asarray([c.num_real_gangs for c in ctxs], np.int32),
    )
    try:
        buf.copy_to_host_async()
    except (AttributeError, RuntimeError):
        pass  # backend without async copies: the fetch blocks normally
    return buf


def host_fingerprint(buf: np.ndarray) -> tuple:
    """(xor, sum) folds over a host i32 buffer, matching the device folds
    bit-for-bit (i32 wraparound on the sum)."""
    arr = np.ascontiguousarray(buf, dtype=np.int32)
    fp_xor = int(np.bitwise_xor.reduce(arr)) if arr.size else 0
    fp_sum = int(np.sum(arr.astype(np.int64)) & 0xFFFFFFFF)
    return fp_xor & 0xFFFFFFFF, fp_sum


def finish_verify(dispatched, ctx, pool: str = "") -> dict:
    """Blocking fetch + verdict of a dispatched verification buffer (ONE
    device->host transfer, counted in TRANSFER_STATS).  Cross-checks the
    device fingerprint against the bytes decode ACTUALLY used
    (HostContext.last_compact_np, stashed by problem._fetch_compact).
    Raises RoundVerificationError on any violation; returns the verdict
    summary on success."""
    buf = np.asarray(dispatched)
    from armada_tpu.models.xfer import TRANSFER_STATS

    TRANSFER_STATS.count_down(buf.nbytes)
    return verdict_of(buf, ctx, pool=pool)


def verdict_of(buf: np.ndarray, ctx, pool: str = "") -> dict:
    """The host-side verdict over one pool's ALREADY-FETCHED i32[_VHEADER]
    row -- finish_verify's tail, split out so the stacked path can fetch
    all pools' rows in one transfer and verdict each at its pool's turn."""
    state = verify_state()

    if buf.shape[0] != _VHEADER or int(buf[_H_VERSION]) != _VERSION:
        detail = f"verify buffer corrupt (len={buf.shape[0]})"
        state.record_failure([SITE_BUFFER], pool, detail)
        raise RoundVerificationError([SITE_BUFFER], detail)

    sites = []
    flags = int(buf[_H_FLAGS])
    for bit, name in enumerate(CHECK_NAMES):
        if flags & (1 << bit):
            sites.append(name)

    compact_raw = getattr(ctx, "last_compact_np", None)
    if compact_raw is not None:
        fp_xor, fp_sum = host_fingerprint(compact_raw)
        dev_xor = int(buf[_H_FP_XOR]) & 0xFFFFFFFF
        dev_sum = int(buf[_H_FP_SUM]) & 0xFFFFFFFF
        if (
            fp_xor != dev_xor
            or fp_sum != dev_sum
            or compact_raw.size != int(buf[_H_COMPACT_LEN])
        ):
            sites.append(SITE_FINGERPRINT)

    if sites:
        detail = (
            f"sched_count={int(buf[_H_SCHED_COUNT])} "
            f"slot_members={int(buf[_H_SLOT_MEMBERS])} "
            f"placed_members={int(buf[_H_PLACED_MEMBERS])} "
            f"node_diff={float(np.int32(buf[_H_NODE_DIFF_BITS]).view(np.float32)):.3f} "
            f"queue_diff={float(np.int32(buf[_H_QUEUE_DIFF_BITS]).view(np.float32)):.3f}"
        )
        state.record_failure(sites, pool, detail)
        raise RoundVerificationError(sites, detail)

    state.record_pass(pool)
    return {
        "ok": True,
        "placed_gangs": int(buf[_H_PLACED_GANGS]),
        "scheduled_count": int(buf[_H_SCHED_COUNT]),
    }


# ---------------------------------------------------------------- drills ----


def maybe_corrupt_result(result):
    """The device-side legs of the ``round_corrupt`` fault site
    (core/faults; one-shot): `header` perturbs the scheduled_count header
    scalar, `lane` overwrites a placement lane with an out-of-range node --
    each breaks exactly the redundancy its invariant cross-checks.  The
    `bytes` leg (a fetched-transfer bit flip) lives in
    problem._fetch_compact, where the bytes exist.  Costs one dict lookup
    when ARMADA_FAULT is unset."""
    from armada_tpu.core import faults

    if not os.environ.get("ARMADA_FAULT"):
        return result
    mode = faults.active("round_corrupt", modes=("header", "lane"))
    if mode is None:
        return result
    import jax.numpy as jnp

    if mode == "header":
        return result._replace(
            scheduled_count=result.scheduled_count + jnp.int32(7)
        )
    # lane: point a placement lane at an out-of-range node.  Force the
    # lane LIVE (count >= 1, n_slots >= 1) so the drill is observable even
    # on a round that placed nothing -- a masked injection would burn the
    # one-shot entry and report green, implicating verification instead of
    # the drill world.
    N = result.alloc.shape[1]
    return result._replace(
        slot_nodes=result.slot_nodes.at[0, 0].set(jnp.int32(N)),
        slot_counts=result.slot_counts.at[0, 0].max(jnp.int32(1)),
        n_slots=jnp.maximum(result.n_slots, jnp.int32(1)),
    )
