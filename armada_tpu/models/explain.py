"""The explain pass: on-device unschedulable-reason attribution.

The reference answers "why wasn't my job scheduled" with per-job,
per-node-type unschedulable reasons recorded while the scheduler walks each
job (internal/scheduler/reports, nodedb.go PodRequirementsNotMetReason).  At
1M queued jobs per-job Python forensics cannot exist -- this module is the
dense equivalent: a SECOND jitted program that runs after the round kernel
over the same device-resident slab and attributes every unplaced job to a
dominant reason, per *scheduling key* (core/keys.class_signature determines
(request, PC), so K << J and the pass is O(K x N) dense), decoded lazily on
host (the LazyJobIds pattern: host work stays O(reported)).

Reason codes (the catalogue; docs/observability.md):

  ``shape-infeasible``   the key fits NO node even empty (static
                         selector/taint masks + node totals) -- resubmitting
                         will never help on this fleet.
  ``capacity-blocked``   fits at least one empty node, but current
                         allocations block it: it was attempted and failed
                         the fit, or was still pending when the round ended
                         with NO node able to hold it at the round-final
                         free capacity (the default config's 1.0 round-cap
                         fraction trips exactly when the pool fills, so the
                         full-pool overflow must read as capacity, not as
                         an incidental termination).  This is the
                         fragmentation signal; the pass also reports the
                         pool's largest-fitting-request-per-resource
                         fragmentation index.
  ``fairness-capped``    the job's queue was deactivated by a per-queue
                         burst or per-(queue, PC) cap at its priority level
                         (RoundResult.q_killed) while the job was still
                         pending.
  ``gang-partial``       a multi-member gang (or a gang invalidated by the
                         all-or-nothing rollback) could not place as a unit.
  ``round-terminated``   the round stopped first (global burst / round
                         resource cap / iteration budget) while round-final
                         capacity could still hold the job -- a genuinely
                         early stop, not exhaustion.
  ``type-mismatch``      the key would fit an empty node if its node-type
                         whitelist (JobSpec.node_type_scores) were ignored,
                         but every node of an admitted hardware type is too
                         small / tainted / selector-excluded.  Splits the
                         old shape-infeasible bucket: resubmitting with a
                         wider type map CAN help, resubmitting a true
                         shape-infeasible job cannot.

``shape-infeasible``, ``capacity-blocked``, ``gang-partial`` and
``type-mismatch`` partition the *failed* set (g_state == 2); all reasons
can appear for still-pending jobs (g_state == 0), which are reported in
the queue/pool histograms but are not in ``RoundOutcome.failed``,
mirroring the kernel's semantics (gated gangs keep their chance next
round).

Transfer economics (the CLAUDE.md constraint): the whole result packs into
ONE i32 buffer fetched in ONE device->host transfer (~90KB at the default
caps), dispatched in the decode shadow and fetched after the round's own
compact fetch, and amortized every ``ARMADA_EXPLAIN_INTERVAL`` rounds
(0 = disabled -- the library/tests default; serve arms 10, bench arms it
for the headline).  Attribution uses only round-final state, so reading it
off the critical path is sound: shape-infeasibility is time-invariant and
capacity/fairness/termination attribution is defined against the round the
operator asks about.

Approximations (documented, pinned by tests/test_explain.py):
- the per-key representative request is a scatter-max over the key's
  unplaced gangs; builder problems intern (request, PC) into the key
  (core/keys.py) so this is exact, synthetic label-keys get max-request
  attribution (observability only -- decisions never read this pass).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import os
from typing import Optional

import numpy as np

# Reason code order is part of the wire layout AND the bench/report key
# names: append, never reorder.
REASON_NONE = 0
REASON_SHAPE = 1
REASON_CAPACITY = 2
REASON_FAIRNESS = 3
REASON_GANG = 4
REASON_TERMINATED = 5
REASON_TYPE = 6
NUM_REASONS = 7
REASON_NAMES = (
    "none",
    "shape-infeasible",
    "capacity-blocked",
    "fairness-capped",
    "gang-partial",
    "round-terminated",
    "type-mismatch",
)
# The reasons that partition RoundOutcome.failed (g_state == 2).
FAILED_REASONS = (REASON_SHAPE, REASON_CAPACITY, REASON_GANG, REASON_TYPE)

# Packed-buffer caps; module-level so tests can shrink them to force the
# truncation paths (mirrors problem._COMPACT_FCAP).
_EXPLAIN_KCAP = 4096
_EXPLAIN_FCAP = 8192

_HEADER = 8  # [version, n_keys, n_failed_gangs, n_failed_jobs, Q, R, T, 0]
_VERSION = 2  # v2: type-mismatch reason + per-type fragmentation rows


def explain_interval() -> int:
    """Cadence in rounds; 0 disables.  ``ARMADA_EXPLAIN_INTERVAL`` wins,
    else the most recently armed plane default (arm_default), else the
    library default set_default_interval governs -- 0, so tests and
    library embedders never pay the extra compile or transfer unless they
    arm it.  A malformed env value falls back to the armed/process default
    (the ARMADA_WATCHDOG_S parse discipline): a wrapper script exporting
    garbage must not silently disarm a serve-armed pass."""
    env = os.environ.get("ARMADA_EXPLAIN_INTERVAL")
    if env is not None:
        try:
            return max(0, int(env))
        except ValueError:
            pass
    if _ARMED:
        return next(reversed(_ARMED.values()))
    return _DEFAULT_INTERVAL


_DEFAULT_INTERVAL = 0
# Armed plane defaults, token -> interval (insertion-ordered: the latest
# armed still-running plane wins).  Token-based like the watchdog
# supervisor's arm/disarm, so overlapping plane lifetimes (HA tests start
# two planes and stop them in either order) never corrupt the default.
_ARMED: dict = {}
_next_token = itertools.count(1)
_round_counters: dict = {}


def set_default_interval(interval: int) -> int:
    """Process LIBRARY default used when the env var is unset and no plane
    has armed one; returns the previous value (restore discipline for
    embedders).  Serving planes use arm_default/disarm_default instead."""
    global _DEFAULT_INTERVAL
    prev = _DEFAULT_INTERVAL
    _DEFAULT_INTERVAL = max(0, int(interval))
    return prev


def arm_default(interval: int) -> int:
    """Arm a plane-scoped explain default; returns a token for
    disarm_default.  The latest armed token wins while several planes
    coexist in one process; disarming restores whatever remains."""
    token = next(_next_token)
    _ARMED[token] = max(0, int(interval))
    return token


def disarm_default(token: int) -> None:
    _ARMED.pop(token, None)


def reset_cadence() -> None:
    """Test hook: restart the round counters so the next round of every
    pool is an explain round."""
    _round_counters.clear()


def explain_due(pool: str = "") -> bool:
    """Advance `pool`'s cadence counter; True on its explain rounds.
    Called once per scheduling round (models.run_round_on_device).  The
    counter is PER POOL: a global counter ticking once per pool-round
    aliases whenever gcd(num_pools, interval) > 1 (a 2-pool plane at the
    default interval 10 would attribute pool[0] forever and pool[1]
    never), so each pool gets attributed every Nth round of its own."""
    interval = explain_interval()
    if interval <= 0:
        return False
    count = _round_counters.get(pool, 0)
    _round_counters[pool] = count + 1
    return count % interval == 0


_KERNEL = None


def _kernel():
    """Build the jitted explain program on first use: this module must stay
    importable without initializing a jax backend (reports/metrics/CLI read
    only the reason-name constants)."""
    global _KERNEL
    if _KERNEL is None:
        import jax

        _KERNEL = functools.partial(
            jax.jit, static_argnames=("kcap", "fcap", "num_reasons")
        )(jax.named_scope("armada.explain")(_explain_kernel_impl))
    return _KERNEL


def _explain_kernel_impl(
    compat,
    compat_pre_type,
    node_type,
    node_ok,
    node_total,
    node_axes,
    g_req,
    g_card,
    g_queue,
    g_key,
    g_run,
    g_valid,
    g_absent,
    g_state,
    alloc,
    q_killed,
    num_real_gangs,
    *,
    kcap: int,
    fcap: int,
    num_reasons: int = NUM_REASONS,
):
    """Dense reason attribution over round-final state; ONE i32 buffer out.

    O(K x N) in the key fit check and O(G) everywhere else -- no per-job
    host work, no [G x N] intermediate.  Everything here is a single dense
    pass (no while_loop), so the gathered-row-compute constraint that rules
    the round kernel does not arise.

    Layout (i32): [version, n_keys, n_failed_gangs, n_failed_jobs, Q, R,
    T, 0] ++ counts_failed[NUM_REASONS] ++ counts_pending[NUM_REASONS] ++
    queue_counts[Q*NUM_REASONS] ++ key_id[kcap] ++ key_reason[kcap] ++
    key_count[kcap] ++ failed_idx[fcap] ++ failed_reason[fcap] ++
    frag_free_bits[R] ++ frag_max_bits[R] ++ type_frag_free_bits[T*R] ++
    type_frag_max_bits[T*R].  ``failed_idx``/``failed_reason`` come from
    the ascending nonzero scan of the SAME failed mask compact_result
    packs (real & g_state == 2), so the host expands gang -> job ids
    lazily without a second transfer.
    """
    import jax
    import jax.numpy as jnp

    G = g_state.shape[0]
    K = compat.shape[0]
    N, R = node_total.shape
    Q = q_killed.shape[0]

    real = jnp.arange(G, dtype=jnp.int32) < num_real_gangs
    # Job-carrying gangs: evictee slots (g_run >= 0) report through the
    # preempted set, absent slots (slab holes / lookback) report nowhere.
    jobg = real & (g_run < 0) & ~g_absent
    failed = jobg & (g_state == 2)
    pending = jobg & (g_state == 0)
    keyed = g_key >= 0
    ksafe = jnp.where(keyed, g_key, 0)

    # Per-key representative request/level: scatter-max over this round's
    # unplaced gangs (builder keys determine (request, PC) -- core/keys.py).
    rel = (failed | pending) & keyed
    kidx_scatter = jnp.where(rel, g_key, K)
    req_k = (
        jnp.zeros((K, R), jnp.float32).at[kidx_scatter].max(g_req, mode="drop")
    )
    # Per-node fit only ever sees node-bound axes (floating axes gate at the
    # pool level, never per node).
    req_node_k = req_k * node_axes[None, :]

    # Round-final free capacity at the clean level over schedulable nodes
    # (shared by the now-fit check and the fragmentation forensics below).
    free = jnp.where(
        node_ok[:, None], jnp.maximum(alloc[0], 0.0), 0.0
    )  # [N, R]

    # Empty-fleet fit per key: static compat x schedulable x raw node totals
    # -- the single-member case of the kernel's _fit_row arithmetic against
    # an empty node.  The R axis is unrolled (R is a small static shape) so
    # the working set stays [K, N], never [K, N, R].  `fits_now` is the same
    # check against round-final FREE capacity: a pending key that fits no
    # node NOW is blocked by allocations regardless of why the round
    # stopped.  ``fits_empty_pre`` re-runs the empty-fleet check with the
    # node-type whitelist gate REMOVED (compat_pre_type, core/keys
    # static_fit_matrix(pre_type=True)): a key feasible pre-type but not
    # post-type is a type mismatch, not a shape infeasibility.
    fits_empty = compat[:, node_type] & node_ok[None, :]  # [K, N]
    fits_empty_pre = compat_pre_type[:, node_type] & node_ok[None, :]
    fits_now = fits_empty
    for ri in range(R):
        size_ok = node_total[:, ri][None, :] >= req_node_k[:, ri][:, None]
        fits_empty = fits_empty & size_ok
        fits_empty_pre = fits_empty_pre & size_ok
        fits_now = fits_now & (
            free[:, ri][None, :] >= req_node_k[:, ri][:, None]
        )
    shape_ok = jnp.any(fits_empty, axis=1)  # [K]
    shape_ok_pre = jnp.any(fits_empty_pre, axis=1)  # [K]
    now_ok = jnp.any(fits_now, axis=1)  # [K]

    # Shape-infeasibility is TIME-INVARIANT, so it dominates every dynamic
    # reason -- a job that fits no node even empty reports shape-infeasible
    # whether the round attempted it (failed) or a cap/termination gate
    # stopped the round first (pending; the round-cap gate trips on the
    # oversized candidate itself without ever marking it failed).  Pending
    # attribution order: fairness gate (the queue was deactivated first),
    # then blocked-by-allocations-now, then a genuinely early stop.
    shape_bad_g = keyed & ~shape_ok_pre[ksafe]
    # Feasible ignoring the type whitelist, infeasible under it: the
    # whitelist is what blocks.  Both are time-invariant static facts, so
    # both dominate the dynamic reasons; true shape dominates type (a job
    # too big for EVERY node is not helped by widening its type map).
    type_bad_g = keyed & ~shape_ok[ksafe]
    now_blocked_g = keyed & ~now_ok[ksafe]
    reason_g = jnp.where(
        failed | pending,
        jnp.where(
            shape_bad_g,
            REASON_SHAPE,
            jnp.where(
                type_bad_g,
                REASON_TYPE,
                jnp.where(
                    failed,
                    jnp.where(
                        (g_card > 1) | ~g_valid, REASON_GANG, REASON_CAPACITY
                    ),
                    jnp.where(
                        q_killed[g_queue],
                        REASON_FAIRNESS,
                        jnp.where(
                            now_blocked_g, REASON_CAPACITY, REASON_TERMINATED
                        ),
                    ),
                ),
            ),
        ),
        REASON_NONE,
    ).astype(jnp.int32)

    w = g_card * (reason_g > 0)  # member counts; reason 0 weighs nothing
    counts_failed = (
        jnp.zeros((num_reasons,), jnp.int32)
        .at[reason_g]
        .add(w * failed)
    )
    counts_pending = (
        jnp.zeros((num_reasons,), jnp.int32)
        .at[reason_g]
        .add(w * pending)
    )
    queue_counts = (
        jnp.zeros((Q * num_reasons,), jnp.int32)
        .at[g_queue * num_reasons + reason_g]
        .add(w, mode="drop")
    )

    # Dominant reason per key over every unplaced gang (failed + pending).
    kr = (
        jnp.zeros((K * num_reasons,), jnp.int32)
        .at[ksafe * num_reasons + reason_g]
        .add(w * keyed, mode="drop")
    ).reshape(K, num_reasons)
    key_count = jnp.sum(kr, axis=1)
    key_reason = jnp.argmax(kr, axis=1).astype(jnp.int32)
    key_has = key_count > 0
    n_keys = jnp.sum(key_has).astype(jnp.int32)
    (key_sel,) = jnp.nonzero(key_has, size=kcap, fill_value=-1)
    key_sel_safe = jnp.maximum(key_sel, 0)
    key_id_out = key_sel.astype(jnp.int32)
    key_reason_out = jnp.where(key_sel >= 0, key_reason[key_sel_safe], 0)
    key_count_out = jnp.where(key_sel >= 0, key_count[key_sel_safe], 0)

    # Per-failed-gang reasons, aligned with compact_result's failed_idx scan
    # (same mask, same ascending nonzero order).
    cfailed = real & (g_state == 2)
    n_failed_gangs = jnp.sum(cfailed).astype(jnp.int32)
    (fidx,) = jnp.nonzero(cfailed, size=fcap, fill_value=-1)
    failed_reason_out = jnp.where(
        fidx >= 0, reason_g[jnp.maximum(fidx, 0)], 0
    )
    n_failed_jobs = jnp.sum(counts_failed).astype(jnp.int32)

    # Capacity forensics: frag_max IS "the largest request per resource
    # that still fits on some single node" -- the fragmentation numerator.
    frag_free = jnp.sum(free, axis=0)
    frag_max = jnp.max(free, axis=0)

    # Per-hardware-type fragmentation: the same forensics split by the
    # node's static type id (one scatter-add + scatter-max over [N, R] --
    # a shattered accelerator pool hides inside healthy aggregate numbers
    # when the CPU tier holds most of the free capacity).
    T = compat_pre_type.shape[1]
    type_frag_free = jnp.zeros((T, R), jnp.float32).at[node_type].add(free)
    type_frag_max = jnp.zeros((T, R), jnp.float32).at[node_type].max(free)

    header = jnp.stack(
        [
            jnp.int32(_VERSION),
            n_keys,
            n_failed_gangs,
            n_failed_jobs.astype(jnp.int32),
            jnp.int32(Q),
            jnp.int32(R),
            jnp.int32(T),
            jnp.int32(0),
        ]
    )
    bits = lambda a: jax.lax.bitcast_convert_type(  # noqa: E731
        a.astype(jnp.float32), jnp.int32
    )
    return jnp.concatenate(
        [
            header,
            counts_failed,
            counts_pending,
            queue_counts,
            key_id_out.astype(jnp.int32),
            key_reason_out.astype(jnp.int32),
            key_count_out.astype(jnp.int32),
            fidx.astype(jnp.int32),
            failed_reason_out.astype(jnp.int32),
            bits(frag_free),
            bits(frag_max),
            bits(type_frag_free.reshape(-1)),
            bits(type_frag_max.reshape(-1)),
        ]
    )


@dataclasses.dataclass
class ExplainOutcome:
    """Host-decoded explain pass of one scheduling round.

    Aggregates are exact (computed densely on device); the per-key table and
    the per-job pairing are capped (truncated_* flags).  ``queue_counts``
    and ``counts`` include the still-pending set's reasons -- only the
    ``failed_counts`` vector partitions ``RoundOutcome.failed``.  One
    documented skew: decode-time gang-atomicity unwinds (placed siblings
    appended to ``failed`` AFTER the device pass) are folded into
    ``failed_counts``/``counts`` as gang-partial but cannot be placed in
    ``queue_counts`` (the host fold knows their count, not their queue), so
    on the rare unwind round the per-queue histograms under-count
    gang-partial by exactly that fold."""

    counts: dict  # reason name -> job count (failed + pending combined)
    failed_counts: dict  # reason name -> jobs; partitions RoundOutcome.failed
    pending_counts: dict  # reason name -> jobs the round never attempted
    queue_counts: dict  # queue name -> {reason name: job count}
    key_reasons: list  # [{"key": int, "reason": str, "jobs": int}]
    fragmentation: dict  # resource -> {free, largest_request, index} (atoms)
    # hw type -> {resource -> {free, largest_request, index}}; {} on
    # single-type fleets (the aggregate row says the same thing).
    fragmentation_by_type: dict = dataclasses.field(default_factory=dict)
    truncated_keys: bool = False
    job_reasons_complete: bool = True
    _failed_idx: Optional[np.ndarray] = None
    _failed_reason: Optional[np.ndarray] = None
    _ctx: object = None

    def iter_job_reasons(self):
        """Lazy (job_id, reason name) pairs for the failed set -- the
        LazyJobIds discipline: a bounded consumer (the reports LRU) never
        pays a whole-backlog decode."""
        if self._failed_idx is None or self._ctx is None:
            return
        for gi, r in zip(self._failed_idx, self._failed_reason):
            r = int(r)
            if r == REASON_NONE:  # evictee slot / empty gang: not a job
                continue
            for jid in self._ctx.members_of(int(gi)):
                yield jid, REASON_NAMES[r]

    def summary(self) -> dict:
        """The JSON-ready block reports / healthz / bench share."""
        out = {
            "counts": dict(self.counts),
            "failed_counts": dict(self.failed_counts),
            "pending_counts": dict(self.pending_counts),
            "fragmentation": {
                name: dict(vals) for name, vals in self.fragmentation.items()
            },
            "keys": list(self.key_reasons),
            "truncated_keys": self.truncated_keys,
        }
        if self.fragmentation_by_type:
            out["fragmentation_by_type"] = {
                t: {name: dict(vals) for name, vals in row.items()}
                for t, row in self.fragmentation_by_type.items()
            }
        return out


def dispatch_explain(device_problem, result, ctx):
    """Enqueue the explain kernel behind the round WITHOUT reading it back;
    returns (device buffer, kcap, fcap) or None (pass unavailable for this
    round).  Mirrors problem._dispatch_compact: dispatch/fetch split so the
    device compute and its device->host copy ride the decode shadow."""
    import jax

    if not isinstance(result.g_state, jax.Array):
        return None
    G = int(result.g_state.shape[0])
    K = int(device_problem.compat.shape[0])
    kcap = min(K, _EXPLAIN_KCAP)
    fcap = min(G, _EXPLAIN_FCAP)
    buf = _kernel()(
        device_problem.compat,
        device_problem.compat_pre_type,
        device_problem.node_type,
        device_problem.node_ok,
        device_problem.node_total,
        device_problem.node_axes,
        device_problem.g_req,
        device_problem.g_card,
        device_problem.g_queue,
        device_problem.g_key,
        device_problem.g_run,
        device_problem.g_valid,
        device_problem.g_absent,
        result.g_state,
        result.alloc,
        result.q_killed,
        np.int32(ctx.num_real_gangs),
        kcap=kcap,
        fcap=fcap,
    )
    try:
        buf.copy_to_host_async()
    except (AttributeError, RuntimeError):
        pass  # backend without async copies: the fetch blocks normally
    return buf, kcap, fcap


def finish_explain(dispatched, ctx, outcome=None) -> Optional[ExplainOutcome]:
    """Blocking fetch + host decode of a dispatched explain buffer (ONE
    device->host transfer, counted in TRANSFER_STATS).  When `outcome` is
    given, decode-time gang-atomicity unwinds (placed siblings appended to
    ``failed`` after the device pass ran) are folded into ``gang-partial``
    so the failed-set partition stays exact."""
    if dispatched is None:
        return None
    buf_dev, kcap, fcap = dispatched
    buf = np.asarray(buf_dev)
    from armada_tpu.models.xfer import TRANSFER_STATS

    TRANSFER_STATS.count_down(buf.nbytes)
    version, n_keys, n_failed_gangs, n_failed_jobs, Q, R, T = (
        int(v) for v in buf[:7]
    )
    if version != _VERSION:
        return None
    off = _HEADER
    failed_vec = buf[off : off + NUM_REASONS]
    off += NUM_REASONS
    pending_vec = buf[off : off + NUM_REASONS]
    off += NUM_REASONS
    queue_counts_vec = buf[off : off + Q * NUM_REASONS].reshape(Q, NUM_REASONS)
    off += Q * NUM_REASONS
    key_id = buf[off : off + kcap]
    off += kcap
    key_reason = buf[off : off + kcap]
    off += kcap
    key_count = buf[off : off + kcap]
    off += kcap
    failed_idx = buf[off : off + fcap]
    off += fcap
    failed_reason = buf[off : off + fcap]
    off += fcap
    frag_free = buf[off : off + R].view(np.float32)
    off += R
    frag_max = buf[off : off + R].view(np.float32)
    off += R
    type_frag_free = buf[off : off + T * R].view(np.float32).reshape(T, R)
    off += T * R
    type_frag_max = buf[off : off + T * R].view(np.float32).reshape(T, R)

    failed_counts = {
        REASON_NAMES[r]: int(failed_vec[r]) for r in range(1, NUM_REASONS)
    }
    pending_counts = {
        REASON_NAMES[r]: int(pending_vec[r]) for r in range(1, NUM_REASONS)
    }
    if outcome is not None:
        # Post-decode unwinds: placed siblings of a failed sub-gang were
        # moved into `failed` on host -- they are gang-atomicity failures.
        extra = len(outcome.failed) - n_failed_jobs
        if extra > 0:
            failed_counts[REASON_NAMES[REASON_GANG]] += extra
    counts = {
        name: failed_counts[name] + pending_counts[name]
        for name in REASON_NAMES[1:]
    }

    queue_counts = {}
    for qi in range(min(Q, ctx.num_real_queues)):
        row = {
            REASON_NAMES[r]: int(queue_counts_vec[qi, r])
            for r in range(1, NUM_REASONS)
            if queue_counts_vec[qi, r]
        }
        if row:
            queue_counts[ctx.queue_names[qi]] = row

    keys = [
        {
            "key": int(k),
            "reason": REASON_NAMES[int(r)],
            "jobs": int(c),
        }
        for k, r, c in zip(key_id, key_reason, key_count)
        if k >= 0
    ]

    factory = ctx.config.resource_list_factory()

    def frag_row(free_vec, max_vec):
        row = {}
        for ri, name in enumerate(factory.names):
            if ri >= R:
                break
            free_units = float(free_vec[ri])
            max_units = float(max_vec[ri])
            res = factory.resolutions[ri]
            row[name] = {
                "free": int(round(free_units * res)),
                "largest_request": int(round(max_units * res)),
                # 1 - largest contiguous block / total free: 0 = one node
                # could absorb all free capacity, ->1 = shattered.
                "index": (
                    round(1.0 - max_units / free_units, 6)
                    if free_units > 0
                    else 0.0
                ),
            }
        return row

    fragmentation = frag_row(frag_free, frag_max)
    # Device rows beyond the real type count are bucket padding (all-zero);
    # name rows by the host-side hardware-type registry.  Single-type fleets
    # skip the split -- the aggregate row already says it.
    type_names = list(getattr(ctx, "type_names", ()) or ())
    fragmentation_by_type = {}
    if len(type_names) > 1:
        for ti, tname in enumerate(type_names):
            if ti >= T:
                break
            fragmentation_by_type[tname or "untyped"] = frag_row(
                type_frag_free[ti], type_frag_max[ti]
            )

    live = failed_idx >= 0
    out = ExplainOutcome(
        counts=counts,
        failed_counts=failed_counts,
        pending_counts=pending_counts,
        queue_counts=queue_counts,
        key_reasons=keys,
        fragmentation=fragmentation,
        fragmentation_by_type=fragmentation_by_type,
        truncated_keys=n_keys > kcap,
        job_reasons_complete=n_failed_gangs <= fcap,
        _failed_idx=failed_idx[live],
        _failed_reason=failed_reason[live],
        _ctx=ctx,
    )
    return out
