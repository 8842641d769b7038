"""The tensorised scheduling round: fair-share eviction + greedy placement +
oversubscription repair, compiled as one XLA program.

This kernel is the TPU-native replacement for the reference call chain
PreemptingQueueScheduler.Schedule (preempting_queue_scheduler.go:108-300)
-> QueueScheduler.Schedule (queue_scheduler.go:87) -> GangScheduler.Schedule
(gang_scheduler.go:100) -> NodeDb.SelectNodeForJobWithTxn (nodedb.go:392).

Structure (matching the reference's phases):
  1. *Fair-share eviction*: queues whose DRF cost exceeds `protected_fraction` of
     their fair share have their preemptible running jobs evicted -- usage moves to
     the reserved evicted level 0, and their pinned re-scheduling candidates are
     activated (pqs.go:117-160).
  2. *Placement loop* (`lax.while_loop`): each iteration picks the queue whose
     next gang yields the lowest proposed DRF cost (CostBasedCandidateGangIterator
     Less, queue_scheduler.go:589-636, default ordering), then places that gang
     all-or-nothing: clean fit first (at the evicted level, where evicted markers
     still count -- nodedb.go:506-514), else urgency preemption at the gang's own
     priority.  Failures of single-job gangs register a globally unfeasible
     scheduling key, immediately retiring every identical pending job
     (gang_scheduler.go:85-96).  Queue/global burst and resource caps mirror
     constraints.go, except that exhausted caps block only *new* jobs here --
     evicted jobs always keep their chance to re-schedule (strictly safer than the
     reference's round termination).
  3. *Oversubscription repair*: nodes driven negative at some priority by urgency
     preemption evict their preemptible jobs at oversubscribed levels
     (NewOversubscribedEvictor, eviction.go:130-180), which then re-schedule onto
     their pinned nodes via a vectorised fixed-point (the reference's second
     schedule pass over evicted jobs only, pqs.go:222-247).
  4. Evicted jobs that did not make it back are preempted; their markers are
     removed (the unbind step, pqs.go:286-296).

Control flow is sequential-greedy to preserve the reference's ordering semantics,
but every step inside an iteration is a dense vector op (fit masks over all nodes,
segment-min over all gangs), so one iteration costs microseconds regardless of
problem size, and the iteration count is bounded by gangs *attempted* (scheduled +
distinct unfeasible keys + queue deactivations), not by queue length.
"""

from __future__ import annotations

import functools
import os as _os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from armada_tpu.models.problem import SchedulingProblem
from armada_tpu.ops.fairness import fair_shares, unweighted_drf_cost, weighted_drf_cost
from armada_tpu.ops.fit import allocatable_from_used
from armada_tpu.ops.packing import (
    member_capacity,
    node_packing_score,
    select_best_node,
    select_gang_nodes_compact,
)

# Plain numpy, NOT jnp: module-level jnp scalars initialize the default jax
# backend at import time (claiming the chip before any caller can pin a
# platform).
_BIGI = np.int32(2**31 - 1)
_INF = np.float32(3.0e38)
# Prefer-large ordering: offset lifting over-budget keys above every
# within-budget key while staying far below the masked-out _INF.
_PL_OVER = np.float32(1.0e30)

TERM_EXHAUSTED = 0
TERM_GLOBAL_BURST = 1
TERM_ROUND_CAP = 2
TERM_MAX_ITER = 3


class RoundResult(NamedTuple):
    g_state: jax.Array  # i32[G]: 0 not attempted, 1 scheduled, 2 failed/skipped, 3 absent
    slot_gang: jax.Array  # i32[S]
    slot_nodes: jax.Array  # i32[S, W]
    slot_counts: jax.Array  # i32[S, W]
    n_slots: jax.Array  # i32
    run_evicted: jax.Array  # bool[RJ]
    run_rescheduled: jax.Array  # bool[RJ]
    alloc: jax.Array  # f32[P1, N, R] final allocatable-by-level
    q_alloc: jax.Array  # f32[Q, R]
    iterations: jax.Array  # i32
    termination: jax.Array  # i32
    scheduled_count: jax.Array  # i32 newly scheduled members
    # Market pools: bid of the gang whose placement crossed the spot cutoff
    # (queue_scheduler.go:135-150); -1 = not set.
    spot_price: jax.Array  # f32
    # Queues deactivated mid-round by per-queue burst / per-(queue, PC) cap
    # trips (constraints.go gate_queue); consumed by the explain pass
    # (models/explain.py) to attribute still-pending jobs to
    # `fairness-capped` rather than `round-terminated`.
    q_killed: jax.Array  # bool[Q]
    # Physical while-loop body applications.  `iterations` stays the LOGICAL
    # sequential step count (bit-identical at any commit_k/batch_k -- it
    # feeds TERM_MAX_ITER); kernel_iters is the observability counter the
    # multi-commit work shrinks (commits_per_iter = iterations/kernel_iters).
    # Excluded from the bit-equality contract the parity suites pin.
    kernel_iters: jax.Array  # i32
    # Trips on which more queues moved their cursor than the body rebuilds
    # row by row, so the carried skip window was gathered whole again (see
    # _make_place_iteration).  An observability counter like kernel_iters,
    # and excluded from the bit-equality contract with it.
    window_refills: jax.Array  # i32


# Header slots of the packed decode buffer (see compact_result).
_COMPACT_HEADER = 10


@functools.partial(jax.jit, static_argnames=("fcap", "ecap"))
@jax.named_scope("armada.compact")
def compact_result(result: RoundResult, num_real_gangs, num_real_runs, *, fcap: int, ecap: int):
    """Pack the O(decisions) slice of a RoundResult into ONE i32 buffer.

    Pulling g_state ([G] i32 = 4MB at 1M gangs) plus half a dozen small
    arrays is seven device->host transfers per round (what one costs on
    this host is not measured -- ROADMAP S5).  This packs
    the failed-gang indices, preempted/rescheduled run indices, placement
    slots and scalars into one buffer a single transfer fetches.  The header
    carries the true counts so the host detects cap overflow (mass
    key-retirement rounds) and falls back to the full pull.

    Layout (i32): [n_slots, iterations, termination, sched_count,
    spot_price_bits, n_failed, n_pre, n_res, kernel_iters, window_refills]
    ++ slot_gang[S] ++ slot_nodes[S*W] ++ slot_counts[S*W] ++ failed_idx[fcap] ++
    pre_idx[ecap] ++ res_idx[ecap].
    """
    g = result.g_state
    G = g.shape[0]
    real_g = jnp.arange(G, dtype=jnp.int32) < num_real_gangs
    failed_mask = real_g & (g == 2)
    n_failed = jnp.sum(failed_mask).astype(jnp.int32)
    (failed_idx,) = jnp.nonzero(failed_mask, size=fcap, fill_value=-1)

    RJ = result.run_evicted.shape[0]
    real_r = jnp.arange(RJ, dtype=jnp.int32) < num_real_runs
    pre_mask = result.run_evicted & ~result.run_rescheduled & real_r
    res_mask = result.run_evicted & result.run_rescheduled & real_r
    n_pre = jnp.sum(pre_mask).astype(jnp.int32)
    n_res = jnp.sum(res_mask).astype(jnp.int32)
    (pre_idx,) = jnp.nonzero(pre_mask, size=ecap, fill_value=-1)
    (res_idx,) = jnp.nonzero(res_mask, size=ecap, fill_value=-1)

    header = jnp.stack(
        [
            result.n_slots.astype(jnp.int32),
            result.iterations.astype(jnp.int32),
            result.termination.astype(jnp.int32),
            result.scheduled_count.astype(jnp.int32),
            jax.lax.bitcast_convert_type(
                result.spot_price.astype(jnp.float32), jnp.int32
            ),
            n_failed,
            n_pre,
            n_res,
            result.kernel_iters.astype(jnp.int32),
            result.window_refills.astype(jnp.int32),
        ]
    )
    return jnp.concatenate(
        [
            header,
            result.slot_gang.astype(jnp.int32),
            result.slot_nodes.reshape(-1).astype(jnp.int32),
            result.slot_counts.reshape(-1).astype(jnp.int32),
            failed_idx.astype(jnp.int32),
            pre_idx.astype(jnp.int32),
            res_idx.astype(jnp.int32),
        ]
    )


class _Carry(NamedTuple):
    alloc: jax.Array
    q_alloc: jax.Array
    q_alloc_pc: jax.Array
    q_killed: jax.Array
    q_sched: jax.Array
    q_head: jax.Array  # i32[Q] cursor into the (queue, order)-sorted gang index
    # The skip window at q_head ([Q, _SKIP_WINDOW]; see _skip_window), carried
    # and patched by what each trip changed instead of gathered every trip.
    w_gang: jax.Array  # i32: gang ids of the window's entries
    w_key: jax.Array  # i32: their scheduling keys
    w_skip: jax.Array  # bool: decided (g_state != 0) or key registered unfeasible
    g_state: jax.Array
    key_bad: jax.Array
    run_rescheduled: jax.Array
    slot_gang: jax.Array
    slot_nodes: jax.Array
    slot_counts: jax.Array
    cursor: jax.Array
    sched_count: jax.Array
    sched_res: jax.Array
    float_used: jax.Array  # f32[R] pool-level floating usage
    new_blocked: jax.Array
    iterations: jax.Array
    kernel_iters: jax.Array  # physical body applications (see RoundResult)
    window_refills: jax.Array  # trips that gathered the whole window again
    done: jax.Array
    termination: jax.Array
    spot_price: jax.Array  # f32; -1 = unset
    # Resources of EVERY placed gang incl. rescheduled evictees -- the
    # reference's scheduledResource (queue_scheduler.go:127-137) accrues all
    # gangs, unlike sched_res which feeds the new-jobs-only round caps.
    spot_res: jax.Array  # f32[R]
    # --- per-scheduling-key fit/score caches (see _make_place_iteration) ----
    # The per-iteration cost of the placement loop is dominated by the [N,R]
    # member-capacity chains; a scheduling key determines (request, priority
    # class) exactly (core/keys.py key_of folds resources + PC into the key,
    # like the reference's SchedulingKeyGenerator), so single-job candidates
    # with an interned key can reuse a cached bool[N] fit row, incrementally
    # re-derived on the <=W nodes each commit touches.  Decisions are
    # bit-identical to the uncached path: rows/scores are exact recomputes of
    # the same formulas, just memoized.
    fitc_clean: jax.Array  # bool[S*N] flat: fit at the clean level 0, ok-masked
    fitc_lvl: jax.Array  # bool[S*N] flat: fit at the key's own level, ok-masked
    score_c: jax.Array  # f32[P1*N] flat node packing score per level
    # Block-minima of the masked score per slot (f32[S*(N/B)] flat): the hot
    # path's argmin runs over these [N/B] rows + one [B] block, never [N].
    bmc_clean: jax.Array
    bmc_lvl: jax.Array
    cslot_key: jax.Array  # i32[S] interned key cached in each slot (-1 empty)
    cslot_lvl: jax.Array  # i32[S]
    cslot_req: jax.Array  # f32[S, R] node-axis request of the cached key


# How many queue-head entries each queue can skip (retired gangs, unfeasible
# scheduling keys) per iteration.  Skipping is the rare path -- the window just
# bounds how fast a mass-retired run of identical jobs drains.
_SKIP_WINDOW = 16


def _skip_window(p: SchedulingProblem, q_start, q_head, g_state, key_bad, check_keys: bool):
    """The skip window of the queues whose gq segments start at ``q_start``
    with cursors ``q_head`` (both i32[A]): the next _SKIP_WINDOW entries of
    each queue in the (queue, order)-sorted gang index, as three [A, W] tables
    -- gang id, scheduling key, and "skippable": the gang is already decided
    (g_state != 0) or, under check_keys, its key is registered unfeasible
    (gang_scheduler.go:85-96 -- the reference skips these through its
    iterator the same way).  Entries past a queue's tail (and the clip at
    G - 1) hold whatever gang the index has there; the body masks them with
    its own ``in_r``.  Three dependent gathers from [G] arrays: on the v5e a
    gather costs 7-13 ns an ELEMENT over a floor of ~1 us, so all Q = 256
    rows are 29 us apiece and one row is the floor (PERF.md, PR 26) -- the
    loop calls this on the rows that moved, and on all of them only when it
    must.  At Q = 1,024 (925 queues; PERF.md, PR 28) a round of 1,002 trips
    takes the full gather TWICE, where 64 queues take it ~15 times: the
    `window` conditional averages 14.4 us a trip there against 13.5, so
    the refills are not what a thousand queues pay for; the body's [Q]- and
    [Q, W]-wide fusions are (a trip is 200 us for 135)."""
    G = p.g_req.shape[0]
    offs = q_head[:, None] + jnp.arange(_SKIP_WINDOW, dtype=jnp.int32)[None, :]
    slot = jnp.clip(q_start[:, None] + offs, 0, G - 1)
    wg = p.gq_gang[slot]
    wkey = p.g_key[wg]
    wbad = jnp.bool_(check_keys) & (wkey >= 0) & key_bad[jnp.maximum(wkey, 0)]
    return wg, wkey, (g_state[wg] != 0) | wbad


def _level_mask(num_levels: int, level, lo):
    """bool[P1]: levels lo..level inclusive (the allocatable levels a binding at
    `level` consumes; lo=1 when moving an evicted marker up, else 0)."""
    lv = jnp.arange(num_levels, dtype=jnp.int32)
    return (lv >= lo) & (lv <= level)


def _move_runs_to_evicted(alloc, q_alloc, q_alloc_pc, p: SchedulingProblem, move, num_levels):
    """Move usage of runs in `move` from their level to the evicted level 0.

    Allocatable at levels 1..run_level gains the freed capacity; level 0 is
    unchanged (the marker still counts against clean fit).  Queue allocation drops
    (context eviction accounting, context/queue.go EvictJob).
    """
    delta = p.run_req * move[:, None]
    # Node allocatable only tracks node-bound axes; floating axes live in
    # q_alloc and the pool-level float_used counter.
    delta_node = delta * p.node_axes[None, :]
    lv = jnp.arange(num_levels, dtype=jnp.int32)
    mask = ((lv[:, None] >= 1) & (lv[:, None] <= p.run_level[None, :])).astype(
        jnp.float32
    )  # [P1, RJ]
    # lint: allow(axis1-scatter) -- per-ROUND [P1,N,R] alloc init from run
    # rows, outside the placement iteration chain; the flat-cache rule
    # targets per-iteration cache writes
    alloc = alloc.at[:, p.run_node, :].add(mask[:, :, None] * delta_node[None, :, :])
    q_alloc = q_alloc.at[p.run_queue].add(-delta)
    q_alloc_pc = q_alloc_pc.at[p.run_queue, p.run_pc].add(-delta)
    return alloc, q_alloc, q_alloc_pc


def _block_size(n: int) -> int:
    """Largest power-of-two block size <= 64 dividing n (block-minima rows
    must tile the node axis exactly)."""
    for b in (64, 32, 16, 8, 4, 2):
        if n % b == 0:
            return b
    return 1


def _fit_row(alloc_rows, req):
    """bool[...]: >=1 member of `req` fits, replicating member_capacity's
    exact arithmetic (floor-of-division then min) so cached rows are
    bit-identical to the uncached mask."""
    safe_req = jnp.where(req > 0, req, 1.0)
    per = jnp.where(req > 0, jnp.floor(alloc_rows / safe_req), _INF)
    return jnp.min(per, axis=-1) >= 1.0


def _make_place_iteration(
    p: SchedulingProblem,
    num_levels: int,
    slot_width: int,
    check_keys: bool,
    prefer_large: bool = False,
    q_budget=None,
    cache_slots: int = 0,
    max_iterations: int = 0,
    batch_k: int = 1,
    commit_k: int = 1,
):
    """One trip of the placement loop: select (cursor advance, candidate,
    queue order, gates), fit, commit, and the upkeep of the carried window.

    THE CARRIED SKIP WINDOW.  A queue's cursor may skip entries whose gang is
    already decided or whose key is registered unfeasible; what it looks at
    is the [Q, W] window of _skip_window.  Gathering it costs three dependent
    [Q, W] gathers from [G] arrays, which on the v5e is 7 ns an ELEMENT
    (29 us each at Q = 256, against a per-operation floor of 1-2 us: PERF.md,
    PR 26; four times the elements at Q = 1,024, where PR 28 measured two
    refills in a round's 1,002 trips), and a trip changes almost none of
    it.  So the window is loop state (_Carry.w_gang / w_key / w_skip), with
    one invariant:
    AT THE TOP OF EVERY TRIP THE CARRIED TABLES EQUAL
    _skip_window(p, p.q_start, c.q_head, c.g_state, c.key_bad, check_keys).
    Everything the body derives from them (in_r, skippable, nskip, q_head,
    cand, head_visible, and parked / nn / tail_known / hidden of the batch_k
    and commit_k extensions) is therefore the same expression of the same
    values as when the window was gathered every trip.  The `window` scope
    at the body's end keeps it: what the trip decided is patched
    elementwise (g_state changes at the committed gangs, key_bad at one
    key); a queue whose cursor moved has its row gathered at the new head,
    `rows` of them (one per gang a trip can commit); a trip that moves more
    cursors than that -- a key registration retiring several queues' heads
    -- takes a lax.cond to the full gather and counts in window_refills.
    Loop state that changes at one index a trip is carried and patched,
    never gathered again (CLAUDE.md).  Under jax.vmap (the stacked round)
    the cond is a select and both branches run: that form keeps the full
    gather's cost.

    prefer_large is a STATIC flag (like check_keys): the default compile
    carries none of the alternate-ordering work.  q_budget is the per-queue
    weighted budget from the round's fair-share computation (passed in so the
    water-filling loop is not traced twice).  cache_slots sizes the
    per-scheduling-key fit cache (see _Carry; 0 compiles the uncached body).

    max_iterations > 0 compiles an `active` gate into the body: a step past
    done/max-iterations is a true no-op (no cursor movement, no commits, no
    iteration count), which is what lets schedule_round UNROLL several body
    applications inside one while_loop iteration with bit-exact semantics
    (the tail steps of the last unrolled group self-disable).

    batch_k > 1 appends the CERTIFIED BATCH extension (SURVEY section 7
    "schedule K gangs per device step"): after the normal head placement,
    up to batch_k-1 additional queue heads commit in the same iteration --
    each one proven to be exactly what the sequential loop's next iteration
    would have decided (cost order vs every placed queue's next candidate
    with the argmin tie-break, node choice re-derived exactly at the <=K
    nodes this batch touched, caps/burst/spot walked in commit order).
    Anything unprovable cuts the batch and defers to the next iteration, so
    the batch commits a certified PREFIX of the sequential order or
    nothing; decisions are bit-identical at any batch_k.  Requires
    cache_slots == 0 and not prefer_large (enforced by schedule_round).

    commit_k > 1 appends the CONFLICT-FREE MULTI-COMMIT extension
    (ARMADA_COMMIT_K): unlike batch_k's serial replay (K sub-picks, each
    with its own argmin/cond chain -- K times the op count, the measured
    r3 dead end), this takes the top-K queue heads in ONE ordered
    selection (lax.top_k over the same order keys the argmin reads; ties
    break to the lower index, matching argmin) and certifies the set
    non-interacting with vectorized [K]/[KxK] checks whose op count is
    CONSTANT in K:
      * pairwise-distinct queues by construction (top_k ranks), so no
        pick perturbs another's fair-share row -- and each placed queue's
        NEXT candidate cost is proven to not precede any later pick
        (strictly greater, or equal with a higher queue index: the exact
        argmin tie-break), using the sequential association
        ((q_alloc + req) + penalty) + next_req;
      * singles only -- gangs, evictees, banned (retry anti-affinity)
        candidates and market rounds truncate (their replay semantics are
        order-dependent; they run as exact heads next iteration);
      * pairwise-distinct nodes among the extension picks, no clean-fit
        flip and no newly-dominating score at any earlier pick's node
        (alloc deltas are [KxK]-checked against fit and the first-argmin
        tie-break), so every pick's node choice equals the sequential
        re-derivation;
      * caps/burst/float walked in commit order with the sequential f32
        accumulation; a pick that WOULD trip a gate truncates so the gate
        (and its new_blocked/q_killed/termination side effects) fires
        next iteration.
    The certified prefix commits in ONE batched scatter per table
    (constant-value / distinct-lane `mode='drop'` scatters, dummy lanes
    pushed out of range -- never a gathered-old-value race).  commit_k=1
    compiles the existing body; decisions are bit-identical at any K
    (only RoundResult.kernel_iters differs).  Works with the cached-fit
    body (the maintenance pass re-derives at every committed node);
    requires batch_k == 1 and not prefer_large (enforced by
    schedule_round)."""
    G = p.g_req.shape[0]
    N, R = p.node_total.shape
    Q = p.q_weight.shape[0]
    RJ = p.run_req.shape[0]
    S = cache_slots
    # Queues whose cursor one trip can move without a key registration: the
    # head pick's, plus one per committed extension lane.
    rows = min(max(commit_k, batch_k, 1), Q)

    # Loop-invariant masked request tables, gathered per iteration: computing
    # req * node_axes inside the body would depend on the gathered row and
    # defeat XLA's invariant hoisting (measured 6x slower at 1M gangs).
    g_req_node = p.g_req * p.node_axes[None, :]  # [G, R] node-bound axes
    g_float_tot = (
        p.g_req * (1.0 - p.node_axes)[None, :]
    ) * p.g_card[:, None].astype(jnp.float32)  # [G, R] floating total per gang
    # Heterogeneity (per-node-type throughput bias): a STATIC shape switch --
    # TR == 1 means no type-sensitive key exists and the body below compiles
    # bit-identical to the pre-hetero kernel.  When armed, the per-node bias
    # table is precomputed here ([TR, N], loop-invariant) and the body does
    # ONE row gather through the already-gathered key -- the ban_mask
    # discipline; any in-loop compute from the gathered row would defeat
    # XLA's invariant hoisting.
    hetero = int(p.type_bias.shape[0]) > 1
    if hetero:
        type_bias_nodes = p.type_bias[:, p.node_type]  # [TR, N]
    if prefer_large:
        # itemSize = unweighted gang cost x queue weight (queue_scheduler.go:518
        # -- a highly-weighted queue's gangs "look larger"); [G], gathered.
        g_size = unweighted_drf_cost(
            p.g_req * p.g_card[:, None].astype(jnp.float32),
            p.total_pool,
            p.drf_mult,
        ) * p.q_weight[p.g_queue]

    def body(c: _Carry) -> _Carry:
        # A trip's phases carry names for the profiler: select (cursor
        # advance, candidate, queue order, gates), fit (fit + node
        # selection), commit (commit, gang state, cache maintenance), window
        # (the carried skip window's upkeep).
        # Names are operation metadata only: the compiled program is the
        # same operations (tests/test_trace.py).
        with jax.named_scope("select"):
            # Unrolled-group gate: once done (or past the iteration budget) the
            # remaining inner steps of the group are exact no-ops.
            if max_iterations > 0:
                active = (~c.done) & (c.iterations < max_iterations)
            else:
                active = jnp.bool_(True)
            # --- advance per-queue cursors past retired/unfeasible heads ------------
            # The window into the (queue, order)-sorted gang index is CARRIED
            # (see the docstring's invariant): O(Q*W) elementwise work, no
            # gather.
            W = _SKIP_WINDOW
            offs = c.q_head[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]  # [Q, W]
            in_r = offs < p.q_len[:, None]
            wg = c.w_gang  # [Q, W] gang ids
            wkey = c.w_key
            skippable = in_r & c.w_skip
            lead = jnp.cumprod(skippable.astype(jnp.int32), axis=1)  # leading-True run
            nskip = jnp.sum(lead, axis=1).astype(jnp.int32) * active.astype(jnp.int32)
            q_head = c.q_head + nskip
            advanced = jnp.any(nskip > 0)

            # --- per-queue candidate: the head entry, if visible in the window ------
            pos = jnp.minimum(nskip, W - 1)
            head_visible = (nskip < W) & jnp.take_along_axis(in_r, pos[:, None], axis=1)[:, 0]
            cand = jnp.take_along_axis(wg, pos[:, None], axis=1)[:, 0]  # [Q]
            cand = jnp.where(head_visible, cand, 0)
            cand_new = p.g_run[cand] < 0
            has = (
                head_visible
                & ~(cand_new & (c.new_blocked | c.q_killed))
                & (p.q_weight > 0)
            )

            # --- queue order: min proposed DRF cost (queue_scheduler.go Less:589),
            # --- or max bid price in market pools (market_iterator.go:245) ------
            req_tot_q = p.g_req[cand] * p.g_card[cand][:, None].astype(jnp.float32)
            # Ordering cost includes the short-job penalty (queue_scheduler.go:
            # 514-515 GetAllocationInclShortJobPenalty); fair shares, caps and
            # eviction protection do not.
            proposed = weighted_drf_cost(
                c.q_alloc + p.q_penalty + req_tot_q, p.total_pool, p.drf_mult, p.q_weight
            )
            if prefer_large:
                # Prefer-large ordering (queue_scheduler.go Less:598-626): queues
                # within budget rank by CURRENT cost (larger gang breaks exact
                # ties) and always beat over-budget queues, which rank by
                # proposed cost.
                current = weighted_drf_cost(
                    c.q_alloc + p.q_penalty, p.total_pool, p.drf_mult, p.q_weight
                )
                size = g_size[cand]
                within = proposed <= q_budget
                order_key = jnp.where(within, current, _PL_OVER + proposed)
                order_key = jnp.where(p.market, -p.g_price[cand], order_key)
                order_key = jnp.where(has, order_key, _INF)
                kmin = jnp.min(order_key)
                tied = has & (order_key == kmin)
                # among exact ties: the largest gang, then the lowest queue index
                # (the reference's queue-name tie-break).
                tie_size = jnp.where(tied, size, -_INF)
                pick = tied & (tie_size >= jnp.max(tie_size))
                qidx = jnp.arange(Q, dtype=jnp.int32)
                qstar = jnp.min(jnp.where(pick, qidx, Q - 1)).astype(jnp.int32)
            else:
                order_key = jnp.where(p.market, -p.g_price[cand], proposed)
                order_key = jnp.where(has, order_key, _INF)
                # lint: allow(full-argmin) -- [Q]-axis queue pick, not [N]
                qstar = jnp.argmin(order_key).astype(jnp.int32)
            any_q = jnp.any(has)

            g = cand[qstar]
            req = p.g_req[g]
            card = p.g_card[g]
            cardf = card.astype(jnp.float32)
            level = p.g_level[g]
            key = p.g_key[g]
            pc = p.g_pc[g]
            run = p.g_run[g]
            is_evictee = run >= 0
            run_safe = jnp.where(is_evictee, run, RJ - 1)
            pinned = jnp.where(is_evictee, p.run_node[run_safe], -1)
            req_tot = req * cardf
            req_node = g_req_node[g]  # per-node fit sees node-bound axes only
            req_float_tot = g_float_tot[g]

            # --- constraint gates (constraints.go:97-159); all gated on any_q so the
            # --- dummy candidate of an exhausted round has no side effects ----------
            # (unfeasible scheduling keys never reach here: the cursor skip above
            # retires them before candidate selection)
            hit_burst = (~is_evictee) & (c.sched_count + card > p.global_burst)
            hit_round_cap = (~is_evictee) & jnp.any(c.sched_res + req_tot > p.round_cap)
            hit_q_burst = (~is_evictee) & (c.q_sched[qstar] + card > p.perq_burst[qstar])
            hit_q_cap = (~is_evictee) & jnp.any(
                c.q_alloc_pc[qstar, pc] + req_tot > p.pc_queue_cap[pc]
            )
            gate_global = (hit_burst | hit_round_cap) & any_q & active
            gate_queue = (hit_q_burst | hit_q_cap) & ~gate_global & any_q & active
            attempt = any_q & active & ~gate_global & ~gate_queue

        # --- fit + node selection ----------------------------------------------
        with jax.named_scope("fit"):
            # Three compute classes (cheapest first); all produce decisions
            # bit-identical to the original single [N,R] path:
            #   0. pinned evictee: only its run node can host it -- O(R).
            #   1. cacheable single (card 1, no bans, interned key): cached
            #      bool[N] fit rows + the maintained score table; a miss pays the
            #      full [N,R] member-capacity chains once per (key % S) slot.
            #   2. general (gangs, banned, keyless): the original full path.
            static_ok = jnp.where(key >= 0, p.compat[jnp.maximum(key, 0)][p.node_type], True)
            if hetero:
                # Bias row of the candidate's key (row 0 = insensitive/keyless);
                # one invariant-table gather, like ban_mask.
                trow = jnp.where(
                    key >= 0, p.key_type_row[jnp.maximum(key, 0)], 0
                )
            # Pool-level floating capacity (evictee slots already counted at init).
            float_ok = is_evictee | jnp.all(
                c.float_used + req_float_tot <= p.float_total + 1e-3
            )
            empty_nodes = jnp.full((slot_width,), N, jnp.int32)
            empty_counts = jnp.zeros((slot_width,), jnp.int32)
            zero_row = jnp.zeros((N,), bool)
            B = _block_size(N)
            NB = N // B
            zero_bm = jnp.full((NB,), _INF, jnp.float32)

            def evictee_path(_):
                pin_safe = jnp.clip(pinned, 0, N - 1)
                fits = (
                    _fit_row(c.alloc[level, pin_safe], req_node) & p.node_ok[pin_safe]
                )
                nodes = empty_nodes.at[0].set(jnp.where(fits, pinned, N))
                counts = empty_counts.at[0].set(fits.astype(jnp.int32))
                return (
                    nodes, counts, fits, zero_row, zero_row, zero_bm, zero_bm,
                    jnp.bool_(False),
                )

            def cached_single_path(_):
                slot = jnp.where(key >= 0, key, 0) % S
                # Builder problems intern (request, PC) into the key
                # (core/keys.py), but the kernel must stay correct for ANY
                # input: a same-key gang with a different request/level (e.g.
                # synthetic label keys) must miss, not reuse foreign fit rows.
                hit = (
                    (c.cslot_key[slot] == key)
                    & (c.cslot_lvl[slot] == level)
                    & jnp.all(c.cslot_req[slot] == req_node)
                )

                def pick_cached(_):
                    # Two-level exact argmin: the [NB] block-minima row names the
                    # FIRST block attaining the global min (argmin tie-break),
                    # then the first in-block index attaining it -- the global
                    # first argmin, with no [N]-length reduce on the hot path
                    # (XLA:CPU's argmin is a scalar loop, ~190us at N=51k; the
                    # [NB]+[B] pair is ~2us).
                    bm0 = jax.lax.dynamic_slice(c.bmc_clean, (slot * NB,), (NB,))

                    def pick_at(bm, score_off):
                        # lint: allow(full-argmin) -- [NB] block-minima row: this
                        # IS the blocked path the rule points at
                        b = jnp.argmin(bm).astype(jnp.int32)
                        m = bm[b]
                        found = m < _INF
                        fit_b = jax.lax.dynamic_slice(
                            c.fitc_clean if score_off is None else c.fitc_lvl,
                            (slot * N + b * B,),
                            (B,),
                        )
                        sc_b = jax.lax.dynamic_slice(
                            c.score_c,
                            ((0 if score_off is None else score_off) * N + b * B,),
                            (B,),
                        )
                        masked = jnp.where(fit_b, sc_b, _INF)
                        # lint: allow(full-argmin) -- [B]=block-size in-block pick
                        j = jnp.argmin(masked).astype(jnp.int32)
                        return (b * B + j).astype(jnp.int32), found

                    def clean_pick(_):
                        return pick_at(bm0, None)

                    def lvl_pick(_):
                        bml = jax.lax.dynamic_slice(c.bmc_lvl, (slot * NB,), (NB,))
                        return pick_at(bml, level)

                    found0 = jnp.min(bm0) < _INF
                    node, found = jax.lax.cond(found0, clean_pick, lvl_pick, None)
                    return node, found, zero_row, zero_row, zero_bm, zero_bm

                def pick_fresh(_):
                    ok = static_ok & p.node_ok
                    fc_row = ok & _fit_row(c.alloc[0], req_node)
                    fl_row = ok & _fit_row(c.alloc[level], req_node)
                    score0 = jax.lax.dynamic_slice(c.score_c, (0,), (N,))
                    masked0 = jnp.where(fc_row, score0, _INF)
                    bm0 = jnp.min(masked0.reshape(NB, B), axis=1)
                    scorel = jax.lax.dynamic_slice(c.score_c, (level * N,), (N,))
                    maskedl = jnp.where(fl_row, scorel, _INF)
                    bml = jnp.min(maskedl.reshape(NB, B), axis=1)
                    # lint: allow(full-argmin) -- cache-MISS fill path: pays one
                    # [N] pick per miss and returns the bm rows that make every
                    # later hit take the blocked path
                    node0 = jnp.argmin(masked0).astype(jnp.int32)
                    found0 = masked0[node0] < _INF

                    def clean_pick(_):
                        return node0, found0

                    def lvl_pick(_):
                        # lint: allow(full-argmin) -- cache-miss fill (see above)
                        nodel = jnp.argmin(maskedl).astype(jnp.int32)
                        return nodel, maskedl[nodel] < _INF

                    node, found = jax.lax.cond(found0, clean_pick, lvl_pick, None)
                    return node, found, fc_row, fl_row, bm0, bml

                node, found, fc_row, fl_row, bm0, bml = jax.lax.cond(
                    hit, pick_cached, pick_fresh, None
                )
                nodes = empty_nodes.at[0].set(jnp.where(found, node, N))
                counts = empty_counts.at[0].set(found.astype(jnp.int32))
                return nodes, counts, found, fc_row, fl_row, bm0, bml, ~hit

            def general_path(_):
                pin_ok = jnp.where(
                    pinned >= 0, jnp.arange(N, dtype=jnp.int32) == pinned, True
                )
                # Retry anti-affinity: one gather into the precomputed row table
                # (row 0 = no bans); built outside the loop so XLA hoists it.
                banned = p.ban_mask[p.g_ban_row[g]]
                ok_base = static_ok & p.node_ok & pin_ok & ~banned
                alloc_clean = c.alloc[0]
                alloc_lvl = c.alloc[level]
                # Capacity clipped to the gang cardinality: keeps int32 sums/
                # cumsums exact (the builder rejects cardinalities large enough
                # to overflow N * card).
                cap_clean = jnp.where(
                    ok_base, jnp.minimum(member_capacity(alloc_clean, req_node), card), 0
                )
                cap_lvl = jnp.where(
                    ok_base, jnp.minimum(member_capacity(alloc_lvl, req_node), card), 0
                )
                use_clean = (~is_evictee) & (jnp.sum(cap_clean) >= card)
                cap_sel = jnp.where(use_clean, cap_clean, cap_lvl)
                alloc_sel = jnp.where(use_clean, alloc_clean, alloc_lvl)
                score = node_packing_score(alloc_sel, p.inv_scale)
                if hetero:
                    # One gathered row of the precomputed [TR, N] table; the
                    # f32 add is mirrored by the sequential oracle.
                    score = score + type_bias_nodes[trow]
                fit_feasible = jnp.sum(cap_sel) >= card

                def single_branch(_):
                    # Cheap path: one argmin, no sort (select_best_node semantics).
                    found, node = select_best_node(cap_sel >= 1, score)
                    nodes = empty_nodes.at[0].set(jnp.where(found, node, N))
                    counts = empty_counts.at[0].set(found.astype(jnp.int32))
                    return nodes, counts

                def gang_branch(_):
                    _, nodes, counts = select_gang_nodes_compact(
                        cap_sel >= 1, cap_sel, card, score, slot_width
                    )
                    return nodes, counts

                nodes, counts = jax.lax.cond(card == 1, single_branch, gang_branch, None)
                return (
                    nodes, counts, fit_feasible, zero_row, zero_row, zero_bm,
                    zero_bm, jnp.bool_(False),
                )

            if S > 0:
                cacheable = (
                    (card == 1) & (~is_evictee) & (key >= 0) & (p.g_ban_row[g] == 0)
                )
                if hetero:
                    # score_c is a per-LEVEL table shared across cache slots; a
                    # per-key bias cannot bake into it.  Type-sensitive
                    # candidates take the general path (exact, biased) instead.
                    cacheable &= trow == 0
                branch = jnp.where(is_evictee, 0, jnp.where(cacheable, 1, 2))
                branches = [evictee_path, cached_single_path, general_path]
            else:
                branch = jnp.where(is_evictee, 0, 1)
                branches = [evictee_path, general_path]
            (
                nodes_w,
                counts_w,
                fit_feasible,
                fc_row,
                fl_row,
                bm0_row,
                bml_row,
                cache_write,
            ) = jax.lax.switch(branch, branches, None)
            feasible = fit_feasible & float_ok

            placed = attempt & feasible
            place_f = placed.astype(jnp.float32)

        # --- commit (all updates masked by `placed`) ----------------------------
        with jax.named_scope("commit"):
            lvl_lo = jnp.where(is_evictee, 1, 0)
            lmask = _level_mask(num_levels, level, lvl_lo).astype(jnp.float32)
            sub = counts_w[:, None].astype(jnp.float32) * req_node[None, :]  # [W, R]
            delta = lmask[:, None, None] * sub[None, :, :] * place_f  # [P1, W, R]
            # lint: allow(axis1-scatter) -- the round's own alloc commit ([W]
            # placement lanes into [P1,N,R]); its cost is pinned by the e2e
            # headline, and alloc has no flat equivalent (levels share nodes)
            alloc = c.alloc.at[:, nodes_w, :].add(-delta, mode="drop")
            q_alloc = c.q_alloc.at[qstar].add(req_tot * place_f)
            q_alloc_pc = c.q_alloc_pc.at[qstar, pc].add(req_tot * place_f)

            new_sched = placed & ~is_evictee
            sched_count = c.sched_count + jnp.where(new_sched, card, 0)
            sched_res = c.sched_res + jnp.where(new_sched, req_tot, 0.0)
            # Spot price (queue_scheduler.go:135-150): first gang whose placement
            # pushes the round's scheduled share past the cutoff sets the price
            # (the gang's MINIMUM member bid, :138-144).  The share counts every
            # placed gang, rescheduled evictees included, like the reference's
            # scheduledResource.
            spot_res = c.spot_res + jnp.where(placed, req_tot, 0.0)
            sched_share = jnp.max(
                jnp.where(p.total_pool > 0, spot_res / jnp.maximum(p.total_pool, 1e-9), 0.0)
                * p.drf_mult
            )
            crossed = (
                p.market & placed & (c.spot_price < 0) & (sched_share > p.spot_cutoff)
            )
            spot_price = jnp.where(crossed, p.g_spot_price[g], c.spot_price)
            float_used = c.float_used + jnp.where(new_sched, req_float_tot, 0.0)
            q_sched = c.q_sched.at[qstar].add(jnp.where(new_sched, card, 0))
            # lint: allow(commit-scatter-gathered-old) -- single scalar lane
            # (the head pick): one lane cannot lane-race; the rule targets
            # batched dummy-lane commits
            run_rescheduled = c.run_rescheduled.at[run_safe].set(
                jnp.where(is_evictee & placed, True, c.run_rescheduled[run_safe])
            )

            # slot recording for newly scheduled gangs (evictee placement is implied
            # by run_rescheduled + its pinned node)
            rec = new_sched
            cur = c.cursor
            slot_gang = c.slot_gang.at[cur].set(jnp.where(rec, g, c.slot_gang[cur]), mode="drop")
            slot_nodes = c.slot_nodes.at[cur].set(
                jnp.where(rec, nodes_w, c.slot_nodes[cur]), mode="drop"
            )
            slot_counts = c.slot_counts.at[cur].set(
                jnp.where(rec, counts_w, c.slot_counts[cur]), mode="drop"
            )
            cursor = cur + rec.astype(jnp.int32)

            # --- gang state + unfeasible-key registration ---------------------------
            failed_fit = attempt & ~feasible
            # lint: allow(commit-scatter-gathered-old) -- single scalar lane
            # (the head pick): one lane cannot lane-race
            g_state = c.g_state.at[g].set(
                jnp.where(placed, 1, jnp.where(failed_fit, 2, c.g_state[g]))
            )
            # Registering the key retires every identical pending gang lazily: the
            # cursor skip drops them as they reach a queue head, and the post-loop
            # sweep in schedule_round marks them failed for reporting.
            register = failed_fit & (card == 1) & (key >= 0) & jnp.bool_(check_keys)
            # lint: allow(commit-scatter-gathered-old) -- single scalar lane
            # (the head pick's key registration): one lane cannot lane-race
            key_bad = c.key_bad.at[jnp.maximum(key, 0)].set(
                jnp.where(register, True, c.key_bad[jnp.maximum(key, 0)])
            )

            q_killed = c.q_killed.at[qstar].set(c.q_killed[qstar] | gate_queue)
            new_blocked = c.new_blocked | gate_global
            termination = jnp.where(
                gate_global & (c.termination == TERM_EXHAUSTED),
                jnp.where(hit_burst, TERM_GLOBAL_BURST, TERM_ROUND_CAP),
                c.termination,
            )
            # An inactive step keeps done as-is: flipping it would misreport a
            # max-iterations exit as exhaustion.
            done = jnp.where(active, ~any_q & ~advanced, c.done)

            extra_iters = jnp.int32(0)
            touched_nodes = nodes_w
            if commit_k > 1 or batch_k > 1:
                # Shared next-candidate cursor tables for BOTH batching shapes
                # (they are mutually exclusive compiles, so one definition
                # keeps the load-bearing parked semantics from drifting):
                # the cursor parks on any undecided entry (in_r & ~skippable);
                # nn[q, i] = first parked window index at-or-after i (W =
                # none); a window that reaches past the queue tail proves
                # nothing hides beyond it.
                parked = in_r & ~skippable
                nn = jnp.full((Q, W + 1), W, jnp.int32)
                for i in range(W - 1, -1, -1):
                    nn = nn.at[:, i].set(jnp.where(parked[:, i], i, nn[:, i + 1]))
                tail_known = ~in_r[:, W - 1]
            if commit_k > 1:
                # --- conflict-free multi-commit extension (see docstring) --------
                # Vectorized over the K-1 extension lanes: every check below is
                # one op with a [E]/[E,E] axis, so the body's op count stays
                # CONSTANT in K (the batch_k replay's failure mode).
                E = commit_k - 1
                S_cap = slot_gang.shape[0]
                iota_e = jnp.arange(E, dtype=jnp.int32)
                iota_k = jnp.arange(E + 1, dtype=jnp.int32)

                # (1) ordered top-K queues by the head's own order key.  top_k is
                # stable (equal keys -> lower index first), matching the argmin
                # tie-break; rank 0 IS the head queue qstar.
                _, topq = jax.lax.top_k(-order_key, E + 1)
                topq = topq.astype(jnp.int32)
                qe = topq[1:]  # [E] extension queues (pairwise distinct)
                keye = order_key[qe]
                ge = cand[qe]
                card_e = p.g_card[ge]
                run_e = p.g_run[ge]
                level_e = p.g_level[ge]
                key_e = p.g_key[ge]
                pc_e = p.g_pc[ge]
                ban_e = p.g_ban_row[ge]
                req_e = p.g_req[ge]  # [E, R]; card 1 => per-member == total
                reqn_e = g_req_node[ge]
                flt_e = g_float_tot[ge]

                # (2) batch gate: the head must have placed (its commit above is
                # the exact sequential step); market rounds are out (bid order +
                # spot crossing replay is order-dependent); and no queue may
                # have skipped past its whole window -- a hidden candidate could
                # surface mid-batch and outrank a pick.
                hidden = jnp.any((nskip >= W) & (q_head < p.q_len))
                batch_ok = placed & ~p.market & ~hidden

                # (3) eligibility: certified picks are non-evictee, unbanned
                # singles with a live order key; everything else truncates and
                # runs as an exact head next iteration.
                elig = (keye < _INF) & (card_e == 1) & (run_e < 0) & (ban_e == 0)
                if hetero:
                    # Type-sensitive extension candidates truncate: the
                    # same-node-stacking proof in (7) reasons about the UNBIASED
                    # packing score, and a per-key node offset can flip the
                    # first-argmin between lanes of different keys.  The head
                    # lane is the exact biased path, so sensitive picks run
                    # solo-head next iteration (bit-exact, just fewer commits
                    # per trip on sensitive-heavy mixes).
                    elig &= (
                        jnp.where(
                            key_e >= 0, p.key_type_row[jnp.maximum(key_e, 0)], 0
                        )
                        == 0
                    )

                # (4) caps/burst/float in commit order.  Distinct queues mean the
                # per-queue gates see no intra-batch accumulation; the global
                # accumulators replicate the sequential f32 association exactly
                # (an unrolled E-step scalar chain -- E adds, not E iterations).
                okc = []
                run_res, run_flt = sched_res, float_used
                for i in range(E):
                    nxt_res = run_res + req_e[i]
                    nxt_flt = run_flt + flt_e[i]
                    ci = (
                        ((sched_count + i + 1) <= p.global_burst)
                        & jnp.all(nxt_res <= p.round_cap)
                        & jnp.all(nxt_flt <= p.float_total + 1e-3)
                    )
                    if max_iterations > 0:
                        ci &= (c.iterations + 1 + i) < max_iterations
                    okc.append(ci)
                    run_res, run_flt = nxt_res, nxt_flt
                ok_caps = jnp.stack(okc)
                ok_caps &= (q_sched[qe] + 1) <= p.perq_burst[qe]
                ok_caps &= jnp.all(
                    q_alloc_pc[qe, pc_e] + req_e <= p.pc_queue_cap[pc_e], axis=1
                )

                # (5) queue-order certification: after each batch queue's head
                # commits, its NEXT candidate's proposed cost must not precede
                # any later pick.  Next candidates come from the shared
                # parked/nn/tail_known tables above.
                qk = jnp.concatenate([qstar[None], qe])  # [K] batch queues
                npos = nn[qk, jnp.minimum(pos[qk] + 1, W)]
                np_safe = jnp.minimum(npos, W - 1)
                g_next = wg[qk, np_safe]
                next_tot = p.g_req[g_next] * p.g_card[g_next][:, None].astype(
                    jnp.float32
                )
                # head's commit is already in q_alloc; extension rows add their
                # own -- the sequential ((q_alloc + req) + penalty) + next_req
                # association either way.
                own_req = jnp.concatenate(
                    [jnp.zeros((1, R), jnp.float32), req_e], axis=0
                )
                row_k = q_alloc[qk] + own_req
                nk = weighted_drf_cost(
                    (row_k + p.q_penalty[qk]) + next_tot,
                    p.total_pool, p.drf_mult, p.q_weight[qk],
                )
                next_new = p.g_run[g_next] < 0
                allowed = (
                    ~(next_new & (new_blocked | q_killed[qk]))
                    & (p.q_weight[qk] > 0)
                )
                nk = jnp.where(allowed, nk, _INF)
                nk = jnp.where(
                    npos < W, nk, jnp.where(tail_known[qk], _INF, -_INF)
                )
                prior_k = iota_k[:, None] <= iota_e[None, :]  # j commits before e
                ok_pair = (nk[:, None] > keye[None, :]) | (
                    (nk[:, None] == keye[None, :]) & (qk[:, None] > qe[None, :])
                )
                ok_order = jnp.all(ok_pair | ~prior_k, axis=0)  # [E]

                # (6) fit + node choice per pick against the post-head slab --
                # the same masked-score first-argmin the cached and general
                # single paths compute, via the blocked [NB]+[B] pair.
                static_e = jnp.where(
                    (key_e >= 0)[:, None],
                    p.compat[jnp.maximum(key_e, 0)][:, p.node_type],
                    True,
                )
                okn_e = static_e & p.node_ok[None, :]
                fit0_e = okn_e & _fit_row(alloc[0][None, :, :], reqn_e[:, None, :])
                fitl_e = okn_e & _fit_row(alloc[level_e], reqn_e[:, None, :])
                score_lvls = node_packing_score(alloc, p.inv_scale)  # [P1, N]
                use_clean_e = jnp.any(fit0_e, axis=1)
                msel = jnp.where(
                    use_clean_e[:, None],
                    jnp.where(fit0_e, score_lvls[0][None, :], _INF),
                    jnp.where(fitl_e, score_lvls[level_e], _INF),
                )
                bm_e = jnp.min(msel.reshape(E, NB, B), axis=2)
                # lint: allow(full-argmin) -- [NB] blocked rows x [B] in-block:
                # the sanctioned two-level pick, vectorized over the E lanes
                b_e = jnp.argmin(bm_e, axis=1).astype(jnp.int32)
                blk = jnp.take_along_axis(
                    msel.reshape(E, NB, B), b_e[:, None, None], axis=1
                )[:, 0]
                # lint: allow(full-argmin) -- [B]-length in-block pick
                j_in = jnp.argmin(blk, axis=1).astype(jnp.int32)
                node_e = b_e * B + j_in
                score_e = jnp.take_along_axis(msel, node_e[:, None], axis=1)[:, 0]
                found_e = score_e < _INF
                lvl_sel_e = jnp.where(use_clean_e, 0, level_e)

                # (7) conflict certification with CUMULATIVE prior deltas: for
                # pick e, every earlier extension pick k (the head's lanes are
                # already in `alloc`, so the tables above see them exactly)
                # subtracts its request at its node.  Same-node STACKING is the
                # dominant best-fit pattern (consecutive same-shape picks pack
                # the same fullest node until it fills) and certifies exactly:
                # the node's score only drops, so it stays the first argmin
                # while it still fits.  Requirements per pick e:
                #   * no clean-fit flip at any prior node (use_clean provably
                #     unchanged -- a flip means a node just filled; truncate);
                #   * pick e's own node still fits under the cumulative delta
                #     (sequential re-derivation lands on the same node);
                #   * no OTHER prior node's post-commit score wins pick e's
                #     first-argmin against its own ADJUSTED score (strictly
                #     lower, or equal at a lower node index).
                nj_safe = jnp.clip(node_e, 0, N - 1)
                prior_f = (iota_e[:, None] > iota_e[None, :]).astype(
                    jnp.float32
                )  # [e, k]: pick k commits before pick e
                samen = (node_e[:, None] == node_e[None, :]).astype(
                    jnp.float32
                )  # [j, k]: picks sharing a node
                cum0 = jnp.einsum("ek,jk,kr->ejr", prior_f, samen, reqn_e)
                adj0 = alloc[0][nj_safe][None, :, :] - cum0
                post_fit0 = okn_e[:, nj_safe] & _fit_row(adj0, reqn_e[:, None, :])
                flip0 = fit0_e[:, nj_safe] & ~post_fit0  # [E(e), E(j)]
                applies = prior_f * (
                    lvl_sel_e[:, None] <= level_e[None, :]
                ).astype(jnp.float32)
                cum_sel = jnp.einsum("ek,jk,kr->ejr", applies, samen, reqn_e)
                adj_sel = alloc[lvl_sel_e][:, nj_safe] - cum_sel  # [E, E, R]
                adj_fit = okn_e[:, nj_safe] & _fit_row(adj_sel, reqn_e[:, None, :])
                adj_score = node_packing_score(adj_sel, p.inv_scale)  # [E, E]
                # pick e's own adjusted row is the (e, j=e) diagonal: cum_sel
                # there sums every prior at n_e with lvl_sel_e[e] in range --
                # exactly what the sequential recompute would see.
                diag = jnp.arange(E, dtype=jnp.int32)
                self_fit = adj_fit[diag, diag]
                self_score = adj_score[diag, diag]
                beats = adj_fit & (
                    (adj_score < self_score[:, None])
                    | (
                        (adj_score == self_score[:, None])
                        & (node_e[None, :] < node_e[:, None])
                    )
                )
                self_pair = node_e[:, None] == node_e[None, :]
                prior_e = iota_e[None, :] < iota_e[:, None]
                conflict = jnp.where(self_pair, flip0, flip0 | beats)
                ok_nodes = self_fit & ~jnp.any(conflict & prior_e, axis=1)

                # (8) the certified prefix
                raw_ok = batch_ok & elig & ok_caps & ok_order & ok_nodes & found_e
                ok_e = jnp.cumprod(raw_ok.astype(jnp.int32)).astype(bool)
                okf = ok_e.astype(jnp.float32)
                n_ext = jnp.sum(ok_e.astype(jnp.int32))

                # (9) ONE batched commit per table: constant-value /
                # distinct-lane scatters, dummy lanes pushed out of range with
                # mode='drop' -- never a gathered-old-value write.
                commit_nodes = jnp.where(ok_e, node_e, N)
                lv_c = jnp.arange(num_levels, dtype=jnp.int32)
                lm_c = (lv_c[:, None] <= level_e[None, :]).astype(jnp.float32)
                # lint: allow(axis1-scatter) -- the multi-commit's own alloc
                # update ([E] certified lanes into [P1,N,R]), the batched twin
                # of the head commit above
                alloc = alloc.at[:, commit_nodes, :].add(
                    -lm_c[:, :, None] * (reqn_e * okf[:, None])[None, :, :],
                    mode="drop",
                )
                qe_ok = jnp.where(ok_e, qe, Q)
                q_alloc = q_alloc.at[qe_ok].add(req_e, mode="drop")
                q_alloc_pc = q_alloc_pc.at[qe_ok, pc_e].add(req_e, mode="drop")
                q_sched = q_sched.at[qe_ok].add(1, mode="drop")
                sched_count = sched_count + n_ext
                # sequential-association accumulators (they feed ordering
                # comparisons in later iterations)
                for i in range(E):
                    sched_res = sched_res + req_e[i] * okf[i]
                    float_used = float_used + flt_e[i] * okf[i]
                    spot_res = spot_res + req_e[i] * okf[i]
                g_state = g_state.at[jnp.where(ok_e, ge, G)].set(1, mode="drop")
                sidx = jnp.where(ok_e, cursor + iota_e, S_cap)
                ext_nodes_w = (
                    jnp.full((E, slot_width), N, jnp.int32).at[:, 0].set(node_e)
                )
                ext_counts_w = (
                    jnp.zeros((E, slot_width), jnp.int32).at[:, 0].set(1)
                )
                slot_gang = slot_gang.at[sidx].set(ge, mode="drop")
                slot_nodes = slot_nodes.at[sidx].set(ext_nodes_w, mode="drop")
                slot_counts = slot_counts.at[sidx].set(ext_counts_w, mode="drop")
                cursor = cursor + n_ext
                extra_iters = n_ext
                touched_nodes = jnp.concatenate([nodes_w, commit_nodes])

            # --- cache maintenance --------------------------------------------------
            fitc_clean, fitc_lvl, score_c = c.fitc_clean, c.fitc_lvl, c.score_c
            bmc_clean, bmc_lvl = c.bmc_clean, c.bmc_lvl
            cslot_key, cslot_lvl, cslot_req = c.cslot_key, c.cslot_lvl, c.cslot_req
            if S > 0:
                # 1. write-back on a cached-path miss: the freshly computed fit
                # rows + block-minima (pre-commit alloc) land in the key's slot;
                # step 2 then re-derives anything this iteration's own commit
                # touched.  (All flat leading-dim scatters: in-place.)
                iota_n = jnp.arange(N, dtype=jnp.int32)
                wslot = jnp.where(cache_write, jnp.where(key >= 0, key, 0) % S, S)
                widx = wslot * N + iota_n  # >= S*N when dropped
                fitc_clean = fitc_clean.at[widx].set(fc_row, mode="drop")
                fitc_lvl = fitc_lvl.at[widx].set(fl_row, mode="drop")
                bidx = wslot * NB + jnp.arange(NB, dtype=jnp.int32)
                bmc_clean = bmc_clean.at[bidx].set(bm0_row, mode="drop")
                bmc_lvl = bmc_lvl.at[bidx].set(bml_row, mode="drop")
                cslot_key = cslot_key.at[wslot].set(key, mode="drop")
                cslot_lvl = cslot_lvl.at[wslot].set(level, mode="drop")
                cslot_req = cslot_req.at[wslot].set(req_node, mode="drop")
                # 2. exact re-derivation at every node this iteration's commits
                # touched -- the head's <=slot_width lanes plus the multi-commit
                # extension's certified lanes (unplaced iterations recompute
                # unchanged values: no-op).
                tn = touched_nodes  # [W(+E)], N = unused sentinel (dropped below)
                tn_safe = jnp.clip(tn, 0, N - 1)
                a_rows = alloc[:, tn_safe, :]  # [P1, W, R]
                sc_rows = jnp.sum(a_rows * p.inv_scale[None, None, :], axis=-1)  # [P1, W]
                lv = jnp.arange(num_levels, dtype=jnp.int32)
                sidx = jnp.where(
                    tn[None, :] < N, lv[:, None] * N + tn[None, :], num_levels * N
                )
                score_c = score_c.at[sidx].set(sc_rows, mode="drop")
                key_s = cslot_key  # post-write-back tables: a new slot patches too
                ok_t = (
                    p.compat[jnp.maximum(key_s, 0)][:, p.node_type[tn_safe]]  # [S, W]
                    & p.node_ok[tn_safe][None, :]
                    & (key_s >= 0)[:, None]
                )
                a0_t = alloc[0, tn_safe]  # [W, R]
                al_t = alloc[cslot_lvl[:, None], tn_safe[None, :]]  # [S, W, R]
                fit0_t = ok_t & _fit_row(a0_t[None, :, :], cslot_req[:, None, :])
                fitl_t = ok_t & _fit_row(al_t, cslot_req[:, None, :])
                sl = jnp.arange(S, dtype=jnp.int32)
                fidx = jnp.where(tn[None, :] < N, sl[:, None] * N + tn[None, :], S * N)
                fitc_clean = fitc_clean.at[fidx].set(fit0_t, mode="drop")
                fitc_lvl = fitc_lvl.at[fidx].set(fitl_t, mode="drop")
                # 3. block-minima of every touched (slot, block), recomputed from
                # the PATCHED fit rows + scores: gather the whole [B] block per
                # touched node per slot ([S, W, B] -- a few thousand elements).
                tb = tn_safe // B  # [W] touched blocks
                jb = jnp.arange(B, dtype=jnp.int32)
                nblk = tb[:, None] * B + jb[None, :]  # [W, B] node ids
                fblk_idx = sl[:, None, None] * N + nblk[None, :, :]  # [S, W, B]
                f0_blk = fitc_clean[fblk_idx]
                fl_blk = fitc_lvl[fblk_idx]
                s0_blk = score_c[nblk]  # [W, B] level-0 scores
                slvl_blk = score_c[cslot_lvl[:, None, None] * N + nblk[None, :, :]]
                bm0_t = jnp.min(jnp.where(f0_blk, s0_blk[None, :, :], _INF), axis=-1)
                bml_t = jnp.min(jnp.where(fl_blk, slvl_blk, _INF), axis=-1)  # [S, W]
                bpidx = jnp.where(tn[None, :] < N, sl[:, None] * NB + tb[None, :], S * NB)
                bmc_clean = bmc_clean.at[bpidx].set(bm0_t, mode="drop")
                bmc_lvl = bmc_lvl.at[bpidx].set(bml_t, mode="drop")

            if batch_k > 1:
                # --- certified pick-chain extension (see docstring) --------------
                # After the head commit, SIMULATE the sequential loop's next
                # picks with tiny [Q] state (per-queue keys + window cursors)
                # and commit up to batch_k-1 of them in this iteration.  The
                # simulation replays the exact argmin pick order -- including
                # same-queue monopolies, the dominant pattern under DRF (the
                # cheapest queue places many consecutive jobs) -- and every
                # f32 expression matches the sequential path's association, so
                # decisions are bit-identical.  Anything unprovable (gangs,
                # window exhaustion, cap trips, float shortfalls, no-fit
                # failures) cuts the chain and defers to the next iteration.
                E = batch_k - 1
                max_slots_cap = slot_gang.shape[0]
                iota_q = jnp.arange(Q, dtype=jnp.int32)

                # Window candidate tables ([Q, W] gathers; the window is the
                # simulation horizon)
                wcard = p.g_card[wg]
                wrun = p.g_run[wg]
                wev = wrun >= 0
                wlevel = p.g_level[wg]
                wpc = p.g_pc[wg]
                wban = p.g_ban_row[wg]
                wreq = p.g_req[wg]  # [Q, W, R] per-member
                wreq_tot = wreq * wcard[..., None].astype(jnp.float32)
                wreq_node = g_req_node[wg]
                wfloat = g_float_tot[wg]
                wprice = p.g_price[wg]
                wspot = p.g_spot_price[wg]
                wpin = jnp.where(wev, p.run_node[jnp.maximum(wrun, 0)], 0)
                # Cursor semantics EXACTLY mirror the sequential loop: the
                # cursor parks on any undecided entry (in_r & ~skippable),
                # whether or not the candidate gate would allow picking it.
                # The gate (new_blocked / q_killed / zero weight -- `has`)
                # applies to the KEY instead: a parked-blocked queue reads +INF
                # -- never picked, never constraining, exactly like sequential.
                wallowed = (
                    ~((~wev) & (c.new_blocked | c.q_killed[:, None]))
                    & (p.q_weight > 0)[:, None]
                )
                # parked/nn/tail_known come from the shared tables above the
                # commit_k block (one definition for both batching shapes)

                # simulation state
                sim_row = q_alloc  # post-head [Q, R]; value-identical to what
                # the sequential loop reads next iteration
                pos_clip = jnp.minimum(pos + 1, W)
                simpos = jnp.where(
                    iota_q == qstar, nn[iota_q, pos_clip], nn[iota_q, pos]
                )
                sp_safe = jnp.minimum(simpos, W - 1)
                head_tot = jnp.take_along_axis(
                    wreq_tot, sp_safe[:, None, None], axis=1
                )[:, 0]
                sim_keys = weighted_drf_cost(
                    (sim_row + p.q_penalty) + head_tot,
                    p.total_pool, p.drf_mult, p.q_weight,
                )
                head_price = jnp.take_along_axis(
                    wprice, sp_safe[:, None], axis=1
                )[:, 0]
                sim_keys = jnp.where(p.market, -head_price, sim_keys)
                head_allowed = jnp.take_along_axis(
                    wallowed, sp_safe[:, None], axis=1
                )[:, 0]
                sim_keys = jnp.where(head_allowed, sim_keys, _INF)
                # beyond-window queues: certifiable only when truly exhausted
                sim_keys = jnp.where(
                    simpos < W, sim_keys, jnp.where(tail_known, _INF, -_INF)
                )

                # chain accumulators
                t_nodes = jnp.full((E,), N, jnp.int32)
                t_lo = jnp.zeros((E,), jnp.int32)
                t_level = jnp.zeros((E,), jnp.int32)
                t_req = jnp.zeros((E, R), jnp.float32)
                ex_placed = jnp.zeros((E,), bool)
                ex_gang = jnp.zeros((E,), jnp.int32)
                ex_queue = jnp.zeros((E,), jnp.int32)
                ex_pcv = jnp.zeros((E,), jnp.int32)
                ex_reqs = jnp.zeros((E, R), jnp.float32)
                ex_floats = jnp.zeros((E, R), jnp.float32)
                ex_evs = jnp.zeros((E,), bool)
                ex_runs = jnp.full((E,), RJ, jnp.int32)
                r_count, r_res, r_float = sched_count, sched_res, float_used
                r_spot_res, r_spot = spot_res, spot_price
                r_iter = c.iterations + active.astype(jnp.int32)
                alive = placed
                iota_e = jnp.arange(E, dtype=jnp.int32)
                # one-entry within-step fit-row cache: same-key chains reuse it
                cache_key = jnp.int32(-2)
                cache_lvl = jnp.int32(-1)
                cache_ban = jnp.int32(-1)
                cache_req = jnp.full((R,), -1.0, jnp.float32)
                zrow = jnp.zeros((N,), bool)
                cache_fit0, cache_fitl = zrow, zrow
                cache_m0 = jnp.full((N,), _INF, jnp.float32)
                cache_ml = jnp.full((N,), _INF, jnp.float32)
                cache_n0 = jnp.int32(0)
                score_all = jnp.sum(alloc * p.inv_scale[None, None, :], axis=-1)

                def deltas_at(nodes, lvl):
                    vis = ex_placed_l & (t_lo_l <= lvl) & (lvl <= t_level_l)
                    aff = (
                        (nodes[:, None] == t_nodes_l[None, :]) & vis[None, :]
                    ).astype(jnp.float32)
                    return aff @ t_req_l

                for k in range(E):
                    # lint: allow(full-argmin) -- [Q]-axis simulated queue pick
                    qj = jnp.argmin(sim_keys).astype(jnp.int32)
                    kj = sim_keys[qj]
                    i_j = simpos[qj]
                    ok = alive & (kj < _INF) & (i_j < W) & (
                        r_iter < max_iterations
                    )
                    i_safe = jnp.minimum(i_j, W - 1)
                    g_j = wg[qj, i_safe]
                    card_j = wcard[qj, i_safe]
                    ev_j = wev[qj, i_safe]
                    run_j = jnp.where(ev_j, wrun[qj, i_safe], RJ)
                    lvl_j = wlevel[qj, i_safe]
                    pc_j = wpc[qj, i_safe]
                    key_j = wkey[qj, i_safe]
                    ban_j = wban[qj, i_safe]
                    req_j = wreq[qj, i_safe]
                    reqn_j = wreq_node[qj, i_safe]
                    flt_j = wfloat[qj, i_safe]
                    pin_j = wpin[qj, i_safe]
                    if hetero:
                        # this pick's bias row ([N], row 0 for keyless) -- the
                        # replay mirrors the head path's (score) + bias add
                        tb_j = type_bias_nodes[
                            jnp.where(
                                key_j >= 0,
                                p.key_type_row[jnp.maximum(key_j, 0)],
                                0,
                            )
                        ]
                    ok &= card_j == 1  # gang heads defer to the full path
                    # running caps/bursts incl. same-queue repeats in this chain
                    prevq = ex_placed & (ex_queue == qj) & ~ex_evs
                    prev_cnt = jnp.sum(prevq.astype(jnp.int32))
                    prev_pc = prevq & (ex_pcv == pc_j)
                    prev_pc_res = jnp.sum(
                        jnp.where(prev_pc[:, None], ex_reqs, 0.0), axis=0
                    )
                    # Replay gate checks over the already-committed prefix: a
                    # mis-associated near-tie can only FAIL a gate, and a gate
                    # trip truncates to the exact sequential head path (r15),
                    # so decisions stay bit-equal (parity-pinned at K in {1,8}).
                    ok &= ev_j | (
                        (r_count + 1 <= p.global_burst)
                        & jnp.all(r_res + req_j <= p.round_cap)
                        # lint: allow(vectorized-accumulator-ordering) -- integer count sum (exact); gate-trip truncates to the head path
                        & (q_sched[qj] + prev_cnt + 1 <= p.perq_burst[qj])
                        & jnp.all(
                            # lint: allow(vectorized-accumulator-ordering) -- gate-trip truncates to the exact head path
                            (q_alloc_pc[qj, pc_j] + prev_pc_res) + req_j
                            <= p.pc_queue_cap[pc_j]
                        )
                    )
                    ok &= ev_j | jnp.all(
                        r_float + flt_j <= p.float_total + 1e-3
                    )

                    # fit rows: reuse the cached (key, level, ban) rows or
                    # recompute; either way identical to the sequential formulas
                    ex_placed_l, t_lo_l, t_level_l = ex_placed, t_lo, t_level
                    t_nodes_l, t_req_l = t_nodes, t_req
                    # key AND request must match: builder problems intern the
                    # request into the key (core/keys.py), but the kernel must
                    # stay correct for any input (synthetic keys are labels)
                    match = (
                        (key_j == cache_key)
                        & (key_j >= 0)
                        & (lvl_j == cache_lvl)
                        & (ban_j == cache_ban)
                        & jnp.all(reqn_j == cache_req)
                    )

                    def fresh(_):
                        static_j = jnp.where(
                            key_j >= 0,
                            p.compat[jnp.maximum(key_j, 0)][p.node_type],
                            True,
                        )
                        okn = static_j & p.node_ok & ~p.ban_mask[ban_j]
                        f0 = okn & _fit_row(alloc[0], reqn_j[None, :])
                        fl = okn & _fit_row(alloc[lvl_j], reqn_j[None, :])
                        s0, sl_ = score_all[0], score_all[lvl_j]
                        if hetero:
                            s0 = s0 + tb_j
                            sl_ = sl_ + tb_j
                        m0 = jnp.where(f0, s0, _INF)
                        ml = jnp.where(fl, sl_, _INF)
                        return f0, fl, m0, ml, jnp.sum(f0).astype(jnp.int32)

                    def cached(_):
                        return (
                            cache_fit0, cache_fitl, cache_m0, cache_ml, cache_n0
                        )

                    fit0_j, fitl_j, m0_j, ml_j, n0_j = jax.lax.cond(
                        match, cached, fresh, None
                    )
                    cache_key = jnp.where(ev_j, cache_key, key_j)
                    cache_req = jnp.where(ev_j, cache_req, reqn_j)
                    cache_lvl = jnp.where(ev_j, cache_lvl, lvl_j)
                    cache_ban = jnp.where(ev_j, cache_ban, ban_j)
                    cache_fit0 = jnp.where(ev_j, cache_fit0, fit0_j)
                    cache_fitl = jnp.where(ev_j, cache_fitl, fitl_j)
                    cache_m0 = jnp.where(ev_j, cache_m0, m0_j)
                    cache_ml = jnp.where(ev_j, cache_ml, ml_j)
                    cache_n0 = jnp.where(ev_j, cache_n0, n0_j)

                    # clean-count corrections at touched nodes (fits only flip
                    # True -> False; count distinct nodes once)
                    tn_safe = jnp.clip(t_nodes, 0, N - 1)
                    first_occ = ex_placed & (
                        jnp.sum(
                            (
                                (t_nodes[None, :] == t_nodes[:, None])
                                & ex_placed[None, :]
                                & (iota_e[None, :] < iota_e[:, None])
                            ),
                            axis=1,
                        )
                        == 0
                    )
                    adj0 = alloc[0][tn_safe] - deltas_at(tn_safe, jnp.int32(0))
                    fit0_adj = (
                        _fit_row(adj0, reqn_j[None, :]) & fit0_j[tn_safe]
                    )
                    flips = first_occ & fit0_j[tn_safe] & ~fit0_adj
                    n0_adj = n0_j - jnp.sum(flips.astype(jnp.int32))
                    use_clean = (~ev_j) & (n0_adj >= 1)
                    lvl_sel = jnp.where(use_clean, 0, lvl_j)

                    msel = jnp.where(use_clean, m0_j, ml_j)
                    msel = msel.at[t_nodes].set(_INF, mode="drop")
                    # lint: allow(full-argmin) -- gang-unit member pick: units
                    # bypass the per-key fit cache (CLAUDE.md), O(members) rare
                    u_node = jnp.argmin(msel).astype(jnp.int32)
                    u_score = msel[u_node]
                    adjs = alloc[lvl_sel][tn_safe] - deltas_at(tn_safe, lvl_sel)
                    fsel = jnp.where(use_clean, fit0_j, fitl_j)
                    fit_t = (
                        _fit_row(adjs, reqn_j[None, :])
                        & fsel[tn_safe]  # static/ok/ban masks are node-stable
                        & ex_placed
                    )
                    base_t = jnp.sum(adjs * p.inv_scale[None, :], axis=-1)
                    if hetero:
                        base_t = base_t + tb_j[tn_safe]
                    sc_t = jnp.where(fit_t, base_t, _INF)
                    t_best_score = jnp.min(sc_t)
                    t_best_node = jnp.min(
                        jnp.where(sc_t == t_best_score, t_nodes, N)
                    ).astype(jnp.int32)
                    t_wins = (t_best_score < u_score) | (
                        (t_best_score == u_score) & (t_best_node < u_node)
                    )
                    node_j = jnp.where(t_wins, t_best_node, u_node)
                    found = jnp.minimum(t_best_score, u_score) < _INF

                    # evictee: pinned-node fit at its level, exactly
                    pin_adj = alloc[lvl_j, pin_j] - deltas_at(
                        pin_j[None], lvl_j
                    )[0]
                    ev_fit = (
                        _fit_row(pin_adj[None, :], reqn_j[None, :])[0]
                        & p.node_ok[pin_j]
                    )
                    node_j = jnp.where(ev_j, pin_j, node_j)
                    found = jnp.where(ev_j, ev_fit, found)
                    # a no-fit FAILS sequentially (state 2 + key retirement):
                    # defer; an unplaced pick always ends the chain
                    ok &= found

                    t_nodes = t_nodes.at[k].set(jnp.where(ok, node_j, N))
                    t_lo = t_lo.at[k].set(jnp.where(ev_j, 1, 0))
                    t_level = t_level.at[k].set(lvl_j)
                    t_req = t_req.at[k].set(reqn_j * ok.astype(jnp.float32))
                    ex_placed = ex_placed.at[k].set(ok)
                    ex_gang = ex_gang.at[k].set(g_j)
                    ex_queue = ex_queue.at[k].set(qj)
                    ex_pcv = ex_pcv.at[k].set(pc_j)
                    ex_reqs = ex_reqs.at[k].set(
                        req_j * ok.astype(jnp.float32)
                    )
                    ex_floats = ex_floats.at[k].set(
                        flt_j * ok.astype(jnp.float32)
                    )
                    ex_evs = ex_evs.at[k].set(ev_j & ok)
                    ex_runs = ex_runs.at[k].set(jnp.where(ev_j & ok, run_j, RJ))
                    new_k = ok & ~ev_j
                    r_count = r_count + new_k.astype(jnp.int32)
                    r_res = r_res + jnp.where(new_k, req_j, 0.0)
                    r_float = r_float + jnp.where(new_k, flt_j, 0.0)
                    r_spot_res = r_spot_res + jnp.where(ok, req_j, 0.0)
                    share_k = jnp.max(
                        jnp.where(
                            p.total_pool > 0,
                            r_spot_res / jnp.maximum(p.total_pool, 1e-9),
                            0.0,
                        )
                        * p.drf_mult
                    )
                    crossed_k = (
                        p.market & ok & (r_spot < 0) & (share_k > p.spot_cutoff)
                    )
                    r_spot = jnp.where(
                        crossed_k, wspot[qj, i_safe], r_spot
                    )
                    r_iter = r_iter + ok.astype(jnp.int32)

                    # advance the picked queue's simulation state
                    npos = nn[qj, jnp.minimum(i_j + 1, W)]
                    np_safe = jnp.minimum(npos, W - 1)
                    sim_row = sim_row.at[qj].add(
                        jnp.where(ok, req_j, 0.0)
                    )
                    next_tot = wreq_tot[qj, np_safe]
                    keyn = weighted_drf_cost(
                        ((sim_row[qj] + p.q_penalty[qj]) + next_tot)[None, :],
                        p.total_pool, p.drf_mult, p.q_weight[qj][None],
                    )[0]
                    keyn = jnp.where(p.market, -wprice[qj, np_safe], keyn)
                    keyn = jnp.where(wallowed[qj, np_safe], keyn, _INF)
                    keyn = jnp.where(
                        npos < W,
                        keyn,
                        jnp.where(tail_known[qj], _INF, -_INF),
                    )
                    sim_keys = sim_keys.at[qj].set(
                        jnp.where(ok, keyn, sim_keys[qj])
                    )
                    simpos = simpos.at[qj].set(jnp.where(ok, npos, simpos[qj]))
                    alive = ok

                # --- vectorized commit of the placed picks -----------------------
                pf = ex_placed.astype(jnp.float32)
                lv_e = jnp.arange(num_levels, dtype=jnp.int32)
                lm_e = (
                    (lv_e[:, None] >= t_lo[None, :])
                    & (lv_e[:, None] <= t_level[None, :])
                ).astype(jnp.float32)
                # lint: allow(axis1-scatter) -- batched window-commit of placed
                # picks into [P1,N,R] alloc, once per window refill
                alloc = alloc.at[:, t_nodes, :].add(
                    -lm_e[:, :, None] * t_req[None, :, :], mode="drop"
                )
                # duplicate queue indices accumulate; integral units stay exact
                q_alloc = q_alloc.at[ex_queue].add(ex_reqs)
                q_alloc_pc = q_alloc_pc.at[ex_queue, ex_pcv].add(ex_reqs)
                new_e = ex_placed & ~ex_evs
                sched_count = sched_count + jnp.sum(new_e.astype(jnp.int32))
                sched_res = sched_res + jnp.sum(
                    ex_reqs * new_e[:, None].astype(jnp.float32), axis=0
                )
                float_used = float_used + jnp.sum(
                    ex_floats * new_e[:, None].astype(jnp.float32), axis=0
                )
                q_sched = q_sched.at[ex_queue].add(new_e.astype(jnp.int32))
                spot_res = r_spot_res
                spot_price = r_spot
                # scatter ONLY placed picks: unplaced rows default to gang 0 /
                # run RJ, and a gather-set there races the real writes
                g_state = g_state.at[jnp.where(ex_placed, ex_gang, G)].set(
                    1, mode="drop"
                )
                run_rescheduled = run_rescheduled.at[ex_runs].set(
                    True, mode="drop"
                )
                ranks = jnp.cumsum(new_e.astype(jnp.int32)) - new_e.astype(
                    jnp.int32
                )
                sidx = jnp.where(new_e, cursor + ranks, max_slots_cap)
                ex_nodes_w = (
                    jnp.full((E, slot_width), N, jnp.int32)
                    .at[:, 0]
                    .set(jnp.where(new_e, t_nodes, N))
                )
                ex_counts_w = (
                    jnp.zeros((E, slot_width), jnp.int32)
                    .at[:, 0]
                    .set(new_e.astype(jnp.int32))
                )
                slot_gang = slot_gang.at[sidx].set(ex_gang, mode="drop")
                slot_nodes = slot_nodes.at[sidx].set(ex_nodes_w, mode="drop")
                slot_counts = slot_counts.at[sidx].set(ex_counts_w, mode="drop")
                cursor = cursor + jnp.sum(new_e.astype(jnp.int32))
                extra_iters = jnp.sum(ex_placed.astype(jnp.int32))

        # --- the carried skip window: patch what this trip changed ----------------
        with jax.named_scope("window"):
            # State: g_state moved at the head pick (and at the extension's
            # committed lanes), key_bad at the registered key (`register`
            # holds check_keys) -- elementwise on [Q, W], no gather.
            hit = (wg == g) & attempt
            if commit_k > 1:
                hit |= jnp.any((wg[:, :, None] == ge) & ok_e, axis=-1)
            if batch_k > 1:
                hit |= jnp.any((wg[:, :, None] == ex_gang) & ex_placed, axis=-1)
            w_skip = c.w_skip | hit | (register & (wkey == key))
            # Cursor: a queue that moved gets its row gathered at the new head
            # (from the post-commit g_state / key_bad, so the patch above is
            # moot for it).  A trip moves the queues whose heads the previous
            # trip decided, at most `rows` of them; more than that (a key
            # registration that retires several queues' heads at once) takes
            # the branch that gathers every row, which returns the [Q, W]
            # tables only -- a [G] array through a branch is copied per trip.
            moved = nskip > 0
            if rows == 1:
                # one reduce, where top_k sorts
                # lint: allow(full-argmin) -- [Q]-axis first moved queue, not [N]
                qm = jnp.argmax(moved).astype(jnp.int32)[None]
            else:
                _, qm = jax.lax.top_k(moved.astype(jnp.int32), rows)  # lowest index first
                qm = qm.astype(jnp.int32)
            moved_rows = _skip_window(
                p, p.q_start[qm], q_head[qm], g_state, key_bad, check_keys
            )
            qm = jnp.where(moved[qm], qm, Q)  # lanes with nothing to rebuild: dropped
            window = tuple(
                t.at[qm].set(r, mode="drop")
                for t, r in zip((wg, wkey, w_skip), moved_rows)
            )
            refill = jnp.sum(moved.astype(jnp.int32)) > rows
            w_gang, w_key, w_skip = jax.lax.cond(
                refill,
                lambda: _skip_window(p, p.q_start, q_head, g_state, key_bad, check_keys),
                lambda: window,
            )

        return _Carry(
            alloc=alloc,
            q_alloc=q_alloc,
            q_alloc_pc=q_alloc_pc,
            q_killed=q_killed,
            q_sched=q_sched,
            q_head=q_head,
            w_gang=w_gang,
            w_key=w_key,
            w_skip=w_skip,
            g_state=g_state,
            key_bad=key_bad,
            run_rescheduled=run_rescheduled,
            slot_gang=slot_gang,
            slot_nodes=slot_nodes,
            slot_counts=slot_counts,
            cursor=cursor,
            sched_count=sched_count,
            sched_res=sched_res,
            float_used=float_used,
            new_blocked=new_blocked,
            iterations=c.iterations + active.astype(jnp.int32) + extra_iters,
            kernel_iters=c.kernel_iters + active.astype(jnp.int32),
            window_refills=c.window_refills + refill.astype(jnp.int32),
            done=done,
            termination=termination,
            spot_price=spot_price,
            spot_res=spot_res,
            fitc_clean=fitc_clean,
            fitc_lvl=fitc_lvl,
            score_c=score_c,
            bmc_clean=bmc_clean,
            bmc_lvl=bmc_lvl,
            cslot_key=cslot_key,
            cslot_lvl=cslot_lvl,
            cslot_req=cslot_req,
        )

    return body


def _phase_b(p: SchedulingProblem, alloc, q_alloc, q_alloc_pc, run_evicted,
             run_rescheduled, num_levels: int, max_fixpoint_iters: int = 128):
    """Oversubscription repair + pinned re-scheduling fixed point."""
    RJ, R = p.run_req.shape
    N = p.node_total.shape[0]

    # Oversubscribed levels per node: allocatable negative at a real level
    # (eviction.go:146-156; level 0 = evicted priority is exempt).
    over_lvl = jnp.any(alloc < 0, axis=-1)  # [P1, N]
    over_lvl = over_lvl.at[0].set(False)
    holds_slot = p.run_valid & (~run_evicted | run_rescheduled)
    evict2 = (
        holds_slot
        & p.run_preemptible
        & (p.run_gang >= 0)
        & over_lvl[p.run_level, p.run_node]
    )
    alloc, q_alloc, q_alloc_pc = _move_runs_to_evicted(
        alloc, q_alloc, q_alloc_pc, p, evict2.astype(jnp.float32), num_levels
    )
    run_evicted = run_evicted | evict2
    run_rescheduled = run_rescheduled & ~evict2

    # Pinned re-schedule fixed point: per iteration, each node admits its
    # cheapest-queue evictee that fits (the second schedule pass, pqs.go:222-247).
    def cond(state):
        i, pending, _, _, _, progress = state
        return (i < max_fixpoint_iters) & progress

    def body(state):
        i, pending, alloc, q_alloc, run_rescheduled, _ = state
        alloc_at = alloc[p.run_level, p.run_node]  # [RJ, R]
        run_req_node = p.run_req * p.node_axes[None, :]
        fits = jnp.all(alloc_at >= run_req_node, axis=-1) & pending
        cost = weighted_drf_cost(
            q_alloc[p.run_queue] + p.run_req,
            p.total_pool,
            p.drf_mult,
            p.q_weight[p.run_queue],
        )
        cost = jnp.where(fits, cost, _INF)
        nmin = jax.ops.segment_min(cost, p.run_node, num_segments=N)
        win = fits & (cost <= nmin[p.run_node])
        ridx = jnp.where(win, jnp.arange(RJ, dtype=jnp.int32), _BIGI)
        rmin = jax.ops.segment_min(ridx, p.run_node, num_segments=N)
        win = win & (jnp.arange(RJ, dtype=jnp.int32) == rmin[p.run_node])

        winf = win.astype(jnp.float32)
        delta = p.run_req * winf[:, None]
        delta_node = run_req_node * winf[:, None]
        lv = jnp.arange(num_levels, dtype=jnp.int32)
        mask = ((lv[:, None] >= 1) & (lv[:, None] <= p.run_level[None, :])).astype(
            jnp.float32
        )
        # lint: allow(axis1-scatter) -- per-round eviction unwind over run
        # rows into [P1,N,R] alloc, outside the iteration chain
        alloc = alloc.at[:, p.run_node, :].add(
            -mask[:, :, None] * delta_node[None, :, :]
        )
        q_alloc = q_alloc.at[p.run_queue].add(delta)
        run_rescheduled = run_rescheduled | win
        pending = pending & ~win
        return (i + 1, pending, alloc, q_alloc, run_rescheduled, jnp.any(win))

    state = (jnp.int32(0), evict2, alloc, q_alloc, run_rescheduled, jnp.any(evict2))
    _, _, alloc, q_alloc, run_rescheduled, _ = jax.lax.while_loop(cond, body, state)
    return alloc, q_alloc, run_evicted, run_rescheduled


def schedule_round(
    p: SchedulingProblem,
    *,
    num_levels: int,
    max_slots: int,
    slot_width: int,
    max_iterations: int = 0,
    prefer_large: bool = False,
    cache_slots: int = -1,
    unroll: int = -1,
    batch_k: int = -1,
    commit_k: int = -1,
) -> RoundResult:
    """Run one full scheduling round on device.

    num_levels = priority-ladder length + 1 (level 0 = evicted marker level).
    max_slots/slot_width size the placement record buffer (HostContext.max_slots /
    .slot_width).  max_iterations=0 derives the safe bound #gangs + #queues + 8.
    cache_slots sizes the per-scheduling-key fit cache (-1 = derive from the
    compat table; 0 = disable, compiling the original uncached body).
    unroll applies the placement body this many times per while_loop
    iteration (-1 = derive: several on accelerators, 1 on CPU) -- each inner
    step IS one full sequential iteration (decisions bit-identical at any
    unroll; tail steps past done self-disable via the body's active gate),
    but grouping them lets XLA fuse/overlap the many small per-iteration ops
    whose fixed latencies dominate the accelerator round.
    commit_k (-1 = env ARMADA_COMMIT_K, default 1) arms the conflict-free
    multi-commit extension: up to commit_k certified-independent placements
    commit per while-loop iteration, shrinking the trip count itself (see
    _make_place_iteration).  Decisions are bit-identical at any K; commit_k=1
    compiles the single-commit body -- the A/B and escape hatch.
    """
    G = p.g_req.shape[0]
    Q = p.q_weight.shape[0]
    statics = _resolve_round_statics(
        compat_rows=p.compat.shape[0],
        G=G,
        Q=Q,
        max_iterations=max_iterations,
        prefer_large=prefer_large,
        cache_slots=cache_slots,
        unroll=unroll,
        batch_k=batch_k,
        commit_k=commit_k,
    )
    return _schedule_round_jit(
        p,
        num_levels=num_levels,
        max_slots=max_slots,
        slot_width=slot_width,
        **statics,
    )


def schedule_round_stacked(
    p: SchedulingProblem,
    *,
    num_levels: int,
    max_slots: int,
    slot_width: int,
    max_iterations: int = 0,
    prefer_large: bool = False,
    cache_slots: int = -1,
    unroll: int = -1,
    batch_k: int = -1,
    commit_k: int = -1,
) -> RoundResult:
    """Run P independent pools' rounds as ONE kernel launch (round 17).

    `p` is a SchedulingProblem whose every field carries a leading pool
    axis: lane i is pool i's padded problem, all lanes bucket-identical in
    shape (the caller groups pools by exact array shapes -- compat/ban
    tables key on REAL content, so sig equality is not enough).  The body
    is ``jax.vmap`` over the solo jit: while_loop batching runs lanes in
    lockstep until every lane's cond clears, masking finished lanes'
    carries with select, so each lane's decisions are bit-identical to a
    solo ``schedule_round`` on its slice -- pinned by
    tests/test_pool_parallel.py against the serial loop.  The win is
    dispatch-count economics: P small pools cost ONE launch whose trip
    count is max(lane trips), not sum -- the multi-tenant analog of the
    commit_k trip-count work (and one upload + one compact fetch serve
    the whole stack).

    Statics resolve exactly like schedule_round (shared helper), from the
    per-lane shapes -- a stacked compile keys on the same resolved values
    a solo lane would.
    """
    P = p.g_req.shape[0]
    assert P >= 1 and p.q_weight.ndim == 2, "expected a [P, ...] stacked problem"
    G = p.g_req.shape[1]
    Q = p.q_weight.shape[1]
    statics = _resolve_round_statics(
        compat_rows=p.compat.shape[1],
        G=G,
        Q=Q,
        max_iterations=max_iterations,
        prefer_large=prefer_large,
        cache_slots=cache_slots,
        unroll=unroll,
        batch_k=batch_k,
        commit_k=commit_k,
    )
    return _schedule_round_stacked_jit(
        p,
        num_levels=num_levels,
        max_slots=max_slots,
        slot_width=slot_width,
        **statics,
    )


def _resolve_round_statics(
    *,
    compat_rows: int,
    G: int,
    Q: int,
    max_iterations: int,
    prefer_large: bool,
    cache_slots: int,
    unroll: int,
    batch_k: int,
    commit_k: int,
) -> dict:
    """Resolve the platform/env-derived compile statics OUTSIDE the jit
    boundary -- shared by schedule_round and schedule_round_stacked so a
    stacked lane compiles the exact body its solo twin would."""
    if cache_slots < 0:
        # The per-key fit caches exist to dodge XLA:CPU's scalar-loop argmin
        # ([N] argmin at 51k nodes is ~190us there); a real TPU has a vector
        # unit, runs the uncached body 5.8x FASTER than the cached one
        # (measured: 0.19s vs 1.13s at 1M x 50k on v5e), and pays for the
        # cache's flat-scatter bookkeeping instead.  Decisions are
        # bit-identical either way (the cache is exact memoization).
        # Polarity: cache only on XLA:CPU -- any accelerator platform string
        # gets the vectorized body.  ARMADA_CACHE_SLOTS / ARMADA_BATCH_K override the
        # platform defaults (how the CPU parity suites pin the TPU-shaped
        # compile: cache 0 + batch 8).
        env = _os.environ.get("ARMADA_CACHE_SLOTS")
        if env is not None:
            cache_slots = min(int(env), compat_rows)
        else:
            cache_slots = (
                min(64, compat_rows)
                if jax.default_backend() == "cpu"
                else 0
            )
    if unroll < 0:
        # Measured (TPU v5e, 1M x 50k): unroll 8/16 changes NOTHING
        # (~0.19s either way) -- the per-iteration cost is the sequential
        # dependence chain of the body's ops, not while_loop overhead, so
        # grouping steps cannot overlap them.  The knob stays for
        # experiments; batching that actually shortens the chain is
        # batch_k (certified multi-placement per iteration).
        unroll = 1
    if batch_k < 0:
        # Default 1 EVERYWHERE -- measured on the real chip (v5e-lite,
        # 1M x 50k): the certified pick chain is bit-exact (full parity
        # gauntlet green at batch_k=8) but SLOWER (0.46s vs 0.19s at k=8,
        # 0.36s at k=16): per-op dispatch latency ~1-2us dominates this
        # chip, so replaying K sequential decisions inside one iteration
        # costs what K iterations cost.  The machinery stays behind the
        # knob (ARMADA_BATCH_K) for chips where [N]-vector work, not op
        # count, is the per-iteration floor.  prefer_large's within-budget
        # ordering re-ranks per placement, which the certification does
        # not model; the cached CPU body would recompute what its cache
        # exists to avoid -- both force 1.
        env = _os.environ.get("ARMADA_BATCH_K")
        batch_k = int(env) if env is not None else 1
    if cache_slots > 0 or prefer_large:
        batch_k = 1
    if commit_k < 0:
        commit_k = resolve_commit_k()
    # prefer_large re-ranks every queue per placement (within-budget uses
    # CURRENT cost), which the distinct-queue certification does not model;
    # a single queue cannot batch.  The multi-commit extension and the
    # batch_k replay are mutually exclusive shapes of the same iteration --
    # commit_k (the supported one) wins.
    commit_k = max(1, min(commit_k, Q))
    if prefer_large:
        commit_k = 1
    if commit_k > 1:
        batch_k = 1
    if max_iterations <= 0:
        # every iteration either decides a gang (<= G), advances a cursor
        # (<= G total across the round), or is the final no-op
        max_iterations = 2 * G + Q + 8
    return dict(
        max_iterations=max_iterations,
        prefer_large=prefer_large,
        cache_slots=cache_slots,
        unroll=unroll,
        batch_k=batch_k,
        commit_k=commit_k,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_levels", "max_slots", "slot_width", "max_iterations", "prefer_large",
        "cache_slots", "unroll", "batch_k", "commit_k",
    ),
)
def _schedule_round_stacked_jit(
    p: SchedulingProblem,
    *,
    num_levels: int,
    max_slots: int,
    slot_width: int,
    max_iterations: int,
    prefer_large: bool,
    cache_slots: int,
    unroll: int,
    batch_k: int,
    commit_k: int,
) -> RoundResult:
    """vmap of the solo round over the leading pool axis: one XLA program,
    P lockstep lanes.  The inner call is the already-jitted solo entry --
    under trace it inlines, so both compiles share cached lowering work."""
    return jax.vmap(
        lambda lane: _schedule_round_jit(
            lane,
            num_levels=num_levels,
            max_slots=max_slots,
            slot_width=slot_width,
            max_iterations=max_iterations,
            prefer_large=prefer_large,
            cache_slots=cache_slots,
            unroll=unroll,
            batch_k=batch_k,
            commit_k=commit_k,
        )
    )(p)


def resolve_commit_k() -> int:
    """The env-resolved multi-commit width (ARMADA_COMMIT_K, default 1 --
    the single-commit body), floored at 1 so reporters never echo a
    nonsensical 0/negative arm.  Resolved OUTSIDE every jit boundary (the
    schedule_round discipline: compiles key on the resolved value), and
    exported so mesh/serve/bench report the ARMED K without re-parsing.
    schedule_round additionally clamps the effective K to the problem's
    queue-axis width (and market/prefer-large rounds force 1)."""
    env = _os.environ.get("ARMADA_COMMIT_K")
    try:
        return max(1, int(env)) if env else 1
    except ValueError:
        return 1


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_levels", "max_slots", "slot_width", "max_iterations", "prefer_large",
        "cache_slots", "unroll", "batch_k", "commit_k",
    ),
)
@jax.named_scope("armada.round")
def _schedule_round_jit(
    p: SchedulingProblem,
    *,
    num_levels: int,
    max_slots: int,
    slot_width: int,
    max_iterations: int,
    prefer_large: bool,
    cache_slots: int,
    unroll: int,
    batch_k: int,
    commit_k: int,
) -> RoundResult:
    """The fully-resolved compile: schedule_round (the public wrapper)
    resolves platform/env-derived statics OUTSIDE the jit boundary, so the
    jit cache keys on the RESOLVED values -- an env override mid-process
    can never silently reuse a compile traced under the old value.

    The program carries names for the profiler (``jax.named_scope``,
    metadata only): ``armada.round`` around the whole, and its three parts
    ``armada.round.evict`` (set-up, fair-share eviction, gang activation),
    ``armada.round.loop`` (the placement loop, whose body names its trip's
    phases ``select`` / ``fit`` / ``commit`` / ``window``) and ``armada.round.repair``
    (key retirement, oversubscription repair, unbinding)."""
    with jax.named_scope("armada.round.evict"):
        G = p.g_req.shape[0]
        N, R = p.node_total.shape
        Q = p.q_weight.shape[0]
        C = p.pc_queue_cap.shape[0]

        runf = p.run_valid.astype(jnp.float32)
        run_req_node = p.run_req * p.node_axes[None, :]
        used = jnp.zeros((num_levels, N, R), jnp.float32)
        used = used.at[p.run_level, p.run_node].add(run_req_node * runf[:, None])
        alloc = allocatable_from_used(p.node_total, used)
        float_used0 = jnp.sum(
            p.run_req * (1.0 - p.node_axes)[None, :] * runf[:, None], axis=0
        )
        q_alloc = jnp.zeros((Q, R), jnp.float32).at[p.run_queue].add(p.run_req * runf[:, None])
        q_alloc_pc = (
            jnp.zeros((Q, C, R), jnp.float32)
            .at[p.run_queue, p.run_pc]
            .add(p.run_req * runf[:, None])
        )

        # --- fair-share eviction (pqs.go:117-160) ----------------------------------
        shares = fair_shares(p.q_weight, p.q_cds)
        actual = unweighted_drf_cost(q_alloc, p.total_pool, p.drf_mult)
        fairsh = jnp.maximum(shares.demand_capped_adjusted_fair_share, shares.fair_share)
        frac = jnp.where(fairsh > 0, actual / jnp.where(fairsh > 0, fairsh, 1.0), _INF)
        over = (frac > p.protected_fraction) & (p.q_weight > 0)
        run_evicted = p.run_valid & p.run_preemptible & over[p.run_queue] & (p.run_gang >= 0)
        alloc, q_alloc, q_alloc_pc = _move_runs_to_evicted(
            alloc, q_alloc, q_alloc_pc, p, run_evicted.astype(jnp.float32), num_levels
        )

        # --- gang activation: queued gangs pending; evictee slots pending iff evicted
        evictee_active = jnp.where(
            p.g_run >= 0, run_evicted[jnp.maximum(p.g_run, 0)], False
        )
        pending0 = p.g_valid & ((p.g_run < 0) | evictee_active)
        g_state = jnp.where(pending0, 0, 2).astype(jnp.int32)
        # Evictee slots whose run was NOT evicted are not candidates this round:
        # absent (3), not failed.  Decode ignored them anyway (empty ids), but
        # counting them as state 2 overflowed the compact-decode cap at scale
        # (every preemptible run would land in n_failed).
        g_state = jnp.where(p.g_valid & (p.g_run >= 0) & ~evictee_active, 3, g_state)
        g_state = jnp.where(p.g_valid, g_state, 2)
        # Slots not in this cycle's problem (slab holes, beyond-lookback jobs,
        # slack regions) are ABSENT, not failed: decode must never report them.
        g_state = jnp.where(p.g_absent, 3, g_state)

        q_head0 = jnp.zeros((Q,), jnp.int32)
        key_bad0 = jnp.zeros((p.compat.shape[0],), bool)
        w_gang0, w_key0, w_skip0 = _skip_window(
            p, p.q_start, q_head0, g_state, key_bad0, check_keys=False  # none is bad yet
        )
        carry = _Carry(
            alloc=alloc,
            q_alloc=q_alloc,
            q_alloc_pc=q_alloc_pc,
            q_killed=~(p.q_weight > 0),
            q_sched=jnp.zeros((Q,), jnp.int32),
            q_head=q_head0,
            w_gang=w_gang0,
            w_key=w_key0,
            w_skip=w_skip0,
            g_state=g_state,
            key_bad=key_bad0,
            run_rescheduled=jnp.zeros_like(run_evicted),
            slot_gang=jnp.zeros((max_slots,), jnp.int32),
            slot_nodes=jnp.full((max_slots, slot_width), N, jnp.int32),
            slot_counts=jnp.zeros((max_slots, slot_width), jnp.int32),
            cursor=jnp.int32(0),
            sched_count=jnp.int32(0),
            sched_res=jnp.zeros((R,), jnp.float32),
            float_used=float_used0,
            new_blocked=jnp.bool_(False),
            iterations=jnp.int32(0),
            kernel_iters=jnp.int32(0),
            window_refills=jnp.int32(0),
            done=jnp.bool_(False),
            termination=jnp.int32(TERM_EXHAUSTED),
            spot_price=jnp.float32(-1.0),
            spot_res=jnp.zeros((R,), jnp.float32),
            # key-fit caches: score over the POST-eviction alloc (the loop's
            # starting state); fit slots start empty and fill on first miss.
            # Flat slot-major [S*N] / level-major [P1*N] layouts: row reads are
            # contiguous dynamic slices and every update is a leading-dim scatter
            # (in-place; 2-D axis-1 scatters copy the buffer each iteration).
            fitc_clean=jnp.zeros((cache_slots * N,), bool),
            fitc_lvl=jnp.zeros((cache_slots * N,), bool),
            score_c=jnp.sum(alloc * p.inv_scale[None, None, :], axis=-1).reshape(-1),
            bmc_clean=jnp.full((cache_slots * (N // _block_size(N)),), _INF, jnp.float32),
            bmc_lvl=jnp.full((cache_slots * (N // _block_size(N)),), _INF, jnp.float32),
            cslot_key=jnp.full((cache_slots,), -1, jnp.int32),
            cslot_lvl=jnp.zeros((cache_slots,), jnp.int32),
            cslot_req=jnp.zeros((cache_slots, R), jnp.float32),
        )

        q_budget = None
        if prefer_large:
            # weighted budget = adjustedFairShare / weight (queue_scheduler.go:417);
            # reuses the shares already computed for eviction above.
            q_budget = jnp.where(
                p.q_weight > 0,
                shares.demand_capped_adjusted_fair_share
                / jnp.maximum(p.q_weight, 1e-9),
                0.0,
            )
        body = _make_place_iteration(
            p, num_levels, slot_width, check_keys=True,
            prefer_large=prefer_large, q_budget=q_budget, cache_slots=cache_slots,
            max_iterations=max_iterations, batch_k=batch_k, commit_k=commit_k,
        )
        if unroll > 1:
            inner = body

            def body(c):  # noqa: F811 - the grouped body replaces the single step
                for _ in range(unroll):
                    c = inner(c)
                return c

    with jax.named_scope("armada.round.loop"):
        carry = jax.lax.while_loop(
            lambda c: (~c.done) & (c.iterations < max_iterations), body, carry
        )
    with jax.named_scope("armada.round.repair"):
        termination = jnp.where(
            (~carry.done) & (carry.iterations >= max_iterations), TERM_MAX_ITER, carry.termination
        )

        # Retire gangs whose scheduling key was registered unfeasible but which the
        # cursor never reached (one O(G) sweep per round, not per iteration).
        g_state_final = jnp.where(
            (carry.g_state == 0)
            & p.g_valid
            & (p.g_key >= 0)
            & carry.key_bad[jnp.maximum(p.g_key, 0)],
            2,
            carry.g_state,
        )
        carry = carry._replace(g_state=g_state_final)

        # --- oversubscription repair + second pass ---------------------------------
        alloc, q_alloc, run_evicted, run_rescheduled = _phase_b(
            p,
            carry.alloc,
            carry.q_alloc,
            carry.q_alloc_pc,
            run_evicted,
            carry.run_rescheduled,
            num_levels,
        )

        # --- unbind preempted jobs: drop their evicted markers (pqs.go:286-296) ----
        gone = (run_evicted & ~run_rescheduled).astype(jnp.float32)
        alloc = alloc.at[0, p.run_node, :].add(
            p.run_req * p.node_axes[None, :] * gone[:, None]
        )

        return RoundResult(
            g_state=carry.g_state,
            slot_gang=carry.slot_gang,
            slot_nodes=carry.slot_nodes,
            slot_counts=carry.slot_counts,
            n_slots=carry.cursor,
            run_evicted=run_evicted,
            run_rescheduled=run_rescheduled,
            alloc=alloc,
            q_alloc=q_alloc,
            iterations=carry.iterations,
            termination=termination,
            scheduled_count=carry.sched_count,
            spot_price=carry.spot_price,
            q_killed=carry.q_killed,
            kernel_iters=carry.kernel_iters,
            window_refills=carry.window_refills,
        )
