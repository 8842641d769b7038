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
    # Trips of the placement loop.  Equal to `iterations` (which feeds
    # TERM_MAX_ITER) since every trip is one sequential step; kept as its own
    # counter because the stats JSON and perfbench/layers read it (ROADMAP.md
    # names the duplicate as a debt).  Excluded from the bit-equality
    # contract the parity suites pin.
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
    cand, head_visible) is therefore the same expression of the same
    values as when the window was gathered every trip.  The `window` scope
    at the body's end keeps it: what the trip decided is patched
    elementwise (g_state changes at the committed gang, key_bad at one
    key); the one queue whose cursor moved has its row gathered at the new
    head; a trip that moves more cursors than one -- a key registration
    retiring several queues' heads -- takes a lax.cond to the full gather
    and counts in window_refills.
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
    iteration count).  The loop's own condition already stops there, so the
    gate is constant true now (ROADMAP.md names it as a debt).

    History: three ways to do more than one placement a trip lived here until
    PR 31 and went because none won on the v5e (PERF.md section 6, PR 21) --
    `unroll` 8/16 changed nothing (~0.19 s either way), `batch_k` = 8 took
    0.46 s against 0.19 s, `commit_k` = 8 took 1,012 -> 274 trips and 0.923 s
    against 0.259 s.  The code:
    git show 486be4b:armada_tpu/models/fair_scheduler.py"""
    G = p.g_req.shape[0]
    N, R = p.node_total.shape
    Q = p.q_weight.shape[0]
    RJ = p.run_req.shape[0]
    S = cache_slots

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
            # Once done (or past the iteration budget) a step is an exact
            # no-op.
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
                # 2. exact re-derivation at every node this iteration's commit
                # touched -- the head's <=slot_width lanes (unplaced iterations
                # recompute unchanged values: no-op).
                tn = nodes_w  # [W], N = unused sentinel (dropped below)
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

        # --- the carried skip window: patch what this trip changed ----------------
        with jax.named_scope("window"):
            # State: g_state moved at the head pick, key_bad at the registered
            # key (`register` holds check_keys) -- elementwise on [Q, W], no
            # gather.
            hit = (wg == g) & attempt
            w_skip = c.w_skip | hit | (register & (wkey == key))
            # Cursor: a queue that moved gets its row gathered at the new head
            # (from the post-commit g_state / key_bad, so the patch above is
            # moot for it).  A trip moves the queue whose head the previous
            # trip decided; more than one (a key registration that retires
            # several queues' heads at once) takes the branch that gathers
            # every row, which returns the [Q, W] tables only -- a [G] array
            # through a branch is copied per trip.
            moved = nskip > 0
            # lint: allow(full-argmin) -- [Q]-axis first moved queue, not [N]
            qm = jnp.argmax(moved).astype(jnp.int32)[None]
            moved_rows = _skip_window(
                p, p.q_start[qm], q_head[qm], g_state, key_bad, check_keys
            )
            qm = jnp.where(moved[qm], qm, Q)  # lanes with nothing to rebuild: dropped
            window = tuple(
                t.at[qm].set(r, mode="drop")
                for t, r in zip((wg, wkey, w_skip), moved_rows)
            )
            refill = jnp.sum(moved.astype(jnp.int32)) > 1
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
            iterations=c.iterations + active.astype(jnp.int32),
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
) -> RoundResult:
    """Run one full scheduling round on device.

    num_levels = priority-ladder length + 1 (level 0 = evicted marker level).
    max_slots/slot_width size the placement record buffer (HostContext.max_slots /
    .slot_width).  max_iterations=0 derives the safe bound #gangs + #queues + 8.
    cache_slots sizes the per-scheduling-key fit cache (-1 = derive from the
    compat table; 0 = disable, compiling the original uncached body).
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
    count is max(lane trips), not sum (and one upload + one compact fetch
    serve the whole stack).

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
        # gets the vectorized body.  So the CPU suites run the cached body
        # (the one the watchdog's CPU failover serves); ARMADA_CACHE_SLOTS
        # overrides the platform default, and is how the cases that pin the
        # chip's body reach it through the served path (the tests named
        # "chip body": golden traces, full parity, verify, pipeline, pool
        # parallel, mesh).
        env = _os.environ.get("ARMADA_CACHE_SLOTS")
        if env is not None:
            cache_slots = min(int(env), compat_rows)
        else:
            cache_slots = (
                min(64, compat_rows)
                if jax.default_backend() == "cpu"
                else 0
            )
    if max_iterations <= 0:
        # every iteration either decides a gang (<= G), advances a cursor
        # (<= G total across the round), or is the final no-op
        max_iterations = 2 * G + Q + 8
    return dict(
        max_iterations=max_iterations,
        prefer_large=prefer_large,
        cache_slots=cache_slots,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_levels", "max_slots", "slot_width", "max_iterations", "prefer_large",
        "cache_slots",
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
        )
    )(p)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_levels", "max_slots", "slot_width", "max_iterations", "prefer_large",
        "cache_slots",
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
            max_iterations=max_iterations,
        )

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
