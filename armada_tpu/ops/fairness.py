"""Dominant-resource fairness on device.

Reference semantics:
- cost of an allocation = max(0, max_r(alloc_r / total_r * multiplier_r)), weighted
  cost divides by queue weight (fairness.go:99-103, DivideZeroOnError -> 0 where
  total_r == 0).
- Fair shares are computed by iterative water-filling that re-shares capacity queues
  don't demand (context/scheduling.go updateFairShares:220-300): at most 10
  iterations, stopping once <=1% of capacity remains unallocated.

The Go version walks sorted queue structs; here every step is a [Q]-vector op, so one
iteration is a handful of VPU instructions regardless of queue count.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


def unweighted_drf_cost(alloc, total, multipliers):
    """DRF cost of allocation(s) `alloc[..., R]` against pool totals `total[R]`.

    Matches fairness.go UnweightedCostFromAllocation:103: per-resource fraction of
    pool total, scaled by the configured multiplier, dominant (max) reduced; zero
    totals contribute zero (DivideZeroOnError).
    """
    safe_total = jnp.where(total > 0, total, 1.0)
    frac = jnp.where(total > 0, alloc / safe_total, 0.0) * multipliers
    return jnp.maximum(0.0, jnp.max(frac, axis=-1))


def weighted_drf_cost(alloc, total, multipliers, weight):
    """fairness.go WeightedCostFromAllocation:99: unweighted cost / queue weight."""
    safe_w = jnp.where(weight > 0, weight, 1.0)
    return jnp.where(weight > 0, unweighted_drf_cost(alloc, total, multipliers) / safe_w, 0.0)


class FairShares(NamedTuple):
    """Per-queue share vectors (context/queue.go QueueSchedulingContext fields)."""

    fair_share: jax.Array  # weight / sum-of-weights
    demand_capped_adjusted_fair_share: jax.Array  # share given current demand
    uncapped_adjusted_fair_share: jax.Array  # share if demand were infinite
    # trips of the water-filling loop (1 when every queue wants its share or
    # more; more where queues under their share hand capacity on)
    iterations: jax.Array


def theoretical_share(weights, constrained_demand_share, priority: float) -> float:
    """The demand-capped adjusted fair share a NEW queue with weight
    1/priority and unbounded demand would receive alongside the given queues
    (context/scheduling.go CalculateTheoreticalShare:199)."""
    import numpy as np

    w = np.append(np.asarray(weights, np.float32), np.float32(1.0 / priority))
    cds = np.append(
        np.asarray(constrained_demand_share, np.float32), np.float32(1.0)
    )
    shares = fair_shares(w, cds)
    return float(np.asarray(shares.demand_capped_adjusted_fair_share)[-1])


def fair_shares(weights, constrained_demand_share, *, max_iterations: int = 10) -> FairShares:
    """Water-filling fair-share computation over [Q] vectors.

    `weights[q]` must be 0 for padding/absent queues (they then receive zero shares
    and never absorb capacity).  `constrained_demand_share[q]` is the DRF cost of the
    queue's constraint-capped demand (scheduling_algo.go:486-573 computes this from
    demand capped by per-queue limits).

    Mirrors context/scheduling.go updateFairShares:220-300 exactly, including the
    iteration-order subtleties: the uncapped share update uses the *previous*
    iteration's spare shares, and the loop breaks after the uncapped update when all
    queues have achieved demand.

    JITTED (round 17): the eager path closed over weights/cds inside the
    while_loop body, embedding fresh constant arrays in the jaxpr every
    call -- jax's primitive cache missed and XLA recompiled the loop on
    EVERY invocation (~49ms/call measured on the CPU host; with
    queue_stats_from_result calling this once per pool per cycle, an
    8-pool cycle burned ~0.4s pure recompilation).  As traced arguments
    they key the compile cache on shape only; inside an enclosing jit
    (the round kernel) the inner jit inlines as before.
    """
    return _fair_shares_jit(
        jnp.asarray(weights, jnp.float32),
        jnp.asarray(constrained_demand_share, jnp.float32),
        max_iterations=max_iterations,
    )


import functools as _functools


@_functools.partial(jax.jit, static_argnames=("max_iterations",))
def _fair_shares_jit(weights, cds, *, max_iterations: int) -> FairShares:
    weight_sum = jnp.sum(weights)
    fair_share = jnp.where(weight_sum > 0, weights / jnp.where(weight_sum > 0, weight_sum, 1.0), 0.0)

    def cond(state):
        i, unallocated, running, _, _, _, _ = state
        return (i < max_iterations) & (unallocated > 0.01) & running

    def body(state):
        i, unallocated, running, achieved, spare, dcafs, ucafs = state
        active_w = jnp.where(achieved, 0.0, weights)
        total_weight = jnp.sum(active_w)
        # Uncapped share: every queue takes its weight-share of (unallocated minus its
        # own spare), as if it alone had infinite demand (scheduling.go:260-272).
        denom = total_weight + jnp.where(achieved, weights, 0.0)
        take = jnp.where(denom > 0, weights / jnp.where(denom > 0, denom, 1.0), 0.0)
        ucafs = ucafs + take * (unallocated - spare)
        # scheduling.go:274-276 -- all demand achieved: stop (after ucafs update).
        running = total_weight > 0.0
        # Demand-capped share for queues still short of demand (scheduling.go:278-284).
        safe_tw = jnp.where(total_weight > 0, total_weight, 1.0)
        add = jnp.where(achieved | (total_weight <= 0), 0.0, weights / safe_tw * unallocated)
        dcafs = dcafs + add
        # Clip to demand; overspill becomes next iteration's unallocated pool
        # (scheduling.go:286-297).
        spare_new = dcafs - cds
        newly_achieved = spare_new > 0.0
        dcafs = jnp.where(newly_achieved, cds, dcafs)
        spare = jnp.where(newly_achieved, spare_new, 0.0)
        achieved = achieved | newly_achieved
        unallocated = jnp.where(running, jnp.sum(spare * newly_achieved), 0.0)
        # Keep non-running exit consistent with the Go break: when running is False we
        # leave dcafs untouched above (add==0) and the loop condition ends it.
        return (i + 1, unallocated, running, achieved, spare, dcafs, ucafs)

    q = weights.shape[0]
    zeros = jnp.zeros((q,), jnp.float32)
    init = (
        jnp.int32(0),
        jnp.float32(1.0),
        jnp.bool_(True),
        jnp.zeros((q,), bool),
        zeros,
        zeros,
        zeros,
    )
    iterations, _, _, _, _, dcafs, ucafs = jax.lax.while_loop(cond, body, init)
    return FairShares(fair_share, dcafs, ucafs, iterations)
