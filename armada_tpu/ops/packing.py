"""Bin-packing node selection and bind/unbind scatter updates.

The reference scans a memdb index ordered by rounded allocatable resources and takes
the first fitting node -- i.e. best-fit: the fullest node that still fits
(nodedb/nodedb.go selectNodeForPodAtPriority:615, key encoding encoding.go:22-54).
Here the same policy is an argmin over a packing score; selection lands in the same
best-fit equivalence class (identical resource shape ties may break differently,
which placement-set parity tolerates -- see SURVEY.md section 7 "Hard parts").

Gang placement generalises single placement: per-node member capacity (how many
copies of the request fit) followed by a score-ordered prefix take until the gang
cardinality is covered (all-or-nothing, gang_scheduler.go:100-247).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

# Plain numpy, NOT jnp: a module-level jnp scalar would initialize the default
# jax backend at import time -- claiming the chip before any caller can pin
# a platform.  Importing this package must never touch a backend.
_BIG = np.float32(3.0e38)


def node_packing_score(alloc_at_p, inv_scale):
    """float32[N] packing score; lower = fuller = preferred (best-fit).

    inv_scale[R]: precomputed 1/max-capacity per resource, weighting resources into
    a comparable sum (plays the role of the index key order, encoding.go:22-54).

    Mathematically tied nodes are common (free cpu trades against free memory
    at the fleet's cpu:memory ratio) and their f32 sums differ in the last bit
    by compiler: XLA:CPU fuses the multiply-add into an FMA, XLA:TPU does not
    (PR 21 chip run).  Decisions are therefore reproducible per backend, not
    node for node across backends.
    """
    return jnp.sum(alloc_at_p * inv_scale[None, :], axis=-1)


def select_best_node(mask, score):
    """(found: bool, node: int32) -- argmin of score over masked nodes.

    Ties break to the lowest node index, making selection deterministic
    (the reference's nodeIndex key tie-break, nodedb.go:84-90).
    """
    masked = jnp.where(mask, score, _BIG)
    node = jnp.argmin(masked).astype(jnp.int32)
    found = jnp.any(mask)
    return found, jnp.where(found, node, -1)


def member_capacity(alloc_at_p, req):
    """int32[N]: how many copies of req fit on each node (0 where none).

    Gangs may pack multiple members per node, like repeated single placements in one
    txn (nodedb.go ScheduleManyWithTxn:347).
    """
    safe_req = jnp.where(req > 0, req, 1.0)
    per_r = jnp.where(req[None, :] > 0, jnp.floor(alloc_at_p / safe_req[None, :]), _BIG)
    cap = jnp.min(per_r, axis=-1)
    return jnp.clip(cap, 0, 2**30).astype(jnp.int32)


def select_gang_nodes(mask, capacity, cardinality, score):
    """(feasible: bool, counts: int32[N]) -- all-or-nothing member spread.

    Takes nodes in packing-score order, filling each to its member capacity, until
    `cardinality` members are placed.  feasible=False (and zero counts) if the gang
    cannot fully fit (gang atomicity, gang_scheduler.go:229-247).

    Per-node capacity is clipped to `cardinality` so int32 sums stay exact
    (member_capacity clamps at 2**30, which would overflow a multi-node sum).
    """
    cap = jnp.minimum(jnp.where(mask, capacity, 0), cardinality)
    order = jnp.argsort(jnp.where(mask, score, _BIG))
    cap_sorted = cap[order]
    before = jnp.cumsum(cap_sorted) - cap_sorted
    take_sorted = jnp.clip(cardinality - before, 0, cap_sorted)
    feasible = jnp.sum(cap) >= cardinality
    counts = jnp.zeros_like(cap).at[order].set(take_sorted)
    counts = jnp.where(feasible, counts, 0)
    return feasible, counts.astype(jnp.int32)


def select_gang_nodes_compact(mask, capacity, cardinality, score, width: int):
    """Like select_gang_nodes but returns the spread as `width` (node, count)
    record slots (node index = N for unused slots).

    The nonzero takes form a prefix of the score-sorted node order of length at
    most min(cardinality, N) <= width, so the compact form is lossless.  This is
    the form the round kernel's placement buffer stores.
    """
    n = capacity.shape[0]
    cap = jnp.minimum(jnp.where(mask, capacity, 0), cardinality)
    order = jnp.argsort(jnp.where(mask, score, _BIG))
    cap_sorted = cap[order]
    before = jnp.cumsum(cap_sorted) - cap_sorted
    take_sorted = jnp.clip(cardinality - before, 0, cap_sorted)
    feasible = jnp.sum(cap) >= cardinality
    nodes = order[:width].astype(jnp.int32)
    counts = take_sorted[:width].astype(jnp.int32)
    nodes = jnp.where(counts > 0, nodes, n)
    return feasible, nodes, counts


def bind_to_node(used, node, req, prio_level, count=1):
    """Scatter-add `count` copies of req onto `used[prio_level, node]`.

    used: [P, N, R] per-level usage; allocatable is derived (fit.py), so binding at a
    priority automatically shrinks allocatable at that level and below
    (nodedb.go BindJobToNode:804 + MarkAllocated).
    """
    return used.at[prio_level, node, :].add(req * count)


def bind_counts(used, counts, req, prio_level):
    """Bind a gang spread: counts[N] members of req at one priority level."""
    add = counts[:, None].astype(used.dtype) * req[None, :]
    return used.at[prio_level].add(add)


def unbind_from_node(used, node, req, prio_level, count=1):
    """Inverse of bind_to_node (nodedb.go UnbindJobFromNode:931 / EvictJobsFromNode:858)."""
    return used.at[prio_level, node, :].add(-req * count)
