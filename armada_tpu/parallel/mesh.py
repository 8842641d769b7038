"""Mesh construction + sharding specs for the scheduling round.

Sharding layout (SURVEY.md section 7 "Tensor reformulation" / section 2.8):

- axis ``nodes``: node-dimension tensors (node_total[N,R], node_type[N],
  node_ok[N], and the alloc[P1,N,R] carry) are sharded -- the 50k-node pool is
  split across devices, so per-node fit masks, member capacities and packing
  scores are computed locally and the best-fit argmin is a cross-device
  reduction that XLA lowers onto ICI.
- axis ``jobs``: gang- and run-dimension tensors (g_req[G,R], g_order[G], ...,
  run_req[RJ,R], ...) are sharded -- the 1M-gang backlog is split, and the
  per-queue segment-min candidate scan reduces across devices.
- queue/pool tensors ([Q], [Q,R], [R], scalars) are replicated: Q is small
  (thousands at most) and every device needs the full fairness state.

The round kernel (models/fair_scheduler.py schedule_round) is reused unchanged:
`sharded_schedule_round` jits it with these shardings; GSPMD partitions the
while-loop body.  This mirrors how the reference runs ONE logical round over a
whole executor fleet's nodes (scheduling_algo.go:126-186) -- the parallelism is
inside the round, not across rounds.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from armada_tpu.models.fair_scheduler import schedule_round
from armada_tpu.models.problem import SchedulingProblem

AXIS_NODES = "nodes"
AXIS_JOBS = "jobs"


def make_mesh(
    devices: Optional[Sequence] = None,
    *,
    node_shards: Optional[int] = None,
    job_shards: int = 1,
) -> Mesh:
    """A 2D (nodes x jobs) device mesh.

    Defaults to all visible devices on the ``nodes`` axis: node count (50k)
    dwarfs everything else in the fit/score inner product, so that is the axis
    whose sharding buys HBM locality.  ``job_shards`` > 1 splits the backlog
    scan as well (use for very deep queues).
    """
    if devices is None:
        devices = jax.devices()
    devices = np.asarray(devices)
    n = devices.size
    if node_shards is None:
        node_shards = n // job_shards
    if node_shards * job_shards != n:
        raise ValueError(
            f"mesh {node_shards}x{job_shards} != {n} devices"
        )
    return Mesh(devices.reshape(node_shards, job_shards), (AXIS_NODES, AXIS_JOBS))


def problem_shardings(mesh: Mesh) -> SchedulingProblem:
    """A SchedulingProblem pytree of NamedShardings matching its field layout."""

    def s(*spec):
        return NamedSharding(mesh, P(*spec))

    nodes = s(AXIS_NODES)
    nodes_r = s(AXIS_NODES, None)
    jobsax = s(AXIS_JOBS)
    jobs_r = s(AXIS_JOBS, None)
    repl = s()
    return SchedulingProblem(
        node_total=nodes_r,
        node_type=nodes,
        node_ok=nodes,
        run_req=jobs_r,
        run_node=jobsax,
        run_level=jobsax,
        run_queue=jobsax,
        run_pc=jobsax,
        run_preemptible=jobsax,
        run_gang=jobsax,
        run_valid=jobsax,
        g_req=jobs_r,
        g_card=jobsax,
        g_level=jobsax,
        g_queue=jobsax,
        g_key=jobsax,
        g_pc=jobsax,
        g_order=jobsax,
        g_run=jobsax,
        g_valid=jobsax,
        g_absent=jobsax,
        g_price=jobsax,
        g_spot_price=jobsax,
        # gq_gang is read-only index data gathered with [Q,W] indices every
        # iteration; replicated so the gather never crosses devices.
        gq_gang=repl,
        q_start=repl,
        q_len=repl,
        q_weight=repl,
        q_cds=repl,
        q_penalty=repl,
        compat=repl,
        total_pool=repl,
        drf_mult=repl,
        inv_scale=repl,
        round_cap=repl,
        pc_queue_cap=repl,
        protected_fraction=repl,
        global_burst=repl,
        perq_burst=repl,
        node_axes=repl,
        float_total=repl,
        market=repl,
        spot_cutoff=repl,
        # ban rows follow the node axis; the row-index vector follows gangs
        ban_mask=s(None, AXIS_NODES),
        g_ban_row=jobsax,
        # type tables are small ([TR,T]/[K]/[K,T]) and gathered through the
        # already-gathered key every iteration; replicated like compat.
        type_bias=repl,
        key_type_row=repl,
        compat_pre_type=repl,
    )


def _check_divisible(problem: SchedulingProblem, mesh: Mesh) -> None:
    """Internal invariant check (post-pad): a trip here is a build bug, not
    an operator configuration problem -- `shard_problem` pads and the
    serving-path builders align their slab buckets to the mesh multiple
    (models/incremental._node_bucket)."""
    n_shards = mesh.shape[AXIS_NODES]
    j_shards = mesh.shape[AXIS_JOBS]
    N = problem.node_total.shape[0]
    G = problem.g_req.shape[0]
    RJ = problem.run_req.shape[0]
    for size, shards, name in ((N, n_shards, "nodes"), (G, j_shards, "gangs"), (RJ, j_shards, "runs")):
        if size % shards:
            raise ValueError(
                f"{name} axis {size} not divisible by its {shards} mesh shards "
                f"after padding -- pad_problem missed an axis (build bug)"
            )


# Axis membership for pad_problem.  Everything not listed (queue tensors,
# scalars, compat, gq offsets) is replicated and never padded.
_NODE_AX0 = ("node_total", "node_type", "node_ok")
_RUN_AX0 = (
    "run_req", "run_node", "run_level", "run_queue", "run_pc",
    "run_preemptible", "run_gang", "run_valid",
)
_GANG_AX0 = (
    "g_req", "g_card", "g_level", "g_queue", "g_key", "g_pc", "g_order",
    "g_run", "g_valid", "g_absent", "g_price", "g_spot_price", "g_ban_row",
    "gq_gang",
)
# Pad lanes must be INERT: absent gangs (kernel state 3, decode-invisible),
# invalid runs, unschedulable zero-capacity nodes -- the exact values the
# builders already use for their own bucket padding, so a padded round is
# bit-identical to the unpadded one (tests/test_mesh_serving.py pins it).
_PAD_VALUE = {"g_absent": True, "g_key": -1, "g_run": -1, "run_gang": -1}


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult if mult > 1 else n


def pad_problem(
    problem: SchedulingProblem, node_multiple: int = 1, job_multiple: int = 1
) -> SchedulingProblem:
    """Pad the node/gang/run axes up to shard multiples with inert lanes.

    Returns `problem` unchanged when the axes already divide.  Operates on
    host arrays (np.asarray); callers shard the result.  Padded lanes can
    never influence decisions: padded nodes are node_ok=False with zero
    capacity (same as the builders' bucket padding), padded gang slots are
    g_absent (kernel state 3, which decode ignores), padded run slots are
    run_valid=False.  Decode bounds (ctx.num_real_*) predate the pad, so
    compact fetch and failed/evicted scans never see the new lanes."""
    N = problem.node_total.shape[0]
    G = problem.g_req.shape[0]
    RJ = problem.run_req.shape[0]
    N2 = _round_up(N, node_multiple)
    G2 = _round_up(G, job_multiple)
    RJ2 = _round_up(RJ, job_multiple)
    if (N2, G2, RJ2) == (N, G, RJ):
        return problem
    out = {}
    for name, arr in zip(problem._fields, problem):
        arr = np.asarray(arr)
        if name in _NODE_AX0:
            target = N2
        elif name in _RUN_AX0:
            target = RJ2
        elif name in _GANG_AX0:
            target = G2
        elif name == "ban_mask" and N2 != N:
            # rows follow the ban table, columns follow the node axis; a
            # padded node is never banned (node_ok already excludes it)
            grown = np.zeros((arr.shape[0], N2), arr.dtype)
            grown[:, :N] = arr
            out[name] = grown
            continue
        else:
            out[name] = arr
            continue
        if target != arr.shape[0]:
            pad = np.full(
                (target - arr.shape[0],) + arr.shape[1:],
                _PAD_VALUE.get(name, 0),
                arr.dtype,
            )
            arr = np.concatenate([arr, pad], axis=0)
        out[name] = arr
    return SchedulingProblem(**out)


def shard_problem(
    problem: SchedulingProblem, mesh: Mesh, pad: bool = True
) -> SchedulingProblem:
    """Place a (host or device) problem onto the mesh with the round
    shardings, padding non-divisible axes with inert lanes first (pad=True;
    a mid-serve ValueError on an odd axis helped nobody -- the round-11
    `_check_divisible` raise is now an internal post-pad assertion)."""
    if pad:
        problem = pad_problem(
            problem, mesh.shape[AXIS_NODES], mesh.shape[AXIS_JOBS]
        )
    _check_divisible(problem, mesh)
    shardings = problem_shardings(mesh)
    return SchedulingProblem(
        *(jax.device_put(a, sh) for a, sh in zip(problem, shardings))
    )


# lint: allow(unpinned-out-shardings) -- deliberate: operand shardings
# propagate through the while-loop (shard_problem pre-shards every input)
# and the OUTPUTS are pulled back replicated for host decode (slots/
# states/flags are small; callers re-shard alloc for the next round).  The
# measured gather hazard is the SCATTER program, pinned in mesh_slab.py.
@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "num_levels", "max_slots", "slot_width", "max_iterations",
    ),
)
def _sharded_round(
    problem, *, mesh, num_levels, max_slots, slot_width, max_iterations,
):
    # Inputs arrive pre-sharded (shard_problem); jit propagates their shardings
    # through the while-loop and GSPMD inserts the collectives.  Outputs are
    # pulled back replicated: everything the host decodes is small ([S,W] slots,
    # [G] states, [RJ] flags) except alloc, which callers feeding the next round
    # re-shard anyway.
    return schedule_round(
        problem,
        num_levels=num_levels,
        max_slots=max_slots,
        slot_width=slot_width,
        max_iterations=max_iterations,
    )


def sharded_schedule_round(
    problem: SchedulingProblem,
    mesh: Mesh,
    *,
    num_levels: int,
    max_slots: int,
    slot_width: int,
    max_iterations: int = 0,
):
    """Run one scheduling round SPMD over the mesh.

    Equivalent single-device call: models.schedule_round.  Results are
    numerically identical (the kernel is deterministic and sharding only
    distributes the reductions).
    """
    problem = shard_problem(problem, mesh)
    with mesh:
        return _sharded_round(
            problem,
            mesh=mesh,
            num_levels=num_levels,
            max_slots=max_slots,
            slot_width=slot_width,
            max_iterations=max_iterations,
        )
