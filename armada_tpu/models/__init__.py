"""Scheduling models: the tensorised scheduling round.

`problem` builds dense device tensors from host job/node/queue objects;
`incremental` maintains them across cycles from event deltas;
`fair_scheduler` is the jitted round kernel -- the TPU-native replacement for the
reference's PreemptingQueueScheduler -> QueueScheduler -> GangScheduler -> NodeDb
pipeline (internal/scheduler/scheduling/*.go).
"""

import dataclasses as _dataclasses

from armada_tpu.models.problem import (
    begin_decode,
    SchedulingProblem,
    HostContext,
    build_problem,
    decode_result,
    RoundOutcome,
)
from armada_tpu.models.fair_scheduler import schedule_round, RoundResult


class _ShadowOnce:
    """Shadow thunks with run-once accounting across a watchdog failover:
    the device attempt and the CPU re-run share one cursor, so a thunk that
    already STARTED in the abandoned worker is never re-entered (a torn
    re-run would double-apply host mutations; skipping is safe because
    shadow work is decision-independent and self-healing -- unshipped rows
    ride the next bundle, unswept terminals sweep next round).  The cursor
    advance is locked: an abandoned worker that UNWEDGES while the failover
    thread is draining must not be handed the same thunk (each index is
    claimed under the lock; the thunk itself runs outside it)."""

    def __init__(self, thunks):
        from armada_tpu.analysis.tsan import make_lock

        self._thunks = list(thunks)
        self._next = 0
        self._lock = make_lock("models.shadow_once")

    def run_pending(self) -> None:
        from armada_tpu.ops.trace import recorder as _trace

        while True:
            with self._lock:
                if self._next >= len(self._thunks):
                    return
                fn = self._thunks[self._next]
                idx = self._next
                self._next += 1
            with _trace().span("shadow_thunk", index=idx):
                fn()


def _note_round_devices(result) -> None:
    """Tell the supervisor where this round's outputs live, read from the
    arrays themselves (/healthz names the platform from it)."""
    devices = getattr(result.g_state, "devices", None)
    if devices is not None:  # host-array results (patched kernels) have none
        from armada_tpu.core.watchdog import supervisor

        supervisor().note_round_devices(devices())


def _ladder_errors() -> tuple:
    """The DELIBERATELY NARROW error classes that walk the failover ladder:
    RoundTimeout = device wedge (thread abandoned); JaxRuntimeError = the
    backend died under us; FaultInjected = a drill; RoundVerificationError
    = the round-output certification caught a silently-wrong answer
    (models/verify.py).  A generic RuntimeError out of decode/rollback is a
    host code bug -- degrading on it would hide the bug behind a
    spuriously-working CPU re-run (and drop every device cache for
    nothing), so it propagates untouched."""
    from armada_tpu.core import faults
    from armada_tpu.core.watchdog import RoundTimeout
    from armada_tpu.models.verify import RoundVerificationError

    from jax.errors import JaxRuntimeError

    return (
        RoundTimeout, JaxRuntimeError, faults.FaultInjected,
        RoundVerificationError,
    )


def _round_env(problem, ctx, config, shadow_work, explain_enabled):
    """The per-round prologue shared by run_round_on_device and the
    phase-split pool-parallel dispatchers: resolved kernel statics, the
    run-once shadow cursor, mesh/supervisor singletons, and the ONE explain
    cadence tick this scheduling round gets (the failover / mesh-degrade
    ladder re-enters the round body for the SAME round, and the committed
    re-run must keep the attribution the device attempt was armed for.
    Away rounds pass explain_enabled=False and never TICK: their
    outcome.explain is discarded by the away apply, and a tick here would
    halve/drift the host pool's advertised cadence)."""
    from armada_tpu.core.watchdog import supervisor
    from armada_tpu.parallel.serving import mesh_serving

    kernel_kwargs = dict(
        num_levels=len(ctx.ladder) + 2,
        max_slots=ctx.max_slots,
        slot_width=ctx.slot_width,
        # Static flag (not a tensor): the default compile carries none of the
        # alternate-ordering work.  Market pools keep bid ordering.
        prefer_large=bool(
            config.enable_prefer_large_job_ordering
            and not bool(problem.market)
        ),
    )
    shadow = _ShadowOnce(shadow_work)
    explain_armed = False
    if explain_enabled:
        from armada_tpu.models import explain as _explain_mod

        explain_armed = _explain_mod.explain_due(getattr(ctx, "pool", ""))
    return kernel_kwargs, shadow, mesh_serving(), supervisor(), explain_armed


def _build_device_problem(problem, device_problem, mesh_sv, sup):
    """Resolve the device-resident problem for one round: the caller's
    cached buffers (value or thunk), else a fresh upload -- sharded onto
    the serving mesh for from-scratch rounds (legacy path, away rounds) so
    every round the plane runs sees the same backend shape.  Incremental
    rounds arrive pre-sharded via MeshDeviceDeltaCache.  While the
    supervisor is degraded to CPU the mesh is out of the loop entirely
    (the CPU rung sits BELOW the ladder)."""
    import jax.numpy as jnp

    dp = device_problem() if callable(device_problem) else device_problem
    if dp is None:
        mesh = (
            mesh_sv.serving_mesh()
            if mesh_sv.enabled() and not sup.degraded
            else None
        )
        if mesh is not None:
            from armada_tpu.parallel.mesh import shard_problem

            dp = shard_problem(problem, mesh)
        else:
            dp = SchedulingProblem(*(jnp.asarray(a) for a in problem))
    return dp


def _failover_ladder(
    e, *, problem, ctx, config, kernel_kwargs, shadow, explain_armed,
    host_problem, mesh_sv, sup, deadline,
):
    """Mesh degrade ladder + CPU rung for a failed device attempt --
    shared by the watchdog path (hang/XLA error/drill/verification), the
    inline path (verification only: nothing hangs there, the round
    completed with a WRONG answer), and the pool-parallel phase-split
    finishers (a failed pool walks the ladder ALONE -- the other pools'
    already-committed or still-in-flight rounds are untouched, which is
    what bounds a verification failure's blast radius to one pool).
    Verification failures additionally feed the per-device quarantine
    score (scheduler/quarantine.py) -- N strikes stop the re-probe loops
    from re-promoting the device until operator clear."""
    from armada_tpu.core.watchdog import run_with_deadline
    from armada_tpu.models.verify import RoundVerificationError
    from armada_tpu.ops.trace import recorder as _trace

    reason = f"{type(e).__name__}: {e}"
    if isinstance(e, RoundVerificationError):
        _quarantine_strike(mesh_sv, sup, reason)
    try:
        hp = host_problem() if callable(host_problem) else host_problem
    except BaseException:
        # The materialize thunk itself failed mid-failover: still
        # record the DEVICE loss (degrade + reset hooks + re-probe) so
        # subsequent cycles do not re-attempt the wedged backend at a
        # full watchdog deadline each, then let the host error surface.
        sup.record_failure(reason)
        raise
    if hp is None and hasattr(problem, "_fields"):
        hp = problem
    if hp is None:
        sup.record_failure(reason)
        raise e  # no host tables to fail over from (legacy caller)
    # Mesh degrade ladder (parallel/serving.py) BEFORE the CPU rung:
    # chip loss re-runs the SAME round on a halved mesh from host
    # tables (the reset hooks just replaced every device cache, so the
    # next cycle's apply is one full slab upload re-sharded onto the
    # smaller mesh).  The supervisor never records a failure for a
    # rung that recovers on-device -- the backend is still "device".
    # While the supervisor is ALREADY degraded to CPU this round never
    # ran on the mesh (_build_device_problem skipped it), so a failure
    # here is a CPU-rung failure: walking the ladder would re-target
    # the accelerator the supervisor marked down and misfile the loss.
    while mesh_sv.enabled() and not sup.degraded:
        smaller = mesh_sv.degrade(reason)
        if smaller is None:
            break
        n = int(smaller.devices.size)
        _trace().annotate(mesh_degraded=True, mesh_devices=n)
        try:
            fn = lambda m=smaller: _run_round_on_mesh(  # noqa: E731
                hp, ctx, config, kernel_kwargs, shadow, m, explain_armed,
            )
            with _trace().span(
                "mesh_degrade_rerun", devices=n, reason=reason[:300]
            ):
                # The inline (no-watchdog) path re-runs inline too: a
                # verification failure proved the answer wrong, not
                # the backend wedged, so no deadline thread exists.
                out = (
                    run_with_deadline(
                        fn, deadline, what=f"mesh round ({n} devices)"
                    )
                    if deadline > 0
                    else fn()
                )
            sup.record_success()
            return out
        except _ladder_errors() as e2:
            reason = f"{type(e2).__name__}: {e2}"
            if isinstance(e2, RoundVerificationError):
                _quarantine_strike(mesh_sv, sup, reason, mesh=smaller)
            continue
    # Failover attribution (ops/trace.py): tag the CYCLE that paid the
    # failover window -- the same cycle the SLO layer's fallback-delta
    # rule files as degraded -- and record the re-run as its own span.
    sup.record_failure(reason)
    _trace().annotate(degraded=True, failover_reason=reason[:300])
    with _trace().span("cpu_failover", reason=reason[:300]):
        # A verification failure ON THIS RUNG propagates out: decisions
        # that disagree with the conservation invariants on the CPU
        # backend mean the corruption is host-side or systemic --
        # looping would commit to never answering.
        return _run_round_cpu_failover(
            hp, ctx, config, kernel_kwargs, shadow, explain_armed
        )


def run_round_on_device(
    problem, ctx, config, device_problem=None, shadow_work=(),
    host_problem=None, explain_enabled=True,
):
    """(result, outcome): run the jitted round on a built problem and decode,
    including the gang-txn rollback loop.  Shared by the from-scratch path
    (run_scheduling_round) and the incremental-builder path
    (scheduler/incremental_algo.py); `device_problem` lets callers supply
    cached device buffers (models.incremental.DeviceProblemCache /
    slab.DeviceDeltaCache) -- or a ZERO-ARG CALLABLE producing them, which
    moves the device apply/upload inside the watchdog deadline too (a hung
    scatter is a device loss exactly like a hung kernel).

    `shadow_work`: zero-arg callables run between the decode dispatch and
    the blocking fetch -- the KERNEL SHADOW.  Anything that neither reads
    this round's outcome nor mutates what decode still needs is sound here
    (submit-side table inserts and prefetch_content are; the ctx id
    vectors are snapshots, slab.SnapshotIds, precisely for this).  The
    thunks run ONCE, before the first decode -- gang-rollback re-runs never
    repeat them, and a watchdog failover resumes after the last thunk that
    started.

    `host_problem`: the host-array ground truth for CPU failover (a
    SchedulingProblem or a thunk building one, e.g. DeltaBundle.materialize).
    When the device round times out (core/watchdog deadline) or dies on an
    XLA error, the SAME round re-runs on the explicit XLA:CPU backend from
    these host tables -- sound because the problem is fully assembled
    host-side and decisions commit only after decode (the abort-on-publish
    discipline already guarantees no partial commit).  Defaults to
    `problem` when that is a real SchedulingProblem."""
    from armada_tpu.core import faults
    from armada_tpu.core.watchdog import run_with_deadline
    from armada_tpu.models.verify import RoundVerificationError

    kernel_kwargs, shadow, mesh_sv, sup, explain_armed = _round_env(
        problem, ctx, config, shadow_work, explain_enabled
    )

    if sup.degraded:
        # Degraded steady state: rounds target the explicit CPU backend
        # (slab caches were reset and route uploads there via
        # watchdog.data_device()); no watchdog thread -- the host cannot
        # hang on itself -- and no device fault check (the device sites
        # model the ACCELERATOR boundary, which is out of the loop here).
        # A RoundVerificationError here propagates UNTOUCHED: the CPU rung
        # is the trusted floor, so a wrong answer on it escalates loudly
        # instead of looping the ladder (models/verify.py).
        import jax

        with jax.default_device(jax.devices("cpu")[0]):
            return _round_body(
                _build_device_problem(problem, device_problem, mesh_sv, sup),
                ctx, config, kernel_kwargs, shadow, explain_armed,
            )

    deadline = sup.deadline_s()

    def _failover(e):
        return _failover_ladder(
            e, problem=problem, ctx=ctx, config=config,
            kernel_kwargs=kernel_kwargs, shadow=shadow,
            explain_armed=explain_armed, host_problem=host_problem,
            mesh_sv=mesh_sv, sup=sup, deadline=deadline,
        )

    if deadline <= 0:
        # Watchdog disabled (tests/bench default): the original inline
        # path.  Hangs cannot be caught here (nothing watches the clock),
        # but a verification failure CAN -- the round completed, with a
        # wrong answer -- so the silent-corruption defense works without
        # the watchdog armed.
        faults.check("device_round")
        try:
            return _round_body(
                _build_device_problem(problem, device_problem, mesh_sv, sup),
                ctx, config, kernel_kwargs, shadow, explain_armed,
            )
        except RoundVerificationError as e:
            return _failover(e)

    def _device_attempt():
        faults.check("device_round")
        return _round_body(
            _build_device_problem(problem, device_problem, mesh_sv, sup),
            ctx, config, kernel_kwargs, shadow, explain_armed,
        )

    if mesh_sv.enabled() and mesh_sv.device_count():
        from armada_tpu.ops.trace import recorder as _trace

        _trace().annotate(mesh_devices=mesh_sv.device_count())
    try:
        out = run_with_deadline(_device_attempt, deadline)
        sup.record_success()
        return out
    except _ladder_errors() as e:
        return _failover(e)


def dispatch_round_on_device(
    problem, ctx, config, device_problem=None, shadow_work=(),
    host_problem=None, explain_enabled=True,
):
    """Phase-split run_round_on_device (pool-parallel serving, round 17):
    dispatch NOW -- devcache apply, kernel, compaction, verify/explain
    enqueues, shadow thunks -- and return a zero-arg ``finish()`` ->
    (result, outcome) that performs the blocking fetch, verification
    verdict, decode and the gang-rollback loop LATER.  Between dispatch
    and finish the caller may dispatch OTHER pools' rounds: the device
    executes the kernels back to back while the transfers and host-side
    assembles overlap, which is what moves a P-pool cycle's wall clock
    from ~sum(pools) toward ~max(pool).

    Error semantics match run_round_on_device exactly, scoped to THIS
    round: a dispatch failure walks the failover ladder immediately (the
    returned finish hands back the committed re-run); a finish failure
    (timeout, XLA death, drill, RoundVerificationError) walks the ladder
    at finish time -- other pools' rounds are untouched.  Decisions are
    bit-identical to the serial path: the split only reorders asynchronous
    enqueues that never read another round's output (the PR-2 dependency
    discipline), pinned by tests/test_pool_parallel.py."""
    env = _round_env(problem, ctx, config, shadow_work, explain_enabled)
    return _dispatch_one(problem, ctx, config, device_problem, host_problem, env)


def _dispatch_one(
    problem, ctx, config, device_problem, host_problem, env,
    on_dispatch_failover=None,
):
    """dispatch_round_on_device with a precomputed _round_env (the explain
    cadence tick happens in _round_env -- exactly once per round, so paths
    that may fall back between dispatch strategies resolve it first).
    `on_dispatch_failover` fires when the DISPATCH phase walks the ladder
    (the fallback count moves before any finish runs -- pool-parallel
    degraded attribution needs the exact pool)."""
    from armada_tpu.core import faults
    from armada_tpu.core.watchdog import run_with_deadline
    from armada_tpu.models.verify import RoundVerificationError

    kernel_kwargs, shadow, mesh_sv, sup, explain_armed = env

    def _failover(e, deadline):
        return _failover_ladder(
            e, problem=problem, ctx=ctx, config=config,
            kernel_kwargs=kernel_kwargs, shadow=shadow,
            explain_armed=explain_armed, host_problem=host_problem,
            mesh_sv=mesh_sv, sup=sup, deadline=deadline,
        )

    if sup.degraded:
        # CPU steady state: the "device" IS the host, there is nothing to
        # overlap a dispatch against -- run the whole round inline now
        # (same semantics as run_round_on_device's degraded branch) and
        # hand back the completed answer.
        import jax

        with jax.default_device(jax.devices("cpu")[0]):
            out = _round_body(
                _build_device_problem(problem, device_problem, mesh_sv, sup),
                ctx, config, kernel_kwargs, shadow, explain_armed,
            )
        return lambda: out

    deadline = sup.deadline_s()

    if deadline <= 0:
        # Inline path (tests/bench default): dispatch errors propagate like
        # run_round_on_device's inline branch; only a verification failure
        # at finish walks the ladder.
        faults.check("device_round")
        handle = _dispatch_body(
            _build_device_problem(problem, device_problem, mesh_sv, sup),
            ctx, config, kernel_kwargs, shadow, explain_armed,
        )

        def finish_inline():
            try:
                return _finish_body(handle)
            except RoundVerificationError as e:
                return _failover(e, 0.0)

        return finish_inline

    def _dispatch_attempt():
        faults.check("device_round")
        return _dispatch_body(
            _build_device_problem(problem, device_problem, mesh_sv, sup),
            ctx, config, kernel_kwargs, shadow, explain_armed,
        )

    try:
        handle = run_with_deadline(
            _dispatch_attempt, deadline, what="round dispatch"
        )
    except _ladder_errors() as e:
        if on_dispatch_failover is not None:
            on_dispatch_failover()
        out = _failover(e, deadline)
        return lambda: out

    def finish():
        try:
            out = run_with_deadline(
                lambda: _finish_body(handle), deadline, what="round fetch"
            )
            sup.record_success()
            return out
        except _ladder_errors() as e:
            return _failover(e, deadline)

    return finish


@_dataclasses.dataclass
class PoolRoundSpec:
    """One pool's round inputs for dispatch_pool_rounds -- the same five
    arguments its run_round_on_device call would take."""

    problem: object  # stats_view / SchedulingProblem (host side)
    ctx: object  # HostContext
    device_problem: object = None  # cached device buffers, or a thunk
    host_problem: object = None  # CPU-failover ground truth (thunk ok)
    shadow_work: tuple = ()
    explain_enabled: bool = True


def dispatch_pool_rounds(specs, config, allow_stacking=True):
    """Dispatch MANY pools' rounds through the device before ANY fetch --
    the pool-parallel cycle's device phase (scheduler/algo.py windows).

    Returns ``(finishes, stacked_launches, stacked_pools,
    dispatch_failed)``: ``finishes[i]()`` ->
    (result, outcome) for specs[i], to be called IN POOL ORDER (the caller
    decodes/applies serially, preserving the serial loop's cross-pool
    apply order exactly).  Pools whose device problems match in EVERY
    array shape/dtype (and compile statics) batch into ONE stacked kernel
    launch with a leading pool axis (fair_scheduler.schedule_round_stacked
    + begin_decode_stacked + verify.dispatch_verify_stacked: one launch,
    one compact fetch, one verify fetch for the whole group) --
    ``stacked_launches`` counts them.  Shape matching is exact because
    compat/ban tables key on REAL content; `shape_bucket` quantization is
    what makes matches common for small tenants.  ``dispatch_failed`` is
    the set of spec indices whose DISPATCH already walked the failover
    ladder (their finishes return the committed re-run) -- the caller's
    per-pool degraded attribution needs it, because the fallback count
    moved before any finish ran.

    Stacking is skipped (pipelined dispatch only) when: the supervisor is
    degraded (CPU inline), a serving mesh is armed (jnp.stack over
    NamedSharded slabs would gather them -- the round-12 hazard; pipelined
    dispatch composes with the mesh instead), or ARMADA_FAULT is set (the
    round_corrupt drill lanes are solo-shaped).  A pool whose stacked
    dispatch or finish fails walks the SAME per-pool failover ladder as
    the solo path -- re-run solo from its own host tables, blast radius
    one pool."""
    import os as _os

    from armada_tpu.core import faults
    from armada_tpu.core.watchdog import run_with_deadline, supervisor
    from armada_tpu.ops.trace import recorder as _trace
    from armada_tpu.parallel.serving import mesh_serving

    sup = supervisor()
    mesh_sv = mesh_serving()
    envs = [
        _round_env(s.problem, s.ctx, config, s.shadow_work, s.explain_enabled)
        for s in specs
    ]
    can_stack = (
        allow_stacking
        and len(specs) > 1
        and not sup.degraded
        and not mesh_sv.enabled()
        and not _os.environ.get("ARMADA_FAULT")
    )
    finishes: list = [None] * len(specs)
    dispatch_failed: set = set()
    if not can_stack:
        for i, s in enumerate(specs):
            finishes[i] = _dispatch_one(
                s.problem, s.ctx, config, s.device_problem, s.host_problem,
                envs[i],
                on_dispatch_failover=lambda i=i: dispatch_failed.add(i),
            )
        return finishes, 0, 0, dispatch_failed

    deadline = sup.deadline_s()
    errors = _ladder_errors()

    def _fail(i, e):
        s = specs[i]
        kk, shadow, _, _, explain_armed = envs[i]
        out = _failover_ladder(
            e, problem=s.problem, ctx=s.ctx, config=config, kernel_kwargs=kk,
            shadow=shadow, explain_armed=explain_armed,
            host_problem=s.host_problem, mesh_sv=mesh_sv, sup=sup,
            deadline=deadline,
        )
        return lambda: out

    # Phase 1: build every pool's device problem (the O(delta) devcache
    # scatters), each under its own deadline/blast radius.
    dps: list = [None] * len(specs)
    for i, s in enumerate(specs):

        def _build(s=s, env=envs[i]):
            faults.check("device_round")
            return _build_device_problem(s.problem, s.device_problem, env[2], env[3])

        if deadline <= 0:
            # inline discipline (run_round_on_device's no-watchdog branch):
            # build/dispatch errors propagate -- laddering a host/XLA bug
            # here would mask it behind a spuriously-working CPU re-run
            dps[i] = _build()
            continue
        try:
            dps[i] = run_with_deadline(
                _build, deadline, what="pool round dispatch"
            )
        except errors as e:
            finishes[i] = _fail(i, e)
            dispatch_failed.add(i)

    # Phase 2: group by (compile statics, exact array shapes/dtypes);
    # insertion order keeps groups in first-member pool order.
    groups: dict = {}
    for i in range(len(specs)):
        if finishes[i] is not None:
            continue
        kk = envs[i][0]
        # shape + dtype OBJECTS (hashable) -- stringifying 30+ dtypes per
        # pool per cycle measurably taxed the steady cycle
        key = (
            tuple(sorted(kk.items())),
            tuple((a.shape, a.dtype) for a in dps[i]),
        )
        groups.setdefault(key, []).append(i)

    stacked_launches = 0
    stacked_pools = 0
    for _key, idxs in groups.items():
        if len(idxs) == 1:
            i = idxs[0]
            finishes[i] = _dispatch_one(
                specs[i].problem, specs[i].ctx, config, dps[i],
                specs[i].host_problem, envs[i],
                on_dispatch_failover=lambda i=i: dispatch_failed.add(i),
            )
            continue
        if deadline <= 0:
            # inline discipline: stacked dispatch errors propagate too
            group_finishes = _dispatch_stacked_group(
                idxs, specs, envs, dps, config, deadline, mesh_sv, sup, _key
            )
        else:
            try:
                group_finishes = run_with_deadline(
                    lambda idxs=idxs, key=_key: _dispatch_stacked_group(
                        idxs, specs, envs, dps, config, deadline, mesh_sv,
                        sup, key,
                    ),
                    deadline,
                    what="stacked pool dispatch",
                )
            except errors as e:
                for i in idxs:
                    finishes[i] = _fail(i, e)
                    dispatch_failed.add(i)
                continue
        stacked_launches += 1
        stacked_pools += len(idxs)
        for i, fin in zip(idxs, group_finishes):
            finishes[i] = fin
    if stacked_launches:
        _trace().annotate(pools_stacked_launches=stacked_launches)
    return finishes, stacked_launches, stacked_pools, dispatch_failed


_STACK_PROBLEMS = None
# (group key) -> (per-pool dp tuples, stacked problem).  Steady-state
# cycles present the SAME device problem objects every cycle (the
# devcache's no-op apply keeps _prev untouched), so the stack copy can be
# reused by identity.  Entries hold strong refs, which is what makes the
# identity check ABA-safe (a cached object cannot be freed and its id
# reused while the entry lives); staleness is bounded by the size cap and
# the watchdog reset hook (device loss must drop buffers pinned on a dead
# backend).
_STACK_CACHE: dict = {}
_STACK_CACHE_CAP = 8
_STACK_HOOKED = False


def _stack_problems(key, dps):
    """Stack P device problems along a new leading pool axis as ONE jitted
    program -- the eager form was one XLA dispatch per field (~0.45ms each
    on CPU x 30+ fields = the stacking win, erased) -- memoized by operand
    IDENTITY so mostly-idle steady cycles skip even that.  Device-side
    copies, never a host transfer."""
    global _STACK_PROBLEMS, _STACK_HOOKED
    if not _STACK_HOOKED:
        from armada_tpu.core.watchdog import add_reset_hook

        add_reset_hook(_STACK_CACHE.clear)
        _STACK_HOOKED = True
    dps = tuple(dps)
    hit = _STACK_CACHE.get(key)
    if hit is not None and len(hit[0]) == len(dps) and all(
        a is b for a, b in zip(hit[0], dps)
    ):
        return hit[1]
    if _STACK_PROBLEMS is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _stack(*trees):
            return jax.tree_util.tree_map(
                lambda *lanes: jnp.stack(lanes), *trees
            )

        _STACK_PROBLEMS = _stack
    stacked = _STACK_PROBLEMS(*dps)
    if len(_STACK_CACHE) >= _STACK_CACHE_CAP:
        _STACK_CACHE.clear()
    _STACK_CACHE[key] = (dps, stacked)
    return stacked


def _dispatch_stacked_group(
    idxs, specs, envs, dps, config, deadline, mesh_sv, sup, group_key=None
):
    """ONE stacked launch for a shape-matched pool group: stack the
    device-resident problems along a leading pool axis (device-side
    copies, no host transfer), run the vmapped round, dispatch the
    stacked compaction + verification, and hand back per-pool finish
    callables that share the two fetched buffers."""
    import jax.numpy as jnp
    import numpy as _np

    from armada_tpu.core.watchdog import run_with_deadline
    from armada_tpu.models import verify as _verify
    from armada_tpu.models.fair_scheduler import schedule_round_stacked
    from armada_tpu.models.problem import begin_decode_stacked
    from armada_tpu.ops.trace import recorder as _trace

    trace = _trace()
    kk = envs[idxs[0]][0]
    ctxs = [specs[i].ctx for i in idxs]
    stacked = _stack_problems(group_key, [dps[i] for i in idxs])
    with trace.span("kernel_dispatch", stacked=len(idxs)):
        result = schedule_round_stacked(stacked, **kk)
    _note_round_devices(result)
    verify_armed = _verify.verify_enabled()
    with trace.span("decode_dispatch", stacked=len(idxs)):
        fins = begin_decode_stacked(result, ctxs)
    if fins is None:
        # No device result to stack-decode (host-array backend): solo
        # dispatch per lane -- correctness over amortization.
        return [
            _dispatch_one(
                specs[i].problem, specs[i].ctx, config,
                SchedulingProblem(*(a[j] for a in stacked)),
                specs[i].host_problem, envs[i],
            )
            for j, i in enumerate(idxs)
        ]
    ver_buf = None
    if verify_armed:
        with trace.span("verify_dispatch", stacked=len(idxs)):
            ver_buf = _verify.dispatch_verify_stacked(
                stacked, result, fins[0].dispatched[0], ctxs
            )
    vbox: dict = {}

    def ver_rows() -> _np.ndarray:
        if "v" not in vbox:
            arr = _np.asarray(ver_buf)
            from armada_tpu.models.xfer import TRANSFER_STATS

            TRANSFER_STATS.count_down(arr.nbytes)
            vbox["v"] = arr
        return vbox["v"]

    from armada_tpu.models.problem import lane_slice

    out = []
    for j, i in enumerate(idxs):
        s = specs[i]
        ctx = s.ctx
        pool = getattr(ctx, "pool", "")
        explain_armed = envs[i][4]
        fin = fins[j]
        # Lane views resolve LAZILY through one jitted slice program
        # (problem.lane_slice): eager per-field slices cost ~0.6ms of XLA
        # dispatch each on CPU, and most rounds never touch the lanes
        # (decode rides the compact tuple; dp lanes only serve the
        # rollback / verify-rerun / explain paths).
        lane_result = lambda j=j: lane_slice(result, j)  # noqa: E731
        ver_check = None
        if ver_buf is not None:

            def ver_check(j=j, ctx=ctx, pool=pool, fin=fin):
                fin.fetch()  # this pool's compact row (one shared transfer)
                with _trace().span("verify_fetch", stacked=True):
                    _verify.verdict_of(ver_rows()[j], ctx, pool=pool)

        exp_dispatched = None
        if explain_armed:
            from armada_tpu.models import explain as _explain

            with trace.span("explain_dispatch", pool=pool):
                exp_dispatched = _explain.dispatch_explain(
                    lane_slice(stacked, j), lane_result(), ctx,
                )
        handle = _RoundHandle(
            (lambda j=j: lane_slice(stacked, j)),
            ctx, config, kk, lane_result, fin, ver_check, exp_dispatched,
            explain_armed, verify_armed, pool,
        )
        envs[i][1].run_pending()  # this spec's shadow thunks ride the stack

        def finish(handle=handle, i=i, s=s):
            from armada_tpu.models.verify import RoundVerificationError

            def ladder(e):
                kk_i, shadow_i, _, _, explain_i = envs[i]
                return _failover_ladder(
                    e, problem=s.problem, ctx=s.ctx, config=config,
                    kernel_kwargs=kk_i, shadow=shadow_i,
                    explain_armed=explain_i, host_problem=s.host_problem,
                    mesh_sv=mesh_sv, sup=sup, deadline=deadline,
                )

            if deadline <= 0:
                # inline discipline (run_round_on_device's no-watchdog
                # branch): only a verification failure walks the ladder --
                # a host/XLA error out of the fetch is a code bug and
                # propagates untouched
                try:
                    return _finish_body(handle)
                except RoundVerificationError as e:
                    return ladder(e)
            try:
                res = run_with_deadline(
                    lambda: _finish_body(handle), deadline,
                    what="stacked round fetch",
                )
                sup.record_success()
                return res
            except _ladder_errors() as e:
                return ladder(e)

        out.append(finish)
    return out


def _quarantine_strike(mesh_sv, sup, reason: str, mesh=None) -> None:
    """Record one verification strike against the devices that produced
    the bad round (scheduler/quarantine.DeviceQuarantine).  Safe to touch
    jax here: a VERIFICATION failure means the backend answered (wrongly)
    -- it is not wedged, unlike the timeout path, which never strikes."""
    from armada_tpu.scheduler.quarantine import device_quarantine

    devices: list = []
    try:
        if mesh is None and mesh_sv.enabled() and not sup.degraded:
            mesh = mesh_sv.serving_mesh()
        if mesh is not None:
            devices = [str(d) for d in mesh.devices.flat]
        else:
            import jax

            devices = [str(jax.devices()[0])]
    except Exception:  # device enumeration must never mask the failover
        devices = ["default-device"]
    device_quarantine().record_strikes(devices, reason)


def _run_round_on_mesh(
    host_problem, ctx, config, kernel_kwargs, shadow, mesh, explain_armed=False
):
    """Re-run the SAME round sharded over a (smaller) mesh from host
    tables -- the degrade-ladder rung between full mesh and CPU failover.
    The device caches were reset by the ladder's hooks; this path pays one
    full sharded upload, and the next cycle's cache apply re-shards too."""
    from armada_tpu.parallel.mesh import shard_problem

    return _round_body(
        shard_problem(host_problem, mesh), ctx, config, kernel_kwargs, shadow,
        explain_armed,
    )


def _run_round_cpu_failover(
    host_problem, ctx, config, kernel_kwargs, shadow, explain_armed=False
):
    """Re-run the SAME round on the explicit XLA:CPU backend from host
    tables.  The device caches were reset by the supervisor's failure hooks
    (stale device state must never be consulted again); this path re-uploads
    the full problem to CPU memory -- a memcpy, not a device transfer."""
    import jax
    import numpy as _np

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        dp = SchedulingProblem(
            # lint: allow(mesh-gather) -- explicit CPU failover: the caches
            # were reset, nothing sharded survives; host tables re-upload
            *(jax.device_put(_np.asarray(a), cpu) for a in host_problem)
        )
        return _round_body(
            dp, ctx, config, kernel_kwargs, shadow, explain_armed
        )


class _RoundHandle:
    """Everything a dispatched round's finish phase needs -- the seam the
    pool-parallel cycle splits run_round_on_device at.  `device_problem`
    may be a thunk (stacked lanes slice lazily: the rollback / partial-gang
    paths are the only consumers, and most rounds never take them)."""

    __slots__ = (
        "device_problem", "ctx", "config", "kernel_kwargs", "result",
        "finish", "ver_check", "exp_dispatched", "explain_armed",
        "verify_armed", "pool", "_dp",
    )

    def __init__(
        self, device_problem, ctx, config, kernel_kwargs, result, finish,
        ver_check, exp_dispatched, explain_armed, verify_armed, pool,
    ):
        self.device_problem = device_problem
        self.ctx = ctx
        self.config = config
        self.kernel_kwargs = kernel_kwargs
        self.result = result
        self.finish = finish
        self.ver_check = ver_check
        self.exp_dispatched = exp_dispatched
        self.explain_armed = explain_armed
        self.verify_armed = verify_armed
        self.pool = pool
        self._dp = None

    def dp(self):
        if self._dp is None:
            self._dp = (
                self.device_problem()
                if callable(self.device_problem)
                else self.device_problem
            )
        return self._dp


def _dispatch_body(
    device_problem, ctx, config, kernel_kwargs, shadow, explain_armed=False
) -> _RoundHandle:
    """The round's DISPATCH half: kernel + compaction + verify/explain
    enqueues and the shadow thunks -- everything asynchronous.  Nothing
    here blocks on the device; the blocking waits live in _finish_body,
    which is what lets the pool-parallel cycle fire every pool's dispatch
    before any pool's fetch."""
    from armada_tpu.models import explain as _explain
    from armada_tpu.models import verify as _verify
    from armada_tpu.ops.trace import recorder as _trace

    trace = _trace()
    pool = getattr(ctx, "pool", "")
    with trace.span("kernel_dispatch"):
        result = schedule_round(device_problem, **kernel_kwargs)
    _note_round_devices(result)
    # round_corrupt drill (core/faults): device-side header/lane corruption
    # injected BEFORE the compact dispatch, so both the decode transfer and
    # the verification pass see the corrupted state -- exactly like a real
    # silently-wrong device result.  One dict lookup when unarmed.
    result = _verify.maybe_corrupt_result(result)
    verify_armed = _verify.verify_enabled()
    # Overlapped decode (begin_decode): the compaction + its device->host
    # copy are enqueued behind the kernel with no host sync in between, so
    # the transfer streams as soon as the kernel finishes -- a blocking
    # decode_result here pays one extra sync + fetch round trip per round
    # (its cost on this host is not measured -- ROADMAP S5).
    with trace.span("decode_dispatch"):
        finish = begin_decode(result, ctx)
    # Round verification (models/verify.py): dispatched BEHIND the decode
    # compaction so the invariant pass and its device->host copy ride the
    # decode shadow; the verdict is checked between the compact FETCH and
    # the host decode, so a corrupted round never reaches decode's loops
    # (RoundVerificationError -> run_round_on_device's failover ladder).
    # ONE extra transfer per verified round.
    ver_check = None
    if verify_armed:
        with trace.span("verify_dispatch"):
            ver_dispatched = _verify.dispatch_verify(
                device_problem, result, finish.dispatched, ctx
            )
        if ver_dispatched is not None:

            def ver_check():
                finish.fetch()  # blocking compact fetch (stashes raw bytes)
                with _trace().span("verify_fetch"):
                    _verify.finish_verify(ver_dispatched, ctx, pool=pool)

    # Explain pass (models/explain.py): dispatched BEHIND the decode
    # compaction so its device compute and device->host copy ride the
    # decode shadow; the blocking fetch happens after the outcome, off the
    # decision path.  ONE extra transfer, only on explain rounds.
    exp_dispatched = None
    if explain_armed:
        with trace.span("explain_dispatch"):
            exp_dispatched = _explain.dispatch_explain(
                device_problem, result, ctx
            )
    with trace.span("shadow"):
        shadow.run_pending()
    return _RoundHandle(
        device_problem, ctx, config, kernel_kwargs, result, finish,
        ver_check, exp_dispatched, explain_armed, verify_armed, pool,
    )


def _finish_body(h: _RoundHandle):
    """The round's FETCH half: the blocking verify/compact waits, decode,
    the gang-txn rollback loop, and (on its cadence) the explain fetch."""
    import jax.numpy as jnp
    import numpy as _np

    from armada_tpu.models import explain as _explain
    from armada_tpu.models import verify as _verify
    from armada_tpu.ops.trace import recorder as _trace

    trace = _trace()
    ctx, config, kernel_kwargs = h.ctx, h.config, h.kernel_kwargs
    pool = h.pool
    # Stacked lanes hand the result as a THUNK (one jitted lane slice);
    # it stays unresolved unless the rollback loop replaces it or a
    # consumer needs arrays -- steady rounds with collect_stats off never
    # pay the slice.  Callers that read the returned result resolve it
    # with callable() (collect_round_stats' contract).
    result = h.result
    exp_dispatched = h.exp_dispatched
    # The fetch span is where kernel + transfer latency surfaces: the
    # dispatch spans above are async enqueues, this is the blocking wait.
    with trace.span("fetch_decode"):
        if h.ver_check is not None:
            h.ver_check()
        outcome = h.finish()
    # The round span carries the trips of the placement loop and how many of
    # them gathered the whole skip window again.  Values ride the compact
    # decode buffer -- no extra transfer.
    if outcome.kernel_iters:
        trace.annotate(
            kernel_iters=outcome.kernel_iters,
            window_refills=outcome.window_refills,
        )

    # Gang-txn rollback (nodedb.go:347 ScheduleManyWithTxn: a gang is one txn,
    # all-or-nothing): if a split gang's sibling placed but another sub-gang
    # failed on runtime contention, decode unwound the sibling -- but evictions
    # its placement caused are still in the round state.  Re-run the same
    # compiled kernel with the doomed gangs invalidated, so the outcome equals
    # a round in which they were never attempted; the re-decode reports the
    # doomed members failed (invalid gangs start at g_state=2).  Each re-run
    # kills >=1 declared gang, so this terminates; the attempt cap only bounds
    # latency in adversarial rounds (beyond it the unwind itself is still
    # applied, so no half-gang ever leases either way).
    attempts = 0
    while attempts < 4:
        kill: list = []
        if outcome.unwound_groups:
            # Group tags live only on multi-member units under the vectorized
            # representation (same rule as decode's unwind scan) -- and slab
            # contexts have G ~ backlog slots, so never range-scan
            # num_real_gangs unless gangs are list-represented.
            tagged = (
                ctx.gang_members_over.keys()
                if ctx.gang_members is None
                else range(ctx.num_real_gangs)
            )
            kill.extend(
                gi for gi in tagged
                if ctx.gang_group[gi] in outcome.unwound_groups
            )
        # Running-gang fate-sharing (preempting_queue_scheduler.go:345-399):
        # the reference evicts the REMAINS of partially evicted gangs and
        # re-schedules each evicted gang as one all-or-nothing unit with
        # per-member node pins, so a running gang either keeps every member
        # or loses every member.  Our kernel gives each preemptible run an
        # independent evictee slot; when a round preempts SOME members of a
        # running gang but retains others, invalidate ALL the gang's evictee
        # slots and re-run -- none can re-place, so the whole gang preempts
        # and its capacity frees for the rest of the round's decisions,
        # exactly like the reference's failed unit (pinned members that lost
        # their node doom the unit).  Golden trace: "Preempted Gang Job"
        # (testdata/golden/, ref simulator_test.go).
        kill.extend(_partial_running_gangs(ctx, h.dp, outcome))
        if not kill:
            break
        attempts += 1
        with trace.span("gang_rerun", attempt=attempts, killed=len(set(kill))):
            device_problem = h.dp()
            g_valid = _np.asarray(device_problem.g_valid).copy()
            g_valid[_np.asarray(sorted(set(kill)), _np.int64)] = False
            device_problem = device_problem._replace(g_valid=jnp.asarray(g_valid))
            h._dp = device_problem
            result = schedule_round(device_problem, **kernel_kwargs)
            fin = begin_decode(result, ctx)
            if h.verify_armed:
                # Every attempt's state is verified between its fetch and
                # its decode -- a corrupted re-run must not steer the
                # rollback loop (or crash its decode) any more than the
                # first attempt may.
                vd = _verify.dispatch_verify(
                    device_problem, result, fin.dispatched, ctx
                )
                if vd is not None:
                    fin.fetch()
                    with trace.span("verify_fetch"):
                        _verify.finish_verify(vd, ctx, pool=pool)
            outcome = fin()
    if attempts and h.explain_armed:
        # Attribution must describe the FINAL (post-rollback) round, so the
        # shadow-dispatched buffer is stale -- re-dispatch ONCE here rather
        # than per re-run attempt (each abandoned dispatch would still pay
        # its O(KxN) pass + async copy).
        if callable(result):
            result = result()
        exp_dispatched = _explain.dispatch_explain(h.dp(), result, ctx)
    if attempts >= 4:
        # Attempt-cap backstop: never report a half-preempted running gang.
        # Force the retained members into the preempted set -- their freed
        # capacity goes unused this cycle (under-scheduling is safe,
        # half-gangs are not).
        _force_preempt_partials(ctx, outcome)
    if exp_dispatched is not None:
        with trace.span("explain_fetch"):
            outcome.explain = _explain.finish_explain(
                exp_dispatched, ctx, outcome
            )
    outcome.pool_totals = ctx.pool_total_atoms
    outcome.queue_axis = {
        "queues": ctx.num_real_queues,
        "queues_padded": ctx.queues_padded,
        "queues_pending": ctx.queues_pending,
    }
    outcome.assemble = ctx.assemble_stats
    return result, outcome


def _round_body(
    device_problem, ctx, config, kernel_kwargs, shadow, explain_armed=False
):
    """One complete round against already-device-resident tensors: kernel,
    overlapped decode + shadow work, the gang-txn rollback loop, and (on
    its cadence) the explain pass -- dispatch and finish back to back (the
    serial path; the pool-parallel cycle interleaves the halves)."""
    return _finish_body(
        _dispatch_body(
            device_problem, ctx, config, kernel_kwargs, shadow, explain_armed
        )
    )


def _iter_partial_gangs(ctx, outcome):
    """Yield (run_indices, retained_job_ids) for each running gang this
    round preempted only PARTIALLY (some members kept, some lost) -- the one
    predicate both the cascade trigger and the attempt-cap backstop share.

    ctx.running_gangs may be a zero-arg callable (the incremental assembles
    build the mapping lazily: most cycles preempt nothing, and an eager
    per-member locate on the slab hot path would erode the TPU cycle);
    materialization is deferred until a round actually preempted something.
    """
    if not outcome.preempted or not ctx.running_gangs:
        return
    rg = ctx.running_gangs
    if callable(rg):
        rg = ctx.running_gangs = rg()  # cache across re-runs
        if not rg:
            return
    pre = set(outcome.preempted)
    for ris in rg.values():
        retained = [
            jid
            for ri in ris
            if (jid := ctx.run_job_id(int(ri))) not in pre
        ]
        if retained and len(retained) < len(ris):
            yield ris, retained


def _partial_running_gangs(ctx, dp_thunk, outcome) -> list:
    """Evictee-slot gang indices to invalidate for the cascade re-run.
    `dp_thunk` resolves the device problem lazily -- stacked lanes slice on
    demand, and most rounds preempt nothing, so the slice never happens."""
    import numpy as _np

    run_gang = None
    kill: list = []
    for ris, _retained in _iter_partial_gangs(ctx, outcome):
        if run_gang is None:
            run_gang = _np.asarray(dp_thunk().run_gang)
        for ri in ris:
            gi = int(run_gang[ri])
            if gi >= 0:
                kill.append(gi)
    return kill


def _force_preempt_partials(ctx, outcome) -> None:
    for _ris, retained in _iter_partial_gangs(ctx, outcome):
        for jid in retained:
            outcome.preempted.append(jid)
            if jid in outcome.rescheduled:
                outcome.rescheduled.remove(jid)


def collect_round_stats(result, problem, ctx, config, outcome) -> None:
    """Attach per-queue share stats (and indicative shares) to the outcome --
    an extra device->host transfer + host-side DRF recompute, so callers skip
    it when neither metrics nor reports consume it.  `result` may be a
    zero-arg thunk (a stacked round's lazy lane slice): resolved here, the
    one consumer that actually reads the arrays."""
    if callable(result):
        result = result()
    from armada_tpu.models.problem import queue_stats_from_result
    from armada_tpu.ops.trace import recorder as _trace

    with _trace().span("queue_stats", queues=ctx.num_real_queues):
        outcome.queue_stats, iterations = queue_stats_from_result(result, problem, ctx)
    outcome.queue_axis["fair_share_iterations"] = iterations
    if config.indicative_share_base_priorities:
        from armada_tpu.ops.fairness import theoretical_share

        # config parsing rejects non-positive priorities up front
        outcome.indicative_shares = {
            p: theoretical_share(problem.q_weight, problem.q_cds, float(p))
            for p in config.indicative_share_base_priorities
        }


def run_scheduling_round(
    config,
    *,
    pool,
    nodes,
    queues,
    queued_jobs,
    running=(),
    collect_stats=True,
    bid_price_of=None,
    away_mode=False,
    global_tokens=None,
    queue_tokens=None,
    banned_nodes=None,
    queue_penalty=None,
):
    """Convenience host API: build the dense problem, run the jitted round on
    device, decode back to ids.  Equivalent of one SchedulingAlgo.Schedule call for
    one pool (scheduling_algo.go SchedulePool:574)."""
    problem, ctx = build_problem(
        config,
        pool=pool,
        nodes=nodes,
        queues=queues,
        queued_jobs=queued_jobs,
        running=running,
        bid_price_of=bid_price_of,
        away_mode=away_mode,
        global_tokens=global_tokens,
        queue_tokens=queue_tokens,
        banned_nodes=banned_nodes,
        queue_penalty=queue_penalty,
    )
    result, outcome = run_round_on_device(
        # away rounds: attribution is a HOME-round signal (the away apply
        # discards outcome.explain) -- don't tick the host pool's cadence
        problem, ctx, config, explain_enabled=not away_mode
    )
    if collect_stats:
        collect_round_stats(result, problem, ctx, config, outcome)
    return outcome


__all__ = [
    "run_scheduling_round",
    "run_round_on_device",
    "dispatch_round_on_device",
    "dispatch_pool_rounds",
    "PoolRoundSpec",
    "collect_round_stats",
    "SchedulingProblem",
    "HostContext",
    "build_problem",
    "begin_decode",
    "decode_result",
    "RoundOutcome",
    "schedule_round",
    "RoundResult",
]
