"""The steady-cycle pipeline switch.

The shadow-pipelined cycle (round 7) hides decision-independent host work
behind the device round: while the kernel and its result transfer are in
flight, the drivers run (a) the previous cycle's decision-dependent but
problem-independent bookkeeping and (b) the next cycle's decision-
independent feed -- proto->Job conversion, submit-side table inserts, and
the slab upload of new-submit rows (IncrementalBuilder.prefetch_content).
Decisions are bit-identical either way: the pipeline only reorders work
that neither reads the round's output nor feeds its problem -- the
soundness boundary pinned by tests/test_pipeline.py.

``ARMADA_PIPELINE=0`` is the escape hatch (A/B measurement, bisection):
every pipelined call site degrades to the sequential order.  The env var is
read per call so a test can flip it with monkeypatch; ``serve
--no-pipeline`` sets it process-wide.
"""

from __future__ import annotations

import os


def pipeline_enabled() -> bool:
    """True unless ARMADA_PIPELINE=0: shadow-pipeline the steady cycle."""
    return os.environ.get("ARMADA_PIPELINE", "1") != "0"


def pool_parallel_enabled() -> bool:
    """Pool-parallel serving armed (round 17)?  ``ARMADA_POOL_PARALLEL=1``
    / ``serve --pool-parallel`` restructures the multi-pool cycle into
    dispatch/fetch phases (pool B's upload + kernel dispatch fire while
    pool A's fetch is in flight) and stacks shape-matched small pools into
    one kernel launch.  Default OFF in the library/tests -- the serial
    per-pool loop -- because arming it is a *throughput* choice; decisions
    are bit-identical either way, but only when the cycle's pools are
    certified independent (scheduler/algo.py falls back to the serial
    order per-cycle whenever they are not: shared queued candidates,
    armed rate limiters, market pools).  Read per call so tests flip it
    with monkeypatch (the ARMADA_PIPELINE discipline)."""
    return os.environ.get("ARMADA_POOL_PARALLEL", "0") not in ("0", "")


def prefetch_worthwhile() -> bool:
    """Whether the slab content prefetch pays for itself.

    The prefetch trades an extra device scatter pass for moving its upload
    off the round's critical path.  On an accelerator the scatter runs on
    the device and the H2D transfer overlaps host work (what the transfer
    costs on this host is not measured -- ROADMAP S5); on XLA:CPU the
    "device" IS the host -- the extra pass costs real milliseconds per
    cycle (measured ~96ms at 200k jobs, round 7) with no transfer to hide.
    Default: accelerator backends only.  ARMADA_PIPELINE_PREFETCH=1/0 overrides
    (tests pin the scatter path on CPU with 1; 0 isolates the prefetch in
    a TPU A/B)."""
    env = os.environ.get("ARMADA_PIPELINE_PREFETCH")
    if env is not None:
        return env != "0"
    from armada_tpu.core.watchdog import supervisor

    if supervisor().degraded:
        # Device loss (core/watchdog): data lives on XLA:CPU regardless of
        # what backend jax reports, so the scatter pass is pure host cost
        # with no transfer to hide -- same economics as the cpu branch.
        return False
    import jax

    return jax.default_backend() != "cpu"
