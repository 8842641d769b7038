#!/usr/bin/env python3
"""Compile seconds and device memory per program, at the headline shapes.

Builds the 1M x 50k x 64-queue world on an IncrementalBuilder (the served
path's state, so verify/explain get a real decode context) and runs each
device program of the cycle ONCE cold and once warm, in this order: the
full slab upload, the round, compaction, verify, explain, the
`scatter_content` prefetch and the slab delta scatter of the next cycle,
and -- toy shapes -- the vmapped stacked round.

For each it prints the XLA backend-compile seconds spent inside the first
call (jax.monitoring), the first and second call's wall time, and the
device's `memory_stats()` afterwards.  `peak_bytes_in_use` is a high-water
mark over the process, so read it cumulatively in the order above.

Owns the chip in one process and refuses to run without an accelerator
unless JAX_PLATFORMS=cpu is set explicitly (downscale with --jobs/--nodes
there; a CPU run's seconds are not device numbers).

    python tools/chip_programs.py [--jobs N --nodes N] [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--jobs", type=int, default=1_000_000)
    ap.add_argument("--nodes", type=int, default=50_000)
    ap.add_argument("--queues", type=int, default=64)
    ap.add_argument("--burst", type=int, default=1_000)
    ap.add_argument("--out", help="write the rows as JSON here")
    args = ap.parse_args(argv)

    from bench import bench_devices
    from chip_smoke import CompileLog

    devices = bench_devices()  # exits 2 without an accelerator
    dev0 = devices[0]

    from armada_tpu.core.platform import enable_compilation_cache
    from armada_tpu.core.types import RunningJob
    from armada_tpu.models import begin_decode, explain, verify
    from armada_tpu.models.fair_scheduler import (
        schedule_round,
        schedule_round_stacked,
    )
    from armada_tpu.models.incremental import IncrementalBuilder
    from armada_tpu.models.problem import SchedulingProblem
    from armada_tpu.models.slab import DeviceDeltaCache
    from armada_tpu.models.synthetic import synthetic_problem, synthetic_world

    enable_compilation_cache()
    compiles = CompileLog().compiles
    rows: list = []

    def measure(name, fn, repeat=True):
        """fn() must end in a host fetch (the repo's fetch-not-barrier
        rule); returns the first call's value."""
        mark = len(compiles)
        t0 = time.perf_counter()
        value = fn()
        first = time.perf_counter() - t0
        compiled = compiles[mark:]
        second = None
        if repeat:
            t0 = time.perf_counter()
            fn()
            second = time.perf_counter() - t0
        stats = dev0.memory_stats() or {}
        row = {
            "program": name,
            "compile_s": round(sum(s for _, s in compiled), 3),
            "compiled": [(str(n), round(s, 3)) for n, s in compiled],
            "first_call_s": round(first, 3),
            "second_call_s": None if second is None else round(second, 4),
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
        return value

    t0 = time.perf_counter()
    config, nodes, queues, specs, running, spec_factory = synthetic_world(
        num_nodes=args.nodes,
        num_jobs=args.jobs,
        num_queues=args.queues,
        num_runs=args.nodes // 2,
        seed=7,
        shape_bucket=max(8192, 4 * args.burst),
    )
    builder = IncrementalBuilder(config, "default", queues)
    builder.set_nodes(nodes)
    builder.submit_many(specs)
    for r in running:
        builder.lease(r)
    spec_of = {s.id: s for s in specs}
    print(
        f"chip_programs: {dev0.device_kind} x{len(devices)} "
        f"({dev0.platform}); world {args.jobs} x {args.nodes} x "
        f"{args.queues} loaded in {time.perf_counter() - t0:.1f}s",
        file=sys.stderr,
    )

    devcache = DeviceDeltaCache()
    bundle, ctx = builder.assemble_delta()
    kw = dict(
        num_levels=len(ctx.ladder) + 2,
        max_slots=ctx.max_slots,
        slot_width=ctx.slot_width,
    )
    dev = measure(
        "slab_full_upload",
        lambda: jax.block_until_ready(devcache.apply(bundle)),  # lint: allow(fetch-not-barrier) -- an upload has nothing to fetch
        repeat=False,
    )

    def run_round():
        result = schedule_round(dev, **kw)
        return result, int(result.scheduled_count), int(result.kernel_iters)

    result, n_sched, trips = measure("round", run_round)
    assert n_sched > 0, "round scheduled nothing"
    print(f"chip_programs: scheduled {n_sched} in {trips} trips", file=sys.stderr)

    def compact():
        fin = begin_decode(result, ctx)
        fin.fetch()
        return fin

    fin = measure("compact_result", compact)

    def run_verify():
        return verify.finish_verify(
            verify.dispatch_verify(dev, result, fin.dispatched, ctx), ctx
        )

    verdict = measure("verify", run_verify)
    assert verdict.get("ok", True), verdict

    def run_explain():
        return explain.finish_explain(
            explain.dispatch_explain(dev, result, ctx), ctx
        )

    measure("explain", run_explain)

    # Apply the decisions and the next cycle's submits like the served cycle
    # does, so the two scatter programs see a real delta.
    outcome = fin()
    fresh = spec_factory(args.burst, 100.0)
    builder.submit_many(fresh)
    measure(
        "scatter_content",
        lambda: (
            builder.prefetch_content(devcache),
            np.asarray(devcache._prev.g_valid[:1]),
        ),
        repeat=False,
    )
    builder.remove_many(outcome.scheduled.keys())
    builder.lease_many(
        [
            RunningJob(job=spec_of[jid], node_id=nid)
            for jid, nid in outcome.scheduled.items()
        ]
    )
    bundle2, _ = builder.assemble_delta()

    def scatter():
        d = devcache.apply(bundle2)
        np.asarray(d.g_valid[:1])
        return d

    measure("slab_delta_scatter", scatter, repeat=False)

    # Toy shapes: the vmapped stacked round (pool-parallel serving).
    toy, meta = synthetic_problem(
        num_nodes=64, num_gangs=256, num_queues=8, num_runs=32,
        global_burst=64, perq_burst=32, seed=3,
    )
    stacked = SchedulingProblem(
        *(jnp.stack([jnp.asarray(a)] * 4) for a in toy)
    )
    toy_kw = dict(
        num_levels=meta["num_levels"],
        max_slots=meta["max_slots"],
        slot_width=meta["slot_width"],
    )

    def run_stacked():
        out = schedule_round_stacked(stacked, **toy_kw)
        return np.asarray(out.scheduled_count)

    lanes = measure("round_stacked_toy_x4", run_stacked)
    assert (lanes > 0).all(), lanes

    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(
                {
                    "device": {
                        "platform": dev0.platform,
                        "kind": dev0.device_kind,
                        "count": len(devices),
                    },
                    "world": vars(args),
                    "rows": rows,
                },
                f,
                indent=1,
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
