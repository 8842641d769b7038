"""Standing soak drill: sustained open-loop traffic + streaming SLOs.

Drives the full in-process serving stack (armada_tpu/loadgen/soak.py) for a
wall-clock window at a target event rate, optionally arming an ARMADA_FAULT
site mid-window so failover is measured as a latency distribution under
load.  Prints exactly ONE JSON line (same contract as bench.py); exit 0
when the run's invariants held (no dropped/double-leased jobs, fault fired
and re-promoted when configured).

    python tools/soak.py --window 120 --rate 500
    python tools/soak.py --window 60 --rate 200 --fault device_round:hang

Env downscale (CPU hosts): ARMADA_SOAK_WINDOW_S, ARMADA_SOAK_RATE,
ARMADA_SOAK_NODES, ARMADA_SOAK_QUEUES, ARMADA_SOAK_DSN (external postgres
for the scheduler DB, through the pgwire driver).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--window", type=float, default=None, help="soak window seconds")
    ap.add_argument("--rate", type=float, default=None, help="target events/s")
    ap.add_argument(
        "--process",
        choices=("poisson", "bursty", "ramp"),
        default="poisson",
        help="arrival process shape",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--nodes", type=int, default=None)
    ap.add_argument("--queues", type=int, default=None)
    ap.add_argument(
        "--fault",
        default=None,
        help="ARMADA_FAULT entry to arm mid-soak, e.g. device_round:hang",
    )
    ap.add_argument(
        "--fault-at",
        type=float,
        default=0.5,
        help="when to arm the fault, as a fraction of the window",
    )
    ap.add_argument(
        "--watchdog-s",
        type=float,
        default=5.0,
        help="round deadline while a fault is armed",
    )
    ap.add_argument(
        "--crash",
        nargs="?",
        const=0.5,
        type=float,
        default=None,
        metavar="FRAC",
        help="mid-soak kill/restart leg: checkpoint, wipe the materialized "
        "store, rebuild from snapshot + log-suffix replay at FRAC of the "
        "window (default 0.5); RTO lands in restart_recovery_s",
    )
    ap.add_argument(
        "--ingest-shards",
        type=int,
        default=None,
        dest="ingest_shards",
        help="partition-parallel ingestion width (ingest/shards.py; sets "
        "ARMADA_INGEST_SHARDS for the window incl. the fault/crash legs "
        "via the drill's env save/restore); default: inherit the "
        "environment (1 = serial)",
    )
    ap.add_argument(
        "--store-shards",
        type=int,
        default=None,
        dest="store_shards",
        help="sharded materialized store width (ingest/storeunion.py; sets "
        "ARMADA_STORE_SHARDS for the window; the ingest width rounds up to "
        "a multiple); default: inherit the environment (1 = one writer)",
    )
    ap.add_argument(
        "--json",
        action="store_true",
        help="JSON-line output (the default; kept for bench.py symmetry)",
    )
    args = ap.parse_args()
    if args.ingest_shards is not None:
        os.environ["ARMADA_INGEST_SHARDS"] = str(args.ingest_shards)
    if args.store_shards is not None:
        os.environ["ARMADA_STORE_SHARDS"] = str(args.store_shards)

    # Tests force CPU; a standalone run uses whatever backend is healthy.
    from armada_tpu.loadgen.soak import SoakConfig, run_soak_cli

    overrides = {}
    if args.window is not None:
        overrides["window_s"] = args.window
    if args.rate is not None:
        overrides["target_eps"] = args.rate
    if args.nodes is not None:
        overrides["num_nodes"] = args.nodes
    if args.queues is not None:
        overrides["num_queues"] = args.queues
    report = run_soak_cli(
        SoakConfig.from_env(
            process=args.process,
            seed=args.seed,
            fault=args.fault,
            fault_at_frac=args.fault_at,
            watchdog_s=args.watchdog_s,
            crash_at_frac=args.crash,
            **overrides,
        )
    )
    print(json.dumps(report, default=float))
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except Exception as e:  # one-JSON-line contract, like bench.py
        import traceback

        traceback.print_exc()
        print(
            json.dumps(
                {"tool": "soak", "ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
            )
        )
        sys.exit(2)
