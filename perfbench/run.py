#!/usr/bin/env python3
"""perfbench: one run of one cell of `BENCHMARK.json` on the served path.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device`, `breakdown` when traced, and last `checks`:
every number `correct` compared, beside its limit); earlier lines
are diagnostics, and the per-cycle record goes to `perfbench_out/`.  With no
TPU it exits 2 and prints no result.  See README.md beside this file.
"""

from __future__ import annotations

import os
import sys
import time

_T0 = "PERFBENCH_T0"

if __name__ == "__main__" and os.environ.get("PYTHONHASHSEED") != "0":
    # One hash seed for every run: the mirror and the feed are dicts keyed by
    # job-id strings, and a randomised string hash changes their layout, and
    # with it the host's work, from process to process.  Nothing is imported
    # before this point; set-up time is counted from the first process.
    env = dict(os.environ, PYTHONHASHSEED="0")
    env.setdefault(_T0, repr(time.time()))
    os.execve(sys.executable, [sys.executable] + sys.argv, env)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import argparse
    import json

    t0 = float(os.environ.get(_T0) or time.time())
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--benchmark",
        default=os.path.join(ROOT, "BENCHMARK.json"),
        help="the BENCHMARK.json whose cells, files and paths to use (tests point it at a copy)",
    )
    ap.add_argument("--out", help="directory for the per-cycle record (default <root>/perfbench_out)")
    ap.add_argument(
        "--allow-cpu",
        action="store_true",
        help="for tests: run without a TPU, name the device cpu and report counts only",
    )
    ap.add_argument("--keep-trace", action="store_true", help="also write the reduced trace's input")
    args = ap.parse_args(argv)
    if args.trace:
        # the program's existing bridge: its spans become profiler annotations
        os.environ["ARMADA_TRACE_JAX"] = "1"
    sys.path.insert(0, ROOT)
    from perfbench.harness.cell import CellError
    from perfbench.harness.runner import run_cell

    try:
        code, result = run_cell(args, t0)
    except CellError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    if result is not None:
        print(json.dumps(result), flush=True)
        # the run's last words on standard error: each number compared, beside its limit
        for name, c in result["checks"].items():
            print(f"perfbench check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
        sys.stderr.flush()
    return code


if __name__ == "__main__":
    sys.exit(main())
