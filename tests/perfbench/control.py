#!/usr/bin/env python3
"""The controls of a full fleet's two rules, at a cell's own size: one run of
a cell as `perfbench/run.py` makes it, then the run's own record again with
ONE thing broken, twice:

  - invariant 9: the recorded answers through the plain checker, where a
    round that gave up loses one of its leases, so it gave up while that job
    still fitted where it had been put;
  - stationarity: `runner.drift` over the run's FIRST cycles, as many as the
    window holds and no more than `lifetime_cycles`: the fleet is still
    filling and finishes nothing, which is a window that does not stand still.

    python3 tests/perfbench/control.py --workload <cell> --seed 7 --seconds 35

It takes run.py's arguments, prints run.py's result line, and then one line
more, `perfbench control {...}`: how many violations the honest record gives
(0 in a `correct` run), what the doctored one gives (it has to be reported,
by invariant 9 and by nothing else: the round doctored is the LAST one that
gave up, so no later round's counts are there to disagree), and both drifts
of the early window beside their limits (one has to be over).  Exit code 0
only if the run is `correct` and every control comes out not correct.
`tests/perfbench/test_perfbench_run.py` runs it at tiny size, on the tests'
full fleet; on the chip it runs at the size of whatever cell `--workload`
names, once one whose rounds give up is declared (perfbench/README.md, "A
full fleet"); the benchmark's own runs never run it.
"""

from __future__ import annotations

import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
STILL_FITS = "while a job still fits"


def replay(run, cycles) -> list:
    """The violations a fresh checker finds in `cycles` (records as
    `runner.Run.cycle` makes them)."""
    from perfbench.harness.checker import Checker

    old = run.checker
    checker = Checker(
        run.world, cap=old.cap, queue_cap=old.queue_cap, lookback=old.lookback,
        priority_classes=run.cell.scheduling()["priorityClasses"],
    )
    for n, c in enumerate(cycles):
        checker.cycle(n, c)
    return checker.violations


def lease_removed(run) -> tuple:
    """(the doctored cycles, the round doctored or None): the last round that
    gave up, without the first lease of its response."""
    cycles = [copy.copy(c) for c in run.cycles]
    for n in range(len(cycles) - 1, -1, -1):
        if run.checker.gave_up(cycles[n]) and cycles[n]["leases"]:
            cycles[n]["leases"] = cycles[n]["leases"][1:]
            return cycles, n
    return cycles, None


def early_window(run) -> dict:
    """`runner.drift` over the run's first cycles, before the fleet finishes
    anything: each count's drift beside the limit the mix gives a window of
    that length, and whether the rule reports it."""
    from perfbench.harness.runner import drift

    steps = min(run.lifetime, len(run.cycles) - run.first_window_k - 1)
    checks, problem = drift(run.cycles[: steps + 1], run.traffic)
    return {"cycles": steps + 1, "checks": checks, "reported": problem is not None}


def control(run) -> dict:
    honest = replay(run, run.cycles)
    cycles, n = lease_removed(run)
    doctored = replay(run, cycles) if n is not None else []
    new = [v for v in doctored if v not in honest]
    return {
        "early_window": early_window(run),
        "honest_violations": len(honest),
        "round_doctored": n,
        "rounds_that_gave_up": sum(run.checker.gave_up(c) for c in run.cycles),
        "doctored_violations": len(new),
        "reported_by_invariant_9": bool(new) and all(STILL_FITS in v for v in new),
        "first": new[0] if new else None,
    }


def main(argv=None) -> int:
    sys.path.insert(0, ROOT)
    from perfbench import run as cli
    from perfbench.harness import runner

    kept, write_record = {}, runner.write_record

    def keeping(run, summary, *rest):
        kept.update(run=run, correct=summary["result"]["correct"])
        return write_record(run, summary, *rest)

    runner.write_record = keeping
    try:
        code = cli.main(argv)
    finally:
        runner.write_record = write_record
    if code != 0 or "run" not in kept:
        return code or 1
    out = dict(control(kept["run"]), run_correct=kept["correct"])
    print("perfbench control " + json.dumps(out), flush=True)
    sound = out["run_correct"] and not out["honest_violations"]
    return 0 if sound and out["reported_by_invariant_9"] and out["early_window"]["reported"] else 1


if __name__ == "__main__":
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.execve(sys.executable, [sys.executable] + sys.argv, dict(os.environ, PYTHONHASHSEED="0"))
    sys.exit(main())
