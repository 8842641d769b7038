#!/usr/bin/env python3
"""The controls of the checker's newer rules, at a cell's own size: one run of
a cell as `perfbench/run.py` makes it, then the run's own record again with
ONE thing broken, once a rule the cell's rounds exercise:

  - invariant 9: the recorded answers through the plain checker, where a
    round that gave up loses one of its leases, so it gave up while that job
    still fitted where it had been put;
  - stationarity: `runner.drift` over the run's FIRST cycles, as many as the
    window holds and no more than `lifetime_cycles`: the fleet is still
    filling and finishes nothing, which is a window that does not stand still;
  - invariant 10: the last round that leased a gang loses ONE member's lease
    (the gang was leased in part);
  - invariant 11: in the last round that leased a job with a selector or a
    toleration, one such lease names a node of a type that does not admit
    the job (a GPU member on a node without the label);
  - invariant 12: the last round that preempted a gang's members preempts
    one fewer (the gang was preempted in part).
  Each of the three is replayed up to the round doctored and no further, so
  that no later round's counts are there to disagree: the doctored record has
  to be reported by that invariant and by no other.

    python3 tests/perfbench/control.py --workload <cell> --seed 7 --seconds 35

It takes run.py's arguments, prints run.py's result line, and then one line
more, `perfbench control {...}`: how many violations the honest record gives
(0 in a `correct` run), what the doctored one gives (it has to be reported,
by invariant 9 and by nothing else: the round doctored is the LAST one that
gave up, so no later round's counts are there to disagree), and both drifts
of the early window beside their limits (one has to be over), and under
`gangs` the three newer controls, each with the round doctored (None where no
round of the run did what the control breaks: a cell whose rounds never give
up has no round for invariant 9, one without gangs none for 10 and 12).  Exit
code 0 only if the run is `correct`, the early window is reported, at least
one control found its round, and every one that did comes out not correct,
reported by its own invariant alone.
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

import numpy as np

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
    checks, problem = drift(run.cycles[: steps + 1], run.traffic, whole_gangs=bool(len(run.world.gang_size)))
    return {"cycles": steps + 1, "checks": checks, "reported": problem is not None}


def _last(run, doctor) -> tuple:
    """(the run's cycles up to and with the last round that `doctor` changes,
    that round's number or None): `doctor(record)` returns the record changed,
    or None where the round gives it nothing to break."""
    for n in range(len(run.cycles) - 1, -1, -1):
        changed = doctor(run.cycles[n])
        if changed is not None:
            return run.cycles[:n] + [changed], n
    return [], None


def member_lease_dropped(run) -> tuple:
    """Invariant 10: the last round that leased a gang, without the lease of
    the gang's last member in the response."""
    w = run.world

    def doctor(rec):
        gangs = [k for k, (job_id, _, _) in enumerate(rec["leases"]) if _gang(w, job_id) >= 0]
        return dict(rec, leases=rec["leases"][: gangs[-1]] + rec["leases"][gangs[-1] + 1 :]) if gangs else None

    return _last(run, doctor)


def member_moved_off_label(run) -> tuple:
    """Invariant 11: the last round that leased a job with a node selector
    which some node type does not satisfy (a GPU member), with that lease moved
    to the first node of such a type."""
    w = run.world

    def doctor(rec):
        for k in range(len(rec["leases"]) - 1, -1, -1):
            job_id, _, queue = rec["leases"][k]
            try:
                shape = w.job_shape[w.job_number(job_id)]
            except KeyError:
                continue
            shut = np.flatnonzero(~w.shape_admits[shape])
            if len(shut) and w.shape_selector[shape]:
                node = w.node_ids[int(np.flatnonzero(np.isin(w.node_kind, shut))[0])]
                return dict(rec, leases=rec["leases"][:k] + [(job_id, node, queue)] + rec["leases"][k + 1 :])
        return None

    return _last(run, doctor)


def preempted_member_dropped(run) -> tuple:
    """Invariant 12: the last round that preempted two or more members of one
    gang, preempting one fewer.  A round preempts to make room, so the node of
    the member that now keeps its lease has as a rule been leased to in the
    same round: invariant 3 then finds THAT node over capacity, which is the
    same fault seen from the node's side and is allowed beside 12 (`also`); a
    member whose node the round left alone is taken where there is one."""
    w = run.world
    node_of = {job_id: node_id for rec in run.cycles for job_id, node_id, _ in rec["leases"]}
    also = []

    def doctor(rec):
        gangs = [_gang(w, job_id) for job_id in rec["preempted"]]
        reused = {node_id for _, node_id, _ in rec["leases"]}
        mine = [k for k in range(len(gangs)) if gangs[k] >= 0 and gangs.count(gangs[k]) > 1]
        if not mine:
            return None
        k = next((k for k in mine[::-1] if node_of.get(rec["preempted"][k]) not in reused), mine[-1])
        also.append(f"node {node_of.get(rec['preempted'][k])} holds")
        return dict(rec, preempted=rec["preempted"][:k] + rec["preempted"][k + 1 :])

    return (*_last(run, doctor), also)


def _gang(w, job_id: str) -> int:
    try:
        return int(w.job_gang[w.job_number(job_id)])
    except KeyError:
        return -1  # an initial run, or an id the checker reports


GANG_CONTROLS = {
    "member_lease_dropped": (member_lease_dropped, "leased in part"),
    "member_moved_off_label": (member_moved_off_label, "which does not admit it"),
    "preempted_member_dropped": (preempted_member_dropped, "preempted in part"),
}


def control(run) -> dict:
    honest = replay(run, run.cycles)
    cycles, n = lease_removed(run)
    doctored = replay(run, cycles) if n is not None else []
    new = [v for v in doctored if v not in honest]
    gangs = {}
    for name, (doctor, says) in GANG_CONTROLS.items():
        cycles, at, *also = doctor(run)
        found = [v for v in replay(run, cycles) if v not in honest] if at is not None else []
        mine = [v for v in found if says in v]
        gangs[name] = {
            "round_doctored": at,
            "doctored_violations": len(found),
            # by its own invariant and no other; `also`: the same fault as invariant 3 sees it, on the one node
            "by_its_invariant_alone": bool(mine) and all(v in mine or any(a in v for a in sum(also, [])) for v in found),
            "also": len(found) - len(mine),
            "first": mine[0] if mine else None,
        }
    return {
        "early_window": early_window(run),
        "honest_violations": len(honest),
        "round_doctored": n,
        "rounds_that_gave_up": sum(run.checker.gave_up(c) for c in run.cycles),
        "doctored_violations": len(new),
        "reported_by_invariant_9": bool(new) and all(STILL_FITS in v for v in new),
        "first": new[0] if new else None,
        "gangs": gangs,
    }


def controls_hold(out: dict) -> bool:
    """Whether every control that found its round came out not correct, by
    its own invariant alone, and at least one found one."""
    held = [out["reported_by_invariant_9"]] if out["round_doctored"] is not None else []
    held += [c["by_its_invariant_alone"] for c in out["gangs"].values() if c["round_doctored"] is not None]
    return bool(held) and all(held)


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
    return 0 if sound and controls_hold(out) and out["early_window"]["reported"] else 1


if __name__ == "__main__":
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.execve(sys.executable, [sys.executable] + sys.argv, dict(os.environ, PYTHONHASHSEED="0"))
    sys.exit(main())
