#!/usr/bin/env python3
"""The first rounds of a cell, outside any timed window, beside the repo's
sequential oracle (`tests/test_parity_full.py`) and the program's own
from-scratch round: which jobs each side leased and preempted, round by round.

    python3 tests/perfbench/oracle_rounds.py --workload <cell> --seed 7 --rounds 27

Two steps, which may run in two places:

  - serve: the cell's world and mirror load as `perfbench/run.py` makes them,
    then `--rounds` cycles of the client's loop (no warm-up, no window, no
    timing), each through the plain checker; what every round answered goes to
    `<out>/oracle_rounds.<cell>.seed<n>.json`.  Needs the cell's chips
    (`--allow-cpu` for the tests).
  - oracle: from that file alone (`--from-dump FILE` skips the serve step and
    needs no device), the world again from the seed, the state at the start
    of a round rebuilt from the rounds before it (who is queued, who runs
    where), one round of the oracle from there, and the comparison.  Round 0
    first, then the first round that gave up under its cap, then the others in
    order, for as long as `--oracle-seconds` allows (the oracle walks every
    node in Python for every lease: minutes a round at 5,000 nodes).

One line a round compared, `perfbench oracle round {...}`, and a last line
`perfbench oracle {...}`.  What is HELD is round 0 on a fleet with room: both
sides start from the same world, and the leased set is the oracle's (the tiny
tests hold the same).  Everything else is REPORTED, not held: per round, how
many jobs only one side leased, how many leases sit on the same node, and
whether the preempted sets are equal, id for id or kind for kind (queue, size,
class).  On the full fleet PR 35 measured at 100k x 5k and did not declare
(PERF.md section 6, PR 35, and section 7): of 24 rounds that fill their cap 21
lease the oracle's set and three differ by one job a side; the three rounds
that end `exhausted` do NOT: 649 / 694 / 574 leases against the oracle's 662 /
580 / 480 from the same state.  What a round that gives up is held to is
invariant 9 of the checker, which every served round goes through here, not
the oracle.  Each round compared also goes a third way, through the program's
own from-scratch round (`run_scheduling_round`) on THIS process's backend
from the same state: from a dump under `JAX_PLATFORMS=cpu` that is the second
witness beside what a chip served, and `leases_by_shape_...` shows a size
that one side stopped leasing (PERF.md section 7: the chip refuses a job whose
memory of 33 equals a node's free memory; XLA:CPU and the oracle place it).
A world with gangs, node types and a third resource goes in whole (`problem_of`):
every resource, the gangs' ids and cardinalities, and to the program's round the
labels, taints, selectors and tolerations as the configuration words them; the
oracle knows no label and no taint, so it gets the node types that admit each job
as the job's list of node types at throughput 1.0, which changes no score.  Each
round's line says how many gangs each side leased, whether they are the same gangs
(`same_gangs`; round 0's `same_jobs` holds it gang for gang) and whether any side
leased a gang in part.  Exit code 0 only if the served rounds broke no invariant and round 0,
where compared, leased the oracle's jobs: it says nothing of the rounds after.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def serve(cell, seed: int, rounds: int) -> tuple:
    """`rounds` cycles of the cell from a fresh plane: (the run, its records)."""
    from perfbench.harness.runner import Run

    run, records = Run(cell, seed, 1.0, False), []
    with tempfile.TemporaryDirectory(prefix="perfbench-") as data_dir:
        run.start(data_dir)
        try:
            run.load_mirror()
            for _ in range(rounds):
                rec = run.cycle()
                if rec["error"]:
                    raise RuntimeError(f"round {rec['k']} failed: {rec['error']}")
                records.append(rec)
                print(
                    f"[oracle_rounds] round {rec['k']}: {len(rec['leases'])} leases, "
                    f"{len(rec['preempted'])} preempted, {rec.get('termination')}, "
                    f"trips {rec.get('kernel_iters')}", flush=True,
                )
        finally:
            run.stop()
    return run, records


def dump_of(run, records) -> dict:
    for n, rec in enumerate(records):
        run.checker.cycle(n, rec)
    return {
        "workload": run.cell.name,
        "seed": run.seed,
        "device": records[0].get("device"),
        "violations": list(run.checker.violations),
        "rounds": [
            {
                "k": rec["k"],
                "leases": rec["leases"],
                "preempted": rec["preempted"],
                "completed": rec["completed"],
                "submitted": [rec["submitted"][0], rec["submitted"][-1] + 1],
                "termination": rec.get("termination"),
                "kernel_iters": rec.get("kernel_iters"),
                "gave_up": bool(run.checker.gave_up(rec)),
            }
            for rec in records
        ],
    }


def world_of(cell, dump: dict):
    """The cell's world from the seed, grown by each round's submits as the
    runner's `prebuild(1)` grows it: the same draws in the same order."""
    from perfbench.harness.runner import NOW0_NS
    from perfbench.harness.world import World

    w = World(cell.config["world"], dump["seed"])
    step_ns = int(float(cell.traffic["logical_cycle_s"]) * 1e9)
    for r in dump["rounds"]:
        numbers = w.extend_batches(
            int(cell.traffic["submits_per_cycle"]), [(NOW0_NS + (r["k"] + 1) * step_ns) / 1e9]
        )[0]
        assert [numbers.start, numbers.stop] == r["submitted"], (r["k"], numbers, r["submitted"])
    return w


def state_before(w, dump: dict, k: int) -> tuple:
    """(queued job numbers, {job number: node id} of live leases, initial run
    numbers still live) at the start of round k: cycle k's submits and
    completions are in, rounds 0..k-1 have leased and preempted."""
    leased, live, gone_runs = set(), {}, set()
    for r in dump["rounds"][:k]:
        for job_id, node_id, _ in r["leases"]:
            i = w.job_number(job_id)
            leased.add(i)
            live[i] = node_id
        for job_id in r["preempted"]:
            if job_id.startswith("r"):
                gone_runs.add(w.run_number(job_id))
            else:
                live.pop(w.job_number(job_id))
    for r in dump["rounds"][: k + 1]:
        for i in r["completed"]:
            live.pop(i)
    queued = [i for i in range(dump["rounds"][k]["submitted"][1]) if i not in leased]
    runs = [i for i in range(len(w.run_shape)) if i not in gone_runs]
    return queued, live, runs


def kinds(w, job_ids) -> list:
    """(queue, cpu, memory, preemptible) of each id, sorted: a preempted set
    up to which of two interchangeable jobs on tied nodes was taken."""
    out = []
    for job_id in job_ids:
        if job_id.startswith("r"):
            i = w.run_number(job_id)
            out.append((int(w.run_queue[i]), *w.run_shapes[w.run_shape[i]]))
        else:
            i = w.job_number(job_id)
            out.append((int(w.job_queue[i]), *w.shapes[w.job_shape[i]]))
    return sorted(out)


def problem_of(cell, w, queued, live: dict, runs, by_type: bool = False) -> tuple:
    """(config, nodes, queues, queued jobs, running jobs) of one round, as the
    program's own types.  `queued`: the job numbers in the backlog; `live`:
    {job number: node id} of leases still running; `runs`: the initial runs
    still live.  Every resource of the world, the nodes' labels and taints,
    the jobs' selectors, tolerations and gangs go in as the configuration
    words them.

    `by_type` is the form the sequential oracle reads: it knows no label and
    no taint, but it keeps a job that names node types to nodes of those
    types.  So which node types ADMIT each shape is worked out here
    (`world.admits`, plain Python over the configuration's words) and handed
    over as the job's list of node types, each at throughput 1.0, which adds
    nothing to a node's score; a world with no label and no taint gives the
    oracle what it always got."""
    from armada_tpu.core.config import scheduling_config_from_dict
    from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob, Taint, Toleration

    cfg = scheduling_config_from_dict(cell.scheduling())
    f = cfg.resource_list_factory()
    rl = lambda req: f.from_mapping({name: f"{int(a)}m" for name, a in zip(w.resources, req)})  # noqa: E731
    shape_rl = [rl(req) for req in w.shape_req]
    run_rl = [rl(req) for req in w.run_shape_req]
    kind_rl = {}
    plain = bool(w.shape_admits.all() and w.run_shape_admits.all())  # no label or taint keeps any job off any node
    names = [k["name"] for k in w.node_kinds]
    nodes = []
    for i, k in enumerate(w.node_kind.tolist()):
        if k not in kind_rl:
            kind_rl[k] = rl(w.node_total[i])
        kind = w.node_kinds[k]
        if by_type:
            more = {} if plain else {"node_type": names[k]}
        else:
            more = {"labels": dict(kind["labels"]),
                    "taints": tuple(Taint(t["key"], t.get("value", ""), t.get("effect", "NoSchedule")) for t in kind["taints"])}
        nodes.append(NodeSpec(id=w.node_ids[i], pool="default", total_resources=kind_rl[k], **more))

    def placement(admitted, selector=(), tolerations=()):
        if by_type:
            return {} if plain else {"node_type_scores": tuple(sorted((names[k], 1.0) for k in np.flatnonzero(admitted)))}
        return {"node_selector": dict(selector),
                "tolerations": tuple(Toleration(key=t.get("key", ""), operator=t.get("operator", "Equal"),
                                                value=t.get("value", ""), effect=t.get("effect", "")) for t in tolerations)}

    shape_more = [placement(*x) for x in zip(w.shape_admits, w.shape_selector, w.shape_tolerations)]
    run_more = [placement(admitted) for admitted in w.run_shape_admits]

    def spec(i):
        s, g = w.job_shape[i], w.job_gang[i]
        gang = {} if g < 0 else {"gang_id": w.gang_id(g), "gang_cardinality": int(w.gang_size[g]),
                                 "gang_node_uniformity_label": w.shape_uniformity[s]}
        return JobSpec(
            id=w.job_id(i), queue=w.queue_names[w.job_queue[i]], priority_class=w.class_name(w.shapes[s][2]),
            submit_time=float(w.job_submit[i]), resources=shape_rl[s], **shape_more[s], **gang,
        )

    running = [
        RunningJob(
            job=JobSpec(
                id=f"r{i:08d}", queue=w.queue_names[w.run_queue[i]],
                priority_class=w.class_name(w.run_shapes[w.run_shape[i]][2]), submit_time=-1.0,
                resources=run_rl[w.run_shape[i]], **run_more[w.run_shape[i]],
            ),
            node_id=w.node_ids[w.run_node[i]],
        )
        for i in runs
    ] + [RunningJob(job=spec(i), node_id=node_id) for i, node_id in live.items()]
    return cfg, nodes, [Queue(q, 1.0) for q in w.queue_names], [spec(i) for i in queued], running


def oracle_sets(cell, w, queued, live: dict, runs) -> tuple:
    """One round of the sequential oracle over the cell's fleet and queues:
    (scheduled {job id: node id}, preempted ids)."""
    tests = os.path.join(ROOT, "tests")
    if tests not in sys.path:
        sys.path.insert(0, tests)
    import test_parity_full as parity

    o_sched, o_preempted, _ = parity._Oracle(*problem_of(cell, w, queued, live, runs, by_type=True)).run()
    return dict(o_sched), set(o_preempted)


def program_sets(cell, w, queued, live: dict, runs) -> tuple:
    """The same round through the program's own from-scratch entry
    (`run_scheduling_round`) on THIS process's backend: with
    `JAX_PLATFORMS=cpu`, the second witness beside what a chip served."""
    from armada_tpu.models import run_scheduling_round

    cfg, nodes, queues, jobs, running = problem_of(cell, w, queued, live, runs)
    out = run_scheduling_round(cfg, pool="default", nodes=nodes, queues=queues, queued_jobs=jobs, running=running,
                               collect_stats=False)
    return dict(out.scheduled), set(out.preempted)


def gangs_of(w, job_ids) -> dict:
    """{gang number: members among `job_ids`} of the ids that are a gang's."""
    out: dict = {}
    for job_id in job_ids:
        if not job_id.startswith("r"):
            g = int(w.job_gang[w.job_number(job_id)])
            if g >= 0:
                out[g] = out.get(g, 0) + 1
    return out


def by_shape(w, *lease_sets) -> dict:
    """{"cpu_milli x memory": [leases of that size in each set]}: a key the
    kernel wrongly retires for a round shows as ONE size missing on one side."""
    sizes = sorted({s[:2] for s in w.shapes})
    counts = [[0] * len(lease_sets) for _ in sizes]
    for n, leases in enumerate(lease_sets):
        for job_id in leases:
            counts[sizes.index(w.shapes[w.job_shape[w.job_number(job_id)]][:2])][n] += 1
    return {f"{cpu}x{mem}": c for (cpu, mem), c in zip(sizes, counts)}


def oracle_round(cell, w, dump: dict, k: int) -> dict:
    """Round k from the state the served rounds before it left: what the
    program answered when it was served, beside the sequential oracle and the
    program's own from-scratch round on this process's backend."""
    import jax

    t = time.perf_counter()
    queued, live, runs = state_before(w, dump, k)
    o_sched, o_preempted = oracle_sets(cell, w, queued, live, runs)
    p_sched, p_preempted = program_sets(cell, w, queued, live, runs)
    r = dump["rounds"][k]
    got = {job_id: node_id for job_id, node_id, _ in r["leases"]}
    gangs = [gangs_of(w, x) for x in (got, o_sched, p_sched)]  # served, the oracle's, the program's
    return {
        "round": k,
        "gave_up": r["gave_up"],
        "termination": r["termination"],
        "queued": len(queued),
        "running": len(runs) + len(live),
        "leases": len(got),
        "oracle_leases": len(o_sched),
        "same_jobs": set(got) == set(o_sched),
        "served_not_oracle": len(set(got) - set(o_sched)),
        "oracle_not_served": len(set(o_sched) - set(got)),
        "same_node": sum(1 for j, n in got.items() if o_sched.get(j) == n),
        # gang for gang: which gangs each side leased, and whether each was leased whole
        "gangs_served_oracle_program": [len(x) for x in gangs],
        "same_gangs": set(gangs[0]) == set(gangs[1]),
        "gangs_in_part": sum(1 for x in gangs for g, k in x.items() if k != w.gang_size[g]),
        "preempted": len(r["preempted"]),
        "oracle_preempted": len(o_preempted),
        "same_preempted": set(r["preempted"]) == o_preempted,
        "same_preempted_kinds": kinds(w, r["preempted"]) == kinds(w, o_preempted),
        "program_backend": jax.default_backend(),
        "program_leases": len(p_sched),
        "program_preempted": len(p_preempted),
        "served_not_program": len(set(got) - set(p_sched)),
        "program_not_served": len(set(p_sched) - set(got)),
        "program_not_oracle": len(set(p_sched) - set(o_sched)),
        "oracle_not_program": len(set(o_sched) - set(p_sched)),
        "leases_by_shape_served_oracle_program": by_shape(w, got, o_sched, p_sched),
        "oracle_s": time.perf_counter() - t,
    }


def order_of(dump: dict) -> list:
    """Round 0, the first round that gave up, then the rest in order."""
    n = len(dump["rounds"])
    first = next((r["k"] for r in dump["rounds"] if r["gave_up"]), None)
    head = [0] + ([first] if first else [])
    return head + [k for k in range(n) if k not in head]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=27)
    ap.add_argument("--oracle-seconds", type=float, default=300.0,
                    help="start no further oracle round once this much has gone (0: serve and dump only)")
    ap.add_argument("--only", help="comma-separated rounds to compare, in place of the default order")
    ap.add_argument("--from-dump", help="a file an earlier serve step wrote: compare it, serve nothing")
    ap.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--out", help="directory for the dump (default <root>/perfbench_out)")
    ap.add_argument("--allow-cpu", action="store_true", help="for tests: serve without a TPU")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from perfbench.harness import runner
    from perfbench.harness.cell import Cell

    cell = Cell(args.benchmark, args.workload)
    if args.from_dump:
        with open(args.from_dump, encoding="utf-8") as f:
            dump = json.load(f)
        w = world_of(cell, dump)
    else:
        try:
            runner.device_block(cell.chips, args.allow_cpu)
        except runner.NoAccelerator as e:
            print(f"oracle_rounds: {e}", file=sys.stderr)
            return 2
        run, records = serve(cell, args.seed, args.rounds)
        dump, w = dump_of(run, records), run.world
        out_dir = args.out or os.path.join(cell.root, "perfbench_out")
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"oracle_rounds.{cell.name}.seed{args.seed}.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(dump, f)
        print(f"[oracle_rounds] dump: {path}", flush=True)
    order = [int(k) for k in args.only.split(",")] if args.only else order_of(dump)
    compared, t0 = [], time.perf_counter()
    for k in order:
        if time.perf_counter() - t0 >= args.oracle_seconds:
            break
        compared.append(oracle_round(cell, w, dump, k))
        print("perfbench oracle round " + json.dumps(compared[-1]), flush=True)
    first = next((c for c in compared if c["round"] == 0), None)
    out = {
        "workload": dump["workload"],
        "seed": dump["seed"],
        "device": dump["device"],
        "rounds_served": len(dump["rounds"]),
        "gave_up": [r["k"] for r in dump["rounds"] if r["gave_up"]],
        "violations": len(dump["violations"]),
        "rounds_compared": [c["round"] for c in compared],
        "same_jobs": [c["round"] for c in compared if c["same_jobs"]],
        "same_preempted": [c["round"] for c in compared if c["same_preempted"]],
        "same_preempted_kinds": [c["round"] for c in compared if c["same_preempted_kinds"]],
        "round_0_agrees": None if first is None else first["same_jobs"],
    }
    print("perfbench oracle " + json.dumps(out), flush=True)
    return 0 if not dump["violations"] and out["round_0_agrees"] is not False else 1


if __name__ == "__main__":
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.execve(sys.executable, [sys.executable] + sys.argv, dict(os.environ, PYTHONHASHSEED="0"))
    sys.exit(main())
