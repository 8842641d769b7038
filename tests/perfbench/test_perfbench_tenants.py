"""The `tenants-925q-1m-50k` configuration's shape at tiny size on the CPU:
hundreds of queues with 1/k demand behind the served path (the queue axis
padded past its first 256-bucket), through `run.py` and against the repo's
sequential oracle.  The tiny cell is `perfbench_tiny.make_tiny`'s with
`queues` set to 300 in the test's own copy of the configuration: files only.
Every comparison is exact (sets and counts)."""

import collections
import json
import os

import numpy as np
import pytest

import test_perfbench_run as run_tests  # its CLI runner and its oracle harness
from perfbench_tiny import load, make_tiny

QUEUES, PADDED = 300, 512
SIZE = dict(nodes=120, queued=3000, running=60, burst=40, lifetime=3)


def make_tenants(root, collect_stats=False, **size):
    """The tiny cell with 300 queues; `collect_stats` sets `publishMetricEvents`
    in the session's config, the switch by which a sidecar session collects
    per-queue stats at all (it runs its algo with `collect_stats=False`)."""
    bench = make_tiny(root, **{**SIZE, **size})
    path = os.path.join(str(root), "perfbench", "configs", "tiny.json")
    with open(path, encoding="utf-8") as f:
        config = json.load(f)
    config["world"]["queues"] = QUEUES
    if collect_stats:
        config["scheduling"]["publishMetricEvents"] = True
    with open(path, "w", encoding="utf-8") as f:
        json.dump(config, f)
    return bench


def test_the_declared_configuration_is_the_envelope_with_925_queues():
    env = load("perfbench", "configs", "envelope-1m-50k.json")
    new = load("perfbench", "configs", "tenants-925q-1m-50k.json")
    assert new["world"] == dict(env["world"], queues=925)
    assert new["scheduling"] == env["scheduling"] and new["guarantees"] == env["guarantees"]
    assert new["reduced"] == [] and new["chips"] == 1
    cells = {w["name"]: w for w in load("BENCHMARK.json")["workloads"]}
    assert cells["tenants.steady-1k"]["traffic"] == cells["envelope.steady-1k"]["traffic"] == "steady-1k"


@pytest.fixture(scope="module")
def tenants_run(tmp_path_factory):
    root = tmp_path_factory.mktemp("tenants")
    bench = make_tenants(root)
    out = os.path.join(root, "out")
    p = run_tests._run_cli(
        "--workload", "tiny.steady-40", "--seed", "3000000019", "--seconds", "2", "--trace", "1",
        "--allow-cpu", "--benchmark", bench, "--out", out,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    record = json.load(open(os.path.join(out, "tiny.steady-40.seed3000000019.trace1.0.json")))
    return json.loads(p.stdout.strip().splitlines()[-1]), record


def test_tiny_tenants_cell_is_correct_through_the_command_line(tenants_run):
    result, record = tenants_run
    assert result["correct"] is True and result["failed"] == 0, record["problems"]
    assert all(c["value"] <= c["limit"] for c in result["checks"].values())
    window = [c for c in record["per_cycle"] if c["phase"] == "window"]
    assert len(window) >= 3 and all(c["leases"] == 40 for c in window)
    # the round's counters, in every cycle's record (scalars of the stats JSON)
    assert all((c["queues"], c["queues_padded"]) == (QUEUES, PADDED) for c in record["per_cycle"])
    assert all(1 <= c["queues_scheduled"] <= min(40, c["queues_pending"]) <= QUEUES for c in window)
    assert "fair_share_iterations" not in window[0]  # a sidecar session collects no queue stats
    # the new metrics that are counts are reported on the CPU; `queue_host_s` is seconds
    metrics = result["metrics"]
    assert metrics["queues_scheduled_per_cycle"]["value"] == np.median([c["queues_scheduled"] for c in window])
    assert metrics["response_bytes_per_cycle"]["value"] == np.median([c["response_bytes"] for c in window])
    assert "queue_host_s" not in metrics
    # the mice drain: a steady window leases from far fewer queues than the first round
    first = record["per_cycle"][0]
    assert first["queues_pending"] == QUEUES and first["queues_scheduled"] == 40
    assert metrics["queues_scheduled_per_cycle"]["value"] < 40


class _KeepResponses:
    """The harness's client with every round's response kept."""

    kept: list = []

    @classmethod
    def install(cls, monkeypatch):
        from perfbench.harness import runner

        cls.kept = []

        class Wire(runner.Wire):
            def __init__(self, port):
                super().__init__(port)
                inner = self.round

                def keeping(req):
                    cls.kept.append(inner(req))
                    return cls.kept[-1]

                self.round = keeping

        monkeypatch.setattr(runner, "Wire", Wire)


@pytest.mark.parametrize("collect_stats", [False, True], ids=["served", "stats-collected"])
def test_first_round_at_300_queues_agrees_with_the_oracle_and_counts_its_queues(
    tmp_path, monkeypatch, collect_stats
):
    """The first round over the wire: the jobs leased and the count from every
    queue are the sequential oracle's (tests/test_parity_full.py; a cost tie
    between queues goes to the lower queue NAME, in the reference, the oracle
    and the kernel alike: tests/test_queue_axis.py), and the round's new
    counters equal what the leases and the world's tables give.  `queue_stats`
    rides the response empty as served, and with exactly the 300 declared
    queues where the session's config makes it collect them."""
    from perfbench.harness.cell import Cell

    _KeepResponses.install(monkeypatch)
    cell = Cell(make_tenants(tmp_path, collect_stats=collect_stats, nodes=60, queued=2000, running=30),
                "tiny.steady-40")
    run, (rec,), o_sched, o_preempted = run_tests._first_round_and_oracle(cell, 7)
    assert rec["error"] is None and len(rec["leases"]) == 40
    run.checker.cycle(0, rec)
    assert run.checker.violations == []
    leased = {job_id for job_id, _, _ in rec["leases"]}
    assert leased == set(o_sched), (leased - set(o_sched), set(o_sched) - leased)
    assert not o_preempted and not rec["preempted"]
    w = run.world
    per_queue = collections.Counter(q for _, _, q in rec["leases"])
    assert per_queue == collections.Counter(w.queue_names[w.job_queue[w.job_number(j)]] for j in o_sched)

    pool = rec["pool"]
    n = int(w.sizes["queued_jobs"]) + int(cell.traffic["submits_per_cycle"])  # backlog + cycle 0's submits
    assert pool["queues"] == QUEUES == len(w.queue_names) and pool["queues_padded"] == PADDED
    assert pool["queues_pending"] == len(set(w.job_queue[:n].tolist()))
    assert pool["queues_scheduled"] == len(per_queue) == 40  # one job a queue from forty queues
    (resp,) = _KeepResponses.kept
    stats = json.loads(resp.pool_stats_json)["pools"][0]
    assert rec["response_bytes"] == resp.ByteSize()
    if collect_stats:
        assert set(stats["queue_stats"]) == set(w.queue_names) and len(w.queue_names) == QUEUES
        assert pool["fair_share_iterations"] == stats["fair_share_iterations"] >= 2  # mice under their share
        assert all(len(v) == 7 for v in stats["queue_stats"].values())
    else:
        assert stats["queue_stats"] == {} and "fair_share_iterations" not in stats
