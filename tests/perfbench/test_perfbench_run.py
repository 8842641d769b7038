"""The runner end to end at tiny size on the CPU: it reports counts only and
names the device `cpu`, it refuses to report anything without a TPU, it holds
the window stationary, and its decisions agree with the repo's sequential
oracle."""

import json
import os
import subprocess
import sys
import tempfile

import pytest

from perfbench_tiny import ROOT, RUN, make_tiny


def _run_cli(*argv, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONHASHSEED", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, RUN, *argv], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600
    )


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """One traced run of the tiny cell through the command line."""
    root = tmp_path_factory.mktemp("tiny")
    bench = make_tiny(root)
    out = os.path.join(root, "out")
    p = _run_cli(
        "--workload", "tiny.steady-40", "--seed", "3000000019", "--seconds", "2",
        "--trace", "1", "--allow-cpu", "--benchmark", bench, "--out", out,
        env_extra={"BENCH_RUN": "ignored"},
    )
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    record = json.load(open(os.path.join(out, "tiny.steady-40.seed3000000019.trace1.0.json")))
    return json.loads(lines[-1]), lines, record


def test_last_line_is_the_contract_object_and_nothing_else(tiny_run):
    result, lines, _ = tiny_run
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 3
    assert all(line.startswith("[perfbench]") for line in lines[:-1] if line.startswith("["))
    assert any("PYTHONHASHSEED=0" in line for line in lines)


def test_cpu_run_names_the_device_and_reports_counts_only(tiny_run):
    result, _, record = tiny_run
    assert result["device"]["platform"] == "cpu" and result["device"]["kind"] == "cpu"
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert result["metrics"], "the counts are reported"
    for name, m in result["metrics"].items():
        assert m["unit"] in ("count", "bytes"), name
    assert {"uploads_per_cycle", "kernel_trips_per_cycle", "compiles_in_window"} <= set(result["metrics"])
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert record["values"] is None and record["wall_histogram"] is None
    for row in record["per_cycle"]:
        assert not [k for k in row if k.endswith("_s")], row


def test_metric_added_as_a_file_is_reported(tiny_run):
    result, _, _ = tiny_run
    assert result["metrics"]["downloads_per_cycle"]["value"] > 0


def test_window_is_stationary(tiny_run):
    _, _, record = tiny_run
    window = [c for c in record["per_cycle"] if c["phase"] == "window"]
    assert window[0]["num_queued"] == window[-1]["num_queued"]
    assert window[0]["num_running"] == window[-1]["num_running"]
    assert all(c["leases"] == 40 and c["completions"] == 40 for c in window)
    assert record["problems"] == [] and record["failed_cycles"] == {}
    warm = [c for c in record["per_cycle"] if c["phase"] == "warm"]
    assert len(warm) > 3 and warm[0]["completions"] == 0
    assert record["gc"]["counts_in_run"][0] > 0 and record["hash_seed"] == "0"


@pytest.mark.parametrize("trace", ["0", "1"])
def test_no_tpu_no_result(tmp_path, trace):
    bench = make_tiny(tmp_path)
    p = _run_cli("--workload", "tiny.steady-40", "--seed", "1", "--seconds", "1",
                 "--trace", trace, "--benchmark", bench, "--out", str(tmp_path / "out"))
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [line for line in p.stdout.splitlines() if line.startswith("{")]


def test_unknown_workload_is_refused(tmp_path):
    bench = make_tiny(tmp_path)
    p = _run_cli("--workload", "nope", "--seed", "1", "--allow-cpu", "--benchmark", bench)
    assert p.returncode != 0 and "no workload" in p.stderr
    assert not [line for line in p.stdout.splitlines() if line.startswith("{")]


def test_first_round_agrees_with_the_sequential_oracle(tmp_path):
    """The harness's first round, over the wire, against the repo's
    independent sequential oracle (tests/test_parity_full.py): the same jobs
    leased, and the same count from every queue."""
    import test_parity_full as parity  # tests/ is on sys.path (rootdir conftest)

    from armada_tpu.core.config import scheduling_config_from_dict
    from armada_tpu.core.types import JobSpec, NodeSpec, Queue, RunningJob

    from perfbench.harness.cell import Cell
    from perfbench.harness.runner import Run

    cell = Cell(make_tiny(tmp_path, nodes=60, queued=500, running=30, burst=40), "tiny.steady-40")
    run = Run(cell, 7, 1.0, False)
    with tempfile.TemporaryDirectory() as data_dir:
        run.start(data_dir)
        try:
            run.load_mirror()
            rec = run.cycle()
        finally:
            run.stop()
    assert rec["error"] is None and len(rec["leases"]) == 40
    run.checker.cycle(0, rec)
    assert run.checker.violations == []

    w = run.world
    cfg = scheduling_config_from_dict(cell.scheduling())
    f = cfg.resource_list_factory()
    rl = lambda cpu, mem: f.from_mapping({"cpu": f"{cpu}m", "memory": str(mem)})  # noqa: E731
    nodes = [
        NodeSpec(id=w.node_ids[i], pool="default",
                 total_resources=rl(int(c) * 1000, int(c) * int(w.sizes["memory_per_core"])))
        for i, c in enumerate(w.node_cores)
    ]
    queues = [Queue(q, 1.0) for q in w.queue_names]
    jobs = [
        JobSpec(
            id=w.job_id(i), queue=w.queue_names[w.job_queue[i]],
            priority_class="batch" if w.shapes[w.job_shape[i]][2] else "prod",
            submit_time=float(w.job_submit[i]),
            resources=rl(w.shapes[w.job_shape[i]][0], w.shapes[w.job_shape[i]][1]),
        )
        for i in range(500 + 40)  # the backlog and cycle 0's submits
    ]
    running = [
        RunningJob(
            job=JobSpec(
                id=f"r{i:08d}", queue=w.queue_names[w.run_queue[i]],
                priority_class="batch" if w.run_shapes[w.run_shape[i]][2] else "prod",
                submit_time=-1.0,
                resources=rl(w.run_shapes[w.run_shape[i]][0], w.run_shapes[w.run_shape[i]][1]),
            ),
            node_id=w.node_ids[w.run_node[i]],
        )
        for i in range(30)
    ]
    o_sched, o_preempted, _ = parity._Oracle(cfg, nodes, queues, jobs, running).run()
    leased = {job_id for job_id, _, _ in rec["leases"]}
    assert leased == set(o_sched), (leased - set(o_sched), set(o_sched) - leased)
    assert not o_preempted and not rec["preempted"]
