"""The seeded generator: a seed changes the order, never the amount of work."""

import numpy as np
import pytest

from perfbench_tiny import load

from perfbench.harness.world import World, exact_counts

SIZES = dict(
    load("perfbench", "configs", "cluster-100k-5k.json")["world"],
    nodes=403, queued_jobs=5003, running_jobs=207, queues=13,
)


@pytest.mark.parametrize("seeds", [(1, 2), (7, 3_000_000_019), (0, 2**31 + 5)])
def test_two_seeds_same_histograms_other_order(seeds):
    a, b = World(SIZES, seeds[0]), World(SIZES, seeds[1])
    assert a.histograms() == b.histograms()
    assert (a.job_queue != b.job_queue).any() and (a.node_cores != b.node_cores).any()
    # later submits too: every cycle's batch has one histogram
    for w in (a, b):
        w.extend(1000, 5.0)
        w.extend(1000, 6.0)
    for w in (a, b):
        first = np.bincount(w.job_queue[5003:6003], minlength=13)
        second = np.bincount(w.job_queue[6003:7003], minlength=13)
        assert (first == second).all()
        assert (np.bincount(w.job_shape[5003:6003]) == np.bincount(w.job_shape[6003:7003])).all()
    assert (np.bincount(a.job_shape[5003:6003]) == np.bincount(b.job_shape[5003:6003])).all()


def test_same_seed_same_world():
    a, b = World(SIZES, 11), World(SIZES, 11)
    assert (a.job_queue == b.job_queue).all() and (a.job_submit == b.job_submit).all()
    assert (a.run_node == b.run_node).all()


@pytest.mark.parametrize("n,weights", [(1000, [1, 1 / 2, 1 / 3]), (7, [0.7, 0.3]), (0, [1, 1]), (5, [1] * 8)])
def test_exact_counts(n, weights):
    counts = exact_counts(n, weights)
    assert counts.sum() == n
    ideal = n * np.asarray(weights) / np.sum(weights)
    assert (np.abs(counts - ideal) < 1.0).all()


def test_demand_skew_and_shapes():
    w = World(SIZES, 5)
    h = w.histograms()
    assert h["job_queue"][0] > h["job_queue"][-1] * 10  # 1/k over 13 queues
    assert h["runs_per_node_max"] == 1
    share = sum(c for c, s in zip(h["job_shape"], w.shapes) if s[2]) / 5003
    assert abs(share - 0.7) < 0.001
    with pytest.raises(KeyError):
        w.job_number("j999999999")
    with pytest.raises(KeyError):
        w.job_number("r00000001")
    assert w.job_number("j000000042") == 42
