"""The padded sizes a delta scatter runs with (slab.DeviceDeltaCache._buckets_for, PR 30).

A delta's index vectors are padded to buckets so the jitted scatter
recompiles on bucket crossings only.  A delta that dips one bucket under the
sizes its variant has already run with pads up to that compiled program; it
never pads further than one step (4x), whatever ran before, in whatever
order.
"""

import numpy as np
import pytest

import tests.test_slab_delta as slab_delta
from armada_tpu.models import slab
from armada_tpu.models.incremental import IncrementalBuilder
from armada_tpu.models.slab import DeviceDeltaCache, _pad_bucket
from armada_tpu.ops.trace import recorder
from tests.test_trace import _find, _fresh_recorder  # noqa: F401  (a fixture)

STEADY = ("apply", True, ("q_start", "q_len", "q_cds"))

# (name, [(counts, sizes the scatter runs with)]) on one variant, in order
ORDERINGS = [
    (
        # the steady envelope: the splice sits just above 1,024 entries and
        # dips under it once in a few dozen cycles (PERF.md section 7)
        "steady-then-dip",
        [
            ((280, 0, 1700), (1024, 256, 4096)),
            ((266, 0, 1010), (1024, 256, 4096)),  # one step under: no new program
            ((280, 0, 1700), (1024, 256, 4096)),
            ((250, 0, 1700), (1024, 256, 4096)),  # not (256, ...)
        ],
    ),
    (
        # a catch-up burst right after the full upload, before any steady cycle
        "burst-first",
        [
            ((70_000, 0, 70_000), (262144, 256, 262144)),
            ((280, 0, 1700), (1024, 256, 4096)),  # its own, never the burst's
            ((280, 0, 900), (1024, 256, 4096)),  # and the dip as above
            ((70_000, 0, 70_000), (262144, 256, 262144)),
            ((280, 0, 1700), (1024, 256, 4096)),
        ],
    ),
    (
        # a busy period, then a quiet one two buckets below it
        "quiet-after-busy",
        [
            ((5000, 0, 9000), (16384, 256, 16384)),
            ((4000, 0, 5000), (16384, 256, 16384)),  # (4096, ..) is one step under
            ((280, 0, 900), (1024, 256, 1024)),  # two steps under: its own
            ((200, 0, 900), (1024, 256, 1024)),  # one step under the quiet one
            ((5000, 0, 9000), (16384, 256, 16384)),
        ],
    ),
    (
        # one step in one entry and two in another is not "one step"
        "mixed-steps",
        [
            ((5000, 0, 1700), (16384, 256, 4096)),
            ((280, 0, 1700), (1024, 256, 4096)),
            ((1500, 0, 200), (4096, 256, 256)),
        ],
    ),
    (
        # a delta ABOVE what ran always takes its own sizes
        "growing",
        [
            ((100, 0, 100), (256, 256, 256)),
            ((300, 0, 100), (1024, 256, 256)),
            ((300, 300, 100), (1024, 1024, 256)),
            ((100, 0, 100), (256, 256, 256)),  # ran before: exactly
        ],
    ),
]


@pytest.mark.parametrize("steps", [o[1] for o in ORDERINGS], ids=[o[0] for o in ORDERINGS])
def test_a_delta_pads_at_most_one_bucket_step_up_to_a_program_that_ran(steps):
    cache = DeviceDeltaCache()
    for counts, sizes in steps:
        got = cache._buckets_for(STEADY, counts)
        assert got == sizes, (counts, got, sizes)
        assert all(_pad_bucket(n) <= k <= 4 * _pad_bucket(n) for n, k in zip(counts, got))


def test_variants_keep_their_own_sizes_and_a_new_device_problem_forgets_them():
    cache = DeviceDeltaCache()
    assert cache._buckets_for(STEADY, (280, 0, 1700)) == (1024, 256, 4096)
    # another variant of the program (other full fields ship) compiled nothing yet
    other = ("apply", True, ("q_start", "q_len", "q_cds", "perq_burst"))
    assert cache._buckets_for(other, (280, 0, 900)) == (1024, 256, 1024)
    assert cache._buckets_for(("content",), (1000, 2000)) == (1024, 4096)
    assert cache._buckets_for(("content",), (1000, 900)) == (1024, 4096)
    cache.reset()  # a new device problem: every variant compiles anew anyway
    assert cache._buckets_for(STEADY, (280, 0, 900)) == (1024, 256, 1024)


# ------------------------------------- through the scatter itself, on the CPU ----


def _programs() -> int:
    """Scatter programs the process has compiled (the jit cache's size)."""
    return slab._APPLY._cache_size() if slab._APPLY is not None else 0


class _Served:
    """A slab builder and a device cache at a size whose steady delta sits
    over the 1,024 bucket: 6,000 queued singles on [G] = 9,024 slots, so a
    cycle of 1,100 removals and 1,100 submits is a 1,100-row scatter with a
    1,100-entry order splice, and no cycle grows the slab."""

    def __init__(self):
        cfg = slab_delta.make_config()
        self.F, nodes, queues = slab_delta.make_world(
            cfg, np.random.default_rng(0), num_nodes=8
        )
        self.builder = IncrementalBuilder(cfg, "default", queues)
        self.builder.set_nodes(nodes)
        self.cache = DeviceDeltaCache()
        self.submitted = self.removed = 0
        self.turn(6000)

    def turn(self, k: int, apply: bool = True):
        """Remove the k oldest jobs and submit k, assemble, apply: (the
        apply span's `bucket` without its full-field count, None on a full
        upload; scatter programs compiled by the apply).  The device problem
        is held to a fresh upload of the bundle's ground truth, bit for bit."""
        b = self.builder
        if self.submitted:  # (the first turn fills the backlog)
            b.remove_many([f"j{i}" for i in range(self.removed, self.removed + k)])
            self.removed += k
        b.submit_many(
            [
                slab_delta.make_job(self.F, i, f"q{i % 3}")
                for i in range(self.submitted, self.submitted + k)
            ]
        )
        self.submitted += k
        rec = recorder()
        with rec.cycle("turn"):
            bundle, _ = b.assemble_delta()
            if not apply:
                return None
            before = _programs()
            dev = self.cache.apply(bundle)
            compiled = _programs() - before
        for name, dev_arr, host_arr in zip(dev._fields, dev, bundle.materialize()):
            np.testing.assert_array_equal(
                np.asarray(dev_arr), np.asarray(host_arr), err_msg=f"scatter drift in {name}"
            )
        (span,) = _find(rec.last()[-1].root, "devcache_apply")
        bucket = span.args.get("bucket")
        return (None if bucket is None else bucket.rsplit("/", 1)[0]), compiled


def test_through_the_scatter_a_dip_compiles_nothing_and_the_device_holds_the_truth(
    _fresh_recorder,
):
    w = _Served()  # the first apply: a full upload
    assert w.turn(1100) == ("4096/256/4096", 1)  # the steady delta compiles its program
    assert w.turn(1100) == ("4096/256/4096", 0)
    # a dip under the bucket (1,024 would be its own): the program that ran,
    # nothing compiled, and the padded-up scatter is still bit-exact (turn)
    assert w.turn(900) == ("4096/256/4096", 0)
    assert w.turn(1100) == ("4096/256/4096", 0)
    # two buckets under: its own, compiled once, and from then on its own
    assert w.turn(200) == ("256/256/256", 1)
    assert w.turn(200) == ("256/256/256", 0)
    assert w.turn(1100) == ("4096/256/4096", 0)
    assert w.turn(900) == ("4096/256/4096", 0)


def test_through_the_scatter_a_reset_and_a_full_upload_forget_what_ran(_fresh_recorder):
    w = _Served()
    assert w.turn(1100)[0] == "4096/256/4096"
    assert w.turn(900)[0] == "4096/256/4096"
    w.cache.reset()  # device loss: the next apply uploads whole
    assert w.turn(1100) == (None, 0)
    assert w.turn(900)[0] == "1024/256/1024"  # nothing ran on this device problem yet
    assert w.turn(1100)[0] == "4096/256/4096"
    assert w.turn(900)[0] == "1024/256/1024"  # ran before: exactly
    # a skipped bundle breaks the seq chain: a full upload, which forgets too
    w.turn(1100, apply=False)
    assert w.turn(1100) == (None, 0)
    # a burst right after it, then the steady delta two buckets under: its
    # own sizes, never the burst's
    assert w.turn(1100)[0] == "4096/256/4096"
    assert w.turn(200)[0] == "256/256/256"
    assert w.turn(200)[0] == "256/256/256"
