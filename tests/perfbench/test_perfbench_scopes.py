"""Device time by the program's named scopes (perfbench/harness/scopes.py):
on a small hand-checked trace, on the recorded chip trace, and on a cut of a
chip profile in the profiler's own format (data/scoped_programs.xplane.pb)."""

import copy
import json
import os

import pytest

from perfbench_tiny import ROOT  # noqa: F401  (puts the repo root on sys.path)

from perfbench.harness import scopes, tracered

M = tracered.CYCLE_MARKER
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LOOP = "jit(_schedule_round_jit)/armada.round/armada.round.loop/while"
ROUND = "jit__schedule_round_jit(1)"

# Two cycles of 1.0 s, 0.2 s apart.  Per cycle: the scatter program, the
# round program with its loop's `while` (0.4 s), the compaction, the
# verification.  Inside the first call of the loop: a select op; a
# conditional the profiler gives no scope, holding a fit op and a commit op
# that XLA sank into its branch; an unscoped copy; a window op.  Inside the
# second: select, fit, and `fusion.1`, a name the scatter program uses too.
SCOPED = {
    "device": {
        "/device:TPU:0": [
            ["fusion.1", 0.10, 0.05],
            ["while.33", 0.30, 0.40],
            ["fusion.212", 0.31, 0.10],
            ["cond.42", 0.45, 0.20],
            ["fusion.50", 0.46, 0.12],  # inside cond.42
            ["fusion.51", 0.60, 0.04],  # inside cond.42
            ["copy.9", 0.66, 0.02],
            ["fusion.60", 0.685, 0.01],
            ["while.7", 0.72, 0.03],
            ["fusion.9", 0.80, 0.05],
            ["fusion.1", 1.30, 0.05],
            ["while.33", 1.50, 0.40],
            ["fusion.212", 1.51, 0.10],
            ["fusion.50", 1.70, 0.10],
            ["fusion.1", 1.80, 0.05],
            ["while.7", 1.92, 0.03],
            ["fusion.9", 2.00, 0.05],
            ["fusion.9", 2.30, 0.05],  # after the last cycle's end: outside
        ]
    },
    "host": [[M, 0.0, 1.0], [M, 1.2, 1.0], ["sidecar_round", 0.25, 0.75]],
    "scope_of": {
        "jit_apply_delta(7)": {"fusion.1": "jit(apply_delta)/armada.scatter/scatter"},
        ROUND: {
            "fusion.212": f"{LOOP}/body/select/lt",
            "fusion.50": f"{LOOP}/body/fit/cond/branch_1_fun/reduce_sum",
            "fusion.51": f"{LOOP}/body/commit/mul",
            "fusion.60": f"{LOOP}/body/window/cond/branch_1_fun/gather",
            "fusion.1": f"{LOOP}/body/window/gather",
        },
        "jit_compact_result(3)": {"fusion.3": "jit(compact_result)/armada.compact/scatter-add"},
        "jit__verify_kernel_impl(5)": {"fusion.9": "jit(_verify_kernel_impl)/armada.verify/gather"},
        "jit_squeeze(9)": {"squeeze.1": "jit(squeeze)/squeeze"},
    },
    "modules": {
        "/device:TPU:0": [
            ["jit_apply_delta(7)", 0.10, 0.05],
            [ROUND, 0.29, 0.42],
            ["jit_compact_result(3)", 0.72, 0.03],
            ["jit__verify_kernel_impl(5)", 0.80, 0.05],
            ["jit_apply_delta(7)", 1.30, 0.05],
            [ROUND, 1.49, 0.42],
            ["jit_compact_result(3)", 1.92, 0.03],
            ["jit_squeeze(9)", 1.96, 0.01],
            ["jit__verify_kernel_impl(5)", 2.00, 0.05],
            ["jit__verify_kernel_impl(5)", 2.30, 0.05],
        ]
    },
}

PHASE_FIELDS = [f"round_{p}_device_s" for p in scopes.PHASES + ("loop_other",)]


def test_phases_partition_the_kernel_time():
    r = scopes.reduce(SCOPED)
    kernel = tracered.reduce_trace(SCOPED)["kernel_device_s_per_cycle"]
    assert kernel == pytest.approx(0.4)
    assert sum(r[f] for f in PHASE_FIELDS) == pytest.approx(kernel, abs=1e-9)
    # select: two 0.10 ops; fit: the two fusion.50 and the conditional's own
    # 0.04; commit: the op sunk into the conditional; window: fusion.60 and
    # the round program's fusion.1 (not the scatter program's)
    want = {"select": 0.20, "fit": 0.12 + 0.04 + 0.10, "commit": 0.04, "window": 0.01 + 0.05}
    # the rest: the unscoped copy, and the while's own time in both calls
    want["loop_other"] = 0.02 + (0.40 - 0.10 - 0.20 - 0.02 - 0.01) + (0.40 - 0.25)
    for phase, seconds in want.items():
        assert r[f"round_{phase}_device_s"] == pytest.approx(seconds / 2, abs=1e-9), phase
    assert r["round_loop_ops_total"] == 6 + 3
    assert [name for name, _ in r["round_loop_other_ops"]] == ["while.33", "copy.9"]


def test_the_innermost_event_takes_the_time():
    """A commit op inside a conditional of fit is commit's; the conditional
    keeps only what no nested event covers."""
    events = [
        ["while.33", 0.0, 1.0],
        ["cond.42", 0.1, 0.5],
        ["fusion.50", 0.1, 0.2],
        ["fusion.51", 0.3, 0.1],
    ]
    scope = {"cond.42": f"{LOOP}/body/fit/cond", "fusion.51": f"{LOOP}/body/commit/mul"}
    got = {name: (ns, depth, phase) for name, ns, depth, phase in scopes._phases(events, scope)}
    assert got["fusion.51"] == (100_000_000, 2, "commit")
    assert got["cond.42"] == (200_000_000, 1, "fit")
    assert got["fusion.50"] == (200_000_000, 2, "fit")  # unscoped: the conditional's
    assert got["while.33"] == (500_000_000, 0, "loop_other")


def test_a_conditional_without_a_scope_takes_the_phase_of_most_of_its_time():
    scope = {"a": f"{LOOP}/body/fit/gather", "b": f"{LOOP}/body/commit/mul"}

    def phase_of_cond(a_s, b_s):
        events = [["while.33", 0.0, 1.0], ["cond.58", 0.1, 0.5], ["a", 0.1, a_s], ["b", 0.1 + a_s, b_s]]
        return {name: ph for name, _, _, ph in scopes._phases(events, scope)}["cond.58"]

    assert phase_of_cond(0.3, 0.1) == "fit"
    assert phase_of_cond(0.1, 0.3) == "commit"


def test_phase_and_program_of_a_path():
    assert scopes.phase_of(f"{LOOP}/body/fit/cond/branch_1_fun/cond/branch_0_fun/gather") == "fit"
    assert scopes.phase_of(f"{LOOP}/body/window/cond/branch_1_fun/select/add") == "window"
    assert scopes.phase_of(LOOP) == "loop_other"
    assert scopes.phase_of(f"{LOOP}/cond/lt") == "loop_other"
    assert scopes.phase_of("jit(_schedule_round_jit)/armada.round/armada.round.evict/select/add") == "loop_other"
    assert scopes.program_of(f"{LOOP}/body/fit") == "armada.round"
    assert scopes.program_of("jit(apply_delta)/armada.scatter/jit(_where)/select_n") == "armada.scatter"
    assert scopes.program_of("jit(squeeze)/squeeze") == ""


def test_device_seconds_per_program():
    r = scopes.reduce(SCOPED)
    assert r["scatter_device_s"] == pytest.approx(0.05)
    assert r["round_program_device_s"] == pytest.approx(0.42)
    assert r["compact_device_s"] == pytest.approx(0.03)
    assert r["verify_device_s"] == pytest.approx(0.05)  # the call after the window is out
    assert r["explain_device_s"] == 0.0
    assert r["unowned_device_s"] == pytest.approx(0.01 / 2)


def test_no_scopes_no_new_fields_and_the_old_ones_unchanged():
    plain = {key: SCOPED[key] for key in ("device", "host")}
    assert scopes.reduce(plain) == dict.fromkeys(scopes.FIELDS)
    assert tracered.reduce_trace(copy.deepcopy(SCOPED)) == tracered.reduce_trace(plain)
    assert scopes.reduce({"device": {}, "host": SCOPED["host"], "scope_of": SCOPED["scope_of"]}) == dict.fromkeys(
        scopes.FIELDS
    )


RECORDED = os.path.join(DATA, "recorded_trace.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded chip trace in the tree")
def test_the_recorded_trace_reduces_as_before_with_scopes_beside_it():
    with open(RECORDED, encoding="utf-8") as f:
        trace = json.load(f)["trace"]
    before = tracered.reduce_trace(trace)
    scoped = dict(trace, scope_of={}, modules={})
    assert tracered.reduce_trace(scoped) == before  # to the last digit, every field
    assert scopes.reduce(trace) == dict.fromkeys(scopes.FIELDS)


XPLANE = os.path.join(DATA, "scoped_programs.xplane.pb")


def test_load_reads_the_profilers_own_format():
    """A cut of a chip profile (cluster.steady-1k, TPU v5 lite, libtpu
    0.0.34): the device plane's stat names, the `XLA Modules` line, and the
    metadata of a few operations, `fusion.1` among them in five programs."""
    got = scopes.load(XPLANE)
    calls = got["modules"]["/device:TPU:0"]
    assert len(calls) == 21 and set(got["modules"]) == {"/device:TPU:0"}
    round_call = "jit__schedule_round_jit(3473967640115435020)"
    ops = got["scope_of"][round_call]
    assert ops["fusion.218"] == f"{LOOP}/body/select/lt"
    assert ops["select_reduce_fusion.1"] == f"{LOOP}/body/fit/gather"
    assert ops["fusion.1"] == f"{LOOP}/body/window/cond/branch_1_fun/gather"
    assert ops["and_reduce_fusion.12"] == LOOP  # made by the compiler: the while's path
    # the profiler gives control flow no path, nor some operations XLA made
    assert not {"while.39", "cond.58", "broadcast_select_fusion"} & set(ops)
    programs = {name: scopes.program_scope(got["scope_of"][name]) for name, _, _ in calls}
    assert programs[round_call] == "armada.round"
    assert programs["jit_compact_result(1863465557166542070)"] == "armada.compact"
    assert programs["jit__verify_kernel_impl(18150178616598933628)"] == "armada.verify"
    assert programs["jit_apply_delta(8451705991172529838)"] == "armada.scatter"
    assert programs["jit_squeeze(892887315100023215)"] == ""


def test_load_keeps_the_clock_of_the_jax_reader():
    """Program calls are on the clock and the whole-nanosecond grid the
    operations have (`tracered.load_xplane`, through jax's reader)."""
    from jax.profiler import ProfileData

    plane = next(p for p in ProfileData.from_file(XPLANE).planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == scopes.MODULE_LINE)
    want = [[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9] for e in line.events]
    assert scopes.load(XPLANE)["modules"]["/device:TPU:0"] == want


def test_command_line_reads_a_profile(capsys):
    """The command prints the device reduction and the scopes' fields; this
    cut has no operation events and no host spans, so both are empty."""
    assert scopes.main([XPLANE]) == 0
    assert json.loads(capsys.readouterr().out) == {}
