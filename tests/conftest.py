"""Test harness: force an 8-device virtual CPU mesh before jax is imported.

Sharding/collective paths are validated on virtual CPU devices, mirroring how the
driver dry-runs the multi-chip path (xla_force_host_platform_device_count); real-TPU
execution is covered by chip_smoke.py on hardware.
"""

import os

# Unit tests validate logic + sharding on the virtual 8-device CPU mesh, whatever
# the session presets; chip_smoke.py is what runs on hardware.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402

_last_module = [None]


@pytest.fixture(autouse=True)
def _tsan_violations_fail_tests():
    """ARMADA_TSAN=1 (analysis/tsan): any lock-order inversion or
    generation-stale write recorded during a test FAILS it -- the race
    harness turns zombie-worker races into red tests instead of debugging
    sessions.  Zero-cost no-op when the harness is disarmed."""
    from armada_tpu.analysis import tsan

    if not tsan.enabled():
        yield
        return
    tsan.reset()
    yield
    found = tsan.take_violations()
    assert not found, "tsan violations:\n" + "\n".join(found)


@pytest.fixture(params=["chip", "cpu"])
def round_body(request, monkeypatch):
    """Which body of the round the served path compiles: an accelerator's (no
    fit cache, ARMADA_CACHE_SLOTS=0) or XLA:CPU's per-key fit cache, which
    schedule_round derives from the platform and a CPU failover serves."""
    if request.param == "chip":
        monkeypatch.setenv("ARMADA_CACHE_SLOTS", "0")
    else:
        monkeypatch.delenv("ARMADA_CACHE_SLOTS", raising=False)
    return request.param


@pytest.fixture(autouse=True)
def _bound_xla_mappings(request):
    """Drop compiled executables at each module boundary.

    Every round-kernel compile holds ~660 VIRTUAL MEMORY MAPPINGS (XLA:CPU
    code + buffer segments); vm.max_map_count is 65530, so ~100 live
    executables make the next mmap fail -- surfacing as MemoryError with
    gigabytes of RAM free (this killed the full suite at a deterministic
    test twice in round 3).  Clearing per MODULE bounds live mappings while
    keeping within-module recompiles at zero -- unless one module alone comes
    near the limit (tests/test_pool_parallel.py's ~70 round compiles did,
    once the round program grew a few kernels in PR 26: a segfault inside
    the 17th test's compile): then the count itself clears them."""
    module = request.node.nodeid.split("::", 1)[0]
    if (
        _last_module[0] is not None and module != _last_module[0]
    ) or _live_mappings() > _MAX_LIVE_MAPPINGS:
        import jax

        jax.clear_caches()
    _last_module[0] = module
    yield


_MAX_LIVE_MAPPINGS = 40_000  # of vm.max_map_count = 65530; a test may add ~10,000


def _live_mappings() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:  # not Linux: no such limit to watch
        return 0


# --- test tiers --------------------------------------------------------------
# `-m fast` = the <10-minute tier (driver/CI smoke; CLAUDE.md contract):
# wholly-fast modules run in full, every OTHER module contributes its first
# few tests so no component goes unrepresented.  The full gauntlet (no -m)
# is unchanged.  Modules NOT listed here default to the representative rule,
# so a new test module is automatically covered by the fast tier.

# Modules cheap enough to run whole (unit-ish: no kernel compiles at large
# shapes, no multi-second worlds).
_FAST_MODULES = {
    "tests/test_core_keys.py",
    "tests/test_core_resources.py",
    "tests/test_ops_fairness.py",
    "tests/test_ops_fit_packing.py",
    "tests/test_jobdb.py",
    "tests/test_eventlog.py",
    "tests/test_ingest.py",
    "tests/test_server.py",
    "tests/test_authn.py",
    "tests/test_health.py",
    "tests/test_logging_context.py",
    "tests/test_ratelimit.py",
    "tests/test_quarantine.py",
    "tests/test_serve_config.py",
    "tests/test_cli.py",
    "tests/test_short_job_penalty.py",
    "tests/test_submitcheck.py",
    "tests/test_kube_leader.py",
    "tests/test_reports_proxy.py",
    "tests/test_podchecks.py",
    "tests/test_binoculars.py",
    "tests/test_airflow_operator.py",
    "tests/test_metric_events.py",
    "tests/test_submit_brake.py",
    "tests/test_lookout.py",
    # armada-lint self-hosting gate: the fast tier IS the CI path that
    # keeps the tree lint-clean (tools/lint.py; docs/lint.md).  The
    # dataflow engine behind the v2 semantic rules is pinned separately
    # so rule bugs and lattice bugs fail different tests.
    "tests/test_lint.py",
    "tests/test_dataflow.py",
    # soak-subsystem units: histogram-vs-numpy-oracle exactness + the
    # loadgen arrival/mix/lifecycle machinery (no kernel compiles).
    "tests/test_slo_metrics.py",
    "tests/test_loadgen.py",
    # mesh serving plane: kernel compiles, but all at tiny bucket-64 shapes
    # on the 8-device virtual mesh (~30s whole); the fast tier must carry
    # BOTH the churn equality (burst incl.) and the degrade-ladder drill.
    "tests/test_mesh_serving.py",
}
# How many representative tests each remaining module contributes.
_FAST_PICKS = 2
# Kernel-compiling integration modules contribute ONE representative (each
# pick costs a 10-40s XLA:CPU compile on the 1-CPU round host; picks=2
# measured 13:38 for the tier, over the <10-min contract).
_FAST_PICKS_OVERRIDE = {
    "tests/test_market_columnar.py": 1,
    "tests/test_parity_full.py": 1,
    "tests/test_parity.py": 1,
    "tests/test_scheduler_service.py": 1,
    "tests/test_e2e_stack.py": 1,
    "tests/test_golden_traces.py": 1,
    "tests/test_incremental.py": 1,
    "tests/test_home_away.py": 1,
    "tests/test_floating_market.py": 1,
    "tests/test_gang_uniformity.py": 1,
    "tests/test_round_scheduler.py": 1,
    "tests/test_market_pricing.py": 1,
    "tests/test_sidecar.py": 1,
    "tests/test_simulator.py": 1,
    "tests/test_optimiser.py": 1,
    "tests/test_executor_loop.py": 1,
    "tests/test_anti_affinity.py": 1,
    "tests/test_gang_rollback.py": 1,
    "tests/test_round_termination.py": 1,
    "tests/test_decode_compact.py": 1,
    "tests/test_slab_delta.py": 1,
    "tests/test_parallel_sharding.py": 1,
    # 2 representatives + the explicitly-marked ARMADA_PIPELINE=0 parity
    # guard (the sequential escape hatch must not rot out of the fast tier).
    "tests/test_pipeline.py": 2,
    # first 4 = the cheap in-process race-harness drills (the subprocess
    # pipeline/faults-under-ARMADA_TSAN=1 leg stays full-tier only).
    "tests/test_tsan.py": 4,
    # first test = the chaos-under-load smoke (mid-soak device hang: no
    # SLO gap, no tsan violations, nothing dropped/double-leased) -- the
    # soak subsystem's acceptance gate; the clean window + subprocess
    # JSON-contract legs stay full-tier.
    "tests/test_soak.py": 1,
}
# Never in the fast tier (opt-in external deps / native builds).
_FAST_EXCLUDE_MODULES = {
    "tests/test_kind_e2e.py",
    "tests/test_cpp_client.py",
    "tests/test_client_codegen.py",
}


def pytest_collection_modifyitems(config, items):
    seen: dict = {}
    for item in items:
        mod = item.location[0]
        if mod in _FAST_EXCLUDE_MODULES:
            continue
        if mod in _FAST_MODULES:
            item.add_marker(pytest.mark.fast)
            continue
        n = seen.get(mod, 0)
        if n < _FAST_PICKS_OVERRIDE.get(mod, _FAST_PICKS):
            item.add_marker(pytest.mark.fast)
            seen[mod] = n + 1
