"""Chip bring-up contracts that a CPU host can check (seconds, no compile):
the compile cache is placed from outside, chip_smoke.py refuses a machine
without a TPU, the device re-probe runs in-process and refuses a CPU, and
/healthz names the platform rounds ran on.  The chip itself is checked by
`python chip_smoke.py` on hardware.
"""

import json
import os
import subprocess
import sys
import threading
import time
import types
import urllib.request

from armada_tpu.core import platform as platform_mod
from armada_tpu.core import watchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- cache ----


def test_cache_helper_sets_nothing_when_placed_from_outside(monkeypatch, tmp_path):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    assert platform_mod.compilation_cache_dir() is None
    before = jax.config.jax_compilation_cache_dir
    platform_mod.enable_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "outside").exists()  # jax creates it, not us


def test_cache_helper_defaults_to_the_checkout(monkeypatch):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = os.path.join(REPO, ".jax_cache")
    assert platform_mod.compilation_cache_dir() == expected
    before = jax.config.jax_compilation_cache_dir
    try:
        platform_mod.enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == expected
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ----------------------------------------------------------- chip_smoke ----


def test_chip_smoke_refuses_a_cpu_before_building_a_world():
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "cpu" in out.stderr.lower()
    assert '"ok"' not in out.stdout
    assert "synthetic world" not in out.stdout
    assert time.monotonic() - t0 < 60


# ---------------------------------------------------------------- probe ----


def _fake_device(platform):
    return types.SimpleNamespace(platform=platform, device_kind=f"fake {platform}")


def test_probe_runs_in_process_and_rejects_a_cpu_device():
    # the real probe: this host's default device IS a cpu
    ok, detail = watchdog.probe_device(30.0)
    assert not ok and "cpu" in detail
    ok, detail = watchdog.probe_device(5.0, run=lambda: _fake_device("tpu"))
    assert ok and detail == "tpu:fake tpu"


def test_probe_times_out_on_a_hung_callable_and_reports_errors():
    release = threading.Event()
    try:
        t0 = time.monotonic()
        ok, detail = watchdog.probe_device(0.2, run=release.wait)
        assert not ok and "timed out" in detail
        assert time.monotonic() - t0 < 5
        # while that worker is wedged, no second one is stacked behind it
        ok, detail = watchdog.probe_device(0.2, run=lambda: _fake_device("tpu"))
        assert not ok and "still hung" in detail
    finally:
        release.set()
    assert _wait_until(
        lambda: watchdog.probe_device(5.0, run=lambda: _fake_device("tpu"))[0]
    )

    def boom():
        raise RuntimeError("backend gone")

    ok, detail = watchdog.probe_device(5.0, run=boom)
    assert not ok and "backend gone" in detail


def _wait_until(pred, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


def test_degraded_supervisor_refuses_a_cpu_probe_then_promotes_on_a_chip():
    sup = watchdog.reset_supervisor()
    try:
        sup.configure(reprobe_interval_s=0.02, healthy_checks=2)
        probes = []
        real_probe = sup._probe
        device = [_fake_device("cpu")]

        def probe(timeout_s):
            probes.append(device[0].platform)
            return real_probe(timeout_s, run=lambda: device[0])

        sup._probe = probe
        sup.record_failure("drill")
        assert sup.degraded
        # a probe that comes up on the cpu is no evidence: never promotes
        assert _wait_until(lambda: len(probes) >= 4)
        assert sup.degraded and sup.promotions == 0
        device[0] = _fake_device("tpu")
        assert _wait_until(lambda: not sup.degraded)
        assert sup.snapshot()["promotions"] == 1
    finally:
        watchdog.reset_supervisor()


# -------------------------------------------------------------- healthz ----


def test_healthz_device_block_names_the_platform_rounds_ran_on():
    import jax.numpy as jnp

    from armada_tpu.core.health import FunctionChecker, HealthServer

    sup = watchdog.reset_supervisor()
    srv = HealthServer(0)
    try:
        srv.checker.add(FunctionChecker(lambda: None, "ok"))
        srv.device_status = sup.snapshot
        url = f"http://127.0.0.1:{srv.port}/healthz"
        with urllib.request.urlopen(url, timeout=10) as r:
            dev = json.loads(r.read())["device"]
        # before any round: nothing is claimed
        assert dev["platform"] is None and dev["device_count"] == 0
        out = jnp.zeros(1)
        sup.note_round_devices(out.devices())
        with urllib.request.urlopen(url, timeout=10) as r:
            dev = json.loads(r.read())["device"]
        first = next(iter(out.devices()))
        assert dev["platform"] == first.platform == "cpu"
        assert dev["device_kind"] == first.device_kind
        assert dev["device_count"] == 1
        assert dev["backend"] == "device" and dev["fallbacks"] == 0
    finally:
        srv.stop()
        watchdog.reset_supervisor()


# ----------------------------------------------------- one process/chip ----


def test_converter_workers_are_pinned_to_the_cpu():
    """The converter forkserver's workers get JAX_PLATFORMS=cpu whatever the
    serving process runs on; the parent's own variable is left alone."""
    code = (
        "import os\n"
        "from armada_tpu.ingest import shards\n"
        "pool = shards._convert_pool(1)\n"
        "print('worker=' + str(pool.submit(os.getenv, 'JAX_PLATFORMS').result(60)))\n"
        "print('parent=' + str(os.environ.get('JAX_PLATFORMS')))\n"
        "pool.shutdown()\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "JAX_PLATFORMS": "tpu,cpu", "PYTHONPATH": REPO},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "worker=cpu" in out.stdout
    assert "parent=tpu,cpu" in out.stdout
