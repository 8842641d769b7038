"""Device-loss watchdog: round deadlines, CPU failover state, re-probe.

A scheduler that calls the device inline wedges mid-round, while holding
leadership, if the device hangs -- a zombie leader.  This module bounds
the production serve/sidecar round:

* ``run_with_deadline`` runs the device round in a worker thread under a
  deadline; a timeout ABANDONS the wedged thread (Python threads cannot be
  cancelled) and raises ``RoundTimeout`` to the caller, which re-runs the
  round on the CPU backend from host tables (models.run_round_on_device).
* ``DeviceSupervisor`` is the process-wide degradation state: which backend
  rounds target ("device" = the default jax backend, "cpu" = the explicit
  XLA:CPU failover), consecutive failures, the last fallback reason, and
  the devices the last round's outputs actually lived on.  A failure fires
  the registered reset hooks (device-resident caches must drop state that
  now lives on an unreachable or reset device) and starts a background
  re-probe, which re-promotes to the device after N consecutive healthy
  checks, riding one full slab re-upload (the reset hooks fire again on
  promotion).  The re-probe runs IN THIS PROCESS under
  ``run_with_deadline``: libtpu gives a chip to one process at a time and
  this process owns it, so a child could only fail or come up on the CPU.

The state surfaces in /healthz (core/health.py), scheduler metrics, and
the bench JSON.  Knobs: ``ARMADA_WATCHDOG_S`` (round deadline; 0 =
disabled -- the default outside `serve`, which arms 120s),
``ARMADA_REPROBE_INTERVAL_S`` (default 30; 0 disables auto re-promotion),
``ARMADA_REPROBE_HEALTHY`` (consecutive healthy probes to promote, default
2), ``ARMADA_REPROBE_TIMEOUT_S`` (per-probe budget, default 60).
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from typing import Callable, Optional

from armada_tpu.analysis.tsan import make_lock
from armada_tpu.core.logging import get_logger

_log = get_logger(__name__)


class RoundTimeout(RuntimeError):
    """The device round exceeded the watchdog deadline (device wedge).
    `worker` is the abandoned thread."""

    def __init__(self, message: str, worker: Optional[threading.Thread] = None):
        super().__init__(message)
        self.worker = worker


def _probe_matmul():
    """One small matmul on the default device, fetched to the host; returns
    the device its output lived on."""
    import jax.numpy as jnp
    import numpy as np

    x = jnp.ones((128, 128), jnp.bfloat16)
    y = x @ x
    np.asarray(y[0, 0])
    return next(iter(y.devices()))


# The abandoned worker of a probe that timed out.  While it is still wedged
# on the device no further probe is started: each one would leave another
# thread hung on the same call.
_hung_probe: Optional[threading.Thread] = None


def probe_device(
    timeout_s: float = 60.0, run: Callable = _probe_matmul
) -> tuple[bool, str]:
    """In-process health check of the accelerator this process owns: `run`
    under ``run_with_deadline`` (a hang is still just a timeout).  Healthy
    only when the computation ran on a non-CPU device -- a probe that came
    up on the CPU is no evidence the accelerator is back.  Returns
    (healthy, detail)."""
    global _hung_probe
    if _hung_probe is not None:
        _hung_probe.join(0.05)
        if _hung_probe.is_alive():
            return False, "an earlier probe is still hung on the device"
        _hung_probe = None
    try:
        dev = run_with_deadline(run, timeout_s, what="device probe")
    except RoundTimeout as e:
        _hung_probe = e.worker
        return False, f"probe timed out after {timeout_s:.0f}s"
    except Exception as e:  # noqa: BLE001 - any probe failure is "unhealthy"
        return False, f"{type(e).__name__}: {e}"[:300]
    if dev.platform == "cpu":
        return False, "probe ran on cpu (no accelerator answered)"
    return True, f"{dev.platform}:{dev.device_kind}"


def run_with_deadline(fn: Callable, deadline_s: float, what: str = "device round"):
    """Run fn() in a daemon worker; return its result, re-raise its
    exception, or abandon it and raise RoundTimeout after `deadline_s`.

    An abandoned worker is NOT cancelled (Python threads cannot be): it
    stays wedged on the dead backend and is forgotten.  Callers must only
    pass work whose host-side mutations are safe to abandon mid-flight
    (see models.run_round_on_device for the exact discipline)."""
    box: dict = {}
    # Cycle-trace adoption (ops/trace.py): the worker's spans (kernel
    # dispatch, fetch, shadow thunks) nest under the CALLER's open span,
    # exactly like the inline path -- without this they'd flatten onto the
    # cycle root and double-count as stages while the caller's round span
    # covers the same wall time.  The handle carries the owning trace so
    # an ABANDONED worker that unwedges after its cycle finalized records
    # nothing (the recorder's zombie guard).
    from armada_tpu.ops.trace import recorder as _trace_recorder

    _rec = _trace_recorder()
    _trace_handle = _rec.capture() if _rec.enabled else None

    def _worker():
        _rec.adopt(_trace_handle)
        try:
            box["result"] = fn()
        except BaseException as e:  # noqa: BLE001 - transported to caller
            box["error"] = e

    t = threading.Thread(target=_worker, daemon=True, name=f"watchdog:{what}")
    t.start()
    t.join(deadline_s)
    if t.is_alive():
        raise RoundTimeout(
            f"{what} exceeded {deadline_s:.1f}s deadline", worker=t
        )
    if "error" in box:
        raise box["error"]
    return box["result"]


# Reset hooks live at MODULE level (not on the supervisor instance) so
# reset_supervisor() -- a test/embedding convenience -- cannot silently
# detach long-lived feeds from failover notifications.  Weak references:
# a closed control plane's feed must not be kept alive by the registry.
_reset_hooks: list = []
_hooks_lock = make_lock("watchdog.reset_hooks")


def add_reset_hook(fn: Callable[[], None]) -> None:
    """Register a callback fired on EVERY backend transition (device->cpu
    fallback and cpu->device promotion).  Bound methods are held weakly."""
    with _hooks_lock:
        try:
            ref = weakref.WeakMethod(fn)
        except TypeError:
            ref = weakref.ref(fn)
        _reset_hooks.append(ref)


def fire_reset_hooks() -> None:
    """Public form of the reset-hook broadcast, for OTHER backend-shaped
    transitions than the supervisor's own device<->cpu flips: the mesh
    serving ladder (parallel/serving.py) fires it on every degrade/restore
    rung, so device caches drop state sharded over a mesh that no longer
    exists exactly as they drop state on an unreachable device."""
    _fire_reset_hooks()


# Promotion gate: an optional () -> Optional[str] veto consulted before ANY
# re-promotion toward the accelerator (DeviceSupervisor.promote and the mesh
# restore, parallel/serving.MeshServing.restore).  Registered by the device
# quarantine (scheduler/quarantine.device_quarantine): a device whose rounds
# keep failing output verification must not be re-promoted by a healthy
# matmul probe -- a probe cannot see silent corruption.  Module-level like
# the reset hooks, so reset_supervisor() cannot silently detach it.
_promotion_gate: Optional[Callable[[], Optional[str]]] = None


def set_promotion_gate(fn: Optional[Callable[[], Optional[str]]]) -> None:
    global _promotion_gate
    _promotion_gate = fn


def promotion_blocked() -> Optional[str]:
    """The gate's veto reason, or None (no gate / not blocked).  A raising
    gate never blocks -- quarantine must not be able to wedge recovery --
    but the fail-open is LOGGED loudly: silently re-promoting a device the
    gate was holding down would invert the gate's purpose."""
    gate = _promotion_gate
    if gate is None:
        return None
    try:
        return gate()
    except Exception:
        _log.error(
            "promotion gate raised; failing OPEN (promotion allowed)",
            exc_info=True,
        )
        return None


def _fire_reset_hooks() -> None:
    with _hooks_lock:
        hooks = list(_reset_hooks)
    for ref in hooks:
        fn = ref()
        if fn is None:
            continue
        try:
            fn()
        except Exception:
            _log.warning("device reset hook failed", exc_info=True)
    with _hooks_lock:
        _reset_hooks[:] = [r for r in _reset_hooks if r() is not None]


class DeviceSupervisor:
    """Process-wide device-backend health state."""

    def __init__(self):
        self._lock = make_lock("watchdog.supervisor")
        self.backend = "device"  # "device" = default jax backend
        self.consecutive_failures = 0
        self.fallbacks = 0
        self.promotions = 0
        self.last_failure: Optional[str] = None
        self.last_fallback_ts: Optional[float] = None
        self._deadline_s: Optional[float] = None
        self._armings: dict[int, float] = {}
        self._arm_seq = 0
        self._reprobe_interval_s: Optional[float] = None
        self._healthy_checks: Optional[int] = None
        self._probe = probe_device  # patchable in tests
        self._reprobe_thread: Optional[threading.Thread] = None
        # (platform, device_kind, count) of the devices the last round's
        # output arrays lived on -- read from the arrays, never inferred
        # from jax.default_backend().  None until a round has run.
        self._round_devices: Optional[tuple] = None

    # ------------------------------------------------------------ config ----

    def configure(
        self,
        deadline_s: Optional[float] = None,
        reprobe_interval_s: Optional[float] = None,
        healthy_checks: Optional[int] = None,
    ) -> None:
        """Explicit settings beat the env defaults (serve calls this)."""
        if deadline_s is not None:
            self._deadline_s = float(deadline_s)
        if reprobe_interval_s is not None:
            self._reprobe_interval_s = float(reprobe_interval_s)
        if healthy_checks is not None:
            self._healthy_checks = int(healthy_checks)

    def arm(self, deadline_s: float) -> int:
        """Scoped arming for a control plane living inside a larger
        process: returns a token for disarm().  Reference-counted, NOT
        save/restore -- planes overlap and stop in any order (HA tests run
        leader+follower and kill the leader first), so each registers its
        own deadline and deadline_s() takes the max over live registrations;
        when the last plane disarms, the env default is back in force."""
        with self._lock:
            self._arm_seq += 1
            token = self._arm_seq
            self._armings[token] = float(deadline_s)
        return token

    def disarm(self, token: int) -> None:
        with self._lock:
            self._armings.pop(token, None)

    def deadline_s(self) -> float:
        """The armed round deadline; <= 0 means the watchdog is disabled
        (the default outside serve: tests/bench keep the inline path)."""
        with self._lock:
            if self._armings:
                return max(self._armings.values())
        if self._deadline_s is not None:
            return self._deadline_s
        try:
            return float(os.environ.get("ARMADA_WATCHDOG_S", "0"))
        except ValueError:
            return 0.0

    def reprobe_interval_s(self) -> float:
        if self._reprobe_interval_s is not None:
            return self._reprobe_interval_s
        try:
            return float(os.environ.get("ARMADA_REPROBE_INTERVAL_S", "30"))
        except ValueError:
            return 30.0

    def healthy_checks(self) -> int:
        if self._healthy_checks is not None:
            return self._healthy_checks
        try:
            return int(os.environ.get("ARMADA_REPROBE_HEALTHY", "2"))
        except ValueError:
            return 2

    @property
    def degraded(self) -> bool:
        return self.backend == "cpu"

    # ------------------------------------------------------- transitions ----

    def record_failure(self, reason: str) -> None:
        """A device round failed (timeout/XLA error): degrade to the CPU
        backend, drop device-resident cache state, start the re-probe."""
        with self._lock:
            self.consecutive_failures += 1
            self.fallbacks += 1
            self.last_failure = str(reason)[:500]
            self.last_fallback_ts = time.time()
            was_degraded = self.backend == "cpu"
            self.backend = "cpu"
        _log.error(
            "device round failed (%s); scheduling degraded to the CPU "
            "backend (failure %d)",
            reason,
            self.consecutive_failures,
        )
        # Hooks fire on the TRANSITION and on repeat failures alike: a
        # CPU-mode failure still means the caches' device state is suspect.
        _fire_reset_hooks()
        if not was_degraded or self._reprobe_thread is None:
            self._start_reprobe()

    def record_success(self) -> None:
        with self._lock:
            self.consecutive_failures = 0

    def note_round_devices(self, devices) -> None:
        """Record where a round's outputs live (models._dispatch_body hands
        `result.g_state.devices()`); /healthz and the bench line name the
        platform from this, so a CPU round can never read as a chip's."""
        devices = tuple(devices)
        if not devices:
            return
        first = devices[0]
        seen = (first.platform, first.device_kind, len(devices))
        with self._lock:
            self._round_devices = seen

    def promote(self) -> bool:
        """Re-promote rounds to the device backend; device caches were
        reset, so the next cycle rides one full slab re-upload.  Returns
        False (and stays degraded) while the promotion gate vetoes --
        a quarantined device is only re-admitted by operator clear
        (scheduler/quarantine.py); the re-probe loop keeps polling so the
        clear takes effect on the next healthy probe."""
        blocked = promotion_blocked()
        if blocked:
            _log.warning(
                "device backend probes healthy but promotion is blocked: %s",
                blocked,
            )
            return False
        with self._lock:
            if self.backend == "device":
                return True
            self.backend = "device"
            self.consecutive_failures = 0
            self.promotions += 1
        _log.warning(
            "device backend healthy again: re-promoting (next cycle pays "
            "one full slab re-upload)"
        )
        _fire_reset_hooks()
        return True

    # ----------------------------------------------------------- reprobe ----

    def _start_reprobe(self) -> None:
        interval = self.reprobe_interval_s()
        if interval <= 0:
            return  # operator/tests promote manually
        with self._lock:
            if self._reprobe_thread is not None and self._reprobe_thread.is_alive():
                return
            t = threading.Thread(
                target=self._reprobe_loop, daemon=True, name="device-reprobe"
            )
            self._reprobe_thread = t
        t.start()

    def _reprobe_loop(self) -> None:
        timeout = float(os.environ.get("ARMADA_REPROBE_TIMEOUT_S", "60"))
        healthy = 0
        need = self.healthy_checks()
        while self.degraded:
            time.sleep(self.reprobe_interval_s())
            if not self.degraded:
                break
            ok, detail = self._probe(timeout)
            if ok:
                healthy += 1
                _log.info(
                    "device re-probe healthy (%s): %d/%d", detail, healthy, need
                )
                if healthy >= need and self.promote():
                    break
                # gate-blocked (quarantine): keep polling at the probe
                # cadence so an operator clear promotes on the next pass
            else:
                healthy = 0
                _log.info("device re-probe still failing: %s", detail)
        with self._lock:
            self._reprobe_thread = None

    # ------------------------------------------------------------ export ----

    def snapshot(self) -> dict:
        # deadline_s() takes the lock itself (the armings map): resolve it
        # BEFORE entering, the lock is not reentrant.
        deadline = self.deadline_s()
        with self._lock:
            platform, device_kind, device_count = self._round_devices or (
                None, None, 0
            )
            return {
                "backend": self.backend,
                "platform": platform,
                "device_kind": device_kind,
                "device_count": device_count,
                "consecutive_failures": self.consecutive_failures,
                "fallbacks": self.fallbacks,
                "promotions": self.promotions,
                "last_fallback_reason": self.last_failure,
                "last_fallback_ts": self.last_fallback_ts,
                "watchdog_deadline_s": deadline,
            }


_SUPERVISOR = DeviceSupervisor()


def supervisor() -> DeviceSupervisor:
    return _SUPERVISOR


def reset_supervisor() -> DeviceSupervisor:
    """Fresh supervisor state (tests).  Reset hooks are module-level and
    survive; in-flight reprobe threads of the old instance die with its
    `degraded` flag flipping false-y only on their next poll, so tests
    should keep reprobe_interval_s small or 0."""
    global _SUPERVISOR
    _SUPERVISOR = DeviceSupervisor()
    return _SUPERVISOR


def data_device():
    """Where device-resident problem data should live right now: None =
    the default jax backend; an explicit jax CPU device while degraded
    (models/slab.py routes every upload through this, so the delta cache
    keeps its O(delta) scatter path during CPU-failover operation)."""
    if not _SUPERVISOR.degraded:
        return None
    import jax

    return jax.devices("cpu")[0]
