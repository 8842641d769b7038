"""Mesh serving plane: process-global mesh state + the chip-loss ladder.

`parallel/mesh.py` proves the sharded round; this module makes the REAL
steady cycle run on it.  ``MeshServing`` is the process-wide answer to
"how many chips do rounds target right now", mirroring the watchdog's
DeviceSupervisor (core/watchdog.py) exactly one rung higher on the degrade
ladder::

    full mesh (serve --mesh N / ARMADA_MESH)
      -> smaller mesh      (chip loss: halve, re-shard, one slab re-upload)
      -> single device     (mesh exhausted: the plain single-chip path)
      -> XLA:CPU failover  (the watchdog's existing rung)

A degrade fires the SAME module-level reset hooks the watchdog uses
(core/watchdog.fire_reset_hooks): every feed replaces its device caches,
so the next cycle's apply() is one full slab upload sharded onto the
CURRENT mesh -- the generation/identity machinery that already makes
device->cpu flips race-safe (zombie watchdog workers only ever touch the
orphaned cache of their own round) covers mesh re-shards for free.

Divisibility is a BUILD-time property, never a serve-time error: the
incremental builders round their node-axis pad bucket to
``mesh_axis_multiple()`` (models/incremental._node_bucket) and the generic
``shard_problem`` pads inert lanes, so geometric slab growth can never
trip ``_check_divisible`` mid-serve.

Restore mirrors the watchdog re-probe: after a degrade, a background
in-process probe (core/watchdog.probe_device: this process owns the chips,
so no child could ask them anything) re-arms the FULL mesh after N
consecutive healthy checks, riding one full re-shard upload.  Knobs are
shared with the watchdog:
``ARMADA_REPROBE_INTERVAL_S`` (0 disables -- tests/operators call
``restore()`` themselves), ``ARMADA_REPROBE_HEALTHY``,
``ARMADA_REPROBE_TIMEOUT_S``.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from armada_tpu.analysis.tsan import make_lock
from armada_tpu.core.logging import get_logger

_log = get_logger(__name__)


class MeshServing:
    """Process-wide mesh serving state (see module docstring)."""

    def __init__(self):
        self._lock = make_lock("parallel.mesh_serving")
        self._requested = 0  # serve --mesh N / ARMADA_MESH (0 = off)
        self._active = 0  # current ladder rung (devices rounds target)
        self._meshes: dict = {}  # active count -> constructed Mesh
        self.degrades = 0
        self.restores = 0
        self.last_degrade_reason: Optional[str] = None
        self.last_degrade_ts: Optional[float] = None
        self._restore_thread: Optional[threading.Thread] = None
        self._probe = None  # patchable in tests; default watchdog.probe_device

    # ------------------------------------------------------------ config ----

    def configure(self, n_devices: int) -> None:
        """Arm (n >= 2) or disarm (0/1) mesh serving.  Called by serve
        before the feed builds its device caches; idempotent."""
        n = max(0, int(n_devices))
        with self._lock:
            self._requested = n
            self._active = n
            self._meshes = {}

    def enabled(self) -> bool:
        """Mesh serving is armed (regardless of the current ladder rung or
        the watchdog backend) -- cheap, touches no jax state."""
        return self._requested >= 2

    def device_count(self) -> int:
        """Devices the current rung targets (0 when off/exhausted).  A
        display/trace number; `serving_mesh()` is the placement truth."""
        with self._lock:
            return self._active if self._requested >= 2 and self._active >= 2 else 0

    def axis_multiple(self) -> int:
        """The node-axis alignment every problem/slab axis must honour:
        the CONFIGURED mesh size (monotone over the whole ladder -- every
        smaller rung is reached by halving, so a multiple of the configured
        size divides every rung)."""
        return self._requested if self._requested >= 2 else 1

    # ------------------------------------------------------------- meshes ---

    def serving_mesh(self):
        """The Mesh rounds should run on right now, or None (mesh off or
        ladder exhausted).  Raises when fewer devices are visible than the
        configured mesh needs.  First call per rung touches jax.devices()."""
        with self._lock:
            n = self._active if self._requested >= 2 else 0
        return self._mesh_for(n)

    def _mesh_for(self, n: int):
        """Construct (or return the cached) Mesh for a SPECIFIC rung --
        callers that just set a rung pass it explicitly, so a concurrent
        restore() can never hand them a different (larger) mesh than the
        one their transition decided on."""
        if n < 2:
            return None
        mesh = self._meshes.get(n)
        if mesh is not None:
            return mesh
        import jax

        from armada_tpu.parallel.mesh import make_mesh

        avail = len(jax.devices())
        if n > avail:
            # Never serve on a smaller mesh than asked for without saying
            # so: the operator sized the slab for n chips' memory.
            raise RuntimeError(
                f"mesh serving requested {n} devices, {avail} visible"
            )
        mesh = make_mesh(
            jax.devices()[:n], node_shards=n, job_shards=1
        )
        with self._lock:
            self._meshes[n] = mesh
        return mesh

    # -------------------------------------------------------- transitions ---

    def degrade(self, reason: str):
        """One rung down the ladder (chip loss): halve the mesh, fire the
        device-cache reset hooks, start the restore probe.  Returns the new
        (smaller) Mesh for the caller's immediate re-run, or None when the
        ladder is exhausted (single device next, then the watchdog's CPU
        failover)."""
        with self._lock:
            if self._requested < 2 or self._active < 2:
                return None
            self._active = (
                self._active // 2 if self._active % 2 == 0 else 1
            )
            self.degrades += 1
            self.last_degrade_reason = str(reason)[:300]
            self.last_degrade_ts = time.time()
            new_n = self._active
        _log.error(
            "mesh round failed (%s): degrading to %d devices (one full "
            "slab re-shard)", reason, new_n,
        )
        from armada_tpu.core.watchdog import fire_reset_hooks

        fire_reset_hooks()
        self._start_restore_probe()
        # The rung THIS transition decided on -- never re-read _active: a
        # fast concurrent restore() (drill-speed probes) would hand the
        # caller back the full mesh that just failed.
        return self._mesh_for(new_n)

    def restore(self) -> bool:
        """Back to the full configured mesh (probe-driven or operator);
        device caches re-shard on their next apply via the reset hooks.
        Returns False (staying on the smaller rung) while the watchdog
        promotion gate vetoes -- a quarantined chip must not rejoin the
        mesh until the operator clears it (scheduler/quarantine.py)."""
        from armada_tpu.core.watchdog import promotion_blocked

        blocked = promotion_blocked()
        if blocked:
            _log.warning(
                "mesh probes healthy but restore is blocked: %s", blocked
            )
            return False
        with self._lock:
            if self._requested < 2 or self._active >= self._requested:
                return True
            self._active = self._requested
            self.restores += 1
        _log.warning(
            "mesh healthy again: restoring the full %d-device mesh (next "
            "cycle pays one full slab re-upload)", self._requested,
        )
        from armada_tpu.core.watchdog import fire_reset_hooks

        fire_reset_hooks()
        return True

    # ------------------------------------------------------------ reprobe ---

    def _start_restore_probe(self) -> None:
        from armada_tpu.core.watchdog import supervisor

        if supervisor().reprobe_interval_s() <= 0:
            return  # operator/tests restore manually
        with self._lock:
            if self._restore_thread is not None and self._restore_thread.is_alive():
                return
            t = threading.Thread(
                target=self._restore_loop, daemon=True, name="mesh-restore"
            )
            self._restore_thread = t
        t.start()

    def _restore_loop(self) -> None:
        from armada_tpu.core.watchdog import probe_device, supervisor

        sup = supervisor()
        probe = self._probe or probe_device
        timeout = float(os.environ.get("ARMADA_REPROBE_TIMEOUT_S", "60"))
        healthy = 0
        need = sup.healthy_checks()
        while True:
            with self._lock:
                done = self._requested < 2 or self._active >= self._requested
            if done:
                break
            time.sleep(sup.reprobe_interval_s())
            ok, detail = probe(timeout)
            if ok:
                healthy += 1
                _log.info("mesh re-probe healthy (%s): %d/%d", detail, healthy, need)
                if healthy >= need and self.restore():
                    break
                # gate-blocked (quarantine): keep polling so an operator
                # clear restores on the next healthy pass
            else:
                healthy = 0
                _log.info("mesh re-probe still failing: %s", detail)
        with self._lock:
            self._restore_thread = None

    # ------------------------------------------------------------- export ---

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "requested": self._requested,
                # 0 = mesh off or ladder exhausted (single-device rounds)
                "devices": (
                    self._active
                    if self._requested >= 2 and self._active >= 2
                    else 0
                ),
                "degrades": self.degrades,
                "restores": self.restores,
                "last_degrade_reason": self.last_degrade_reason,
                "last_degrade_ts": self.last_degrade_ts,
            }


_MESH_SERVING = MeshServing()


def mesh_serving() -> MeshServing:
    return _MESH_SERVING


def reset_mesh_serving() -> MeshServing:
    """Fresh state (tests).  Like watchdog.reset_supervisor: an in-flight
    restore thread of the old instance exits on its next poll."""
    global _MESH_SERVING
    _MESH_SERVING = MeshServing()
    return _MESH_SERVING


def mesh_axis_multiple() -> int:
    """Alignment the problem builders apply to sharded axes (1 = off).
    Cheap (no jax): safe on every assemble."""
    return _MESH_SERVING.axis_multiple()


def dryrun_round(n_devices: int) -> int:
    """One sharded round on tiny shapes over an n-device (nodes x jobs)
    mesh -- the driver's multi-chip compile check (__graft_entry__.py
    delegates here; this is the ONE home of the dry-run's mesh dispatch).
    Returns the scheduled-member count (> 0 asserted)."""
    import jax

    from armada_tpu.models.synthetic import synthetic_problem
    from armada_tpu.parallel.mesh import make_mesh, sharded_schedule_round

    devices = jax.devices("cpu")[:n_devices]
    job_shards = 2 if n_devices % 2 == 0 and n_devices >= 4 else 1
    node_shards = n_devices // job_shards
    mesh = make_mesh(devices, node_shards=node_shards, job_shards=job_shards)

    pad = 2 * node_shards * job_shards
    problem, meta = synthetic_problem(
        num_nodes=max(16, pad),
        num_gangs=max(64, 4 * pad),
        num_queues=4,
        num_runs=max(8, pad),
        max_gang_cardinality=2,
        global_burst=16,
        perq_burst=8,
        node_pad_to=pad,
        gang_pad_to=pad,
    )
    result = sharded_schedule_round(
        problem,
        mesh,
        num_levels=meta["num_levels"],
        max_slots=meta["max_slots"],
        slot_width=meta["slot_width"],
    )
    jax.block_until_ready(result)  # lint: allow(fetch-not-barrier) -- dry-run on the virtual CPU mesh; the scalar fetch below is the real sync
    scheduled = int(result.scheduled_count)
    assert scheduled > 0, "dry run scheduled nothing"
    return scheduled
