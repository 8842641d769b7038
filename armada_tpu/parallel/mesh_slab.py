"""Node-axis-sharded slab cache: the steady cycle's O(delta) path on a mesh.

`models/slab.DeviceDeltaCache` keeps the problem device-resident and
updated by one jitted scatter program per cycle; this subclass keeps every
slab array under its mesh NamedSharding (parallel/mesh.problem_shardings)
instead of on one chip:

* full uploads `jax.device_put` each field WITH its sharding (a 50k-node
  slab lands N/M rows per chip -- no single-chip staging copy);
* the scatter program is compiled with `out_shardings` pinned to the slab
  layout, so an O(delta) apply (and the shadow pipeline's
  `scatter_content` prefetch) scatters replicated dirty rows into the
  sharded slab WITHOUT gathering it -- GSPMD left to its own devices may
  choose a gather+scatter+reshard, which would put the whole 1M-row slab
  on one chip's HBM every cycle;
* TRANSFER_STATS reports per-chip bytes for sharded fields
  (models/xfer.py `up_chip_bytes`).

Mesh resolution is LAZY (first apply, i.e. inside the watchdog deadline)
and consults the serving
ladder (parallel/serving.py) plus the watchdog: while the supervisor is
degraded to CPU this cache behaves exactly like its base class, so the
reset-hook machinery can keep swapping cache instances without caring
which rung the ladder sits on.

Divisibility is guaranteed at build time (the builders align their node
bucket to `mesh_axis_multiple()`); `_full_upload` asserts it so a
misaligned problem fails loudly as a build bug, not a GSPMD shape error
three frames deep.
"""

from __future__ import annotations

import numpy as np

from armada_tpu.models.slab import DeviceDeltaCache, _make_apply
from armada_tpu.models.xfer import TRANSFER_STATS

# One sharding-pinned scatter program PER MESH, shared by every cache
# instance on it -- the mesh analog of slab.py's module-level _APPLY.  The
# feed builds one cache per pool and REPLACES all of them on every reset
# hook (watchdog flip, each ladder rung, restore, resync); a per-instance
# jit would re-trace P pools x every transition, right in the recovery
# window.  MeshServing caches its Mesh per rung, so the same key returns
# on restore and the dict stays ladder-sized.
_SHARDED_APPLY: dict = {}


def _sharded_apply_for(mesh, shardings):
    fn = _SHARDED_APPLY.get(mesh)
    if fn is None:
        fn = _SHARDED_APPLY[mesh] = _make_apply(out_shardings=shardings)
    return fn


class MeshDeviceDeltaCache(DeviceDeltaCache):
    """DeviceDeltaCache whose resident problem is node-axis-sharded."""

    def __init__(self, serving=None):
        super().__init__()
        if serving is None:
            from armada_tpu.parallel.serving import mesh_serving

            serving = mesh_serving()
        self._serving = serving
        self._mesh = None
        self._shardings = None  # field name -> NamedSharding
        self._repl = None  # replicated NamedSharding for unnamed payloads
        self._field_shards = None  # field name -> shard count (for stats)
        self._sharded_apply = None
        # True while a _sync_mesh entry resolved "no mesh" -- pins the
        # decision for the whole apply()/scatter_content() call.
        self._none_sticky = False

    # ------------------------------------------------------------ resolve ---

    def _ensure_mesh(self):
        """The mesh this cache places on, or None (plain base behavior:
        serving disarmed/exhausted, or the watchdog degraded to CPU --
        there the base `_to_device` routes through data_device()).

        STICKY once resolved -- in EITHER direction: every
        `_to_device`/`_apply_fn` call within one apply() must see the same
        mesh (or the same absence of one), or a ladder transition / re-probe
        promotion landing mid-upload would mix old-placement residents with
        a new-placement program and force a silent GSPMD gather.
        Transitions are detected only at apply/scatter ENTRY (`_sync_mesh`,
        which re-resolves and re-pins) -- and normally never even there,
        because every transition fires the reset hooks that REPLACE this
        cache outright."""
        if self._mesh is not None:
            return self._mesh
        if self._none_sticky:
            return None
        from armada_tpu.core.watchdog import supervisor

        if supervisor().degraded:
            return None
        mesh = self._serving.serving_mesh()
        if mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        from armada_tpu.parallel.mesh import problem_shardings

        sh = problem_shardings(mesh)
        self._mesh = mesh
        self._shardings = dict(zip(sh._fields, sh))
        self._repl = NamedSharding(mesh, P())
        self._field_shards = {
            name: int(
                np.prod([mesh.shape[ax] for ax in (s.spec or ()) if ax] or [1])
            )
            for name, s in self._shardings.items()
        }
        self._sharded_apply = _sharded_apply_for(mesh, sh)
        return self._mesh

    def _sync_mesh(self) -> None:
        """Entry guard for apply()/scatter_content(): if the serving ladder
        moved since this cache resolved its mesh (a restore() racing the
        reset-hook replacement, or a degrade the hooks have not reached
        yet), drop ALL device-resident state and re-resolve -- a scatter
        compiled for the new mesh over residents sharded on the old one
        would force GSPMD to gather/reshard the whole slab silently, the
        exact hazard this module exists to prevent.  The forced full
        re-upload is the same cost every ladder transition already budgets.

        The resolution made here is PINNED for the duration of the call
        (`_none_sticky` + the resolved `_mesh`): per-field `_ensure_mesh`
        probes must not re-consult the supervisor/ladder, or a re-probe
        promotion landing mid-full-upload would shard the later fields of a
        problem whose earlier fields already landed on the CPU data_device."""
        from armada_tpu.core.watchdog import supervisor

        if self._mesh is not None:
            cur = None if supervisor().degraded else self._serving.serving_mesh()
            if cur is not self._mesh:
                self.reset()
                self._mesh = None
                self._shardings = None
                self._repl = None
                self._field_shards = None
                self._sharded_apply = None
        self._none_sticky = False
        self._none_sticky = self._ensure_mesh() is None

    def apply(self, bundle):
        self._sync_mesh()
        return super().apply(bundle)

    def scatter_content(self, **kwargs) -> bool:
        self._sync_mesh()
        return super().scatter_content(**kwargs)

    @property
    def mesh_devices(self) -> int:
        """Devices the resident slab is sharded over (0 = single-device)."""
        return 0 if self._mesh is None else int(self._mesh.devices.size)

    # -------------------------------------------------------- base hooks ----

    def _apply_fn(self):
        if self._ensure_mesh() is None:
            return super()._apply_fn()
        return self._sharded_apply

    def _to_device(self, arr, name=None):
        if self._ensure_mesh() is None:
            return super()._to_device(arr, name)
        import jax

        sh = self._shardings.get(name) if name is not None else None
        return jax.device_put(np.asarray(arr), sh if sh is not None else self._repl)

    def _count_up(self, arr, name=None) -> None:
        shards = 1
        if self._mesh is not None and name is not None and self._field_shards:
            shards = self._field_shards.get(name, 1)
        TRANSFER_STATS.count_up(np.asarray(arr).nbytes, shards=shards)

    def _full_upload(self, problem):
        mesh = self._ensure_mesh()
        if mesh is not None:
            from armada_tpu.parallel.mesh import _check_divisible

            # Build-time alignment (incremental._node_bucket / pad_problem)
            # guarantees this; tripping it mid-serve is a build bug.
            _check_divisible(problem, mesh)
        return super()._full_upload(problem)
