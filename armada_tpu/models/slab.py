"""Slot-stable problem slabs: O(deltas) device upload per scheduling cycle.

The incremental builder's tables give O(1) *host* work per delta, but the
dense problem they assemble is laid out positionally -- removals compact and
inserts shift, so ~85% of the 1M-row job tensors change content every cycle
and re-upload (the reference's analog is keeping the jobDb cached between
cycles, scheduler.go:240-246).

This module fixes the layout: every queued single, running job, and gang
unit owns a SLOT whose content never moves.  Slots are allocated from a
free-list (no compaction, no shifts); candidate *order* is carried entirely
by the per-cycle ``gq_gang`` permutation (small enough to re-upload whole).
The gang axis is three fixed regions::

    [ singles 0..s_cap | evictee slots s_cap..s_cap+r_cap | units ... ]

The evictee region is a pure projection of the run slab (evictee slot i
mirrors run slot i), so run-slot writes dirty both axes at once.  Slots not
in the current cycle's problem (free-list holes, jobs beyond the queue
lookback, unknown-queue rows, unit slack) are marked ``g_absent`` so the
kernel gives them state 3 (absent), which decode ignores (fair_scheduler.py).

Per cycle the builder emits a :class:`DeltaBundle` -- dirty slot ids + their
rows, the rebuilt order/queue tensors, and scalars -- and
:class:`DeviceDeltaCache` applies it to the device-resident problem with one
jitted scatter program (device-to-device copies; XLA fuses the
scatters, and on-device copy bandwidth makes them microseconds).
Exactness: slot content is written once per logical row; demand is
maintained in integral float64 (resolution units are integers, so
incremental +=/-= is exact and order-independent).  The bundle carries a
``materialize`` thunk building the complete host-side problem;
tests/test_slab_delta.py pins that the scattered device state equals a
fresh upload of it bit-for-bit, cycle after cycle.
"""

from __future__ import annotations

import dataclasses
import functools
import weakref

import numpy as np

from armada_tpu.analysis.tsan import GenerationGuard
from armada_tpu.models.xfer import TRANSFER_STATS
from armada_tpu.ops.trace import recorder as _trace

_ID_DTYPE = "S48"

# Dirty-index buckets: scatter index vectors are padded to these sizes so the
# jitted apply program recompiles only on bucket crossings, not every cycle.
_IDX_BUCKETS = (256, 1024, 4096, 16384, 65536, 262144, 1048576)


def _pad_bucket(n: int) -> int:
    for b in _IDX_BUCKETS:
        if n <= b:
            return b
    return n


def _grow2(arr: np.ndarray, cap: int) -> np.ndarray:
    out = np.zeros((cap,) + arr.shape[1:], arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def _pad_rows(arr: np.ndarray, k: int) -> np.ndarray:
    if arr.shape[0] == k:
        return arr
    out = np.zeros((k,) + arr.shape[1:], arr.dtype)
    out[: arr.shape[0]] = arr
    return out


class IdSnapshot:
    """A job-id vector as it stood when the snapshot was taken, at the cost
    of what has been overwritten since and never of its width.

    `_live` is the owner's array, written in place after the snapshot;
    `_before` holds the before-image of every slot overwritten while this
    snapshot was outstanding (recorded by :meth:`SnapshotIds.write` BEFORE
    the write lands).  A read takes the live value first and the overlay
    second: a write that races the read has then either not landed (the live
    value is still the snapshot's) or has already left its before-image.
    Indexes like the array it stands for, as far as its readers do: an int
    gives one id (``members_of``), a 1-D array of non-negative ints a fresh
    array of ids (``ctx.gang_ids_vec[g2]``)."""

    __slots__ = ("_live", "_before", "__weakref__")

    def __init__(self, live: np.ndarray):
        self._live = live
        self._before: dict = {}

    def __getitem__(self, i):
        before = self._before
        if isinstance(i, (int, np.integer)):
            v = self._live[i]
            b = before.get(int(i))
            return v if b is None else b
        idx = np.asarray(i, np.int64)
        out = self._live[idx]
        if before and idx.size:
            held = np.fromiter(list(before), np.int64)
            for j in np.flatnonzero(np.isin(idx, held)).tolist():
                out[j] = before[int(idx[j])]
        return out


class SnapshotIds:
    """A live id vector that hands out point-in-time snapshots.

    Replaces the copy-on-write of the whole vector (57 MB at 1.2M slots,
    every cycle, for a round that rewrites a thousand of them): the owner
    writes through :meth:`write`, which first leaves the old ids of the
    slots it is about to overwrite with every outstanding snapshot that
    does not hold them yet.  A snapshot therefore costs what is written
    while it is held; one that is dropped costs nothing more (the registry
    is weak), and one that is retained pays per overwritten slot, never a
    whole copy.  `bytes_copied` counts the before-images taken and the
    growth copies, in bytes, since the owner last read it."""

    def __init__(self, n: int = 0):
        self.live = np.zeros((n,), _ID_DTYPE)
        self._snaps: list = []
        self.bytes_copied = 0

    def take_bytes_copied(self) -> int:
        n, self.bytes_copied = self.bytes_copied, 0
        return n

    def snapshot(self) -> IdSnapshot:
        snap = IdSnapshot(self.live)
        self._snaps = [r for r in self._snaps if r() is not None]
        self._snaps.append(weakref.ref(snap))
        return snap

    def replace(self, new_live: np.ndarray, copied: int) -> None:
        """A fresh array takes over (growth, a change of width), `copied`
        ids carried into it; outstanding snapshots keep the old one, which
        nothing writes any more."""
        self.live = new_live
        self._snaps = []
        self.bytes_copied += copied * new_live.dtype.itemsize

    def write(self, slots, values, span: str = "") -> None:
        """``live[slots] = values`` (an int or an int array).  With `span`
        set, the before-images are taken under a trace span of that name
        carrying their `bytes`; opened only when there is something to
        take, so a write no snapshot sees leaves no span."""
        live = self.live
        if self._snaps:
            snaps = [s for s in (r() for r in self._snaps) if s is not None]
            if len(snaps) != len(self._snaps):
                self._snaps = [weakref.ref(s) for s in snaps]
            if snaps:
                slot_list = (
                    [int(slots)]
                    if isinstance(slots, (int, np.integer))
                    else np.asarray(slots).tolist()
                )
                todo = [
                    (s._before, [k for k in slot_list if k not in s._before])
                    for s in snaps
                ]
                n = sum(len(new) for _, new in todo)
                if n:
                    nbytes = n * live.dtype.itemsize
                    self.bytes_copied += nbytes
                    if span:
                        with _trace().span(span, bytes=nbytes):
                            self._record(todo)
                    else:
                        self._record(todo)
        live[slots] = values

    def _record(self, todo) -> None:
        live = self.live
        for before, new in todo:
            if new:
                for k, old in zip(new, live[new].tolist()):
                    before.setdefault(k, old)


class RowSlab:
    """Append-only columnar slot store with free-list reuse.

    Content at a slot is immutable while the slot is held; ``write_batch``
    marks slots dirty, ``release`` invalidates (valid=False) and returns to
    the free-list.  ``epoch`` bumps when capacity grows: all content must
    re-upload, and shapes changed anyway so the kernel recompiles."""

    def __init__(self, num_resources: int, columns: dict, bucket: int):
        self.R = num_resources
        self.bucket = max(64, bucket)
        self.cap = 0
        self.hw = 0  # high-water mark
        self.free: list[int] = []
        self.epoch = 0
        self._columns = dict(columns)  # name -> dtype (besides req/ids/valid)
        self.req = np.zeros((0, num_resources), np.float32)
        # The ids vector hands decode contexts snapshots (share_ids) that
        # cost what is overwritten while they are held, never a copy.
        self._ids = SnapshotIds()
        self.valid = np.zeros((0,), bool)
        for name, dt in self._columns.items():
            setattr(self, name, np.zeros((0,), dt))
        # Mutation log of dirtied slots; assemble_delta drains and clears
        # it once per cycle (single consumer; a skipped bundle is caught by
        # the DeltaBundle seq guard and forces a full upload).
        self.dirty_log: list[int] = []

    @property
    def ids(self) -> np.ndarray:
        return self._ids.live

    def _grow(self, need: int) -> None:
        # GEOMETRIC growth (>=1.5x), not fixed-bucket: the slab cap IS the
        # padded problem axis, and every cap change recompiles the round
        # kernel + compaction + scatter programs (27s + 6s + 4s of XLA:TPU
        # compile at 1M x 50k on a v5e, PR 21 chip run; a 10k-job burst
        # crossing a 40k bucket every 4 cycles is what blew the burst
        # cycle's 5s budget in round 5).  Geometric caps make crossings
        # logarithmic in backlog growth; the bucket stays the floor and the
        # alignment grain.
        new_cap = self.cap
        while new_cap < need:
            scaled = int(new_cap * 1.5)
            new_cap = max(
                new_cap + self.bucket,
                # ceil-aligned so the >=1.5x guarantee actually holds
                ((scaled + self.bucket - 1) // self.bucket) * self.bucket,
            )
        self.req = _grow2(self.req, new_cap)
        # a fresh array: outstanding snapshots keep the old one
        self._ids.replace(_grow2(self.ids, new_cap), self.cap)
        self.valid = _grow2(self.valid, new_cap)
        for name in self._columns:
            setattr(self, name, _grow2(getattr(self, name), new_cap))
        self.cap = new_cap
        self.epoch += 1

    def alloc(self, n: int = 1) -> np.ndarray:
        take = min(n, len(self.free))
        slots = [self.free.pop() for _ in range(take)]
        fresh = n - take
        if fresh:
            if self.hw + fresh > self.cap:
                self._grow(self.hw + fresh)
            slots.extend(range(self.hw, self.hw + fresh))
            self.hw += fresh
        return np.asarray(slots, np.int64)

    def share_ids(self) -> IdSnapshot:
        """Snapshot of the ids vector for a decode context."""
        return self._ids.snapshot()

    def take_id_bytes_copied(self) -> int:
        """Id bytes copied on behalf of snapshots (and by growth) since the
        last call."""
        return self._ids.take_bytes_copied()

    def write_batch(self, slots: np.ndarray, ids, reqs, **cols) -> None:
        self.req[slots] = reqs
        self._ids.write(slots, ids)
        self.valid[slots] = True
        for name, vals in cols.items():
            getattr(self, name)[slots] = vals
        self.dirty_log.extend(int(s) for s in slots)

    def release(self, slot: int) -> None:
        self.valid[slot] = False
        self._ids.write(slot, b"")
        self.free.append(slot)
        self.dirty_log.append(slot)

    def set_valid(self, slots: np.ndarray, value) -> None:
        """Participation flips (lookback/queue/node filters); content stays."""
        if len(slots):
            self.valid[slots] = value
            self.dirty_log.extend(int(s) for s in slots)


@dataclasses.dataclass
class DeltaBundle:
    """One cycle's device update.

    `sig` guards shape/epoch compatibility: a mismatch with the device
    cache's stored sig (slab growth, node-fleet change, first cycle) falls
    back to a full upload via `materialize()`.  `materialize` is a thunk
    building the complete current host-side SchedulingProblem -- the ground
    truth the scatter path must reproduce exactly.  It closes over live
    slab state: call it before any further builder mutation.

    `gq_splice`: when set, the per-cycle candidate-order vector gq_gang is
    NOT shipped whole (4MB at 1M gangs, the dominant per-cycle upload by
    bytes).  Instead the device rebuilds it
    from ITS previous gq: (rem_pos, ins_pos, ins_val) -- positions removed
    from the previous order, plus (final position, slot) pairs inserted --
    a few KB in steady state.  The builder only emits a splice when the
    surviving candidates' relative order is unchanged (verified host-side
    against its own previous gq); anything else ships the full vector."""

    sig: tuple
    seq: int  # consecutive-cycle guard: a skipped bundle forces full upload
    materialize: object  # () -> SchedulingProblem of host arrays (ground truth)
    ev_base: int  # gang-axis offset of the evictee region (= s_cap)
    sg_idx: np.ndarray  # gang-axis dirty slots (singles + units regions)
    sg_cols: dict  # field -> rows at sg_idx
    rr_idx: np.ndarray  # run-axis dirty slots
    rr_cols: dict  # run_* field -> rows at rr_idx
    ev_cols: dict  # evictee g-row field -> rows at ev_base + rr_idx
    fulls: dict  # field name -> host array re-uploaded whole (identity-skipped)
    gq_splice: tuple = None  # (rem_pos[R], ins_pos[M], ins_val[M]) or None

    def stats_view(self):
        """The small host tensors run_round_on_device / queue-stats read
        (problem.market, q_weight, ...) without materializing the problem."""
        import types

        f = self.fulls
        return types.SimpleNamespace(
            market=f["market"],
            q_weight=f["q_weight"],
            q_cds=f["q_cds"],
            total_pool=f["total_pool"],
            drf_mult=f["drf_mult"],
            q_penalty=f["q_penalty"],
        )


# Node-axis fields: identity-cached (same array objects across cycles while
# the fleet is unchanged), re-uploaded only on node-epoch change.
_NODE_FIELDS = (
    "node_total", "node_type", "node_ok", "compat",
    "type_bias", "key_type_row", "compat_pre_type",
)

_SG_FIELDS = (
    "g_req", "g_card", "g_level", "g_queue", "g_key", "g_pc", "g_run",
    "g_valid", "g_absent", "g_price", "g_spot_price", "g_ban_row",
)
_RR_FIELDS = (
    "run_req", "run_node", "run_level", "run_queue", "run_pc",
    "run_preemptible", "run_gang", "run_valid",
)
_EV_FIELDS = (
    "g_req", "g_level", "g_queue", "g_pc", "g_run", "g_valid", "g_absent",
    "g_price", "g_spot_price",
)


def _make_apply(out_shardings=None):
    """The jitted scatter program.  `out_shardings` (a SchedulingProblem
    pytree of NamedShardings) pins the output layout for the mesh cache
    (parallel/mesh_slab.py): without it GSPMD may elect to gather the
    sharded slab while scattering replicated update rows into it."""
    import jax

    jit_kwargs = dict(static_argnames=("ev_base", "splice"))
    if out_shardings is not None:
        jit_kwargs["out_shardings"] = out_shardings

    @functools.partial(jax.jit, **jit_kwargs)
    @jax.named_scope("armada.scatter")
    def apply_delta(
        prev, sg_idx, sg_cols, rr_idx, rr_cols, ev_cols, fulls, gq_args,
        *, ev_base, splice,
    ):
        """Scatter one cycle's dirty rows into the device-resident problem.

        Index vectors are bucket-padded; padding entries carry sentinel G
        (gang axis) / RJ (run axis) and are dropped (scatter mode='drop';
        the evictee projection maps run sentinels to G explicitly so they
        cannot land on the units region).

        splice=True: rebuild gq_gang on device from prev.gq_gang +
        (rem_pos, ins_pos, ins_val) -- delete the removed positions, close
        the gaps, and write the inserted (final position, slot) pairs; the
        host guarantees counts match and surviving order is unchanged."""
        import jax.numpy as jnp

        out = prev._asdict()
        G = prev.g_req.shape[0]
        RJ = prev.run_req.shape[0]
        out.update(fulls)
        if splice:
            rem_pos, ins_pos, ins_val = gq_args
            gq_prev = prev.gq_gang
            keep = jnp.ones((G,), bool).at[rem_pos].set(False, mode="drop")
            # compact the kept entries: kept_buf[j] = j-th kept prev value
            krank = jnp.cumsum(keep) - 1
            kept_buf = (
                jnp.zeros((G,), gq_prev.dtype)
                .at[jnp.where(keep, krank, G)]
                .set(gq_prev, mode="drop")
            )
            # final position p: an inserted entry, or the next kept entry
            occupied = jnp.zeros((G,), bool).at[ins_pos].set(True, mode="drop")
            kidx = jnp.cumsum(~occupied) - 1
            gq = jnp.where(occupied, 0, kept_buf[kidx]).astype(gq_prev.dtype)
            out["gq_gang"] = gq.at[ins_pos].set(ins_val, mode="drop")
        for name in _SG_FIELDS:
            out[name] = out[name].at[sg_idx].set(sg_cols[name], mode="drop")
        for name in _RR_FIELDS:
            out[name] = out[name].at[rr_idx].set(rr_cols[name], mode="drop")
        ev_idx = jnp.where(rr_idx >= RJ, G, rr_idx + ev_base)
        for name in _EV_FIELDS:
            out[name] = out[name].at[ev_idx].set(ev_cols[name], mode="drop")
        return type(prev)(**out)

    return apply_delta


_APPLY = None


class DeviceDeltaCache:
    """Device-resident SchedulingProblem updated by DeltaBundle scatters.

    Falls back to a full upload whenever the bundle's shape/epoch signature
    changes (slab growth, node-fleet change) or a bundle was skipped."""

    def __init__(self):
        self._sig = None
        self._seq = None
        self._prev = None
        self.splice_applies = 0  # cycles where gq rode the device splice
        self.content_prefetches = 0  # scatter_content applications
        self.resets = 0  # explicit device-loss/promotion resets
        # host-object identity of what is currently on device, per field;
        # node tensors also keep their device copy for reuse across full
        # uploads (the fleet rarely changes).
        self._host_ids: dict = {}
        self._node_dev: dict = {}
        # The bucket sizes each scatter variant has run with on the standing
        # device problem (_buckets_for): a delta one bucket step under one of
        # them does not ask for its own, uncompiled one.
        self._variants: dict = {}
        # Race harness (analysis/tsan, ARMADA_TSAN=1): every mutation must
        # commit under the generation it began under; reset() bumps.  A
        # zombie watchdog worker finishing a scatter after a device-loss
        # reset is recorded as a violation instead of silently racing.
        self._tsan = GenerationGuard("devcache")

    def reset(self) -> None:
        """Explicit device-state invalidation (device loss / re-promotion,
        core/watchdog reset hooks): drop EVERYTHING that refers to device
        buffers -- the resident problem, the seq chain, and the reusable
        node-tensor copies -- so the next apply() is a full upload to the
        backend the supervisor now targets.  The sig/seq guards would make
        most stale paths silent no-ops anyway; the explicit reset makes the
        invalidation a guarantee rather than a property of guard coverage
        (and frees buffers pinned on a dead backend)."""
        self._tsan.bump()
        self._sig = None
        self._seq = None
        self._prev = None
        self._host_ids = {}
        self._node_dev = {}
        self._variants = {}
        self.resets += 1

    def _to_device(self, arr, name=None):
        """Upload one host array to the current data device: the default
        backend, or the explicit CPU device while the supervisor is degraded
        (core/watchdog.data_device) -- the delta cache keeps its O(delta)
        scatter economics during CPU-failover operation.  `name` is the
        problem field (None for unnamed payloads); the mesh cache overrides
        this to place each field with its slab sharding."""
        import jax
        import jax.numpy as jnp

        from armada_tpu.core.watchdog import data_device

        dev = data_device()
        if dev is None:
            return jnp.asarray(arr)
        return jax.device_put(np.asarray(arr), dev)

    def _count_up(self, arr, name=None) -> None:
        """Per-field upload accounting hook; the mesh cache overrides it to
        report per-chip bytes for node-axis-sharded fields."""
        TRANSFER_STATS.count_up(np.asarray(arr).nbytes)

    def _apply_fn(self):
        """The jitted scatter program this cache scatters with; the mesh
        cache overrides it with a sharding-pinned compile."""
        global _APPLY
        if _APPLY is None:
            _APPLY = _make_apply()
        return _APPLY

    def _buckets_for(self, variant: tuple, counts: tuple) -> tuple:
        """Padded sizes for a scatter of `counts` indices (one entry an index
        vector): the counts' own buckets, unless this `variant` of the
        scatter program has not run with them on the standing device problem
        and HAS run with sizes that hold them within one bucket step (at most
        4x the padding, a few KB); then those.  A cycle whose delta dips
        under a bucket it is usually over (the steady envelope's splice sits
        just above 1,024 entries and falls under it once in a few dozen
        cycles: PERF.md section 7, in the parent as in the change) would
        otherwise meet a scatter program nobody compiled: seconds of XLA
        inside a served cycle.  One step and no further: after a burst (a
        70k-row catch-up runs at 262,144) a steady 280-row delta compiles
        its own 1,024 once, it never pads up to the burst's."""
        want = tuple(_pad_bucket(n) for n in counts)
        used = self._variants.setdefault(variant, set())
        if want not in used:
            near = [
                v
                for v in used
                if all(b <= a <= _pad_bucket(b + 1) for a, b in zip(v, want))
            ]
            if near:
                return min(near, key=sum)
            used.add(want)
        return want

    def _full_upload(self, problem):
        self._variants = {}  # new shapes: every variant compiles anew anyway
        out = []
        for name, arr in zip(problem._fields, problem):
            if (
                name in _NODE_FIELDS
                and self._host_ids.get(name) is arr
                and name in self._node_dev
            ):
                out.append(self._node_dev[name])
            else:
                self._count_up(arr, name)
                dev = self._to_device(arr, name)
                if name in _NODE_FIELDS:
                    self._node_dev[name] = dev
                out.append(dev)
            self._host_ids[name] = arr
        self._prev = type(problem)(*out)
        return self._prev

    def apply(self, bundle: DeltaBundle):
        tok = self._tsan.begin()
        if (
            self._sig != bundle.sig
            or self._prev is None
            or self._seq is None
            or bundle.seq != self._seq + 1
        ):
            self._sig = bundle.sig
            self._seq = bundle.seq
            with _trace().span("devcache_apply", full_upload=True):
                problem = bundle.materialize()
                self._tsan.commit(tok, "apply/full-upload")
                return self._full_upload(problem)
        self._seq = bundle.seq

        # Steady-state no-op (round 17): a cycle that changed NOTHING -- no
        # dirty gang/run rows, an empty gq splice, and every full-field
        # payload bit-equal to what is already resident (identity for big
        # tables like ban_mask, value-equality for the small per-cycle
        # scalars/vectors the builder rebuilds each assemble: burst caps,
        # q_penalty) -- keeps the resident slab untouched.  An idle tenant's
        # round then costs zero scatter-program dispatches, which is what
        # makes a many-mostly-idle-pool cycle scale (the pool-parallel
        # bench's steady shape); the skip is bit-exact by construction.
        if (
            bundle.sg_idx.shape[0] == 0
            and bundle.rr_idx.shape[0] == 0
            and bundle.gq_splice is not None
            and bundle.gq_splice[0].shape[0] == 0
            and bundle.gq_splice[1].shape[0] == 0
            and all(
                self._host_ids.get(name) is arr
                or (
                    getattr(arr, "nbytes", 1 << 30) <= 4096
                    and np.array_equal(arr, self._host_ids.get(name))
                )
                for name, arr in bundle.fulls.items()
            )
        ):
            self._tsan.commit(tok, "apply/steady-noop")
            return self._prev

        # The compiled variant of the scatter program this apply runs is
        # fixed by the index buckets, the splice flag and which full fields
        # ship: it rides the span as `program` and `bucket` (rows / runs /
        # splice / full fields), so a window that alternates between
        # variants, or meets a new one, shows in the trace.
        splice = bundle.gq_splice is not None
        # full fields whose host object changed (the others' device copies
        # are current)
        changed = {
            name: arr
            for name, arr in bundle.fulls.items()
            if self._host_ids.get(name) is not arr
        }
        counts = (bundle.sg_idx.shape[0], bundle.rr_idx.shape[0]) + (
            (max(bundle.gq_splice[0].shape[0], bundle.gq_splice[1].shape[0]),)
            if splice
            else ()
        )
        kg, kr, *kq = self._buckets_for(("apply", splice, tuple(changed)), counts)
        kq = kq[0] if splice else 0
        with _trace().span(
            "devcache_apply",
            full_upload=False,
            sg_rows=int(bundle.sg_idx.shape[0]),
            rr_rows=int(bundle.rr_idx.shape[0]),
            splice=splice,
            program="apply_delta.splice" if splice else "apply_delta",
            bucket=f"{kg}/{kr}/{kq}/{len(changed)}",
        ):
            G = self._prev.g_req.shape[0]
            RJ = self._prev.run_req.shape[0]
            sg_idx = np.full((kg,), G, np.int32)
            sg_idx[: bundle.sg_idx.shape[0]] = bundle.sg_idx
            rr_idx = np.full((kr,), RJ, np.int32)
            rr_idx[: bundle.rr_idx.shape[0]] = bundle.rr_idx
            sg_cols = {n: _pad_rows(bundle.sg_cols[n], kg) for n in _SG_FIELDS}
            rr_cols = {n: _pad_rows(bundle.rr_cols[n], kr) for n in _RR_FIELDS}
            ev_cols = {n: _pad_rows(bundle.ev_cols[n], kr) for n in _EV_FIELDS}
            fulls = {}
            for name, arr in changed.items():
                self._count_up(arr, name)
                if name in _NODE_FIELDS:
                    # keep the reusable device copy current, else a later full
                    # upload would resurrect a stale buffer via _node_dev
                    dev = self._to_device(np.asarray(arr), name)
                    self._node_dev[name] = dev
                    fulls[name] = dev
                else:
                    fulls[name] = np.asarray(arr)
                self._host_ids[name] = arr
            if splice:
                rem, ins, vals = bundle.gq_splice
                rem_pos = np.full((kq,), G, np.int32)
                rem_pos[: rem.shape[0]] = rem
                ins_pos = np.full((kq,), G, np.int32)
                ins_pos[: ins.shape[0]] = ins
                ins_val = np.zeros((kq,), np.int32)
                ins_val[: ins.shape[0]] = vals
                gq_args = (rem_pos, ins_pos, ins_val)
                self.splice_applies += 1
            else:
                gq_args = ()
            for arr in (sg_idx, rr_idx, *gq_args):
                TRANSFER_STATS.count_up(arr.nbytes)
            for cols in (sg_cols, rr_cols, ev_cols):
                for arr in cols.values():
                    TRANSFER_STATS.count_up(arr.nbytes)
            self._tsan.commit(tok, "apply/scatter")
            self._prev = self._apply_fn()(
                self._prev, sg_idx, sg_cols, rr_idx, rr_cols, ev_cols, fulls,
                gq_args, ev_base=bundle.ev_base, splice=splice,
            )
            return self._prev

    def scatter_content(
        self, *, sig, seq, ev_base, sg_idx, sg_cols, rr_idx, rr_cols, ev_cols
    ) -> bool:
        """Content-only prefetch: scatter already-final slot rows into the
        device problem WITHOUT a cycle bundle -- the shadow-pipeline's
        stage (b) (ISSUE 3): new-submit rows ship while the kernel and its
        result transfer are in flight, so the next assemble's bundle only
        carries lease/evict-dependent rows.

        This is the decision-INDEPENDENT half of the delta stream: order
        vectors, queue tensors, demand shares and scalars (the `fulls` +
        gq splice) are decision-dependent and only ever ship with
        assemble_delta's bundle.  A content scatter never consumes a seq --
        the next bundle continues the chain, and the builder excludes the
        prefetched rows from its payload (incremental.prefetch_content).

        Guards: the caller must target the exact device state its last
        bundle produced -- same sig (shapes/epochs) and the very next seq
        (`seq` = the seq the NEXT bundle will carry).  Anything else (slab
        growth, a skipped bundle, a fresh cache) returns False and the rows
        simply ride the next bundle or its full-upload fallback."""
        tok = self._tsan.begin()
        if (
            self._prev is None
            or self._sig != sig
            or self._seq is None
            or seq != self._seq + 1
        ):
            return False
        kg, kr = self._buckets_for(("content",), (sg_idx.shape[0], rr_idx.shape[0]))
        with _trace().span(
            "scatter_content",
            sg_rows=int(sg_idx.shape[0]),
            rr_rows=int(rr_idx.shape[0]),
            program="apply_delta",
            bucket=f"{kg}/{kr}/0/0",
        ):
            G = self._prev.g_req.shape[0]
            RJ = self._prev.run_req.shape[0]
            sg_pad = np.full((kg,), G, np.int32)
            sg_pad[: sg_idx.shape[0]] = sg_idx
            rr_pad = np.full((kr,), RJ, np.int32)
            rr_pad[: rr_idx.shape[0]] = rr_idx
            sg_cols = {n: _pad_rows(sg_cols[n], kg) for n in _SG_FIELDS}
            rr_cols = {n: _pad_rows(rr_cols[n], kr) for n in _RR_FIELDS}
            ev_cols = {n: _pad_rows(ev_cols[n], kr) for n in _EV_FIELDS}
            for arr in (sg_pad, rr_pad):
                TRANSFER_STATS.count_up(arr.nbytes)
            for cols in (sg_cols, rr_cols, ev_cols):
                for arr in cols.values():
                    TRANSFER_STATS.count_up(arr.nbytes)
            self._tsan.commit(tok, "scatter_content")
            self._prev = self._apply_fn()(
                self._prev, sg_pad, sg_cols, rr_pad, rr_cols, ev_cols, {},
                (), ev_base=ev_base, splice=False,
            )
            self.content_prefetches += 1
            return True
