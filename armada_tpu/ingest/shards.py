"""Partition-parallel ingestion: N shard workers over disjoint partition sets.

The reference scales ingestion by partitioning Pulsar topics and running
parallel consumers (internal/common/ingest/ingestion_pipeline.go:40-79); this
port kept the partitioned log but serialized every view behind ONE
IngestionPipeline thread.  ``PartitionedIngestionPipeline`` is the parallel
plane:

* **Sharding is sound because ordering is per-partition.**  The publisher
  routes every EventSequence by ``jobset_key(queue, jobset)``, so all the
  orderings the materialized views rely on (a job's lifecycle, a jobset's
  submit/cancel interleaving) are confined to one partition; and
  ``consumer_positions`` is keyed ``(consumer, partition, position)``, so
  each shard commits exactly its own cursor rows (the shard-cursor
  invariant, lint rule ``shard-foreign-cursor``).  Fences stay exact:
  ``positions()`` -- and therefore checkpoint restore, the replicator's
  ``min_acked`` and /ready -- is the union of per-partition rows, each
  advanced transactionally with its shard's data.

* **The converter runs OFF the GIL.**  The pure-CPU leg (proto parse ->
  DbOps -> rendered SQL plan) is shipped to a converter subprocess as raw
  record buffers (``EventLog.read_raw``: the C read, no Python framing) and
  comes back as a picklable plan (``schedulerdb.render_scheduler_ops``) or
  converted batch; the shard thread keeps only the C read and the
  transactional store leg.  Threads alone measured 1.01x on the CPU host --
  parse/convert hold the GIL -- so the subprocess hop IS the speedup.
  ``convert_mode="inline"`` (or ``ARMADA_INGEST_CONVERT=inline``) keeps
  everything in-process.

* **The '$control-plane' stream gets a designated-partition barrier.**
  Queue CRUD and executor sweeps resolve membership against the LIVE tables
  at apply time, so they need a global order against every partition.  The
  shard owning the control partition detects control records by their key,
  fences the log (end offsets at detection time), waits until every sibling
  shard has COMMITTED past the fence, and only then applies the control
  segment -- every event published before the control event is applied
  before it, which is strictly stronger than the serial pipeline's
  poll-order approximation.  Partition markers are NOT control records
  (their op is per-partition) and ride the normal path.

Exactly-once is unchanged: each shard's store commits data + its cursor rows
in one transaction; the ``ingest_ack`` crash window between commit and
in-memory ack replays idempotently on restart (tests/test_ingest_shards.py
drills it per shard under tsan).
"""

from __future__ import annotations

import os
import struct
import threading
import time
from typing import Callable, Optional, Sequence

from armada_tpu.analysis.tsan import make_lock
from armada_tpu.eventlog import EventLog
from armada_tpu.eventlog.publisher import jobset_key, partition_for_key
from armada_tpu.events import events_pb2 as pb
from armada_tpu.ingest.pipeline import Sink
from armada_tpu.ingest.stats import RateEstimator, registry as stats_registry

# The reserved control-plane stream key (server/controlplane.py
# CONTROL_PLANE_JOBSET; duplicated here by value so shard workers never
# import the server package -- tests/test_ingest_shards.py pins equality).
CONTROL_PLANE_JOBSET = "$control-plane"
_CONTROL_KEY = jobset_key("", CONTROL_PLANE_JOBSET)


def control_partition_of(log: EventLog) -> int:
    """The partition every '$control-plane' sequence routes to."""
    return partition_for_key(_CONTROL_KEY, log.num_partitions)


def resolve_num_shards(explicit: Optional[int] = None) -> int:
    """Shard count: explicit argument > ARMADA_INGEST_SHARDS > 1 (serial)."""
    if explicit is not None:
        return max(1, int(explicit))
    try:
        return max(1, int(os.environ.get("ARMADA_INGEST_SHARDS", "1")))
    except ValueError:
        return 1


# --------------------------------------------------------------------------
# converter subprocess side
# --------------------------------------------------------------------------

def _iter_frames(buf: bytes):
    """Yield (key_start, key_len, payload_start, payload_len, total) per
    record of a read_raw buffer -- the ONE Python mirror of the native
    framing ([u32 paylen][u32 keylen][key][payload][u32 crc],
    native/eventlog.cc; EventLog.read carries the only other copy).  Every
    walker below slices through this so a framing change lands in one
    place."""
    pos = 0
    n = len(buf)
    while pos < n:
        paylen, keylen = struct.unpack_from("<II", buf, pos)
        kstart = pos + 8
        yield kstart, keylen, kstart + keylen, paylen, 8 + keylen + paylen + 4
        pos += 8 + keylen + paylen + 4


def _frame_payloads(buf: bytes) -> list[bytes]:
    """Record payloads out of a raw buffer."""
    return [
        bytes(buf[ps : ps + pl]) for (_ks, _kl, ps, pl, _t) in _iter_frames(buf)
    ]


def _has_control(buf: bytes) -> bool:
    """Does a raw control-partition buffer hold any '$control-plane' record?
    A key-only frame walk -- no payload decode, no object construction."""
    klen = len(_CONTROL_KEY)
    return any(
        kl == klen and buf[ks : ks + kl] == _CONTROL_KEY
        for (ks, kl, _ps, _pl, _t) in _iter_frames(buf)
    )


def _frame_records(buf: bytes, base_offset: int) -> list[tuple[bytes, bytes, int]]:
    """(key, payload, next_offset) triples out of a raw buffer."""
    out = []
    off = base_offset
    for ks, kl, ps, pl, total in _iter_frames(buf):
        off += total
        out.append((bytes(buf[ks : ks + kl]), bytes(buf[ps : ps + pl]), off))
    return out


_RESOLVED: dict[str, Callable] = {}


def _resolve(spec: str) -> Callable:
    """Import "module:qualname" (cached; the worker-side half of the
    ship-functions-by-name protocol)."""
    fn = _RESOLVED.get(spec)
    if fn is None:
        import importlib

        module, _, qualname = spec.partition(":")
        fn = importlib.import_module(module)
        for part in qualname.split("."):
            fn = getattr(fn, part)
        _RESOLVED[spec] = fn
    return fn


def _spec_of(fn: Callable) -> Optional[str]:
    """The importable "module:qualname" of `fn`, or None when it cannot be
    shipped to a subprocess (lambdas, closures, instance methods)."""
    module = getattr(fn, "__module__", None)
    qualname = getattr(fn, "__qualname__", "")
    if not module or not qualname or "<" in qualname or "." in qualname:
        return None
    if module == "__main__":
        # "__main__" names a DIFFERENT module inside a worker process.
        return None
    try:
        if _resolve(f"{module}:{qualname}") is not fn:
            return None
    except Exception:  # noqa: BLE001 - unimportable = not offloadable
        return None
    return f"{module}:{qualname}"


def _pack_plan(plan) -> list[tuple]:
    """Columnar transform for the pipe: pickling a plan as 100k+ small row
    tuples costs ~0.4s of main-process GIL to unpickle; as a handful of
    per-column lists it is a few big C-speed loads + one zip per statement
    (measured ~3x cheaper on the receiving side).  Renderers now emit
    columnar tuples natively (schedulerdb.PlanStmt) -- those ship as-is;
    legacy row-list params still get transposed here."""
    packed = []
    for st in plan:
        if st.many and st.params and not isinstance(st.params, tuple):
            packed.append(
                (st.domain, st.sql, tuple(zip(*st.params)), st.serial_pos, True)
            )
        else:
            packed.append(
                (st.domain, st.sql, st.params, st.serial_pos, st.many)
            )
    return packed


def _unpack_plan(packed: list[tuple]):
    from armada_tpu.ingest.schedulerdb import PlanStmt

    plan = []
    for domain, sql, params, serial_pos, many in packed:
        # Columnar tuples pass straight through -- _execute_plan streams
        # them row-wise via one zip; only legacy row lists need no work
        # here either, so everything is passthrough now that renderers are
        # columnar.  (Empty many-params normalize to an empty list.)
        if many and not params:
            params = []
        plan.append(PlanStmt(domain, sql, params, serial_pos, many))
    return plan


def _worker_convert(
    converter_spec: str, renderer_spec: Optional[str], buffers: list[bytes]
):
    """The subprocess leg: frame -> parse -> convert [-> render].  Returns
    (kind, payload, n_sequences, n_events) where kind is "plan" (a rendered
    SQL plan, columnar-packed, the sink executes via store_plan) or "ops"
    (the converted batch for sink.store)."""
    payloads = [p for buf in buffers for p in _frame_payloads(buf)]
    sequences = [pb.EventSequence.FromString(p) for p in payloads]
    n_events = sum(len(s.events) for s in sequences)
    converted = _resolve(converter_spec)(sequences)
    if renderer_spec is not None:
        plan = _resolve(renderer_spec)(converted)
        if plan is not None:
            return ("plan", _pack_plan(plan), len(sequences), n_events)
    return ("ops", converted, len(sequences), n_events)


# One process-global converter pool shared by every sharded pipeline in the
# process (forkserver context: forking a thread-heavy serving process
# deadlocks).
_pool = None
_pool_lock = make_lock("ingest.convert_pool")


def _start_forkserver_on_cpu() -> None:
    """Start the forkserver NOW, with JAX_PLATFORMS=cpu in its environment
    (its workers inherit it).  The preloaded ingest chain imports jax, the
    serving process may hold the chip, and libtpu gives a chip to one
    process at a time: a converter worker must never be able to initialise
    an accelerator backend.  This process's own jax read the variable at
    import, so the brief override does not touch it."""
    from multiprocessing import forkserver

    prev = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        forkserver.ensure_running()
    finally:
        if prev is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = prev


def _convert_pool(workers: int):
    global _pool
    with _pool_lock:
        if _pool is None:
            import atexit
            import multiprocessing as mp
            from concurrent.futures import ProcessPoolExecutor

            # forkserver, not spawn and not fork: fork from a thread-heavy
            # serving process can deadlock on copied lock state, and spawn
            # re-prepares __main__ in every worker (re-importing a heavy
            # driver script, and breaking outright under stdin mains).  The
            # forkserver is ONE clean process that preloads the light
            # convert chain; workers fork from it in milliseconds.
            ctx = mp.get_context("forkserver")
            ctx.set_forkserver_preload(["armada_tpu.ingest.shards"])
            # Worker startup re-prepares the parent's __main__.  A script
            # main (bench.py imports jax at top) would be re-imported into
            # every worker, and a <stdin> main breaks startup outright --
            # point the preparation at THIS light module instead.  Only
            # mains without a __spec__ are touched (python -m / pytest
            # mains already carry an importable name), and converters
            # defined in __main__ are rejected by _spec_of.
            import importlib.util
            import sys as _sys

            main_mod = _sys.modules.get("__main__")
            if main_mod is not None and getattr(main_mod, "__spec__", None) is None:
                main_mod.__spec__ = importlib.util.find_spec(
                    "armada_tpu.ingest._worker_main"
                )
            # The pool is PROCESS-GLOBAL and created once, by whichever
            # pipeline asks first -- serve runs three sharded views against
            # it, and tests create pipelines at assorted widths.  Size it
            # for the host, not the first caller, so a narrow early
            # pipeline cannot starve a wide later one (workers spawn
            # lazily, so unused width costs nothing).
            size = min(os.cpu_count() or 8, max(workers, 8))
            _start_forkserver_on_cpu()
            _pool = ProcessPoolExecutor(max_workers=size, mp_context=ctx)
            atexit.register(_pool.shutdown, wait=False, cancel_futures=True)
        return _pool


# --------------------------------------------------------------------------
# the pipeline
# --------------------------------------------------------------------------

class _Shard:
    """One worker: a disjoint partition set, its own positions, backoff and
    transactional store leg."""

    def __init__(
        self,
        pipeline: "PartitionedIngestionPipeline",
        idx: int,
        partitions: Sequence[int],
        sink: Sink,
        start_positions: dict[int, int],
    ):
        self.pipeline = pipeline
        self.idx = idx
        self.partitions = tuple(partitions)
        self.sink = sink
        self.positions = {p: start_positions.get(p, 0) for p in self.partitions}
        self.wakeup = threading.Event()
        self.thread: Optional[threading.Thread] = None
        # Store-leg write latency (this shard's sink transactions): feeds
        # /healthz's per-shard block and the
        # armada_ingest_store_write_seconds{consumer,shard} gauge.  Written
        # only by this shard's thread; read racily by snapshot() (floats --
        # a torn read shows a stale value, never corruption).
        self.store_writes = 0
        self.store_s_total = 0.0
        self.store_last_s = 0.0

    def _note_store_write(self, dt: float) -> None:
        self.store_writes += 1
        self.store_s_total += dt
        self.store_last_s = dt

    # ------------------------------------------------------------ polling --

    def caught_up(self) -> bool:
        log = self.pipeline.log
        return all(self.positions[p] >= log.end_offset(p) for p in self.partitions)

    def _poll_raw(self, start: dict[int, int], max_bytes: int):
        """Raw buffers from `start` across owned partitions; returns
        (buffers, next_positions, control_raw)."""
        pipe = self.pipeline
        log = pipe.log
        buffers: list[bytes] = []
        nxt: dict[int, int] = {}
        control_raw = None
        for p in self.partitions:
            buf, next_off = log.read_raw(p, start[p], max_bytes=max_bytes)
            if not buf:
                continue
            if p == pipe.control_partition and _has_control(buf):
                # Control records are detected by KEY (a raw frame walk, no
                # payload decode); the batch takes the barriered path.
                control_raw = (buf, start[p])
            else:
                buffers.append(buf)
                nxt[p] = next_off
        return buffers, nxt, control_raw

    def run_once(self) -> int:
        """One consume->convert->store->ack round; returns #sequences."""
        from armada_tpu.core import faults

        buffers, nxt, control_raw = self._poll_raw(
            self.positions, self.pipeline.max_bytes_per_partition
        )
        applied = 0
        if buffers:
            applied += self._apply_buffers(buffers, nxt)
            faults.check("ingest_ack")
            self._ack(nxt)
        if control_raw is not None:
            applied += self._apply_control_batch(*control_raw)
        return applied

    def _convert_begin(self, buffers: list[bytes]) -> Callable[[], tuple]:
        """Kick off conversion; returns a resolver yielding
        (kind, payload, n_sequences, n_events).  With offload the work is
        already in flight when this returns -- the threaded loop polls its
        NEXT batch while this one converts."""
        pipe = self.pipeline
        # Poison drill hook (ARMADA_FAULT=convert_record): MUST run host-side
        # -- the forkserver workers carry their own fault/latch state, so a
        # subprocess fire would never stick.  Armed-only: one falsy check in
        # production.
        from armada_tpu.ingest import dlq

        if dlq.poison_armed():
            dlq.poison_check(
                [p for buf in buffers for p in _frame_payloads(buf)]
            )
        if pipe.offload:
            fut = pipe.pool.submit(
                _worker_convert,
                pipe.converter_spec,
                pipe.renderer_spec,
                buffers,
            )

            def resolve():
                try:
                    kind, payload, n_seqs, n_events = fut.result()
                except Exception as exc:
                    if not _is_broken_pool(exc):
                        raise
                    # A killed worker poisons the whole pool; fall back to
                    # in-process conversion for the rest of this pipeline's
                    # life rather than looping on a dead executor.
                    pipe._disable_offload(exc)
                    return _inline_convert(pipe.converter, pipe.renderer, buffers)
                if kind == "plan":
                    payload = _unpack_plan(payload)
                return kind, payload, n_seqs, n_events

            return resolve
        return lambda: _inline_convert(pipe.converter, pipe.renderer, buffers)

    def _store_converted(self, result: tuple, nxt: dict[int, int]) -> int:
        kind, payload, n_seqs, n_events = result
        pipe = self.pipeline
        t0 = time.perf_counter()
        if kind == "plan":
            self.sink.store_plan(
                payload, consumer=pipe.consumer_name, next_positions=nxt
            )
        else:
            self.sink.store(
                payload, consumer=pipe.consumer_name, next_positions=nxt
            )
        self._note_store_write(time.perf_counter() - t0)
        pipe.rate.record(n_events)
        pipe.note_counts(n_seqs, n_events)
        return n_seqs

    def _finish(self, resolver: Callable[[], tuple], nxt: dict[int, int]) -> int:
        from armada_tpu.core import faults

        n = self._store_converted(resolver(), nxt)
        faults.check("ingest_ack")
        self._ack(nxt)
        return n

    def _apply_buffers(self, buffers: list[bytes], nxt: dict[int, int]) -> int:
        return self._store_converted(self._convert_begin(buffers)(), nxt)

    # ------------------------------------------------- control-plane path --

    def _apply_control_batch(
        self,
        buf: bytes,
        base_offset: int,
        stop: Optional[threading.Event] = None,
    ) -> int:
        """The designated-partition barrier: apply the control partition's
        backlog segment by segment, fencing every control segment behind the
        whole plane's committed positions.  Inline conversion throughout --
        control batches are small and ordering, not throughput, is what
        matters here."""
        from armada_tpu.core import faults

        pipe = self.pipeline
        applied = 0
        part = pipe.control_partition
        records = _frame_records(buf, base_offset)
        # Poison drill hook: the barrier path converts inline, so the latch
        # check lives here (a poison CONTROL record halts this shard loudly
        # in isolation -- never auto-skipped).
        from armada_tpu.ingest import dlq

        if dlq.poison_armed():
            dlq.poison_check([payload for (_k, payload, _o) in records])
        i = 0
        while i < len(records):
            is_control = records[i][0] == _CONTROL_KEY
            j = i
            while j < len(records) and (records[j][0] == _CONTROL_KEY) == is_control:
                j += 1
            segment = records[i:j]
            if is_control:
                # Everything published before this control record -- in any
                # partition -- must be applied before it.  The fence is the
                # log's end at detection time (>= the publish point).
                fence = {
                    p: pipe.log.end_offset(p)
                    for p in range(pipe.log.num_partitions)
                }
                self._await_fence(fence, stop)
            sequences = [
                pb.EventSequence.FromString(payload)
                for (_key, payload, _off) in segment
            ]
            n_events = sum(len(s.events) for s in sequences)
            nxt = {part: segment[-1][2]}
            t0 = time.perf_counter()
            self.sink.store(
                pipe.converter(sequences),
                consumer=pipe.consumer_name,
                next_positions=nxt,
            )
            self._note_store_write(time.perf_counter() - t0)
            faults.check("ingest_ack")
            self._ack(nxt)
            pipe.rate.record(n_events)
            pipe.note_counts(len(sequences), n_events)
            applied += len(segment)
            i = j
        return applied

    def _await_fence(
        self, fence: dict[int, int], stop: Optional[threading.Event] = None
    ) -> None:
        """Block until every partition OUTSIDE this shard is committed past
        `fence` (own non-control partitions: drain them here), driving
        sibling shards inline when no background threads are running (the
        synchronous run_until_caught_up mode would otherwise deadlock on
        itself).  The control partition itself is excluded: its order is
        exactly the segment loop in _apply_control_batch.  `stop` is the
        caller's CAPTURED per-start event -- an abandoned thread must keep
        observing its own (set) event, not a successor start's fresh one."""
        pipe = self.pipeline
        if stop is None:
            stop = pipe._stop
        # Own partitions first: this shard is the only one that can move them.
        for p in self.partitions:
            if p == pipe.control_partition:
                continue
            while self.positions[p] < min(fence[p], pipe.log.end_offset(p)):
                self._drain_own_partition(p)
        while not stop.is_set():
            acked = pipe.acked_positions()
            if all(
                acked.get(p, 0) >= fence[p]
                for p in fence
                if p not in self.partitions
            ):
                return
            if pipe._threads_running:
                time.sleep(0.002)
            else:
                pipe._drive_siblings(self)
        # Stopped mid-barrier: applying the control segment WITHOUT the
        # fence would reorder it before unapplied foreign events.  Raise --
        # positions were never acked, so a restart replays it exactly-once.
        raise RuntimeError("stopped while awaiting the control-plane fence")

    def _drain_own_partition(self, p: int) -> None:
        """One batch of `p` applied in place (the caller's fence loop
        bounds progress; the read itself deliberately overshoots a fence --
        extra own-partition records applied before a control segment only
        strengthen the barrier guarantee)."""
        from armada_tpu.core import faults

        pipe = self.pipeline
        buf, next_off = pipe.log.read_raw(
            p, self.positions[p], max_bytes=pipe.max_bytes_per_partition
        )
        if not buf:
            return
        nxt = {p: next_off}
        self._apply_buffers([buf], nxt)
        faults.check("ingest_ack")
        self._ack(nxt)

    # ----------------------------------------------------------- plumbing --

    def _ack(self, nxt: dict[int, int]) -> None:
        self.positions.update(nxt)
        self.pipeline._record_ack(nxt)

    def lag(self) -> dict[int, int]:
        log = self.pipeline.log
        return {
            p: max(0, log.end_offset(p) - self.positions[p])
            for p in self.partitions
        }


def _inline_convert(converter, renderer, buffers: list[bytes]):
    payloads = [p for buf in buffers for p in _frame_payloads(buf)]
    sequences = [pb.EventSequence.FromString(p) for p in payloads]
    n_events = sum(len(s.events) for s in sequences)
    converted = converter(sequences)
    if renderer is not None:
        plan = renderer(converted)
        if plan is not None:
            return ("plan", plan, len(sequences), n_events)
    return ("ops", converted, len(sequences), n_events)


def _is_broken_pool(exc: BaseException) -> bool:
    from concurrent.futures.process import BrokenProcessPool

    return isinstance(exc, BrokenProcessPool)


class PartitionedIngestionPipeline:
    """N shard workers, each owning a disjoint partition set with its own
    consumer positions, backoff and transactional store leg.  Drop-in for
    IngestionPipeline (run_once / run_until_caught_up / start / stop /
    alive), with `num_shards=1` degenerating to a single worker."""

    def __init__(
        self,
        log: EventLog,
        sink: Sink,
        converter: Callable[[list[pb.EventSequence]], object],
        consumer_name: str,
        num_shards: Optional[int] = None,
        start_positions: Optional[dict[int, int]] = None,
        poll_interval: float = 0.05,
        convert_mode: Optional[str] = None,
        max_bytes_per_partition: int = 1 << 22,
    ):
        self.log = log
        self.consumer_name = consumer_name
        self.converter = converter
        self.poll_interval = poll_interval
        self.max_bytes_per_partition = max_bytes_per_partition
        self.control_partition = control_partition_of(log)
        num_shards = min(resolve_num_shards(num_shards), log.num_partitions)
        self.num_shards = max(1, num_shards)

        # Offload decision: worker processes need the converter (and the
        # sink's plan renderer, when it has one) importable by name.
        # Default ON for a genuinely sharded pipeline -- the GIL-bound
        # converter is the reason shards exist; ARMADA_INGEST_CONVERT=
        # inline (or convert_mode="inline") keeps everything in-process.
        mode = convert_mode or os.environ.get("ARMADA_INGEST_CONVERT", "process")
        self.converter_spec = _spec_of(converter)
        renderer = getattr(sink, "plan_renderer", None)
        self.renderer = renderer if callable(renderer) else None
        self.renderer_spec = (
            _spec_of(self.renderer) if self.renderer is not None else None
        )
        self.offload = (
            mode == "process"
            and self.num_shards > 1
            and self.converter_spec is not None
        )
        self.pool = _convert_pool(self.num_shards) if self.offload else None

        # Shard k owns partitions {p : p % num_shards == k}: the control
        # partition lands in exactly one shard, which carries the barrier.
        start_positions = dict(start_positions or {})
        self._acked_lock = make_lock("ingest.shards.acked")
        self._acked = {
            p: start_positions.get(p, 0) for p in range(log.num_partitions)
        }
        self._counts_lock = make_lock("ingest.shards.counts")
        self.total_sequences = 0
        self.total_events = 0
        self._barrier_applied = 0
        self.rate = RateEstimator()
        self._stop = threading.Event()
        self._threads_running = False
        self._abandoned = 0
        self._driving = False
        self.shards = [
            _Shard(
                self,
                k,
                [p for p in range(log.num_partitions) if p % self.num_shards == k],
                sink.shard_sink(k, self.num_shards)
                if hasattr(sink, "shard_sink")
                else sink,
                start_positions,
            )
            for k in range(self.num_shards)
        ]
        # Shard sinks WE created (external PG: one wire connection each;
        # embedded stores return the shared sink) are closed on stop() --
        # otherwise every pipeline lifecycle leaks N server-side sessions.
        # Sharded stores (ingest/storeunion.py) OWN their shard legs for
        # the store's lifetime -- a pipeline restart reuses the same files,
        # so stop() must not close them.
        self._owned_sinks = (
            []
            if getattr(sink, "shard_sinks_owned_by_store", False)
            else [s.sink for s in self.shards if s.sink is not sink]
        )
        # One stable bound-method object: the stats registry unregisters by
        # identity.  Registration happens in start() (serving pipelines);
        # synchronously-driven pipelines never register.
        self._stats_snapshot = self.snapshot

    # ------------------------------------------------------------ running --

    def run_once(self) -> int:
        """One round of EVERY shard, in the caller's thread.  Sequences a
        barrier drove through SIBLING shards mid-round are counted here
        (and those shards are then already drained for their own turn)."""
        n = sum(shard.run_once() for shard in self.shards)
        with self._counts_lock:
            n += self._barrier_applied
            self._barrier_applied = 0
        return n

    def run_until_caught_up(self, max_rounds: int = 1_000_000) -> int:
        total = 0
        for _ in range(max_rounds):
            n = self.run_once()
            total += n
            if n == 0 and all(s.caught_up() for s in self.shards):
                return total
        return total

    def _drive_siblings(self, barrier_shard: _Shard) -> None:
        """Synchronous-mode barrier progress: run every OTHER shard one
        round in this thread (only the control shard ever barriers, so no
        reentrancy is possible)."""
        if self._driving:  # defensive: never recurse through a barrier
            time.sleep(0.002)
            return
        self._driving = True
        try:
            applied = 0
            for shard in self.shards:
                if shard is not barrier_shard:
                    applied += shard.run_once()
            with self._counts_lock:
                self._barrier_applied += applied
        finally:
            self._driving = False

    # --- background service mode -------------------------------------------

    def start(self) -> None:
        if self._threads_running:
            raise RuntimeError("pipeline already started")
        # A FRESH stop event per start, captured by each loop: an abandoned
        # (timed-out) shard thread from a previous start keeps observing
        # ITS event -- still set -- and exits when it unwedges, instead of
        # being resurrected alongside the new threads.
        self._stop = threading.Event()
        self._threads_running = True
        stats_registry().register(self.consumer_name, self._stats_snapshot)
        for shard in self.shards:
            shard.thread = threading.Thread(
                target=self._shard_loop,
                args=(shard, self._stop),
                daemon=True,
                name=f"ingest-{self.consumer_name}-s{shard.idx}",
            )
            shard.thread.start()

    def stop(self, timeout_s: float = 5.0) -> None:
        """Bounded join (the watchdog's abandon discipline): a shard wedged
        in a hung store must not block SIGTERM drain -- log, count it
        abandoned, and let the daemon thread die with the process."""
        from armada_tpu.core.logging import get_logger

        self._stop.set()
        deadline = time.monotonic() + max(0.0, timeout_s)
        for shard in self.shards:
            if shard.thread is None:
                continue
            shard.wakeup.set()
            shard.thread.join(timeout=max(0.0, deadline - time.monotonic()))
            if shard.thread.is_alive():
                self._abandoned += 1
                get_logger(__name__).warning(
                    "ingestion shard %s/%d did not stop within %.1fs; "
                    "abandoning the thread (a store that still commits "
                    "remains exactly-once; an uncommitted batch replays "
                    "on restart)",
                    self.consumer_name,
                    shard.idx,
                    timeout_s,
                )
            shard.thread = None
        self._threads_running = False
        stats_registry().unregister(self.consumer_name, self._stats_snapshot)
        # Release per-shard store connections (external PG); a stopped
        # PG-backed pipeline is torn down, not restartable -- build a new
        # one (the embedded path shares the caller's sink and is
        # unaffected).  Not closed while a thread was abandoned: its
        # in-flight store still owns the connection.
        if not self._abandoned:
            for sink in self._owned_sinks:
                try:
                    sink.close()
                except Exception:  # noqa: BLE001 - teardown best effort
                    pass

    def alive(self) -> bool:
        """True while every shard loop is running (feeds health checks)."""
        return self._threads_running and all(
            s.thread is not None and s.thread.is_alive() for s in self.shards
        )

    def notify(self, partitions: set) -> None:
        """Publisher-side wakeup hook (Publisher.add_wakeup): rouse exactly
        the shards whose partitions got data."""
        for shard in self.shards:
            if any(p in partitions for p in shard.partitions):
                shard.wakeup.set()

    # Backlog-drain batch ramp: the first store can only happen after the
    # first conversion, so starting small gets the sink busy in ~100ms and
    # doubling up to max_bytes_per_partition amortizes per-batch overhead
    # once the pipeline is full.  Steady serving polls small batches anyway.
    _RAMP_START_BYTES = 256 << 10

    def _shard_loop(self, shard: _Shard, stop: threading.Event) -> None:
        from armada_tpu.core.backoff import Backoff
        from armada_tpu.core.logging import get_logger, log_context

        from armada_tpu.ingest import dlq
        from armada_tpu.ingest.pipeline import ingest_retries

        log = get_logger(__name__)
        # Jittered exponential backoff on batch failures, per shard -- a
        # restarting external DB must not see every shard retry in lockstep.
        # BOUNDED: exhaustion escalates to poison isolation (ingest/dlq.py)
        # instead of wedging the shard behind one bad record forever.
        backoff = Backoff(
            base_s=self.poll_interval,
            cap_s=5.0,
            max_attempts=ingest_retries(),
        )
        # One-deep prefetch: while `pending` converts (in a worker process),
        # this thread polls and submits the NEXT batch, so the sink lock
        # never idles waiting on conversion.  `read_pos` runs ahead of the
        # acked positions by at most one batch; any failure drops the
        # prefetched work and re-reads from the last ack (replay is
        # idempotent, so a wasted conversion is the whole cost).
        read_pos = dict(shard.positions)
        pending: Optional[tuple[Callable[[], tuple], dict[int, int]]] = None
        batch_bytes = min(self._RAMP_START_BYTES, self.max_bytes_per_partition)
        with log_context(consumer=f"{self.consumer_name}/s{shard.idx}"):
            while not stop.is_set():
                try:
                    buffers, nxt, control_raw = shard._poll_raw(
                        read_pos, batch_bytes
                    )
                    progressed = bool(buffers) or control_raw is not None
                    if buffers:
                        resolver = shard._convert_begin(buffers)
                        if pending is not None:
                            shard._finish(*pending)
                        pending = (resolver, nxt)
                        read_pos.update(nxt)
                        batch_bytes = min(
                            batch_bytes * 2, self.max_bytes_per_partition
                        )
                    if control_raw is not None:
                        # The barrier path is strictly ordered: flush the
                        # prefetched batch, then apply segments in place.
                        if pending is not None:
                            shard._finish(*pending)
                            pending = None
                        shard._apply_control_batch(*control_raw, stop=stop)
                        # Resync the read cursor for EVERY owned partition:
                        # the fence drained this shard's other partitions
                        # past read_pos, and re-reading them would re-apply
                        # events AFTER the sweep and commit their cursors
                        # backward.  pending is None here, so positions is
                        # exactly the committed frontier.
                        read_pos.update(shard.positions)
                    if not progressed:
                        if pending is not None:
                            shard._finish(*pending)
                            pending = None
                            continue  # the store may have taken a while: re-poll
                        # Idle: sleep on the publish wakeup, with the old
                        # poll interval as the fallback for writers that
                        # bypass the publisher (the log replicator on
                        # follower replicas).
                        batch_bytes = min(
                            self._RAMP_START_BYTES, self.max_bytes_per_partition
                        )
                        shard.wakeup.wait(self.poll_interval)
                        shard.wakeup.clear()
                    backoff.reset()
                except Exception:  # noqa: BLE001 - service thread survives
                    pending = None
                    read_pos = dict(shard.positions)
                    if stop.is_set():
                        # Teardown, not a failure: a stop() landing inside
                        # a fence wait or a closing sink raises by design;
                        # a clean SIGTERM must not page on ERROR logs.
                        break
                    dlq.registry().note_batch_retry(self.consumer_name)
                    delay = backoff.next_delay()
                    log.exception(
                        "ingestion shard %s/%d: batch failed (attempt %d); "
                        "retrying in %.2fs",
                        self.consumer_name,
                        shard.idx,
                        backoff.attempts,
                        delay,
                    )
                    if backoff.exhausted():
                        made_progress = self._isolate_shard(shard, log)
                        backoff.reset()
                        # Isolation committed positions through the shard's
                        # own sink txns; the prefetch cursor must follow.
                        read_pos = dict(shard.positions)
                        if made_progress:
                            continue
                    stop.wait(delay)
                    continue
            # A pending batch at stop is simply dropped: its positions were
            # never acked, so a restarted pipeline replays it exactly-once.

    def _isolate_shard(self, shard: _Shard, log) -> bool:
        """Bounded retries exhausted on one shard: hand its stuck batch to
        the poison isolation engine (ingest/dlq.py).  Runs inline on the
        shard's own thread against the shard's own sink leg, so the DLQ row
        and cursor advance share the shard's transaction (the r19 fence
        discipline).  stop_at_control=True: a HEALTHY control record ends
        isolation -- the barrier path owns its ordering; a POISON control
        record halts this shard loudly (never auto-skipped)."""
        from armada_tpu.ingest import dlq

        if not hasattr(shard.sink, "store_dead_letters"):
            return False
        try:
            out = dlq.isolate_batch(
                log_=self.log,
                sink=shard.sink,
                converter=self.converter,
                consumer=self.consumer_name,
                partitions=shard.partitions,
                positions=dict(shard.positions),
                renderer=self.renderer,
                stop_at_control=True,
            )
        except Exception:  # noqa: BLE001 - isolation is best-effort;
            log.exception(  # the retry loop survives either way
                "ingestion shard %s/%d: poison isolation failed; "
                "keeping plain retries",
                self.consumer_name,
                shard.idx,
            )
            return False
        if out.new_positions:
            shard._ack(out.new_positions)
        if out.applied_sequences:
            self.rate.record(out.applied_events)
            self.note_counts(out.applied_sequences, out.applied_events)
        return out.progressed

    # --------------------------------------------------------- accounting --

    def _record_ack(self, nxt: dict[int, int]) -> None:
        with self._acked_lock:
            for p, off in nxt.items():
                if off > self._acked.get(p, 0):
                    self._acked[p] = off

    def acked_positions(self) -> dict[int, int]:
        with self._acked_lock:
            return dict(self._acked)

    def note_counts(self, n_sequences: int, n_events: int) -> None:
        with self._counts_lock:
            self.total_sequences += n_sequences
            self.total_events += n_events

    def lag(self) -> dict[int, int]:
        """Unapplied log backlog per partition, in BYTES (positions are
        byte offsets; bytes track events 1:1 at a steady record-size mix)."""
        out: dict[int, int] = {}
        for shard in self.shards:
            out.update(shard.lag())
        return out

    def store_write_stats(self) -> dict[str, dict]:
        """Per-shard store-leg write latency: {shard: {writes, avg_s,
        last_s}}.  Shards sharing one store file (plain embedded sink)
        still report separately -- the spread is what shows a single-writer
        convoy vs a sharded store's parallel legs."""
        out: dict[str, dict] = {}
        for shard in self.shards:
            n = shard.store_writes
            out[str(shard.idx)] = {
                "writes": n,
                "avg_s": round(shard.store_s_total / n, 6) if n else 0.0,
                "last_s": round(shard.store_last_s, 6),
            }
        return out

    def snapshot(self) -> dict:
        """The /healthz `ingest` block entry for this consumer."""
        lag = self.lag()
        return {
            "shards": self.num_shards,
            "alive": self.alive() if self._threads_running else None,
            "offload": self.offload,
            "events_per_s": round(self.rate.value(), 1),
            "total_events": self.total_events,
            "total_sequences": self.total_sequences,
            "lag_bytes": {str(p): v for p, v in sorted(lag.items())},
            "lag_total": sum(lag.values()),
            "abandoned_threads": self._abandoned,
            "control_partition": self.control_partition,
            "store_write": self.store_write_stats(),
        }

    def _disable_offload(self, exc: BaseException) -> None:
        from armada_tpu.core.logging import get_logger

        if self.offload:
            self.offload = False
            get_logger(__name__).warning(
                "ingest converter pool broke (%s); %s falls back to "
                "in-process conversion",
                exc,
                self.consumer_name,
            )
