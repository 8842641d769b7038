"""Per-cycle device-transfer accounting.

Per-cycle transfer COUNT and BYTES are exact on any backend, while what a
transfer costs on this host is not measured (ROADMAP S5 prices it) -- a
regression that doubles the upload payload is invisible in a CPU-only
run's wall clock.  These counters make it legible without a TPU: the slab
delta cache counts every
host->device array it ships (slab.DeviceDeltaCache), the compact decode
counts its device->host fetch (problem._fetch_compact), and bench.py and
perfbench/run.py (`uploads_per_cycle`) report the per-cycle numbers.

Counters are process-global and single-threaded like the cycle itself;
``reset()`` at cycle start, ``snapshot()`` at cycle end.

Each counted transfer also lands as an instant event in the active cycle
trace (ops/trace.py) with its byte count, so a Perfetto timeline shows
WHERE in the cycle each transfer happened -- the counters stay
the aggregate contract, the trace is the correlated view of the same
stream (no-op outside an armed cycle).
"""

from __future__ import annotations

from armada_tpu.ops.trace import recorder as _trace


class TransferStats:
    __slots__ = (
        "up_transfers", "up_bytes", "down_transfers", "down_bytes",
        "up_chip_bytes", "up_sharded_transfers",
    )

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.up_transfers = 0
        self.up_bytes = 0
        self.down_transfers = 0
        self.down_bytes = 0
        # Mesh serving (parallel/mesh_slab.py): a node-axis-sharded upload
        # lands nbytes/shards per chip.  up_chip_bytes accumulates the
        # per-chip share (== up_bytes when nothing is sharded), so the
        # single-chip HBM pressure stays legible on a mesh.
        self.up_chip_bytes = 0
        self.up_sharded_transfers = 0

    def count_up(self, nbytes: int, shards: int = 1) -> None:
        self.up_transfers += 1
        self.up_bytes += int(nbytes)
        per_chip = (int(nbytes) + shards - 1) // shards if shards > 1 else int(nbytes)
        self.up_chip_bytes += per_chip
        if shards > 1:
            self.up_sharded_transfers += 1
            _trace().note("xfer_up", bytes=int(nbytes), shards=int(shards))
        else:
            _trace().note("xfer_up", bytes=int(nbytes))

    def count_down(self, nbytes: int) -> None:
        self.down_transfers += 1
        self.down_bytes += int(nbytes)
        _trace().note("xfer_down", bytes=int(nbytes))

    def snapshot(self) -> dict:
        out = {
            "up_transfers": self.up_transfers,
            "up_bytes": self.up_bytes,
            "down_transfers": self.down_transfers,
            "down_bytes": self.down_bytes,
        }
        if self.up_sharded_transfers:
            out["up_chip_bytes"] = self.up_chip_bytes
            out["up_sharded_transfers"] = self.up_sharded_transfers
        return out


TRANSFER_STATS = TransferStats()
