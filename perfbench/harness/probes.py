"""What the harness counts in its own process while the system runs: XLA
compiles and persistent-cache traffic (jax.monitoring), garbage-collector
pauses by generation (gc.callbacks), and the program's own counters and span
trees, read between cycles."""

from __future__ import annotations

import gc
import time


class CompileLog:
    """Every XLA backend compile in this process, by program name, and the
    persistent cache's hits and misses (a miss is a compile that is then
    written).  A cache hit inside the window still stalls a cycle, so the
    window counts both."""

    def __init__(self):
        import jax

        self.compiles: list = []  # (program, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((str(kw.get("fun_name")), float(secs)))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> tuple:
        return (len(self.compiles), self.cache_hits, self.cache_misses)

    def since(self, mark: tuple) -> int:
        """Programs compiled or fetched from the cache since `mark`."""
        return max(
            len(self.compiles) - mark[0],
            (self.cache_hits - mark[1]) + (self.cache_misses - mark[2]),
        )


class GcLog:
    """Collections by generation with the seconds each paused the process
    (the interpreter holds the GIL through a collection, so client, server
    and every other thread stand still for it)."""

    def __init__(self):
        self.counts = [0, 0, 0]
        self.pause_s = [0.0, 0.0, 0.0]
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            g = info["generation"]
            self.counts[g] += 1
            self.pause_s[g] += time.perf_counter() - self._t0

    def close(self) -> None:
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)

    def mark(self) -> tuple:
        return (tuple(self.counts), tuple(self.pause_s))

    def since(self, mark: tuple) -> dict:
        counts = [a - b for a, b in zip(self.counts, mark[0])]
        pauses = [a - b for a, b in zip(self.pause_s, mark[1])]
        return {"gc_counts": counts, "gc_pauses_s": pauses, "gc_pause_s": sum(pauses)}


def transfer_counts() -> dict:
    """The program's running totals of host<->device transfers."""
    from armada_tpu.models.xfer import TRANSFER_STATS as t

    return {
        "uploads": t.up_transfers,
        "upload_bytes": t.up_bytes,
        "downloads": t.down_transfers,
        "download_bytes": t.down_bytes,
    }


def span_trees_since(seen: set) -> list:
    """Offset-form span trees of the program's cycle traces that finished
    since the last call (the recorder keeps a ring of the newest)."""
    from armada_tpu.ops.trace import recorder

    out = []
    for t in recorder().last():
        key = (t.trace_id, t.kind)
        if key not in seen:
            seen.add(key)
            out.append(t.root.to_dict(t.root.t0))
    return out


def span_names(tree: dict, into: set) -> set:
    into.add(tree.get("name"))
    for c in tree.get("children", ()):
        span_names(c, into)
    return into


def find_span(tree: dict, name: str):
    if tree.get("name") == name:
        return tree
    for c in tree.get("children", ()):
        hit = find_span(c, name)
        if hit is not None:
            return hit
    return None
