"""From a profiler trace to device metrics: the reduction every PR shares.

The reduction works on a neutral form, so that it can be tested on a small
recorded trace without a chip:

    {"device": {"<plane>": [[name, start_s, dur_s], ...]},   # device op events
     "host": [[name, start_s, dur_s], ...]}                  # program spans

`load_xplane` makes that form from the `.xplane.pb` the JAX profiler writes.
Device events are the operation line of each `/device:TPU:n` plane; host
events are the `TraceAnnotation`s whose names the caller asks for (the
program's spans, bridged by its `ARMADA_TRACE_JAX=1`, and the harness's own
`perfbench_cycle` marker).  Both are on the profiler's clock.

The profiler puts no scope on a device event (PERF.md section 3), so the round
kernel is found structurally: of the top-level `while` operations on the
device, the one with the most device time.
"""

from __future__ import annotations

import re

CYCLE_MARKER = "perfbench_cycle"
OP_LINE = "XLA Ops"
_WHILE = re.compile(r"(^|[^a-z])while([^a-z]|$)")


def short_name(name: str) -> str:
    """`%while.33 = (f32[4,50176,4]{...}, ...) while(...)` -> `while.33`: the
    profiler names a device event by the operation's whole HLO text."""
    return name.split(" = ", 1)[0].strip().lstrip("%")[:120]


def load_xplane(path: str, host_names) -> dict:
    from jax.profiler import ProfileData

    wanted = set(host_names) | {CYCLE_MARKER}
    out = {"device": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OP_LINE] or lines
            out["device"][plane.name] = [
                [short_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9]
                for ln in ops
                for e in ln.events
            ]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in wanted:
                        out["host"].append([e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9])
    return out


def union(intervals) -> list:
    """Sorted, merged [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(merged, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] inside the merged intervals."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def _ns(seconds: float) -> int:
    """Whole nanoseconds: the profiler's own grid (`load_xplane` made the
    seconds from integer `start_ns` / `duration_ns`, and this recovers them)."""
    return round(seconds * 1e9)


def top_level(events) -> list:
    """Events not inside another event of the same line (a `while`'s body ops
    are traced as events nested in it).  Nesting is decided on whole
    nanoseconds: the device runs operations back to back on a 1 ns grid, and a
    float `start + dur` rounds above the next event's start often enough to
    take a top-level call for a child of the one before it."""
    out = []
    end = None
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        at = _ns(start)
        if end is None or at >= end:
            out.append([name, start, dur])
            end = at + _ns(dur)
    return out


def find_kernel(top) -> str:
    """Name of the top-level `while` with the most device time, or ""."""
    by_name: dict = {}
    for name, _, dur in top:
        if _WHILE.search(name.lower()):
            by_name[name] = by_name.get(name, 0.0) + dur
    return max(by_name, key=by_name.get) if by_name else ""


def innermost_segments(host) -> list:
    """The host timeline cut into [start, end, name of the innermost open
    span]: what the host was doing at each instant, by the program's spans."""
    points = sorted({t for _, s, d in host for t in (s, s + d)})
    spans = sorted(host, key=lambda e: (e[1], -e[2]))
    out = []
    for lo, hi in zip(points, points[1:]):
        mid = (lo + hi) / 2
        best = None
        for name, s, d in spans:
            if s > mid:
                break
            if s + d >= mid and (best is None or d <= best[1]):
                best = (name, d)
        if best is not None:
            out.append([lo, hi, best[0]])
    return out


def reduce_trace(trace: dict, top_n: int = 10) -> dict:
    """Device metrics of the traced window.  The window is the span from the
    first cycle marker's start to the last one's end."""
    cycles = sorted([s, s + d] for name, s, d in trace["host"] if name == CYCLE_MARKER)
    if not cycles or not trace["device"]:
        return {}
    w0, w1 = cycles[0][0], cycles[-1][1]
    window = w1 - w0
    spans = [e for e in trace["host"] if e[0] != CYCLE_MARKER]

    busy_total = 0.0
    kernel_total = 0.0
    post_total = 0.0
    kernel_calls = 0
    op_seconds: dict = {}
    gap_seconds: dict = {}
    kernel_name = ""
    segments = innermost_segments(spans)
    for plane, events in sorted(trace["device"].items()):
        events = [e for e in events if e[1] + e[2] > w0 and e[1] < w1]
        top = top_level(events)
        merged = union([s, s + d] for _, s, d in top)
        busy = covered(merged, w0, w1)
        busy_total += busy
        for name, _, dur in events:
            op_seconds[name] = op_seconds.get(name, 0.0) + dur
        kernel = find_kernel(top)
        kernel_name = kernel_name or kernel
        others = union([s, s + d] for name, s, d in top if name != kernel)
        calls = [(s, d) for name, s, d in top if name == kernel]
        for c0, c1 in cycles:
            mine = [(s, d) for s, d in calls if c0 <= s < c1]
            kernel_total += sum(d for _, d in mine)
            kernel_calls += len(mine)
            if mine:
                post_total += covered(others, max(s + d for s, d in mine), c1)
        # idle gaps of this chip, by what the host was doing in them
        gaps, at = [], w0
        for s, e in merged:
            if e <= w0 or s >= w1:
                continue
            if s > at:
                gaps.append([at, s])
            at = max(at, e)
        if at < w1:
            gaps.append([at, w1])
        for lo, hi, name in segments:
            idle = covered(gaps, lo, hi)
            if idle > 0.0:
                gap_seconds[name] = gap_seconds.get(name, 0.0) + idle
    chips = len(trace["device"])
    n = len(cycles)
    busy_s = busy_total / chips

    def top_of(d):
        ranked = sorted(d.items(), key=lambda kv: -kv[1])[:top_n]
        return [[name, seconds / chips] for name, seconds in ranked]

    return {
        "window_s": window,
        "busy_s": busy_s,
        "device_idle_share_pct": 100.0 * (1.0 - busy_s / window),
        "traced_cycles": n,
        "kernel_name": kernel_name,
        "kernel_calls": kernel_calls // chips,
        "kernel_device_s_total": kernel_total / chips,
        "kernel_device_s_per_cycle": kernel_total / chips / n,
        "post_round_device_s_per_cycle": post_total / chips / n,
        "device_ops": top_of(op_seconds),
        "idle_gaps": top_of(gap_seconds),
    }
