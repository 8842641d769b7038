"""Device time by the program's own named scopes.

The device programs wrap their parts in `jax.named_scope` (`armada.round` >
`armada.round.evict` / `armada.round.loop` / `armada.round.repair`, a trip's
phases `select` / `fit` / `commit` / `window` inside the loop's body;
`armada.compact`, `armada.verify`, `armada.scatter`, `armada.explain`).  XLA
keeps each scope in the `op_name` metadata of the operations it compiles, and
the TPU profiler (libtpu 0.0.34) copies it into the `tf_op` stat of each
operation's event METADATA, once per operation of a program, beside its
`program_id`.  The jax profiler's own reader (`ProfileData`, which
`tracered.load_xplane` uses) gives an event's stats but not its metadata's,
so this module reads the `.xplane.pb` itself.  It extends `tracered.py`'s
neutral form by two keys:

    "scope_of": {<program call>: {<short operation name>: <op_name path>}},
    "modules":  {"<plane>": [[<program call>, start_s, dur_s], ...]},

a program call being its event's name on the `XLA Modules` line,
`jit_<function>(<program_id>)` (operation names repeat across programs:
`fusion.1` is in five).  `load(path)` makes both; `reduce(trace)` gives
`FIELDS`, per traced cycle, and all of them None for a trace without
`scope_of`.  From a profile on the command line, the cycles being the host
spans that `--cycle` names (the benchmark's own marker by default; a profile
of a served plane with `ARMADA_TRACE_JAX=1` has `sidecar_round`):

    python -m perfbench.harness.scopes PROFILE.xplane.pb [--cycle sidecar_round]
"""

from __future__ import annotations

import argparse
import bisect
import collections
import json

from perfbench.harness import tracered

MODULE_LINE = "XLA Modules"
LOOP = "armada.round.loop"
PHASES = ("select", "fit", "commit", "window")
PROGRAMS = {
    "armada.round": "round_program_device_s",
    "armada.compact": "compact_device_s",
    "armada.verify": "verify_device_s",
    "armada.scatter": "scatter_device_s",
    "armada.explain": "explain_device_s",
}
FIELDS = (
    [f"round_{p}_device_s" for p in PHASES + ("loop_other",)]
    + ["round_loop_ops_total", "round_loop_other_ops"]
    + list(PROGRAMS.values())
    + ["unowned_device_s"]
)


def _xspace():
    """The message class of an `.xplane.pb`: the fields of the profiler's
    `XSpace` (tsl/profiler/protobuf/xplane.proto, same numbers) that this
    module reads; the rest is skipped by the parser.  The jax profiler's own
    reader (`ProfileData`) does not give an event's metadata, where the
    scope is."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    proto = descriptor_pb2.FileDescriptorProto(name="perfbench_xplane.proto", package="perfbench_xplane")
    types = {
        "XSpace": [("planes", 1, "XPlane")],
        "XPlane": [("name", 2, F.TYPE_STRING), ("lines", 3, "XLine"),
                   ("event_metadata", 4, "EventMetadataEntry"), ("stat_metadata", 5, "StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, F.TYPE_INT64), ("value", 2, "XEventMetadata!")],
        "StatMetadataEntry": [("key", 1, F.TYPE_INT64), ("value", 2, "XStatMetadata!")],
        "XLine": [("name", 2, F.TYPE_STRING), ("timestamp_ns", 3, F.TYPE_INT64), ("events", 4, "XEvent")],
        "XEvent": [("metadata_id", 1, F.TYPE_INT64), ("offset_ps", 2, F.TYPE_INT64), ("duration_ps", 3, F.TYPE_INT64)],
        "XEventMetadata": [("name", 2, F.TYPE_STRING), ("display_name", 4, F.TYPE_STRING), ("stats", 5, "XStat")],
        "XStat": [("metadata_id", 1, F.TYPE_INT64), ("uint64_value", 3, F.TYPE_UINT64),
                  ("int64_value", 4, F.TYPE_INT64), ("str_value", 5, F.TYPE_STRING), ("ref_value", 7, F.TYPE_UINT64)],
        "XStatMetadata": [("name", 2, F.TYPE_STRING)],
    }
    for name, fields in types.items():
        msg = proto.message_type.add(name=name)
        for field, number, kind in fields:
            f = msg.field.add(name=field, number=number)
            if isinstance(kind, str):  # a message: repeated, or one where the name ends "!"
                f.type, f.type_name = F.TYPE_MESSAGE, f".perfbench_xplane.{kind.rstrip('!')}"
                f.label = F.LABEL_OPTIONAL if kind.endswith("!") else F.LABEL_REPEATED
            else:
                f.type, f.label = kind, F.LABEL_OPTIONAL
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("perfbench_xplane.XSpace"))


def load(path: str) -> dict:
    """`scope_of` and `modules` of a profile (the `/device:TPU:n` planes).
    A program call's scope is the outermost `armada.*` scope most of its
    operations carry (none: a helper such as `jit_squeeze`)."""
    space = _xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    scope_of: dict = {}
    modules: dict = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        stat_name = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        by_program: dict = {}
        for md in meta.values():
            stats = {stat_name.get(s.metadata_id): s for s in md.stats}
            if "tf_op" in stats and "program_id" in stats:
                ops = by_program.setdefault(str(stats["program_id"].uint64_value), {})
                ops[tracered.short_name(md.name)] = stats["tf_op"].str_value.rstrip(":")
        for line in plane.lines:
            if line.name != MODULE_LINE:
                continue
            events = modules.setdefault(plane.name, [])
            for e in line.events:
                name = meta[e.metadata_id].name
                scope_of.setdefault(name, by_program.get(name.rpartition("(")[2].rstrip(")"), {}))
                # whole nanoseconds, as the jax reader gives the operations
                start_ns = line.timestamp_ns + e.offset_ps // 1000
                events.append([name, start_ns * 1e-9, (e.duration_ps // 1000) * 1e-9])
    return {"scope_of": scope_of, "modules": modules}


def phase_of(path: str) -> str:
    """The first of `PHASES` after `armada.round.loop` in an op_name path,
    else "loop_other" (the `while` itself, its condition, body code outside
    the four)."""
    parts = path.split("/")
    if LOOP in parts:
        for part in parts[parts.index(LOOP) + 1 :]:
            if part in PHASES:
                return part
    return "loop_other"


def program_of(path: str) -> str:
    """The outermost `armada.*` scope of an op_name path, or ""."""
    for part in path.split("/"):
        if part in PROGRAMS:
            return part
    return ""


def program_scope(ops: dict) -> str:
    """The outermost `armada.*` scope most of a program's operations carry."""
    counts = collections.Counter(program_of(p) for p in ops.values())
    counts.pop("", None)
    return counts.most_common(1)[0][0] if counts else ""


def _phases(events, scope: dict) -> list:
    """Each event of one kernel call as [name, ns no nested event covers,
    depth, phase]: every nanosecond goes to the innermost event that covers
    it (nesting on whole nanoseconds, as `top_level`), and the event's phase
    is its op_name path's.  The profiler leaves a `while` or a conditional
    without a path, and the compiler some operations it made: such an event
    takes the phase that holds most of the time nested in it (XLA sinks a
    few operations of the next phase into a conditional's branches), else
    the phase of the event it is nested in; the call itself, the loop's
    `while`, is "loop_other"."""
    nodes: list = []  # [name, self ns, depth, phase, parent index, ns]
    stack: list = []  # [node index, end ns]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        at = tracered._ns(start)
        end = at + tracered._ns(dur)
        while stack and stack[-1][1] <= at:
            stack.pop()
        parent = stack[-1][0] if stack else -1
        if stack:
            nodes[parent][1] -= min(end, stack[-1][1]) - at
        path = scope.get(name)
        nodes.append([name, end - at, len(stack), path and phase_of(path), parent, end - at])
        stack.append([len(nodes) - 1, end])
    nested = collections.defaultdict(collections.Counter)  # node index -> phase -> ns
    for i in range(len(nodes) - 1, -1, -1):
        node = nodes[i]
        if node[3] is None and node[2] > 0 and nested[i]:
            node[3] = nested[i].most_common(1)[0][0]
        if node[3] is not None and node[4] >= 0:
            nested[node[4]][node[3]] += node[5]
    for node in nodes:
        if node[3] is None:
            node[3] = nodes[node[4]][3] if node[4] >= 0 else "loop_other"
    return [node[:4] for node in nodes]


def reduce(trace: dict, top_n: int = 10) -> dict:
    """Per traced cycle: the kernel's device time by phase (the five sum to
    `kernel_device_s_per_cycle`; `round_loop_other_ops` names the largest
    parts of `loop_other`, the `while`'s own time being the gaps between its
    operations), the operations nested in the kernel's calls (all cycles
    together), and each program's device time by the `XLA Modules` line
    (`unowned_device_s`: calls of programs under no `armada.*` scope)."""
    out = dict.fromkeys(FIELDS)
    scope_of = trace.get("scope_of")
    cycles = sorted([s, s + d] for name, s, d in trace["host"] if name == tracered.CYCLE_MARKER)
    if not scope_of or not cycles or not trace["device"]:
        return out
    w0, w1 = cycles[0][0], cycles[-1][1]
    chips, n = len(trace["device"]), len(cycles)

    def in_cycle(start: float) -> bool:
        return any(c0 <= start < c1 for c0, c1 in cycles)

    phase_ns = dict.fromkeys(PHASES + ("loop_other",), 0)
    other_ns: dict = {}
    ops = 0
    for plane, events in trace["device"].items():
        events = sorted((e for e in events if e[1] + e[2] > w0 and e[1] < w1), key=lambda e: e[1])
        starts = [tracered._ns(e[1]) for e in events]
        top = tracered.top_level(events)
        kernel = tracered.find_kernel(top)
        programs = trace.get("modules", {}).get(plane, [])
        for _, start, dur in (e for e in top if e[0] == kernel and in_cycle(e[1])):
            lo, hi = tracered._ns(start), tracered._ns(start) + tracered._ns(dur)
            calls = [m for m in programs if tracered._ns(m[1]) <= lo and hi <= tracered._ns(m[1] + m[2])]
            scope = scope_of.get(calls[0][0], {}) if calls else {}
            inside = events[bisect.bisect_left(starts, lo) : bisect.bisect_left(starts, hi)]
            for name, ns, depth, phase in _phases(inside, scope):
                phase_ns[phase] += ns
                if phase == "loop_other":
                    other_ns[name] = other_ns.get(name, 0) + ns
                ops += depth > 0
    for phase, ns in phase_ns.items():
        out[f"round_{phase}_device_s"] = ns * 1e-9 / chips / n
    out["round_loop_ops_total"] = ops // chips
    ranked = sorted(other_ns.items(), key=lambda kv: -kv[1])[:top_n]
    out["round_loop_other_ops"] = [[name, ns * 1e-9 / chips / n] for name, ns in ranked]

    program_s = dict.fromkeys(list(PROGRAMS) + [""], 0.0)
    for name, start, dur in (e for evs in trace.get("modules", {}).values() for e in evs):
        if in_cycle(start):
            program_s[program_scope(scope_of.get(name, {}))] += dur
    for scope, field in PROGRAMS.items():
        out[field] = program_s[scope] / chips / n
    out["unowned_device_s"] = program_s[""] / chips / n
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="device time of a profile by the program's named scopes")
    ap.add_argument("xplane", help="the .xplane.pb the jax profiler wrote")
    ap.add_argument("--cycle", default=tracered.CYCLE_MARKER, help="host span that marks a cycle")
    args = ap.parse_args(argv)
    trace = tracered.load_xplane(args.xplane, {args.cycle})
    trace["host"] = [[tracered.CYCLE_MARKER, s, d] for name, s, d in trace["host"] if name == args.cycle]
    trace.update(load(args.xplane))
    reduced = tracered.reduce_trace(trace)
    print(json.dumps(dict(reduced, **reduce(trace)) if reduced else {}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
