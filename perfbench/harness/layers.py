"""The one general reader of per-layer metrics.  Each metric is a data file,
`layers/<name>.json`, whose `read` object says where the number comes from:

    {"kind": "cycle_field", "field": "wire_s", "reduce": "median"}
        a field of the per-cycle record (counters, host clocks); a dotted name
        goes into a group of the record: `pool.<key>` is any scalar of the
        round's own stats JSON, `scatter_rows.sg_rows` an arg of a span;
        with "equals": <value> each sample becomes 1 where the field has that
        value and 0 where it has another, before the reduction; the reduction
        "count" is how many samples there are (a share of the window's rounds
        is a `ratio` of a "sum" of such 0 / 1 samples over a "count")
    {"kind": "span_sum", "spans": ["assemble"], "reduce": "median"}
        per cycle, the seconds inside the named program spans (a span nested
        in another named span counts once), then the reduction over cycles;
        "root": "sidecar_round" looks only under that root span
    {"kind": "trace_field", "field": "kernel_device_s_per_cycle"}
        a field of the device-trace reduction (tracered.py)
    {"kind": "run_field", "field": "memory_peak_bytes"}
        a reading of the whole run
    {"kind": "ratio", "num": <read>, "den": <read>, "scale": 1e6}
    {"kind": "roofline", "seconds": <read>, "trips": <read>, "bytes": "<fn>"}
        least seconds the chip could take for `trips` trips, by the named
        byte count of roofline.py over the device's peak, as % of `seconds`

A reader that finds nothing to read returns None and the metric is left out.
`cycles` may be "window" (default) or "traced" (only the profiled cycles).
"""

from __future__ import annotations

import statistics

from perfbench.harness import roofline


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile, q in [0, 100]."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


_REDUCE = {
    "median": statistics.median,
    "mean": statistics.fmean,
    "sum": sum,
    "max": max,
    "min": min,
    "count": len,
    "p75": lambda xs: percentile(xs, 75.0),
}


def span_seconds(tree: dict, names) -> float:
    """Seconds inside spans named in `names` in one offset-form span tree; a
    match is not descended into, so nested matches count once."""
    if tree.get("name") in names:
        return float(tree.get("dur_s", 0.0))
    return sum(span_seconds(c, names) for c in tree.get("children", ()))


def field_of(record: dict, dotted: str):
    """`record["a"]["b"]` for "a.b"; None where a part is missing."""
    for key in dotted.split("."):
        record = record.get(key) if isinstance(record, dict) else None
    return record


def read(spec: dict, ctx: dict):
    """The value `spec` describes, or None when there is nothing to read.
    `ctx` holds the window's per-cycle records (`cycles`), the device-trace
    reduction (`trace`, empty without one), whole-run readings (`run`) and the
    cell's sizes (`shapes`)."""
    kind = spec["kind"]
    cycles, trace = ctx["cycles"], ctx["trace"]
    if spec.get("cycles") == "traced":
        cycles = [c for c in cycles if c.get("traced")]
    if kind == "cycle_field":
        xs = [x for x in (field_of(c, spec["field"]) for c in cycles) if x is not None]
        if "equals" in spec:
            xs = [1 if x == spec["equals"] else 0 for x in xs]
        return _REDUCE[spec.get("reduce", "median")](xs) if xs else None
    if kind == "span_sum":
        names = set(spec["spans"])
        xs = [
            sum(
                span_seconds(t, names)
                for t in c["spans"]
                if spec.get("root") in (None, t.get("name"))
            )
            for c in cycles
            if c.get("spans")
        ]
        return _REDUCE[spec.get("reduce", "median")](xs) if xs else None
    if kind == "trace_field":
        return None if not trace else trace.get(spec["field"])
    if kind == "run_field":
        return ctx["run"].get(spec["field"])
    if kind == "ratio":
        num = read(spec["num"], ctx)
        den = read(spec["den"], ctx)
        if num is None or not den:
            return None
        return float(spec.get("scale", 1.0)) * num / den
    if kind == "roofline":
        seconds = read(spec["seconds"], ctx)
        trips = read(spec["trips"], ctx)
        if not seconds or not trips or not trace:
            return None
        peak = roofline.peak(trace["device_kind"], "hbm_bytes_per_s")
        least = trips * getattr(roofline, spec["bytes"])(ctx["shapes"]) / peak
        return 100.0 * least / seconds
    raise ValueError(f"unknown reader kind {kind!r}")
