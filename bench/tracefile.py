"""From a profiler trace to numbers: device busy time, idle gaps, kernel time.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain records: the device's operations (name, start, duration, and the text
of their stats, which names the kernel a custom call runs) and the host
spans the benchmark opened.  ``summarize`` reduces those records; it is
what the tests check on a small recorded trace.
"""

from __future__ import annotations

import glob
import os

#: host spans the benchmark opens around calls into the program's layers
HOST_SPANS = ("engine.step", "session.decode", "session.prefill_chunks", "sampling",
              "session.forward", "host.fetch")
#: stats whose text can name an operation's kernel or source
_TEXT_STATS = ("long_name", "hlo_op", "tf_op", "name", "kernel_details", "hlo_module",
               "program_id", "source", "deduplicated_name")


def _stats_text(ev) -> str:
    parts = []
    for k, v in ev.stats:
        if k in _TEXT_STATS:
            parts.append(f"{k}={v}")
    return " ".join(parts)


def read_xplane(trace_dir: str) -> dict:
    """Device op events of the first TPU and the benchmark's host spans, as
    plain lists of records (ns)."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, host = [], []
    devices = sorted(p.name for p in pd.planes if p.name.startswith("/device:TPU:"))
    for plane in pd.planes:
        if devices and plane.name == devices[0]:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [[ev.name, ev.start_ns, ev.duration_ns, _stats_text(ev)]
                            for ev in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [[ev.name, ev.start_ns, ev.duration_ns]
                         for ev in line.events if ev.name in HOST_SPANS]
    return {"device": devices[0] if devices else None, "ops": ops, "host": host}


def union_ns(intervals) -> list[tuple[float, float]]:
    """Merged, sorted [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted((s, s + d) for s, d in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def idle_by_span(gaps, host) -> dict[str, float]:
    """Seconds of device idle time under each host span: every piece of a
    gap goes to the innermost span open over it (the latest started), or to
    "no span"."""
    marks = [(g0, 0, -1) for g0, _ in gaps] + [(g1, 1, -1) for _, g1 in gaps]
    for i, (_name, s0, d) in enumerate(host):
        marks += [(s0, 2, i), (s0 + d, 3, i)]
    marks.sort(key=lambda m: m[0])
    out: dict[str, float] = {}
    open_spans: set[int] = set()
    in_gap, last = False, None
    for t, kind, i in marks:
        if in_gap and last is not None and t > last:
            inner = max(open_spans, key=lambda j: (host[j][1], -host[j][2]), default=None)
            name = host[inner][0] if inner is not None else "no span"
            out[name] = out.get(name, 0.0) + (t - last) / 1e9
        last = t
        if kind == 0:
            in_gap = True
        elif kind == 1:
            in_gap = False
        elif kind == 2:
            open_spans.add(i)
        else:
            open_spans.discard(i)
    return out


def summarize(rec: dict, window: tuple[float, float] | None = None, top: int = 10) -> dict:
    """Busy and idle time of the device over ``window`` (ns; by default from
    the first to the last recorded event, device or host), the device operations that took
    most time, and idle time grouped by what the host was doing."""
    ops = rec["ops"]
    if not ops:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [], "idle_gaps": [],
                "op_time_s": {}}
    if window is None:
        every = [(o[1], o[2]) for o in ops] + [(h[1], h[2]) for h in rec["host"]]
        window = (min(s for s, _ in every), max(s + d for s, d in every))
    lo, hi = window
    clipped = [(max(s, lo), min(s + d, hi) - max(s, lo)) for _, s, d, _ in ops
               if s + d > lo and s < hi]
    busy = union_ns(clipped)
    busy_ns = sum(e - s for s, e in busy)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    idle = idle_by_span(gaps, rec["host"])
    per_op: dict[str, float] = {}
    for name, s, d, _ in ops:
        if s + d > lo and s < hi:
            per_op[name] = per_op.get(name, 0.0) + d / 1e9
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": sorted(([k, v] for k, v in per_op.items()), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in idle.items()), key=lambda kv: -kv[1])[:top],
        "op_time_s": per_op,
    }


def op_name(event) -> str:
    """An op event's own instruction name: the trace names an op by its HLO
    text, ``%name.N = shape op(operands...)``, whose operands name others."""
    return event[0].split(" = ", 1)[0].lstrip("%")


def kernel_events(rec: dict, prefixes) -> list:
    """Op events whose instruction name starts with one of ``prefixes``
    (a Pallas kernel's custom call is named after its ``pallas_call``)."""
    return [o for o in rec["ops"] if op_name(o).startswith(tuple(prefixes))]
