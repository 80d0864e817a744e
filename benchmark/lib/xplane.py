"""The reduction from a profiler trace (.xplane.pb) to device busy and
idle time, kernel time by name, and idle gaps by what the host was doing.

Run as a script it reads one trace and prints the reduction as JSON; the
harness starts it as a child with JAX_PLATFORMS=cpu AFTER the daemon has
gone, because reading a trace needs jax's ProfileData and the harness's
own process never imports jax.  `reduce` itself is plain Python over
(name, start_ns, end_ns) tuples, which is what the self-test feeds it."""

from __future__ import annotations

import json
import sys

DEVICE_PLANE = "/device:TPU:"
SPAN_PREFIX = "crane:solve:"
# a pause of the device shorter than this is the kernel launch rhythm,
# not something the host can be asked about
GAP_FLOOR_NS = 1_000_000


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged copy of [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float) -> float:
    """Length of the merged intervals inside [lo, hi)."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def short(name: str) -> str:
    """`%while.102 = (s32[]...) while(...)` -> `while.102`: the trace
    names an op by its whole HLO line."""
    return name.split(" = ")[0].lstrip("%")[:64]


def cycle_starts(spans) -> list[float]:
    """When each traced cycle's first solve span began.  A cycle begins
    at every span that carries the label of the trace's first span
    (`backfill` where the head and the tail each solve, `immediate`
    where one solve does)."""
    spans = sorted(spans, key=lambda s: s[1])
    return [s for label, s, _ in spans if label == spans[0][0]]


def reduce(device_planes: dict, spans) -> dict:
    """device_planes: {plane name: [(op name, start_ns, end_ns), ...]};
    spans: [(label, start_ns, end_ns)] of the host's solve spans.

    The program's span closes when the solve is ENQUEUED (it waits for
    the device after the span), so a span says when a cycle's solves
    began, not how long they ran.  A cycle's device work is therefore
    taken from its first span's start to the next cycle's.  Within that
    stretch the longest pause of the device is where the host commits,
    dispatches and builds the next batch: the idle time before it is
    `inside_solve` (the device waits for the host between the head's and
    the tail's programs), and from it on `between_solves`."""
    if not device_planes:
        return {}
    edges = [t for evs in device_planes.values() for _, s, e in evs
             for t in (s, e)] + [t for _, s, e in spans for t in (s, e)]
    if not edges:
        return {}
    lo, hi = min(edges), max(edges)
    busy = []
    by_name: dict[str, float] = {}
    merged_all = []
    for evs in device_planes.values():
        merged = union((s, e) for _, s, e in evs)
        merged_all.append(merged)
        busy.append(covered(merged, lo, hi))
        for name, s, e in evs:
            by_name[short(name)] = by_name.get(short(name), 0.0) + (e - s)
    merged = merged_all[0]
    starts = cycle_starts(spans) if spans else []
    bounds = list(zip(starts, starts[1:] + [hi]))
    # each cycle's solve interval: from its first span's start to where
    # the longest pause of that cycle begins
    solving = []
    for c_lo, c_hi in bounds:
        ops = [(s, e) for s, e in merged if c_lo <= s < c_hi]
        if not ops:
            continue
        pauses = [(nxt[0] - cur[1], cur[1])
                  for cur, nxt in zip(ops, ops[1:] + [(c_hi, c_hi)])]
        solving.append((c_lo, max(pauses)[1]))
    gaps = []
    cursor = lo
    for s, e in merged + [(hi, hi)]:
        if s - cursor >= GAP_FLOOR_NS:
            mid = (cursor + s) / 2.0
            inside = any(a <= mid < b for a, b in solving)
            gaps.append(("inside_solve" if inside else "between_solves",
                         s - cursor))
        cursor = max(cursor, e)
    sums: dict[str, float] = {}
    for name, ns in gaps:
        sums["sum:" + name] = sums.get("sum:" + name, 0.0) + ns
    longest = sorted(gaps, key=lambda g: -g[1])[:10 - len(sums)]
    # the last cycle may be cut by the trace's end: leave it out
    cycle_busy_ms = [covered(merged, c_lo, c_hi) / 1e6
                     for c_lo, c_hi in bounds[:-1]]
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(device_planes),
        "device_ops": [[n, ns / 1e9] for n, ns in top],
        "idle_gaps": ([[n, ns / 1e9] for n, ns in sorted(sums.items())]
                      + [[n, ns / 1e9] for n, ns in longest]),
        "solve_spans": len(spans),
        "cycles": len(bounds),
        "cycle_busy_ms": cycle_busy_ms,
    }


def load(path: str):
    """(device_planes, spans) from an .xplane.pb file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_planes: dict[str, list] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            device_planes[plane.name] = [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for ln in ops for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return device_planes, spans


def main(argv) -> int:
    if len(argv) != 2:
        print("usage: xplane.py <trace.xplane.pb>", file=sys.stderr)
        return 2
    print(json.dumps(reduce(*load(argv[1]))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
