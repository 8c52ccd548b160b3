"""From a profiler trace to numbers: the device's busy time and idle share,
the operations that took most of it, and the longest idle gaps with what the
host was doing in each.  Kept with the benchmark, so that every PR computes
these in the same way.

``load_events`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``;
``reduce_events`` works on plain tuples, so that the self-check can feed it
a recorded list (``benchmark/fixtures``) where no chip is at hand.

A device plane is named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
event per executed operation (an enclosing ``while`` and the operations in
its body overlap: busy time is the union, and an operation's own time is its
span less what its children cover; own times are summed per kind of operation,
the name less the compiler's instance number).  Host spans are the benchmark's
``jax.profiler.TraceAnnotation``s, found on the host plane by their prefix.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, NamedTuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench:"
TOP = 10


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _short(name: str) -> str:
    """A device operation's event is named by its whole HLO instruction,
    ``%fusion.3 = bf16[...] fusion(...)``: keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_events(xplane_path: str) -> list:
    """Device operations and the benchmark's host spans, nothing else."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(HOST_PREFIX):
                    events.append(Event(plane.name, line.name,
                                        _short(ev.name), float(ev.start_ns),
                                        float(ev.duration_ns)))
    return events


def _union(intervals: list) -> list:
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _stem(name: str) -> str:
    """``core_attention.36`` -> ``core_attention``: the compiler numbers the
    instances of one kind of operation, and an unrolled 24-layer step has
    24 numbers for the same kernel."""
    return re.sub(r"\.\d+$", "", name)


def _self_times(ops: list) -> dict:
    """Own time per kind of operation (the name less its instance number):
    each event's span less the spans of the events nested in it."""
    total: dict = {}
    stack: list = []     # [name, end, own]
    for ev in sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns)):
        end = ev.start_ns + ev.dur_ns
        while stack and stack[-1][1] <= ev.start_ns:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0.0) + own
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - ev.start_ns
        stack.append([_stem(ev.name), end, ev.dur_ns])
    for name, _, own in stack:
        total[name] = total.get(name, 0.0) + own
    return total


def _host_label(gap: tuple, host: list) -> str:
    """The host span that covers most of the gap."""
    best, cover = "host:no_span", 0.0
    for ev in host:
        lap = (min(gap[1], ev.start_ns + ev.dur_ns)
               - max(gap[0], ev.start_ns))
        if lap > cover:
            best, cover = ev.name, lap
    return best


def reduce_events(events: Iterable[Event]) -> dict:
    """``{}`` where no operation ran on a device plane (a CPU trace)."""
    events = list(events)
    planes = sorted({e.plane for e in events
                     if e.plane.startswith(DEVICE_PLANE)})
    if not planes:
        return {}
    host = [e for e in events if not e.plane.startswith(DEVICE_PLANE)]
    busy_ns, window_ns, own, gaps = 0.0, 0.0, {}, []
    for plane in planes:
        ops = [e for e in events if e.plane == plane]
        merged = _union([(e.start_ns, e.start_ns + e.dur_ns) for e in ops])
        busy_ns += sum(end - start for start, end in merged)
        window_ns += merged[-1][1] - merged[0][0]
        for name, ns in _self_times(ops).items():
            own[name] = own.get(name, 0.0) + ns / len(planes)
        for (_, end), (start, _) in zip(merged, merged[1:]):
            gaps.append((end, start, end - merged[0][0]))
    busy_s = busy_ns / len(planes) / 1e9
    window_s = window_ns / len(planes) / 1e9
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": [[name, ns / 1e9] for name, ns in sorted(
            own.items(), key=lambda kv: -kv[1])[:TOP]],
        # named by what the host was doing and by when in the slice it was
        "idle_gaps": [[f"{_host_label(g, host)}@{g[2] / 1e9:.3f}s",
                       (g[1] - g[0]) / 1e9] for g in gaps[:TOP]],
        "n_device_events": sum(e.plane in planes for e in events),
    }


def reduce_trace(trace_dir: str) -> dict:
    return reduce_events(load_events(find_xplane(trace_dir)))


def _main(argv=None) -> int:
    """``python3 benchmark/trace_reduce.py <trace_dir> [--planes]
    [--record out.json.gz --share 0.3]``: print the reduction; list the
    planes and lines of the trace; record the leading share of its events
    as a fixture."""
    import argparse
    import gzip
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--planes", action="store_true")
    ap.add_argument("--record")
    ap.add_argument("--share", type=float, default=0.3)
    args = ap.parse_args(argv)
    path = find_xplane(args.trace_dir)
    if args.planes:
        from jax.profiler import ProfileData

        for plane in ProfileData.from_file(path).planes:
            print(f"plane {plane.name!r}")
            for line in plane.lines:
                evs = list(line.events)
                print(f"  line {line.name!r}: {len(evs)} events")
                for ev in evs[:3]:
                    stats = {k: str(v)[:80] for k, v in list(ev.stats)[:8]}
                    print(f"    {ev.name[:100]!r} start={ev.start_ns} "
                          f"dur={ev.duration_ns} stats={stats}")
    events = load_events(path)
    print(json.dumps(reduce_events(events), indent=1))
    if args.record:
        device = [e for e in events if e.plane.startswith(DEVICE_PLANE)]
        t0 = min(e.start_ns for e in device)
        t1 = max(e.start_ns + e.dur_ns for e in device)
        cut = t0 + args.share * (t1 - t0)
        kept = [[e.plane, e.line, e.name, e.start_ns - t0, e.dur_ns]
                for e in events if t0 <= e.start_ns
                and e.start_ns + e.dur_ns <= cut]
        with gzip.open(args.record, "wt") as f:
            json.dump({"events": kept,
                       "expect": reduce_events(Event(*k) for k in kept)}, f)
        print(f"recorded {len(kept)} events to {args.record}")
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
