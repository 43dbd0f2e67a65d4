#!/usr/bin/env python3
"""Whose fault is the idle device? Join a profiler trace's device idle
gaps to the request stage the host was in.

    python tools/trace_gaps.py <file.xplane.pb | events.json> [--json]

The server opens a `jax.profiler.TraceAnnotation("pilosa:<stage>")`
around every stage of every request record (utils/timeline.py), so a
trace taken while it serves (`jax.profiler.start_trace`, host tracer
level >= 1) holds, in its host plane, one line per thread with the
`pilosa:` events of that thread — on the same clock as the device
planes' `XLA Ops` lines. This tool reads both:

1. per device, the union of the `XLA Ops` intervals; every hole
   between two of them is an idle gap;
2. for each gap, the host thread that enqueued the program the gap
   ends with: the thread whose launch event (JAX's own
   `PjitFunction(<name>)` event around every call of a jitted
   function, eager `jnp` helpers included, or the `pilosa:dispatch`
   stage) started last before the gap's end;
3. that thread's innermost open `pilosa:` stage over the gap, piece by
   piece; where no stage is open on it, `idle.no_request` (the server
   had nothing to do), `idle.trace_start` before the thread's first
   recorded stage (a span that was already open when the profiler
   started leaves no event), and `idle.no_launch` where no launch
   event precedes the gap at all.

It prints idle seconds by stage, largest first (means over devices).
A stage that tops this table is where host time turns into device
idle time; `request.stage_seconds` in /debug/vars says how long the
stage takes, this says how much of that the chip waited for.

`events.json` is the same data as plain lists, for tests and for
traces reduced elsewhere: {"planes": [{"name": "/device:TPU:0",
"lines": [{"name": "XLA Ops", "events": [[name, start_ns, dur_ns],
...]}]}, {"name": "/host:CPU", "lines": [...]}]}.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = re.compile(r"^/host:")
STAGE_PREFIX = "pilosa:"
LAUNCH = re.compile(r"^PjitFunction\(|^pilosa:dispatch$")
NO_REQUEST = "idle.no_request"
NO_LAUNCH = "idle.no_launch"
TRACE_START = "idle.trace_start"


def load(path: str) -> list:
    """[{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns]]}]}] from an .xplane.pb (via jax.profiler.ProfileData) or
    from the JSON form of the same."""
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)["planes"]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not HOST_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            lines.append({"name": line.name, "events": [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def busy_union(events: list) -> list:
    """Sorted, merged [start, end) of the events with a duration."""
    out = []
    for s, e in sorted((s, s + d) for _, s, d in events if d > 0):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def innermost_segments(events: list) -> list:
    """One thread's `pilosa:` events (properly nested, as context
    managers are) flattened to disjoint (start, end, stage) pieces
    named by the innermost event open there. Sorted by start."""
    marks = []
    for name, start, dur in events:
        if dur <= 0:
            continue
        marks.append((start, 1, -dur, name))
        marks.append((start + dur, 0, 0, name))
    # Ends before starts at one instant; longer (outer) events first.
    marks.sort()
    out, stack, last = [], [], None
    for t, opening, _, name in marks:
        if stack and last is not None and t > last:
            out.append((last, t, stack[-1]))
        if opening:
            stack.append(name)
        elif stack:
            # Close the innermost event of that name.
            for i in range(len(stack) - 1, -1, -1):
                if stack[i] == name:
                    del stack[i]
                    break
        last = t
    return out


def attribute(planes: list) -> dict:
    """{"idle_s", "busy_s", "devices", "by_stage": {stage: seconds}}:
    every device's idle gaps split by host stage; seconds are means
    over devices."""
    threads = []     # (segments, segment starts)
    launches = []    # (start_ns, thread index), sorted
    for plane in planes:
        if not HOST_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            staged = [e for e in line["events"]
                      if e[0].startswith(STAGE_PREFIX)]
            hits = [e[1] for e in line["events"] if LAUNCH.search(e[0])]
            if not staged and not hits:
                continue
            segs = innermost_segments(staged)
            threads.append((segs, [s for s, _, _ in segs]))
            launches.extend((t, len(threads) - 1) for t in hits)
    launches.sort()
    launch_starts = [t for t, _ in launches]

    by_stage, idle_ns, busy_ns, n_dev = {}, 0, 0, 0
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        ops = [e for line in plane["lines"] if line["name"] == OPS_LINE
               for e in line["events"]]
        busy = busy_union(ops)
        if not busy:
            continue
        n_dev += 1
        busy_ns += sum(e - s for s, e in busy)
        for (_, g0), (g1, _) in zip(busy, busy[1:]):
            idle_ns += g1 - g0
            k = bisect.bisect_right(launch_starts, g1) - 1
            if k < 0:
                by_stage[NO_LAUNCH] = by_stage.get(NO_LAUNCH, 0) + g1 - g0
                continue
            segs, starts = threads[launches[k][1]]
            covered = 0
            if starts and g0 < starts[0]:
                # Whatever this thread was in when the trace began.
                early = min(g1, starts[0]) - g0
                by_stage[TRACE_START] = by_stage.get(TRACE_START, 0) \
                    + early
                covered += early
            i = max(0, bisect.bisect_right(starts, g0) - 1)
            while i < len(segs) and segs[i][0] < g1:
                s, e, name = segs[i]
                lap = min(e, g1) - max(s, g0)
                if lap > 0:
                    stage = name[len(STAGE_PREFIX):]
                    by_stage[stage] = by_stage.get(stage, 0) + lap
                    covered += lap
                i += 1
            if g1 - g0 > covered:
                by_stage[NO_REQUEST] = by_stage.get(NO_REQUEST, 0) \
                    + (g1 - g0 - covered)
    n = max(1, n_dev)
    return {"devices": n_dev, "idle_s": idle_ns / n / 1e9,
            "busy_s": busy_ns / n / 1e9,
            "host_threads": len(threads), "launch_events": len(launches),
            "by_stage": {k: v / n / 1e9 for k, v in sorted(
                by_stage.items(), key=lambda kv: -kv[1])}}


def render(result: dict) -> str:
    idle = result["idle_s"]
    lines = [f"devices {result['devices']}  busy {result['busy_s']:.4f} s"
             f"  idle between ops {idle:.4f} s  host threads with "
             f"pilosa: events {result['host_threads']}  launch events "
             f"{result['launch_events']}",
             f"{'stage':<24}{'idle s':>12}{'share':>9}"]
    for stage, secs in result["by_stage"].items():
        share = 100.0 * secs / idle if idle > 0 else 0.0
        lines.append(f"{stage:<24}{secs:>12.6f}{share:>8.1f}%")
    return "\n".join(lines)


def main(argv: list = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help=".xplane.pb, or the JSON form")
    ap.add_argument("--json", action="store_true",
                    help="print the result as one JSON object")
    args = ap.parse_args(argv)
    result = attribute(load(args.trace))
    if not result["devices"]:
        print("trace_gaps: no device plane with XLA Ops in", args.trace,
              file=sys.stderr)
        return 1
    print(json.dumps(result) if args.json else render(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
