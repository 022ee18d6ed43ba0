"""The reduction from a profiler trace and host spans to numbers.

A trace here is a plain dict, so the same code reads the profiler's
``.xplane.pb`` (``load_xplane``) and the small hand-built trace the test file
keeps beside it:

    {"devices": {"<id>": {"ops": [[name, start_s, dur_s], ...],
                          "async_ops": [...], "modules": [...]}},
     "host": [[name, start_s, dur_s], ...]}        # TraceAnnotations

``ops`` are the events of the device's "XLA Ops" line (one at a time on the
core), ``async_ops`` those of "Async XLA Ops" (copies and collectives that
overlap them), ``modules`` the whole executables. All times are seconds on
the trace's clock. Host spans of the program (the observability tracer's, on
``perf_counter_ns``) are brought onto that clock by an anchor: the benchmark
emits a ``TraceAnnotation`` and a tracer span of one name at one moment.
"""

import bisect
import re

BENCH_PREFIX = "bench::"
ANCHOR = "bench::anchor"
WINDOW = "bench::window"


def load_xplane(path):
    """The profiler's trace as the dict above. Device planes are
    ``/device:TPU:<n>``; annotations come from every line of ``/host:CPU``."""
    from jax.profiler import ProfileData

    lines = {"XLA Ops": "ops", "Async XLA Ops": "async_ops",
             "XLA Modules": "modules"}
    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            dev = out["devices"].setdefault(
                m.group(1), {"ops": [], "async_ops": [], "modules": []})
            for line in plane.lines:
                key = lines.get(line.name)
                if key:
                    dev[key].extend(
                        [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                    for e in line.events if e.name.startswith(BENCH_PREFIX))
    return out


def host_event(trace, name):
    """(start_s, end_s) of the first host annotation called ``name``."""
    for n, start, dur in trace["host"]:
        if n == name:
            return start, start + dur
    raise KeyError(f"no host annotation {name!r} in the trace")


def spans_on_trace_clock(spans, trace):
    """The tracer's spans as [name, start_s, end_s] on the trace's clock:
    shifted so that the tracer's anchor span starts where the trace's
    anchor annotation does."""
    (anchor,) = [s for s in spans if s["name"] == ANCHOR]
    shift = host_event(trace, ANCHOR)[0] - anchor["start_ns"] * 1e-9
    return [[s["name"], s["start_ns"] * 1e-9 + shift,
             (s["start_ns"] + s["dur_ns"]) * 1e-9 + shift]
            for s in spans if s["name"] != ANCHOR]


def union(intervals):
    """Sorted, disjoint [start, end] cover of ``intervals``."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def total(intervals):
    return sum(end - start for start, end in intervals)


def clip(events, window):
    """[start, end] of each [name, start, dur] event, cut to ``window``."""
    lo, hi = window
    out = []
    for _name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append([a, b])
    return out


def subtract(cover, holes):
    """The part of the disjoint sorted ``cover`` outside the disjoint
    sorted ``holes``."""
    out = []
    j = 0
    for start, end in cover:
        cur = start
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < end:
            if holes[k][0] > cur:
                out.append([cur, holes[k][0]])
            cur = max(cur, holes[k][1])
            k += 1
        if cur < end:
            out.append([cur, end])
    return out


def busy_and_gaps(device, window):
    """(busy seconds, idle gaps) of one device in ``window``: busy is the
    union of the intervals in which an operation ran, a gap is what lies
    between."""
    busy = union(clip(device["ops"], window))
    return total(busy), subtract([list(window)], busy)


def matching_seconds(device, pattern, window, lines=("ops",)):
    """Device seconds of the events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(
        end - start
        for line in lines
        for (start, end) in clip(
            [e for e in device[line] if rx.search(e[0])], window))


def exposed_seconds(device, pattern, window):
    """Seconds in which an event matching ``pattern`` (a collective, on
    either line) runs on the device and no other operation does."""
    rx = re.compile(pattern)
    hit = union(clip([e for line in ("ops", "async_ops")
                      for e in device[line] if rx.search(e[0])], window))
    other = union(clip([e for e in device["ops"] if not rx.search(e[0])],
                       window))
    return total(subtract(hit, other))


def span_segments(spans):
    """A disjoint, sorted labelling of time by what the host was doing:
    [start, end, label]. Inside a span of the program the label is the
    innermost one open (the latest started); between two of them it is
    ``after:<the one that ended last>``. The benchmark's own spans label
    only the time no span of the program has touched yet."""
    program = [s for s in spans if not s[0].startswith(BENCH_PREFIX)]
    own = [s for s in spans if s[0].startswith(BENCH_PREFIX)]
    edges = sorted({t for _n, a, b in program + own for t in (a, b)})
    starts = sorted(program, key=lambda s: s[1])
    out = []
    open_now = []
    nxt = 0
    last_ended = None
    for lo, hi in zip(edges, edges[1:]):
        while nxt < len(starts) and starts[nxt][1] <= lo:
            open_now.append(starts[nxt])
            nxt += 1
        ended = [s for s in open_now if s[2] <= lo]
        if ended:
            last_ended = max(ended, key=lambda s: s[2])[0]
            open_now = [s for s in open_now if s[2] > lo]
        if open_now:
            label = max(open_now, key=lambda s: s[1])[0]
        elif last_ended is not None:
            label = "after:" + last_ended
        else:
            inside = [s for s in own if s[1] <= lo and s[2] >= hi]
            label = (max(inside, key=lambda s: s[1])[0] if inside
                     else "no_span")
        if out and out[-1][2] == label and out[-1][1] == lo:
            out[-1][1] = hi
        else:
            out.append([lo, hi, label])
    return out


def attribute_gaps(gaps, segments):
    """Idle seconds by label: each gap is cut along ``segments`` and every
    piece goes to the label of the host's doing at that time."""
    starts = [s[0] for s in segments]
    by_label = {}
    for a, b in gaps:
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        covered = 0.0
        while i < len(segments) and segments[i][0] < b:
            lo, hi, label = segments[i]
            piece = min(hi, b) - max(lo, a)
            if piece > 0:
                by_label[label] = by_label.get(label, 0.0) + piece
                covered += piece
            i += 1
        if b - a - covered > 1e-12:
            by_label["no_span"] = by_label.get("no_span", 0.0) + (
                b - a - covered)
    return by_label


def module_seconds_by_span(device, spans, window):
    """Device seconds of whole executables, by the program's span that
    launched them: a module belongs to the span of the program that started
    last at or before the module did (the host launches in order and the
    device runs in order). Returns {span name: seconds}."""
    program = sorted((s for s in spans if not s[0].startswith(BENCH_PREFIX)),
                     key=lambda s: s[1])
    starts = [s[1] for s in program]
    out = {}
    lo, hi = window
    for _name, start, dur in device["modules"]:
        if not lo <= start < hi:
            continue
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0:
            name = program[i][0]
            out[name] = out.get(name, 0.0) + dur
    return out


_HLO = re.compile(r"^%?([\w.\-]+) = (\(?[a-z0-9]+\[[0-9,]*\])?.*? ([a-z\-]+)\(")


def op_label(name):
    """A short label for a device operation: the profiler names one by its
    whole HLO instruction, ``%fusion.3 = f32[8,128]{...} fusion(...)``."""
    m = _HLO.match(name)
    if not m:
        return name[:96]
    shape = (m.group(2) or "").lstrip("(")
    return f"{m.group(1)} {shape} {m.group(3)}".replace("  ", " ")


def top_ops(device, window, n=10):
    """The ``n`` device operations with most time in ``window``."""
    by_name = {}
    lo, hi = window
    for name, start, dur in device["ops"]:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            by_name[name] = by_name.get(name, 0.0) + (b - a)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[op_label(name), seconds] for name, seconds in ranked]
