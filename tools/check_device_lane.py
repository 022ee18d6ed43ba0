#!/usr/bin/env python
"""The engine's device lane held against the profiler's own trace, on the chip.

    python tools/check_device_lane.py --workload <serving cell> --seed <n> \\
        [--seconds 51] [--lanes 0] [--out chiprun_out/device_lane]

Runs the cell's traced benchmark run (`benchmark/run.py --trace 1`, in this
process; the one thing changed is that the harness's `enable_tracing()` asks
for the tracer's lanes, which the benchmark itself never does; `--lanes 0`
leaves that as it is, for the other side of a cost pair) and, where the
harness reads the profiler's trace, keeps what it throws away: every event of the device's "XLA Modules"
line (name, start, duration) beside the lane's events (`Tracer.lanes()`,
brought onto the trace's clock by the harness's own anchor). The harness's
result line is printed as it is; after it, LAST on stdout, one JSON object:

* ``modules``: per module name, count, mean / median / p10 / p90 ms and the
  summed seconds inside the traced window (since ISSUE 53 a module is named
  for its program: ``jit_decode_<model>_step``);
* ``lane``: per ``device::<kind>``, the count of stamped launches, of queued
  ones, of those no unstamped program rode with, and the mean ms of the last
  (the lane's reading of one launch's device time, to hold against the
  modules of that name);
* ``wake_up_us``: the watcher's stamp less the END of the module it waited
  for (the same kind's module that ends nearest), p10 / p50 / p90 / max over
  the stamped launches: the watcher's wake-up error plus the anchor's;
* ``queued_minus_module_us``: for queued launches with nothing riding, the
  lane's duration less that module's own, the same quantiles;
* ``lane_event_seconds``: the seconds of the window's lane events that
  were queued and of those that were not (``unqueued``), and the window's
  rest, ``idle``: the gaps the events leave, to hold against the harness's
  ``device_idle.serve`` in the line above;
* ``device_seconds``: the four ``serving_device_*_seconds_total`` as the
  engine's registry holds them when the run ends (the whole time lanes
  were on, not the window alone).

The whole of it (every module and lane event) goes to
``<out>/<workload>.<seed>.json``. A builder's instrument (PERF.md §6, PR 53):
it measures on a TPU or not at all, as the benchmark does.
"""

import argparse
import bisect
import json
import os
import re
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _quantiles(values, scale=1.0):
    if not values:
        return None
    values = sorted(values)

    def at(q):
        return scale * values[min(int(q * len(values)), len(values) - 1)]

    return {"n": len(values), "p10": at(0.1), "p50": at(0.5), "p90": at(0.9),
            "max": scale * values[-1], "mean": scale * statistics.fmean(values)}


def _kind_of(module):
    """``jit_decode_<model>_<kind>(<fingerprint>)`` -> ``<kind>``."""
    m = re.match(r"^jit_decode_.*_(step|chunk|prefill|inject)(\(|$)", module)
    return m.group(1) if m else None


def compare(trace, window, lanes, shift):
    """The summary described above, from the harness's trace dict, the
    traced window on its clock, the tracer's lane events and the seconds
    that bring the tracer's clock onto the trace's."""
    device = next(iter(trace["devices"].values()))
    lo, hi = window
    modules = [(name, start, dur) for name, start, dur in device["modules"]
               if lo <= start < hi]
    by_name = {}
    for name, _start, dur in modules:
        by_name.setdefault(re.sub(r"\(.*\)$", "", name), []).append(dur)
    out = {"modules": {
        name: dict(_quantiles(durs, 1e3), seconds=sum(durs))
        for name, durs in sorted(by_name.items())}}

    ends = {}       # kind -> sorted [(end, dur)] of its modules
    for name, start, dur in device["modules"]:
        kind = _kind_of(name)
        if kind:
            ends.setdefault(kind, []).append((start + dur, dur))
    for rows in ends.values():
        rows.sort()

    wake, excess, lane = [], [], {}
    parts = {"queued": 0.0, "unqueued": 0.0}
    for ev in lanes:
        start = ev["start_ns"] * 1e-9 + shift
        end = start + ev["dur_ns"] * 1e-9
        if not lo <= end < hi:
            continue
        kind = ev["name"].split("::", 1)[1]
        row = lane.setdefault(kind, {"stamped": 0, "queued": 0, "clean": 0,
                                     "clean_seconds": 0.0})
        row["stamped"] += 1
        queued = ev["args"].get("queued")
        clean = queued and not any(
            n in ("inject", "step", "chunk", "prefill")
            for n in ev["args"]["with"])
        if queued is not None:
            parts["queued" if queued else "unqueued"] += ev["dur_ns"] * 1e-9
        row["queued"] += bool(queued)
        row["clean"] += bool(clean)
        if clean:
            row["clean_seconds"] += ev["dur_ns"] * 1e-9
        rows = ends.get(kind)
        if not rows:
            continue
        i = bisect.bisect_left(rows, (end, 0.0))
        near = min(rows[max(i - 1, 0):i + 1], key=lambda r: abs(r[0] - end))
        wake.append(end - near[0])
        if clean:
            excess.append(ev["dur_ns"] * 1e-9 - near[1])
    for row in lane.values():
        row["clean_mean_ms"] = (1e3 * row["clean_seconds"] / row["clean"]
                                if row["clean"] else None)
    out["lane"] = lane
    out["wake_up_us"] = _quantiles(wake, 1e6)
    out["queued_minus_module_us"] = _quantiles(excess, 1e6)
    parts["idle"] = (hi - lo) - parts["queued"] - parts["unqueued"]
    out["lane_event_seconds"] = parts
    return out, modules


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--lanes", type=int, default=1, choices=(0, 1))
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "device_lane"))
    args = ap.parse_args()

    from benchmark import profile, run
    from benchmark import trace as tr
    from paddle_tpu import observability as obs

    if args.lanes:
        enable = obs.enable_tracing
        obs.enable_tracing = lambda **kw: enable(lanes=True, **kw)

    kept = {}
    read = profile.Traced.read

    def reading(self):
        reduced = read(self)
        if reduced.get("trace"):
            (anchor,) = [s for s in self.tracer_spans
                         if s["name"] == tr.ANCHOR]
            shift = (tr.host_event(reduced["trace"], tr.ANCHOR)[0]
                     - anchor["start_ns"] * 1e-9)
            lanes = obs.get_tracer().lanes()
            summary, modules = compare(reduced["trace"],
                                       reduced["trace_window"], lanes, shift)
            kept.update(summary=summary, modules=modules, lanes=lanes,
                        shift=shift, window=reduced["trace_window"])
        return reduced

    profile.Traced.read = reading
    run.main(["--workload", args.workload, "--seed", str(args.seed),
              "--seconds", str(args.seconds), "--trace", "1"])
    if not kept:
        sys.exit("no device trace was read: this runs on the chip only")
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.workload}.{args.seed}.json")
    with open(path, "w") as f:
        json.dump(kept, f)
    from paddle_tpu.observability.metrics import registry

    totals = {name: sum(family.values())
              for name, family in registry().snapshot().items()
              if name.startswith("serving_device_")}
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "lanes": args.lanes, "device_seconds": totals,
                      **kept["summary"]}), flush=True)


if __name__ == "__main__":
    main()
