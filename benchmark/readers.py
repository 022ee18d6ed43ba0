"""The fixed set of readers that per-layer metric files name.

A metric file (``metrics/<name>.json``) gives a ``reader`` and its ``args``:
a counter's family, a histogram, a span name, a pattern of device event
names with an operations-and-bytes function. A metric over a NEW span,
counter or kernel is a new file and no code. Every reader takes
``(args, run)`` and returns a number, or None when there is nothing to read
(no trace in this run, no such span): the harness then leaves the metric
out of the line.

``run`` is the dict a driver fills: ``facts`` (numbers the driver measured
itself), ``registry`` (before/after snapshots of the program's metrics
registry around the window), ``stretch_registry`` (the same at the edges of
the traced stretch, where the driver takes them), ``spans`` and ``trace``
with ``trace_window`` (traced runs on the chip only), ``config``,
``sizes``, ``chips`` and ``peaks``.
"""

import re
import statistics

from benchmark import trace as tr
from benchmark.manifest import count_function


def _lookup(tree, dotted):
    for key in dotted.split("."):
        tree = tree[key]
    return tree


def _value(expr, run):
    """A size for a kernel call, from the metric file: a number, a dotted
    path into the run's sizes (``model.hidden_size``,
    ``published.num_hidden_layers``, ``traffic.batch``, ``chips``),
    {"counter": family} (how far that counter of the program's registry
    moved over the traced stretch: a size that follows the traffic), or
    {"mul": [...]} / {"div": [a, b]} of such. Two whole numbers divide to a
    whole number (a batch over the chips), a counter's movement exactly.
    None where a counter named is not there to read."""
    if isinstance(expr, (int, float)):
        return expr
    if isinstance(expr, str):
        return _lookup(run["sizes"], expr)
    if "counter" in expr:
        if run.get("stretch_registry") is None:
            return None
        return _family_delta(run, expr["counter"], key="stretch_registry")
    parts = [_value(part, run) for part in expr.get("mul") or expr["div"]]
    if None in parts:
        return None
    if "mul" in expr:
        out = 1
        for part in parts:
            out *= part
        return out
    a, b = parts
    return a // b if isinstance(a, int) and isinstance(b, int) else a / b


def _call_sizes(call, run):
    """The sizes a count function is called with, or None where one of
    them is not there to read."""
    sizes = {name: _value(expr, run) for name, expr in call.items()}
    return None if None in sizes.values() else sizes


def _family_delta(run, family, field=None, key="registry"):
    """How far all series of a family moved over the window (or, with
    ``key``, between another pair of snapshots); for a histogram ``field``
    is ``sum`` or ``count``."""
    before, after = run[key]
    if family not in after:
        return None
    moved = 0.0
    for labels, value in after[family].items():
        start = before.get(family, {}).get(labels, 0)
        if field is not None:
            value = value[field]
            start = start[field] if start else 0
        moved += value - start
    return moved


def fact(args, run):
    """A number the driver measured itself, by key."""
    value = run["facts"].get(args["key"])
    return None if value is None else value * args.get("scale", 1.0)


def counter_delta(args, run):
    return _family_delta(run, args["family"])


def counter_ratio(args, run):
    """scale * d(numerator) / (d(denominator) * a size of the
    configuration), e.g. active slot-steps over steps * slots."""
    num = _family_delta(run, args["numerator"])
    den = _family_delta(run, args["denominator"])
    if not num and not den or not den:
        return None
    if "denominator_times" in args:
        den *= _lookup(run["sizes"], args["denominator_times"])
    return args.get("scale", 1.0) * num / den


def histogram_mean(args, run):
    """scale * d(sum) / d(count) of a histogram over the window: the mean
    of what it observed there (its buckets are too coarse for a median)."""
    total = _family_delta(run, args["family"], "sum")
    count = _family_delta(run, args["family"], "count")
    if not count:
        return None
    return args.get("scale", 1.0) * total / count


def histogram_share(args, run):
    """scale * the summed seconds of some histograms over the window's
    length."""
    sums = [_family_delta(run, f, "sum") for f in args["families"]]
    if all(s is None for s in sums):
        return None
    return (args.get("scale", 1.0) * sum(s or 0.0 for s in sums)
            / run["facts"]["window_s"])


def span_stat(args, run):
    """A statistic of the program's spans whose name matches ``pattern``,
    inside the traced window: ``sum_per_step`` (seconds per step of the
    driver), ``mean``, ``median``, ``count``."""
    if run.get("spans") is None:
        return None
    rx = re.compile(args["pattern"])
    lo, hi = run["trace_window"]
    durs = [end - start for name, start, end in run["spans"]
            if rx.search(name) and lo <= start < hi]
    if not durs:
        return None
    stat = args["stat"]
    if stat == "sum_per_step":
        value = sum(durs) / run["facts"]["traced_steps"]
    elif stat == "count":
        value = len(durs)
    else:
        value = getattr(statistics, stat)(durs)
    return args.get("scale", 1.0) * value


def _devices(run):
    return list(run["trace"]["devices"].values()) if run.get("trace") else []


def device_idle(args, run):
    """100 * (1 - busy / window), averaged over the chips used."""
    devs = _devices(run)
    if not devs:
        return None
    lo, hi = run["trace_window"]
    busy = [tr.busy_and_gaps(d, (lo, hi))[0] for d in devs]
    return 100.0 * (1.0 - statistics.fmean(busy) / (hi - lo))


def device_share(args, run):
    """100 * device seconds of the events matching ``pattern`` over busy
    seconds, on the first device."""
    devs = _devices(run)
    if not devs:
        return None
    window = run["trace_window"]
    busy = tr.busy_and_gaps(devs[0], window)[0]
    hit = tr.matching_seconds(devs[0], args["pattern"], window)
    return 100.0 * hit / busy if hit else None


def device_roofline(args, run):
    """100 * the least time the chip could take over the time it took, for
    the kernels in ``kernels``: each gives a ``pattern`` of event names and
    a count function (``manifest.count_function``) with the sizes of one
    call (``_value`` expressions). The least time of a call is
    max(operations / peak FLOP/s, bytes / peak bytes/s). A kernel whose
    work follows the traffic says ``"summed": true``: its ``call`` then
    gives the sizes of ALL its calls in the traced stretch together (a
    counter's movement), and the least time is taken once, not per
    event."""
    devs = _devices(run)
    if not devs:
        return None
    window = run["trace_window"]
    peaks = run["peaks"]
    least = took = 0.0
    for k in args["kernels"]:
        rx = re.compile(k["pattern"])
        events = tr.clip([e for e in devs[0]["ops"] if rx.search(e[0])],
                         window)
        if not events:
            continue
        call = _call_sizes(k["call"], run)
        if call is None:
            continue
        ops, moved = count_function(k["function"])(**call)
        least += (1 if k.get("summed") else len(events)) * max(
            ops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"])
        took += tr.total(events)
    return 100.0 * least / took if took else None


def device_exposed(args, run):
    """scale * seconds per step in which an event matching ``pattern`` (a
    collective) runs on the first device and nothing else does."""
    devs = _devices(run)
    if not devs:
        return None
    seconds = tr.exposed_seconds(devs[0], args["pattern"],
                                 run["trace_window"])
    return args.get("scale", 1.0) * seconds / run["facts"]["traced_steps"]


def device_seconds_per_span(args, run):
    """scale * device seconds of the executables launched under the
    program's span ``span``, per such span in the traced window."""
    devs = _devices(run)
    if not devs or run.get("spans") is None:
        return None
    lo, hi = run["trace_window"]
    count = sum(1 for name, start, _e in run["spans"]
                if name == args["span"] and lo <= start < hi)
    seconds = tr.module_seconds_by_span(
        devs[0], run["spans"], (lo, hi)).get(args["span"])
    if not count or seconds is None:
        return None
    return args.get("scale", 1.0) * seconds / count


def span_mfu(args, run):
    """100 * the operations the traced stretch's work required (a count
    function over ``call`` sizes, counters' movements among them) / (device
    seconds of the executables launched under the program's span ``span``
    in that stretch * peak FLOP/s): a whole step's share of the chip's
    peak where the work of a step follows the traffic."""
    devs = _devices(run)
    if not devs or run.get("spans") is None:
        return None
    seconds = tr.module_seconds_by_span(
        devs[0], run["spans"], run["trace_window"]).get(args["span"])
    call = _call_sizes(args["call"], run)
    if not seconds or call is None:
        return None
    ops, _moved = count_function(args["function"])(**call)
    return 100.0 * ops / (seconds * run["peaks"]["bf16_flops"])


def mfu(args, run):
    """100 * required operations per step (the configuration's count
    function) * steps per second / (chips * peak FLOP/s)."""
    rate = run["facts"].get("steps_per_s")
    if rate is None or run.get("peaks") is None:
        return None
    cfg = run["config"]
    ops = count_function(cfg["flops"]["function"])(
        run["sizes"]["model"], cfg["settings"], run["sizes"]["traffic"])
    return 100.0 * ops * rate / (run["chips"] * run["peaks"]["bf16_flops"])


READERS = {f.__name__: f for f in (
    fact, counter_delta, counter_ratio, histogram_mean, histogram_share,
    span_stat, device_idle, device_share, device_roofline, device_exposed,
    device_seconds_per_span, span_mfu, mfu)}
