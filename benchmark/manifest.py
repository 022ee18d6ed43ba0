"""Loads BENCHMARK.json and the data files it names; refuses what it does not know.

One file per configuration (``configs/<name>.json``), per traffic mix
(``traffic/<name>.json``) and per per-layer metric (``metrics/<name>.json``),
each found by the name in BENCHMARK.json. Nothing here, or anywhere in the
harness, branches on the name of a cell, a configuration or a metric.

A configuration taken from a published ``config.json`` keeps its source's
keys under their published names at the TOP LEVEL of its file, named in
``source_keys``: that is where the driver's check reads them
(``check_against_source`` is that check, as far as three refusals show it).
"""

import importlib
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

MANIFEST_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"}
CONFIG_ENTRY_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
END_TO_END_KEYS = {"name", "unit", "better", "bound", "source", "workloads"}
PER_LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

CONFIG_FILE_KEYS = {"name", "builder", "source", "source_keys",
                    "source_values", "reduced", "assumed", "deployment",
                    "why", "model", "settings", "flops", "reference",
                    "rehearsal"}
PUBLISHED = "published."
# what section 4 of the model-configs guide lets a depth cut or a chip's
# share change: how many layers, routed experts, heads and rows of the
# vocabulary are held here, and the layer pattern that shrinks with the
# depth. Whatever else differs from the source is a width.
COUNTS = re.compile(
    r"(layers?|heads?|vocab(_size)?|routed_experts|(num|n)(_local)?_experts"
    r"|pattern|layer_types|block_types?)$")
TRAFFIC_KINDS = {"train_steps", "open_loop", "closed_loop"}
TRAFFIC_FILE_KEYS = {
    "train_steps": {"kind", "why", "batch", "seq_len", "image_shape",
                    "warmup_steps", "trace_seconds", "mesh", "rehearsal"},
    "open_loop": {"kind", "why", "rate_rps", "arrivals", "block_requests",
                  "prompt_len", "answer_len", "max_total_len", "sharing",
                  "schedule_seed", "preroll_s", "abandon_after_s", "check_requests",
                  "check_tokens", "check_tolerance", "check_min_equal",
                  "trace_seconds", "rehearsal"},
    "closed_loop": {"kind", "why", "clients", "prompt_len", "answer_len",
                    "max_total_len", "sharing", "schedule_seed",
                    "preroll_s", "abandon_after_s", "check_requests", "check_tokens",
                    "check_tolerance", "check_min_equal", "trace_seconds",
                    "rehearsal"},
}
# which cells report a metric is BENCHMARK.json's entry's to say, and no
# one else's: a file that carried the list could not take a new cell's
# name without an edit, so each cell brought copies of the files
METRIC_FILE_KEYS = {"name", "layer", "unit", "better", "source", "moves",
                    "reader", "args", "what"}


class ManifestError(ValueError):
    """A benchmark data file holds something the harness does not know."""


def _load(path):
    with open(path) as f:
        return json.load(f)


def _only(keys, allowed, what):
    unknown = sorted(set(keys) - set(allowed))
    if unknown:
        raise ManifestError(f"{what}: unknown keys {unknown}")


def _name(value, what):
    if not isinstance(value, str) or not NAME.match(value):
        raise ManifestError(f"{what}: {value!r} is not a name")
    return value


def load_manifest(root=ROOT):
    """BENCHMARK.json, checked against the keys and characters the contract
    allows (the driver checks the same before any run)."""
    m = _load(os.path.join(root, "BENCHMARK.json"))
    _only(m, MANIFEST_KEYS, "BENCHMARK.json")
    missing = MANIFEST_KEYS - set(m)
    if missing:
        raise ManifestError(f"BENCHMARK.json: missing {sorted(missing)}")
    seen = set()
    for c in m["configs"]:
        _only(c, CONFIG_ENTRY_KEYS, f"config {c.get('name')}")
        _name(c["name"], "config name")
        for key in c["reduced"]:
            _name(key, f"reduced key of {c['name']}")
    for w in m["workloads"]:
        _only(w, WORKLOAD_KEYS, f"workload {w.get('name')}")
        _name(w["name"], "workload name")
        _name(w["config"], "workload config")
        _name(w["traffic"], "workload traffic")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"{w['name']}: chips must be 1 or 4")
        if (w["config"], w["traffic"]) in seen:
            raise ManifestError(f"{w['name']}: pair appears twice")
        seen.add((w["config"], w["traffic"]))
    for group, keys in (("end_to_end", END_TO_END_KEYS),
                        ("per_layer", PER_LAYER_KEYS)):
        for metric in m[group]:
            _only(metric, keys, f"{group} {metric.get('name')}")
            _name(metric["name"], f"{group} name")
            if not UNIT.match(metric["unit"]):
                raise ManifestError(f"{metric['name']}: unit {metric['unit']!r}")
            if metric["better"] not in ("lower", "higher"):
                raise ManifestError(f"{metric['name']}: better")
            if metric["source"] not in SOURCES:
                raise ManifestError(f"{metric['name']}: source")
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    if len(names) != len(set(names)):
        raise ManifestError("two metrics share a name")
    return m


def workload(manifest, name):
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(
        f"no workload {name!r} in BENCHMARK.json "
        f"(known: {[w['name'] for w in manifest['workloads']]})")


def metrics_of(manifest, group, workload_name):
    """The metrics of ``group`` that the cell reports: those with no
    ``workloads`` key, and those that list it."""
    return [x for x in manifest[group]
            if "workloads" not in x or workload_name in x["workloads"]]


def load_config(manifest, name):
    (entry,) = [c for c in manifest["configs"] if c["name"] == name]
    cfg = load_config_file(os.path.join(ROOT, entry["file"]),
                           entry["reduced"])
    if cfg["name"] != name:
        raise ManifestError(f"{entry['file']}: name is {cfg['name']!r}")
    return cfg


def load_config_file(path, entry_reduced=None):
    """A configuration's file. Beside the harness's own keys it may hold
    its source's published keys at the top level, each named in
    ``source_keys``, with the value that is run here; where it states its
    source's values (``source_values``) it is held to them as the driver
    holds it to the catalog's row. ``entry_reduced`` is ``reduced`` of the
    file's entry in BENCHMARK.json, for a file that has one."""
    cfg = _load(path)
    published = cfg.get("source_keys", [])
    for key in published:
        _name(key, f"{path}: source key")
    clash = sorted(CONFIG_FILE_KEYS & set(published))
    if clash:
        raise ManifestError(f"{path}: source_keys names the harness's own "
                            f"{clash}")
    _only(cfg, CONFIG_FILE_KEYS | set(published), path)
    missing = [k for k in published if k not in cfg]
    if missing:
        raise ManifestError(f"{path}: source_keys names {missing}, which "
                            "the file does not give at its top level")
    _only(cfg.get("rehearsal", {}), published, f"{path}: rehearsal")
    try:
        model = model_sizes(cfg, False)
    except KeyError as e:
        raise ManifestError(f"{path}: model names published.{e.args[0]}, "
                            "which source_keys does not") from None
    for key in published:
        if not _same(model.get(key, cfg[key]), cfg[key]):
            raise ManifestError(
                f"{path}: {key} is {cfg[key]!r} at the top level and "
                f"{model[key]!r} under model")
    if published and "source_values" in cfg:
        complaint = check_against_source(
            cfg, cfg["reduced"] if entry_reduced is None else entry_reduced,
            cfg["source_values"])
        if complaint:
            raise ManifestError(f"{path}: {complaint}")
    if "flops" in cfg:
        count_function(cfg["flops"]["function"])
    return cfg


def _is_number(value):
    return isinstance(value, (int, float)) and not isinstance(value, bool)


def _same(ours, theirs):
    return ours == theirs and isinstance(ours, bool) == isinstance(
        theirs, bool)


def _widths_changed(key, ours, theirs):
    """The keys, by dotted path, that part ``ours`` from ``theirs`` and are
    no count: a group is compared leaf by leaf, each leaf under its own
    name."""
    if isinstance(ours, dict) and isinstance(theirs, dict):
        return [f"{key}.{path}" for k in sorted(set(ours) | set(theirs))
                for path in _widths_changed(k, ours.get(k), theirs.get(k))]
    return [] if _same(ours, theirs) or COUNTS.search(key) else [key]


def check_against_source(cfg, entry_reduced, row_config):
    """The driver's rule for a configuration of the catalog, as three
    refusals state it; returns the first complaint, or None. ``cfg`` is the
    configuration's file, ``entry_reduced`` the ``reduced`` of its entry in
    BENCHMARK.json, ``row_config`` the ``config`` of its source's row.

    Every number and every nested group of the row stands at the TOP LEVEL
    of the file under the same key (a published null is not asked for).
    A key whose value differs is listed in ``reduced``, in the file and in
    the entry alike, and is a count (``COUNTS``); anything else that
    differs is a width, and is refused whether or not ``reduced`` lists it,
    inside a group too. ``source`` has at most 200 characters."""
    if len(cfg["source"]) > 200:
        return (f"source has {len(cfg['source'])} characters, and may have "
                "200")
    if sorted(cfg["reduced"]) != sorted(entry_reduced):
        return (f"reduced is {sorted(cfg['reduced'])} in the file and "
                f"{sorted(entry_reduced)} in BENCHMARK.json's entry")
    for key, theirs in row_config.items():
        demanded = _is_number(theirs) or isinstance(theirs, dict)
        ours = cfg.get(key)
        if ours is None and not demanded:
            continue
        if ours is None:
            where = ("only under model" if key in cfg.get("model", {})
                     else "as null" if key in cfg else "nowhere")
            return (f"the file gives {key} {where} and its source gives "
                    f"{theirs!r}: every number and every nested group of "
                    "the source stands at the top level of the file, under "
                    "the same key and named in source_keys")
        if _same(ours, theirs):
            continue
        widths = _widths_changed(key, ours, theirs)
        if widths:
            return (f"{widths[0]} is {ours!r} and its source gives "
                    f"{theirs!r}: only a count may differ (layers, routed "
                    "experts, heads, rows of the vocabulary, the layer "
                    "pattern); a width may not change, whether or not "
                    "reduced lists it or the group that holds it")
        if key not in cfg["reduced"]:
            return (f"{key} is {ours!r} and its source gives {theirs!r}, "
                    "and reduced does not list it")
    return None


def count_function(name):
    """The operations-and-bytes function a metric's kernel or a
    configuration's ``flops`` names: ``<function>`` of flops.py, or
    ``<module>.<function>`` of ``counts/<module>.py``, a file of its
    own."""
    module, _, function = name.rpartition(".")
    for part in (module or "flops", function):
        _name(part, f"count function {name!r}")
    try:
        found = importlib.import_module(
            f"benchmark.counts.{module}" if module else "benchmark.flops")
    except ModuleNotFoundError as e:
        raise ManifestError(f"count function {name!r}: {e}") from None
    if not callable(getattr(found, function, None)):
        raise ManifestError(f"count function {name!r}: {found.__name__} "
                            f"has no function {function!r}")
    return getattr(found, function)


def load_traffic(name):
    """A traffic mix is found by name alone, so a file that no cell uses
    yet (a later PR's cell) loads and rehearses like any other."""
    _name(name, "traffic name")
    path = os.path.join(HERE, "traffic", name + ".json")
    t = _load(path)
    if t.get("kind") not in TRAFFIC_KINDS:
        raise ManifestError(f"{path}: kind {t.get('kind')!r} is not one of "
                            f"{sorted(TRAFFIC_KINDS)}")
    _only(t, TRAFFIC_FILE_KEYS[t["kind"]], path)
    return t


def load_metric(name):
    _name(name, "metric name")
    path = os.path.join(HERE, "metrics", name + ".json")
    m = load_metric_file(path)
    if m["name"] != name:
        raise ManifestError(f"{path}: name is {m['name']!r}")
    return m


def load_metric_file(path):
    m = _load(path)
    _only(m, METRIC_FILE_KEYS, path)
    args = m.get("args", {})
    for named in [args] + args.get("kernels", []):
        if "function" in named:
            count_function(named["function"])
    return m


def sizes(section, rehearse):
    """A data file's sizes, with its ``rehearsal`` overrides laid over them
    in the CPU rehearsal and left out otherwise."""
    out = {k: v for k, v in section.items() if k != "rehearsal"}
    if rehearse:
        out.update(section.get("rehearsal", {}))
    return out


def published(cfg, rehearse):
    """The source's keys as they are run here, one group: the published
    value, the cut one for a key in ``reduced``, the file's top-level
    ``rehearsal`` laid over them in the CPU rehearsal. Empty for a
    configuration that names no ``source_keys``."""
    return sizes({k: cfg[k] for k in cfg.get("source_keys", ())}
                 | {"rehearsal": cfg.get("rehearsal", {})}, rehearse)


def model_sizes(cfg, rehearse):
    """``model`` as the builder gets it: a size given as
    ``"published.<key>"`` is that key of ``published``."""
    group = published(cfg, rehearse)
    return {k: group[v[len(PUBLISHED):]]
            if isinstance(v, str) and v.startswith(PUBLISHED) else v
            for k, v in sizes(cfg["model"], rehearse).items()}


def run_sizes(cfg, traffic, chips, rehearse):
    """What a metric file's ``call`` and ``denominator_times`` may name by
    dotted path."""
    return {"model": model_sizes(cfg, rehearse),
            "published": published(cfg, rehearse),
            "traffic": sizes(traffic, rehearse), "chips": chips}
