"""Loads BENCHMARK.json and the data files it names; refuses what it does not know.

One file per configuration (``configs/<name>.json``), per traffic mix
(``traffic/<name>.json``) and per per-layer metric (``metrics/<name>.json``),
each found by the name in BENCHMARK.json. Nothing here, or anywhere in the
harness, branches on the name of a cell, a configuration or a metric.
"""

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

CONFIG_FILE_KEYS = {"name", "builder", "source", "source_values", "reduced",
                    "assumed", "deployment", "why", "model", "settings",
                    "flops", "reference", "rehearsal"}
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
METRIC_FILE_KEYS = {"name", "layer", "unit", "better", "source", "moves",
                    "workloads", "reader", "args", "what"}


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
    cfg = _load(os.path.join(ROOT, entry["file"]))
    _only(cfg, CONFIG_FILE_KEYS, entry["file"])
    if cfg["name"] != name:
        raise ManifestError(f"{entry['file']}: name is {cfg['name']!r}")
    return cfg


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
    m = _load(path)
    _only(m, METRIC_FILE_KEYS, path)
    if m["name"] != name:
        raise ManifestError(f"{path}: name is {m['name']!r}")
    return m


def sizes(section, rehearse):
    """A data file's sizes, with its ``rehearsal`` overrides laid over them
    in the CPU rehearsal and left out otherwise."""
    out = {k: v for k, v in section.items() if k != "rehearsal"}
    if rehearse:
        out.update(section.get("rehearsal", {}))
    return out
