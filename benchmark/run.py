"""BENCHMARK.json's command: runs ONE cell once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's system from its configuration file, warms up the shapes
its traffic uses (all of that is ``setup_s``), measures for ``--seconds``
and prints, last on stdout, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, traced
``breakdown``, and last ``compared``: each number that decided ``correct``
beside its limit, and whether it holds (the last lines of stderr say the
same). It measures on a TPU or not at all: with no TPU, or fewer chips than
the cell asks for, it exits non-zero and prints no result.

``--rehearse-cpu`` is the explicit mode the test file uses: the sizes under
``rehearsal`` in the data files, the CPU platform REQUIRED, Pallas kernels
interpreted, and every metric's value null: a CPU run never supplies a
number under the name of a device metric. With it, ``--config`` and
``--traffic`` may name files that no cell registers yet: ``--config`` a
configuration of BENCHMARK.json by name or a configuration's FILE by its
path from the checkout's root, ``--traffic`` a file of ``traffic/``.
"""

import time

CLOCK0 = time.perf_counter()   # set-up runs from here to the window's opening

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the checkout's root in place of this directory: `import benchmark` and
# `import paddle_tpu` resolve, and no module here shadows a stdlib one
sys.path[:] = [ROOT] + [p for p in sys.path
                        if os.path.abspath(p or ".") != HERE]

from benchmark import manifest, readers, trace as tr  # noqa: E402

DRIVERS = {"train_steps": "benchmark.train", "open_loop": "benchmark.serve",
           "closed_loop": "benchmark.serve"}


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--config", help="with --rehearse-cpu, in place of "
                    "--workload: a configuration of BENCHMARK.json, or "
                    "the path of a configuration's file")
    ap.add_argument("--traffic", help="with --rehearse-cpu: a traffic file")
    args = ap.parse_args(argv)
    if not args.rehearse_cpu and (args.config or args.traffic
                                  or not args.workload):
        ap.error("--workload names the cell; --config/--traffic are for "
                 "--rehearse-cpu")
    return args


def _peaks(kind):
    with open(os.path.join(manifest.HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no published peaks recorded for device_kind "
                       f"{kind!r} (known: "
                       f"{[k for k in table if not k.startswith('_')]})")
    return table[kind]


def _device(jax, system, traced):
    devs = jax.devices()
    peak = 0
    for d in system.devices:
        stats = d.memory_stats() or {}
        peak = max(peak, stats.get("peak_bytes_in_use", 0),
                   stats.get("bytes_in_use", 0))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs),
           "memory_peak_bytes": max(peak, system.compiled_bytes())}
    if traced.get("trace"):
        lo, hi = traced["trace_window"]
        busy = [tr.busy_and_gaps(d, (lo, hi))[0]
                for d in traced["trace"]["devices"].values()]
        out["busy_s"] = sum(busy) / len(busy)
        out["window_s"] = hi - lo
    return out


def _breakdown(traced):
    device = next(iter(traced["trace"]["devices"].values()))
    window = traced["trace_window"]
    gaps = tr.busy_and_gaps(device, window)[1]
    idle = tr.attribute_gaps(gaps, tr.span_segments(traced["spans"]))
    return {"device_ops": tr.top_ops(device, window),
            "idle_gaps": [[name, seconds] for name, seconds in sorted(
                idle.items(), key=lambda kv: -kv[1])[:10]]}


def main(argv=None):
    args = _args(argv)
    bench = manifest.load_manifest()
    if args.workload:
        cell = manifest.workload(bench, args.workload)
        config = manifest.load_config(bench, cell["config"])
    else:
        config = (manifest.load_config_file(os.path.join(ROOT, args.config))
                  if args.config.endswith(".json")
                  else manifest.load_config(bench, args.config))
        cell = {"name": f"{config['name']}.{args.traffic}", "chips": 1,
                "traffic": args.traffic}
    traffic = manifest.load_traffic(cell["traffic"])

    if not args.rehearse_cpu:
        # the compile cache at the fixed path the repo itself uses, so that
        # only the first run of a cell in a checkout compiles
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devs = jax.devices()
    want = "cpu" if args.rehearse_cpu else "tpu"
    if devs[0].platform != want or len(devs) < cell["chips"]:
        sys.exit(f"{cell['name']} needs {cell['chips']} device(s) of "
                 f"platform {want!r}; jax.devices() is {devs!r}")
    peaks = None if args.rehearse_cpu else _peaks(devs[0].device_kind)

    from paddle_tpu import kernels

    from benchmark.compiles import Compiles
    from benchmark.profile import Traced, traced_window

    compiles = Compiles()
    builder = importlib.import_module(
        "benchmark.builders." + config["builder"])
    driver = importlib.import_module(DRIVERS[traffic["kind"]])
    mode = (kernels.scoped_mode("interpret") if args.rehearse_cpu
            else contextlib.nullcontext())
    tracer = traced = None
    if args.trace:
        traced = Traced(os.path.join(ROOT, ".bench_trace", cell["name"]),
                        on_chip=not args.rehearse_cpu)
        tracer = lambda: traced_window(traced)  # noqa: E731
    with mode:
        t_import = time.perf_counter()
        system = builder.build(config, traffic, args.seed,
                               args.rehearse_cpu)
        print(f"# set-up: imports {t_import - CLOCK0:.2f} s, build (program, "
              f"weights; a server also compiles or loads here) "
              f"{time.perf_counter() - t_import:.2f} s", flush=True)
        result = driver.run(system, manifest.sizes(traffic, args.rehearse_cpu), args,
                            CLOCK0, compiles, tracer)
        t_device = time.perf_counter()
        reduced = traced.read() if traced is not None else {}
        device = _device(jax, system, reduced)
        print(f"# after the window: reading the trace and memory analysis "
              f"{time.perf_counter() - t_device:.2f} s", flush=True)
    result["facts"]["peak_memory_gb"] = device["memory_peak_bytes"] / 1e9

    if args.trace:
        run = {"facts": result["facts"], "registry": result["registry"],
               "stretch_registry": result["stretch_registry"],
               "config": config, "chips": cell["chips"], "peaks": peaks,
               "sizes": manifest.run_sizes(config, traffic, cell["chips"],
                                           args.rehearse_cpu),
               **reduced}
        metrics = {}
        for entry in manifest.metrics_of(bench, "per_layer", cell["name"]):
            spec = manifest.load_metric(entry["name"])
            value = readers.READERS[spec["reader"]](spec.get("args", {}), run)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
    else:
        # a cell reports every end-to-end metric BENCHMARK.json lists for
        # it; a rehearsal of files no cell registers reports what its
        # driver measures
        entries = (manifest.metrics_of(bench, "end_to_end", cell["name"])
                   if args.workload else
                   [e for e in bench["end_to_end"]
                    if e["name"] in result["end_to_end"]])
        metrics = {
            entry["name"]: {"value": result["end_to_end"][entry["name"]],
                            "unit": entry["unit"]}
            for entry in entries}
    if args.rehearse_cpu:
        for m in metrics.values():
            m["value"] = None
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device}
    if reduced.get("trace"):
        line["breakdown"] = _breakdown(reduced)
    line["compared"] = {
        name: {"value": value, "limit": limit, "holds": bool(holds)}
        for name, (value, limit, holds) in result["compared"].items()}
    print(json.dumps(line), flush=True)
    for name, (value, limit, holds) in result["compared"].items():
        print(f"compared {name}: {value!r}, limit {limit!r}: "
              f"{'holds' if holds else 'FAILS'}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
