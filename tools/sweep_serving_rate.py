"""One run of a serving cell's open loop at ANOTHER rate, for the sweep that
finds the rate a tree sustains (PERF.md section 4): the cell's own command
(``benchmark/run.py``: same builder, traffic, warm-up, pre-roll and window)
with ``rate_rps`` replaced and the comparison with the plain reference left
out (``check_requests`` 0: a sweep reads rates, not answers; ``--check``
keeps it).

    python3 tools/sweep_serving_rate.py --workload <cell> --rate <rps>
        --seed <n> [--seconds 51] [--trace 1] [--check]
        [--model key=value ...] [--rehearse-cpu]

``--model`` replaces a size of the configuration's ``model`` group for the
run (``chunk_tokens=1024``: the sweep that sizes a chunk).

Prints ``benchmark/run.py``'s lines, then one JSON line: the rate, the
seed, the requests sent and finished inside the window, the failed ones and
the cell's end-to-end metrics. A rate is sustained when at least 98 % of
the requests sent in the window finish in it.
"""

import argparse
import contextlib
import io
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1),
                    help="1: the per-layer readings of the run besides")
    ap.add_argument("--check", action="store_true",
                    help="keep the cell's comparison with the reference")
    ap.add_argument("--model", action="append", default=[],
                    metavar="key=value")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import manifest, run

    load = manifest.load_traffic
    sized = dict((k, int(v)) for k, v in (
        pair.split("=", 1) for pair in args.model))
    if sized:
        load_config = manifest.load_config

        def with_sizes(*a):
            cfg = load_config(*a)
            group = "rehearsal" if args.rehearse_cpu else None
            model = dict(cfg["model"])
            if group:
                model[group] = dict(model.get(group, {}), **sized)
            else:
                model.update(sized)
            return dict(cfg, model=model)

        manifest.load_config = with_sizes

    def at_rate(name):
        return dict(load(name), rate_rps=args.rate,
                    **({} if args.check else {"check_requests": 0}))

    manifest.load_traffic = at_rate
    out = io.StringIO()

    class Tee(io.TextIOBase):
        def write(self, text):
            sys.__stdout__.write(text)
            return out.write(text)

        def flush(self):
            sys.__stdout__.flush()

    with contextlib.redirect_stdout(Tee()):
        run.main(["--workload", args.workload, "--seed", str(args.seed),
                  "--seconds", str(args.seconds), "--trace", str(args.trace)]
                 + ["--rehearse-cpu"] * args.rehearse_cpu)
    text = out.getvalue()
    line = json.loads(text.strip().splitlines()[-1])
    sends = re.search(r"over (\d+) sends", text)
    finished = re.search(r"(\d+) requests finished in the window", text)
    print(json.dumps({
        "rate_rps": args.rate, "seed": args.seed, "model": sized,
        "sent": int(sends.group(1)), "finished": int(finished.group(1)),
        "failed": line["failed"], "correct": line["correct"],
        "metrics": {k: v["value"] for k, v in line["metrics"].items()}}),
        flush=True)


if __name__ == "__main__":
    main()
