"""``kernels/moe.py``'s ``moe_experts`` kernel COMPILED and ALONE in a
program, on the device it is given: what a call costs by the device's own
clock against the time its bytes take, and what of that is the walk over
held experts that no token chose. The reading no interpreter, no chip-free
compile and no cell's trace gives (in a cell the step's other streams run
under the kernel, and a step touches what its traffic makes it touch).

    python3 tools/check_moe_experts.py [--seed <n>] [--repeats 24]
        [--cells trinity_large_preview,mistral_small_4_119b,...]
        [--touched 1,2,4,all] [--against <another tree's kernels/moe.py>]
        [--interpret]

At the decode step's geometry of each routed serving cell
(``kernels.MOE_EXPERTS_STEPS``: tokens, hidden size, expert width, held
experts, matrices an expert; bfloat16) and for each number of TOUCHED experts (drawn among the held ones
by the seed, so never the leading ids alone; one or two tokens each), three
programs that are the kernel and nothing else, ``--repeats`` launches each
under ONE profiler session:

- ``held``: the kernel handed every held expert's matrices, as the step
  program calls it;
- ``touched_only``: the same kernel handed ONLY the touched experts'
  matrices (held = the touched count): the same bytes and not one grid row
  without work, so ``held`` less this is what the untouched experts cost,
  and this against ``bytes_us`` is what the tile of the hidden size costs;
- ``against``, with ``--against``: ``moe_experts`` of ANOTHER tree's
  ``kernels/moe.py`` (the file is loaded beside this tree's) handed every
  held expert: a parent's kernel beside the change's in one session.

``kernel_us`` is the mean device time of the events named ``moe_experts``
(what ``moe_experts_roofline`` and ``moe_experts_device_share`` match in a
cell's trace), ``launch_us`` the whole module's (the order of the experts,
the tokens' tiles turned and turned back: XLA's), ``bytes_us`` the touched
experts' matrices read once at the chip's peak bytes/s (the roofline's
count), ``of_bytes`` the share ``bytes_us`` is of ``kernel_us`` in %,
``grid_rows`` the expert rows the call's grid walks (read off the traced
call: its static extent, or the touched count where the extent is traced)
and ``idle_rows`` those of them without a touched expert. Before the
times, each compiled program against ``experts_composite`` on the same
operands (largest error over largest value), the grid's rows and the
registry's fallbacks counted, in one JSON line a cell. Then a JSON line a
table row, and the table. ``--interpret``: the same code at a toy size
through the interpreter on any backend, no times."""

import argparse
import functools
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

REHEARSAL = {"toy": (6, 256, 40, 8, 3)}


def load_kernel(path):
    """``moe_experts`` of the ``kernels/moe.py`` at ``path``, loaded beside
    this tree's (its imports are this tree's)."""
    spec = importlib.util.spec_from_file_location("moe_against", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.moe_experts


def operands(key, geometry, dtype):
    """``(x [T, H], the matrices [E, F, H] ...)`` on the device, drawn
    there: an expert's 56 MB is no host's to make."""
    import jax

    tokens, hidden, ffn, held, matrices = geometry
    keys = jax.random.split(key, matrices + 1)
    x = jax.random.normal(keys[0], (tokens, hidden), dtype)
    return x, [0.02 * jax.random.normal(k, (held, ffn, hidden), dtype)
               for k in keys[1:]]


def choices(rng, tokens, held, count):
    """``(ids, c [T, held])``: ``count`` touched experts drawn among the
    held ones, one or two tokens each with a weight in (0.1, 1.1)."""
    ids = np.sort(rng.permutation(held)[:count])
    c = np.zeros((tokens, held), "float32")
    for n, e in enumerate(ids):
        rows = rng.permutation(tokens)[:1 + n % 2]
        c[rows, e] = rng.uniform(0.1, 1.1, len(rows))
    return ids, c


def grid_rows(fn, args, touched):
    """Expert rows the grid of ``fn``'s ``moe_experts`` call walks with
    ``touched`` experts touched: the traced call's static first extent, or
    ``max(touched, 1)`` where that extent is a traced bound."""
    import jax

    (call,) = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    rows = call.params["grid_mapping"].grid[0]
    return rows if isinstance(rows, int) else max(touched, 1)


def _device_us(programs, repeats):
    """``(kernel_us, launch_us)`` a program of ``programs`` (pairs of a
    jitted function and its operands): ONE profiler session, ``repeats``
    launches a program one after the other."""
    import jax

    from benchmark import trace as tr

    for run, args in programs:
        jax.block_until_ready(run(*args))
    directory = tempfile.mkdtemp(prefix="check_moe_experts_")
    try:
        jax.profiler.start_trace(directory)
        for run, args in programs:
            for _ in range(repeats):
                out = run(*args)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            directory, "plugins", "profile", "*", "*.xplane.pb"))
        device = tr.load_xplane(path)["devices"]["0"]
    finally:
        shutil.rmtree(directory, ignore_errors=True)

    def by_program(events):
        events = sorted(events, key=lambda e: e[1])
        if len(events) != repeats * len(programs):
            return [None] * len(programs)
        return [1e6 * sum(e[2] for e in events[i * repeats:(i + 1) * repeats])
                / repeats for i in range(len(programs))]

    kernels = by_program([e for e in device["ops"] if "moe_experts" in e[0]])
    return list(zip(kernels, by_program(device["modules"])))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=6200000901)
    ap.add_argument("--repeats", type=int, default=24)
    ap.add_argument("--cells", default=None,
                    help="of kernels.MOE_EXPERTS_STEPS; all of them")
    ap.add_argument("--touched", default="1,2,4,all")
    ap.add_argument("--against", default=None)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark import run as bench_run
    from paddle_tpu import kernels
    from paddle_tpu.kernels import moe

    cells = REHEARSAL if args.interpret else {
        name: kernels.MOE_EXPERTS_STEPS[name]
        for name in (args.cells.split(",") if args.cells
                     else kernels.MOE_EXPERTS_STEPS)}
    dtype = jnp.float32 if args.interpret else jnp.bfloat16
    kind = jax.devices()[0].device_kind
    peak = None if args.interpret else bench_run._peaks(
        kind)["hbm_bytes_per_s"]
    forms = {"held": moe.moe_experts}
    if args.against:
        forms["against"] = load_kernel(args.against)
    forms = {form: functools.partial(fn, interpret=args.interpret)
             for form, fn in forms.items()}
    rng = np.random.default_rng(args.seed)
    composite = jax.jit(moe.experts_composite)
    table = []
    for name, geometry in cells.items():
        tokens, hidden, ffn, held, matrices = geometry
        x, weights = operands(jax.random.PRNGKey(args.seed % (2 ** 31)),
                              geometry, dtype)
        counts = sorted({held if c == "all" else min(int(c), held)
                         for c in args.touched.split(",")})
        rows, programs, errors = [], [], {}
        before = kernels.fallback_counter().value
        for count in counts:
            ids, c = choices(rng, tokens, held, count)
            c = jnp.asarray(c)
            want = np.asarray(composite(x, c, *weights), np.float64)
            handed = {form: (fn, (x, c, *weights))
                      for form, fn in forms.items()}
            handed["touched_only"] = (forms["held"], (
                x, c[:, ids], *[w[ids] for w in weights]))
            for form, (fn, operands_) in handed.items():
                run = jax.jit(fn)
                got = np.asarray(run(*operands_), np.float64)
                errors[f"{form}:{count}"] = float(
                    np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))
                walked = grid_rows(fn, operands_, count)
                rows.append({
                    "cell": name, "form": form, "touched": count,
                    "grid_rows": walked, "idle_rows": walked - count})
                programs.append((run, operands_))
        # none touched: one row walked, nothing added
        untouched = jnp.zeros((tokens, held))
        report = {"seed": args.seed, "device": kind, "cell": name,
                  "geometry": geometry, "errors": errors,
                  "grid_rows": {f"{r['form']}:{r['touched']}": r["grid_rows"]
                                for r in rows},
                  "fallbacks": kernels.fallback_counter().value - before,
                  "none_touched_is_zeros": not any(
                      np.asarray(jax.jit(fn)(x, untouched, *weights)).any()
                      for fn in forms.values())}
        print(json.dumps(report), flush=True)
        if args.interpret:
            continue
        expert_bytes = matrices * ffn * hidden * jnp.dtype(dtype).itemsize
        for row, (kernel_us, launch_us) in zip(
                rows, _device_us(programs, args.repeats)):
            bytes_us = 1e6 * row["touched"] * expert_bytes / peak
            row.update(kernel_us=kernel_us, launch_us=launch_us,
                       bytes_us=bytes_us, of_bytes=kernel_us
                       and 100.0 * bytes_us / kernel_us)
            print(json.dumps(row), flush=True)
        table += rows
        del x, weights, programs
    if args.interpret:
        return
    print("cell form touched grid_rows idle_rows kernel_us launch_us "
          "bytes_us of_bytes_%")
    show = lambda v: "-" if v is None else f"{v:.1f}"  # noqa: E731
    for r in table:
        print(f"{r['cell']:>24} {r['form']:>12} {r['touched']:>3} "
              f"{r['grid_rows']:>3} {r['idle_rows']:>3} "
              + " ".join(f"{show(r[k]):>9}" for k in (
                  "kernel_us", "launch_us", "bytes_us", "of_bytes")))


if __name__ == "__main__":
    main()
