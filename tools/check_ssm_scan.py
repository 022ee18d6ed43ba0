"""``kernels/mamba.py``'s ``ssm_scan`` kernel COMPILED, on the device it is
given, against the composite it replaces (``ssm_scan_chunked``'s einsums):
the reading no interpreter and no chip-free compile gives.

    python3 tools/check_ssm_scan.py [--seed <n>] [--repeats 24]
        [--skip-times] [--rehearse-cpu]

*Errors*, at ``granite_4_0_h_micro``'s geometry (a launch of 512 tokens in
scan chunks of 256, 64 heads of 64 over a state of 128, one group; a real
``h0``; then a ragged launch, 301 real tokens with ``dt = 0`` behind them)
and ``nemotron3_nano_30b_a3b``'s (128 tokens, one scan chunk, 8 groups):
composite | kernel, each one's largest error over the output's largest
value, for ``y`` and for the last state, against ``ssm_scan_sequential``'s
recurrence evaluated token by token in float64 on the host. The kernel's
products are read off its jaxpr (operand types and precision), and the
registry's fallbacks counted.

*Times*, composite | kernel, two readings a geometry. ``device``: the
profiler's trace of ``--repeats`` launches of the compiled scan alone, a
launch's module and inside it the scan's loop (``^%?while``, what
``ssm_scan_device_share`` matches in a cell's trace) and the kernel's calls
(``^%?ssm_scan``). ``chain``: the host's clock over ``--repeats`` DEPENDENT
launches inside ONE program (a launch's ``y`` is the next one's ``x``, its
last state the next one's ``h0``), the best of three runs; the chain's own
carries are in that reading (XLA lays a loop's operands out as the loop
around it likes), so where the two disagree the device's is the scan's. A
JSON line each. ``--rehearse-cpu``: the same code at a toy size through the
interpreter, no times."""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: (name, T, real tokens, heads, head dim, state, groups, scan chunk)
CASES = [
    ("granite_4_0_h_micro", 512, 512, 64, 64, 128, 1, 256),
    ("granite_4_0_h_micro.ragged", 512, 301, 64, 64, 128, 1, 256),
    ("nemotron3_nano_30b_a3b", 128, 128, 64, 64, 128, 8, 128),
]
REHEARSAL = [
    ("rehearsal", 32, 32, 8, 8, 16, 1, 16),
    ("rehearsal.ragged", 32, 19, 8, 8, 16, 1, 16),
    ("rehearsal.groups", 16, 16, 8, 8, 16, 4, 16),
]


def sequential_float64(x, dt, a, b, c, h0):
    """``ssm_scan_sequential`` in float64 on the host."""
    x, dt, a, b, c, h = (np.asarray(v, np.float64)
                         for v in (x, dt, a, b, c, h0))
    per = x.shape[1] // b.shape[1]
    y = np.empty(x.shape)
    for t in range(x.shape[0]):
        bt, ct = np.repeat(b[t], per, 0), np.repeat(c[t], per, 0)
        h = (np.exp(dt[t] * a)[:, None, None] * h
             + (dt[t][:, None] * x[t])[:, :, None] * bt[:, None])
        y[t] = np.sum(h * ct[:, None], -1)
    return y, h


def kernel_products(fn, args):
    """``(operand types, precision)`` of every product inside the Pallas
    calls of ``fn`` traced at ``args``."""
    import jax

    found = []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if inside and eqn.primitive.name == "dot_general":
                found.append((
                    [str(v.aval.dtype) for v in eqn.invars],
                    str(eqn.params["precision"])))
            for name, sub in eqn.params.items():
                for j in (sub if isinstance(sub, (tuple, list)) else (sub,)):
                    j = getattr(j, "jaxpr", j)
                    if hasattr(j, "eqns"):
                        walk(j, inside or eqn.primitive.name == "pallas_call")

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return found


def _errors(args, report):
    import jax

    from paddle_tpu import kernels
    from paddle_tpu.kernels import mamba

    interpret = args.rehearse_cpu
    rng = np.random.default_rng(args.seed)
    before = kernels.fallback_counter().value
    out = []
    for name, t, real, heads, p, n_state, groups, chunk in (
            REHEARSAL if interpret else CASES):
        case = kernels._scan_case(rng, t, heads, p, n_state, groups,
                                  real)
        want = sequential_float64(*case)
        reading = {"geometry": name, "tokens": t, "real": real,
                   "chunk": chunk, "groups": groups}
        by_kernel = lambda *v: mamba.ssm_scan_chunked(  # noqa: E731
            *v, chunk, kernel=interpret)
        for side, fn in (("composite", lambda *v: mamba.ssm_scan_chunked(
                *v, chunk)), ("kernel", by_kernel)):
            got = jax.jit(fn)(*case)
            for what, g, w in zip(("y", "state"), got, want):
                g = np.asarray(g, np.float64)
                reading[f"{side}_{what}_error"] = float(
                    np.abs(g - w).max() / np.abs(w).max())
            reading[f"{side}_finite"] = bool(
                all(np.isfinite(np.asarray(g)).all() for g in got))
        products = kernel_products(by_kernel, case)
        reading["kernel_products"] = len(products)
        reading["kernel_products_float32_highest"] = bool(products) and all(
            set(types) == {"float32"} and "HIGHEST" in precision
            for types, precision in products)
        out.append(reading)
    report["errors"] = out
    report["fallbacks"] = kernels.fallback_counter().value - before


def _device_us(fn, case, repeats):
    """us a launch of ``fn`` on the device by the profiler's trace of
    ``repeats`` launches: the module, the scan's loop and the kernel's
    calls (None where the module has no such event)."""
    import glob
    import shutil
    import tempfile

    import jax

    from benchmark import trace as tr

    run = jax.jit(fn)
    case = [jax.device_put(v) for v in case]
    jax.block_until_ready(run(*case))
    directory = tempfile.mkdtemp(prefix="check_ssm_scan_")
    try:
        jax.profiler.start_trace(directory)
        for _ in range(repeats):
            jax.block_until_ready(run(*case))
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            directory, "plugins", "profile", "*", "*.xplane.pb"))
        device = tr.load_xplane(path)["devices"]["0"]
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    window = (0.0, float("inf"))
    per = lambda seconds: 1e6 * seconds / repeats if seconds else None  # noqa: E731
    return {"launch_us": per(tr.matching_seconds(device, "", window,
                                                 ("modules",))),
            "loop_us": per(tr.matching_seconds(device, "^%?while", window)),
            "kernel_us": per(tr.matching_seconds(device, "^%?ssm_scan",
                                                 window))}


def _chain_us(fn, case, repeats):
    """us a launch of ``fn``: ``repeats`` dependent launches inside ONE
    program, compiled once, the best of three runs by the host's clock."""
    import jax

    def many(x, dt, a, b, c, h0):
        body = lambda _i, xh: fn(xh[0], dt, a, b, c, xh[1])  # noqa: E731
        return jax.lax.fori_loop(0, repeats, body, (x, h0))

    run = jax.jit(many)
    jax.block_until_ready(run(*case))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*case))
        best = min(best, time.perf_counter() - t0)
    return 1e6 * best / repeats


def _times(args, report):
    from paddle_tpu import kernels
    from paddle_tpu.kernels import mamba

    rng = np.random.default_rng(args.seed + 1)
    out = []
    for name, t, real, heads, p, n_state, groups, chunk in CASES:
        case = kernels._scan_case(rng, t, heads, p, n_state, groups,
                                  real)
        reading = {"geometry": name, "tokens": t, "real": real,
                   "chunk": chunk, "trips": -(-t // chunk)}
        for side, fn in (
                ("composite", lambda *v: mamba.ssm_scan_chunked(*v, chunk)),
                ("kernel", lambda *v: mamba.ssm_scan_chunked(
                    *v, chunk, kernel=False))):
            reading[side] = dict(_device_us(fn, case, args.repeats),
                                 chain_us=_chain_us(fn, case, args.repeats))
        for what in ("launch_us", "loop_us", "chain_us"):
            if reading["kernel"][what]:
                reading[f"speedup_{what[:-3]}"] = (
                    reading["composite"][what] / reading["kernel"][what])
        out.append(reading)
    report["times"] = out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=5200000901)
    ap.add_argument("--repeats", type=int, default=24)
    ap.add_argument("--skip-times", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    report = {"seed": args.seed, "device": jax.devices()[0].device_kind}
    _errors(args, report)
    print(json.dumps(report), flush=True)
    if not (args.skip_times or args.rehearse_cpu):
        times = {"seed": args.seed}
        _times(args, times)
        print(json.dumps(times), flush=True)


if __name__ == "__main__":
    main()
