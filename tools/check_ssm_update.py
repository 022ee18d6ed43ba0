"""``kernels/mamba.py``'s ``ssm_update`` kernel COMPILED and ALONE in a
program, on the device it is given: what a call costs by the device's own
clock, against the time its bytes take and against the composite it
replaces. The reading no interpreter, no chip-free compile and no cell's
trace gives (in a cell the step's other streams run under the kernel).

    python3 tools/check_ssm_update.py [--seed <n>] [--repeats 24]
        [--blocks 16,32,64] [--stepping 1,3,21,32] [--interpret]

At the hybrid serving cells' geometry (32 slots of 64 heads x 64 x 128,
float32; ``nemotron3_nano_30b_a3b`` steps ~21 of them a decode step,
``granite_4_0_h_micro`` ~3), for each number of STEPPING slots (drawn among
the 32 by the seed) and each head block (the heads of one slot a grid step
carries; the row marked ``*`` is at what ``_update_heads`` gives from the
shapes, the kernel as the programs call it): one jitted program that is the update and
nothing else, the state donated and handed on from launch to launch,
``--repeats`` launches under the profiler. ``kernel_us`` is the mean device
time of the events named ``ssm_update`` (what ``ssm_update_roofline`` and
``ssm_update_device_share`` match in a cell's trace), ``launch_us`` the
whole module's (the order of the slots, ``x`` turned, ``y`` turned back and
masked: XLA's), ``bytes_us`` the stepping slots' states read and written
once at the chip's peak bytes/s (the roofline's count), ``of_bytes`` the
share ``bytes_us`` is of ``kernel_us`` in %; the composite's ``launch_us``
beside them (it has no kernel; it moves every slot's state). Before the
times, the compiled kernel against the composite on the same operands:
largest error over largest value for the state and ``y``, idle slots bit for
bit, and the registry's fallbacks counted. A JSON line a table row, then the
table. ``--interpret``: the same code at a toy size through the interpreter
on any backend, no times."""

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: slots, heads, head dim, state size
GEOMETRY = (32, 64, 64, 128)
REHEARSAL = (6, 32, 8, 128)


def operands(rng, geometry):
    """One decode step's operands as ``mixer_step`` makes them: a decay in
    (0, 1), ``dt x`` and ``B`` small enough that a state neither dies nor
    grows over the launches."""
    s, h, p, n = geometry
    draw = lambda *shape: rng.standard_normal(shape).astype("float32")  # noqa: E731
    return (draw(s, h, p, n), 0.1 * draw(s, h, p),
            rng.uniform(0.5, 1.0, (s, h)).astype("float32"),
            draw(s, h, n) / n, draw(s, h, n))


def stepping_mask(rng, slots, count):
    mask = np.zeros(slots, bool)
    mask[rng.permutation(slots)[:count]] = True
    return mask


def _errors(fn, case, masks, wants):
    """Largest error over largest value of ``fn``'s state and ``y`` against
    the composite's (``wants``, a pair a mask), and whether every idle slot
    came back bit for bit with ``y`` 0."""
    import jax

    state, rest = case[0], case[1:]
    run = jax.jit(fn)
    worst, idle_kept = {"state": 0.0, "y": 0.0}, True
    for mask, want in zip(masks, wants):
        got = run(state, *rest, mask)
        for what, g, w in zip(("state", "y"), got, want):
            g = np.asarray(g, np.float64)
            worst[what] = max(worst[what], float(
                np.abs(g - w).max() / max(np.abs(w).max(), 1e-30)))
        idle = ~mask
        idle_kept = bool(
            idle_kept
            and (np.asarray(got[0])[idle] == np.asarray(state)[idle]).all()
            and not np.asarray(got[1])[idle].any())
    return {"state_error": worst["state"], "y_error": worst["y"],
            "idle_slots_bit_for_bit": idle_kept}


def _device_us(fn, case, masks, repeats):
    """us a launch of ``fn`` on the device, a ``(kernel_us, launch_us)`` a
    mask: ONE profiler session, ``repeats`` launches a mask one after the
    other, each handed the state the last one left (donated)."""
    import jax

    from benchmark import trace as tr

    run = jax.jit(fn, donate_argnums=0)
    state, rest = jax.device_put(case[0]), [jax.device_put(v)
                                            for v in case[1:]]
    masks = [jax.device_put(m) for m in masks]
    state = jax.block_until_ready(run(state, *rest, masks[0]))[0]
    directory = tempfile.mkdtemp(prefix="check_ssm_update_")
    try:
        jax.profiler.start_trace(directory)
        for mask in masks:
            for _ in range(repeats):
                state = run(state, *rest, mask)[0]
            jax.block_until_ready(state)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            directory, "plugins", "profile", "*", "*.xplane.pb"))
        device = tr.load_xplane(path)["devices"]["0"]
    finally:
        shutil.rmtree(directory, ignore_errors=True)

    def by_mask(events):
        events = sorted(events, key=lambda e: e[1])
        if len(events) != repeats * len(masks):
            return [None] * len(masks)
        return [1e6 * sum(e[2] for e in events[i * repeats:(i + 1) * repeats])
                / repeats for i in range(len(masks))]

    kernels = by_mask([e for e in device["ops"] if "ssm_update" in e[0]])
    return list(zip(kernels, by_mask(device["modules"])))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=5500000901)
    ap.add_argument("--repeats", type=int, default=24)
    ap.add_argument("--blocks", default="16,32,64")
    ap.add_argument("--stepping", default="1,3,21,32")
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from benchmark import run as bench_run
    from paddle_tpu import kernels
    from paddle_tpu.kernels import mamba

    geometry = REHEARSAL if args.interpret else GEOMETRY
    slots, heads, p, n_state = geometry
    blocks = sorted({min(int(b), heads) for b in args.blocks.split(",")})
    counts = [min(int(c), slots) for c in args.stepping.split(",")]
    rng = np.random.default_rng(args.seed)
    case = operands(rng, geometry)
    masks = [stepping_mask(rng, slots, c) for c in counts]
    kind = jax.devices()[0].device_kind
    chosen = mamba._update_heads(heads, p, n_state)
    update = lambda block: lambda *v: mamba.ssm_update(  # noqa: E731
        *v, interpret=args.interpret, block=block)

    before = kernels.fallback_counter().value
    composite = jax.jit(mamba.ssm_update_composite)
    checked = masks + [np.zeros(slots, bool)]
    wants = [[np.asarray(w, np.float64) for w in composite(*case, mask)]
             for mask in checked]
    report = {"seed": args.seed, "device": kind, "geometry": geometry,
              "chosen_block": chosen,
              "errors": {str(b): _errors(update(b), case, checked, wants)
                         for b in blocks}}
    report["fallbacks"] = kernels.fallback_counter().value - before
    print(json.dumps(report), flush=True)
    if args.interpret:
        return

    peak = bench_run._peaks(kind)["hbm_bytes_per_s"]
    slot_bytes = 2 * 4 * heads * p * n_state
    composite_us = _device_us(mamba.ssm_update_composite, case, masks,
                              args.repeats)
    rows = []
    for block in blocks:
        times = _device_us(update(block), case, masks, args.repeats)
        for count, (kernel_us, launch_us), (_none, whole_us) in zip(
                counts, times, composite_us):
            bytes_us = 1e6 * count * slot_bytes / peak
            row = {"block": block, "chosen": block == chosen,
                   "stepping": count, "kernel_us": kernel_us,
                   "launch_us": launch_us, "bytes_us": bytes_us,
                   "of_bytes": kernel_us and 100.0 * bytes_us / kernel_us,
                   "composite_launch_us": whole_us}
            rows.append(row)
            print(json.dumps(row), flush=True)
    print("block (* chosen) stepping kernel_us launch_us bytes_us "
          "of_bytes_% composite_launch_us")
    show = lambda v: "-" if v is None else f"{v:.1f}"  # noqa: E731
    for r in rows:
        print(f"{r['block']:>5}{'*' if r['chosen'] else ' '} "
              f"{r['stepping']:>8} "
              + " ".join(f"{show(r[k]):>9}" for k in (
                  "kernel_us", "launch_us", "bytes_us", "of_bytes",
                  "composite_launch_us")))


if __name__ == "__main__":
    main()
