"""The paged kernels' COPIES, compiled and ALONE in a program, on the device
it is given: what a call costs by the device's own clock over a block table
whose blocks lie side by side in the arena and over one that is shuffled,
and what of the call is the copies' descriptors and what the reduce. The
reading no interpreter, no chip-free compile and no cell's trace gives (in
a cell the table is what the pool made it, and a step's other streams run
under the kernel).

    python3 tools/check_paged_copies.py [--seed <n>] [--repeats 16]
        [--cases keye.paged,keye.index,trinity.paged,lfm2.paged]
        [--slots 4,16] [--lengths 8192,14336,30000]
        [--run-blocks 0,4,8,16] [--forms together,copies,reduce]
        [--root <another tree>] [--out <file>] [--interpret]

``paged_attention`` at Keye's geometry (4 K/V heads of 128 under 32 query
heads, blocks of 16 rows, ``--slots`` live slots at ``--lengths`` positions,
under a bias that keeps 2,048 positions a slot), ``index_scores``' step form
at the same lengths (16 index heads over a 128-lane key), and
``paged_attention`` at Trinity's full layer (8 K/V heads of 128 under 48
query heads, 8 of 24 slots at 16,384) and at LFM2's (8 K/V heads of 64
under 32, 24 of 128 slots at 336: the fragmented control's geometry), each
over

- ``ascending``: every slot's blocks one run of the arena;
- ``shuffled``: a permutation of the arena's blocks (no two neighbours);

and in three FORMS, each a program that is the kernel and nothing else,
``--repeats`` launches under one profiler session:

- ``together``: the kernel as the step program calls it;
- ``copies``: its reduce taken out (``_paged_pipeline``'s ``reduce_tile``
  hands its carry through): descriptors, DMA and waits alone.
  ``paged_attention`` only: ``index_scores``' product is not a callee;
- ``reduce``: its copies taken out (``_start_copies`` and ``_wait_copies``
  do nothing: the scratch holds zeros): the products and the walk alone.

``--run-blocks`` sets ``kernels.attention._RUN_BLOCKS`` (the blocks ONE
descriptor brings in where the table names them side by side; 0: a
descriptor a block, the kernel as it was before PR 64) for a row each;
without it the tree's own value. ``--root``: the SAME tool over another
tree's ``paddle_tpu`` and ``benchmark`` (a parent's checkout, which has no
``_RUN_BLOCKS``: its rows read ``run_blocks`` null), so parent and change
are read in one call, one process each.

``kernel_us`` is the mean device time of the kernel's events,
``descriptors`` the copy descriptors a call issues by the host's count over
the same table (an arena each), ``bytes_us`` the live rows read once at the
chip's peak bytes/s. Before the times, the ``together`` form against the
composite on the same operands (largest error over largest value). A JSON
line a row (to ``--out`` too), then the table. ``--interpret``: the same
code at a toy size through the interpreter on any backend, no times."""

import argparse
import contextlib
import glob
import inspect
import json
import os
import shutil
import sys
import tempfile

import numpy as np

#: case -> (kernel, K/V heads, head width, query heads, max_len, arena
#: blocks, slots, live slots, lengths): None takes ``--slots``/``--lengths``
CASES = {
    "keye.paged": ("paged", 4, 128, 32, 32768, 20480, None, None, None),
    "keye.index": ("index", 1, 128, 16, 32768, 20480, None, None, None),
    "trinity.paged": ("paged", 8, 128, 48, 33792, 30720, 24, 8, (16384,)),
    "lfm2.paged": ("paged", 8, 64, 32, 2048, 16384, 128, 24, (336,)),
}
REHEARSAL = {
    "toy.paged": ("paged", 2, 16, 4, 128, 96, 4, 3, (100,)),
    "toy.index": ("index", 1, 16, 4, 128, 96, 4, 3, (100,)),
}
BLOCK = 16
KEPT = 2048


def tables(rng, kind, slots, per_slot, blocks):
    """``[slots, per_slot]`` block ids: ``ascending`` a run a slot (the
    slots' runs may overlap: the kernel only reads), ``shuffled`` slices of
    one permutation with no entry its neighbour's successor."""
    if kind == "ascending":
        first = (np.arange(slots) * 1237) % (blocks - per_slot + 1)
        return first[:, None] + np.arange(per_slot)[None, :]
    perm = rng.permutation(blocks)
    table = np.stack([np.roll(perm, -s * 1237)[:per_slot]
                      for s in range(slots)])
    clash = np.diff(table, axis=1) == 1
    table[:, 1:][clash] = (table[:, 1:][clash] + 2) % blocks
    return table


def descriptors(table, lengths, run, unit, arenas):
    """Copy descriptors a call issues over ``table`` by the kernel's rule:
    one an aligned, wholly live, ascending group of ``run`` blocks, one a
    block otherwise (``run`` 0 or a unit that is not whole groups: all)."""
    total = 0
    for row, length in zip(table, lengths):
        live = -(-int(length) // BLOCK)
        if not run or unit % run:
            total += live
            continue
        whole = live // run
        groups = row[:whole * run].reshape(whole, run)
        runs = int(np.all(np.diff(groups, axis=1) == 1, axis=1).sum())
        total += runs + (whole - runs) * run + live - whole * run
    return total * arenas


@contextlib.contextmanager
def form(name, attention, sparse):
    """The kernels traced inside this context are the ``name`` form."""
    saved = [(m, k, getattr(m, k)) for m in (attention, sparse)
             for k in ("_start_copies", "_wait_copies", "_paged_pipeline")
             if hasattr(m, k)]
    try:
        if name == "reduce":
            for m, k, _ in saved:
                if k != "_paged_pipeline":
                    setattr(m, k, lambda *a, **kw: None)
        elif name == "copies":
            pipeline = attention._paged_pipeline
            at = list(inspect.signature(pipeline).parameters).index(
                "reduce_tile")

            def no_reduce(*args, **kw):
                args = list(args)
                args[at] = lambda i, half, row0, t, carry: carry
                return pipeline(*args, **kw)

            attention._paged_pipeline = no_reduce
        yield
    finally:
        for m, k, v in saved:
            setattr(m, k, v)


def _device_us(programs, repeats, trace):
    """Mean device time of each program's kernel events, us: ONE profiler
    session, ``repeats`` launches a program one after the other."""
    import jax

    for run, args, _ in programs:
        jax.block_until_ready(run(*args))
    directory = tempfile.mkdtemp(prefix="check_paged_copies_")
    try:
        jax.profiler.start_trace(directory)
        for run, args, _ in programs:
            for _ in range(repeats):
                out = run(*args)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            directory, "plugins", "profile", "*", "*.xplane.pb"))
        device = trace.load_xplane(path)["devices"]["0"]
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    names = {name for _, _, name in programs}
    # (an event's name is its HLO instruction: the kernel's own starts
    # with its name, its consumers' only mention it)
    events = sorted((e for e in device["ops"]
                     if e[0].lstrip("%").startswith(tuple(names))),
                    key=lambda e: e[1])
    if len(events) != repeats * len(programs):
        seen = {}
        for e in device["ops"]:
            seen[e[0]] = seen.get(e[0], 0) + 1
        print(json.dumps({"events": len(events), "expected":
                          repeats * len(programs),
                          "modules": len(device["modules"]),
                          "names": seen}), file=sys.stderr, flush=True)
        return [None] * len(programs)
    return [1e6 * sum(e[2] for e in events[i * repeats:(i + 1) * repeats])
            / repeats for i in range(len(programs))]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=6400000901)
    ap.add_argument("--repeats", type=int, default=16)
    ap.add_argument("--cases", default=None)
    ap.add_argument("--slots", default="4,16")
    ap.add_argument("--lengths", default="8192,14336,30000")
    ap.add_argument("--run-blocks", default=None)
    ap.add_argument("--forms", default="together,copies,reduce")
    ap.add_argument("--root", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args(argv)

    root = os.path.abspath(args.root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    sys.path.insert(0, root)

    import jax
    import jax.numpy as jnp

    from benchmark import run as bench_run
    from benchmark import trace
    from paddle_tpu.kernels import attention, sparse

    cases = REHEARSAL if args.interpret else CASES
    names = args.cases.split(",") if args.cases else list(cases)
    dtype = jnp.float32 if args.interpret else jnp.bfloat16
    kind = jax.devices()[0].device_kind
    peak = None if args.interpret else bench_run._peaks(
        kind)["hbm_bytes_per_s"]
    own = getattr(attention, "_RUN_BLOCKS", None)
    sweep = ([int(r) for r in args.run_blocks.split(",")]
             if args.run_blocks and own is not None else [own])
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed % (2 ** 31))
    out = open(args.out, "a") if args.out else None
    table_rows = []

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for name in names:
        kernel, heads, width, q_heads, max_len, blocks, S, live, lengths = (
            cases[name])
        H = heads * width
        per_slot = max_len // BLOCK
        arenas = [jax.random.normal(k, (blocks * BLOCK, H), dtype)
                  for k in jax.random.split(key, 1 if kernel == "index"
                                            else 2)]
        combos = ([(S, live, n) for n in lengths] if S else [
            (int(s), int(s), int(n)) for s in args.slots.split(",")
            for n in args.lengths.split(",")])
        for S_, live_, length in combos:
            lens = np.where(np.arange(S_) < live_, length, 0)
            open_ = np.zeros((S_, max_len), bool)
            for s in range(live_):
                open_[s, rng.permutation(length)[:KEPT]] = True
                open_[s, length - 1] = True
            bias = jnp.asarray(np.where(open_, 0.0, -1e9)[:, None],
                               jnp.float32)
            q = jax.random.normal(key, (S_, q_heads * width), dtype)
            w = jax.random.normal(key, (S_, q_heads), jnp.float32)
            programs, rows = [], []
            for table_kind in ("ascending", "shuffled"):
                table = tables(rng, table_kind, S_, per_slot, blocks)
                arena_rows = jnp.asarray(
                    (table[:, :, None] * BLOCK
                     + np.arange(BLOCK)).reshape(-1), jnp.int32)
                if kernel == "paged":
                    def call(q, a, b, rows_, bias_):
                        return attention.paged_attention(
                            q, a, b, rows_, bias_, S_, max_len, BLOCK,
                            width ** -0.5, interpret=args.interpret,
                            kv_heads=heads)

                    def composite(q, a, b, rows_, bias_):
                        return attention.paged_attention_composite(
                            q, a, b, rows_, bias_, S_, max_len,
                            width ** -0.5, kv_heads=heads)

                    operands = (q, *arenas, arena_rows, bias)
                    unit = attention.paged_copy_unit(BLOCK, per_slot, H,
                                                     dtype)
                    event = "paged_attention"
                else:
                    hz = jnp.asarray(lens, jnp.int32)

                    def call(q, w_, a, rows_):
                        return sparse.index_scores(
                            q, w_, a, rows_, S_, BLOCK, hz,
                            interpret=args.interpret)[:, :max_len]

                    def composite(q, w_, a, rows_):
                        return sparse.index_scores_composite(
                            q, w_, a, rows_, S_)

                    operands = (q, w, *arenas, arena_rows)
                    unit = max(1, min(per_slot,
                                      sparse._SCORE_TILE_ROWS // BLOCK))
                    event = sparse.INDEX_SCORES_KERNEL
                want = np.asarray(jax.jit(composite)(*operands), np.float64)
                seen = np.arange(max_len)[None, :] < lens[:, None]
                for run in sweep:
                    if run is not None:
                        attention._RUN_BLOCKS = run
                    for f in args.forms.split(","):
                        if f == "copies" and kernel != "paged":
                            continue
                        with form(f, attention, sparse):
                            # (a fresh function: a new trace a form)
                            fn = jax.jit(lambda *a, call=call: call(*a))
                            got = np.asarray(fn(*operands), np.float64)
                        row = {"tree": root, "case": name, "slots": live_,
                               "length": length, "table": table_kind,
                               "run_blocks": run, "form": f,
                               "descriptors": descriptors(
                                   table, lens, run or 0, unit,
                                   len(arenas))}
                        if f == "together":
                            # (a slot without a position, a row past
                            # the horizon: the composite's is garbage)
                            keep = (seen if kernel == "index"
                                    else lens[:, None] > 0)
                            got = np.where(keep, got, 0.0)
                            ref = np.where(keep, want, 0.0)
                            row["error"] = float(
                                np.abs(got - ref).max()
                                / max(np.abs(ref).max(), 1e-30))
                        rows.append(row)
                        programs.append((fn, operands, event))
            if own is not None:
                attention._RUN_BLOCKS = own
            if args.interpret:
                for row in rows:
                    emit(row)
                continue
            live_bytes = (live_ * -(-length // BLOCK) * BLOCK * H
                          * len(arenas) * jnp.dtype(dtype).itemsize)
            for row, us in zip(rows, _device_us(programs, args.repeats,
                                                trace)):
                row.update(device=kind, kernel_us=us,
                           bytes_us=1e6 * live_bytes / peak)
                emit(row)
            table_rows += rows
            del programs
        del arenas
    if args.interpret:
        return
    print("case slots length table run form descriptors kernel_us bytes_us")
    for r in table_rows:
        us = "-" if r["kernel_us"] is None else f"{r['kernel_us']:.1f}"
        print(f"{r['case']:>14} {r['slots']:>3} {r['length']:>6} "
              f"{r['table']:>9} {str(r['run_blocks']):>4} {r['form']:>8} "
              f"{r['descriptors']:>7} {us:>9} {r['bytes_us']:>8.1f}")


if __name__ == "__main__":
    main()
