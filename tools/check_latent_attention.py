"""Latent attention over ONE arena and the chunk's grouped expert product,
COMPILED, on the device they are given: the readings no interpreter and no
chip-free compile gives (PERF.md section 6, PR 56).

    python3 tools/check_latent_attention.py [--seed <n>] [--repeats 8]
        [--parts chunk,step,grouped] [--grouped-tokens 256,384,512]
        [--grouped-geometry 4096x2048x16x128,3072x3072x32x256]
        [--kernel-tiles 512x512,256x256] [--rehearse-cpu]

At ``mistral_small_4_119b``'s geometry (32 heads of 64 + 64 | 128 over a
latent of 256, rows of 384 lanes, block 16, slots of 33,280 positions, 16
held gated experts of width 2,048 at hidden 4,096, 4 of 128 chosen),
bfloat16, under a shuffled block table:

* *chunk*: a prompt chunk of 512, 1,024 and 2,048 queries behind 0, 8,192
  and 30,720 rows, EXPANDED by the ``latent_chunk_attention`` kernel (what
  serves) and by the loops over live rows, 512 queries at a time (its
  fallback, ``kernels/attention.py latent_chunk_expanded``): a call's
  device time each, beside what its operations take at the chip's peak,
  and the two's largest difference over the loops' largest value (the
  absorbed form through the chunk kernel was read beside the loops once,
  1.75 x slower, and is gone: PERF.md);
* *step*: 16 slots at 8,192 and at 33,280 positions through the step
  kernel handed one arena, a call's time beside the time its rows' 640 B a
  token take at the chip's bytes/s;
* *grouped*: the grouped product beside the dense composite at 512, 1,024
  and 2,048 tokens (``--grouped-tokens``: others), and at 512 with every
  token on one expert: what ``kernels/moe.py takes_grouped`` is held to.
  At each of ``--grouped-geometry`` (16 held gated experts of width 2,048
  at hidden 4,096 of 128, and 32 of width 3,072 at hidden 3,072 of 256),
  choosing 4. The function ALONE in a program under the profiler:
  ``grouped_kernel_ms`` is the ``moe_grouped`` events' device time a
  launch, ``grouped_ms`` and ``dense_ms`` the whole module's (the layout,
  and whatever XLA carries to the kernel and from it).

The chunk's and the step's times are ``--repeats`` dependent calls inside
one program, the best of three runs. A JSON line. ``--rehearse-cpu``: the
same code at a toy size through the interpreter, no times."""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: heads, nope, rope, value, latent, row lanes, block, slot length
FULL = (32, 64, 64, 128, 256, 384, 16, 33280)
TOY = (4, 8, 8, 16, 32, 128, 16, 320)
SCALE = 0.195


def _timer(fn, repeats, timed):
    """ms a call of ``fn(carry, *rest)``: ``repeats`` dependent calls inside
    one program (a call's output, scaled to nothing, is added to the next
    one's first operand), the best of three runs; None where not timed."""
    import jax

    def many(first, *rest):
        def body(_i, c):
            out = fn(c, *rest)
            return c + 0 * out.reshape(-1)[0].astype(c.dtype)
        return jax.lax.fori_loop(0, repeats, body, first)

    run = jax.jit(many)

    def ms(*at):
        if not timed:
            return None
        run(*at).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            run(*at).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return 1e3 * best / repeats

    return ms


def _arena(rng, L, bs, W, latent, rope):
    import jax.numpy as jnp

    per_slot = -(-L // bs)
    pool = per_slot + 37
    ids = rng.permutation(pool)[:per_slot]
    rows = (ids[:, None] * bs + np.arange(bs)).reshape(-1)[:L]
    body = np.zeros((pool * bs, W), np.float32)
    body[:, :latent + rope] = rng.standard_normal(
        (pool * bs, latent + rope), dtype=np.float32)
    return jnp.asarray(body, jnp.bfloat16), jnp.asarray(rows, jnp.int32)


def _chunk(args, report, geometry, timed):
    import jax
    import jax.numpy as jnp

    from benchmark.counts import mistral4
    from paddle_tpu.kernels import attention as A

    heads, nope, rope, value, latent, W, bs, L = geometry
    rng = np.random.default_rng(args.seed)
    arena, rows = _arena(rng, L, bs, W, latent, rope)
    draw = lambda std, *shape: jnp.asarray(  # noqa: E731
        std * rng.standard_normal(shape, dtype=np.float32), jnp.bfloat16)
    w_uk, w_uv = (draw(latent ** -0.5, heads, nope, latent),
                  draw(latent ** -0.5, heads, latent, value))
    out = []
    TILES = (A._LATENT_CHUNK_QUERY_ROWS, A._LATENT_CHUNK_TILE_ROWS)
    chunks = (32,) if args.rehearse_cpu else (512, 1024, 2048)
    starts = ((0, 224) if args.rehearse_cpu else (8192,) if args.quick
              else (0, 8192, 30720))
    tile = 16 if args.rehearse_cpu else A._EXPAND_TILE_ROWS
    interpret = True if args.rehearse_cpu else False
    sweep = [tuple(int(n) for n in pair.split("x"))
             for pair in args.kernel_tiles.split(",") if pair]
    for C in chunks:
        q = draw(2.0, C, heads * (nope + rope))
        fn = lambda q, span: A.latent_chunk_expanded(  # noqa: E731
            q, w_uk, w_uv, arena, rows, span, SCALE, rope, tile_rows=tile)
        kernel = lambda q, span: A.latent_chunk_attention(  # noqa: E731
            q, w_uk, w_uv, arena, rows, span, bs, SCALE, rope,
            interpret=interpret)
        timer = _timer(fn, args.repeats, timed)
        kernel_timer = _timer(kernel, args.repeats, timed)
        # the kernel at other (queries a sub-tile) x (rows a tile): each
        # traced under its own sizes, which the body reads at lowering
        swept = {}
        for qs, tr in sweep:
            A._LATENT_CHUNK_QUERY_ROWS, A._LATENT_CHUNK_TILE_ROWS = qs, tr
            try:
                at = _timer(kernel, args.repeats, timed)
                swept[f"{qs}x{tr}"] = [
                    at(q, jnp.asarray([start, C], jnp.int32))
                    for start in starts]
            finally:
                A._LATENT_CHUNK_QUERY_ROWS, A._LATENT_CHUNK_TILE_ROWS = TILES
        for n, start in enumerate(starts):
            span = jnp.asarray([start, C], jnp.int32)
            got = np.asarray(jax.jit(fn)(q, span), np.float32)
            served = np.asarray(jax.jit(kernel)(q, span), np.float32)
            dense = np.asarray(A.latent_chunk_expanded(
                q, w_uk, w_uv, arena, rows, span, SCALE, rope),
                np.float32) if args.rehearse_cpu else None
            ops, _bytes = mistral4.latent_chunk_calls(
                C * start + C * (C + 1) // 2, start, C, 1, heads, nope, rope,
                value, latent, 2)
            out.append({
                "chunk": C, "start": start, "expanded_ms": timer(q, span),
                "kernel_ms": kernel_timer(q, span),
                "kernel_ms_at": {k: v[n] for k, v in swept.items()},
                "expanded_gflop": ops / 1e9,
                "ms_at_197_TFLOPs": 1e3 * ops / 197e12,
                "kernel_from_expanded": float(
                    np.abs(served - got).max() / np.abs(got).max()),
                "from_dense": None if dense is None else float(
                    np.abs(got - dense).max() / np.abs(dense).max())})
    report["chunk"] = out


def _step(args, report, geometry, timed):
    import jax.numpy as jnp

    from paddle_tpu.kernels import attention as A

    heads, nope, rope, value, latent, W, bs, L = geometry
    S = 4 if args.rehearse_cpu else 16
    rng = np.random.default_rng(args.seed + 1)
    per_slot = -(-L // bs)
    pool = S * per_slot
    ids = rng.permutation(pool).reshape(S, per_slot)
    rows = (ids[:, :, None] * bs + np.arange(bs)).reshape(S, -1)[:, :L]
    arena = jnp.asarray(rng.standard_normal((pool * bs, W), dtype=np.float32),
                        jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((S, heads * W), dtype=np.float32),
                    jnp.bfloat16)
    interpret = True if args.rehearse_cpu else False
    fn = lambda q, rows, bias: A.paged_attention(  # noqa: E731
        q, arena, None, rows, bias, S, L, bs, SCALE, interpret=interpret,
        v_width=latent)
    ms = _timer(fn, args.repeats, timed)
    out = []
    for length in ((64, L) if args.rehearse_cpu else (8192, L)):
        bias = np.full((S, 1, L), -1e9, np.float32)
        bias[:, :, :length] = 0.0
        took = ms(q, jnp.asarray(rows.reshape(-1), jnp.int32),
                  jnp.asarray(bias))
        moved = S * length * (latent + rope) * 2
        out.append({"slots": S, "length": length, "ms": took,
                    "bytes_required": moved,
                    "ms_at_819_GBps": 1e3 * moved / 819e9})
    report["step"] = out


def _device_ms(fn, operands, repeats):
    """``(kernel_ms, whole_ms)`` a launch of ``fn(*operands)`` ALONE in a
    program: ONE profiler session over ``repeats`` launches one after the
    other; the ``moe_grouped`` events' device time a launch (None where the
    program holds none: the dense composite) and the whole module's."""
    import glob
    import shutil
    import tempfile

    import jax

    from benchmark import trace as tr

    run = jax.jit(fn)
    operands = [jax.device_put(v) for v in operands]
    jax.block_until_ready(run(*operands))
    directory = tempfile.mkdtemp(prefix="check_grouped_")
    try:
        jax.profiler.start_trace(directory)
        for _ in range(repeats):
            out = run(*operands)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            directory, "plugins", "profile", "*", "*.xplane.pb"))
        device = tr.load_xplane(path)["devices"]["0"]
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    kernel = [e[2] for e in device["ops"] if "moe_grouped" in e[0]]
    return (1e3 * sum(kernel) / repeats if kernel else None,
            1e3 * sum(e[2] for e in device["modules"]) / repeats)


def _grouped(args, report, geometry, timed):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import moe

    k = 4
    geometries = [(256, 128, 4, 32)] if args.rehearse_cpu else [
        tuple(int(n) for n in g.split("x"))
        for g in args.grouped_geometry.split(",")]
    rng = np.random.default_rng(args.seed + 2)
    draw = lambda std, *shape: jnp.asarray(  # noqa: E731
        std * rng.standard_normal(shape, dtype=np.float32), jnp.bfloat16)
    interpret = True if args.rehearse_cpu else False
    tokens = ([int(t) for t in args.grouped_tokens.split(",")]
              if args.grouped_tokens else (64,) if args.rehearse_cpu
              else (512, 2048) if args.quick else (512, 1024, 2048))
    out = []
    for H, F, E, EA in geometries:
        ws = [draw(0.02, E, F, H) for _ in range(3)]
        gate = jnp.asarray(rng.standard_normal((EA, H), dtype=np.float32))
        # (the matrices are operands: closed over they would be constants
        # of every program, 0.8 and 1.8 GB each on the host)
        grouped = lambda x, idx, w, mask, wg, wu, wd: (  # noqa: E731
            moe.moe_grouped(x, idx, w, mask, 0, wu, wd, wg,
                            interpret=interpret))
        dense = lambda x, c, wg, wu, wd: moe.experts_composite(  # noqa: E731
            x, c, wu, wd, wg)
        for T, select in [(t, None) for t in tokens] + [
                (64 if args.rehearse_cpu else 512, 3)]:
            x = draw(1.0, T, H)
            bias = np.zeros(EA, np.float32)
            if select is not None:
                bias[select] = 100.0
            idx, w = moe.route(x, gate, jnp.asarray(bias), k, 1.0, True,
                               score="softmax")
            mask = jnp.ones((T,), bool)
            c = moe.held_weights(idx, w, mask, 0, E)
            got = np.asarray(jax.jit(grouped)(x, idx, w, mask, *ws))
            want = np.asarray(jax.jit(dense)(x, c, *ws))
            pairs, rows, touched = (int(n) for n in np.asarray(
                moe.grouped_counts(idx, mask, 0, E)))
            kernel_ms, whole_ms = _device_ms(
                grouped, (x, idx, w, mask, *ws), args.repeats) if timed else (
                    None, None)
            out.append({
                "hidden": H, "width": F, "held": E, "of": EA, "tokens": T,
                "one_expert": select is not None, "pairs": pairs,
                "rows": rows, "touched": touched,
                "takes_grouped": bool(moe.takes_grouped(T, k, E, EA)),
                "grouped_kernel_ms": kernel_ms, "grouped_ms": whole_ms,
                "dense_ms": _device_ms(dense, (x, c, *ws), args.repeats)[1]
                if timed else None,
                "differ": float(np.abs(got - want).max()
                                / max(float(np.abs(want).max()), 1e-9)),
                "weights_ms_at_819_GBps":
                    1e3 * touched * 3 * H * F * 2 / 819e9})
    report["grouped"] = out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=5600000711)
    ap.add_argument("--repeats", type=int, default=8)
    ap.add_argument("--quick", action="store_true", help="the chunk behind "
                    "8,192 rows alone, the grouped product at 512 and 2,048")
    ap.add_argument("--parts", default="chunk,step,grouped")
    ap.add_argument("--grouped-tokens", default="", help="the grouped "
                    "product at these token counts, comma-separated")
    ap.add_argument("--grouped-geometry", default="4096x2048x16x128,"
                    "3072x3072x32x256", help="the grouped product's experts "
                    "as hidden x width x held x (the router's experts): "
                    "mistral_small_4_119b's and trinity_large_preview's")
    ap.add_argument("--kernel-tiles", default="", help="the chunk kernel "
                    "also at these (queries a sub-tile)x(rows a tile), e.g. "
                    "512x512,256x256: a tuning sweep")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    geometry, timed = (TOY, False) if args.rehearse_cpu else (FULL, True)
    report = {"seed": args.seed, "device": jax.devices()[0].device_kind}
    parts = {"chunk": _chunk, "step": _step, "grouped": _grouped}
    for name in args.parts.split(","):
        parts[name](args, report, geometry, timed)
        print(json.dumps({k: report[k] for k in list(report)[-1:]}),
              flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "latent_attention.json"),
              "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
