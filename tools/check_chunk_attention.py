"""``kernels/attention.py chunk_attention`` COMPILED, on the device it is
given, against the composite it replaces (``chunk_attention_by_span``): the
reading no interpreter and no chip-free compile gives.

    python3 tools/check_chunk_attention.py [--seed <n>] [--score-std 4]
        [--repeats 24] [--skip-times] [--rehearse-cpu]

*Parity*, at ``granite_4_0_h_micro``'s geometry (512 queries over a slot of
16,896 rows, 8 K/V heads of 64 with 4 query heads each, block 16, bfloat16,
scores x 1/64) under a SHUFFLED block table, with q and k drawn so that the
scaled scores have the standard deviation ``--score-std`` (peaked: a
uniform softmax returns the mean of V whatever K or the mask's edge hold):
the chunk at the prompt's start, behind thousands of rows with a ragged
count of real positions, and at the slot's end. Blocks past a chunk's last
live one hold NaN, so a kernel that read past them would say so. Each
reading is the largest error over the output's largest value against the
composite in float32 at ``Precision.HIGHEST`` on the same bfloat16 values,
beside what XLA's own bfloat16 composite reads against it.

*Times*, kernel beside composite (``CHUNK_KERNEL_MIN_WORK`` set to 0 for
the former, the composite called by name for the latter), a call's device
time from ``--repeats`` dependent calls inside one program: Granite's chunk
behind 0, 4,096 and 16,384 rows, and the accepted cells' short geometries
(the threshold's side of the cut). A JSON line each. ``--rehearse-cpu``: the
same code at a toy size through the interpreter, no times."""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: (name, C, L, block, K/V heads, query heads each, head, block_len)
GRANITE = ("granite_4_0_h_micro", 512, 16896, 16, 8, 4, 64, 1)
SPANS = ((0, 512), (4096, 512), (7680, 301), (12288, 512), (16384, 512))
REHEARSAL = ("rehearsal", 32, 320, 16, 8, 4, 64, 1)
REHEARSAL_SPANS = ((0, 32), (64, 32), (224, 19), (288, 32))
SHORT = [
    ("lfm2_24b_a2b", 128, 2048, 16, 8, 4, 64, 1),
    ("nemotron3_nano_30b_a3b", 128, 2048, 16, 2, 16, 128, 1),
    ("sdar_30b_a3b", 128, 1024, 16, 4, 8, 128, 4),
    ("ouro_2_6b", 256, 1024, 16, 16, 1, 128, 1),
]


def _case(rng, C, L, bs, G, per, D, q_std, k_std):
    import jax.numpy as jnp

    per_slot = -(-L // bs)
    pool = per_slot + 37
    ids = rng.permutation(pool)[:per_slot]
    rows = (ids[:, None] * bs + np.arange(bs)).reshape(-1)[:L]
    draw = lambda std, *shape: jnp.asarray(  # noqa: E731
        std * rng.standard_normal(shape, dtype=np.float32), jnp.bfloat16)
    return (draw(q_std, C, G * per * D), draw(k_std, pool * bs, G * D),
            draw(1.0, pool * bs, G * D), jnp.asarray(rows, jnp.int32))


def _parity(args, report):
    import jax
    import jax.numpy as jnp

    from paddle_tpu import kernels
    from paddle_tpu.kernels import attention as A

    geometry, spans = ((REHEARSAL, REHEARSAL_SPANS) if args.rehearse_cpu
                       else (GRANITE, SPANS))
    _name, C, L, bs, G, per, D, block_len = geometry
    sm = 1.0 / 64.0
    # scores x sm have the standard deviation q_std x k_std x sqrt(D) x sm
    each = float(np.sqrt(args.score_std / (np.sqrt(D) * sm)))
    rng = np.random.default_rng(args.seed)
    q, k, v, rows = _case(rng, C, L, bs, G, per, D, each, each)
    kernel = jax.jit(lambda *a: A.chunk_attention(
        *a, bs, sm, G, block_len=block_len, interpret=args.rehearse_cpu))
    plain = jax.jit(lambda *a: A.chunk_attention_by_span(
        *a, sm, G, block_len))

    def exact(q, k, v, rows, span):
        with jax.default_matmul_precision("highest"):
            return A.chunk_attention_by_span(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), rows, span, sm, G, block_len)

    exact = jax.jit(exact)
    before = kernels.fallback_counter().value
    readings = []
    for start, real in spans:
        span = jnp.asarray([start, real], jnp.int32)
        live = np.zeros(k.shape[0], bool)
        live[np.asarray(rows)[:-(-(start + real) // bs) * bs]] = True
        # the composite reads every row of the slot (masked): it gets zeros
        # past the last live block, the kernel NaN
        clean = lambda t: jnp.where(live[:, None], t, 0).astype(t.dtype)  # noqa: E731
        dirty = lambda t: jnp.where(live[:, None], t, jnp.nan).astype(  # noqa: E731
            t.dtype)
        want = np.asarray(exact(q, clean(k), clean(v), rows, span),
                          np.float32)[:real]
        got = np.asarray(kernel(q, dirty(k), dirty(v), rows, span),
                         np.float32)
        xla = np.asarray(plain(q, clean(k), clean(v), rows, span),
                         np.float32)[:real]
        top = float(np.abs(want).max())
        readings.append({
            "start": start, "real": real,
            "kernel_error": float(np.abs(got[:real] - want).max() / top),
            "xla_bf16_error": float(np.abs(xla - want).max() / top),
            "finite": bool(np.isfinite(got).all()),
            "past_real_zero": bool(not got[real:].any()),
            "output_rms": float(np.sqrt((want ** 2).mean()))})
    report["parity"] = {
        "geometry": geometry[1:], "score_std": args.score_std,
        "q_std": each, "fallbacks": kernels.fallback_counter().value - before,
        "readings": readings}


def _times(args, report):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import attention as A

    rng = np.random.default_rng(args.seed + 1)
    A.CHUNK_KERNEL_MIN_WORK = 0
    R = args.repeats

    def timer(fn):
        """ms a call of ``fn`` at a span: ``R`` dependent calls inside ONE
        program (a call's output is the next one's queries), compiled once
        a geometry, the best of three runs."""
        def many(q, k, v, rows, span):
            body = lambda _i, c: fn(c, k, v, rows, span).astype(c.dtype)  # noqa: E731
            return jax.lax.fori_loop(0, R, body, q)

        run = jax.jit(many)

        def ms(*at):
            run(*at).block_until_ready()
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                run(*at).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            return 1e3 * best / R

        return ms

    out = []
    cases = [(GRANITE, (0, 4096, 16384))] + [
        (g, (g[2] // 2, g[2] - g[1])) for g in SHORT]
    for (name, C, L, bs, G, per, D, block_len), starts in cases:
        sm = 1.0 / float(np.sqrt(D))
        q, k, v, rows = _case(rng, C, L, bs, G, per, D, 1.0, 1.0)
        kernel = timer(lambda *a: A.chunk_attention(
            *a, bs, sm, G, block_len=block_len))
        plain = timer(lambda *a: A.chunk_attention_by_span(
            *a, sm, G, block_len))
        for start in starts:
            span = jnp.asarray([start, C], jnp.int32)
            out.append({"geometry": name, "chunk": C, "length": L,
                        "start": start,
                        "kernel_ms": kernel(q, k, v, rows, span),
                        "composite_ms": plain(q, k, v, rows, span)})
    report["times"] = out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=5100000901)
    ap.add_argument("--score-std", type=float, default=4.0)
    ap.add_argument("--repeats", type=int, default=24)
    ap.add_argument("--skip-times", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    report = {"seed": args.seed, "device": jax.devices()[0].device_kind}
    _parity(args, report)
    print(json.dumps(report), flush=True)
    if not (args.skip_times or args.rehearse_cpu):
        times = {"seed": args.seed}
        _times(args, times)
        print(json.dumps(times), flush=True)


if __name__ == "__main__":
    main()
