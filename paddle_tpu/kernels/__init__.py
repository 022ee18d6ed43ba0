"""paddle_tpu.kernels — the Pallas kernel registry subsystem.

Maps op/composite patterns to optional Pallas TPU kernels with the
XLA-composite lowering as the mandatory fallback (see registry.py for
the selection/mode/fingerprint contract). The built-in kernel set:

==================== ============================== ========= =========
kernel               serves                          parity    activation
==================== ============================== ========= =========
flash_attention      scaled_dot_product_attention    tolerance mode
cached_attention     cached_attention (decode [S,1]) bit       mode
paged_attention      paged_attention, paged_latent_attention (decode [S,1]; one latent arena or K and V) tolerance mode
chunk_paged_attention chunk_paged_attention ([C] of one slot) tolerance mode
latent_chunk_attention chunk_latent_attention ([C] of one slot over ONE latent arena, expanded; fallback: the same form by XLA's loops) tolerance mode
index_select         sparse_index_select (an indexer's scores and exact top-k rows, a step's slots or a chunk's queries) tolerance mode
masked_chunk_attention chunk_paged_attention with a Mask ([C] of one slot under the selection) tolerance mode
moe_experts          moe_routed_experts (decode)     tolerance mode
moe_grouped          moe_routed_experts (a prompt chunk's pairs, by expert) tolerance mode
ssm_update           mamba2_mixer (decode [S,1]; a grid step a stepping slot's heads, as many as VMEM takes) tolerance mode
ssm_scan             mamba2_mixer (a prompt chunk)   tolerance mode
remat_policy         recompute_segment[_grad]        bit       IR attr (policy kind)
==================== ============================== ========= =========

Every entry registers a ``parity_check`` — tests/test_kernels.py
parametrizes over ``all_specs()`` and runs them all, so this table IS
the CI gate (a kernel without a parity test cannot register) — and every
``kind="kernel"`` entry must AOT-compile for the v5e
(tests/test_kernels_tpu_aot.py): a kernel that interprets but does not
lower for the chip cannot stay registered.
"""

import functools

import numpy as np

from paddle_tpu.kernels import registry as _r
from paddle_tpu.kernels.registry import (  # noqa: F401
    FLASH_KERNELS, MODE_ENV, KernelSpec, all_specs, fallback_counter,
    flash_grid_snapshot, get, has, kernel_sig, mode, probe, register,
    registry_fingerprint, resolved_mode, scoped_mode, selected,
)

__all__ = [
    "MODE_ENV", "KernelSpec", "all_specs", "get", "has", "kernel_sig",
    "mode", "probe", "register", "registry_fingerprint", "resolved_mode",
    "scoped_mode", "selected", "selected_for", "fallback_counter",
    "fallback_internal_bytes", "flash_grid_snapshot", "FLASH_KERNELS",
]


def selected_for(op_type, attrs):
    """``selected(op_type)`` for one op as it is written: the paged kernel
    walks a block table it derives from ``Rows``, so it serves only an op
    that names its ``block_size`` (``[R, H]`` does not carry it; a program
    serialized before the attribute existed runs the composite). The
    lowering and ``analysis/memory.py`` both ask here."""
    if (op_type in ("paged_attention", "chunk_paged_attention")
            and not attrs.get("block_size")):
        return None
    return selected(op_type)


def fallback_internal_bytes(op_type, attrs, shape_of, itemsize=4):
    """HBM bytes the COMPOSITE lowering of a fused attention op
    materializes that a kernel keeps in VMEM — what
    ``analysis/memory.py`` adds to the peak estimate when no kernel
    serves the op. ``shape_of(slot)`` resolves an input slot's static
    shape (None when unknown)."""
    if op_type == "paged_attention":
        q = shape_of("Q")
        if q is None:
            return 0
        s, l = int(attrs["seqs"]), int(attrs["length"])
        h = int(q[-1])
        # two dense [S, L, H] gathered views + scores + att [S, 1, L]
        return (2 * s * l * h + 2 * s * l) * itemsize
    if op_type == "cached_attention":
        # K/V are already inputs; only scores + att [S, 1, L] materialize
        k = shape_of("KCache")
        if k is None:
            return 0
        return 2 * int(k[0]) * int(k[1]) * itemsize
    return 0


# ---------------------------------------------------------------------------
# built-in kernel registrations (parity checks import lazily — they run
# inside the test gate, not at import)
# ---------------------------------------------------------------------------


def _assert_bytes_equal(got, ref, what):
    got, ref = np.asarray(got), np.asarray(ref)
    assert got.dtype == ref.dtype and got.shape == ref.shape, \
        f"{what}: {got.dtype}{got.shape} vs {ref.dtype}{ref.shape}"
    assert got.tobytes() == ref.tobytes(), \
        f"{what}: kernel output not BIT-identical to composite " \
        f"(max abs diff {np.abs(got - ref).max()})"


def _assert_close_both_ways(a, b, what, rtol, atol):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                               atol=atol, err_msg=f"{what} (a vs b)")
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=rtol,
                               atol=atol, err_msg=f"{what} (b vs a)")


def _parity_flash(rng):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    B, H, S, D = 2, 2, 32, 8
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
               for _ in range(3))
    bias = jnp.asarray(
        np.where(rng.rand(B, S) > 0.25, 0, -1e9).astype("float32"))
    got = flash_attention(q, k, v, bias=bias, causal=True, interpret=True,
                          block_q=16, block_k=8)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    s = s + bias[:, None, None, :]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    _assert_close_both_ways(got, ref, "flash_attention", 1e-5, 1e-5)


def _tpu_cases_flash():
    """BERT-base heads at s128 in bf16 with the padding bias: the training
    cells' FULL per-chip geometry (batch 256: the heads a grid step serves,
    and so the kernel body, come from B*H), a cut batch (the train leg of
    chip_smoke.py), an odd B*H (each kernel takes another divisor of 21),
    and a causal multi-block length (models/gpt_ir.py)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def attend(causal):
        def fwd(q, k, v, bias):
            return flash_attention(q, k, v, bias=bias, causal=causal,
                                   interpret=False)
        return fwd

    def grads(fwd):
        return jax.grad(
            lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)),
            argnums=(0, 1, 2, 3))

    cases = []
    for label, causal, (b, h, s, d) in (
            ("bert_s128", False, (8, 12, 128, 64)),
            ("bert_s128_b256", False, (256, 12, 128, 64)),
            ("bert_s128_odd", False, (7, 3, 128, 64)),
            ("causal_s512", True, (2, 8, 512, 128))):
        args = [((b, h, s, d), "bfloat16")] * 3 + [((b, s), "float32")]
        cases.append((label + "_fwd", attend(causal), args))
        cases.append((label + "_grad", grads(attend(causal)), args))
    return cases


def _tpu_cases_cached():
    """Serving width (hidden 1024, 8 slots) at the longest cache the
    VMEM gate admits there: 2 x 8 x 128 x 1024 f32 = 8 MiB of K/V."""
    from paddle_tpu.kernels import attention as A

    S, L, H = 8, 128, 1024

    def fwd(q, k, v, bias):
        return A.decode_attention(q, k, v, bias, 1.0 / 32.0,
                                  interpret=False)

    return [("s8_l128_h1024", fwd, [
        ((S, H), "float32"), ((S, L, H), "float32"),
        ((S, L, H), "float32"), ((S, 1, L), "float32")])]


def _parity_cached(rng):
    """Kernel-interpret vs composite UNDER JIT on both sides: every real
    execution path lowers through one jit (core/lowering.py), and the
    bit contract holds for the lowered computation — eager dispatch
    fuses differently and is not a path any program takes."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import attention as A

    S, L, H = 4, 16, 8
    q = jnp.asarray(rng.randn(S, H).astype("float32"))
    k = jnp.asarray(rng.randn(S, L, H).astype("float32"))
    v = jnp.asarray(rng.randn(S, L, H).astype("float32"))
    cur = rng.randint(1, L, S)
    bias = np.where(np.arange(L)[None, :] < cur[:, None], 0.0, -1e9)
    bias = jnp.asarray(bias.astype("float32").reshape(S, 1, L))
    sm = 1.0 / float(np.sqrt(H))
    got = jax.jit(lambda *a: A.decode_attention(*a, sm, interpret=True))(
        q, k, v, bias)
    ref = jax.jit(lambda *a: A.cached_attention_composite(*a, sm))(
        q, k, v, bias)
    _assert_bytes_equal(got, ref, "cached_attention")


def _paged_case(rng, seqs, length, block, hidden, lengths, share=(),
                shuffle=True):
    """A paged-attention problem as the engine feeds it: a pool of
    ``seqs * ceil(length / block)`` blocks, block-aligned row maps (zero
    past a slot's live blocks), prefix-opening biases. ``share`` lists
    ``(slot, other, blocks)``: ``slot`` reads ``other``'s first blocks."""
    per = -(-length // block)
    pool = seqs * per
    ids = (rng.permutation(pool) if shuffle else np.arange(pool))
    ids = ids.reshape(seqs, per)
    for slot, other, n in share:
        ids[slot, :n] = ids[other, :n]
    rows = np.zeros((seqs, length), "int64")
    bias = np.full((seqs, 1, length), -1e9, "float32")
    for s, n in enumerate(lengths):
        for i in range(-(-n // block)):
            lo, hi = i * block, min((i + 1) * block, length)
            rows[s, lo:hi] = ids[s, i] * block + np.arange(hi - lo)
        bias[s, 0, :n] = 0.0
    draw = lambda *shape: rng.randn(*shape).astype("float32")
    return (draw(seqs, hidden), draw(pool * block, hidden),
            draw(pool * block, hidden), rows.reshape(-1), bias)


def _paged_both(args, seqs, length, block, hidden, kv_heads=0):
    """(kernel through the interpreter, composite) on one ``_paged_case``,
    under jit on both sides, as numpy. With ``kv_heads`` the rows hold that
    many heads side by side and ``args``' query is ``[S, heads * D]``."""
    import jax

    from paddle_tpu.kernels import attention as A

    sm = 1.0 / float(np.sqrt(hidden // (kv_heads or 1)))
    got = jax.jit(lambda *a: A.paged_attention(
        *a, seqs, length, block, sm, interpret=True,
        kv_heads=kv_heads))(*args)
    ref = jax.jit(lambda *a: A.paged_attention_composite(
        *a, seqs, length, sm, kv_heads=kv_heads))(*args)
    return np.asarray(got), np.asarray(ref)


def _assert_paged_parity(args, lengths, what, *geometry, **kw):
    """Live slots within 1e-5 both ways of the composite; a free slot's
    bias is all -1e9, so the composite averages garbage rows there (not
    compared) and the kernel reads nothing and writes zeros."""
    got, ref = _paged_both(args, *geometry, **kw)
    live = np.asarray(lengths) > 0
    _assert_close_both_ways(got[live], ref[live], what, 1e-5, 1e-5)
    assert not got[~live].any()


def _parity_paged(rng):
    """Ragged lengths in one batch (a free slot among them), two slots
    sharing prefix blocks, the block table out of order; then what the
    copy pipeline hands from slot to slot: the first slot free, live and
    free slots alternating, the last slot the only live one, all free;
    tests/test_kernels.py holds more geometries. Then the head axis."""
    S, L, bs, H = 6, 64, 16, 128
    lengths = [1, 15, 16, 17, 64, 0]
    args = _paged_case(rng, S, L, bs, H, lengths, share=[(3, 4, 1)])
    _assert_paged_parity(args, lengths, "paged_attention", S, L, bs, H)
    for lengths in ([0, 33, 64, 1, 0, 20], [7, 0, 64, 0, 17, 0],
                    [0, 0, 0, 0, 0, 40], [0] * 6):
        args = _paged_case(rng, S, L, bs, H, lengths)
        _assert_paged_parity(args, lengths, f"paged_attention {lengths}",
                             S, L, bs, H)
    _parity_paged_grouped(rng)
    _parity_paged_latent(rng)


def _parity_paged_latent(rng):
    """ONE arena (a latent cache: a row is its token's key and, its first
    lanes, its value), 8 query heads over rows of 384 lanes with values of
    256, then 4 over 128 with values of 32: lengths a block, a tile and a
    slot long and one off them, a free slot, then slots around a copy
    unit."""
    import jax

    from paddle_tpu.kernels import attention as A

    for heads, W, VW, L, lengths in (
            (8, 384, 256, 64, [1, 15, 17, 64, 0]),
            (4, 128, 32, 64, [16, 0, 33, 64, 1]),
            (8, 384, 256, 1280, [639, 0, 640, 641, 1280])):
        S, bs = len(lengths), 16
        _q, arena, _v, rows, bias = _paged_case(rng, S, L, bs, W, lengths)
        q = rng.randn(S, heads * W).astype("float32")
        got = np.asarray(jax.jit(lambda *a: A.paged_attention(
            a[0], a[1], None, a[2], a[3], S, L, bs, 0.1, interpret=True,
            v_width=VW))(q, arena, rows, bias))
        ref = np.asarray(jax.jit(lambda *a: A.paged_attention_composite(
            a[0], a[1], None, a[2], a[3], S, L, 0.1, v_width=VW))(
                q, arena, rows, bias))
        live = np.asarray(lengths) > 0
        assert got.shape == (S, heads * VW)
        _assert_close_both_ways(got[live], ref[live],
                                "paged_attention (latent)", 1e-5, 1e-5)
        assert not got[~live].any()


def _tpu_cases_paged():
    """The serving cell's geometry (decoder_1024x24: 48 slots x 1,024
    positions, block 16, 49,152 arena rows, hidden 1,024, float32)."""
    from paddle_tpu.kernels import attention as A

    S, L, bs, H = 48, 1024, 16, 1024
    R = S * L

    def fwd(q, k, v, rows, bias):
        return A.paged_attention(q, k, v, rows, bias, S, L, bs, 1.0 / 32.0,
                                 interpret=False)

    return [("s48_l1024_b16_h1024", fwd, [
        ((S, H), "float32"), ((R, H), "float32"), ((R, H), "float32"),
        ((S * L,), "int32"), ((S, 1, L), "float32")])
            ] + _tpu_cases_paged_grouped()


def _parity_paged_grouped(rng):
    """The head axis: 2 K/V heads of 128 a row, 4 query heads to each; then
    8 heads of 64, two to a lane tile; then 16 heads of 128 with ONE query
    head each (one real query row in a tile); then 4 heads of 128 with 32
    query rows each (a block pass: the 8 query heads of a K/V head at each
    of a block's 4 positions, which all see the same rows). Each at lengths
    a block, a tile and a slot long and one off them, then with slots as
    long as the geometry's copy unit (512, 256, 128 and 256 rows in
    float32) and one off it, a free slot between them."""
    for G, per, D, unit_rows in ((2, 4, 128, 512), (8, 4, 64, 256),
                                 (16, 1, 128, 128), (4, 32, 128, 256)):
        _parity_paged_heads(rng, G, per, D, 64, [1, 15, 17, 64, 0])
        _parity_paged_unit_edges(rng, G, per, D, unit_rows)


def _paged_heads_case(rng, G, per, D, L, lengths, bs=16):
    """``_paged_case`` with rows of ``G`` heads of ``D`` and ``per`` query
    heads to each."""
    S = len(lengths)
    _q, k, v, rows, bias = _paged_case(rng, S, L, bs, G * D, lengths)
    q = rng.randn(S, G * per * D).astype("float32")
    return (q, k, v, rows, bias), (S, L, bs, G * D)


def _parity_paged_heads(rng, G, per, D, L, lengths):
    args, geometry = _paged_heads_case(rng, G, per, D, L, lengths)
    _assert_paged_parity(args, lengths, "paged_attention (grouped)",
                         *geometry, kv_heads=G)


def _parity_paged_unit_edges(rng, G, per, D, unit_rows):
    """Slots one under, at and one over a copy unit of ``unit_rows`` rows,
    a free slot between them, then two units."""
    _parity_paged_heads(rng, G, per, D, 2 * unit_rows, [
        unit_rows - 1, 0, unit_rows, unit_rows + 1, 2 * unit_rows])


def _tpu_cases_paged_grouped():
    """The hybrid serving cells' geometries in bfloat16 at block 16 and
    2,048 positions: nemotron3_nano_30b_a3b (32 slots, rows of 2 K/V heads
    of 128, 16 query heads to each) and lfm2_24b_a2b (128 slots, rows of 8
    K/V heads of 64, 4 query heads to each: two heads a lane tile); and
    ouro_2_6b at 1,024 positions (16 slots, rows of 16 K/V heads of 128
    with ONE query head each: one real query row in a 16-row tile) and
    sdar_30b_a3b (32 slots, rows of 4 K/V heads of 128 with 32 query rows
    each: a block of 4 positions x 8 query heads); and granite_4_0_h_micro
    at 16,896 positions (32 slots of 1,056 blocks, lfm2's heads)."""
    from paddle_tpu.kernels import attention as A

    def case(S, L, bs, G, per, D):
        R = S * L

        def fwd(q, k, v, rows, bias):
            return A.paged_attention(q, k, v, rows, bias, S, L, bs,
                                     1.0 / float(np.sqrt(D)), kv_heads=G)

        return (f"s{S}_l{L}_b{bs}_g{G}x{per}x{D}_bf16", fwd, [
            ((S, G * per * D), "bfloat16"), ((R, G * D), "bfloat16"),
            ((R, G * D), "bfloat16"), ((S * L,), "int32"),
            ((S, 1, L), "float32")])

    def latent(S, L, bs, heads, W, VW):
        """mistral_small_4_119b: 16 slots of 33,280 positions over ONE
        arena of 384-lane rows, 32 absorbed query heads, values 256."""
        R = 4 * L

        def fwd(q, arena, rows, bias):
            return A.paged_attention(q, arena, None, rows, bias, S, L, bs,
                                     0.195, v_width=VW)

        return (f"s{S}_l{L}_b{bs}_latent{heads}x{W}v{VW}_bf16", fwd, [
            ((S, heads * W), "bfloat16"), ((R, W), "bfloat16"),
            ((S * L,), "int32"), ((S, 1, L), "float32")])

    return [case(32, 2048, 16, 2, 16, 128), case(128, 2048, 16, 8, 4, 64),
            case(16, 1024, 16, 16, 1, 128), case(32, 1024, 16, 4, 32, 128),
            case(32, 16896, 16, 8, 4, 64), latent(16, 33280, 16, 32, 384, 256)]


def _chunk_case(rng, C, L, bs, G, per, D, dtype="float32"):
    """One slot's arenas under a shuffled block table (more blocks in the
    pool than the slot holds) and a chunk's queries: ``(q, k, v, rows)``."""
    per_slot = -(-L // bs)
    pool = per_slot + 3
    ids = rng.permutation(pool)[:per_slot]
    rows = (ids[:, None] * bs + np.arange(bs)).reshape(-1)[:L]
    draw = lambda *shape: rng.randn(*shape).astype(dtype)
    return (draw(C, G * per * D), draw(pool * bs, G * D),
            draw(pool * bs, G * D), rows.astype("int64"))


@functools.lru_cache(maxsize=None)
def _chunk_both(bs, G, D, block_len, window=0):
    """(kernel through the interpreter, composite under the rule's bias),
    jitted once a geometry: the chunk's span is an argument."""
    import jax

    from paddle_tpu.kernels import attention as A

    sm = 1.0 / float(np.sqrt(D))
    return (jax.jit(lambda *a: A.chunk_attention(
                *a, bs, sm, G, block_len=block_len, interpret=True,
                window=window)),
            jax.jit(lambda *a: A.chunk_attention_by_span(
                *a, sm, G, block_len, window)))


def _assert_chunk_parity(args, start, real, bs, G, D, block_len=1, window=0):
    """The real queries within 2e-5 both ways of the composite under the
    rule's bias; a query past them reads nothing and gives zeros."""
    kernel, composite = _chunk_both(bs, G, D, block_len, window)
    span = np.array([start, real], "int32")
    got = np.asarray(kernel(*args, span))
    ref = np.asarray(composite(*args, span))
    _assert_close_both_ways(got[:real], ref[:real],
                            f"chunk_attention {start}+{real}", 2e-5, 2e-5)
    assert not got[real:].any()


def _parity_chunk_attention(rng):
    """The step kernel's four head geometries, each with the chunk at the
    prompt's start and behind rows, full and short, ending one under, at
    and one over a block and a copy tile (256 rows), under a shuffled block
    table; then the block mask (``block_len`` 4)."""
    for G, per, D in ((2, 4, 128), (8, 4, 64), (16, 1, 128), (4, 8, 128)):
        args = _chunk_case(rng, 32, 320, 16, G, per, D)
        for start, real in ((0, 32), (0, 7), (32, 17), (224, 31), (224, 32),
                            (240, 17), (288, 32)):
            _assert_chunk_parity(args, start, real, 16, G, D)
    args = _chunk_case(rng, 32, 320, 16, 4, 8, 128)
    for start, real in ((0, 28), (32, 32), (252, 8)):
        _assert_chunk_parity(args, start, real, 16, 4, 128, block_len=4)
    # under a window (trinity_large_preview's 8 x 6 x 128): the edge inside
    # the chunk, a block and a copy tile behind it, and past everything
    args = _chunk_case(rng, 32, 320, 16, 8, 6, 128)
    for start, real, window in ((0, 32, 20), (224, 31, 48), (288, 32, 272),
                                (288, 17, 1000)):
        _assert_chunk_parity(args, start, real, 16, 8, 128, window=window)


def _tpu_cases_chunk_attention():
    """granite_4_0_h_micro's chunk (512 queries over up to 16,896 rows of
    8 K/V heads of 64, 4 query heads to each, block 16, bfloat16), and the
    accepted hybrid cells' head geometries at a length the kernel serves
    (a chunk of 512 over 4,096 rows): nemotron3_nano_30b_a3b (2 x 16 x
    128), ouro_2_6b (16 x 1 x 128) and sdar_30b_a3b (4 x 8 x 128 under its
    block mask)."""
    from paddle_tpu.kernels import attention as A

    def case(C, L, bs, G, per, D, block_len=1, window=0):
        R = 4 * L

        def fwd(q, k, v, rows, span):
            return A.chunk_attention(q, k, v, rows, span, bs,
                                     1.0 / float(np.sqrt(D)), G,
                                     block_len=block_len, window=window)

        return (f"c{C}_l{L}_b{bs}_g{G}x{per}x{D}_m{block_len}"
                + (f"_w{window}" if window else "") + "_bf16", fwd, [
            ((C, G * per * D), "bfloat16"), ((R, G * D), "bfloat16"),
            ((R, G * D), "bfloat16"), ((L,), "int32"), ((2,), "int32")])

    # trinity_large_preview (8 x 6 x 128, a chunk of 1,024): the full
    # layer over 33,792 rows, a sliding one over the 321 blocks its window
    # group's row map holds, under the window
    return [case(512, 16896, 16, 8, 4, 64), case(512, 4096, 16, 2, 16, 128),
            case(512, 4096, 16, 16, 1, 128),
            case(512, 4096, 16, 4, 8, 128, block_len=4),
            case(1024, 33792, 16, 8, 6, 128),
            case(1024, 5136, 16, 8, 6, 128, window=4096)]


def _sparse_case(rng, G, Q, L, bs, heads, width, dtype="float32"):
    """``G`` sequences' index arena under shuffled block tables and ``G x
    Q`` index queries with their weights: ``(q, w, arena, rows)``."""
    per_slot = -(-L // bs)
    pool = G * per_slot + 3
    ids = rng.permutation(pool)[:G * per_slot].reshape(G, per_slot)
    rows = (ids[:, :, None] * bs + np.arange(bs)).reshape(G, -1)[:, :L]
    draw = lambda *shape: rng.randn(*shape).astype(dtype)
    return (draw(G * Q, heads * width), draw(G * Q, heads),
            draw(pool * bs, width), rows.reshape(-1).astype("int64"))


def _parity_index_select(rng):
    """The selection's set is ``lax.top_k``'s, ties and exact zeros
    included, whatever the horizon (none, fewer than ``topk``, more); the
    scores kernel is within 2e-5 of its composite for a step's slots and
    for a chunk's queries, at a horizon one under, at and one over a block
    and a copy tile."""
    import jax

    from paddle_tpu.kernels import sparse as S

    for n, l, topk in ((16, 2048, 64), (32, 1024, 200), (8, 1024, 1)):
        scores = rng.randn(n, l).astype("float32")
        # ties: whole runs of one value, and the relu's exact zeros (of
        # both signs) across the threshold
        scores[:, ::3] = np.round(scores[:, ::3])
        scores[:, 1::5] = np.where(rng.rand(n, len(scores[0, 1::5])) < .5,
                                   0.0, -0.0)
        horizon = rng.randint(0, l + 1, n).astype("int32")
        horizon[:4] = (0, 1, topk, l)
        want = _top_k_mask(scores, horizon, topk)
        # (top_k orders -0.0 under 0.0; the selection takes them as equal)
        _vals, idx = jax.lax.top_k(
            _valid_scores(scores + np.float32(0.0), horizon), topk)
        for r in range(n):
            k = min(topk, int(horizon[r]))
            assert sorted(np.asarray(idx[r][:k])) == sorted(
                np.flatnonzero(want[r])), "the test's own set is top_k's"
        got = np.asarray(jax.jit(lambda s, h: S.index_select(
            s, h, topk, interpret=True))(scores, horizon))
        ref = np.asarray(jax.jit(lambda s, h: S.index_select_composite(
            s, h, topk))(scores, horizon))
        assert (got != 0).tolist() == want.tolist(), f"kernel set {n}x{l}"
        assert ref.tolist() == want.tolist(), f"composite set {n}x{l}"
    for G, Q, horizons in ((4, 1, (0, 15, 528, 1040)),
                           (1, 16, tuple(range(505, 521)))):
        args = _sparse_case(rng, G, Q, 1040, 16, 4, 128)
        hz = np.array(horizons, "int32")
        got = np.asarray(jax.jit(lambda *a: S.index_scores(
            *a, G, 16, hz, interpret=True))(*args))
        ref = np.asarray(jax.jit(lambda *a: S.index_scores_composite(
            *a, G))(*args))
        for r, h in enumerate(horizons):
            _assert_close_both_ways(got[r, :h], ref[r, :h],
                                    f"index_scores {G}x{Q} row {r}", 2e-5,
                                    2e-5)


def _top_k_mask(scores, horizon, topk):
    """``lax.top_k``'s set as a bool mask, in numpy, for the tests: of the
    positions under a row's horizon the ``min(topk, horizon)`` of largest
    score, a tie to the lower position."""
    scores = np.asarray(scores, np.float32)
    out = np.zeros(scores.shape, bool)
    for r, hz in enumerate(np.asarray(horizon)):
        hz = int(hz)
        order = np.lexsort((np.arange(hz), -scores[r, :hz]))
        out[r, order[:min(int(topk), hz)]] = True
    return out


def _valid_scores(scores, horizon):
    return np.where(np.arange(scores.shape[1])[None] < horizon[:, None],
                    scores, -np.inf).astype("float32")


def _tpu_cases_index_select():
    """keye_vl_2_0_30b_a3b's step (16 slots of 32,768 positions, 16 index
    heads over one 128-lane key, block 16, bfloat16) and chunk (1,024
    queries): the scores, then the top 2,048."""
    from paddle_tpu.kernels import sparse as S

    def scores(G, Q, L, bs=16, heads=16, width=128):
        def fwd(q, w, arena, rows, hz):
            return S.index_scores(q, w, arena, rows, G, bs, hz)

        return (f"scores_g{G}_q{Q}_l{L}_h{heads}x{width}_bf16", fwd, [
            ((G * Q, heads * width), "bfloat16"), ((G * Q, heads), "float32"),
            ((4 * L, width), "bfloat16"), ((G * L,), "int32"),
            ((G * Q,), "int32")])

    def select(N, L, topk=2048):
        def fwd(s, hz):
            return S.index_select(s, hz, topk)

        return (f"select_n{N}_l{L}_k{topk}", fwd,
                [((N, L), "float32"), ((N,), "int32")])

    return [scores(16, 1, 32768), scores(1, 1024, 32768),
            select(16, 32768), select(1024, 32768)]


def _parity_masked_chunk(rng):
    """The masked chunk kernel within 2e-5 both ways of the composite under
    the mask's bias, the chunk at the prompt's start and behind rows, under
    random masks inside the causal horizon; a query that keeps nothing
    gives zeros."""
    import jax

    from paddle_tpu.kernels import attention as A
    from paddle_tpu.kernels import sparse as S

    G, per, D, bs, C, L = 4, 8, 128, 16, 32, 320
    args = _chunk_case(rng, C, L, bs, G, per, D)
    sm = 1.0 / float(np.sqrt(D))
    kernel = jax.jit(lambda q, k, v, rows, span, mask: S.masked_chunk_attention(
        q, k, v, rows, span, mask, bs, sm, G, interpret=True))
    composite = jax.jit(lambda q, k, v, rows, mask: S.masked_chunk_composite(
        q, k, v, rows, mask, sm, G))
    for start, real in ((0, 32), (0, 7), (224, 31), (240, 17), (288, 32)):
        span = np.array([start, real], "int32")
        horizon = np.asarray(A.chunk_horizon(span, C, L))
        mask = ((rng.rand(C, L) < 0.3)
                & (np.arange(L)[None] < horizon[:, None])).astype("int8")
        mask[: real, 0] = 1          # every real query keeps a row
        got = np.asarray(kernel(*args, span, mask))
        ref = np.asarray(composite(*args, mask))
        _assert_close_both_ways(got[:real], ref[:real],
                                f"masked_chunk {start}+{real}", 2e-5, 2e-5)
        assert not got[real:].any()


def _tpu_cases_masked_chunk():
    """keye_vl_2_0_30b_a3b's chunk: 1,024 queries of 32 heads of 128 over
    up to 32,768 rows of 4 K/V heads, block 16, bfloat16, under the
    selection's int8 mask."""
    from paddle_tpu.kernels import sparse as S

    def case(C, L, bs, G, per, D):
        def fwd(q, k, v, rows, span, mask):
            return S.masked_chunk_attention(q, k, v, rows, span, mask, bs,
                                            1.0 / float(np.sqrt(D)), G)

        return (f"c{C}_l{L}_b{bs}_g{G}x{per}x{D}_bf16", fwd, [
            ((C, G * per * D), "bfloat16"), ((4 * L, G * D), "bfloat16"),
            ((4 * L, G * D), "bfloat16"), ((L,), "int32"), ((2,), "int32"),
            ((C, L), "int8")])

    return [case(1024, 32768, 16, 4, 8, 128)]


def _latent_chunk_case(rng, heads, nope, rope, value, latent, L, bs, C,
                       dtype="float32"):
    """One slot's latent arena (rows ``[c | k^R | zeros]`` of 128 lanes)
    under a shuffled block table, the up-projections and a chunk's
    queries: ``(q, w_uk, w_uv, arena, rows)``."""
    per_slot = -(-L // bs)
    pool = per_slot + 3
    ids = rng.permutation(pool)[:per_slot]
    rows = (ids[:, None] * bs + np.arange(bs)).reshape(-1)[:L]
    arena = np.zeros((pool * bs, 128), dtype)
    arena[:, :latent + rope] = rng.randn(pool * bs, latent + rope)
    return (rng.randn(C, heads * (nope + rope)).astype(dtype),
            (0.3 * rng.randn(heads, nope, latent)).astype(dtype),
            (0.3 * rng.randn(heads, latent, value)).astype(dtype),
            arena, rows.astype("int64"))


def _parity_latent_chunk(rng):
    """The kernel (interpreted) against the dense expanded composite: 4
    heads of 8 + 8 | 16 over a latent of 32, then 12 heads (two groups of
    6) of 4 + 4 | 8 over 8; a chunk of 32 in sub-tiles of 8 over
    320 rows in tiles of 64, so the spans cross dead tiles, tiles a
    sub-tile skips, and tiles it takes unmasked: an empty context, a short
    last chunk, a ragged horizon, a chunk that ends on a block's edge, one
    past several whole tiles, and NO real query (zeros)."""
    import jax

    from paddle_tpu.kernels import attention as A

    sizes = (A._LATENT_CHUNK_QUERY_ROWS, A._LATENT_CHUNK_TILE_ROWS)
    A._LATENT_CHUNK_QUERY_ROWS, A._LATENT_CHUNK_TILE_ROWS = 8, 64
    try:
        for heads, nope, value, latent in ((4, 8, 16, 32), (12, 4, 8, 8)):
            args = _latent_chunk_case(rng, heads, nope, nope, value, latent,
                                      320, 16, 32)
            kernel = jax.jit(lambda *a: A.latent_chunk_attention(
                *a, 16, 0.2, nope, interpret=True))
            dense = jax.jit(lambda *a: A.latent_chunk_expanded(
                *a, 0.2, nope))
            for start, real in ((0, 32), (0, 7), (224, 31), (288, 32),
                                (130, 20), (64, 0)):
                span = np.array([start, real], "int32")
                got = np.asarray(kernel(*args, span))
                ref = np.asarray(dense(*args, span))
                _assert_close_both_ways(
                    got[:real], ref[:real],
                    f"latent_chunk_attention {start}+{real}", 2e-5, 2e-5)
                assert not got[real:].any()
    finally:
        A._LATENT_CHUNK_QUERY_ROWS, A._LATENT_CHUNK_TILE_ROWS = sizes


def _tpu_cases_latent_chunk():
    """mistral_small_4_119b's chunk: 1,024 queries, and a prompt's short
    last launch of 512, of 32 heads of 64 + 64 | 128 over a latent of 256,
    a slot of 33,280 positions in blocks of 16 rows of 384 lanes,
    bfloat16."""
    from paddle_tpu.kernels import attention as A

    heads, nope, rope, value, latent, W, bs, L = 32, 64, 64, 128, 256, 384, \
        16, 33280

    def fwd(q, w_uk, w_uv, arena, rows, span):
        return A.latent_chunk_attention(q, w_uk, w_uv, arena, rows, span,
                                        bs, 0.195, rope)

    return [(f"c{C}_l{L}_b{bs}_h{heads}x{nope}+{rope}x{value}_bf16", fwd, [
        ((C, heads * (nope + rope)), "bfloat16"),
        ((heads, nope, latent), "bfloat16"),
        ((heads, latent, value), "bfloat16"), ((16 * L, W), "bfloat16"),
        ((L,), "int32"), ((2,), "int32")]) for C in (1024, 512)]


def _parity_moe_experts(rng):
    """Held experts some of which no token chose, a masked token, the
    step in which none is touched, and a step whose tokens are no whole
    sublane tiles (padded inside); then, with the gate and without: ONE
    expert touched (the first, one in the middle, the last), all of them,
    all but the first, and touched experts that are not the leading ids
    (the grid stops at the last touched one: the order the wrapper gives
    it decides what it reads)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import moe

    T, H, F, E, EA, k = 16, 24, 20, 4, 16, 3
    x = jnp.asarray(rng.randn(T, H).astype("float32"))
    gate = jnp.asarray(rng.randn(EA, H).astype("float32"))
    sel = jnp.asarray(0.1 * rng.randn(EA).astype("float32"))
    w_up = jnp.asarray(0.2 * rng.randn(E, F, H).astype("float32"))
    w_down = jnp.asarray(0.2 * rng.randn(E, F, H).astype("float32"))
    mask = jnp.asarray(rng.rand(T) > 0.3)
    idx, w = moe.route(x, gate, sel, k, 2.5, True)
    run = jax.jit(lambda *a: moe.moe_experts(*a, interpret=True))
    untouched = 0
    for offset in (0, 4, 12):
        c = moe.held_weights(idx, w, mask, offset, E)
        untouched += int((~np.asarray(c != 0).any(0)).sum())
        _assert_close_both_ways(
            run(x, c, w_up, w_down),
            moe.experts_composite(x, c, w_up, w_down), "moe_experts",
            1e-5, 1e-5)
    assert untouched, "no case left a held expert untouched"
    assert not np.asarray(run(x, jnp.zeros((T, E)), w_up, w_down)).any()
    odd = run(x[:11], c[:11], w_up, w_down)
    assert odd.shape == (11, H)
    _assert_close_both_ways(
        odd, moe.experts_composite(x[:11], c[:11], w_up, w_down),
        "moe_experts (11 tokens)", 1e-5, 1e-5)
    # gated experts: a third matrix and its own accumulator
    H2, E2 = 256, 8
    x2 = jnp.asarray(rng.randn(T, H2).astype("float32"))
    three = [jnp.asarray(0.1 * rng.randn(E2, F, H2).astype("float32"))
             for _ in range(3)]
    c = moe.held_weights(idx, w, mask, 0, E2)
    _assert_close_both_ways(
        run(x2, c, *three), moe.experts_composite(x2, c, *three),
        "moe_experts (gated)", 1e-5, 1e-5)
    for name, ids in (("one touched", [5]), ("the first alone", [0]),
                      ("the last alone", [E2 - 1]),
                      ("all touched", range(E2)),
                      ("all but the first", range(1, E2)),
                      ("not the leading ids", [2, 5, 7])):
        c = np.zeros((T, E2), "float32")
        for e in ids:
            rows = rng.permutation(T)[:1 + e % 3]
            c[rows, e] = 0.1 + rng.rand(len(rows))
        c = jnp.asarray(c)
        for matrices in (three, three[:2]):
            _assert_close_both_ways(
                run(x2, c, *matrices),
                moe.experts_composite(x2, c, *matrices),
                f"moe_experts ({name}, {len(matrices)} matrices)",
                1e-5, 1e-5)


#: the decode step's expert layer of each routed serving cell, by its
#: configuration: tokens, hidden size, expert width, held experts, matrices
#: an expert (3: gated, 2: relu2). nemotron3_nano_30b_a3b steps 32 slots,
#: lfm2_24b_a2b 128, sdar_30b_a3b a block pass's 32 x 4 tokens,
#: trinity_large_preview 24 (no whole sublane tiles: padded to 32 inside the
#: kernel's wrapper), mistral_small_4_119b its 16 slots. The ONE statement
#: of them: the chip-free compile, the traced program's digests and
#: ``tools/check_moe_experts.py`` read it
MOE_EXPERTS_STEPS = {
    "nemotron3_nano_30b_a3b": (32, 2688, 1856, 16, 2),
    "lfm2_24b_a2b": (128, 2048, 1536, 8, 3),
    "sdar_30b_a3b": (128, 2048, 768, 16, 3),
    "trinity_large_preview": (24, 3072, 3072, 32, 3),
    "mistral_small_4_119b": (16, 4096, 2048, 16, 3),
}


def _tpu_cases_moe_experts():
    """``MOE_EXPERTS_STEPS`` in bfloat16: the step of every routed serving
    cell (mistral_small_4_119b's since PR 62)."""
    from paddle_tpu.kernels import moe

    def case(T, H, F, E, matrices):
        return (f"t{T}_h{H}_f{F}_e{E}_m{matrices}_bf16", moe.moe_experts,
                [((T, H), "bfloat16"), ((T, E), "float32")]
                + [((E, F, H), "bfloat16")] * matrices)

    return [case(*step) for step in MOE_EXPERTS_STEPS.values()]


def _parity_moe_grouped(rng):
    """The grouped product against the dense composite over the same
    routing: a balanced router, every token on ONE held expert, no token on
    any, a masked token; held experts at the router's start and behind it;
    two row tiles; gated experts and squared-relu ones."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import moe

    T, H, F, E, EA, k = 64, 256, 40, 4, 16, 3
    x = jnp.asarray(rng.randn(T, H).astype("float32"))
    gate = jnp.asarray(rng.randn(EA, H).astype("float32"))
    gate_w, up_w, down_w = (
        jnp.asarray(0.1 * rng.randn(E, F, H).astype("float32"))
        for _ in range(3))
    mask = jnp.asarray(rng.rand(T) > 0.2)
    seen = set()
    for select in (np.zeros(EA), np.where(np.arange(EA) == 5, 100.0, 0.0),
                   np.where(np.arange(EA) < 8, -100.0, 0.0)):
        idx, w = moe.route(x, gate, jnp.asarray(select.astype("float32")), k,
                           1.0, True, score="softmax")
        for offset in (0, 4):
            c = moe.held_weights(idx, w, mask, offset, E)
            per_expert = np.asarray((c != 0).sum(0))
            seen.add((int(per_expert.max()) == int(np.asarray(mask).sum()),
                      not per_expert.any()))
            for tile, wg in ((8, gate_w), (16, gate_w), (16, None)):
                got = jax.jit(lambda *a: moe.moe_grouped(
                    *a, offset, up_w, down_w, wg, interpret=True,
                    row_tile=tile))(x, idx, w, mask)
                _assert_close_both_ways(
                    got, moe.experts_composite(x, c, up_w, down_w, wg),
                    "moe_grouped", 1e-5, 1e-5)
                pairs, rows, touched = np.asarray(moe.grouped_counts(
                    idx, mask, offset, E, tile))
                assert pairs == per_expert.sum()
                assert rows == (-(-per_expert // tile) * tile).sum()
                assert touched == (per_expert > 0).sum()
    assert (True, False) in seen and (False, True) in seen, seen


def _tpu_cases_moe_grouped():
    """mistral_small_4_119b's chunk (512, the cell's 1,024 and 2,048 tokens
    choosing 4 of 128, 16 held gated experts of width 2,048 at hidden
    4,096) and trinity_large_preview's (1,024 tokens choosing 4 of 256, 32
    held gated experts of width 3,072 at hidden 3,072) in bfloat16, and a
    squared-relu geometry (nemotron3_nano_30b_a3b's experts at a chunk of
    1,024)."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import moe

    def case(T, H, F, E, k, matrices):
        def fwd(x, idx, w, mask, *ws):
            up, down = ws[0], ws[1]
            return moe.moe_grouped(x, idx, w, mask, 0, up, down,
                                   ws[2] if matrices == 3 else None)

        return (f"t{T}_h{H}_f{F}_e{E}_k{k}_m{matrices}_bf16", fwd,
                [((T, H), "bfloat16"), ((T, k), "int32"),
                 ((T, k), "float32"), ((T,), "bool")]
                + [((E, F, H), "bfloat16")] * matrices)

    return [case(512, 4096, 2048, 16, 4, 3), case(1024, 4096, 2048, 16, 4, 3),
            case(2048, 4096, 2048, 16, 4, 3), case(1024, 3072, 3072, 32, 4, 3),
            case(1024, 2688, 1856, 16, 6, 2)]


def _parity_ssm_update(rng):
    """Some slots step, none does, all do, the last alone: a slot that does
    not step keeps its state bit for bit. At the block the shapes give (a
    slot's 32 heads a grid step); tests/test_ssm_update_kernel.py holds the
    smaller blocks."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import mamba

    S, H, P, N = 6, 32, 8, 128
    draw = lambda *shape: jnp.asarray(rng.randn(*shape).astype("float32"))
    state, xdt, bh, ch = (draw(S, H, P, N), draw(S, H, P), draw(S, H, N),
                          draw(S, H, N))
    decay = jnp.asarray(rng.rand(S, H).astype("float32"))
    run = jax.jit(lambda *a: mamba.ssm_update(*a, interpret=True))
    for steps in ([1, 0, 1, 1, 0, 0], [0] * 6, [1] * 6, [0, 0, 0, 0, 0, 1]):
        mask = jnp.asarray(steps, bool)
        new, y = run(state, xdt, decay, bh, ch, mask)
        ref_new, ref_y = mamba.ssm_update_composite(state, xdt, decay, bh,
                                                    ch, mask)
        _assert_close_both_ways(new, ref_new, "ssm_update state", 1e-5, 1e-5)
        _assert_close_both_ways(y, ref_y, "ssm_update y", 1e-5, 1e-4)
        idle = ~np.asarray(mask)
        _assert_bytes_equal(np.asarray(new)[idle], np.asarray(state)[idle],
                            "ssm_update idle slots")


def _tpu_cases_ssm_update():
    """The hybrid serving cells' Mamba layer (32 slots of 64 heads x 64 x
    128 float32 state: a slot's whole state a grid step) and a state so
    wide that a grid step holds one head of it."""
    from paddle_tpu.kernels import mamba

    def case(S, H, P, N):
        return (f"s{S}_h{H}_p{P}_n{N}", mamba.ssm_update, [
            ((S, H, P, N), "float32"), ((S, H, P), "float32"),
            ((S, H), "float32"), ((S, H, N), "float32"),
            ((S, H, N), "float32"), ((S,), "bool")])

    return [case(32, 64, 64, 128), case(4, 3, 512, 1024)]


def _scan_case(rng, T, H, P, N, G, real=None):
    """A launch's scan operands as ``mixer_chunk`` makes them: ``dt`` after
    softplus and 0 past the ``real`` tokens, ``a`` negative, ``b`` scaled so
    that a launch's ``y`` is no larger than its ``x``, a carried ``h0``.
    ``rng`` is a ``RandomState`` or a ``Generator``."""
    draw = lambda *shape: rng.standard_normal(shape).astype("float32")
    dt = np.log1p(np.exp(draw(T, H) - 1.0)).astype("float32")
    dt[T if real is None else real:] = 0.0
    return (draw(T, H, P), dt, -np.exp(0.5 * draw(H)), draw(T, G, N) / N,
            draw(T, G, N), draw(H, P, N))


def _parity_ssm_scan(rng):
    """The scan with the kernel as its loop's body against the einsums:
    one group and several (a head block of whole groups, and of part of
    one), two heads side by side in a lane group and one, a launch that is
    whole scan chunks and a ragged one behind ``dt = 0``."""
    import jax

    from paddle_tpu.kernels import mamba

    for T, H, P, N, G, Q, real in (
            (48, 8, 8, 16, 1, 16, None), (40, 32, 8, 16, 2, 16, 29),
            (32, 8, 8, 16, 4, 16, None), (16, 4, 128, 128, 2, 8, None),
            (16, 16, 64, 128, 8, 16, 11)):
        args = _scan_case(rng, T, H, P, N, G, real)
        got = jax.jit(lambda *a: mamba.ssm_scan_chunked(
            *a, Q, kernel=True))(*args)
        ref = jax.jit(lambda *a: mamba.ssm_scan_chunked(*a, Q))(*args)
        for what, g, r in zip(("y", "state"), got, ref):
            _assert_close_both_ways(g, r, f"ssm_scan {what}", 1e-5, 1e-5)


def _tpu_cases_ssm_scan():
    """granite_4_0_h_micro's launch (512 tokens in scan chunks of 256, 64
    heads of 64 over a state of 128, one group), the same launch ragged
    (301 tokens: padded to two scan chunks inside) and
    nemotron3_nano_30b_a3b's (128 tokens, one scan chunk, 8 groups)."""
    from paddle_tpu.kernels import mamba

    def case(label, T, G, Q, H=64, P=64, N=128):
        def fwd(*a):
            return mamba.ssm_scan_chunked(*a, Q, kernel=False)

        return (label, fwd, [
            ((T, H, P), "float32"), ((T, H), "float32"), ((H,), "float32"),
            ((T, G, N), "float32"), ((T, G, N), "float32"),
            ((H, P, N), "float32")])

    return [case("t512_q256_h64_p64_n128_g1", 512, 1, 256),
            case("t301_q256_h64_p64_n128_g1", 301, 1, 256),
            case("t128_q128_h64_p64_n128_g8", 128, 8, 128)]


def _parity_remat(rng):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import remat

    x = jnp.asarray(rng.randn(4, 8).astype("float32"))
    w1 = jnp.asarray(rng.randn(8, 16).astype("float32"))
    w2 = jnp.asarray(rng.randn(16, 8).astype("float32"))

    def f(x, w1, w2):
        return jnp.sum(jnp.tanh(x @ w1) @ w2)

    # jit on both sides: remat is bit-exact for the LOWERED computation
    # (the only path programs take); see _parity_cached
    v_ref, g_ref = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(
        x, w1, w2)
    for name in remat.POLICY_NAMES:
        pol = remat.checkpoint_policy(name)
        fc = (jax.checkpoint(f, policy=pol) if pol is not None
              else jax.checkpoint(f))
        v, g = jax.jit(jax.value_and_grad(fc, argnums=(0, 1, 2)))(
            x, w1, w2)
        _assert_bytes_equal(v, v_ref, f"remat[{name}] value")
        for a, b in zip(g, g_ref):
            _assert_bytes_equal(a, b, f"remat[{name}] grad")


register(KernelSpec(
    "flash_attention", ("scaled_dot_product_attention",), "tolerance",
    _parity_flash, tpu_cases=_tpu_cases_flash, version=2,
    doc="tiled online-softmax attention, training fwd+bwd, as many "
        "(batch, head) pairs a grid step as a VMEM budget holds "
        "(ops/pallas/flash_attention.py)",
))
register(KernelSpec(
    "cached_attention", ("cached_attention",), "bit", _parity_cached,
    tpu_cases=_tpu_cases_cached,
    doc="fused [S,1] decode attention over a dense slotted cache "
        "(kernels/attention.py)",
))
register(KernelSpec(
    "paged_attention", ("paged_attention", "paged_latent_attention"),
    "tolerance", _parity_paged,
    tpu_cases=_tpu_cases_paged, version=4,
    doc="blocked [S,1] decode attention over the live blocks of a paged "
        "arena, online softmax, the next live copy unit always in flight, "
        "a run of 8 neighbouring blocks one copy descriptor "
        "(kernels/attention.py)",
))
register(KernelSpec(
    "chunk_paged_attention", ("chunk_paged_attention",), "tolerance",
    _parity_chunk_attention, tpu_cases=_tpu_cases_chunk_attention,
    version=2,
    doc="a prompt chunk's queries over the live blocks of its slot, the "
        "mask made on the device from the chunk's span, online softmax "
        "over double-buffered copy tiles (kernels/attention.py "
        "chunk_attention)",
))
register(KernelSpec(
    "index_select", ("sparse_index_select",), "tolerance",
    _parity_index_select, tpu_cases=_tpu_cases_index_select, version=2,
    doc="an indexer's scores over a sequence's live index blocks "
        "(index_scores) and the exact top-k rows a query keeps, found by "
        "bisection over the scores' ordered bits, no sort (index_select): "
        "the selection as a mask (kernels/sparse.py)",
))
register(KernelSpec(
    "masked_chunk_attention", ("chunk_paged_attention",), "tolerance",
    _parity_masked_chunk, tpu_cases=_tpu_cases_masked_chunk, version=2,
    doc="a prompt chunk's queries over the live blocks of its slot under a "
        "[C, L] mask (the indexer's selection), the mask's tile copied "
        "beside K and V, a query head a product (kernels/sparse.py "
        "masked_chunk_attention)",
))
register(KernelSpec(
    "latent_chunk_attention", ("chunk_latent_attention",), "tolerance",
    _parity_latent_chunk, tpu_cases=_tpu_cases_latent_chunk, version=4,
    doc="a prompt chunk's latent attention, EXPANDED: a group of heads a "
        "grid step, the slot's live rows walked once in double-buffered "
        "copy tiles, each up-projected once for all the chunk's queries; "
        "keys, values, scores and running sums stay in VMEM, the softmax "
        "transposed (a query a lane) "
        "(kernels/attention.py latent_chunk_attention)",
))
register(KernelSpec(
    "moe_experts", ("moe_routed_experts",), "tolerance", _parity_moe_experts,
    tpu_cases=_tpu_cases_moe_experts, version=2,
    doc="a decode step's held experts: a grid row for each touched one, "
        "its weights streamed once; the others are never read and cost no "
        "grid step (kernels/moe.py)",
))
register(KernelSpec(
    "moe_grouped", ("moe_routed_experts",), "tolerance", _parity_moe_grouped,
    tpu_cases=_tpu_cases_moe_grouped, version=2,
    doc="a prompt chunk's (token, held expert) pairs sorted by expert, each "
        "expert's rows in whole row tiles, one product a row tile over that "
        "expert's matrices; the tokens' rows and their float32 sums stay in "
        "VMEM, a row tile picks its rows and adds its pairs itself; "
        "dropless; taken where the rows it multiplies are a quarter of the "
        "dense product's or fewer (kernels/moe.py takes_grouped)",
))
register(KernelSpec(
    "ssm_update", ("mamba2_mixer",), "tolerance", _parity_ssm_update,
    tpu_cases=_tpu_cases_ssm_update, version=2,
    doc="one-token Mamba-2 state update of the stepping slots, in place: a "
        "grid step carries as many heads of ONE stepping slot as an 8 MiB "
        "VMEM budget takes (at 64 heads x 64 x 128 the slot's whole state, "
        "2 MB in and 2 MB out) and sums a head's new state times C over "
        "the state dimension on the MXU, y leaving as whole rows "
        "(kernels/mamba.py)",
))
register(KernelSpec(
    "ssm_scan", ("mamba2_mixer",), "tolerance", _parity_ssm_scan,
    tpu_cases=_tpu_cases_ssm_scan,
    doc="a scan chunk of the Mamba-2 recurrence for all heads, a head "
        "block a grid step, its [Q, Q] decay-masked products kept in VMEM: "
        "the body of the chunked scan's loop (kernels/mamba.py)",
))
register(KernelSpec(
    "remat_policy", ("recompute_segment", "recompute_segment_grad"),
    "bit", _parity_remat, kind="policy",
    doc="IR-keyed jax.checkpoint policy table (kernels/remat.py)",
))
