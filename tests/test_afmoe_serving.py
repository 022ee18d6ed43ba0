"""The ``afmoe`` family (serving/decode/hybrid.py ``build_afmoe_model``)
through the ``GenerationEngine``, chunks and then steps over the TWO-group
cache, against its plain reference (benchmark/references/plain_trinity.py)
in float32 at a tiny size: the served LOGITS are the reference's at prompts
on both sides of the window, the controls that misread the description or
corrupt the window's rows are told, what a launch reads stays inside the
window's bound, both pools are whole at shutdown, and the ranks' shares of
an expert layer add up to the uncut layer.

Tolerance: 1e-4 standard deviations of a logits row. Both sides compute in
float32 with float32 accumulation; what parts them is the order of sums (the
kernels' online softmax in tiles against the reference's one softmax; the
routed experts as the program's sorted groups against a sum expert by
expert), read at 2e-6 to 1e-5 here; the least control reads 0.3."""

import importlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools import check_hybrid_logits  # noqa: E402

TOLERANCE = 1e-4
BS, C, L, STEPS = 4, 8, 64, 12
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
CONFIG = dict(
    layer_types=KINDS, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_dense_layers=1, num_experts_per_tok=2, route_scale=2.448,
    route_norm=True, rms_norm_eps=1e-5, rope_theta=10000.0, mup_enabled=True)
#: window, expert rank, prompt lengths (both sides of the window; 52 + 12
#: fills the slot to its last position)
CASES = {"window8_rank0": (8, 0, (5, 20, 37, 9, 52)),
         "window32_rank1": (32, 1, (5, 30, 40, 45))}


def _reference():
    return importlib.import_module("benchmark.references.plain_trinity")


class _Served:
    pass


@pytest.fixture(scope="module", params=sorted(CASES))
def served(request):
    from paddle_tpu.serving import GenerationEngine, build_afmoe_model
    from paddle_tpu.serving.decode import SamplingParams

    window, rank, lengths = CASES[request.param]
    name = "af" + request.param.replace("_", "")

    def make():
        m = build_afmoe_model(
            96, 64, KINDS, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, intermediate_size=96, num_dense_layers=1,
            num_experts=4, router_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, sliding_window=window,
            route_scale=2.448, initializer_range=0.3, expert_rank=rank,
            dtype="float32", slots=4, max_len=L, block_size=BS,
            num_blocks=48, window_num_blocks=30, chunk_tokens=C, name=name)
        m.startup_program.random_seed = 11
        return m

    engine = GenerationEngine(prefix_cache_size=0, host_tier_mb=0)
    entry = engine.register_model(make)
    m = entry.model
    out = _Served()
    out.window, out.offset, out.entry, out.model = window, 4 * rank, entry, m
    out.config = dict(CONFIG, sliding_window=window)
    out.launches = []
    launch = entry._run

    def run(kind, feeds, span=None):
        # what each launch was fed of the window group
        if kind == "step":
            at = m.step_table + m.blocks_per_slot
            out.launches.append(("step", np.array(feeds[m.DEC_STEP])[:, :at + 2]))
        elif kind == "chunk":
            out.launches.append(("chunk", (
                np.array(feeds[m.CHU_SPAN]),
                np.array(feeds[m.chunk_group_feeds(0)[0]]))))
        return launch(kind, feeds, span)

    entry._run = run
    rng = np.random.default_rng(4)
    out.prompts = [[int(t) for t in rng.integers(0, 96, n)] for n in lengths]

    def serve(fault=None):
        rows = {}

        def top(st, row, device_masked):
            row = np.array(row, np.float32)
            rows.setdefault(id(st.request.response), []).append(row)
            return int(row.argmax())

        undo = (check_hybrid_logits._stale(entry, fault) if fault
                else lambda: None)
        entry._choose_token = top
        try:
            sent = [engine.submit(p, max_new_tokens=STEPS,
                                  sampling=SamplingParams(seed=i))
                    for i, p in enumerate(out.prompts)]
            tokens = [[int(t) for t in r.result(timeout=600)["tokens"]]
                      for r in sent]
        finally:
            undo()
        return [(t, np.stack(rows[id(r)])) for t, r in zip(tokens, sent)]

    engine.start()
    try:
        out.sound = serve()
        out.pools_after_sound = [
            p.check_conservation() for p in entry.kv.pools]
        out.faulted = {f: serve(f)
                       for f in ("window_early", "window_early_block")}
    finally:
        engine.shutdown()
    scope, cut = entry._scope, len(name + "_v1.")
    arenas = {n for names in m.all_state_names for n in names}
    out.weights = {
        n[cut:]: scope.find_var(n) for n in scope.var_names()
        if n.startswith(name + "_v1.") and n not in arenas
        and not n.endswith("grouped_counts")}
    return out


def _distance(served, answers, **how):
    """Per request: max |served row - reference row| over the row's
    standard deviation, over the answer's tokens."""
    ref, worst = _reference(), []
    for prompt, (tokens, rows) in zip(served.prompts, answers):
        first = len(prompt) - 1
        want = ref.logits(
            served.weights, served.config, prompt + tokens[:-1],
            range(first, first + len(tokens)), pad_to=L,
            expert_offset=served.offset, **how)
        worst.append(float((np.abs(rows - want).max(1) / want.std(1)).max()))
    return worst


def test_the_served_logits_are_the_references_on_both_sides_of_the_window(
        served):
    lengths = [len(p) for p in served.prompts]
    assert min(lengths) < served.window < max(lengths) + STEPS
    assert all(len(t) == STEPS for t, _r in served.sound)
    worst = _distance(served, served.sound)
    assert max(worst) < TOLERANCE, dict(zip(lengths, worst))


@pytest.mark.parametrize("control", [
    {"sliding_window": 10 ** 6}, {"sliding_window": "one_block_more"},
    {"sliding_window": "one_block_less"}, {"rotate_full": True},
    {"gate": False}, {"route_scale": 1.0}, {"round_to": "bfloat16"}],
    ids=["window_left_out", "window_a_block_wide", "window_a_block_short",
         "full_layer_rotated", "gate_left_out", "route_scale_left_out",
         "reference_in_bfloat16"])
def test_a_control_moves_the_logits(served, control):
    """The sound rows against the reference read otherwise: every control
    of the issue that the description's misreading is (the chip holds the
    cell's own comparison to them: PERF.md section 6, PR 59)."""
    if isinstance(control.get("sliding_window"), str):
        by = BS if control["sliding_window"].endswith("more") else -BS
        control = {"sliding_window": served.window + by}
    # a sequence that never passes the window cannot tell its size
    long = [i for i, p in enumerate(served.prompts)
            if len(p) + STEPS > served.window + BS] \
        if "sliding_window" in control else range(len(served.prompts))
    worst = _distance(served, served.sound, **control)
    floor = 1e-3 if "round_to" in control else 0.1
    assert all(worst[i] > floor for i in long), worst


@pytest.mark.parametrize("fault", ["window_early", "window_early_block"])
def test_a_block_given_back_early_is_a_stale_row_inside_the_window(served,
                                                                   fault):
    """The oldest block of the window group reused while rows of it are
    still inside the window: the rows a sequence past its window is then
    served are not the reference's (its first rows, before the fault first
    bites, are)."""
    worst = _distance(served, served.faulted[fault])
    past = [i for i, p in enumerate(served.prompts)
            if len(p) + STEPS - 2 >= served.window + BS]
    assert past and all(worst[i] > 0.01 for i in past), worst
    short = [i for i, p in enumerate(served.prompts)
             if len(p) + STEPS <= served.window]
    assert all(worst[i] < TOLERANCE for i in short)


def test_a_launch_reads_no_more_than_its_window(served):
    m, w = served.model, served.window
    at = m.step_table + m.blocks_per_slot
    steps = [f for kind, f in served.launches if kind == "step"]
    chunks = [f for kind, f in served.launches if kind == "chunk"]
    assert steps and chunks
    for feed in steps:
        length, low = feed[:, at], feed[:, at + 1]
        live = length > 0
        assert (length[live] <= w + BS - 1).all()
        # what is open is the window, or the whole context below it
        position = feed[:, m.STEP_POSITION]
        assert ((length - low)[live]
                == np.minimum(position[live] + 1, w)).all()
        assert (low[live] < BS).all()
    for span, group_span in chunks:
        start, real = (int(x) for x in span)
        rel, greal = (int(x) for x in group_span)
        assert greal == real
        base = start - rel
        assert base % BS == 0 and base == max(start - w + 1, 0) // BS * BS
        assert rel + real <= w + C - 1 + BS - 1
    counts = served.entry.metrics.snapshot()
    assert counts["attention_rows_read_step"] <= \
        counts["attention_rows_in_context_step"]
    assert counts["attention_window_rows_chunk"] <= \
        counts["attention_rows_read_chunk"] <= \
        counts["attention_rows_in_context_chunk"]
    if any(len(p) + STEPS > w + BS for p in served.prompts):
        assert counts["kv_window_blocks_released"] > 0


def test_both_pools_are_whole_when_the_requests_are_over(served):
    kv = served.entry.kv
    assert kv.pools == [kv.pool] + kv.window_pools and kv.windowed == [1]
    for counts, pool in zip(served.pools_after_sound, kv.pools):
        assert counts["blocks_live"] == 0 and pool.reserved == 0
        assert pool.check_conservation()["blocks_free"] == pool.num_blocks
    assert "window_pools" in served.entry.kv.stats()


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """Guide section 4: eight ranks' routed parts (here four of two experts
    each) plus the shared expert, counted once, are the layer with every
    expert on one chip."""
    import jax
    import jax.numpy as jnp

    ref = _reference()
    rng = np.random.default_rng(8)
    H, F, E, T, held = 32, 16, 8, 24, 2
    sizes = tuple(sorted(dict(
        rms_norm_eps=1e-5, num_attention_heads=2, num_key_value_heads=1,
        head_dim=16, num_experts_per_tok=3, route_norm=True, rope_theta=1e4,
        mup=True, route_scale=2.448).items()))
    _e, _h, _a, dense, route, expert, _c = ref._functions(sizes)
    n = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape) * 0.3, jnp.float32)
    h, pre = n(T, H), jnp.ones((H,), jnp.float32)
    router, bias = n(E, H), n(E) * 0.2
    w1, w3, w2 = n(E, F, H), n(E, F, H), n(E, F, H)
    shared = (n(H, F), n(H, F), n(F, H))
    with jax.default_matmul_precision("highest"):
        x, ranked, weights, _near = route(h, pre, router, bias)
        whole = ref.routed_part(x, ranked, weights, w1, w3, w2, 0, expert)
        parts = [ref.routed_part(
            x, ranked, weights, w1[r * held:(r + 1) * held],
            w3[r * held:(r + 1) * held], w2[r * held:(r + 1) * held],
            r * held, expert) for r in range(E // held)]
        once = dense(h, pre, *shared)
        # the uncut layer by its definition, token by token
        s = jax.nn.sigmoid(x @ router.T)
        want = np.zeros((T, H), np.float32)
        for t in range(T):
            top = np.argsort(-np.asarray(s[t] + bias))[:3]
            z = float(sum(s[t, e] for e in top)) + 1e-20
            for e in top:
                y = (jax.nn.silu(x[t] @ w1[e].T) * (x[t] @ w3[e].T)) @ w2[e]
                want[t] += 2.448 * float(s[t, e]) / z * np.asarray(y)
    np.testing.assert_allclose(np.asarray(whole), want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    # every rank holds a part, and none is the whole
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    assert once.shape == whole.shape
