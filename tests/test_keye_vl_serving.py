"""The ``keye_vl`` family (serving/decode/hybrid.py ``build_keye_vl_model``)
through the ``GenerationEngine``, chunks and then steps over THREE arenas a
layer, against its plain reference (benchmark/references/plain_keye_vl.py)
in float32 at a tiny size: the served LOGITS are the reference's with the
selection live (``topk`` 8 under contexts of up to 62) and with every row
kept (``topk`` past the context: ``paged_attention``'s result, bit for bit),
the controls that misread the indexer are told, the selection's set is
``lax.top_k``'s with ties and exact zeros, a step and a chunk choose the
same rows for the same token, and the ranks' shares of an expert layer add
up to the uncut layer.

Tolerance: 1e-4 standard deviations of a logits row. Both sides compute in
float32 with float32 accumulation; what parts them is the order of sums,
read at 2e-6 to 5e-6 here; the least control reads 0.29."""

import importlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TOLERANCE = 1e-4
BS, C, L, STEPS = 4, 8, 64, 10
LENGTHS = (5, 20, 37, 9, 52)
SA = dict(indexer_head_dim=8, indexer_num_heads=4, indexer_num_kv_heads=1,
          kv_chunk_size=512, q_chunk_size=512)
CONFIG = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              num_experts_per_tok=2, norm_topk_prob=True, rms_norm_eps=1e-6,
              rope_theta=1e7, num_hidden_layers=3)
#: kernels' mode, the indexer's topk, the expert rank
CASES = {"composites_top8": ("off", 8, 1), "kernels_top8": ("interpret", 8, 0),
         "kernels_all_rows": ("interpret", 100, 1)}
CONTROLS = {"select": False, "topk": 4, "relu": False,
            "rotate_index_keys": False, "index_lag": True}


def _reference():
    return importlib.import_module("benchmark.references.plain_keye_vl")


def _build(name, topk, rank, **more):
    from paddle_tpu.serving import build_keye_vl_model

    m = build_keye_vl_model(
        96, 64, 3, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=4, router_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, sa_config=dict(SA, topk=topk),
        initializer_range=0.3, expert_rank=rank, dtype="float32", slots=4,
        max_len=L, block_size=BS, num_blocks=48, chunk_tokens=C, name=name,
        **more)
    m.startup_program.random_seed = 11
    return m


class _Served:
    pass


@pytest.fixture(scope="module", params=sorted(CASES))
def served(request):
    from paddle_tpu import kernels
    from paddle_tpu.serving import GenerationEngine
    from paddle_tpu.serving.decode import SamplingParams

    mode, topk, rank = CASES[request.param]
    name = "kv" + request.param.replace("_", "")
    out = _Served()
    with kernels.scoped_mode(mode):
        engine = GenerationEngine(prefix_cache_size=0, host_tier_mb=0)
        entry = engine.register_model(lambda: _build(name, topk, rank))
        rng = np.random.default_rng(4)
        out.prompts = [[int(t) for t in rng.integers(0, 96, n)]
                       for n in LENGTHS]
        rows = {}

        def top(st, row, device_masked):
            row = np.array(row, np.float32)
            rows.setdefault(id(st.request.response), []).append(row)
            return int(row.argmax())

        entry._choose_token = top
        engine.start()
        try:
            sent = [engine.submit(p, max_new_tokens=STEPS,
                                  sampling=SamplingParams(seed=i))
                    for i, p in enumerate(out.prompts)]
            tokens = [[int(t) for t in r.result(timeout=600)["tokens"]]
                      for r in sent]
            out.pool = entry.kv.pool.check_conservation()
        finally:
            engine.shutdown()
    out.answers = [(t, np.stack(rows[id(r)])) for t, r in zip(tokens, sent)]
    out.model, out.topk, out.offset = entry.model, topk, 4 * rank
    out.config = dict(CONFIG, sa_config=dict(SA, topk=topk))
    scope, cut = entry._scope, len(name + "_v1.")
    arenas = {n for names in entry.model.all_state_names for n in names}
    out.weights = {
        n[cut:]: scope.find_var(n) for n in scope.var_names()
        if n.startswith(name + "_v1.") and n not in arenas
        and not n.endswith("grouped_counts")}
    return out


def _distance(served, **how):
    """Per request: max |served row - reference row| over the row's
    standard deviation, over the answer's tokens."""
    ref, worst = _reference(), []
    for prompt, (tokens, rows) in zip(served.prompts, served.answers):
        first = len(prompt) - 1
        want = ref.logits(
            served.weights, served.config, prompt + tokens[:-1],
            range(first, first + len(tokens)), pad_to=L,
            expert_offset=served.offset, **how)
        worst.append(float((np.abs(rows - want).max(1) / want.std(1)).max()))
    return worst


def test_three_arenas_a_layer_under_one_table(served):
    m = served.model
    assert len(m.index_names) == len(m.state_names) == 3
    assert m.index_width == 128 and m.index_topk == served.topk
    assert m.all_state_names[-3:] == [(n,) for n in m.index_names]
    # K and V at 2 x 16 float32 lanes, the index key at 128: 48 blocks of 4
    assert m.arena_bytes() == 3 * 192 * (2 * 32 + 128) * 4
    assert served.pool


def test_served_logits_are_the_references(served):
    assert max(_distance(served)) < TOLERANCE
    # the reference's k-th largest by bisection is lax.top_k's
    assert max(_distance(served, kth="bisect")) < TOLERANCE


def test_the_selection_left_out_is_told_unless_every_row_is_kept(served):
    worst = _distance(served, select=False)
    if served.topk < L:
        assert min(worst) > 0.1
    else:
        assert max(worst) < TOLERANCE


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_misread_indexer_is_told(served, control):
    """Where the selection is live every misreading is told; where every
    row is kept the indexer decides nothing, and only another ``topk`` is."""
    worst = _distance(served, **{control: CONTROLS[control]})
    if served.topk < L or control == "topk":
        assert min(worst) > 0.1
    else:
        assert max(worst) < TOLERANCE


def test_the_store_refuses_to_carry_an_indexers_arena():
    from paddle_tpu.serving import GenerationEngine
    from paddle_tpu.utils.enforce import EnforceError

    for sizes in ({"prefix_cache_size": 4, "host_tier_mb": 0},
                  {"prefix_cache_size": 0, "host_tier_mb": 1}):
        with pytest.raises(EnforceError, match="indexer|inject"):
            GenerationEngine(**sizes).register_model(
                lambda: _build("kvrefused", 8, 0))


def test_a_chunk_that_is_no_whole_blocks_is_refused():
    with pytest.raises(ValueError, match="whole blocks"):
        from paddle_tpu.serving import build_keye_vl_model

        build_keye_vl_model(
            96, 64, 1, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, num_experts=4, router_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            sa_config=dict(SA, topk=8), block_size=4, chunk_tokens=6,
            max_len=24)


# -- the selection ------------------------------------------------------------

def _scores(rng, n, l):
    """Scores with planted ties: runs of whole numbers, and the ReLU's
    exact zeros of both signs."""
    scores = rng.standard_normal((n, l)).astype("float32")
    scores[:, ::3] = np.round(scores[:, ::3])
    scores[:, 1::5] = np.where(rng.random((n, len(scores[0, 1::5]))) < .5,
                               0.0, -0.0)
    return scores


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["composite", "kernel"])
@pytest.mark.parametrize("n,l,topk", [(16, 2048, 64), (64, 1024, 200),
                                      (8, 1024, 1), (4, 1024, 1024)])
def test_the_selections_set_is_top_ks_ties_included(n, l, topk, interpret):
    import jax

    from paddle_tpu.kernels import sparse

    rng = np.random.default_rng(n + l)
    scores = _scores(rng, n, l)
    horizon = rng.integers(0, l + 1, n).astype("int32")
    horizon[:4] = (0, 1, topk, l)
    seen = np.where(np.arange(l)[None] < horizon[:, None],
                    scores + np.float32(0.0), -np.inf).astype("float32")
    _vals, idx = jax.lax.top_k(seen, topk)
    got = np.asarray(
        sparse.index_select(scores, horizon, topk, interpret=True)
        if interpret else
        sparse.index_select_composite(scores, horizon, topk)) != 0
    for r in range(n):
        k = min(topk, int(horizon[r]))
        assert sorted(np.flatnonzero(got[r])) == sorted(
            int(i) for i in np.asarray(idx[r][:k]))


def test_a_step_and_a_chunk_choose_the_same_rows_for_a_token():
    """The step's form (a bias with the rows not kept closed) and the
    chunk's (a mask) of ONE token: the query at position p of a chunk that
    starts at ``start``, and a slot that steps at p over the same rows."""
    from paddle_tpu.core.registry import OpRegistry
    from paddle_tpu.kernels import _sparse_case

    rng = np.random.RandomState(3)
    bs, length, heads, width, topk, start, chunk = 4, 64, 4, 128, 8, 24, 8
    q, w, arena, rows = _sparse_case(rng, 1, chunk, length, bs, heads, width)
    attrs = {"topk": topk, "block_size": bs}
    op = OpRegistry.get("sparse_index_select").lower
    mask = np.asarray(op(
        {"Q": [q], "W": [w], "Arena": [arena], "Rows": [rows],
         "Span": [np.array([start, chunk], "int32")]}, attrs)["Out"][0])
    at = start + np.arange(chunk)
    bias = np.where(np.arange(length)[None] <= at[:, None], 0.0,
                    -1e9).astype("float32")[:, None]
    stepped = np.asarray(op(
        {"Q": [q], "W": [w], "Arena": [arena],
         "Rows": [np.tile(rows, chunk)], "Bias": [bias]}, attrs)["Out"][0])
    assert mask.dtype == np.int8 and stepped.shape == (chunk, 1, length)
    assert ((stepped[:, 0] == 0) == (mask[:, :length] != 0)).all()
    assert (mask.sum(1) == topk).all()


def test_with_topk_past_the_context_a_step_gets_its_bias_back_bit_for_bit():
    from paddle_tpu.core.registry import OpRegistry
    from paddle_tpu.kernels import _sparse_case

    rng = np.random.RandomState(5)
    q, w, arena, rows = _sparse_case(rng, 3, 1, 32, 4, 4, 128)
    lengths = np.array([0, 7, 32])
    bias = np.where(np.arange(32)[None] < lengths[:, None], 0.0,
                    -1e9).astype("float32")[:, None]
    out = OpRegistry.get("sparse_index_select").lower(
        {"Q": [q], "W": [w], "Arena": [arena], "Rows": [rows],
         "Bias": [bias]}, {"topk": 32, "block_size": 4})["Out"][0]
    assert np.asarray(out).tobytes() == bias.tobytes()


# -- the share tied to the model ------------------------------------------------

def test_the_eight_ranks_shares_add_up_to_the_uncut_layer():
    """One expert layer at a small size: what ranks 0..7 (one of 8 experts
    each) add to the residual sums to what one rank holding all 8 adds."""
    import jax.numpy as jnp

    ref = _reference()
    sizes = tuple(sorted(dict(
        CONFIG, indexer_num_heads=4, indexer_head_dim=8).items()))
    sizes = tuple((k, v) for k, v in sizes if k != "num_hidden_layers")
    experts = ref._functions(sizes)[-1]
    rng = np.random.default_rng(8)
    h = jnp.asarray(rng.standard_normal((12, 64)), jnp.float32)
    norm = jnp.ones((64,), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.standard_normal((8, 32, 64)) * .3, jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((8, 32, 64)) * .3, jnp.float32)
    whole = experts(h, norm, gate, w1, w3, w2, offset=0) - h
    shares = sum(experts(h, norm, gate, w1[e:e + 1], w3[e:e + 1],
                         w2[e:e + 1], offset=e) - h for e in range(8))
    np.testing.assert_allclose(np.asarray(shares), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
