"""An ``sdar_moe`` block-diffusion decoder (``serving/decode/hybrid.py
build_sdar_model``: grouped-query attention with QK-norm and rotary
positions under a block mask, softmax-routed gated experts of which this
chip holds a share, an answer filled a block of positions at a time over
several passes) hosted by a ``GenerationEngine`` like any other model: paged
K/V arena, every prompt's whole blocks through the chunked prefill,
continuous batching, launch-ahead. Weights from the startup program's
seeded draws, on the device; nothing is rescaled.

The comparison with the plain reference REPLAYS the order in which each
block was filled: a served token is held to the reference's row at its
position in the block state in which it was decided, which the response
carries (``decided_at``)."""

import importlib
import time

import numpy as np

from benchmark.builders._program import SEED_MODULUS
from benchmark.builders.decoder_engine import DecoderServer
from benchmark.builders.nemotron_h_engine import NemotronHServer
from benchmark.manifest import model_sizes, published

#: the published keys the model builder takes, under their own names
_BUILDER_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "norm_topk_prob", "rms_norm_eps", "rope_theta")


class _NotingEngine:
    """The engine as the serving drivers drive it, every response noted
    under its prompt (one dict store a request, on the caller's thread):
    the drivers hand ``reference_logits`` tokens, and the replay needs the
    response they came in."""

    def __init__(self, engine):
        self._engine = engine
        self.noted = {}

    def submit(self, prompt_ids, **how):
        response = self._engine.submit(prompt_ids, **how)
        self.noted[tuple(int(t) for t in prompt_ids)] = response
        return response

    def __getattr__(self, name):
        return getattr(self._engine, name)


class SdarServer(NemotronHServer):
    """``NemotronHServer``'s ``weights`` (the served parameters by the
    plain reference's names) over the block-diffusion model, and a
    ``reference_logits`` that replays the served filling order."""

    def __init__(self, engine, entry, config, model, load_s, prefix,
                 reference, expert_offset):
        DecoderServer.__init__(
            self, _NotingEngine(engine), entry,
            dict(model, num_layers=config["num_hidden_layers"],
                 vocab_size=config["vocab_size"]),
            load_s, prefix, reference)
        self.config = dict(config, block_len=model["block_len"],
                           mask_token_id=model["mask_token_id"])
        self.expert_offset = expert_offset

    def block_state(self, prompt, served, decided_at, start, k):
        """The block at ``start`` as pass ``k`` of it found it: ``(tokens,
        decided)``, the prompt's positions decided, an answer's position
        decided where an earlier pass decided it."""
        B, p = self.config["block_len"], len(prompt)
        if start + B > p + len(served):
            raise ValueError(
                f"the block at {start} reaches past the {len(served)} "
                "served tokens: what its last positions held is not in the "
                "response; check whole blocks only")
        block, decided = [], []
        for j in range(start, start + B):
            block.append(prompt[j] if j < p else served[j - p])
            decided.append(j < p or decided_at[j - p] < k)
        return block, decided

    def reference_logits(self, tokens, positions, order="served", **control):
        """For each served token (``tokens`` are a prompt and its served
        tokens but the last, ``positions`` from the prompt's last on, as
        ``benchmark/serve.py`` hands them) the plain reference's row AT
        that token's position, from the block state in which it was
        DECIDED: positions of the block that an earlier pass decided hold
        their served tokens, the rest the mask token. The pass is the
        response's ``decided_at``; ``order="left_to_right"`` is a
        control's (the replay that ignores it), ``control`` the
        reference's own (``round_to``, ``mask``)."""
        positions = list(positions)
        p = positions[0] + 1
        prompt = [int(t) for t in tokens[:p]]
        out = self.engine.noted[tuple(prompt)].result()
        served = [int(t) for t in out["tokens"]]
        decided_at = [int(k) for k in out["decided_at"]]
        B = self.config["block_len"]
        if order != "served":
            # the pass that would have decided each token had the block
            # been filled from its first open position on
            decided_at = [p + j - max((p + j) // B * B, p)
                          for j in range(len(served))]
        whole = prompt + served
        passes, rows = {}, []
        for i in range(len(positions)):
            at = p + i
            start, k = at // B * B, decided_at[i]
            if (start, k) not in passes:
                block, decided = self.block_state(prompt, served,
                                                  decided_at, start, k)
                pad_to = min(self.max_len, -(-(start + B) // 256) * 256)
                passes[start, k] = self.reference.block_pass(
                    self.weights(), self.config, whole[:start], block,
                    decided, pad_to=pad_to,
                    expert_offset=self.expert_offset, **control)
            rows.append(passes[start, k][at - start])
        return np.stack(rows)


def build(config, traffic, seed, rehearse):
    from paddle_tpu.serving import GenerationEngine, build_sdar_model

    model = model_sizes(config, rehearse)
    keys = published(config, rehearse)
    settings = config["settings"]
    sizes = {k: keys[k] for k in _BUILDER_KEYS}

    def make():
        m = build_sdar_model(
            name=config["name"], version="1", dtype=settings["dtype"],
            expert_rank=settings["expert_rank"], **sizes, **model)
        m.startup_program.random_seed = seed % SEED_MODULUS + 1
        return m

    t0 = time.perf_counter()
    engine = GenerationEngine(**settings["engine"])
    entry = engine.register_model(make)
    return SdarServer(
        engine, entry, keys, model, time.perf_counter() - t0,
        prefix=f"{config['name']}_v1.",
        reference=importlib.import_module(
            "benchmark.references." + config["reference"]),
        expert_offset=settings["expert_rank"] * keys["num_experts"])
