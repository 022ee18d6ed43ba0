"""A ``nemotron_h`` decoder (``serving/decode/hybrid.py
build_nemotron_h_model``: Mamba-2, grouped-query attention and routed
experts of which this chip holds a share) hosted by a ``GenerationEngine``
like any other model: paged K/V arena beside per-slot recurrent state,
every prompt through the chunked prefill, continuous batching, launch-ahead.
Weights from the startup program's seeded draws, on the device; nothing is
rescaled (the draws give distinct answers as they are: PERF.md section 6,
PR 36)."""

import importlib
import time

from benchmark.builders._program import SEED_MODULUS
from benchmark.builders.decoder_engine import DecoderServer
from benchmark.manifest import model_sizes, published

#: the published keys the model builder takes, under their own names
_BUILDER_KEYS = (
    "vocab_size", "hidden_size", "hybrid_override_pattern",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
    "conv_kernel", "chunk_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "n_routed_experts",
    "num_experts_per_tok", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor",
    "norm_topk_prob", "layer_norm_epsilon", "time_step_min",
    "time_step_max", "time_step_floor")


class NemotronHServer(DecoderServer):
    """``DecoderServer``'s interface (``engine``, ``entry``, ``vocab_size``,
    ``reference_logits``, ``compiled_bytes``, ``devices``, ``load_s``) over
    the hybrid model and its own plain reference."""

    def __init__(self, engine, entry, config, model, load_s, prefix,
                 reference, expert_offset):
        super().__init__(engine, entry,
                         dict(model, num_layers=len(
                             config["hybrid_override_pattern"]),
                             vocab_size=config["vocab_size"]),
                         load_s, prefix, reference)
        self.config = config
        self.expert_offset = expert_offset

    def weights(self):
        """Every served parameter by the name the plain reference knows it
        under (the program's name less its prefix), as the device arrays
        they are; the arenas and per-slot states are left out."""
        scope, cut = self.entry._scope, len(self.prefix)
        m = self.entry.model
        state = {n for kv in m.state_names for n in kv} | {
            n for n, _s, _d in m.slot_states}
        return {name[cut:]: scope.find_var(name)
                for name in scope.var_names()
                if name.startswith(self.prefix) and name not in state}

    def reference_logits(self, tokens, positions, **control):
        """The plain reference's logits after ``tokens`` at ``positions``,
        the sequence padded to a multiple of 256 (a few compiled shapes,
        all compiled after the window). ``control`` is a control's or a
        diagnosis's (``round_to``: the reference computed in a precision
        below the served one)."""
        pad_to = min(self.max_len, -(-len(tokens) // 256) * 256)
        return self.reference.logits(
            self.weights(), self.config, tokens, positions, pad_to=pad_to,
            expert_offset=self.expert_offset, **control)


def build(config, traffic, seed, rehearse):
    from paddle_tpu.serving import GenerationEngine, build_nemotron_h_model

    model = model_sizes(config, rehearse)
    keys = published(config, rehearse)
    settings = config["settings"]
    sizes = {k: keys[k] for k in _BUILDER_KEYS}

    def make():
        m = build_nemotron_h_model(
            name=config["name"], version="1", dtype=settings["dtype"],
            state_dtype=settings["state_dtype"],
            expert_rank=settings["expert_rank"], **sizes, **model)
        m.startup_program.random_seed = seed % SEED_MODULUS + 1
        return m

    t0 = time.perf_counter()
    engine = GenerationEngine(**settings["engine"])
    entry = engine.register_model(make)
    return NemotronHServer(
        engine, entry, keys, model, time.perf_counter() - t0,
        prefix=f"{config['name']}_v1.",
        reference=importlib.import_module(
            "benchmark.references." + config["reference"]),
        expert_offset=settings["expert_rank"] * keys["n_routed_experts"])
