"""A ``mistral4`` decoder (``serving/decode/hybrid.py
build_latent_moe_model``: latent attention over ONE paged arena a layer,
YaRN rotation, softmax-routed gated experts of which this chip holds a share
beside a shared expert) hosted by a ``GenerationEngine`` like any other
model: every prompt through the chunked prefill, admission by reservation,
continuous batching, launch-ahead. Weights from the startup program's seeded
draws, on the device; nothing is rescaled."""

import importlib
import time

from benchmark.builders._program import SEED_MODULUS
from benchmark.builders.decoder_engine import DecoderServer
from benchmark.builders.nemotron_h_engine import NemotronHServer
from benchmark.manifest import model_sizes, published, sizes

#: the published keys the model builder takes, under their own names
_BUILDER_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "rope_parameters", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "moe_intermediate_size", "routed_scaling_factor",
    "norm_topk_prob", "rope_interleave", "rms_norm_eps")

#: the reference's sequences are padded to a multiple of this (a document's
#: 32k tokens: nine compiled lengths at most, all compiled after the
#: window), or to the slot's length where that is shorter
_PAD = 4096


class Mistral4Server(NemotronHServer):
    """``NemotronHServer``'s ``weights`` (the served parameters by the plain
    reference's names; the device's sum of the chunks' routing counts is no
    parameter and is left out) over the latent-attention model; the
    reference has no cache, so a sequence may be padded past the slot's
    length."""

    def __init__(self, engine, entry, config, model, load_s, prefix,
                 reference, expert_offset):
        DecoderServer.__init__(
            self, engine, entry,
            dict(model, num_layers=config["num_hidden_layers"],
                 vocab_size=config["vocab_size"]),
            load_s, prefix, reference)
        self.config = config
        self.expert_offset = expert_offset

    def weights(self):
        return {name: value for name, value in super().weights().items()
                if name != "grouped_counts"}

    def reference_logits(self, tokens, positions, **control):
        """The plain reference's logits after ``tokens`` at ``positions``.
        ``control`` is a control's: ``round_to`` (the reference in a
        precision below the served one) or the description misread
        (``llama_4_scaling_beta=0.0``)."""
        # the float32 pass over a 32k-token sequence needs the room the
        # arenas hold (2.4 GB at the published size)
        self.entry.release_states()
        pad = min(_PAD, self.max_len)
        return self.reference.logits(
            self.weights(), self.config, tokens, positions,
            pad_to=-(-len(tokens) // pad) * pad,
            expert_offset=self.expert_offset, **control)


def build(config, traffic, seed, rehearse):
    from paddle_tpu.serving import GenerationEngine, build_latent_moe_model

    model = model_sizes(config, rehearse)
    keys = published(config, rehearse)
    settings = sizes(config["settings"], rehearse)
    published_sizes = {k: keys[k] for k in _BUILDER_KEYS}

    def make():
        m = build_latent_moe_model(
            name=config["name"], version="1", dtype=settings["dtype"],
            expert_rank=settings["expert_rank"],
            initializer_range=settings["initializer_range"],
            **published_sizes, **model)
        m.startup_program.random_seed = seed % SEED_MODULUS + 1
        return m

    t0 = time.perf_counter()
    engine = GenerationEngine(**settings["engine"])
    entry = engine.register_model(make)
    return Mistral4Server(
        engine, entry, keys, model, time.perf_counter() - t0,
        prefix=f"{config['name']}_v1.",
        reference=importlib.import_module(
            "benchmark.references." + config["reference"]),
        expert_offset=settings["expert_rank"] * keys["n_routed_experts"])
