"""A dense ``granitemoehybrid`` decoder (``serving/decode/hybrid.py
build_granite_hybrid_model``: Mamba-2 or position-free grouped-query mixers,
a dense SwiGLU in every layer, four scalar multipliers, a tied head) hosted
whole by a ``GenerationEngine`` like any other model: paged K/V arena beside
per-slot recurrent state, every prompt through the chunked prefill,
admission by reservation, continuous batching, launch-ahead. Weights from
the startup program's seeded draws, on the device; nothing is rescaled."""

import importlib
import time

from benchmark.builders._program import SEED_MODULUS
from benchmark.builders.decoder_engine import DecoderServer
from benchmark.builders.nemotron_h_engine import NemotronHServer
from benchmark.manifest import model_sizes, published, sizes

#: the published keys the model builder takes, under their own names
_BUILDER_KEYS = (
    "vocab_size", "hidden_size", "layer_types", "num_attention_heads",
    "num_key_value_heads", "shared_intermediate_size", "mamba_n_heads",
    "mamba_d_head", "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
    "mamba_chunk_size", "embedding_multiplier", "attention_multiplier",
    "residual_multiplier", "logits_scaling", "num_local_experts",
    "rms_norm_eps")

#: the reference's sequences are padded to a multiple of this (a document's
#: 16k tokens: nine compiled lengths at most, all compiled after the
#: window), or to the slot's length where that is shorter
_PAD = 2048


class GraniteHybridServer(NemotronHServer):
    """``NemotronHServer``'s ``weights`` (the served parameters by the plain
    reference's names) over the dense hybrid; the reference takes no expert
    offset and has no cache, so a sequence may be padded past the slot's
    length."""

    def __init__(self, engine, entry, config, model, load_s, prefix,
                 reference):
        DecoderServer.__init__(
            self, engine, entry,
            dict(model, num_layers=len(config["layer_types"]),
                 vocab_size=config["vocab_size"]),
            load_s, prefix, reference)
        self.config = config
        self.expert_offset = 0

    def reference_logits(self, tokens, positions, **control):
        """The plain reference's logits after ``tokens`` at ``positions``.
        ``control`` is a control's: ``round_to`` (the reference in a
        precision below the served one) or a published key misread
        (``attention_multiplier=0.125``)."""
        # the float32 pass over a 16k-token sequence needs the room the
        # arenas and per-slot states hold (4.7 GB at the published size)
        self.entry.release_states()
        pad = min(_PAD, self.max_len)
        return self.reference.logits(
            self.weights(), self.config, tokens, positions,
            pad_to=-(-len(tokens) // pad) * pad, **control)


def build(config, traffic, seed, rehearse):
    from paddle_tpu.serving import (
        GenerationEngine, build_granite_hybrid_model)

    model = model_sizes(config, rehearse)
    keys = published(config, rehearse)
    settings = sizes(config["settings"], rehearse)
    published_sizes = {k: keys[k] for k in _BUILDER_KEYS}

    def make():
        m = build_granite_hybrid_model(
            name=config["name"], version="1", dtype=settings["dtype"],
            state_dtype=settings["state_dtype"],
            initializer_range=settings["initializer_range"],
            qk_initializer_range=settings["qk_initializer_range"],
            **published_sizes, **model)
        m.startup_program.random_seed = seed % SEED_MODULUS + 1
        return m

    t0 = time.perf_counter()
    engine = GenerationEngine(**settings["engine"])
    entry = engine.register_model(make)
    return GraniteHybridServer(
        engine, entry, keys, model, time.perf_counter() - t0,
        prefix=f"{config['name']}_v1.",
        reference=importlib.import_module(
            "benchmark.references." + config["reference"]))
