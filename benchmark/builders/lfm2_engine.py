"""An ``lfm2_moe`` decoder (``serving/decode/hybrid.py build_lfm2_model``:
gated short convolutions, grouped-query attention with QK-norm and rotary
positions, gated routed experts of which this chip holds a share) hosted by
a ``GenerationEngine`` like any other model: paged K/V arena beside
per-slot convolution tails, every prompt through the chunked prefill,
continuous batching, launch-ahead. Weights from the startup program's
seeded draws, on the device; nothing is rescaled."""

import importlib
import time

from benchmark.builders._program import SEED_MODULUS
from benchmark.builders.decoder_engine import DecoderServer
from benchmark.builders.nemotron_h_engine import NemotronHServer
from benchmark.manifest import model_sizes, published

#: the published keys the model builder takes, under their own names
_BUILDER_KEYS = (
    "vocab_size", "hidden_size", "layer_types", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "num_dense_layers",
    "num_experts", "num_experts_per_tok", "moe_intermediate_size",
    "conv_L_cache", "routed_scaling_factor", "norm_topk_prob", "norm_eps")


class Lfm2Server(NemotronHServer):
    """``NemotronHServer``'s ``weights`` and ``reference_logits`` (the
    served parameters by the plain reference's names; the reference padded
    to a few compiled lengths) over the ``lfm2_moe`` model."""

    def __init__(self, engine, entry, config, model, load_s, prefix,
                 reference, expert_offset):
        DecoderServer.__init__(
            self, engine, entry,
            dict(model, num_layers=len(config["layer_types"]),
                 vocab_size=config["vocab_size"]),
            load_s, prefix, reference)
        self.config = config
        self.expert_offset = expert_offset


def build(config, traffic, seed, rehearse):
    from paddle_tpu.serving import GenerationEngine, build_lfm2_model

    model = model_sizes(config, rehearse)
    keys = published(config, rehearse)
    settings = config["settings"]
    sizes = {k: keys[k] for k in _BUILDER_KEYS}

    def make():
        m = build_lfm2_model(
            name=config["name"], version="1", dtype=settings["dtype"],
            state_dtype=settings["state_dtype"],
            expert_rank=settings["expert_rank"],
            rope_theta=keys["rope_parameters"]["rope_theta"], **sizes,
            **model)
        m.startup_program.random_seed = seed % SEED_MODULUS + 1
        return m

    t0 = time.perf_counter()
    engine = GenerationEngine(**settings["engine"])
    entry = engine.register_model(make)
    return Lfm2Server(
        engine, entry, keys, model, time.perf_counter() - t0,
        prefix=f"{config['name']}_v1.",
        reference=importlib.import_module(
            "benchmark.references." + config["reference"]),
        expert_offset=settings["expert_rank"] * keys["num_experts"])
