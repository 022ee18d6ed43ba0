"""An ``ouro`` looped decoder (``serving/decode/hybrid.py build_ouro_model``:
one stack of layers applied several times a token with shared parameters,
paged K/V rows per (pass, layer), an exit gate) hosted WHOLE by a
``GenerationEngine`` like any other model: every prompt through the chunked
prefill, continuous batching, launch-ahead, and an arena that cannot give
every slot its full length, so that admission reserves a request's whole
block chain. Weights from the startup program's seeded draws, on the
device; nothing is rescaled."""

import importlib
import time

from benchmark.builders._program import SEED_MODULUS
from benchmark.builders.decoder_engine import DecoderServer
from benchmark.builders.nemotron_h_engine import NemotronHServer
from benchmark.manifest import model_sizes, published

#: the published keys the model builder takes, under their own names
_BUILDER_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size", "total_ut_steps",
    "early_exit_threshold", "rms_norm_eps", "rope_theta")


class OuroServer(NemotronHServer):
    """``NemotronHServer``'s ``weights`` (the served parameters by the plain
    reference's names) over the looped model; the reference takes no
    expert offset."""

    def __init__(self, engine, entry, config, model, load_s, prefix,
                 reference):
        DecoderServer.__init__(
            self, engine, entry,
            dict(model, num_layers=config["num_hidden_layers"],
                 vocab_size=config["vocab_size"]),
            load_s, prefix, reference)
        self.config = config

    def reference_logits(self, tokens, positions, **control):
        """The plain reference's logits after ``tokens`` at ``positions``,
        the sequence padded to a multiple of 256 (a few compiled shapes,
        all compiled after the window). ``control`` is a control's
        (``round_to``, ``passes``) or a diagnosis's (``round_operands``)."""
        pad_to = min(self.max_len, -(-len(tokens) // 256) * 256)
        return self.reference.logits(self.weights(), self.config, tokens,
                                     positions, pad_to=pad_to, **control)


def build(config, traffic, seed, rehearse):
    from paddle_tpu.serving import GenerationEngine, build_ouro_model

    model = model_sizes(config, rehearse)
    keys = published(config, rehearse)
    settings = config["settings"]
    sizes = {k: keys[k] for k in _BUILDER_KEYS}

    def make():
        m = build_ouro_model(name=config["name"], version="1",
                             dtype=settings["dtype"], **sizes, **model)
        m.startup_program.random_seed = seed % SEED_MODULUS + 1
        return m

    t0 = time.perf_counter()
    engine = GenerationEngine(**settings["engine"])
    entry = engine.register_model(make)
    return OuroServer(
        engine, entry, keys, model, time.perf_counter() - t0,
        prefix=f"{config['name']}_v1.",
        reference=importlib.import_module(
            "benchmark.references." + config["reference"]))
