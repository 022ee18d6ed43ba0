"""The repo's servable decoder (``serving/decode/model.py
build_decoder_model``) hosted by a ``GenerationEngine``: paged arena,
chunked prefill, continuous batching; weights from the startup program on
the device, seeded."""

import importlib
import time

from benchmark.builders._program import SEED_MODULUS
from benchmark.manifest import model_sizes


class DecoderServer:
    """What the serving drivers drive: the engine, its one entry and the
    geometry the traffic generator needs."""

    def __init__(self, engine, entry, model, load_s, prefix, reference):
        self.engine = engine
        self.entry = entry
        self.prefix = prefix
        self.reference = reference
        self.num_layers = model["num_layers"]
        self.vocab_size = model["vocab_size"]
        self.max_len = model["max_len"]
        self.slots = model["slots"]
        self.load_s = load_s
        self.devices = [engine.device]

    def weights(self):
        """The served weights as plain arrays by the name the plain reference
        knows them under (the program's parameter name less its
        ``<name>_v<version>.`` prefix); the K/V arenas are left out."""
        scope, cut = self.entry._scope, len(self.prefix)
        parts = ["tok_emb", "pos_emb", "head.w", "head.b"] + [
            f"l{i}.{part}.{wb}" for i in range(self.num_layers)
            for part in ("q", "k", "v", "out", "ffn1", "ffn2")
            for wb in ("w", "b")]
        return {name[cut:]: scope.find_var(name)
                for name in (self.prefix + part for part in parts)}

    def reference_logits(self, tokens, positions):
        """The plain reference's logits after ``tokens`` at ``positions``,
        from the served weights."""
        return self.reference.logits(self.weights(), self.num_layers, tokens,
                                     positions, pad_to=self.max_len)

    def compiled_bytes(self):
        """The largest of the entry's executables by XLA's
        ``memory_analysis()``; the arena and the weights are its arguments."""
        worst = 0
        for _lowered, executable in self.entry._entries.values():
            a = executable.memory_analysis()
            worst = max(worst, a.argument_size_in_bytes
                        + a.temp_size_in_bytes + a.output_size_in_bytes
                        - a.alias_size_in_bytes)
        return worst


def _rescale(scope, weights):
    """Multiplies, on the device, every parameter whose name ends in a key
    of ``weights["scale"]`` by that key's factor. The startup program draws
    Xavier weights, under which the position embedding drowns the token
    embedding and every answer is one token repeated; the factors (the
    configuration's file says which, and why) make the served text depend
    on the prompt, so that the comparison with the reference can fail."""
    import jax

    for suffix, factor in weights.get("scale", {}).items():
        for name in scope.var_names():
            if name.endswith("." + suffix):
                value = scope.find_var(name)
                if not isinstance(value, jax.Array):
                    raise TypeError(f"{name} is not on the device")
                scope.set(name, value * factor)


def build(config, traffic, seed, rehearse):
    from paddle_tpu.serving import GenerationEngine, build_decoder_model

    model = model_sizes(config, rehearse)

    def make():
        m = build_decoder_model(name=config["name"], version="1", **model)
        m.startup_program.random_seed = seed % SEED_MODULUS + 1
        return m

    t0 = time.perf_counter()
    engine = GenerationEngine(**config["settings"]["engine"])
    entry = engine.register_model(make)
    _rescale(entry._scope, config["settings"].get("weights", {}))
    return DecoderServer(engine, entry, model, time.perf_counter() - t0,
                         prefix=f"{config['name']}_v1.",
                         reference=importlib.import_module(
                             "benchmark.references." + config["reference"]))
