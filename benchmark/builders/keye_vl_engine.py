"""A ``keye_vl`` decoder (``serving/decode/hybrid.py build_keye_vl_model``:
``sdar_moe``'s stack a token a step with an indexer a layer, whose keys lie in
a third paged arena and whose top rows are all a query attends to) hosted by a
``GenerationEngine`` like any other model: every prompt through the chunked
prefill, admission by reservation, continuous batching, launch-ahead. Weights
from the startup program's seeded draws, on the device; nothing is
rescaled."""

import importlib
import time

from benchmark.builders._program import SEED_MODULUS
from benchmark.builders.afmoe_engine import AfmoeServer
from benchmark.manifest import model_sizes, published, sizes

#: the published keys the model builder takes, under their own names
_BUILDER_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "norm_topk_prob", "rms_norm_eps", "rope_theta",
    "sa_config")

#: the lengths the reference's sequences are padded to: TWO compiled
#: lengths, both compiled after the window (a layer of the reference costs
#: as the square of the padded length: PERF.md section 6, PR 63)
_PADS = (20480, 32768)


class KeyeVlServer(AfmoeServer):
    """``AfmoeServer``'s interface (the served parameters by the plain
    reference's names, every arena left out, the indexer's too) over the
    ``keye_vl`` model."""

    def reference_logits(self, tokens, positions, **control):
        """The plain reference's logits after ``tokens`` at ``positions``.
        ``control`` is a control's: ``round_to`` (the reference in a
        precision below the served one) or the description misread
        (``select=``, ``topk=``, ``relu=``, ``rotate_index_keys=``,
        ``index_lag=``, ``tie_low=``)."""
        # the float32 pass over a 32k-token sequence needs the room the
        # arenas hold (9 GB at the published size)
        self.entry.release_states()
        n = len(tokens)
        pad_to = next((p for p in _PADS if n <= p), -(-n // 4096) * 4096)
        t0 = time.perf_counter()
        rows = self.reference.logits(
            self.weights(), self.config, tokens, positions,
            pad_to=min(pad_to, self.max_len) if n <= self.max_len else pad_to,
            expert_offset=self.expert_offset, kth="bisect", **control)
        # (the run's clock: PERF.md section 6, PR 63)
        print(f"# reference: {n} tokens padded to {pad_to}"
              f"{' ' + repr(control) if control else ''}: "
              f"{time.perf_counter() - t0:.2f} s", flush=True)
        return rows


def build(config, traffic, seed, rehearse):
    from paddle_tpu.serving import GenerationEngine, build_keye_vl_model

    model = model_sizes(config, rehearse)
    keys = published(config, rehearse)
    settings = sizes(config["settings"], rehearse)
    published_sizes = {k: keys[k] for k in _BUILDER_KEYS}

    def make():
        m = build_keye_vl_model(
            name=config["name"], version="1", dtype=settings["dtype"],
            expert_rank=settings["expert_rank"],
            initializer_range=settings["initializer_range"],
            **published_sizes, **model)
        m.startup_program.random_seed = seed % SEED_MODULUS + 1
        return m

    t0 = time.perf_counter()
    engine = GenerationEngine(**settings["engine"])
    entry = engine.register_model(make)
    return KeyeVlServer(
        engine, entry, keys, model, time.perf_counter() - t0,
        prefix=f"{config['name']}_v1.",
        reference=importlib.import_module(
            "benchmark.references." + config["reference"]),
        expert_offset=settings["expert_rank"] * keys["num_experts"])
