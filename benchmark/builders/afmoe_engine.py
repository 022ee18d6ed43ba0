"""An ``afmoe`` decoder (``serving/decode/hybrid.py build_afmoe_model``:
sliding-window and full attention layers in one stack, gated, four norms a
layer, sigmoid-routed gated experts of which this chip holds a share beside
a shared expert) hosted by a ``GenerationEngine`` like any other model:
every prompt through the chunked prefill, admission by reservation,
continuous batching, launch-ahead. Weights from the startup program's seeded
draws, on the device; nothing is rescaled.

The model's attention layers fall into TWO groups with a block pool each, so
this configuration's ``model`` carries a block count a group:
``num_blocks`` for the full layers' pool and ``window_num_blocks`` for the
sliding layers', whose sequences give back the blocks behind their window
(``serving/decode/kvstate.py``, "Layer groups")."""

import importlib
import time

from benchmark.builders._program import SEED_MODULUS
from benchmark.builders.mistral4_engine import Mistral4Server
from benchmark.manifest import model_sizes, published, sizes

#: the published keys the model builder takes, under their own names
_BUILDER_KEYS = (
    "vocab_size", "hidden_size", "layer_types", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size",
    "num_dense_layers", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "sliding_window", "num_shared_experts",
    "route_scale", "route_norm", "mup_enabled", "rms_norm_eps", "rope_theta")


#: the lengths the reference's sequences are padded to (past the last, to
#: its next multiple of 4,096): THREE compiled lengths, all compiled after
#: the window. A length compiles for ~30 s on the chip and a padded
#: position costs little beside that (PERF.md section 6, PR 59: the run's
#: clock); or the slot's length where that is shorter
_PADS = (8192, 32768)


class AfmoeServer(Mistral4Server):
    """``Mistral4Server``'s interface over the ``afmoe`` model: the served
    parameters by the plain reference's names, the arenas of BOTH groups
    left out; the reference has no cache, so a sequence may be padded past
    the slot's length."""

    def weights(self):
        arenas = {n[len(self.prefix):]
                  for names in self.entry.model.all_state_names
                  for n in names}
        return {name: value for name, value in super().weights().items()
                if name not in arenas}

    def reference_logits(self, tokens, positions, **control):
        """The plain reference's logits after ``tokens`` at ``positions``.
        ``control`` is a control's: ``round_to`` (the reference in a
        precision below the served one) or the description misread
        (``sliding_window=``, ``rotate_full=``, ``gate=``,
        ``route_scale=``)."""
        # the float32 pass over a 32k-token sequence needs the room the
        # arenas hold (4.4 GB at the published size)
        self.entry.release_states()
        n = len(tokens)
        pad_to = next((p for p in _PADS if n <= p), -(-n // 4096) * 4096)
        return self.reference.logits(
            self.weights(), self.config, tokens, positions,
            pad_to=min(pad_to, self.max_len) if n <= self.max_len else pad_to,
            expert_offset=self.expert_offset, **control)

def build(config, traffic, seed, rehearse):
    from paddle_tpu.serving import GenerationEngine, build_afmoe_model

    model = model_sizes(config, rehearse)
    keys = published(config, rehearse)
    settings = sizes(config["settings"], rehearse)
    published_sizes = {k: keys[k] for k in _BUILDER_KEYS}

    def make():
        m = build_afmoe_model(
            name=config["name"], version="1", dtype=settings["dtype"],
            expert_rank=settings["expert_rank"],
            initializer_range=settings["initializer_range"],
            **published_sizes, **model)
        m.startup_program.random_seed = seed % SEED_MODULUS + 1
        return m

    t0 = time.perf_counter()
    engine = GenerationEngine(**settings["engine"])
    entry = engine.register_model(make)
    return AfmoeServer(
        engine, entry, keys, model, time.perf_counter() - t0,
        prefix=f"{config['name']}_v1.",
        reference=importlib.import_module(
            "benchmark.references." + config["reference"]),
        expert_offset=settings["expert_rank"] * keys["num_experts"])
