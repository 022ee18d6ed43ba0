"""BERT pretraining (MLM + NSP) through ``models/bert.py``, with
``bench.py``'s settings: one synthetic batch from the seed, repeated."""

import numpy as np

from benchmark.builders._program import ProgramTrainer
from benchmark.manifest import sizes


def build(config, traffic, seed, rehearse):
    from paddle_tpu.models import bert
    from paddle_tpu.utils.flags import flags

    model = sizes(config["model"], rehearse)
    settings = sizes(config["settings"], rehearse)
    t = sizes(traffic, rehearse)
    cfg = bert.BertConfig(**model)
    cfg.use_flash_attention = settings["flash_attention"]
    cfg.attention_probs_dropout_prob = settings["attention_probs_dropout_prob"]
    flags.rng_impl = settings["rng_impl"]
    max_pred = settings["max_predictions_per_seq"]
    main, startup, _feeds, fetches = bert.build_bert_pretrain(
        cfg, seq_len=t["seq_len"], lr=settings["lr"],
        use_amp=settings["amp"] == "bf16", max_predictions_per_seq=max_pred)
    feed = bert.synthetic_batch(
        np.random.RandomState(seed % 2**32), t["batch"], t["seq_len"], cfg,
        max_predictions_per_seq=max_pred)
    return ProgramTrainer(main, startup, fetches[0], feed, seed,
                          t.get("mesh"), t["batch"])
