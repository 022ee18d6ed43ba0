"""ResNet v1 ImageNet training through ``models/resnet.py``: momentum + L2,
one synthetic batch from the seed, repeated."""

import numpy as np

from benchmark.builders._program import ProgramTrainer
from benchmark.manifest import sizes


def build(config, traffic, seed, rehearse):
    from paddle_tpu.models import resnet

    model = sizes(config["model"], rehearse)
    settings = sizes(config["settings"], rehearse)
    t = sizes(traffic, rehearse)
    main, startup, _feeds, fetches = resnet.build_resnet_train(
        depth=model["depth"], class_dim=model["class_dim"],
        image_shape=tuple(model["image_shape"]), lr=settings["lr"],
        use_amp=settings["amp"] == "bf16")
    rng = np.random.default_rng(seed)
    feed = {
        "img": rng.standard_normal(
            (t["batch"], *model["image_shape"]), dtype=np.float32),
        "label": rng.integers(
            0, model["class_dim"], (t["batch"], 1)).astype("int64"),
    }
    return ProgramTrainer(main, startup, fetches[0], feed, seed,
                          t.get("mesh"), t["batch"])
