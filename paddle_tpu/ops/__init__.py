"""Operator library: importing this package registers every op lowering.

The analog of the reference's static-registrar op library
(reference: paddle/fluid/operators/ — 560 REGISTER_OPERATOR sites); here
registration is module import, and there is one jax lowering per op instead
of per-(place, dtype, layout) kernels.
"""

from paddle_tpu.ops import common  # noqa: F401
from paddle_tpu.ops import math  # noqa: F401
from paddle_tpu.ops import nn  # noqa: F401
from paddle_tpu.ops import tensor  # noqa: F401
from paddle_tpu.ops import optimizers  # noqa: F401
from paddle_tpu.ops import control_flow  # noqa: F401
from paddle_tpu.ops import recompute  # noqa: F401
from paddle_tpu.ops import rnn  # noqa: F401
from paddle_tpu.ops import sequence  # noqa: F401
from paddle_tpu.ops import detection  # noqa: F401
from paddle_tpu.ops import pipeline  # noqa: F401
from paddle_tpu.ops import nn_extra  # noqa: F401
from paddle_tpu.ops import py_func  # noqa: F401
from paddle_tpu.ops import vision  # noqa: F401
from paddle_tpu.ops import moe  # noqa: F401
from paddle_tpu.ops import mamba  # noqa: F401
from paddle_tpu.ops import short_conv  # noqa: F401
from paddle_tpu.ops import misc_extra  # noqa: F401
from paddle_tpu.ops import vision_extra  # noqa: F401
from paddle_tpu.ops import fused  # noqa: F401
from paddle_tpu.ops import yolo_loss  # noqa: F401
from paddle_tpu.ops import extras  # noqa: F401
from paddle_tpu.ops import sharded_embedding  # noqa: F401
from paddle_tpu.ops import crf  # noqa: F401
from paddle_tpu.ops import tail  # noqa: F401
