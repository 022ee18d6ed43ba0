"""LayerHelper: the bridge from layer functions to IR ops.

Same role as the reference's LayerHelper (reference: python/paddle/fluid/
layer_helper.py) — creates parameters (with their init ops in the startup
program), temp output variables, and appends OpDescs to the current block.
Output shapes/dtypes are inferred by abstractly evaluating the op's jax
lowering rule (jax.eval_shape) — one shape-inference implementation shared
with execution, where the reference maintained 560 hand-written InferShape
functions (reference: paddle/fluid/framework/shape_inference.h).
"""

import jax
import jax.numpy as jnp

from paddle_tpu.core.dtypes import to_numpy_dtype
from paddle_tpu.core.ir import default_main_program, default_startup_program
from paddle_tpu.core.registry import OpRegistry
from paddle_tpu.initializer import ConstantInitializer, XavierInitializer
from paddle_tpu.param_attr import ParamAttr
from paddle_tpu.utils import unique_name


# Sentinel concrete size standing in for dynamic (-1) dims during abstract
# evaluation; a large prime so products involving it stay recognizable.
_DYN_SENTINEL = 1031


def infer_op_shapes(op_type, block, inputs, attrs):
    """Abstractly evaluate an op lowering to get output ShapeDtypeStructs.
    Returns {slot: [(shape, dtype_str), ...]} or None if not inferable
    (e.g. value-dependent shapes). Dynamic (-1) dims are traced with a
    sentinel size and mapped back to -1 in the result."""
    if not OpRegistry.has(op_type):
        return None
    op_def = OpRegistry.get(op_type)
    specs = {}
    had_dynamic = False
    for slot, names in inputs.items():
        slot_specs = []
        for n in names:
            v = block._find_var_recursive(n)
            if v is None or v.shape is None:
                return None
            had_dynamic = had_dynamic or any(d < 0 for d in v.shape)
            shape = tuple(_DYN_SENTINEL if d < 0 else d for d in v.shape)
            slot_specs.append(jax.ShapeDtypeStruct(shape, to_numpy_dtype(v.dtype)))
        specs[slot] = slot_specs
    if op_def.stateful:
        specs["__rng_key__"] = [jax.ShapeDtypeStruct((2,), jnp.uint32)]
    clean_attrs = {
        k: v for k, v in attrs.items() if k not in ("op_callstack",)
    }
    from paddle_tpu.ops.common import shape_inference

    try:
        with shape_inference():
            out = jax.eval_shape(
                lambda ins: op_def.lower(ins, clean_attrs), specs)
    except Exception:
        return None
    result = {}
    for slot, vals in out.items():
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        result[slot] = [
            (
                tuple(
                    -1 if had_dynamic and d > 0 and d % _DYN_SENTINEL == 0 else d
                    for d in v.shape
                ),
                str(v.dtype),
            )
            for v in vals
        ]
    return result


class LayerHelper:
    def __init__(self, layer_type, **kwargs):
        self.kwargs = kwargs
        self.layer_type = layer_type
        name = kwargs.get("name")
        self.name = name if name is not None else unique_name.generate(layer_type)
        self.main_program = kwargs.get("main_program") or default_main_program()
        self.startup_program = (
            kwargs.get("startup_program") or default_startup_program()
        )

    @property
    def block(self):
        return self.main_program.current_block()

    @property
    def param_attr(self):
        return ParamAttr._to_attr(self.kwargs.get("param_attr"))

    @property
    def bias_attr(self):
        return ParamAttr._to_attr(self.kwargs.get("bias_attr"))

    def input_dtype(self, var):
        return var.dtype

    def create_parameter(
        self, attr, shape, dtype="float32", is_bias=False, default_initializer=None
    ):
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        suffix = "b" if is_bias else "w"
        name = attr.name or unique_name.generate(f"{self.name}.{suffix}")
        if default_initializer is None:
            default_initializer = (
                ConstantInitializer(0.0) if is_bias else XavierInitializer()
            )
        init = attr.initializer or default_initializer
        # init op goes into the startup program
        sblock = self.startup_program.global_block()
        if name not in sblock.vars:
            svar = sblock.create_var(
                name=name, shape=shape, dtype=dtype, persistable=True
            )
            init(svar, sblock)
        # parameter lives in the main program's global block
        gblock = self.main_program.global_block()
        if name in gblock.vars:
            return gblock.vars[name]
        param = gblock.create_parameter(
            shape,
            dtype,
            name=name,
            trainable=attr.trainable,
            optimize_attr={"learning_rate": attr.learning_rate},
            regularizer=attr.regularizer,
        )
        return param

    def create_variable_for_type_inference(self, dtype="float32", stop_gradient=False):
        return self.block.create_var(
            name=unique_name.generate(self.name + ".tmp"),
            dtype=dtype,
            shape=None,
            persistable=False,
            stop_gradient=stop_gradient,
        )

    def create_global_variable(self, shape, dtype, name=None, persistable=True):
        gblock = self.main_program.global_block()
        return gblock.create_var(
            name=name or unique_name.generate(self.name + ".global"),
            shape=shape,
            dtype=dtype,
            persistable=persistable,
            stop_gradient=True,
        )

    def append_op(self, type, inputs=None, outputs=None, attrs=None):
        op = self.block.append_op(type, inputs, outputs, attrs or {})
        # propagate inferred shapes onto output variables so downstream
        # layers can read .shape at build time
        inferred = infer_op_shapes(type, self.block, op.inputs, op.attrs)
        if inferred:
            for slot, names in op.outputs.items():
                if slot not in inferred:
                    continue
                for (shape, dtype), n in zip(inferred[slot], names):
                    v = self.block.vars.get(n)
                    if v is not None and v.shape is None:
                        v.shape = shape
                        v.dtype = dtype
        return op

    def append_activation(self, out_var):
        act = self.kwargs.get("act")
        if act is None:
            return out_var
        act_out = self.create_variable_for_type_inference(out_var.dtype)
        self.append_op(act, {"X": [out_var.name]}, {"Out": [act_out.name]})
        return act_out

    def append_bias_op(self, out_var, bias, axis=1):
        tmp = self.create_variable_for_type_inference(out_var.dtype)
        self.append_op(
            "elementwise_add",
            {"X": [out_var.name], "Y": [bias.name]},
            {"Out": [tmp.name]},
            {"axis": axis},
        )
        return tmp
