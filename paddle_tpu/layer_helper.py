"""LayerHelper — shared plumbing for the layers DSL.

Reference analog: ``python/paddle/fluid/layer_helper.py`` — creates parameters
in both main and startup programs, temp vars, appends ops and activations.
"""
from __future__ import annotations

from typing import Optional

from .core import unique_name
from .core.dtypes import convert_dtype
from .core.program import (Parameter, default_main_program,
                           default_startup_program)
from .initializer import ConstantInitializer, XavierInitializer
from .param_attr import ParamAttr


class LayerHelper:
    def __init__(self, layer_type: str, **kwargs):
        self.kwargs = kwargs
        self.layer_type = layer_type
        name = kwargs.get("name")
        self.name = name if name is not None else unique_name.generate(layer_type)

    @property
    def main_program(self):
        return default_main_program()

    @property
    def startup_program(self):
        return default_startup_program()

    def append_op(self, *args, **kwargs):
        return self.main_program.current_block().append_op(*args, **kwargs)

    def create_parameter(self, attr, shape, dtype="float32", is_bias: bool = False,
                         default_initializer=None):
        attr = ParamAttr._to_attr(attr)
        if attr is None:
            return None
        suffix = "b" if is_bias else "w"
        if attr.name is None:
            attr.name = unique_name.generate(".".join([self.name, suffix]))
        if default_initializer is None:
            default_initializer = (ConstantInitializer(0.0) if is_bias
                                   else XavierInitializer())
        init = attr.initializer or default_initializer

        dtype = convert_dtype(dtype)
        shape = [int(d) for d in shape]
        gblock = self.main_program.global_block()
        param = gblock.vars.get(attr.name)
        if param is not None:
            # a name the program already holds is that parameter (sharing by
            # ParamAttr(name=...)): one Parameter, one initialiser, one
            # optimizer slot, whatever the later ParamAttr says
            if not isinstance(param, Parameter):
                raise ValueError(
                    f"create_parameter: {attr.name!r} names a variable of "
                    f"the program that is no parameter")
            if list(param.shape) != shape or param.dtype != dtype:
                raise ValueError(
                    f"create_parameter: {attr.name!r} exists with shape "
                    f"{list(param.shape)} {param.dtype}, asked for again "
                    f"with shape {shape} {dtype}")
            return param
        param = self.main_program.current_block().create_parameter(
            name=attr.name, shape=shape, dtype=dtype,
            trainable=attr.trainable, regularizer=attr.regularizer,
            need_clip=attr.need_clip, shard_spec=attr.shard_spec)
        param.optimize_attr = {"learning_rate": attr.learning_rate}

        sblock = self.startup_program.global_block()
        # a startup program shared by two main programs (train and test built
        # under the same names) initialises the parameter once as well
        if attr.name not in sblock.vars:
            svar = sblock.create_var(
                name=attr.name, shape=shape, dtype=dtype, persistable=True)
            init(svar, sblock)
        return param

    def create_variable_for_type_inference(self, dtype="float32", shape=None,
                                           stop_gradient=False):
        return self.main_program.current_block().create_var(
            name=unique_name.generate(".".join([self.name, "tmp"])),
            dtype=convert_dtype(dtype), shape=shape, stop_gradient=stop_gradient)

    def create_global_variable(self, shape, dtype="float32", persistable=True,
                               name=None, stop_gradient=True, initializer=None):
        """Non-parameter persistable state (BN running stats, AUC counters)."""
        name = name or unique_name.generate(".".join([self.name, "gvar"]))
        block = self.main_program.global_block()
        v = block.create_var(name=name, shape=list(shape), dtype=convert_dtype(dtype),
                             persistable=persistable, stop_gradient=stop_gradient)
        sblock = self.startup_program.global_block()
        sv = sblock.create_var(name=name, shape=list(shape),
                               dtype=convert_dtype(dtype), persistable=True)
        (initializer or ConstantInitializer(0.0))(sv, sblock)
        return v

    def append_activation(self, out_var, act: Optional[str]):
        if act is None:
            return out_var
        tmp = self.create_variable_for_type_inference(out_var.dtype, out_var.shape)
        self.append_op(type=act, inputs={"X": [out_var.name]}, outputs={"Out": [tmp.name]}, attrs={})
        return tmp
