"""Generate module-level symbolic op functions from the registry
(parity: python/mxnet/symbol/register.py codegen)."""
from __future__ import annotations

import inspect
import sys

from ..ops import registry as _registry
from . import symbol as _symbol


def _make_op_func(op):
    variadic = len(op.input_names) == 0  # ops taking *data (Concat, stack)

    def fn(*args, name=None, **kwargs):
        node_name = _symbol._auto_name(
            op.name.lower().lstrip("_") + "_", name)
        if variadic:
            inputs = [a for a in args if isinstance(a, _symbol.Symbol)]
            sym_kwargs = [(k, v) for k, v in list(kwargs.items())
                          if isinstance(v, _symbol.Symbol)]
            for k, v in sym_kwargs:
                kwargs.pop(k)
                inputs.append(v)
            kwargs.pop("ctx", None)
            return _symbol.invoke_sym(op.name, inputs, kwargs, name=node_name)

        args, kwargs = op.bind_positional(args, kwargs)

        # named input slots: fill from positionals, then keywords, then
        # auto-create parameter variables the reference way
        # (e.g. Convolution(data) -> conv0_weight / conv0_bias variables;
        # SoftmaxOutput(net) -> <name>_label)
        slots = {}
        for slot_name, a in zip(op.input_names, args):
            if a is not None:
                if not isinstance(a, _symbol.Symbol):
                    raise TypeError("%s: input %r must be Symbol, got %r"
                                    % (op.name, slot_name, type(a)))
                slots[slot_name] = a
        for slot_name in op.input_names:
            if slot_name in kwargs and isinstance(kwargs[slot_name],
                                                  _symbol.Symbol):
                slots[slot_name] = kwargs.pop(slot_name)
        kwargs.pop("ctx", None)
        inputs = []
        for slot_name, optional in zip(op.input_names, op.input_optional):
            if slot_name in slots:
                inputs.append(slots[slot_name])
                continue
            if _should_autocreate(op, slot_name, optional, kwargs):
                if slot_name == "label":
                    vname = "%s_label" % node_name
                else:
                    vname = "%s_%s" % (node_name, slot_name)
                inputs.append(_symbol.Variable(vname))
            # else: trailing optional input omitted entirely
        return _symbol.invoke_sym(op.name, inputs, kwargs, name=node_name)
    fn.__name__ = op.name
    fn.__doc__ = op.doc
    return fn


def _should_autocreate(op, slot_name, optional, params):
    if not optional:
        return True  # required array input with no symbol given -> variable
    if slot_name == "bias":
        # the op's own default of ``no_bias`` where the caller gives none:
        # Deconvolution and the causal convolution carry no bias unless asked
        own = inspect.signature(op.fn).parameters.get("no_bias")
        return not params.get("no_bias", bool(own is not None and own.default))
    if slot_name == "label":
        return True  # loss heads auto-create their label variable
    if slot_name == "state_cell":
        return params.get("mode") == "lstm"
    if slot_name == "gamma" and params.get("act_type") == "prelu":
        return True
    return False


def populate(module_name):
    mod = sys.modules[module_name]
    for name in _registry.list_ops():
        op = _registry.get(name)
        setattr(mod, name, _make_op_func(op))
