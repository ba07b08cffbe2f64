"""Operations of a language model's dense products, from shapes: the
matrices that ``FullyConnected`` and ``_contrib_SwiGLU`` apply to every
token (the projections of the attention, KDA and Mamba-2 layers, the dense
and shared feed-forward blocks), by the reference's own list of the
configuration's parameters (``references/<reference>.py::param_shapes``).
The conventions are ``harness/flops_lm.py``'s: a multiply-add is 2
operations, a training step is 3 forward passes, nothing recomputed. Not
among them, each under a scope and a count of its own: the embedding (a
lookup), the head (``mx/lm_head``), the routed experts (three-dimensional:
the grouped products), their router (inside ``_contrib_MoE``), the short
convolutions; vectors are bandwidth.
"""
from __future__ import annotations

import importlib

_NOT_DENSE = ("embed_weight", "head_weight")
_NOT_DENSE_ENDS = ("_conv_weight", "_router_weight")


def dense_weights(cfg):
    """Weights that one token meets in a dense product."""
    ref = importlib.import_module("references." + cfg["reference"])
    return sum(shape[0] * shape[1]
               for name, shape in ref.param_shapes(cfg).items()
               if len(shape) == 2 and name.endswith("_weight")
               and name not in _NOT_DENSE
               and not name.endswith(_NOT_DENSE_ENDS))


def forward_flops(cfg, batch_size):
    """Of one forward pass over a step's batch; None where the
    configuration names no reference (not a language model's)."""
    if "reference" not in cfg or "sequence_length" not in cfg:
        return None
    return 2 * dense_weights(cfg) * cfg["sequence_length"] * batch_size
