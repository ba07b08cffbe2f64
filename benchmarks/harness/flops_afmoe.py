"""Operations and bytes a training step of a window / full grouped-query
attention model with routed experts needs (``references/afmoe.py``'s
layers), from shapes. The conventions are ``harness/flops_lm.py``'s
(Kaplan et al. 2020, arXiv:2001.08361, section 2.1; Chowdhery et al. 2022,
arXiv:2204.02311, appendix B): a multiply-add is 2 operations, a training
step is 3 forward passes, the routed experts at their expectation under
even routing (``top_k`` x held / published experts a token: what a
configuration's ``selection_bias`` holds to the assignment), nothing
recomputed, norms, rotary, gates, the embedding and
the optimizer are bandwidth. What differs:

* attention counts the pairs of query and key that the mask lets through,
  exactly: ``T (T + 1) / 2`` a head in a full layer, ``sum_i min(i + 1,
  W)`` under a window of ``W``; a pair costs ``2 (d + d)`` operations
  forward (``q k^T`` and ``p v``);
* the keys and values carry ``H_kv`` heads: the least a step moves through
  an attention core is q, o, their cotangents and gradient at ``H`` heads
  and k, v and their gradients at ``H_kv``.
"""
from __future__ import annotations

import importlib


def _dims(cfg):
    ref = importlib.import_module("references." + cfg["reference"])
    return ref.dims(cfg), ref.param_shapes(cfg)


def matmul_params_per_token(cfg):
    """Weights that one token meets in a multiply-add, the routed experts
    at their expectation under even routing."""
    d, shapes = _dims(cfg)
    total = 0.0
    for name, shape in shapes.items():
        n = 1
        for s in shape:
            n *= s
        if name == "embed_weight" or name.endswith(("_gamma",
                                                    "_router_bias")):
            continue
        if "_moe_" in name and "router" not in name:
            n = n * d["top_k"] / d["router"]
        total += n
    return total


def visible_pairs(t, window=None):
    """Pairs (query, key) a causal mask lets through in one head of a
    sequence of ``t`` tokens, under a window if there is one."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def _layers(cfg, windowed):
    d, _ = _dims(cfg)
    return [l for l in d["layers"] if (l in d["window_layers"]) == windowed]


def attention_flops_per_sequence(cfg, windowed, train=True):
    """Of the window layers (``windowed``) or of the full ones."""
    d, _ = _dims(cfg)
    pairs = visible_pairs(cfg["sequence_length"],
                          d["window"] if windowed else None)
    fwd = len(_layers(cfg, windowed)) * pairs * 4 * d["head_dim"] * d["heads"]
    return (3 if train else 1) * fwd


def attention_bytes_per_sequence(cfg, windowed, itemsize=2):
    """q, k, v read and o written forward; those, o and its cotangent
    read and three gradients written backward."""
    d, _ = _dims(cfg)
    wide, narrow = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    per_token = ((wide + 2 * narrow + wide)
                 + (wide + 2 * narrow + 2 * wide)
                 + (wide + 2 * narrow)) * itemsize
    return len(_layers(cfg, windowed)) * cfg["sequence_length"] * per_token


def train_flops_per_sample(cfg):
    """One sample is one sequence of ``sequence_length`` tokens."""
    return 6 * matmul_params_per_token(cfg) * cfg["sequence_length"] \
        + attention_flops_per_sequence(cfg, True) \
        + attention_flops_per_sequence(cfg, False)
