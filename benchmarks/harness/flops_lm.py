"""Operations and bytes a language model's training step needs, from
shapes: what utilization and the kernels' rooflines are measured against.
Never taken from the compiled program, whose count moves when the program
changes (converts, recomputation); recomputed work is not counted.

Conventions, each the usual one (Kaplan et al. 2020, arXiv:2001.08361,
section 2.1; Chowdhery et al. 2022, arXiv:2204.02311, appendix B):

* a multiply-add is 2 operations; a training step is forward plus backward
  = 3 forward passes, so a weight that a token meets in one multiply-add
  costs 6 operations a token;
* of the routed experts a token meets the expectation under even routing,
  ``top_k x held / experts`` of one expert, so that the count does not move
  with the seed; the router itself is counted whole;
* causal attention: ``q k^T`` and ``p v`` over the lower triangle,
  ``T^2 (d_qk + d_v) heads`` forward a sequence, 3 times that for training;
* the KDA core a chunk of C tokens and a head (arXiv:2510.26692, the
  chunkwise form): the two C x C score matrices over the lower triangle
  (``2 C^2 d_k``), the triangular solve for the pseudo-values
  (``C^2 (d_k + d_v)``), and against the carried state ``W S``, ``Q S``,
  ``K^T U`` (``2 C d_k d_v`` each) and ``M U`` (``C^2 d_v``);
* embedding rows are gathered, norms, gates and the optimizer are
  bandwidth: no operations.
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
    held = d["held"][1] - d["held"][0]
    total = 0.0
    for name, shape in shapes.items():
        n = 1
        for s in shape:
            n *= s
        if name == "embed_weight" or name.endswith(
                ("_gamma", "_A_log", "_dt_bias", "_router_bias")):
            continue
        if "_moe_" in name and "router" not in name:
            n = n / held * d["top_k"] * held / d["router"]
        total += n
    return total


def attention_flops_per_sequence(cfg, train=True):
    d, _ = _dims(cfg)
    t = cfg["sequence_length"]
    layers = [l for l in d["layers"] if l not in d["kda"]]
    fwd = len(layers) * t * t * (d["nope"] + d["rope"] + d["v_dim"]) \
        * d["heads"]
    return (3 if train else 1) * fwd


def kda_flops_per_sequence(cfg, train=True, chunk=64):
    d, _ = _dims(cfg)
    t = cfg["sequence_length"]
    dk = dv = d["kda_dim"]
    c = chunk
    per_chunk_head = 2 * c * c * dk + c * c * (dk + dv) + c * c * dv \
        + 3 * 2 * c * dk * dv
    chunks = -(-t // c)
    fwd = len(d["kda"]) * chunks * d["kda_heads"] * per_chunk_head
    return (3 if train else 1) * fwd


def train_flops_per_sample(cfg):
    """One sample is one sequence of ``sequence_length`` tokens."""
    return 6 * matmul_params_per_token(cfg) * cfg["sequence_length"] \
        + attention_flops_per_sequence(cfg) + kda_flops_per_sequence(cfg)


def kda_bytes_per_sequence(cfg, itemsize=2):
    """The least a training step moves through the KDA core of every KDA
    layer: forward it reads q, k, v and the gate's projection (d_k wide
    each a head) and beta, and writes o; backward it reads those and o's
    cotangent and writes the five gradients: three passes over five
    head-wide arrays a token."""
    d, _ = _dims(cfg)
    t = cfg["sequence_length"]
    per_token = (5 * d["kda_heads"] * d["kda_dim"] + d["kda_heads"]) \
        * itemsize
    return 3 * len(d["kda"]) * t * per_token


def attention_bytes_per_sequence(cfg, itemsize=2):
    """q, k, v read and o written forward; those, o and its cotangent
    read and three gradients written backward."""
    d, _ = _dims(cfg)
    t = cfg["sequence_length"]
    layers = [l for l in d["layers"] if l not in d["kda"]]
    qk, v = d["nope"] + d["rope"], d["v_dim"]
    per_token = d["heads"] * ((2 * qk + 2 * v) + (2 * qk + 3 * v)
                              + (2 * qk + v)) * itemsize
    return len(layers) * t * per_token
