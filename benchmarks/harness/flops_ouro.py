"""Operations and bytes a training step of a looped language model needs
(``references/ouro.py``'s layers), from shapes. The conventions are
``harness/flops_lm.py``'s (Kaplan et al. 2020, arXiv:2001.08361, section
2.1; Chowdhery et al. 2022, arXiv:2204.02311, appendix B): a multiply-add
is 2 operations, a training step is 3 forward passes, nothing recomputed;
norms, rotary, the exit gate (a product and a sum a token), the exit
distribution, the embedding's lookup and the optimizer are bandwidth. What
is this model's own:

* every held layer's matrices are met ``total_ut_steps`` times a token, and
  so is the head, which reads the stream after every pass: ``P (layers x
  layer's matrices + vocabulary x hidden)`` weights a token;
* attention by the pairs the causal mask lets through
  (``flops_afmoe.visible_pairs``), in every layer of every pass;
* the least a step moves through the heads: forward each pass reads the
  head's matrix and the stream, backward reads both again and writes their
  gradients, at the operands' two bytes.
"""
from __future__ import annotations

from harness.flops_afmoe import visible_pairs
from references import ouro


def matmul_params_per_token(cfg):
    """Weights that one token meets in a multiply-add over all passes."""
    d = ouro.dims(cfg)
    layer = sum(a * b for a, b in (
        s for s in ouro.layer_shapes(d).values() if len(s) == 2))
    return d["passes"] * (len(d["layers"]) * layer
                          + d["vocab"] * d["hidden"])


def head_flops_per_sequence(cfg, train=True):
    """Of the heads of every pass: ``2 x vocabulary x hidden`` a token and
    a pass forward."""
    d = ouro.dims(cfg)
    fwd = d["passes"] * 2 * d["vocab"] * d["hidden"] * cfg["sequence_length"]
    return (3 if train else 1) * fwd


def head_bytes_per_sequence(cfg, itemsize=2):
    """The head's matrix and the stream read forward, read again and their
    gradients written backward, every pass."""
    d = ouro.dims(cfg)
    return d["passes"] * 3 * (d["vocab"] + cfg["sequence_length"]) \
        * d["hidden"] * itemsize


def attention_flops_per_sequence(cfg, train=True):
    d = ouro.dims(cfg)
    fwd = d["passes"] * len(d["layers"]) \
        * visible_pairs(cfg["sequence_length"]) * 4 * d["head_dim"] \
        * d["heads"]
    return (3 if train else 1) * fwd


def train_flops_per_sample(cfg):
    """One sample is one sequence of ``sequence_length`` tokens."""
    return 6 * matmul_params_per_token(cfg) * cfg["sequence_length"] \
        + attention_flops_per_sequence(cfg)
