"""Operations and bytes a training step of a Mamba-2 / attention hybrid
needs (``references/granite_hybrid.py``'s layers), from shapes. The
conventions are ``harness/flops_lm.py``'s (Kaplan et al. 2020,
arXiv:2001.08361, section 2.1; Chowdhery et al. 2022, arXiv:2204.02311,
appendix B): a multiply-add is 2 operations, a training step is 3 forward
passes, nothing recomputed; norms, the depthwise convolution, gates, the
decays, the ``D`` skip, the embedding's lookup and the optimizer are
bandwidth. What is this model's own:

* the tied matrix is met once a token, as the head (the embedding reads a
  row of it);
* the state-space core is counted as the chunked (SSD) algorithm of
  arXiv:2405.21060 at the configuration's ``mamba_chunk_size`` ``c``,
  whatever implements the op: a chunk's ``C B^T`` (``2 c^2 N``, once for
  the one group of B and C), its masked decays times that against ``delta
  x`` (``2 c^2 P`` a head, whole blocks), its own state (``2 c P N`` a
  head) and ``C`` against the state carried in (``2 c N P`` a head);
* the least a step moves through a state-space layer's scope is x, z, B,
  C and dt read and y written once forward, their gradients written and
  y's cotangent read once backward;
* attention by the pairs the causal mask lets through
  (``flops_afmoe.visible_pairs``), at this file's 32 heads of 64.
"""
from __future__ import annotations

import importlib

from harness.flops_afmoe import visible_pairs

_NOT_MATRICES = ("_gamma", "_conv_weight", "_conv_bias", "_dt_bias",
                 "_A_log", "_D")


def _dims(cfg):
    ref = importlib.import_module("references." + cfg["reference"])
    return ref.dims(cfg), ref.param_shapes(cfg)


def matmul_params_per_token(cfg):
    """Weights that one token meets in a multiply-add: every matrix, the
    tied one once (as the head); without the tie the embedding's rows are
    a lookup."""
    d, shapes = _dims(cfg)
    total = 0
    for name, shape in shapes.items():
        if name.endswith(_NOT_MATRICES) \
                or name == "embed_weight" and not d["tied"]:
            continue
        n = 1
        for s in shape:
            n *= s
        total += n
    return total


def ssm_flops_per_sequence(cfg, train=True):
    """Of the state-space cores of every Mamba layer held."""
    d, _ = _dims(cfg)
    c, h, p, n = d["m_chunk"], d["m_heads"], d["m_head"], d["m_state"]
    per_token = 2 * c * n * d["m_groups"] + 2 * c * p * h + 4 * p * n * h
    fwd = len(d["mamba_layers"]) * cfg["sequence_length"] * per_token
    return (3 if train else 1) * fwd


def ssm_bytes_per_sequence(cfg, itemsize=2):
    """x, z, B, C, dt read and y written forward; y's cotangent read and
    the five gradients written backward."""
    d, _ = _dims(cfg)
    inner = d["m_heads"] * d["m_head"]
    read = 2 * inner + 2 * d["m_groups"] * d["m_state"] + d["m_heads"]
    per_token = 2 * (read + inner) * itemsize
    return len(d["mamba_layers"]) * cfg["sequence_length"] * per_token


def attention_flops_per_sequence(cfg, train=True):
    d, _ = _dims(cfg)
    layers = len(d["layers"]) - len(d["mamba_layers"])
    fwd = layers * visible_pairs(cfg["sequence_length"]) \
        * 4 * d["head_dim"] * d["heads"]
    return (3 if train else 1) * fwd


def train_flops_per_sample(cfg):
    """One sample is one sequence of ``sequence_length`` tokens."""
    return 6 * matmul_params_per_token(cfg) * cfg["sequence_length"] \
        + ssm_flops_per_sequence(cfg) + attention_flops_per_sequence(cfg)
