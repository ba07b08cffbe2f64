"""Symbolic Trinity (arcee-ai, ``model_type: afmoe``): grouped-query
attention of two kinds, three layers that see a sliding window of keys to
one that sees every earlier key (``layer_types``), each with per-head
RMSNorm on q and k, a sigmoid output gate as wide as the heads and rotary
positions on the window layers alone; four norms a layer (before and after
each half); a dense SwiGLU in the leading layers and a sigmoid top-k
expert layer with a shared expert in the others; the embedding scaled by
``sqrt(hidden)``.

The symbol is a training graph for ``Module.fit``, built like
:func:`~mxnet_tpu.models.kimi_linear.kimi_linear_symbol`: ``data``
(sequences, tokens) of token ids, ``softmax_label`` the next ids, one
output, every token's loss. It is one rank's share of a deployment:
``layers`` are the published layers kept (0-based, as ``layer_types``
counts them), ``experts_held = (lo, hi)`` the experts of every expert layer
this rank holds (the router stays ``num_experts`` wide), ``vocab_rows`` the
rows of the vocabulary held. Every matrix is a FullyConnected-style (out,
in) variable with its shape stated; every block is a ``mirror_stage``.

The attention cores run under the device scopes ``mx/attn/window`` and
``mx/attn/full``, the rotary op under its own ``mx/rope``.
"""
from __future__ import annotations

import math

from .. import symbol as sym
from ..attribute import AttrScope
from .kimi_linear import _experts, _fc, _norm, _swiglu, _var

__all__ = ["afmoe_symbol"]


def _attention(x, p, hidden, heads, kv_heads, dim, eps, window, theta):
    """Gated grouped-query attention; ``window`` None is a full layer
    (no positions), else a window layer (rotary on q and k)."""
    def heads_of(name, n):
        return sym.Reshape(_fc(x, p + "attn_" + name, n * dim, hidden),
                           shape=(0, 0, n, dim))
    q = _norm(heads_of("q", heads), p + "attn_q_norm", dim, eps)
    k = _norm(heads_of("k", kv_heads), p + "attn_k_norm", dim, eps)
    v = heads_of("v", kv_heads)
    if window is not None:
        q = sym.contrib.RoPE(q, theta=theta, name=p + "attn_q_rope")
        k = sym.contrib.RoPE(k, theta=theta, name=p + "attn_k_rope")
    q, k, v = (sym.transpose(a, axes=(0, 2, 1, 3)) for a in (q, k, v))
    scope, seen = ("mx/attn/full", {}) if window is None \
        else ("mx/attn/window", {"window": window})
    with AttrScope(device_scope=scope):
        o = sym.contrib.FlashAttention(q, k, v, causal=True, name=p + "attn",
                                       **seen)
    o = sym.Reshape(sym.transpose(o, axes=(0, 2, 1, 3)), shape=(0, 0, -3))
    gate = _fc(x, p + "attn_gate", heads * dim, hidden)
    return _fc(o * sym.sigmoid(gate), p + "attn_o", hidden, heads * dim)


def afmoe_symbol(hidden_size=2048, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, intermediate_size=6144,
                 moe_intermediate_size=1024, num_experts=128,
                 num_experts_per_tok=8, route_scale=2.826, rms_norm_eps=1e-5,
                 num_dense_layers=2, sliding_window=2048, rope_theta=10000,
                 mup_enabled=True,
                 layer_types=("sliding_attention", "sliding_attention",
                              "sliding_attention", "full_attention") * 8,
                 layers=(1, 2, 3, 4, 5), experts_held=(0, 16),
                 vocab_rows=25024):
    """The training symbol of the published layers ``layers``."""
    hid = hidden_size
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, weight=_var("embed_weight", (vocab_rows, hid)),
                      input_dim=vocab_rows, output_dim=hid, name="embed")
    if mup_enabled:
        x = x * math.sqrt(hid)
    for l in layers:
        p = "l%d_" % l
        kind = layer_types[l]
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError("layer %d: unknown layer type %r" % (l, kind))
        window = sliding_window if kind == "sliding_attention" else None
        with AttrScope(mirror_stage=str(l)):
            h = _attention(_norm(x, p + "attn_norm", hid, rms_norm_eps), p,
                           hid, num_attention_heads, num_key_value_heads,
                           head_dim, rms_norm_eps, window, float(rope_theta))
            x = x + _norm(h, p + "post_attn_norm", hid, rms_norm_eps)
            h = _norm(x, p + "ffn_norm", hid, rms_norm_eps)
            if l < num_dense_layers:
                h = _swiglu(h, p + "mlp", hid, intermediate_size)
            else:
                h = _experts(h, p, hid, moe_intermediate_size, num_experts,
                             num_experts_per_tok, tuple(experts_held),
                             route_scale)
            x = x + _norm(h, p + "post_ffn_norm", hid, rms_norm_eps)
    x = _norm(x, "final_norm", hid, rms_norm_eps)
    # the head divides by the tokens of a sequence, Module's default
    # rescale_grad by the sequences: the step follows the mean over tokens
    return sym.contrib.LMHeadLoss(
        data=x, weight=_var("head_weight", (vocab_rows, hid)), label=label,
        normalization="tokens", name="lm_head")
