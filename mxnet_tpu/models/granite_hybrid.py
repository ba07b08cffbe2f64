"""Symbolic Granite 4.0-H (IBM, ``model_type: granitemoehybrid``; the
public ``transformers`` ``modeling_granitemoehybrid.py``): Mamba-2
state-space layers and grouped-query attention without positions (nine to
one in ``layer_types``), every layer followed by one SwiGLU (the model has
no experts: ``num_local_experts`` 0), a tied embedding and head, and
Granite's four multipliers: the embedding times ``embedding_multiplier``,
every branch times ``residual_multiplier`` before it joins the stream, the
attention scores times ``attention_multiplier`` in the place of ``1 /
sqrt(head)``, the logits over ``logits_scaling``.

The symbol is a training graph for ``Module.fit``, built like
:func:`~mxnet_tpu.models.afmoe.afmoe_symbol`: ``data`` (sequences, tokens)
of token ids, ``softmax_label`` the next ids, one output, every token's
loss. ``layers`` are the published layers kept (0-based, as ``layer_types``
counts them), ``vocab_rows`` the rows of the vocabulary held. Every matrix
is a FullyConnected-style (out, in) variable with its shape stated; every
block is a ``mirror_stage``. With ``tie_word_embeddings`` the one variable
``embed_weight`` is read by ``Embedding`` and by the head: its gradient is
the sum of both uses. The SwiGLU keeps separate gate and up matrices (the
published file stacks them as ``input_linear``: the same numbers).

A Mamba layer's convolution, core and gated norm run under the device scope
``mx/ssm`` (``mx/ssm/conv``; ``mx/ssm/intra`` and ``mx/ssm/scan`` inside
the core), the attention core under ``mx/attn/full``.
"""
from __future__ import annotations

from .. import initializer as _init
from .. import symbol as sym
from ..attribute import AttrScope
from .kimi_linear import _fc, _norm, _swiglu, _var

__all__ = ["granite_hybrid_symbol"]


def _mamba(x, p, hidden, heads, head_dim, state, groups, conv, conv_bias,
           eps, chunk):
    """Mamba-2's mixer: ``[z | xBC | dt] = W_in u``, a causal depthwise
    convolution and silu over ``xBC``, the core, the gated norm, ``W_out``."""
    if groups != 1:
        raise ValueError("mamba_n_groups %d: B and C of one group only"
                         % groups)
    inner, bc = heads * head_dim, 2 * groups * state
    proj = _fc(x, p + "mamba_in", inner + inner + bc + heads, hidden)

    def part(lo, hi):
        return sym.slice_axis(proj, axis=2, begin=lo, end=hi)
    z, xbc, dt = (part(0, inner), part(inner, 2 * inner + bc),
                  part(2 * inner + bc, 2 * inner + bc + heads))
    bias = {"bias": _var(p + "mamba_conv_bias", (inner + bc,)),
            "no_bias": False} if conv_bias else {}
    with AttrScope(device_scope="mx/ssm/conv"):
        xbc = sym.contrib.CausalConv1D(
            data=xbc, weight=_var(p + "mamba_conv_weight", (inner + bc, conv)),
            act_type="silu", name=p + "mamba_conv", **bias)

    def conved(lo, hi):
        return sym.slice_axis(xbc, axis=2, begin=lo, end=hi)
    # the core names its own scope, ``mx/ssm``
    y = sym.contrib.Mamba2(
        x=conved(0, inner), b=conved(inner, inner + state),
        c=conved(inner + state, inner + bc), dt=dt,
        # an Initializer that knows no such names starts a head's decay
        # rate at e, its step's bias at 0 and its skip at 1
        dt_bias=_var(p + "mamba_dt_bias", (heads,), init=_init.Zero()),
        a_log=_var(p + "mamba_A_log", (heads,), init=_init.Constant(1.0)),
        d=_var(p + "mamba_D", (heads,), init=_init.One()),
        num_heads=heads, chunk=chunk, name=p + "mamba")
    with AttrScope(device_scope="mx/ssm"):
        y = sym.contrib.GatedRMSNorm(
            data=y, gate=z, gamma=_var(p + "mamba_norm_gamma", (inner,)),
            eps=eps, name=p + "mamba_norm")
    return _fc(y, p + "mamba_out", hidden, inner)


def _attention(x, p, hidden, heads, kv_heads, dim, scale):
    """Causal grouped-query attention with no positions and no bias, the
    scores times ``scale``."""
    def heads_of(name, n):
        h = sym.Reshape(_fc(x, p + "attn_" + name, n * dim, hidden),
                        shape=(0, 0, n, dim))
        return sym.transpose(h, axes=(0, 2, 1, 3))
    q, k, v = heads_of("q", heads), heads_of("k", kv_heads), \
        heads_of("v", kv_heads)
    with AttrScope(device_scope="mx/attn/full"):
        o = sym.contrib.FlashAttention(q, k, v, causal=True, scale=scale,
                                       name=p + "attn")
    o = sym.Reshape(sym.transpose(o, axes=(0, 2, 1, 3)), shape=(0, 0, -3))
    return _fc(o, p + "attn_o", hidden, heads * dim)


def granite_hybrid_symbol(hidden_size=2048, num_attention_heads=32,
                          num_key_value_heads=8, shared_intermediate_size=8192,
                          mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
                          mamba_n_groups=1, mamba_d_conv=4,
                          mamba_conv_bias=True, mamba_chunk_size=256,
                          rms_norm_eps=1e-5, embedding_multiplier=12.0,
                          attention_multiplier=0.015625,
                          residual_multiplier=0.22, logits_scaling=8.0,
                          tie_word_embeddings=True,
                          layer_types=(("mamba",) * 5 + ("attention",)
                                       + ("mamba",) * 4) * 4,
                          layers=tuple(range(10)), vocab_rows=12544):
    """The training symbol of the published layers ``layers``."""
    hid = hidden_size
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    embed = _var("embed_weight", (vocab_rows, hid))
    x = sym.Embedding(data=data, weight=embed, input_dim=vocab_rows,
                      output_dim=hid, name="embed") * float(embedding_multiplier)
    for l in layers:
        p = "l%d_" % l
        kind = layer_types[l]
        if kind not in ("mamba", "attention"):
            raise ValueError("layer %d: unknown layer type %r" % (l, kind))
        with AttrScope(mirror_stage=str(l)):
            h = _norm(x, p + "input_norm", hid, rms_norm_eps)
            if kind == "mamba":
                h = _mamba(h, p, hid, mamba_n_heads, mamba_d_head,
                           mamba_d_state, mamba_n_groups, mamba_d_conv,
                           mamba_conv_bias, rms_norm_eps, mamba_chunk_size)
            else:
                h = _attention(h, p, hid, num_attention_heads,
                               num_key_value_heads,
                               hid // num_attention_heads,
                               float(attention_multiplier))
            x = x + h * float(residual_multiplier)
            h = _swiglu(_norm(x, p + "post_attn_norm", hid, rms_norm_eps),
                        p + "mlp", hid, shared_intermediate_size)
            x = x + h * float(residual_multiplier)
    x = _norm(x, "final_norm", hid, rms_norm_eps)
    # logits over logits_scaling: the head sees the stream divided by it.
    # The head divides by the tokens of a sequence, Module's default
    # rescale_grad by the sequences: the step follows the mean over tokens
    return sym.contrib.LMHeadLoss(
        data=x * (1.0 / float(logits_scaling)),
        weight=embed if tie_word_embeddings
        else _var("head_weight", (vocab_rows, hid)),
        label=label, normalization="tokens", name="lm_head")
