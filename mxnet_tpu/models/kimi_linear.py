"""Symbolic Kimi-Linear (moonshotai, arXiv:2510.26692; ``model_type:
kimi_linear``): a hybrid of KDA linear-attention layers and latent
attention without positions (3 : 1), a dense SwiGLU in the leading layer
and a sigmoid top-k expert layer with a shared expert in the others.

The symbol is a training graph for ``Module.fit``: ``data`` (sequences,
tokens) of token ids, ``softmax_label`` the next ids, and one output, every
token's loss (sequences, tokens). It is built for one rank's share of a
deployment: ``layers`` are the published layers kept (1-based, in order),
``experts_held = (lo, hi)`` the experts of every expert layer this rank
holds (the router stays ``num_experts`` wide), ``vocab_rows`` the rows of
the vocabulary held (ids and loss are over them). Every matrix is a
FullyConnected-style (out, in) variable with its shape stated, so the
graph binds from the data's shape alone.

Each block's nodes carry ``mirror_stage = <layer>``: the executor
rematerialises a stage in the backward pass, so a step holds the residual
stream of every layer and one layer's interior.
"""
from __future__ import annotations

from .. import initializer as _init
from .. import symbol as sym
from ..attribute import AttrScope

__all__ = ["kimi_linear_symbol"]


def _var(name, shape, init=None):
    return sym.Variable(name, shape=tuple(shape), init=init)


def _fc(x, name, n_out, n_in):
    return sym.FullyConnected(data=x, weight=_var(name + "_weight",
                                                  (n_out, n_in)),
                              num_hidden=n_out, no_bias=True, flatten=False,
                              name=name)


def _norm(x, name, width, eps):
    return sym.RMSNorm(data=x, gamma=_var(name + "_gamma", (width,)),
                       eps=eps, name=name)


def _swiglu(x, name, hidden, inter):
    return sym.contrib.SwiGLU(
        data=x, gate_weight=_var(name + "_gate_weight", (inter, hidden)),
        up_weight=_var(name + "_up_weight", (inter, hidden)),
        down_weight=_var(name + "_down_weight", (hidden, inter)), name=name)


def _kda(x, p, hidden, heads, dim, conv, rank, eps, chunk):
    width = heads * dim
    qkv = [sym.contrib.CausalConv1D(
        data=_fc(x, "%skda_%s" % (p, n), width, hidden),
        weight=_var("%skda_%s_conv_weight" % (p, n), (width, conv)),
        act_type="silu", name="%skda_%s_conv" % (p, n)) for n in "qkv"]
    f = _fc(_fc(x, p + "kda_f_down", rank, hidden), p + "kda_f_up", width,
            rank)
    beta = _fc(x, p + "kda_beta", heads, hidden)
    o = sym.contrib.KDA(
        q=qkv[0], k=qkv[1], v=qkv[2], f=f, b=beta,
        # log of the decay rate a head: the public implementation draws it
        # as log U(1, 16); an Initializer that knows no such name gets 1
        a_log=_var(p + "kda_A_log", (heads,), init=_init.Constant(1.0)),
        dt_bias=_var(p + "kda_dt_bias", (width,)),
        num_heads=heads, chunk=chunk, name=p + "kda")
    gate = _fc(_fc(x, p + "kda_g_down", rank, hidden), p + "kda_g_up",
               width, rank)
    o = sym.Reshape(_norm(sym.Reshape(o, shape=(0, 0, heads, dim)),
                          p + "kda_o_norm", dim, eps), shape=(0, 0, -3))
    return _fc(o * sym.sigmoid(gate), p + "kda_o", hidden, width)


def _mla(x, p, hidden, heads, nope, rope, v_dim, kv_rank, eps):
    qk = nope + rope
    q = sym.Reshape(_fc(x, p + "mla_q", heads * qk, hidden),
                    shape=(0, 0, heads, qk))
    kva = _fc(x, p + "mla_kva", kv_rank + rope, hidden)
    c = _norm(sym.slice_axis(kva, axis=2, begin=0, end=kv_rank),
              p + "mla_kv_norm", kv_rank, eps)
    k_r = sym.slice_axis(kva, axis=2, begin=kv_rank, end=kv_rank + rope)
    kv = sym.Reshape(_fc(c, p + "mla_kvb", heads * (nope + v_dim), kv_rank),
                     shape=(0, 0, heads, nope + v_dim))
    # the positional part of the key is one vector a token, shared by the
    # heads; no rotary on it or on q's (mla_use_nope)
    k_r = sym.broadcast_axis(sym.expand_dims(k_r, axis=2), axis=2,
                             size=heads)
    k = sym.Concat(sym.slice_axis(kv, axis=3, begin=0, end=nope), k_r, dim=3)
    v = sym.slice_axis(kv, axis=3, begin=nope, end=nope + v_dim)
    q, k, v = (sym.transpose(a, axes=(0, 2, 1, 3)) for a in (q, k, v))
    with AttrScope(device_scope="mx/mla"):
        o = sym.contrib.FlashAttention(q, k, v, causal=True,
                                       name=p + "mla_attn")
    o = sym.Reshape(sym.transpose(o, axes=(0, 2, 1, 3)), shape=(0, 0, -3))
    return _fc(o, p + "mla_o", hidden, heads * v_dim)


def _experts(x, p, hidden, inter, n_experts, top_k, held, scale):
    lo, hi = held
    n = hi - lo
    routed = sym.contrib.MoE(
        data=x,
        router_weight=_var(p + "moe_router_weight", (n_experts, hidden)),
        router_bias=_var(p + "moe_router_bias", (n_experts,)),
        gate_weight=_var(p + "moe_gate_weight", (n, inter, hidden)),
        up_weight=_var(p + "moe_up_weight", (n, inter, hidden)),
        down_weight=_var(p + "moe_down_weight", (n, hidden, inter)),
        counters=_var(p + "moe_counters", (4,), init=_init.Zero()),
        experts_held=(lo, hi), top_k=top_k, scale=scale, name=p + "moe")
    return _swiglu(x, p + "shared", hidden, inter) + routed


def kimi_linear_symbol(hidden_size=2304, num_attention_heads=32,
                       kda_num_heads=32, kda_head_dim=128,
                       short_conv_kernel_size=4, kda_gate_low_rank=128,
                       qk_nope_head_dim=128, qk_rope_head_dim=64,
                       v_head_dim=128, kv_lora_rank=512,
                       intermediate_size=9216, moe_intermediate_size=1024,
                       num_experts=256, num_experts_per_token=8,
                       routed_scaling_factor=2.446, rms_norm_eps=1e-5,
                       first_k_dense_replace=1,
                       kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15,
                                   17, 18, 19, 21, 22, 23, 25, 26),
                       layers=(1, 2, 3, 4, 5), experts_held=(0, 8),
                       vocab_rows=20480, kda_chunk=64):
    """The training symbol of the published layers ``layers``; every
    block is a ``mirror_stage`` of its own."""
    hid = hidden_size
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data=data, weight=_var("embed_weight", (vocab_rows, hid)),
                      input_dim=vocab_rows, output_dim=hid, name="embed")
    for l in layers:
        p = "l%d_" % l
        with AttrScope(mirror_stage=str(l)):
            h = _norm(x, p + "attn_norm", hid, rms_norm_eps)
            if l in kda_layers:
                h = _kda(h, p, hid, kda_num_heads, kda_head_dim,
                         short_conv_kernel_size, kda_gate_low_rank,
                         rms_norm_eps, kda_chunk)
            else:
                h = _mla(h, p, hid, num_attention_heads, qk_nope_head_dim,
                         qk_rope_head_dim, v_head_dim, kv_lora_rank,
                         rms_norm_eps)
            x = x + h
            h = _norm(x, p + "ffn_norm", hid, rms_norm_eps)
            if l <= first_k_dense_replace:
                h = _swiglu(h, p + "mlp", hid, intermediate_size)
            else:
                h = _experts(h, p, hid, moe_intermediate_size, num_experts,
                             num_experts_per_token, tuple(experts_held),
                             routed_scaling_factor)
            x = x + h
    x = _norm(x, "final_norm", hid, rms_norm_eps)
    # the one place the loss is normalised: the head divides by the tokens
    # of a sequence, Module's default rescale_grad by the sequences, so the
    # step follows the mean over all tokens
    return sym.contrib.LMHeadLoss(
        data=x, weight=_var("head_weight", (vocab_rows, hid)), label=label,
        normalization="tokens", name="lm_head")
