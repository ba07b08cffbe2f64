"""Symbolic Ouro (ByteDance Seed, ``model_type: ouro``; *Scaling Latent
Reasoning via Looped Language Models*, 2025, and the public
``modeling_ouro.py``): a looped language model. One stack of decoder
layers runs ``total_ut_steps`` times over its own output; after every pass
the shared final norm, the head and an exit gate read the stream, and
training weights each pass's loss by the exit distribution the gates give.

With ``x`` the ids, ``E`` the embedding, ``W_h`` the head and ``h = E[x]``,
pass ``t = 1 .. P`` runs every held layer in order:

* ``a = Attn(RMSNorm_in(h))``, ``h <- h + RMSNorm_in2(a)``: causal
  attention, heads of ``head_dim``, no bias, rotary (``rotate_half``, theta
  ``rope_theta``) on q and k, scores times ``1 / sqrt(head_dim)``;
* ``f = SwiGLU(RMSNorm_post(h))``, ``h <- h + RMSNorm_post2(f)`` (the four
  norms are ``input_layernorm(_2)`` and ``post_attention_layernorm(_2)``);

then ``h_t = RMSNorm_final(h)``, which the head, the gate and the next pass
read. ``L_t = CE(h_t W_h^T, next id)``, ``lambda_t = sigmoid(w_g . h_t +
b_g)``, ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` (t < P), ``p_P =
prod_{j<P} (1 - lambda_j)``; a token's loss is ``sum_t p_t L_t + beta sum_t
p_t log p_t`` (``ops/lm_ops.py::loop_exit_loss``; gate, distribution and
loss in float32).

The symbol is a training graph for ``Module.fit``, built like
:func:`~mxnet_tpu.models.granite_hybrid.granite_hybrid_symbol`: ``data``
(sequences, tokens) of ids, ``softmax_label`` the next ids, one output,
every token's loss. ``layers`` are the published layers held (0-based),
``vocab_rows`` the rows of the vocabulary held; the head is a matrix of
its own (the published file unties it). Every layer's variables
exist once and are read by each of its passes, whose gradients add up;
each (pass, layer) is a ``mirror_stage`` of its own, the last of a pass
holding the final norm and the gate. The attention core runs under the
device scope ``mx/attn/full``, the heads under ``mx/lm_head``, the gates
and the weighted loss under ``mx/exit``.
"""
from __future__ import annotations

from .. import initializer as _init
from .. import symbol as sym
from ..attribute import AttrScope
from .kimi_linear import _var

__all__ = ["ouro_symbol"]


def _layer_vars(p, hidden, heads, kv_heads, dim, inter):
    """A layer's variables, made once for all its passes."""
    shapes = {"attn_q_weight": (heads * dim, hidden),
              "attn_k_weight": (kv_heads * dim, hidden),
              "attn_v_weight": (kv_heads * dim, hidden),
              "attn_o_weight": (hidden, heads * dim),
              "mlp_gate_weight": (inter, hidden),
              "mlp_up_weight": (inter, hidden),
              "mlp_down_weight": (hidden, inter)}
    for norm in ("input_norm", "input_norm_2", "post_attn_norm",
                 "post_attn_norm_2"):
        shapes[norm + "_gamma"] = (hidden,)
    return {k: _var(p + k, s) for k, s in shapes.items()}


def _layer(x, w, p, hidden, heads, kv_heads, dim, theta, eps):
    """One pass through one layer, the sandwich norms around each half."""
    def norm(h, name):
        return sym.RMSNorm(data=h, gamma=w[name + "_gamma"], eps=eps)

    def fc(h, name, n_out):
        return sym.FullyConnected(data=h, weight=w[name + "_weight"],
                                  num_hidden=n_out, no_bias=True,
                                  flatten=False)

    u = norm(x, "input_norm")

    def heads_of(name, n):
        return sym.Reshape(fc(u, "attn_" + name, n * dim),
                           shape=(0, 0, n, dim))
    q = sym.contrib.RoPE(heads_of("q", heads), theta=theta)
    k = sym.contrib.RoPE(heads_of("k", kv_heads), theta=theta)
    q, k, v = (sym.transpose(a, axes=(0, 2, 1, 3))
               for a in (q, k, heads_of("v", kv_heads)))
    with AttrScope(device_scope="mx/attn/full"):
        o = sym.contrib.FlashAttention(q, k, v, causal=True, name=p + "attn")
    o = sym.Reshape(sym.transpose(o, axes=(0, 2, 1, 3)), shape=(0, 0, -3))
    x = x + norm(fc(o, "attn_o", hidden), "input_norm_2")
    f = sym.contrib.SwiGLU(
        data=norm(x, "post_attn_norm"), gate_weight=w["mlp_gate_weight"],
        up_weight=w["mlp_up_weight"], down_weight=w["mlp_down_weight"])
    return x + norm(f, "post_attn_norm_2")


def ouro_symbol(hidden_size=2048, num_attention_heads=16,
                num_key_value_heads=16, head_dim=128, intermediate_size=5632,
                rms_norm_eps=1e-6, rope_theta=1000000.0, total_ut_steps=4,
                exit_entropy_weight=0.1, layers=tuple(range(6)),
                vocab_rows=49152):
    """The training symbol of the published layers ``layers``, run
    ``total_ut_steps`` times."""
    hid = hidden_size
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    embed = _var("embed_weight", (vocab_rows, hid))
    head = _var("head_weight", (vocab_rows, hid))
    final = _var("final_norm_gamma", (hid,))
    gate_w = _var("exit_gate_weight", (1, hid))
    gate_b = _var("exit_gate_bias", (1,), init=_init.Zero())
    held = [(l, _layer_vars("l%d_" % l, hid, num_attention_heads,
                            num_key_value_heads, head_dim, intermediate_size))
            for l in layers]
    x = sym.Embedding(data=data, weight=embed, input_dim=vocab_rows,
                      output_dim=hid, name="embed")
    logits, rows = [], []
    for t in range(total_ut_steps):
        for i, (l, w) in enumerate(held):
            p = "p%d_l%d_" % (t, l)
            with AttrScope(mirror_stage=p[:-1]):
                x = _layer(x, w, p, hid, num_attention_heads,
                           num_key_value_heads, head_dim, float(rope_theta),
                           rms_norm_eps)
                if i == len(held) - 1:
                    # what the head and the gate read and the next pass
                    # starts from
                    x = sym.RMSNorm(data=x, gamma=final, eps=rms_norm_eps,
                                    name="final_norm%d" % t)
                    logits.append(sym.contrib.ExitGate(
                        x, gate_w, gate_b, name="exit_gate%d" % t))
        rows.append(sym.contrib.LMHeadLoss(data=x, weight=head, label=label,
                                           name="lm_head%d" % t))
    # the gates first: each pass's gate follows its stage in the
    # topological order, so that a stage's nodes stay one run
    return sym.contrib.LoopExitLoss(
        sym.stack(*logits, axis=0), sym.stack(*rows, axis=0),
        _var("exit_counters", (4,), init=_init.Zero()),
        beta=float(exit_entropy_weight), name="exit")
