"""Plain reference of one training step of Ouro (ByteDance Seed,
``model_type: ouro``; *Scaling Latent Reasoning via Looped Language
Models*, 2025, the public ``modeling_ouro.py`` and the model's
``config.json``), cut to one chip's share of the stack: forward, loss,
gradients and the Adam step in straightforward ``jax.numpy``, float32 at
the highest matmul precision. It imports nothing of the program: the
benchmark makes the weights here from the seed, hands them to the program
and keeps a copy for this file. What it shares with
``references/afmoe.py`` (the linear map, RMSNorm, SwiGLU, rotary, the
masked softmax, Adam, the operand controls) it takes from there.

With ``x`` the ids, ``E`` the embedding (vocabulary x hidden), ``W_h`` the
untied head, every matrix stored (out, in), no bias but the gate's, and
``h = E[x]``; pass ``t = 1 .. P`` (``total_ut_steps``, 4) runs the held
layers in order (eps 1e-6):

* ``a = Attn(RMSNorm_in(h))``, ``h <- h + RMSNorm_in2(a)``; attention is 16
  heads of 128 over 16 key and value heads, key ``j <= i``, rotary on q and
  k (``x cos + rotate_half(x) sin``, halves split at 64, theta 1,000,000,
  positions 0 .. T-1), ``softmax(q k^T / sqrt(128)) v``, then ``W_o``;
* ``f = SwiGLU(RMSNorm_post(h))`` (width 5,632), ``h <- h +
  RMSNorm_post2(f)``; the norms are the public code's
  ``input_layernorm(_2)`` and ``post_attention_layernorm(_2)``;
* after the last layer ``h_t = RMSNorm_final(h)``: the head, the gate and
  pass ``t + 1`` read it;
* ``L_t = CE(h_t W_h^T, next id)`` over the vocabulary;
  ``lambda_t = sigmoid(w_g . h_t + b_g)`` a token;
* ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < P``, ``p_P =
  prod_{j<P} (1 - lambda_j)``; a token's loss is ``sum_t p_t L_t + beta
  sum_t p_t log p_t`` (beta 0.1), and the loss that is differentiated is
  its mean over all tokens; the gate, the distribution and the loss are
  float32 whatever the operands (the gate a product and a sum, exact);
* Adam as MXNet 1.x writes it (``references/afmoe.py::adam``).

Departures, for memory and the compile's length only: the passes are a
scan (one pass compiled, its weights the same at every step), each pass,
each layer, each block of 128 attention rows and of 2,048 head tokens is
rematerialised (``jax.checkpoint``); attention is an explicit masked
softmax over all the keys for a block of query rows at a time. The
arithmetic is unchanged.

``fault`` plants one wrong piece of mathematics by name (``FAULTS``): the
calibration and the tests show that the comparison catches each.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from references.afmoe import (HIGHEST, _identity, _linear, adam,  # noqa: F401
                              bf16_operand, fp8_operand, masked_attention,
                              rms_norm, rotary, swiglu)

FAULTS = ("one_pass", "uniform_exit", "no_entropy", "raw_carry",
          "no_post_norms", "rope_1e4")
HEAD_BLOCK = 2048       # tokens of the head rematerialised together


def dims(cfg):
    """The sizes of the cut, from the configuration file's own keys (the
    published ``config.json`` names)."""
    return {
        "hidden": cfg["hidden_size"],
        "layers": cfg["layers"],
        "passes": cfg["total_ut_steps"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "inter": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"],
        "theta": float(cfg["rope_theta"]),
        "beta": float(cfg["exit_entropy_weight"]),
    }


def layer_shapes(d):
    """A layer's leaves, without its ``l<n>_`` prefix."""
    hid, dh = d["hidden"], d["head_dim"]
    s = {"attn_q_weight": (d["heads"] * dh, hid),
         "attn_k_weight": (d["kv_heads"] * dh, hid),
         "attn_v_weight": (d["kv_heads"] * dh, hid),
         "attn_o_weight": (hid, d["heads"] * dh),
         "mlp_gate_weight": (d["inter"], hid),
         "mlp_up_weight": (d["inter"], hid),
         "mlp_down_weight": (hid, d["inter"])}
    for norm in ("input_norm", "input_norm_2", "post_attn_norm",
                 "post_attn_norm_2"):
        s[norm + "_gamma"] = (hid,)
    return s


def param_shapes(cfg):
    """name -> shape, under the names the program's symbol gives its
    variables."""
    d = dims(cfg)
    hid = d["hidden"]
    s = {"embed_weight": (d["vocab"], hid), "head_weight": (d["vocab"], hid),
         "final_norm_gamma": (hid,), "exit_gate_weight": (1, hid),
         "exit_gate_bias": (1,)}
    for l in d["layers"]:
        s.update({"l%d_%s" % (l, k): v for k, v in layer_shapes(d).items()})
    return s


@functools.lru_cache(maxsize=None)
def _leaf_maker(kind, shape, device):
    """One leaf from its key: matrices (the gate's among them)
    normal(0, 0.02), norms at 1, the gate's bias 0."""
    def make(key):
        if kind == "ones":
            return jnp.ones(shape, jnp.float32)
        if kind == "zeros":
            return jnp.zeros(shape, jnp.float32)
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    return jax.jit(make, device=device)


def init_params(cfg, seed, device=None):
    """name -> float32 array from the seed, leaf by leaf (each leaf's key
    is the seed's folded with the leaf's rank among the sorted names), on
    ``device`` (``None``: JAX's default)."""
    key = jax.random.PRNGKey(seed % (2 ** 31))

    def kind(name):
        if name.endswith("_gamma"):
            return "ones"
        return "zeros" if name == "exit_gate_bias" else "normal"
    return {name: _leaf_maker(kind(name), tuple(shape), device)(
                jax.random.fold_in(key, i))
            for i, (name, shape) in enumerate(
                sorted(param_shapes(cfg).items()))}


def layer_of(params, l):
    """Layer ``l``'s leaves, without their prefix."""
    p = "l%d_" % l
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}


def layer(d, p, h, operand, fault=None):
    """One pass through one layer, the sandwich norms around each half."""
    b, t, _ = h.shape
    nh, nk, dh = d["heads"], d["kv_heads"], d["head_dim"]
    post = (lambda x, name: x) if fault == "no_post_norms" \
        else (lambda x, name: rms_norm(x, p[name], d["eps"]))
    u = rms_norm(h, p["input_norm_gamma"], d["eps"])
    theta = 1e4 if fault == "rope_1e4" else d["theta"]
    q = rotary(_linear(u, p["attn_q_weight"], operand).reshape(b, t, nh, dh),
               theta)
    k = rotary(_linear(u, p["attn_k_weight"], operand).reshape(b, t, nk, dh),
               theta)
    v = _linear(u, p["attn_v_weight"], operand).reshape(b, t, nk, dh)
    kv_of = jnp.arange(nh) // (nh // nk)    # the KV head a query head reads
    o = masked_attention(q, k[:, :, kv_of], v[:, :, kv_of], None, operand)
    h = h + post(_linear(o.reshape(b, t, nh * dh), p["attn_o_weight"],
                         operand), "input_norm_2_gamma")
    z = rms_norm(h, p["post_attn_norm_gamma"], d["eps"])
    f = swiglu(z, p["mlp_gate_weight"], p["mlp_up_weight"],
               p["mlp_down_weight"], operand)
    return h + post(f, "post_attn_norm_2_gamma")


def head_rows(w, x, label, operand, block=HEAD_BLOCK):
    """Every token's cross-entropy of ``label`` under ``softmax(x W^T)``,
    for ``block`` tokens at a time. x: (N, hidden)."""
    n = x.shape[0]
    block = min(block, n)
    pad = (-n) % block

    @jax.checkpoint
    def rows(a):
        xb, lb = a
        logp = jax.nn.log_softmax(_linear(xb, w, operand), axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[-1])
    lb = jnp.pad(label, (0, pad)).reshape(-1, block)
    return lax.map(rows, (xb, lb)).reshape(-1)[:n]


def exit_gate(params, h):
    """Every token's gate logit ``w_g . h + b_g``, float32, a product and
    a sum."""
    return jnp.sum(h * params["exit_gate_weight"][0], -1) \
        + params["exit_gate_bias"][0]


def passes(cfg, params, data, label, operand=None, fault=None):
    """(every pass's loss a token (P, B, T), every pass's gate logit a
    token (P, B, T))."""
    operand = operand or _identity
    d = dims(cfg)
    held = [layer_of(params, l) for l in d["layers"]]
    b, t = data.shape
    label = label.astype(jnp.int32).reshape(b * t)

    @jax.checkpoint
    def one(h, _):
        for p in held:
            h = jax.checkpoint(functools.partial(layer, d, p, operand=operand,
                                                 fault=fault))(h)
        normed = rms_norm(h, params["final_norm_gamma"], d["eps"])
        rows = head_rows(params["head_weight"],
                         normed.reshape(b * t, -1), label, operand)
        carry = h if fault == "raw_carry" else normed
        return carry, (rows.reshape(b, t), exit_gate(params, normed))

    h0 = params["embed_weight"][data.astype(jnp.int32)]
    _, (rows, logits) = lax.scan(
        one, h0, None, length=1 if fault == "one_pass" else d["passes"])
    return rows, logits


def exit_log_probs(logits):
    """(P, ...) gate logits -> log ``p_t``: ``lambda_t prod_{j<t} (1 -
    lambda_j)`` for ``t < P``, ``prod_{j<P} (1 - lambda_j)`` for the last;
    ``log sigmoid`` sums, float32."""
    out, survive = [], 0.0
    for g in logits[:-1]:
        out.append(survive + jax.nn.log_sigmoid(g))
        survive = survive + jax.nn.log_sigmoid(-g)
    return jnp.stack(out + [survive + jnp.zeros_like(logits[-1])])


def exit_loss(rows, logits, beta, fault=None):
    """Every token's ``sum_t p_t L_t + beta sum_t p_t log p_t``."""
    log_p = exit_log_probs(logits)
    if fault == "uniform_exit":
        log_p = jnp.full_like(log_p, -jnp.log(float(rows.shape[0])))
    if fault == "no_entropy":
        beta = 0.0
    p = jnp.exp(log_p)
    return jnp.sum(p * rows, 0) + beta * jnp.sum(p * log_p, 0)


def forward(cfg, params, data, label, operand=None, fault=None):
    """(every token's loss (B, T), {}: the model has no expert layer)."""
    rows, logits = passes(cfg, params, data, label, operand, fault)
    return exit_loss(rows, logits, dims(cfg)["beta"], fault), {}


def train_step(cfg, params, m, v, t, data, label, operand=None, fault=None):
    """One Adam step, the ``t``-th (from 1): (every token's loss before
    the update (B, T), {}, new weights, new first and second moments). The
    loss that is differentiated is the mean over all tokens."""
    opt = cfg["optimizer"]

    def loss_fn(a):
        rows, choices = forward(cfg, a, data, label, operand, fault)
        return jnp.mean(rows), (rows, choices)

    (_, (rows, choices)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params)
    t = jnp.asarray(t, jnp.float32)
    new = {k: adam(opt, params[k], grads[k], m[k], v[k], t) for k in params}
    return (rows, choices, {k: n[0] for k, n in new.items()},
            {k: n[1] for k, n in new.items()},
            {k: n[2] for k, n in new.items()})
