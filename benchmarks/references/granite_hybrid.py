"""Plain reference of one training step of Granite 4.0-H (IBM,
``model_type: granitemoehybrid``; the public ``transformers``
``modeling_granitemoehybrid.py``, state-spaces/mamba's ``Mamba2``,
arXiv:2405.21060, and the model's ``config.json``), cut to one chip's
share: forward, loss, gradients and the Adam step in straightforward
``jax.numpy``, float32 at the highest matmul precision. It imports nothing
of the program: the benchmark makes the weights here from the seed, hands
them to the program and keeps a copy for this file. What it shares with
``references/afmoe.py`` (the linear map, RMSNorm, SwiGLU, Adam, the operand
controls) it takes from there.

The layers (``h`` the residual stream, every matrix stored (out, in), no
bias but the convolution's):

* ``h0 = 12 E[id]`` (``embedding_multiplier``); a final RMSNorm; the head
  is the embedding's own matrix (``tie_word_embeddings``), the logits over
  8 (``logits_scaling``); the loss is the mean over all tokens of the
  cross-entropy of the next token over the vocabulary slice held here;
* block (eps 1e-5): ``a = h + 0.22 Mixer(RMSNorm_in(h))``, ``y = a + 0.22
  SwiGLU(RMSNorm_post(a))`` (``residual_multiplier``); the SwiGLU's gate
  and up matrices are kept apart (the published file stacks them as
  ``input_linear``: the same numbers);
* a ``mamba`` layer, with ``u`` its input: ``[z | xBC | dt] = W_in u``
  (4096 | 4352 | 64); ``xBC = silu(conv1d_causal(xBC, kernel 4) +
  b_conv)``, depthwise, zeros before the sequence; ``[x | B | C] = xBC``
  (64 heads of 64 | 128 | 128: one group, B and C shared by the heads);
  a head ``h``: ``delta_t = softplus(dt_t + dt_bias_h)``, ``a_t = exp(-
  delta_t exp(A_log_h))``, ``S_t = a_t S_{t-1} + delta_t x_t B_t^T`` (64
  x 128, from zero), ``y_t = S_t C_t + D_h x_t``; then ``RMSNorm(y
  silu(z)) w_norm`` over all 4096 (the gate first) and ``W_out``. **The
  recurrence runs token by token** (a scan over tokens), not in chunks;
  ``time_step_limit`` is (0, inf) as the public code's default;
* an ``attention`` layer: 32 heads of 64 over 8 key and value heads
  (query head ``i`` reads KV head ``i // 4``), no positions
  (``position_embedding_type: nope``), no bias, key ``j <= i``, ``o =
  softmax(0.015625 q k^T) v`` (``attention_multiplier``, not ``1 /
  sqrt(64)``), ``W_o o``;
* Adam as MXNet 1.x writes it (``references/afmoe.py::adam``).

Departures, for memory and the compile's length only: each layer's mixer
and feed-forward, each block of 128 tokens of the recurrence, of attention
rows and of the head's tokens is rematerialised (``jax.checkpoint``); the
state's products are written as broadcast multiplies and sums (exact in
float32 whatever the matmul precision). The arithmetic is unchanged.

``fault`` plants one wrong piece of mathematics by name (``FAULTS``): the
calibration and the tests show that the comparison catches each.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from references.afmoe import (HIGHEST, _identity, _linear, adam,  # noqa: F401
                              bf16_operand, fp8_operand, rms_norm, swiglu)

FAULTS = ("no_carry", "no_dt_bias", "no_gate", "no_conv_bias", "no_skip",
          "residual_one", "scale_rsqrt", "untied", "half_tokens")
SCAN_BLOCK = 128        # tokens of the recurrence rematerialised together


def dims(cfg):
    """The sizes of the cut, from the configuration file's own keys (the
    published ``config.json`` names; ``vocab_size`` is what is held here,
    its published value beside it). Layers count from 0, as
    ``layer_types`` does."""
    layers = cfg["layers"]
    heads = cfg["num_attention_heads"]
    return {
        "hidden": cfg["hidden_size"],
        "layers": layers,
        "mamba_layers": [l for l in layers
                         if cfg["layer_types"][l] == "mamba"],
        "heads": heads,
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // heads,
        "inter": cfg["shared_intermediate_size"],
        "m_heads": cfg["mamba_n_heads"],
        "m_head": cfg["mamba_d_head"],
        "m_state": cfg["mamba_d_state"],
        "m_groups": cfg["mamba_n_groups"],
        "m_conv": cfg["mamba_d_conv"],
        "m_chunk": cfg["mamba_chunk_size"],
        "conv_bias": cfg["mamba_conv_bias"],
        "tied": cfg["tie_word_embeddings"],
        "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"],
        "embed_mult": float(cfg["embedding_multiplier"]),
        "attn_mult": float(cfg["attention_multiplier"]),
        "resid_mult": float(cfg["residual_multiplier"]),
        "logits_scale": float(cfg["logits_scaling"]),
    }


def param_shapes(cfg):
    """name -> shape, under the names the program's symbol gives its
    variables."""
    d = dims(cfg)
    hid, dh = d["hidden"], d["head_dim"]
    inner = d["m_heads"] * d["m_head"]
    conv = inner + 2 * d["m_groups"] * d["m_state"]
    s = {"embed_weight": (d["vocab"], hid), "final_norm_gamma": (hid,)}
    if not d["tied"]:
        s["head_weight"] = (d["vocab"], hid)
    for l in d["layers"]:
        p = "l%d_" % l
        s[p + "input_norm_gamma"] = (hid,)
        s[p + "post_attn_norm_gamma"] = (hid,)
        s[p + "mlp_gate_weight"] = (d["inter"], hid)
        s[p + "mlp_up_weight"] = (d["inter"], hid)
        s[p + "mlp_down_weight"] = (hid, d["inter"])
        if l in d["mamba_layers"]:
            s[p + "mamba_in_weight"] = (inner + conv + d["m_heads"], hid)
            s[p + "mamba_conv_weight"] = (conv, d["m_conv"])
            if d["conv_bias"]:
                s[p + "mamba_conv_bias"] = (conv,)
            for n in ("dt_bias", "A_log", "D"):
                s[p + "mamba_" + n] = (d["m_heads"],)
            s[p + "mamba_norm_gamma"] = (inner,)
            s[p + "mamba_out_weight"] = (hid, inner)
        else:
            s[p + "attn_q_weight"] = (d["heads"] * dh, hid)
            s[p + "attn_k_weight"] = (d["kv_heads"] * dh, hid)
            s[p + "attn_v_weight"] = (d["kv_heads"] * dh, hid)
            s[p + "attn_o_weight"] = (hid, d["heads"] * dh)
    return s


def _kind(name):
    for end, kind in (("_gamma", "ones"), ("_mamba_D", "ones"),
                      ("_mamba_A_log", "a_log"), ("_mamba_dt_bias", "dt_bias"),
                      ("_mamba_conv_weight", "conv"),
                      ("_mamba_conv_bias", "conv")):
        if name.endswith(end):
            return kind
    return "normal"


@functools.lru_cache(maxsize=None)
def _leaf_maker(kind, shape, width, device):
    """One leaf from its key, as state-spaces/mamba's ``Mamba2`` starts
    its own: ``A_log = log U[1, 16]``; ``dt_bias`` the inverse softplus of
    a step drawn log-uniform in [0.001, 0.1]; the convolution's weight and
    bias ``U(-1/sqrt(width), 1/sqrt(width))`` (``nn.Conv1d``'s default at a
    depthwise kernel of ``width``); ``D`` and the norms 1; every matrix
    normal(0, 0.02)."""
    def make(key):
        if kind == "ones":
            return jnp.ones(shape, jnp.float32)
        if kind == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if kind == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))
        if kind == "conv":
            bound = 1.0 / math.sqrt(width)
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    return jax.jit(make, device=device)


def init_params(cfg, seed, device=None):
    """name -> float32 array from the seed, leaf by leaf (each leaf's key
    is the seed's folded with the leaf's rank among the sorted names), on
    ``device`` (``None``: JAX's default)."""
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return {name: _leaf_maker(_kind(name), tuple(shape), cfg["mamba_d_conv"],
                              device)(jax.random.fold_in(key, i))
            for i, (name, shape) in enumerate(
                sorted(param_shapes(cfg).items()))}


def causal_conv(x, w, bias):
    """``y_t = sum_i w[:, i] x_{t-(K-1)+i} + bias`` over (B, T, C), zeros
    before the sequence; w: (C, K)."""
    k, t = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + t] * w[:, i] for i in range(k))
    return y if bias is None else y + bias


def ssm_recurrence(x, bm, cm, delta, log_a, reset_every=None):
    """``S_t = a_t S_{t-1} + delta_t x_t B_t^T``, ``y_t = S_t C_t`` token
    by token from a zero state. x: (B, T, H, P) already in the operands'
    precision, as are bm, cm: (B, T, N); delta, log_a: (B, T, H). With
    ``reset_every`` the state is dropped before every such token (the
    fault ``no_carry``)."""
    b, t, h, p = x.shape
    block = min(SCAN_BLOCK, t)
    pad = (-t) % block

    def token(s, a):
        xt, bt, ct, dt, lt, first = a
        s = jnp.where(first, 0.0, s) * jnp.exp(lt)[..., None, None] \
            + (dt[..., None] * xt)[..., None] * bt[:, None, None, :]
        return s, jnp.sum(s * ct[:, None, None, :], -1)

    @jax.checkpoint
    def tokens(s, a):
        return lax.scan(token, s, a)

    first = jnp.zeros((t,), bool) if reset_every is None \
        else jnp.arange(t) % reset_every == 0

    def blocks(v):      # (B, T, ...) -> (T / block, block, B, ...)
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((-1, block) + v.shape[1:])

    first = jnp.pad(first, (0, pad)).reshape(-1, block)
    s0 = jnp.zeros((b, h, p, bm.shape[-1]), jnp.float32)
    _, y = lax.scan(tokens, s0, (blocks(x), blocks(bm), blocks(cm),
                                 blocks(delta), blocks(log_a), first))
    return jnp.moveaxis(y.reshape((-1,) + y.shape[2:]), 0, 1)[:, :t]


def mamba_layer(d, p, u, operand, fault=None):
    b, t, _ = u.shape
    h, ph, n = d["m_heads"], d["m_head"], d["m_state"]
    inner = h * ph
    proj = _linear(u, p["mamba_in_weight"], operand)
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * n], axis=-1)
    bias = p.get("mamba_conv_bias")
    xbc = jax.nn.silu(causal_conv(
        xbc, p["mamba_conv_weight"], None if fault == "no_conv_bias" else bias))
    x, bm, cm = jnp.split(xbc, [inner, inner + n], axis=-1)
    x = x.reshape(b, t, h, ph)
    if fault != "no_dt_bias":
        dt = dt + p["mamba_dt_bias"]
    delta = jax.nn.softplus(dt)
    log_a = -delta * jnp.exp(p["mamba_A_log"])
    y = ssm_recurrence(operand(x), operand(bm), operand(cm), delta, log_a,
                       d["m_chunk"] if fault == "no_carry" else None)
    if fault != "no_skip":
        y = y + p["mamba_D"][:, None] * x
    y = y.reshape(b, t, inner)
    if fault != "no_gate":
        y = y * jax.nn.silu(z)
    y = rms_norm(y, p["mamba_norm_gamma"], d["eps"])
    return _linear(y, p["mamba_out_weight"], operand)


def attention_layer(d, p, x, operand, fault=None, block=128):
    """A masked softmax over all the keys, for ``block`` rows of queries
    at a time."""
    b, t, _ = x.shape
    h, hk, dh = d["heads"], d["kv_heads"], d["head_dim"]
    q = _linear(x, p["attn_q_weight"], operand).reshape(b, t, h, dh)
    k = _linear(x, p["attn_k_weight"], operand).reshape(b, t, hk, dh)
    v = _linear(x, p["attn_v_weight"], operand).reshape(b, t, hk, dh)
    kv_of = jnp.arange(h) // (h // hk)      # the KV head a query head reads
    k, v = k[:, :, kv_of], v[:, :, kv_of]
    scale = 1.0 / math.sqrt(dh) if fault == "scale_rsqrt" else d["attn_mult"]
    block = min(block, t)
    pad = (-t) % block

    @jax.checkpoint
    def rows(qb, start):
        s = jnp.einsum("bqhd,bkhd->bhqk", operand(qb), operand(k),
                       precision=HIGHEST) * scale
        # (rows of padding after the sequence stand at its last position)
        qpos = jnp.minimum(start + jnp.arange(block), t - 1)[:, None]
        w = jax.nn.softmax(jnp.where(jnp.arange(t)[None, :] <= qpos, s,
                                     -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", operand(w), operand(v),
                          precision=HIGHEST)

    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(b, -1, block, h, dh), 1, 0)
    o = lax.map(lambda a: rows(*a), (qb, jnp.arange(qb.shape[0]) * block))
    o = jnp.moveaxis(o, 0, 1).reshape(b, -1, h * dh)[:, :t]
    return _linear(o, p["attn_o_weight"], operand)


def head_loss(d, params, x, label, operand, fault=None, block=2048):
    """Every token's cross-entropy of ``label`` under ``softmax(W
    RMSNorm(x) / logits_scaling)``, ``W`` the embedding's matrix where the
    two are tied, for ``block`` tokens at a time. x: (N, hidden)."""
    x = rms_norm(x, params["final_norm_gamma"], d["eps"])
    w = params["embed_weight"] if d["tied"] else params["head_weight"]
    if fault == "untied":       # the head's gradient never reaches it
        w = lax.stop_gradient(w)
    n = x.shape[0]
    block = min(block, n)
    pad = (-n) % block

    @jax.checkpoint
    def rows(a):
        xb, lb = a
        logp = jax.nn.log_softmax(
            _linear(xb, w, operand) / d["logits_scale"], axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[-1])
    lb = jnp.pad(label, (0, pad)).reshape(-1, block)
    return lax.map(rows, (xb, lb)).reshape(-1)[:n]


def forward(cfg, params, data, label, operand=None, fault=None):
    """(every token's loss (B, T), {}: the model has no expert layer)."""
    operand = operand or _identity
    d = dims(cfg)
    mult = 1.0 if fault == "residual_one" else d["resid_mult"]
    x = params["embed_weight"][data.astype(jnp.int32)] * d["embed_mult"]
    for l in d["layers"]:
        p = {k[len("l%d_" % l):]: v for k, v in params.items()
             if k.startswith("l%d_" % l)}

        @jax.checkpoint
        def mixer(x, p, l=l):
            u = rms_norm(x, p["input_norm_gamma"], d["eps"])
            if l in d["mamba_layers"]:
                return x + mult * mamba_layer(d, p, u, operand, fault)
            return x + mult * attention_layer(d, p, u, operand, fault)

        @jax.checkpoint
        def feed_forward(h, p):
            z = rms_norm(h, p["post_attn_norm_gamma"], d["eps"])
            return h + mult * swiglu(z, p["mlp_gate_weight"],
                                     p["mlp_up_weight"], p["mlp_down_weight"],
                                     operand)

        x = feed_forward(mixer(x, p), p)
    b, t, hid = x.shape
    rows = head_loss(d, params, x.reshape(b * t, hid),
                     label.astype(jnp.int32).reshape(b * t), operand, fault)
    return rows.reshape(b, t), {}


def train_step(cfg, params, m, v, t, data, label, operand=None, fault=None):
    """One Adam step, the ``t``-th (from 1): (every token's loss before
    the update (B, T), {}, new weights, new first and second moments). The
    loss that is differentiated is the mean over all tokens
    (``half_tokens``: over the first half of every sequence)."""
    opt = cfg["optimizer"]

    def loss_fn(a):
        rows, choices = forward(cfg, a, data, label, operand, fault)
        kept = rows[:, :rows.shape[1] // 2] if fault == "half_tokens" \
            else rows
        return jnp.mean(kept), (rows, choices)

    (_, (rows, choices)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params)
    t = jnp.asarray(t, jnp.float32)
    new = {k: adam(opt, params[k], grads[k], m[k], v[k], t) for k in params}
    return (rows, choices, {k: n[0] for k, n in new.items()},
            {k: n[1] for k, n in new.items()},
            {k: n[2] for k, n in new.items()})
