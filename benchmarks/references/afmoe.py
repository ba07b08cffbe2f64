"""Plain reference of one training step of Trinity (arcee-ai, ``model_type:
afmoe``; the public ``transformers`` ``modeling_afmoe.py`` and the model's
``config.json``), cut to one chip's share: forward, loss, gradients and the
Adam step in straightforward ``jax.numpy``, float32 at the highest matmul
precision. It imports nothing of the program: the benchmark makes the
weights here from the seed, hands them to the program and keeps a copy for
this file.

The layers (``h`` the residual stream, every matrix stored (out, in), no
biases):

* ``h0 = E[id] sqrt(hidden)`` (``mup_enabled``); a final RMSNorm; an untied
  head; the loss is the mean over all tokens of the cross-entropy of the
  next token over the vocabulary slice held here;
* block, four norms (eps 1e-5): ``a = h + RMSNorm_post_attn(Attn(
  RMSNorm_in(h)))``, ``y = a + RMSNorm_post_mlp(FFN(RMSNorm_pre_mlp(a)))``;
* attention, ``H`` heads of 128 over ``H_kv`` key and value heads (query
  head ``i`` reads KV head ``i // (H / H_kv)``): ``q = RMSNorm_head(W_q
  x)``, ``k = RMSNorm_head(W_k x)`` (one gamma of the head's width each,
  shared by the heads), ``v = W_v x``, ``g = W_gate x`` as wide as the
  heads; on ``sliding_attention`` layers only, rotary on q and k after the
  norm (``x cos + rotate_half(x) sin``, halves split at 64, theta 10,000,
  positions 0 .. T-1) and key ``j`` visible to query ``i`` when ``i -
  window < j <= i``; on ``full_attention`` layers no rotary and ``j <=
  i``; ``o = softmax(q k^T / sqrt(128)) v``; the output ``W_o [o
  sigmoid(g)]``;
* the dense SwiGLU of the leading ``num_dense_layers`` layers; in the
  others ``s = sigmoid(W_r x)`` over all the published experts, the top 8
  of ``s + b`` chosen, ``w_e = route_scale s_e / sum_top8 s``
  (``route_norm``; the published ``+ 1e-20`` in the denominator is below
  float32's reach beside a sum of eight sigmoids: noted, not computed),
  and ``y = SwiGLU_shared(x) + sum over the chosen experts held here of
  w_e SwiGLU_e(x)``: what the absent experts would add is left out. ``b``
  is no weight under the optimizer (its gradient is zero: it only
  selects): it starts at what the configuration states
  (:func:`selection_bias`) and stays there;
* Adam as MXNet 1.x writes it: ``m = b1 m + (1-b1) g``, ``v = b2 v +
  (1-b2) g^2``, ``w -= lr sqrt(1-b2^t)/(1-b1^t) m / (sqrt(v) + eps)``.

Departures, for memory and the compile's length only: each layer's
attention and feed-forward halves, each block of attention rows and of the
head's tokens is rematerialised (``jax.checkpoint``); attention is an
explicit masked softmax over all the keys for a block of query rows at a
time; the held experts are worked through one after the other by a scan,
each over every token with the weight it has there (zero where it was not
chosen). The arithmetic is unchanged.

``fault`` plants one wrong piece of mathematics by name (``FAULTS``): the
calibration and the tests show that the comparison catches each.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FAULTS = ("no_window", "rope_on_full", "kv_heads_interleaved",
          "no_attn_gate", "no_routed", "renorm_held", "half_tokens")


def dims(cfg):
    """The sizes of the cut, from the configuration file's own keys (the
    published ``config.json`` names; ``num_experts`` and ``vocab_size``
    are what is held here, their published values beside them). Layers
    count from 0, as ``layer_types`` does."""
    layers = cfg["layers"]
    return {
        "hidden": cfg["hidden_size"],
        "layers": layers,
        "window_layers": [l for l in layers
                          if cfg["layer_types"][l] == "sliding_attention"],
        "dense": [l for l in layers if l < cfg["num_dense_layers"]],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "window": cfg["sliding_window"],
        "theta": float(cfg["rope_theta"]),
        "inter": cfg["intermediate_size"],
        "moe_inter": cfg["moe_intermediate_size"],
        "router": cfg["num_experts_published"],
        "top_k": cfg["num_experts_per_tok"],
        "held": tuple(cfg["experts_held"]),
        "scale": cfg["route_scale"],
        "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"],
        "embed_scale": math.sqrt(cfg["hidden_size"])
        if cfg["mup_enabled"] else 1.0,
    }


def param_shapes(cfg):
    """name -> shape, under the names the program's symbol gives its
    variables."""
    d = dims(cfg)
    hid, dh = d["hidden"], d["head_dim"]
    n_held = d["held"][1] - d["held"][0]
    s = {"embed_weight": (d["vocab"], hid),
         "final_norm_gamma": (hid,),
         "head_weight": (d["vocab"], hid)}
    for l in d["layers"]:
        p = "l%d_" % l
        for n in ("attn", "post_attn", "ffn", "post_ffn"):
            s[p + n + "_norm_gamma"] = (hid,)
        s[p + "attn_q_weight"] = (d["heads"] * dh, hid)
        s[p + "attn_k_weight"] = (d["kv_heads"] * dh, hid)
        s[p + "attn_v_weight"] = (d["kv_heads"] * dh, hid)
        s[p + "attn_gate_weight"] = (d["heads"] * dh, hid)
        s[p + "attn_o_weight"] = (hid, d["heads"] * dh)
        s[p + "attn_q_norm_gamma"] = (dh,)
        s[p + "attn_k_norm_gamma"] = (dh,)
        if l in d["dense"]:
            s[p + "mlp_gate_weight"] = (d["inter"], hid)
            s[p + "mlp_up_weight"] = (d["inter"], hid)
            s[p + "mlp_down_weight"] = (hid, d["inter"])
        else:
            s[p + "moe_router_weight"] = (d["router"], hid)
            s[p + "moe_router_bias"] = (d["router"],)
            s[p + "moe_gate_weight"] = (n_held, d["moe_inter"], hid)
            s[p + "moe_up_weight"] = (n_held, d["moe_inter"], hid)
            s[p + "moe_down_weight"] = (n_held, hid, d["moe_inter"])
            s[p + "shared_gate_weight"] = (d["moe_inter"], hid)
            s[p + "shared_up_weight"] = (d["moe_inter"], hid)
            s[p + "shared_down_weight"] = (hid, d["moe_inter"])
    return s


def selection_bias(cfg):
    """The selection bias ``b`` of every expert layer, one number for each
    of the published experts, as the configuration states it under
    ``selection_bias`` (without the key: zero, the published start).

    ``{"always": [e, ...]}`` is the bias of a router whose load is even
    over the ranks and held there: the experts ``always`` (fewer than the
    top k, none of them held here: other ranks' experts) carry 4 and are
    every token's choice, the experts held here carry 2 and the best of
    them by score takes each choice that is left, every other expert
    carries 0 and is never chosen. A score lies in [0, 1] and the levels 2
    apart, so no rounding moves a choice from one level to another. The
    weights ``w_e`` still come from the scores alone."""
    d = dims(cfg)
    bias = [0.0] * d["router"]
    how = cfg.get("selection_bias")
    if how:
        lo, hi = d["held"]
        always = list(how["always"])
        if len(set(always)) != len(always) or len(always) >= d["top_k"] \
                or any(lo <= e < hi or not 0 <= e < d["router"]
                       for e in always):
            raise ValueError("selection_bias.always: fewer than top_k "
                             "distinct experts, none of them held here")
        bias[lo:hi] = [2.0] * (hi - lo)
        for e in always:
            bias[e] = 4.0
    return tuple(bias)


@functools.lru_cache(maxsize=None)
def _leaf_maker(kind, shape, device):
    """One leaf from its key: matrices normal(0, 0.02), norms at 1; a
    selection bias is ``kind`` itself, the tuple of its values."""
    def make(key):
        if kind == "ones":
            return jnp.ones(shape, jnp.float32)
        if kind == "normal":
            return jax.random.normal(key, shape, jnp.float32) * 0.02
        return jnp.asarray(kind, jnp.float32).reshape(shape)
    return jax.jit(make, device=device)


def init_params(cfg, seed, device=None):
    """name -> float32 array from the seed, leaf by leaf (each leaf's key
    is the seed's folded with the leaf's rank among the sorted names), on
    ``device`` (``None``: JAX's default)."""
    key = jax.random.PRNGKey(seed % (2 ** 31))
    bias = selection_bias(cfg)

    def kind(name):
        if name.endswith("_gamma"):
            return "ones"
        return bias if name.endswith("_router_bias") else "normal"
    return {name: _leaf_maker(kind(name), tuple(shape), device)(
                jax.random.fold_in(key, i))
            for i, (name, shape) in enumerate(
                sorted(param_shapes(cfg).items()))}


@jax.custom_vjp
def fp8_operand(x):
    """An operand as float8 e4m3 would hold it, under one scale a tensor;
    the gradient passes straight through. The lower-precision control of
    a bfloat16 configuration."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


fp8_operand.defvjp(lambda x: (fp8_operand(x), None), lambda _, g: (g,))


def bf16_operand(x):
    return x.astype(jnp.bfloat16).astype(x.dtype)


def _identity(x):
    return x


def _linear(x, w, operand):
    """``x W^T`` with ``W`` stored (out, in)."""
    return jnp.einsum("...i,oi->...o", operand(x), operand(w),
                      precision=HIGHEST)


def rms_norm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gamma


def swiglu(x, w_gate, w_up, w_down, operand):
    h = jax.nn.silu(_linear(x, w_gate, operand)) * _linear(x, w_up, operand)
    return _linear(h, w_down, operand)


def rotary(x, theta):
    """``x cos + rotate_half(x) sin`` over the last axis of (B, T, H, D),
    the halves split at D / 2, positions 0 .. T-1."""
    t, n = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(t)[:, None] * theta ** (-jnp.arange(n) / n)[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., n:], x[..., :n]], -1)
    return x * cos + half * sin


def masked_attention(q, k, v, window, operand, block=128):
    """q, k, v: (B, T, H, d), the key and value heads already those each
    query head reads: a masked softmax over all the keys, for ``block``
    rows of queries at a time. ``window`` None: key ``j <= i``; else also
    ``j > i - window``."""
    b, t, h, dq = q.shape
    scale = 1.0 / math.sqrt(dq)
    block = min(block, t)
    pad = (-t) % block

    @jax.checkpoint
    def rows(qb, start):
        s = jnp.einsum("bqhd,bkhd->bhqk", operand(qb), operand(k),
                       precision=HIGHEST) * scale
        # (rows of padding after the sequence stand at its last position:
        # a row that saw no key would be a softmax of nothing)
        qpos = jnp.minimum(start + jnp.arange(block), t - 1)[:, None]
        kpos = jnp.arange(t)[None, :]
        seen = kpos <= qpos
        if window is not None:
            seen = seen & (kpos > qpos - window)
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", operand(w), operand(v),
                          precision=HIGHEST)

    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qp.reshape(b, -1, block, h, dq), 1, 0)
    starts = jnp.arange(qb.shape[0]) * block
    o = lax.map(lambda a: rows(*a), (qb, starts))
    return jnp.moveaxis(o, 0, 1).reshape(b, -1, h, v.shape[-1])[:, :t]


def attention_layer(d, p, x, windowed, operand, fault=None):
    b, t, _ = x.shape
    h, hk, dh = d["heads"], d["kv_heads"], d["head_dim"]
    q = rms_norm(_linear(x, p["attn_q_weight"], operand).reshape(b, t, h, dh),
                 p["attn_q_norm_gamma"], d["eps"])
    k = rms_norm(_linear(x, p["attn_k_weight"], operand).reshape(b, t, hk, dh),
                 p["attn_k_norm_gamma"], d["eps"])
    v = _linear(x, p["attn_v_weight"], operand).reshape(b, t, hk, dh)
    if windowed or fault == "rope_on_full":
        q, k = rotary(q, d["theta"]), rotary(k, d["theta"])
    # the KV head each query head reads
    kv_of = jnp.arange(h) % hk if fault == "kv_heads_interleaved" \
        else jnp.arange(h) // (h // hk)
    window = d["window"] if windowed and fault != "no_window" else None
    o = masked_attention(q, k[:, :, kv_of], v[:, :, kv_of], window, operand)
    o = o.reshape(b, t, h * dh)
    if fault != "no_attn_gate":
        o = o * jax.nn.sigmoid(_linear(x, p["attn_gate_weight"], operand))
    return _linear(o, p["attn_o_weight"], operand)


def route(d, p, x, operand, fault=None):
    """(the chosen experts (..., top_k), their weights): the router runs
    in float32 over all the published experts."""
    s = jax.nn.sigmoid(_linear(x, p["moe_router_weight"], operand))
    _, chosen = lax.top_k(s + p["moe_router_bias"], d["top_k"])
    picked = jnp.take_along_axis(s, chosen, -1)
    total = jnp.sum(picked, -1, keepdims=True)
    if fault == "renorm_held":
        lo, hi = d["held"]
        total = jnp.sum(jnp.where((chosen >= lo) & (chosen < hi), picked, 0),
                        -1, keepdims=True) + 1e-20
    return chosen, d["scale"] * picked / total


def moe_layer(d, p, x, operand, fault=None):
    chosen, weight = route(d, p, x, operand, fault)
    y = swiglu(x, p["shared_gate_weight"], p["shared_up_weight"],
               p["shared_down_weight"], operand)
    if fault == "no_routed":
        return y, chosen
    lo, hi = d["held"]

    @jax.checkpoint
    def one(e, wg, wu, wd):
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1, keepdims=True)
        return w_e * swiglu(x, wg, wu, wd, operand)

    y, _ = lax.scan(lambda y, a: (y + one(*a), None), y,
                    (jnp.arange(lo, hi), p["moe_gate_weight"],
                     p["moe_up_weight"], p["moe_down_weight"]))
    return y, chosen


def head_loss(d, params, x, label, operand, block=2048):
    """Every token's cross-entropy of ``label`` under ``softmax(W_head
    RMSNorm(x))``, for ``block`` tokens at a time. x: (N, hidden)."""
    x = rms_norm(x, params["final_norm_gamma"], d["eps"])
    n = x.shape[0]
    block = min(block, n)
    pad = (-n) % block

    @jax.checkpoint
    def rows(a):
        xb, lb = a
        logp = jax.nn.log_softmax(
            _linear(xb, params["head_weight"], operand), axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[-1])
    lb = jnp.pad(label, (0, pad)).reshape(-1, block)
    return lax.map(rows, (xb, lb)).reshape(-1)[:n]


def forward(cfg, params, data, label, operand=None, fault=None):
    """(every token's loss (B, T), the experts each token chose in every
    expert layer {layer: (B, T, top_k)})."""
    operand = operand or _identity
    d = dims(cfg)
    ids = data.astype(jnp.int32)
    x = params["embed_weight"][ids] * d["embed_scale"]
    choices = {}
    for l in d["layers"]:
        p = {k[len("l%d_" % l):]: v for k, v in params.items()
             if k.startswith("l%d_" % l)}

        @jax.checkpoint
        def attention(x, p, l=l):
            a = attention_layer(d, p, rms_norm(x, p["attn_norm_gamma"],
                                               d["eps"]),
                                l in d["window_layers"], operand, fault)
            return x + rms_norm(a, p["post_attn_norm_gamma"], d["eps"])

        @jax.checkpoint
        def feed_forward(h, p, l=l):
            z = rms_norm(h, p["ffn_norm_gamma"], d["eps"])
            if l in d["dense"]:
                y, chosen = swiglu(z, p["mlp_gate_weight"],
                                   p["mlp_up_weight"], p["mlp_down_weight"],
                                   operand), None
            else:
                y, chosen = moe_layer(d, p, z, operand, fault)
            return h + rms_norm(y, p["post_ffn_norm_gamma"], d["eps"]), chosen

        x, chosen = feed_forward(attention(x, p), p)
        if chosen is not None:
            choices[l] = chosen
    b, t, hid = x.shape
    rows = head_loss(d, params, x.reshape(b * t, hid),
                     label.astype(jnp.int32).reshape(b * t), operand)
    return rows.reshape(b, t), choices


def adam(opt, w, g, m, v, t):
    """MXNet 1.x Adam: the bias correction folded into the rate, epsilon
    outside the root."""
    b1, b2 = opt["beta1"], opt["beta2"]
    g = g + opt["wd"] * w
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    lr_t = opt["learning_rate"] * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    return w - lr_t * m / (jnp.sqrt(v) + opt["epsilon"]), m, v


def train_step(cfg, params, m, v, t, data, label, operand=None, fault=None):
    """One Adam step, the ``t``-th (from 1): (every token's loss before
    the update (B, T), the chosen experts, new weights, new first and
    second moments). The loss that is differentiated is the mean over all
    tokens (``half_tokens``: over the first half of every sequence)."""
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
