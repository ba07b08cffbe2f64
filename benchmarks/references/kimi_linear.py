"""Plain reference of one training step of Kimi-Linear (moonshotai,
arXiv:2510.26692; ``model_type: kimi_linear``), cut to one chip's share:
forward, loss, gradients and the Adam step in straightforward
``jax.numpy``, float32 at the highest matmul precision. It imports nothing
of the program: the benchmark makes the weights here from the seed, hands
them to the program and keeps a copy for this file.

The layers, as the paper and the public ``fla`` implementation write them:

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; a final
  RMSNorm; an untied head; the loss is the mean over all tokens of the
  cross-entropy of the next token over the vocabulary slice held here;
* KDA: ``W_q x, W_k x, W_v x``, each through a causal depthwise
  convolution of width 4 and SiLU; per head ``q = l2norm(q) d_k^-1/2``,
  ``k = l2norm(k)``; the forget gate, per channel,
  ``g = -exp(A_log) softplus(W_f_up W_f_down x + dt_bias)``, ``alpha =
  exp(g)``; ``beta = sigmoid(W_beta x)`` a head; the state, zero at the
  start of a sequence, token by token
  ``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T``,
  ``o_t = S_t^T q_t``; the output ``W_o [RMSNorm_head(o) * sigmoid(W_g_up
  W_g_down x)]``;
* MLA without positions: ``q = W_q x`` (heads of 192); ``[c; k_R] =
  W_kva x``, ``c = RMSNorm(c)``, ``[k_C; v] = W_kvb c`` a head, ``k = [k_C;
  k_R]`` with ``k_R`` shared by the heads and no rotary on either part;
  causal ``softmax(q k^T / sqrt(192)) v``; ``W_o``;
* the dense SwiGLU of the leading layer; in the others ``s = sigmoid(W_r
  x)`` over all the published experts, the top 8 of ``s + b`` chosen,
  ``w_e = 2.446 s_e / sum_top8 s``, and ``y = SwiGLU_shared(x) + sum over
  the chosen experts held here of w_e SwiGLU_e(x)``: what the absent
  experts would add is left out;
* Adam as MXNet 1.x writes it: ``m = b1 m + (1-b1) g``, ``v = b2 v +
  (1-b2) g^2``, ``w -= lr sqrt(1-b2^t)/(1-b1^t) m / (sqrt(v) + eps)``.

Every matrix is stored (out, in), as the program's FullyConnected does.

Departures, for memory only: each layer's attention and feed-forward
halves, each held expert, each block of 64 tokens of the recurrence, each
block of attention rows and of the head's tokens is rematerialised
(``jax.checkpoint``). The arithmetic is unchanged.

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
L2_EPS = 1e-6
FAULTS = ("no_forget", "no_routed", "renorm_held", "half_tokens", "rotary")


def dims(cfg):
    """The sizes of the cut, from the configuration file's own keys (the
    published ``config.json`` names; ``num_experts`` and ``vocab_size``
    are what is held here, their published values beside them)."""
    lin = cfg["linear_attn_config"]
    layers = cfg["layers"]
    return {
        "hidden": cfg["hidden_size"],
        "layers": layers,
        "kda": [l for l in layers if l in lin["kda_layers"]],
        "dense": [l for l in layers if l <= cfg["first_k_dense_replace"]],
        "heads": cfg["num_attention_heads"],
        "kda_heads": lin["num_heads"], "kda_dim": lin["head_dim"],
        "conv": lin["short_conv_kernel_size"],
        "gate_rank": cfg["kda_gate_low_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"], "kv_rank": cfg["kv_lora_rank"],
        "inter": cfg["intermediate_size"],
        "moe_inter": cfg["moe_intermediate_size"],
        "router": cfg["num_experts_published"],
        "top_k": cfg["num_experts_per_token"],
        "held": tuple(cfg["experts_held"]),
        "scale": cfg["routed_scaling_factor"],
        "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"],
    }


def param_shapes(cfg):
    """name -> shape, under the names the program's symbol gives its
    variables."""
    d = dims(cfg)
    hid = d["hidden"]
    kd = d["kda_heads"] * d["kda_dim"]
    n_held = d["held"][1] - d["held"][0]
    s = {"embed_weight": (d["vocab"], hid),
         "final_norm_gamma": (hid,),
         "head_weight": (d["vocab"], hid)}
    for l in d["layers"]:
        p = "l%d_" % l
        s[p + "attn_norm_gamma"] = (hid,)
        s[p + "ffn_norm_gamma"] = (hid,)
        if l in d["kda"]:
            for n in "qkv":
                s[p + "kda_%s_weight" % n] = (kd, hid)
                s[p + "kda_%s_conv_weight" % n] = (kd, d["conv"])
            s[p + "kda_f_down_weight"] = (d["gate_rank"], hid)
            s[p + "kda_f_up_weight"] = (kd, d["gate_rank"])
            s[p + "kda_A_log"] = (d["kda_heads"],)
            s[p + "kda_dt_bias"] = (kd,)
            s[p + "kda_beta_weight"] = (d["kda_heads"], hid)
            s[p + "kda_g_down_weight"] = (d["gate_rank"], hid)
            s[p + "kda_g_up_weight"] = (kd, d["gate_rank"])
            s[p + "kda_o_norm_gamma"] = (d["kda_dim"],)
            s[p + "kda_o_weight"] = (hid, kd)
        else:
            s[p + "mla_q_weight"] = (d["heads"] * (d["nope"] + d["rope"]), hid)
            s[p + "mla_kva_weight"] = (d["kv_rank"] + d["rope"], hid)
            s[p + "mla_kv_norm_gamma"] = (d["kv_rank"],)
            s[p + "mla_kvb_weight"] = (
                d["heads"] * (d["nope"] + d["v_dim"]), d["kv_rank"])
            s[p + "mla_o_weight"] = (hid, d["heads"] * d["v_dim"])
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


def _leaf_kind(name):
    for suffix, kind in (("_gamma", "ones"), ("_router_bias", "zeros"),
                         ("_A_log", "a_log"), ("_dt_bias", "dt_bias")):
        if name.endswith(suffix):
            return kind
    return "normal"


@functools.lru_cache(maxsize=None)
def _leaf_maker(kind, shape, device):
    """One leaf from its key: matrices normal(0, 0.02); norms at 1; the
    selection bias at 0; ``A_log = log U(1, 16)`` and ``dt_bias`` the
    inverse softplus of a step drawn log-uniformly from [0.001, 0.1], as
    the public implementation initialises them."""
    def make(key):
        if kind == "ones":
            return jnp.ones(shape, jnp.float32)
        if kind == "zeros":
            return jnp.zeros(shape, jnp.float32)
        if kind == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                         * (math.log(0.1) - math.log(0.001))
                         + math.log(0.001))
            dt = jnp.maximum(dt, 1e-4)
            return dt + jnp.log(-jnp.expm1(-dt))
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    return jax.jit(make, device=device)


def init_params(cfg, seed, device=None):
    """name -> float32 array from the seed, leaf by leaf (each leaf's key
    is the seed's folded with the leaf's rank among the sorted names), on
    ``device`` (``None``: JAX's default)."""
    key = jax.random.PRNGKey(seed % (2 ** 31))
    return {name: _leaf_maker(_leaf_kind(name), tuple(shape), device)(
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


def causal_conv_silu(x, w):
    """Depthwise over time: ``y_t = sum_i w[:, i] x_{t-(K-1)+i}``, zeros
    before the sequence; ``x`` is (B, T, C), ``w`` (C, K)."""
    k = w.shape[1]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + t] * w[:, i] for i in range(k))
    return jax.nn.silu(y)


def l2norm(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


def kda_recurrence(q, k, v, g, beta, block=64):
    """The delta rule with a per-channel forget gate, token by token.
    q, k, g: (B, T, H, d_k); v: (B, T, H, d_v); beta: (B, T, H). Returns
    o: (B, T, H, d_v). Blocks of ``block`` tokens are rematerialised, so
    that the backward pass keeps one state a block."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x                    # (B, H, ...)
        s = s * jnp.exp(g_t)[..., None]                # Diag(alpha) S
        pred = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=HIGHEST)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t,
                           (v_t - pred) * b_t[..., None], precision=HIGHEST)
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=HIGHEST)

    @jax.checkpoint
    def tokens(s, xs):
        return lax.scan(token, s, xs)

    pad = (-t) % block
    xs = [jnp.moveaxis(jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)),
                       1, 0) for a in (q, k, v, g, beta)]
    xs = [a.reshape((-1, block) + a.shape[1:]) for a in xs]
    _, o = lax.scan(tokens, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((-1, b, h, dv)), 0, 1)[:, :t]


def kda_layer(d, p, x, operand, fault=None):
    b, t, _ = x.shape
    h, dk = d["kda_heads"], d["kda_dim"]
    q, k, v = (causal_conv_silu(_linear(x, p["kda_%s_weight" % n], operand),
                                p["kda_%s_conv_weight" % n])
               .reshape(b, t, h, dk) for n in "qkv")
    q = l2norm(q) * dk ** -0.5
    k = l2norm(k)
    f = _linear(_linear(x, p["kda_f_down_weight"], operand),
                p["kda_f_up_weight"], operand) + p["kda_dt_bias"]
    g = -jnp.exp(p["kda_A_log"])[:, None] \
        * jax.nn.softplus(f.reshape(b, t, h, dk))
    if fault == "no_forget":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(_linear(x, p["kda_beta_weight"], operand))
    o = kda_recurrence(q, k, v, g, beta)
    gate = _linear(_linear(x, p["kda_g_down_weight"], operand),
                   p["kda_g_up_weight"], operand).reshape(b, t, h, dk)
    o = rms_norm(o, p["kda_o_norm_gamma"], d["eps"]) * jax.nn.sigmoid(gate)
    return _linear(o.reshape(b, t, h * dk), p["kda_o_weight"], operand)


def _rotary(x, theta=10000.0):
    """Planted fault only: the rotary embedding this model does not use."""
    t, n = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(t)[:, None] * theta ** (-jnp.arange(n) / n)[None, :]
    ang = ang.reshape((1, t) + (1,) * (x.ndim - 3) + (n,))
    x1, x2 = x[..., :n], x[..., n:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def dense_causal_attention(q, k, v, operand, block=256):
    """q, k: (B, T, H, d); v: (B, T, H, d_v): a masked softmax over all
    the keys, for ``block`` rows of queries at a time."""
    b, t, h, dq = q.shape
    scale = 1.0 / math.sqrt(dq)
    pad = (-t) % block

    @jax.checkpoint
    def rows(qb, start):
        s = jnp.einsum("bqhd,bkhd->bhqk", operand(qb), operand(k),
                       precision=HIGHEST) * scale
        qpos = start + jnp.arange(block)[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= qpos, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", operand(w), operand(v),
                          precision=HIGHEST)

    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qp.reshape(b, -1, block, h, dq), 1, 0)
    starts = jnp.arange(qb.shape[0]) * block
    o = lax.map(lambda a: rows(*a), (qb, starts))
    return jnp.moveaxis(o, 0, 1).reshape(b, -1, h, v.shape[-1])[:, :t]


def mla_layer(d, p, x, operand, fault=None):
    b, t, _ = x.shape
    h, nope, rope, dv = d["heads"], d["nope"], d["rope"], d["v_dim"]
    q = _linear(x, p["mla_q_weight"], operand).reshape(b, t, h, nope + rope)
    kva = _linear(x, p["mla_kva_weight"], operand)
    c = rms_norm(kva[..., :d["kv_rank"]], p["mla_kv_norm_gamma"], d["eps"])
    k_r = kva[..., d["kv_rank"]:]                          # (B, T, rope)
    kv = _linear(c, p["mla_kvb_weight"], operand).reshape(b, t, h, nope + dv)
    if fault == "rotary":
        q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:])], -1)
        k_r = _rotary(k_r)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, :, None], (b, t, h, rope))],
        -1)
    o = dense_causal_attention(q, k, kv[..., nope:], operand)
    return _linear(o.reshape(b, t, h * dv), p["mla_o_weight"], operand)


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

    for i, e in enumerate(range(lo, hi)):
        y = y + one(e, p["moe_gate_weight"][i], p["moe_up_weight"][i],
                    p["moe_down_weight"][i])
    return y, chosen


def head_loss(d, params, x, label, operand, block=2048):
    """Every token's cross-entropy of ``label`` under ``softmax(W_head
    RMSNorm(x))``, for ``block`` tokens at a time. x: (N, hidden)."""
    x = rms_norm(x, params["final_norm_gamma"], d["eps"])
    n = x.shape[0]
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
    x = params["embed_weight"][ids]
    choices = {}
    for l in d["layers"]:
        p = {k[len("l%d_" % l):]: v for k, v in params.items()
             if k.startswith("l%d_" % l)}

        @jax.checkpoint
        def attention(x, p, l=l):
            attn = kda_layer if l in d["kda"] else mla_layer
            return x + attn(d, p, rms_norm(x, p["attn_norm_gamma"], d["eps"]),
                            operand, fault)

        @jax.checkpoint
        def feed_forward(h, p, l=l):
            z = rms_norm(h, p["ffn_norm_gamma"], d["eps"])
            if l in d["dense"]:
                return h + swiglu(z, p["mlp_gate_weight"], p["mlp_up_weight"],
                                  p["mlp_down_weight"], operand), None
            y, chosen = moe_layer(d, p, z, operand, fault)
            return h + y, chosen

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
