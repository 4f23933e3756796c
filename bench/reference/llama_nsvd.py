"""Plain reference of a Llama-style decoder whose linears are NSVD-factored.

Written from the published architecture (Mistral-7B / Phi-3 family: RMSNorm
before attention and MLP, rotary position embedding on the first and
second halves of each head, grouped-query attention with causal softmax,
SwiGLU MLP, untied output head), in float32, straightforward ``jax.numpy``
and nothing of the program: no kernels, no cache, no batching of requests
into slots.  A factored linear {u, v, u2, v2} is y = (x u) v + (x u2) v2.

It runs layer by layer, so that a float32 pass over a whole model needs one
layer's weights in float32 at a time, and attends one sequence at a time.

Params: ``embed.table`` (V, D); ``g0.sub0`` holding every layer stacked on
axis 0: ``norm1.scale``, ``attn.{wq,wk,wv,wo}``, ``norm2.scale``,
``mlp.{wg (gate), wi (up), wo (down)}``; ``final_norm.scale``;
``unembed.kernel`` (D, V).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _linear(w, x, quant):
    """x @ W for a dense or factored W; ``quant`` rounds each matmul's two
    operands (identity for the float32 reference)."""
    def mm(a, b):
        return quant(a, -1) @ quant(b, -2)

    if "kernel" in w:
        return mm(x, w["kernel"])
    return mm(mm(x, w["u"]), w["v"]) + mm(mm(x, w["u2"]), w["v2"])


def _rope(x, pos, theta):
    """x (S, H, hd); the first and second halves of hd rotate together."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None, None].astype(F32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attend(q, k, v):
    """One sequence: q (S, Hq, hd), k/v (S, Hkv, hd), causal."""
    s, hq, hd = q.shape
    g = hq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


def _identity(a, axis):
    return a.astype(F32)


def int8_operand(a, axis):
    """Symmetric int8 along the contracted ``axis`` (per row of an
    activation, per output column of a weight), dequantized: an int8
    matmul's operands."""
    a = a.astype(F32)
    step = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    step = jnp.where(step > 0, step, 1.0)
    return jnp.clip(jnp.round(a / step), -127, 127) * step


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def _layer(p, x, cfg, quant):
    heads, kv_heads, hd, eps, theta = cfg
    n, s, _ = x.shape
    pos = jnp.arange(s)
    h = _rms_norm(x, p["norm1"]["scale"], eps)
    a = p["attn"]
    q = _linear(a["wq"], h, quant).reshape(n, s, heads, hd)
    k = _linear(a["wk"], h, quant).reshape(n, s, kv_heads, hd)
    v = _linear(a["wv"], h, quant).reshape(n, s, kv_heads, hd)
    q = jax.vmap(lambda t: _rope(t, pos, theta))(q)
    k = jax.vmap(lambda t: _rope(t, pos, theta))(k)
    o = jax.lax.map(lambda qkv: _attend(*qkv), (q, k, v))
    x = x + _linear(a["wo"], o.reshape(n, s, heads * hd), quant)
    h = _rms_norm(x, p["norm2"]["scale"], eps)
    m = p["mlp"]
    y = jax.nn.silu(_linear(m["wg"], h, quant)) * _linear(m["wi"], h, quant)
    return x + _linear(m["wo"], y, quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(params, x, targets, eps, quant):
    """Per position: the best logit, and the logit of ``targets`` (N, S, T)
    (several candidate tokens per position)."""
    h = _rms_norm(x, params["final_norm"]["scale"], eps)
    logits = _linear(params["unembed"], h, quant)
    best = jnp.max(logits, -1)
    picked = jnp.take_along_axis(logits, targets, axis=-1)
    return best, picked, jnp.argmax(logits, -1)


def forward(params, config: dict, tokens: np.ndarray, targets: np.ndarray,
            int8: bool = False):
    """tokens (N, S) int32, right-padded; targets (N, S, T) int32.  Returns
    numpy (best (N, S), picked (N, S, T), argmax (N, S)) of the float32
    reference, or with ``int8`` of its control: every linear an int8
    matmul (weights and activations rounded to int8, products exact)."""
    quant = int8_operand if int8 else _identity
    cfg = (config["num_attention_heads"], config["num_key_value_heads"],
           config["head_dim"], config["rms_norm_eps"], config["rope_theta"])
    layers = params["g0"]["sub0"]
    n_layers = jax.tree.leaves(layers)[0].shape[0]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["table"], jnp.asarray(tokens),
                     axis=0).astype(F32)
        for i in range(n_layers):
            x = _layer(jax.tree.map(lambda a: a[i], layers), x, cfg, quant)
        best, picked, arg = _head(params, x, jnp.asarray(targets),
                                  config["rms_norm_eps"], quant)
    return np.asarray(best), np.asarray(picked), np.asarray(arg)
