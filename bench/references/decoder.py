"""Plain reference for a Llama-family decoder: dense SwiGLU (Yi) or a routed
mixture of SwiGLU experts (Mixtral), written from the published
descriptions and importing nothing of the program.

  x   = embed[tokens]
  per layer:  h = rms(x) * g_attn;  q, k, v = h Wq, h Wk, h Wv
              rotary position on q and k (interleaved pairs, theta)
              causal softmax(q k^T / sqrt(d_head)) v, grouped KV heads
              x += o Wo;  h = rms(x) * g_mlp
              dense:  x += (silu(h Wg) * h Wu) Wd
              MoE:    p = softmax(h Wr); the top-k experts by p, weights
                      renormalised to sum to one; x += sum_e w_e SwiGLU_e(h)
  logits = rms(x) * g_f  Wunembed

The reference computes in float32 at the highest matmul precision, layer by
layer and expert by expert so that it fits beside the weights. With
``int8=True`` it computes in int8 instead, the lower-precision control that
``correct`` has to reject: every weight matmul in int8 (weights per output
column, activations per row, symmetric, int32 accumulation), and the
attention's q, k, v and probabilities rounded to int8 per row as an int8
KV cache and int8 attention would hold them.

Weights come as the program lays them out (see bench/weights.py); the
reference reads them by name.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _mm(x, w, int8: bool):
    """x (..., k) @ w (k, m) in f32 at full precision, or W8A8 int8."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if not int8:
        return jnp.matmul(x, w, precision=HI)
    sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
    sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0 + 1e-30
    xq = jnp.round(x / sx).astype(jnp.int8)
    wq = jnp.round(w / sw).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def _q8(x, int8: bool):
    """x rounded to int8 steps per row (last axis), symmetric."""
    if not int8:
        return x
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
    return jnp.round(x / s) * s


def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope(x, theta):
    """x (n, S, heads, d): rotate each (even, odd) pair by position."""
    n, s, h, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # (S, d/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _swiglu(h, wg, wu, wd, int8):
    return _mm(jax.nn.silu(_mm(h, wg, int8)) * _mm(h, wu, int8), wd, int8)


@functools.partial(jax.jit, static_argnames=("conf_key", "int8"))
def _layer(layers, i, x, *, conf_key, int8):
    conf = dict(conf_key)
    at = lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
    n, s, d = x.shape
    nh, nk = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, eps = conf["head_dim"], conf["rms_norm_eps"]
    a = layers["attn"]
    h = _rms(x, at(layers["norm_attn"]), eps)
    q = _rope(_mm(h, at(a["wq"]), int8).reshape(n, s, nh, hd),
              conf["rope_theta"])
    k = _rope(_mm(h, at(a["wk"]), int8).reshape(n, s, nk, hd),
              conf["rope_theta"])
    v = _mm(h, at(a["wv"]), int8).reshape(n, s, nk, hd)
    q, k, v = _q8(q, int8), _q8(k, int8), _q8(v, int8)
    k = jnp.repeat(k, nh // nk, axis=2)
    v = jnp.repeat(v, nh // nk, axis=2)
    sc = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", _q8(jax.nn.softmax(sc, axis=-1), int8),
                   v, precision=HI).reshape(n, s, nh * hd)
    x = x + _mm(o, at(a["wo"]), int8)
    h = _rms(x, at(layers["norm_mlp"]), eps)
    if "experts" not in layers:
        m = layers["mlp"]
        return x + _swiglu(h, at(m["w_gate"]), at(m["w_up"]),
                           at(m["w_down"]), int8)
    # routed experts: the router stays in f32 (a choice, not a matmul
    # whose precision the control lowers)
    probs = jax.nn.softmax(
        jnp.matmul(h, at(layers["router"]).astype(jnp.float32),
                   precision=HI), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, conf["num_experts_per_tok"])
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    ex = layers["experts"]

    def one_expert(e, y):
        w = jnp.sum(jnp.where(top_i == e, top_p, 0.0), axis=-1)  # (n, S)
        pick = lambda a_: jax.lax.dynamic_index_in_dim(at(a_), e,
                                                      keepdims=False)
        out = _swiglu(h, pick(ex["w_gate"]), pick(ex["w_up"]),
                      pick(ex["w_down"]), int8)
        return y + w[..., None] * out

    return x + jax.lax.fori_loop(0, conf["num_local_experts"], one_expert,
                                 jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("conf_key", "int8"))
def _head(embed, x, targets, *, conf_key, int8):
    """Per position: the largest logit, the logit of ``targets`` and the
    position's own first choice."""
    conf = dict(conf_key)
    h = _rms(x, embed["norm_f"], conf["rms_norm_eps"])
    w = embed["tok"].T if conf["tie_word_embeddings"] else embed["unembed"]
    logits = _mm(h, w, int8)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.max(logits, axis=-1), tgt, jnp.argmax(logits, axis=-1)


def _key(conf: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "num_experts_per_tok",
            "num_local_experts", "tie_word_embeddings")
    return tuple((k, conf[k]) for k in keys if k in conf)


def hidden(conf: dict, params, tokens, int8: bool = False):
    """Final hidden states (n, S, d) f32 of token rows (n, S), each row a
    sequence from position 0 (padding after a row's end is harmless: the
    attention is causal)."""
    x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(jnp.float32)
    for i in range(conf["num_hidden_layers"]):
        x = _layer(params["layers"], jnp.int32(i), x, conf_key=_key(conf),
                   int8=int8)
    return x


def logit_stats(conf: dict, params, x, targets, int8: bool = False):
    """(largest logit, logit of ``targets``, first choice), each (n, S)."""
    return _head(params["embed"], x, targets, conf_key=_key(conf),
                 int8=int8)
