"""Shared building blocks: RMSNorm, RoPE, GQA attention (chunked/flash-style),
SwiGLU. Pure functional JAX; params are plain dicts of jnp arrays.

Attention is implemented with a scan over query chunks + online softmax so
prefill at 32k/500k never materializes the full S x S score matrix — this is
what lets every (arch x shape) combination lower on the production mesh.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------

def dense_init(rng, shape, scale: Optional[float] = None, dtype=jnp.bfloat16):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(rng, shape, dtype=jnp.float32) * scale).astype(dtype)


def kv_cache_dtype(cfg):
    """Unquantized KV-cache carrier dtype: cfg.kv_dtype, except int8
    configs keep bf16 payloads on paths that carry no quantization scales
    (the paged pool and the model-level reference caches — the quantized
    kernel is wired separately in kernels/paged_attention_int8)."""
    return jnp.bfloat16 if cfg.kv_dtype == "int8" else jnp.dtype(cfg.kv_dtype)


def rms_norm(x, weight, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    inv = rope_frequencies(d, theta)                       # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * inv   # (..., S, D/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., None, :]                                # broadcast over heads
    cos = cos[..., None, :]
    x1, x2 = x[..., ::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

NEG_INF = -1e30

# §Perf hillclimb knob (set by launch/dryrun via shard_hints()): mesh axis
# name that decode-KV sequence dims are sharded over. When set, attention
# pins its score/softmax chain to stay sequence-sharded — otherwise GSPMD
# reshards the (huge) cache to match the (tiny) heads-sharded q, which
# replicates the whole KV cache every layer (observed: 204 GB/step on
# deepseek-67b decode_32k; EXPERIMENTS.md §Perf iteration 2).
SEQ_SHARD_AXIS: str | None = None


class shard_hints:
    """Context manager: with shard_hints(seq_axis="model"): ... lower ..."""

    def __init__(self, seq_axis):
        self.seq_axis = seq_axis

    def __enter__(self):
        global SEQ_SHARD_AXIS
        self._old = SEQ_SHARD_AXIS
        SEQ_SHARD_AXIS = self.seq_axis

    def __exit__(self, *exc):
        global SEQ_SHARD_AXIS
        SEQ_SHARD_AXIS = self._old


def _constrain_seq(x, seq_dim: int):
    """Pin x's seq_dim to the hinted mesh axis (no-op when hints are off)."""
    if SEQ_SHARD_AXIS is None:
        return x
    from jax.sharding import PartitionSpec as _P
    U = _P.UNCONSTRAINED
    spec = [U] * x.ndim
    spec[seq_dim] = SEQ_SHARD_AXIS
    return jax.lax.with_sharding_constraint(x, _P(*spec))


def _expand_kv(k, q_heads: int):
    """(B,S,K,D) -> (B,S,H,D) by repeating each kv head q_per_kv times."""
    b, s, kh, d = k.shape
    if kh == q_heads:
        return k
    rep = q_heads // kh
    return jnp.repeat(k, rep, axis=2)


def attention(q, k, v, *, causal: bool, window: int = 0,
              q_offset=0, kv_len=None, q_chunk: int = 1024):
    """Chunked multi-head attention with online softmax.

    q: (B, Sq, H, D); k, v: (B, Skv, K, D) with K | H (GQA).
    causal: apply causal mask using absolute positions (q position =
      q_offset + index; kv position = index).
    window: if >0, query i attends only to kv positions > i - window (SWA).
    kv_len: optional (B,) or scalar count of valid kv entries (decode cache).
    Never materializes more than (B, H, q_chunk, Skv) scores at once.
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    scale = 1.0 / math.sqrt(d)
    # KV stays in its storage dtype (bf16 on TPU); matmuls accumulate in f32
    # via preferred_element_type — halves the attention HBM read vs
    # materializing an f32 copy of the whole cache (§Perf iteration 4).
    qt = (jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale).astype(q.dtype)
    kt = jnp.swapaxes(k, 1, 2)                               # (B,H,Skv,D)
    vt = jnp.swapaxes(v, 1, 2)

    kv_pos = jnp.arange(skv, dtype=jnp.int32)

    kt = _constrain_seq(kt, 2)
    vt = _constrain_seq(vt, 2)

    def chunk_attn(q_chunk_arr, q_pos):
        # q_chunk_arr: (B,H,c,D); q_pos: (c,) absolute positions
        s = jnp.einsum("bhqd,bhkd->bhqk", q_chunk_arr.astype(kt.dtype), kt,
                       preferred_element_type=jnp.float32)
        s = _constrain_seq(s, 3)            # scores stay KV-seq-sharded
        mask = jnp.ones((q_pos.shape[0], skv), dtype=bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        if kv_len is not None:
            klen = jnp.asarray(kv_len)
            if klen.ndim == 0:
                mask &= kv_pos[None, :] < klen
                s = jnp.where(mask[None, None], s, NEG_INF)
            else:  # per-batch lengths
                m2 = mask[None, :, :] & (kv_pos[None, None, :] < klen[:, None, None])
                s = jnp.where(m2[:, None], s, NEG_INF)
        else:
            s = jnp.where(mask[None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - jax.lax.stop_gradient(m))
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vt.dtype), vt,
                       preferred_element_type=jnp.float32)
        return o / (jnp.sum(p, axis=-1, keepdims=True) + 1e-30)

    if sq <= q_chunk:
        q_pos = q_offset + jnp.arange(sq, dtype=jnp.int32)
        out = chunk_attn(qt, q_pos)
    else:
        pad = (-sq) % q_chunk
        if pad:
            qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        sq_p = sq + pad
        n_chunks = sq_p // q_chunk
        qc = qt.reshape(b, h, n_chunks, q_chunk, d).transpose(2, 0, 1, 3, 4)

        def body(i, _):
            q_pos = q_offset + i * q_chunk + jnp.arange(q_chunk, dtype=jnp.int32)
            return i + 1, chunk_attn(qc[i], q_pos)

        # scan keeps a single chunk of scores live at a time
        _, outs = jax.lax.scan(lambda c, _: body(c, None), 0, None, length=n_chunks)
        out = outs.transpose(1, 2, 0, 3, 4).reshape(b, h, sq_p, d)[:, :, :sq]

    return jnp.swapaxes(out, 1, 2).astype(q.dtype)          # (B,Sq,H,D)


def kv_cache_update(cache, new, slot):
    """Write ``new`` (B,1,K,D) at sequence position ``slot`` of ``cache``
    (B,C,K,D) via a one-hot select. Unlike dynamic-update-slice with a
    traced offset, this lowers to pure elementwise ops that GSPMD shards
    cleanly when C (the cache sequence dim) is sharded over the model axis
    — the production decode layout (distributed/sharding.py)."""
    c = cache.shape[1]
    onehot = (jnp.arange(c, dtype=jnp.int32) == slot)[None, :, None, None]
    return jnp.where(onehot, new.astype(cache.dtype), cache)


# --------------------------------------------------------------------------
# attention block params + apply
# --------------------------------------------------------------------------

def init_attn(rng, cfg, dtype=jnp.bfloat16):
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rngs = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(rngs[0], (d, h * hd), dtype=dtype),
        "wk": dense_init(rngs[1], (d, k * hd), dtype=dtype),
        "wv": dense_init(rngs[2], (d, k * hd), dtype=dtype),
        "wo": dense_init(rngs[3], (h * hd, d), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((k * hd,), dtype)
        p["bv"] = jnp.zeros((k * hd,), dtype)
    return p


def qkv_proj(p, cfg, x, positions):
    """x: (B,S,d) -> q (B,S,H,D), k/v (B,S,K,D), with RoPE applied."""
    b, s, _ = x.shape
    h, k, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    kk = x @ p["wk"]
    vv = x @ p["wv"]
    if cfg.qkv_bias:
        q, kk, vv = q + p["bq"], kk + p["bk"], vv + p["bv"]
    q = q.reshape(b, s, h, hd)
    kk = kk.reshape(b, s, k, hd)
    vv = vv.reshape(b, s, k, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    kk = apply_rope(kk, positions, cfg.rope_theta)
    return q, kk, vv


def attn_out(p, o):
    b, s, h, d = o.shape
    return o.reshape(b, s, h * d) @ p["wo"]


# --------------------------------------------------------------------------
# SwiGLU MLP
# --------------------------------------------------------------------------

def init_mlp(rng, d_model: int, d_ff: int, dtype=jnp.bfloat16):
    r1, r2, r3 = jax.random.split(rng, 3)
    return {
        "w_gate": dense_init(r1, (d_model, d_ff), dtype=dtype),
        "w_up": dense_init(r2, (d_model, d_ff), dtype=dtype),
        "w_down": dense_init(r3, (d_ff, d_model), dtype=dtype),
    }


def mlp(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


# --------------------------------------------------------------------------
# embeddings / unembedding
# --------------------------------------------------------------------------

def init_embed(rng, cfg, dtype=jnp.bfloat16):
    r1, r2 = jax.random.split(rng)
    p = {"tok": dense_init(r1, (cfg.vocab_size, cfg.d_model), scale=0.02, dtype=dtype),
         "norm_f": jnp.ones((cfg.d_model,), dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(r2, (cfg.d_model, cfg.vocab_size), dtype=dtype)
    return p


def embed(p, tokens):
    return jnp.take(p["tok"], tokens, axis=0)


def unembed(p, cfg, x):
    if cfg.tie_embeddings:
        return x @ p["tok"].T
    return x @ p["unembed"]
