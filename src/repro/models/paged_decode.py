"""Paged decode fast path: the serving engine's hot loop over a block-paged
KV pool (serving/kvcache.py) attending via the Pallas flash-decode kernel
(kernels/paged_attention.py; interpret-mode on CPU, Mosaic on TPU).

Pool layout here is the kernel's native layout with a leading stacked-layer
axis:  k_pages / v_pages : (L, K, n_blocks, page, D).  ``jax.lax.scan`` over
L feeds each layer's (K, P, page, D) slice straight to the kernel — no
per-step transpose of the pool.

Two entry points:

  * ``prefill_bucketed`` — run a prompt padded to a power-of-2 bucket so the
    jit cache holds O(log max_seq) programs instead of one per prompt length
    (the seed engine recompiled ``prefill`` for every new prompt length).
    Causality makes the tail padding invisible to positions < true_len, so
    the last real token's logits and the first true_len KV rows are exact.
  * ``decode_step_paged`` — one continuous-batching decode step: write each
    request's new KV into its current page (scatter by block table), attend
    over the paged pool, sample on device. One host sync per step.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.paged_attention_int8 import (dequantize_pages,
                                                quantize_pages)
from repro.models import hybrid as H
from repro.models import layers as L
from repro.models import moe as M
from repro.serving.sampling import sample

# families the paged serving path covers (vlm/audio/ssm are not engine
# targets: encoder-only or pure-recurrent — see serving/engine.py)
PAGED_FAMILIES = ("dense", "moe", "hybrid")


def kv_layer_indices(cfg):
    """Model layer indices that carry paged KV. All layers for dense/moe;
    only the local-attention layers of a hybrid stack (RG-LRU layers carry
    recurrent state, replicated as blobs instead)."""
    if cfg.arch_type == "hybrid":
        return tuple(i for i, k in enumerate(cfg.layer_kinds())
                     if k == "attn")
    return tuple(range(cfg.n_layers))


def mlp_apply(cfg, p, h, *, decode: bool):
    """Per-family MLP for one layer of the paged path. ``p`` is that layer's
    param dict; MoE routes through the experts (drop-free — see moe.py)."""
    if cfg.arch_type == "moe":
        return M.decode_mlp(cfg, p, h) if decode \
            else M.serving_prefill_mlp(cfg, p, h)
    return L.mlp(p["mlp"], h)


def next_bucket(n: int, lo: int = 8) -> int:
    """Smallest power of two >= max(n, lo)."""
    b = lo
    while b < n:
        b *= 2
    return b


def table_pages(cfg, max_seq: int) -> int:
    """Block-table width (pages per slot) for serving ``max_seq``.

    Windowed archs recycle pages out of the attention window, so the table
    only ever holds the resident ring: ceil(window/page) + 1 pages (the
    window can straddle a page boundary). Unwindowed archs keep the whole
    sequence resident."""
    full = -(-max_seq // cfg.page_size)
    if not cfg.sliding_window:
        return full
    return min(full, -(-cfg.sliding_window // cfg.page_size) + 1)


def kv_dtype(cfg):
    """Paged-pool storage dtype (see layers.kv_cache_dtype)."""
    return L.kv_cache_dtype(cfg)


def init_pages(cfg, n_blocks: int, page_size: int, dtype=None):
    """Zeroed paged pool buffers in kernel layout (L, K, P, page, D)."""
    dtype = dtype or kv_dtype(cfg)
    shape = (cfg.n_layers, cfg.n_kv_heads, n_blocks, page_size, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


# --------------------------------------------------------------------------
# prefill (bucketed)
# --------------------------------------------------------------------------

def prefill_bucketed(cfg, params, tokens, true_len, *, q_chunk: int = 1024):
    """Prompt forward over bucket-padded tokens.

    tokens: (1, S_bucket) int32, positions [true_len, S_bucket) are padding;
    true_len: () int32 (traced — one compile per bucket, not per length).
    Returns (logits (1, V) at position true_len-1, k, v (L, S_bucket, K, D)).
    Rows >= true_len of k/v are garbage and must be masked/overwritten by the
    caller (the paged engine masks by length and overwrites them on decode).
    """
    x = L.embed(params["embed"], tokens)
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    q_chunk = min(q_chunk, s)

    def body(x, p):
        h = L.rms_norm(x, p["norm_attn"], cfg.norm_eps)
        q, k, v = L.qkv_proj(p["attn"], cfg, h, positions)
        o = L.attention(q, k, v, causal=True, window=cfg.sliding_window,
                        q_chunk=q_chunk)
        x = x + L.attn_out(p["attn"], o)
        h = L.rms_norm(x, p["norm_mlp"], cfg.norm_eps)
        x = x + mlp_apply(cfg, p, h, decode=False)
        return x, (k[0].astype(kv_dtype(cfg)), v[0].astype(kv_dtype(cfg)))

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    x = L.rms_norm(x, params["embed"]["norm_f"], cfg.norm_eps)
    xt = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)  # (1,1,d)
    # f32 logits to match transformer.prefill (greedy tie determinism)
    logits = L.unembed(params["embed"], cfg, xt.astype(jnp.float32))
    return logits[:, 0], ks, vs


def init_chunk_buffers(cfg, bucket: int):
    """Zeroed full-precision KV carry buffers for a chunked prefill:
    (L_kv, S_bucket, K, D) in the ACTIVATION dtype — later chunks attend
    over earlier chunks' keys at exactly the precision the monolithic
    prefill sees, which is what makes chunked == monolithic bitwise on
    dense/MoE. Cast to the pool's KV dtype only at page-write time."""
    nl = len(kv_layer_indices(cfg))
    shape = (nl, bucket, cfg.n_kv_heads, cfg.head_dim)
    dt = jnp.dtype(cfg.dtype)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


def seed_chunk_buffers(k_buf, v_buf, k_pages, v_pages, slots):
    """Seed the leading rows of chunked-prefill carry buffers from cached
    pool pages (prefix-cache resume): ``slots`` are the shared page slots
    covering buffer rows [0, len(slots)*page). Bitwise-exact only when the
    pool stores KV in the buffers' activation dtype (the engine's
    ``prefix_skip_compute`` gate); rows past the cached run stay zero and
    are recomputed by the resumed chunks before any query attends them."""
    if not slots:
        return k_buf, v_buf
    idx = jnp.asarray(slots, jnp.int32)
    return (_seed_chunk_buf(k_buf, k_pages, idx),
            _seed_chunk_buf(v_buf, v_pages, idx))


@jax.jit
def _seed_chunk_buf(buf, pages, idx):
    # (L, K, P, page, D) pool pages -> (L, n*page, K, D) buffer rows; the
    # gather+transpose+update fuses into one program per distinct page
    # count (shared-prefix lengths are few, so the jit cache stays small)
    g = pages[:, :, idx]                        # (L, K, n, page, D)
    l, k, n, p, d = g.shape
    rows = g.transpose(0, 2, 3, 1, 4).reshape(l, n * p, k, d)
    return buf.at[:, :n * p].set(rows.astype(buf.dtype))


def init_hybrid_chunk_state(cfg, batch: int = 1):
    """Fresh per-rglru-layer carry state for a chunked hybrid prefill.
    Zeros make the first chunk's resume path exactly equivalent to a fresh
    scan (see ``hybrid.recurrent_prefill_resume``)."""
    w = cfg.lru_width
    return [{"h": jnp.zeros((batch, w), jnp.float32),
             "conv": jnp.zeros((batch, H.CONV_WIDTH - 1, w), jnp.bfloat16)}
            for _ in H.recurrent_layer_indices(cfg)]


def prefill_chunk(cfg, params, tokens, start, take, k_buf, v_buf, *,
                  q_chunk: int = 1024):
    """One chunk of a chunked prefill (dense/MoE).

    tokens: (1, C) int32 — prompt rows at absolute positions
    [start, start + C); rows past the true prompt end are padding (causality
    plus the ``take``-relative logits slice make them invisible).
    start: () int32 — absolute position of the chunk's first row (must be a
    multiple of C; the engine normalizes the chunk size to a power of two so
    chunks always tile the bucket).
    take: () int32 — rows of this chunk that are real prompt (== C except on
    the final, possibly partial, chunk).
    k_buf/v_buf: (L, S_bucket, K, D) carry from ``init_chunk_buffers``.

    Each layer updates its buffer rows [start, start + C) then attends the
    C query rows against the FULL buffer with ``q_offset=start`` — masked
    (future / out-of-window) entries contribute exact zeros, so the chunked
    KV rows and logits are bitwise identical to ``prefill_bucketed``.
    Returns (logits (1, V) at absolute position start + take - 1, k_buf,
    v_buf). Intermediate chunks' logits are a by-product (the unembed of one
    row is cheap); only the final chunk's are sampled.
    """
    x = L.embed(params["embed"], tokens)
    b, c, _ = x.shape
    positions = start + jnp.arange(c, dtype=jnp.int32)[None, :]
    q_chunk = min(q_chunk, c)

    def body(x, layer):
        p, (kb, vb) = layer
        h = L.rms_norm(x, p["norm_attn"], cfg.norm_eps)
        q, k, v = L.qkv_proj(p["attn"], cfg, h, positions)
        kb = jax.lax.dynamic_update_slice_in_dim(kb, k[0], start, axis=0)
        vb = jax.lax.dynamic_update_slice_in_dim(vb, v[0], start, axis=0)
        o = L.attention(q, kb[None], vb[None], causal=True,
                        window=cfg.sliding_window, q_offset=start,
                        q_chunk=q_chunk)
        x = x + L.attn_out(p["attn"], o)
        h = L.rms_norm(x, p["norm_mlp"], cfg.norm_eps)
        x = x + mlp_apply(cfg, p, h, decode=False)
        return x, (kb, vb)

    x, (k_buf, v_buf) = jax.lax.scan(body, x,
                                     (params["layers"], (k_buf, v_buf)))
    x = L.rms_norm(x, params["embed"]["norm_f"], cfg.norm_eps)
    xt = jax.lax.dynamic_slice_in_dim(x, take - 1, 1, axis=1)
    logits = L.unembed(params["embed"], cfg, xt.astype(jnp.float32))
    return logits[:, 0], k_buf, v_buf


def prefill_hybrid_chunk(cfg, params, tokens, start, take, k_buf, v_buf,
                         rstates, *, q_chunk: int = 1024):
    """One chunk of a chunked hybrid prefill: attention layers carry KV
    buffers exactly like ``prefill_chunk`` (L axis = attn layers in depth
    order); RG-LRU layers resume from and re-emit per-layer carry states
    (``hybrid.recurrent_prefill_resume``). The recurrence is mathematically
    identical to the monolithic scan but the associative-scan reduction tree
    differs across chunk lengths, so hybrid chunking is allclose + same
    greedy token rather than bitwise.

    Returns (logits (1, V) at start + take - 1, k_buf, v_buf, rstates,
    blob (1, state_blob_words)) — the blob is packed every chunk (a cheap
    concat) so the final chunk's output is engine-ready.
    """
    x = L.embed(params["embed"], tokens)
    b, c, _ = x.shape
    positions = start + jnp.arange(c, dtype=jnp.int32)[None, :]
    q_chunk = min(q_chunk, c)
    new_states = []
    ai = ri = 0
    for p, kind in zip(params["layers"], cfg.layer_kinds()):
        if kind == "rglru":
            x, h, conv = H.recurrent_prefill_resume(cfg, p, x, take,
                                                    rstates[ri])
            new_states.append({"h": h, "conv": conv})
            ri += 1
        else:
            hh = L.rms_norm(x, p["norm_t"], cfg.norm_eps)
            q, k, v = L.qkv_proj(p["attn"], cfg, hh, positions)
            kb = jax.lax.dynamic_update_slice_in_dim(k_buf[ai], k[0], start,
                                                     axis=0)
            vb = jax.lax.dynamic_update_slice_in_dim(v_buf[ai], v[0], start,
                                                     axis=0)
            k_buf = k_buf.at[ai].set(kb)
            v_buf = v_buf.at[ai].set(vb)
            o = L.attention(q, kb[None], vb[None], causal=True,
                            window=cfg.sliding_window, q_offset=start,
                            q_chunk=q_chunk)
            x = x + L.attn_out(p["attn"], o)
            hh = L.rms_norm(x, p["norm_mlp"], cfg.norm_eps)
            x = x + L.mlp(p["mlp"], hh)
            ai += 1
    x = L.rms_norm(x, params["embed"]["norm_f"], cfg.norm_eps)
    xt = jax.lax.dynamic_slice_in_dim(x, take - 1, 1, axis=1)
    logits = L.unembed(params["embed"], cfg, xt.astype(jnp.float32))
    blob = H.pack_state_blob(cfg, new_states)
    return logits[:, 0], k_buf, v_buf, new_states, blob


def pack_pages(k_seq, v_seq, n_pages: int, page: int):
    """(L, S, K, D) prefill KV -> (L, K, n_pages, page, D) pool blocks.
    S must cover n_pages*page (bucket padding guarantees it)."""
    l, s, kh, d = k_seq.shape
    span = n_pages * page

    def to_blocks(x):
        x = x[:, :span].reshape(l, n_pages, page, kh, d)
        return x.transpose(0, 3, 1, 2, 4)               # (L, K, n_pages, page, D)

    return to_blocks(k_seq), to_blocks(v_seq)


# --------------------------------------------------------------------------
# decode (paged)
# --------------------------------------------------------------------------

def _paged_attn_layer(cfg, p, x, kl, vl, block_tables, lengths, dst_block,
                      dst_off, positions, *, norm_key: str,
                      interpret: bool | None, starts=None,
                      kl_scale=None, vl_scale=None):
    """One attention layer of the paged decode hot loop, shared by every
    family: scatter this step's KV into the current page, attend via the
    Pallas kernel, apply the family MLP. ``norm_key`` names the pre-attn
    norm param ("norm_attn" dense/moe, "norm_t" hybrid). ``starts`` is the
    per-slot window start relative to the first resident page (sliding-
    window recycling); None means attend from position 0.

    When ``kl_scale``/``vl_scale`` are given the pool is int8: the step's
    new KV rows are quantized (per-token symmetric scales) before the
    scatter and attention runs through the int8 kernel — HBM only ever sees
    quantized bytes on this path.
    Returns (x, kl, vl, kl_scale, vl_scale)."""
    h = L.rms_norm(x, p[norm_key], cfg.norm_eps)
    q, k, v = L.qkv_proj(p["attn"], cfg, h, positions)   # (B,1,{H,K},D)
    k_rows = jnp.swapaxes(k[:, 0], 0, 1)                 # (K, B, D)
    v_rows = jnp.swapaxes(v[:, 0], 0, 1)
    if kl_scale is not None:
        kq, ks = quantize_pages(k_rows)
        vq, vs = quantize_pages(v_rows)
        kl = kl.at[:, dst_block, dst_off].set(kq)
        vl = vl.at[:, dst_block, dst_off].set(vq)
        kl_scale = kl_scale.at[:, dst_block, dst_off].set(ks)
        vl_scale = vl_scale.at[:, dst_block, dst_off].set(vs)
        o = ops.paged_attention_int8(q[:, 0], kl, kl_scale, vl, vl_scale,
                                     block_tables, lengths, starts,
                                     interpret=interpret)
    else:
        kl = kl.at[:, dst_block, dst_off].set(k_rows.astype(kl.dtype))
        vl = vl.at[:, dst_block, dst_off].set(v_rows.astype(vl.dtype))
        o = ops.paged_attention(q[:, 0], kl, vl, block_tables, lengths,
                                starts, interpret=interpret)
    x = x + L.attn_out(p["attn"], o[:, None].astype(x.dtype))
    h = L.rms_norm(x, p["norm_mlp"], cfg.norm_eps)
    x = x + mlp_apply(cfg, p, h, decode=True)
    return x, kl, vl, kl_scale, vl_scale


def _sample_head(cfg, params, x, rng, temperature):
    """Final norm -> f32 logits -> on-device sample (shared decode tail)."""
    x = L.rms_norm(x, params["embed"]["norm_f"], cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg,
                       x.astype(jnp.float32))[:, 0]      # (B, V)
    nxt = sample(logits, rng=rng, temperature=temperature)
    return nxt, logits


def _window_addressing(cfg, page: int, block_tables, pos, base):
    """Shared decode addressing: where this step's KV lands and what the
    kernel may attend to, in WINDOW-RELATIVE coordinates.

    ``base`` (B,) int32 is the absolute position of each slot's first
    resident page (always 0 on unwindowed archs / when None). Block tables
    are packed window-relative: column j holds logical page base//page + j.
    Returns (dst_block, dst_off, lengths, starts) — lengths/starts are
    relative to ``base``; ``starts`` masks the stale intra-page prefix older
    than the sliding window (None when the arch has no window)."""
    b = pos.shape[0]
    rows = jnp.arange(b)
    if base is None:
        base = jnp.zeros_like(pos)
    rel = pos - base
    dst_block = block_tables[rows, rel // page]          # (B,) physical slots
    dst_off = rel % page
    lengths, starts = attended_range(pos, base, cfg.sliding_window, jnp)
    return dst_block, dst_off, lengths, starts


def attended_range(pos, base, window: int, xp=jnp):
    """(lengths, starts) of what a decode step writing ABSOLUTE ``pos``
    attends to, relative to ``base``: positions [starts, lengths). ``starts``
    is None without a window. ``xp`` is ``jnp`` in the program, ``np`` for
    the host's own count of the kernel's work."""
    lengths = pos - base + 1
    if not window:
        return lengths, None
    return lengths, xp.maximum(xp.maximum(pos + 1 - window, 0) - base, 0)


def decode_step_paged(cfg, params, token, k_pages, v_pages, block_tables,
                      pos, rng=None, *, base=None, k_scales=None,
                      v_scales=None, temperature: float = 0.0,
                      interpret: bool | None = None):
    """One decode step for B slots over the paged pool.

    token: (B,) int32 — last sampled token per slot (garbage for idle slots);
    k_pages/v_pages: (L, K, P, page, D); block_tables: (B, table_pages)
    int32 physical block per resident logical page (idle slots point every
    entry at a scratch block); pos: (B,) int32 — ABSOLUTE write position ==
    current length (RoPE uses it unchanged); base: optional (B,) int32 —
    absolute position of each slot's first resident page under sliding-
    window recycling (None ≡ zeros: nothing recycled).

    Quantized pool: pass ``k_scales``/``v_scales`` (L, K, P, page, 1) with
    int8 ``k_pages``/``v_pages`` — the step quantizes its new KV rows,
    attends through the int8 kernel, and additionally returns the updated
    scale arrays.

    Each layer scatters the new KV into
    (block_tables[b, (pos-base)//page], pos%page) and attends via the Pallas
    paged kernel over [max(0, pos+1-window), pos] — recycled pages are
    simply absent from the table. Sampling stays on device: returns
    (next_token (B,), logits (B, V), k_pages, v_pages[, k_scales, v_scales])
    with a single host sync left to the caller.
    """
    page = k_pages.shape[3]
    quant = k_scales is not None
    dst_block, dst_off, lengths, starts = _window_addressing(
        cfg, page, block_tables, pos, base)
    positions = pos[:, None]
    x = L.embed(params["embed"], token[:, None])         # (B, 1, d)

    def body(x, layer):
        if quant:
            p, (kl, vl, ksl, vsl) = layer
        else:
            p, (kl, vl) = layer
            ksl = vsl = None
        x, kl, vl, ksl, vsl = _paged_attn_layer(
            cfg, p, x, kl, vl, block_tables, lengths, dst_block, dst_off,
            positions, norm_key="norm_attn", interpret=interpret,
            starts=starts, kl_scale=ksl, vl_scale=vsl)
        return x, ((kl, vl, ksl, vsl) if quant else (kl, vl))

    if quant:
        x, (k_pages, v_pages, k_scales, v_scales) = jax.lax.scan(
            body, x, (params["layers"],
                      (k_pages, v_pages, k_scales, v_scales)))
    else:
        x, (k_pages, v_pages) = jax.lax.scan(
            body, x, (params["layers"], (k_pages, v_pages)))
    nxt, logits = _sample_head(cfg, params, x, rng, temperature)
    if quant:
        return nxt, logits, k_pages, v_pages, k_scales, v_scales
    return nxt, logits, k_pages, v_pages


# --------------------------------------------------------------------------
# hybrid (RG-LRU + local attention): paged KV for attn layers, state blobs
# for the recurrence
# --------------------------------------------------------------------------

def prefill_hybrid_bucketed(cfg, params, tokens, true_len, *,
                            q_chunk: int = 1024):
    """Hybrid prompt forward over bucket-padded tokens.

    Attention layers behave exactly like ``prefill_bucketed`` (causality
    hides the tail padding); RG-LRU layers additionally need their decode
    state extracted *at* ``true_len`` rather than at the padded end —
    ``hybrid.recurrent_prefill`` does that slice.

    Returns (logits (1, V) at true_len - 1,
             k, v (L_attn, S_bucket, K, D) — attention layers only, in
             depth order, rows >= true_len garbage as in the dense path,
             state_blob (1, state_blob_words) f32).
    """
    x = L.embed(params["embed"], tokens)
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    q_chunk = min(q_chunk, s)
    ks, vs, states = [], [], []
    for p, kind in zip(params["layers"], cfg.layer_kinds()):
        if kind == "rglru":
            x, h, conv = H.recurrent_prefill(cfg, p, x, true_len)
            states.append({"h": h, "conv": conv})
        else:
            hh = L.rms_norm(x, p["norm_t"], cfg.norm_eps)
            q, k, v = L.qkv_proj(p["attn"], cfg, hh, positions)
            o = L.attention(q, k, v, causal=True, window=cfg.sliding_window,
                            q_chunk=q_chunk)
            x = x + L.attn_out(p["attn"], o)
            hh = L.rms_norm(x, p["norm_mlp"], cfg.norm_eps)
            x = x + L.mlp(p["mlp"], hh)
            ks.append(k[0].astype(kv_dtype(cfg)))
            vs.append(v[0].astype(kv_dtype(cfg)))
    x = L.rms_norm(x, params["embed"]["norm_f"], cfg.norm_eps)
    xt = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
    logits = L.unembed(params["embed"], cfg, xt.astype(jnp.float32))
    blob = H.pack_state_blob(cfg, states)
    return logits[:, 0], jnp.stack(ks), jnp.stack(vs), blob


def decode_step_paged_hybrid(cfg, params, token, k_pages, v_pages, blobs,
                             block_tables, blob_slots, pos, rng=None, *,
                             base=None, k_scales=None, v_scales=None,
                             blob_scales=None, temperature: float = 0.0,
                             interpret: bool | None = None):
    """One hybrid decode step: paged attention for the local-attn layers
    (pool layer axis = attn layers in depth order), O(1) RG-LRU steps for
    the recurrent layers with state gathered from / scattered back to the
    pool's blob store — the blob IS the source of truth, so a promoted
    replica blob resumes byte-identically with no extra unpacking step.

    token: (B,) int32; k_pages/v_pages: (L_attn, K, P, page, D);
    blobs: (n_blobs, state_blob_words) f32; block_tables: (B, table_pages);
    blob_slots: (B,) int32 physical blob slot per engine slot (idle slots
    point at a scratch blob); pos: (B,) int32 absolute; base: optional (B,)
    int32 first-resident-page position (sliding-window recycling — the
    local-attention window IS cfg.sliding_window, so tables hold only the
    resident ring once decode passes it).

    Quantized pool: pass ``k_scales``/``v_scales``/``blob_scales`` with
    int8 pages and blobs. The recurrent state is dequantized from the int8
    blob, advanced one step, and re-quantized back — the quantized blob
    stays the source of truth, so a promoted replica (identical int8 bytes
    + scales) resumes bit-identically.
    Returns (next_token, logits, k_pages, v_pages, blobs[, k_scales,
    v_scales, blob_scales]).
    """
    page = k_pages.shape[3]
    quant = k_scales is not None
    dst_block, dst_off, lengths, starts = _window_addressing(
        cfg, page, block_tables, pos, base)
    positions = pos[:, None]
    x = L.embed(params["embed"], token[:, None])         # (B, 1, d)
    if quant:
        state_vec = dequantize_pages(blobs[blob_slots],
                                     blob_scales[blob_slots])
    else:
        state_vec = blobs[blob_slots]
    states = H.unpack_state_blob(cfg, state_vec)
    new_states = []
    ai = ri = 0
    for p, kind in zip(params["layers"], cfg.layer_kinds()):
        if kind == "rglru":
            x, st = H._recurrent_block(cfg, p, x, state=states[ri])
            new_states.append(st)
            ri += 1
        else:
            ksl = k_scales[ai] if quant else None
            vsl = v_scales[ai] if quant else None
            x, kl, vl, ksl, vsl = _paged_attn_layer(
                cfg, p, x, k_pages[ai], v_pages[ai], block_tables, lengths,
                dst_block, dst_off, positions, norm_key="norm_t",
                interpret=interpret, starts=starts,
                kl_scale=ksl, vl_scale=vsl)
            k_pages = k_pages.at[ai].set(kl)
            v_pages = v_pages.at[ai].set(vl)
            if quant:
                k_scales = k_scales.at[ai].set(ksl)
                v_scales = v_scales.at[ai].set(vsl)
            ai += 1
    new_blob = H.pack_state_blob(cfg, new_states)
    if quant:
        bq, bs = quantize_pages(new_blob)
        blobs = blobs.at[blob_slots].set(bq)
        blob_scales = blob_scales.at[blob_slots].set(bs)
    else:
        blobs = blobs.at[blob_slots].set(new_blob)
    nxt, logits = _sample_head(cfg, params, x, rng, temperature)
    if quant:
        return (nxt, logits, k_pages, v_pages, blobs,
                k_scales, v_scales, blob_scales)
    return nxt, logits, k_pages, v_pages, blobs
