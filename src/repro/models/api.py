"""Unified model API: dispatch by ``cfg.arch_type``.

Every family exposes:
  init_params(cfg, rng)            -> params pytree
  forward(cfg, params, **inputs)   -> logits (train path)
  init_cache(cfg, batch, capacity) -> decode state (KV / recurrent / None)
  prefill(cfg, params, **inputs)   -> (last logits, cache, pos)
  decode_step(cfg, params, token, cache, pos) -> (logits, cache)
  loss(cfg, params, batch)         -> scalar train loss

``decode_capacity(cfg, shape)`` centralizes the DESIGN.md long-context
policy: ring-buffer window for SWA / long_500k dense variants, full-length
cache otherwise.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encoder, hybrid, moe, ssm, transformer, vlm

_FAMILIES = {
    "dense": transformer,
    "moe": moe,
    "ssm": ssm,
    "hybrid": hybrid,
    "vlm": vlm,
    "audio": encoder,
}


def family(cfg: ModelConfig):
    return _FAMILIES[cfg.arch_type]


def init_params(cfg: ModelConfig, rng):
    """Seeded random weights, built by one jitted program: each weight is
    drawn in float32 and cast inside the program, so only the cast weights
    reach device memory (an eager init would hold a float32 copy of every
    stacked weight at once)."""
    return _init_params_jit(cfg, rng)


@functools.partial(jax.jit, static_argnums=0)
def _init_params_jit(cfg: ModelConfig, rng):
    return family(cfg).init_params(cfg, rng)


# --------------------------------------------------------------------------
# decode window / capacity policy (DESIGN.md long_500k rules)
# --------------------------------------------------------------------------

def decode_window(cfg: ModelConfig, seq_len: int) -> int:
    """Effective ring-buffer window for decode at this context length.
    0 = full cache (no ring)."""
    if cfg.arch_type == "ssm":
        return 0                      # recurrent state; no KV at all
    if cfg.sliding_window:
        return cfg.sliding_window     # native SWA (mixtral, rg local attn)
    if cfg.long_context_window and seq_len > 65_536:
        return cfg.long_context_window  # dense long-context variant
    return 0


def decode_capacity(cfg: ModelConfig, seq_len: int) -> int:
    w = decode_window(cfg, seq_len)
    return w if w else seq_len


def init_cache(cfg: ModelConfig, batch: int, seq_len: int):
    if not cfg.has_decode:
        return None
    return family(cfg).init_cache(cfg, batch, decode_capacity(cfg, seq_len))


# --------------------------------------------------------------------------
# train loss
# --------------------------------------------------------------------------

def next_token_loss(cfg, params, tokens, q_chunk: int = 1024):
    """Causal LM loss over (B, S) tokens (inputs = tokens[:, :-1])."""
    mod = family(cfg)
    if cfg.arch_type == "moe":
        logits, aux = mod.forward(cfg, params, tokens[:, :-1],
                                  q_chunk=q_chunk, return_aux=True)
    else:
        logits = mod.forward(cfg, params, tokens[:, :-1], q_chunk=q_chunk)
        aux = 0.0
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + aux


def loss(cfg: ModelConfig, params, batch: Dict[str, Any], q_chunk: int = 1024):
    """batch keys by family:
      dense/moe/ssm/hybrid: tokens (B,S)
      vlm:   tokens (B,S_txt), patch_embeds (B,P,d)
      audio: frame_embeds (B,S,d), targets (B,S), mask (B,S)
    """
    if cfg.arch_type == "audio":
        return encoder.masked_unit_loss(cfg, params, batch["frame_embeds"],
                                        batch["targets"], batch["mask"])
    if cfg.arch_type == "vlm":
        logits = vlm.forward(cfg, params, batch["tokens"],
                             batch.get("patch_embeds"), q_chunk=q_chunk)
        npatch = 0 if batch.get("patch_embeds") is None else batch["patch_embeds"].shape[1]
        # predict text tokens only (shift within the text segment)
        text_logits = logits[:, npatch:-1] if npatch else logits[:, :-1]
        targets = batch["tokens"][:, 1:]
        logp = jax.nn.log_softmax(text_logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(nll)
    return next_token_loss(cfg, params, batch["tokens"], q_chunk=q_chunk)


# --------------------------------------------------------------------------
# prefill / decode
# --------------------------------------------------------------------------

def prefill(cfg: ModelConfig, params, batch: Dict[str, Any],
            seq_budget: Optional[int] = None, q_chunk: int = 1024):
    """Returns (last-token logits, cache, pos)."""
    mod = family(cfg)
    if cfg.arch_type == "audio":
        raise ValueError("encoder-only arch has no prefill/decode")
    s = batch["tokens"].shape[1]
    total = seq_budget or s
    window = decode_window(cfg, total)
    cap = window if window else total
    kw = dict(capacity=cap, q_chunk=q_chunk)
    if cfg.arch_type == "ssm":
        kw = dict(chunk=cfg.ssm_chunk)
    if cfg.arch_type == "vlm":
        return mod.prefill(cfg, params, batch["tokens"],
                           batch.get("patch_embeds"), **kw)
    if cfg.arch_type == "hybrid":
        return mod.prefill(cfg, params, batch["tokens"],
                           capacity=cap if cfg.sliding_window else 0,
                           q_chunk=q_chunk)
    if cfg.arch_type == "dense" or cfg.arch_type == "moe":
        wo = window if (window and not cfg.sliding_window) else None
        return mod.prefill(cfg, params, batch["tokens"], capacity=cap,
                           window_override=wo, q_chunk=q_chunk)
    return mod.prefill(cfg, params, batch["tokens"], **kw)


def decode_step(cfg: ModelConfig, params, token, cache, pos, seq_len: int):
    mod = family(cfg)
    window = decode_window(cfg, seq_len)
    if cfg.arch_type in ("ssm",):
        return mod.decode_step(cfg, params, token, cache, pos)
    if cfg.arch_type == "hybrid":
        return mod.decode_step(cfg, params, token, cache, pos)
    return mod.decode_step(cfg, params, token, cache, pos, window=window)
