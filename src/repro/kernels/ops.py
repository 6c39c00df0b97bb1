"""Public jit'd wrappers for the Pallas kernels.

``interpret=None`` auto-selects from the process's default backend: Mosaic
on TPU, interpret mode elsewhere (the kernel body runs as plain XLA ops, for
correctness checks on the CPU). The serving engine resolves the choice once
when it is built (``FamilyExecutor.interpret``); a compile against a
described TPU from a CPU process must pass ``interpret=False`` itself."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import paged_attention as _pa
from repro.kernels import paged_attention_int8 as _pa8
from repro.kernels import ssd_scan as _ssd


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_tables, lengths, starts=None,
                    interpret: bool | None = None):
    """Decode attention over a block-paged KV pool. ``starts`` (optional,
    (B,) int32) masks positions below a per-sequence window start — the
    sliding-window recycling path. See kernel docstring."""
    if interpret is None:
        interpret = default_interpret()
    assert q.ndim == 3 and k_pages.ndim == 4
    assert q.shape[1] % k_pages.shape[0] == 0, "H must be a multiple of K"
    return _pa.paged_attention(q, k_pages, v_pages, block_tables, lengths,
                               starts, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_int8(q, k_pages, k_scales, v_pages, v_scales,
                         block_tables, lengths, starts=None,
                         interpret: bool | None = None):
    """Decode attention over an INT8-quantized block-paged KV pool
    (per-row symmetric scales, dequantized in VMEM after the page DMA).
    Same ``starts`` window-lower-bound semantics as ``paged_attention``.
    See kernel docstring."""
    if interpret is None:
        interpret = default_interpret()
    assert q.ndim == 3 and k_pages.ndim == 4
    assert k_pages.dtype == jnp.int8 and v_pages.dtype == jnp.int8
    assert q.shape[1] % k_pages.shape[0] == 0, "H must be a multiple of K"
    return _pa8.paged_attention_int8(q, k_pages, k_scales, v_pages, v_scales,
                                     block_tables, lengths, starts,
                                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xdt, a, B, C, chunk: int = 64, interpret: bool | None = None):
    """Mamba-2 chunked SSD scan. See kernel docstring."""
    if interpret is None:
        interpret = default_interpret()
    return _ssd.ssd_scan(xdt, a, B, C, chunk=chunk, interpret=interpret)
