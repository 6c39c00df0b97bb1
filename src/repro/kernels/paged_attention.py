"""Pallas TPU kernel: decode attention over a block-paged KV pool.

This is the compute hot-spot fed by KevlarFlow's block-replicated KV cache:
the same (K, pages, page_size, D) pool layout is the unit of background
replication, so a migrated request's pages are consumed here unchanged.

TPU design (DESIGN.md hardware adaptation):
  * grid = (batch,): one grid step per slot, all KV heads at once. Inside
    the step a loop walks the slot's *blocks* of ``pages_per_block`` table
    pages; flash-decoding statistics (m, l, acc) live in VMEM scratch
    across the loop.
  * the loop runs only over blocks that hold a position in
    [starts[b], lengths[b]): blocks past the length, or wholly below the
    window start, cost neither a DMA nor compute, and an idle slot
    (length 1 on the scratch block) costs one block. Table entries past the
    last valid page are never read.
  * K/V stay in HBM (``memory_space=pl.ANY``). Each block is gathered by one
    strided async copy per live page and per pool (``k_hbm.at[:, page_id]``,
    every KV head) into a double buffer in VMEM. The block table is a
    scalar-prefetch operand, so the page ids are in SMEM before the copies
    are issued — that is how a "gather" becomes a sequence of dense
    page-sized DMAs on TPU (no warp-level gather exists here, unlike the
    CUDA original).
  * double buffering runs across blocks and across slots: while block i is
    computed, block i+1 (or the next slot's first block) is in flight. The
    buffer parity is carried in SMEM scratch, so the grid axis is
    sequential ("arbitrary").
  * the block size is a pure function of the shapes (``pages_per_block``),
    ~256 positions per block within a VMEM budget; a table width that is
    not a multiple of it leaves a partial last block, masked, never padded.
  * arithmetic: q·k takes q and K in their storage dtype into the MXU with
    float32 accumulation (a bf16×bf16 product is exact in float32); p stays
    float32 and V is widened to float32 for p·v; the softmax statistics and
    the accumulator are float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
NEG_INF = -1e30
# positions a block aims at, and the VMEM its K/V double buffers may take
# (counted at 4 bytes an element, so a float32 pool fits too)
BLOCK_TOKENS = 256
BLOCK_VMEM_BYTES = 4 * 2**20


def pages_per_block(page: int, table_pages: int, kheads: int,
                    head_dim: int) -> int:
    """Table pages the kernel fetches per block: ~``BLOCK_TOKENS``
    positions, never more than the table holds, and K/V double buffers
    (2 pools × 2 buffers × kheads × positions × head_dim) within
    ``BLOCK_VMEM_BYTES``."""
    per_page = 2 * 2 * kheads * page * head_dim * 4
    return max(1, min(BLOCK_TOKENS // page, table_pages,
                      BLOCK_VMEM_BYTES // per_page))


def block_bounds(lengths, starts, block_tokens: int, n_blocks: int, xp=jnp):
    """[lo, hi) of the blocks the kernel walks for a slot: those that hold
    a position in [starts, lengths), and at least one, so that an idle slot
    costs a block and the chain of prefetches never breaks. ``xp`` is
    ``jnp`` in the kernel and ``np`` for a host-side count."""
    lo = xp.clip(starts // block_tokens, 0, n_blocks - 1)
    return lo, xp.clip(-(-lengths // block_tokens), lo + 1, n_blocks)


def kv_blocks(lengths, starts, page: int, table_pages: int, kheads: int,
              head_dim: int) -> tuple[int, int]:
    """(blocks one call fetches, blocks its table holds) for slots with
    these ``lengths`` and ``starts`` (numpy, (B,); ``starts`` None ≡ 0):
    the host's count of the kernel's work, per layer."""
    ppb = pages_per_block(page, table_pages, kheads, head_dim)
    n_blocks = -(-table_pages // ppb)
    lengths = np.asarray(lengths)
    starts = np.zeros_like(lengths) if starts is None else np.asarray(starts)
    lo, hi = block_bounds(lengths, starts, ppb * page, n_blocks, np)
    return int((hi - lo).sum()), len(lengths) * n_blocks


def _kernel(bt_ref, len_ref, start_ref,      # scalar prefetch (SMEM)
            q_ref, k_hbm, v_hbm,             # q block; K/V pools in HBM
            o_ref,                           # VMEM output
            kbuf, vbuf, sems, parity_ref,    # double buffers, DMA state
            m_ref, l_ref, acc_ref,           # VMEM statistics
            *, ppb: int):
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    kheads, _, page, _ = k_hbm.shape
    rep = q_ref.shape[1]
    table_pages = bt_ref.shape[1]
    bk = ppb * page
    n_blocks = -(-table_pages // ppb)

    def bounds(s):
        return block_bounds(len_ref[s], start_ref[s], bk, n_blocks)

    def pages(s, blk):
        """[lo, hi) of the table pages of block ``blk`` of slot ``s`` that
        hold a position in [start, length): the only pages fetched."""
        lo = jnp.maximum(blk * ppb, start_ref[s] // page)
        hi = jnp.minimum(jnp.minimum((blk + 1) * ppb, table_pages),
                         -(-len_ref[s] // page))
        return lo, hi

    def over_pages(s, blk, buf, op):
        """``op`` on the K and V copies of each fetched page of block
        ``blk`` of slot ``s`` into buffer ``buf``."""
        def one(g, carry):
            pid = bt_ref[s, g]
            rows = pl.ds(pl.multiple_of((g - blk * ppb) * page, page), page)
            for w, (src, dst) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                op(pltpu.make_async_copy(src.at[:, pid], dst.at[buf, :, rows],
                                         sems.at[w, buf]))
            return carry
        jax.lax.fori_loop(*pages(s, blk), one, 0)

    def fetch(s, blk, buf):
        over_pages(s, blk, buf, lambda c: c.start())

    def wait(s, blk, buf):
        over_pages(s, blk, buf, lambda c: c.wait())

    @pl.when(b == 0)
    def _prime():
        parity_ref[0] = 0
        fetch(0, bounds(0)[0], 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    length, start = len_ref[b], start_ref[b]
    lo, hi = bounds(b)
    scale = 1.0 / math.sqrt(q_ref.shape[-1])

    def body(i, buf):
        nxt = 1 - buf

        @pl.when(i + 1 < hi)
        def _next_block():
            fetch(b, i + 1, nxt)

        @pl.when((i + 1 == hi) & (b + 1 < n_slots))
        def _next_slot():
            fetch(b + 1, bounds(b + 1)[0], nxt)

        wait(b, i, buf)
        # mask positions beyond this sequence's length AND below its window
        # start (sliding-window recycling: positions are window-relative;
        # resident pages can carry a stale prefix older than the window).
        # Pages not fetched hold stale buffer bytes: masked out of s, and
        # zeroed in V so that p·v never sees them.
        pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (rep, bk), 1)
        valid = (pos >= start) & (pos < length)
        row = i * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        row_valid = (row >= start) & (row < length)
        for h in range(kheads):
            q = q_ref[h]                                   # (rep, D)
            k = kbuf[buf, h]                               # (bk, D)
            if q.dtype != k.dtype:
                q, k = q.astype(jnp.float32), k.astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * scale, NEG_INF)
            m_prev = m_ref[h, :, :1]                       # (rep, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # the where keeps fully-masked blocks exact: with m_new still
            # NEG_INF, exp(s - m_new) == exp(0) would otherwise leak weight 1
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # (rep, bk)
            v = jnp.where(row_valid, vbuf[buf, h].astype(jnp.float32), 0.0)
            l_new = alpha * l_ref[h, :, :1] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, (rep, LANES))
            l_ref[h] = jnp.broadcast_to(l_new, (rep, LANES))
        return nxt

    parity_ref[0] = jax.lax.fori_loop(lo, hi, body, parity_ref[0])
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[:, :, :1], 1e-30)
                  ).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, starts=None,
                    *, interpret: bool = False):
    """q: (B, H, D); k_pages/v_pages: (K, P, page, D);
    block_tables: (B, pages_per_seq) int32; lengths: (B,) int32;
    starts: optional (B,) int32 lower bound — positions < starts[b] are
    masked out (sliding-window serving passes the window start relative to
    the first resident page; None ≡ zeros, the full-prefix behaviour).
    Returns (B, H, D)."""
    b, h, d = q.shape
    kheads, _, page, _ = k_pages.shape
    rep = h // kheads
    table_pages = block_tables.shape[1]
    ppb = pages_per_block(page, table_pages, kheads, d)
    qr = q.reshape(b, kheads, rep, d)
    if starts is None:
        starts = jnp.zeros_like(lengths)

    def slot_map(b_, bt, ln, st):
        return (b_, 0, 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, ppb=ppb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, kheads, rep, d), slot_map),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, kheads, rep, d), slot_map),
            scratch_shapes=[
                pltpu.VMEM((2, kheads, ppb * page, d), k_pages.dtype),
                pltpu.VMEM((2, kheads, ppb * page, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),                 # buffer parity
                pltpu.VMEM((kheads, rep, LANES), jnp.float32),   # m
                pltpu.VMEM((kheads, rep, LANES), jnp.float32),   # l
                pltpu.VMEM((kheads, rep, d), jnp.float32),       # acc
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct((b, kheads, rep, d), q.dtype),
        name="paged_attention",
        interpret=interpret,
    )(block_tables, lengths, starts, qr, k_pages, v_pages)
    return out.reshape(b, h, d)
