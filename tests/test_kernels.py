"""Pallas kernel validation: shape/dtype sweeps + hypothesis, all vs the
pure-jnp oracles in kernels/ref.py (interpret=True on CPU). Only the
hypothesis sweep needs hypothesis; everything else runs everywhere."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels.ops import paged_attention, ssd_scan
from repro.kernels.paged_attention import (BLOCK_TOKENS, BLOCK_VMEM_BYTES,
                                           kv_blocks, pages_per_block)
from repro.kernels.ref import paged_attention_ref, ssd_scan_ref


# --------------------------------------------------------------------------
# paged attention
# --------------------------------------------------------------------------

def _paged_case(b, h, kheads, d, page, pps, dtype, seed=0):
    rng = np.random.default_rng(seed)
    P = pps * b + 3                       # physical pool > logical need
    q = jnp.asarray(rng.standard_normal((b, h, d)), dtype)
    kp = jnp.asarray(rng.standard_normal((kheads, P, page, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((kheads, P, page, d)), dtype)
    tables = rng.permutation(P)[: b * pps].reshape(b, pps).astype(np.int32)
    lengths = rng.integers(1, pps * page + 1, b).astype(np.int32)
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths)


@pytest.mark.parametrize("b,h,kheads,d,page,pps", [
    (1, 4, 4, 64, 16, 2),      # MHA
    (2, 8, 2, 64, 16, 4),      # GQA 4:1
    (3, 8, 1, 128, 16, 3),     # MQA
    (2, 16, 8, 128, 32, 2),    # bigger page
    (4, 4, 2, 256, 16, 5),     # rg-style head_dim 256
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(b, h, kheads, d, page, pps, dtype):
    q, kp, vp, bt, ln = _paged_case(b, h, kheads, d, page, pps, dtype)
    out = paged_attention(q, kp, vp, bt, ln, interpret=True)
    ref = paged_attention_ref(q, kp, vp, bt, ln)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(b=st.integers(1, 4), rep=st.sampled_from([1, 2, 4]),
           kheads=st.sampled_from([1, 2, 4]), page=st.sampled_from([8, 16]),
           pps=st.integers(1, 4), seed=st.integers(0, 10_000))
    def test_paged_attention_hypothesis(b, rep, kheads, page, pps, seed):
        q, kp, vp, bt, ln = _paged_case(b, rep * kheads, kheads, 64, page,
                                        pps, jnp.float32, seed)
        out = paged_attention(q, kp, vp, bt, ln, interpret=True)
        ref = paged_attention_ref(q, kp, vp, bt, ln)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @settings(max_examples=12, deadline=None)
    @given(b=st.integers(1, 4), rep=st.sampled_from([1, 2]),
           kheads=st.sampled_from([1, 2]), page=st.sampled_from([8, 16]),
           pps=st.integers(1, 4), seed=st.integers(0, 10_000))
    def test_paged_attention_starts_hypothesis(b, rep, kheads, page, pps,
                                               seed):
        """Random window starts (0 <= start < length) vs the oracle."""
        rng = np.random.default_rng(seed)
        q, kp, vp, bt, ln = _paged_case(b, rep * kheads, kheads, 64, page,
                                        pps, jnp.float32, seed)
        st_ = jnp.asarray(rng.integers(0, np.asarray(ln)), jnp.int32)
        out = paged_attention(q, kp, vp, bt, ln, st_, interpret=True)
        ref = paged_attention_ref(q, kp, vp, bt, ln, st_)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_paged_attention_length_masking():
    """Tokens beyond `length` must not influence the output."""
    q, kp, vp, bt, ln = _paged_case(2, 4, 2, 64, 16, 3, jnp.float32)
    ln = jnp.asarray([5, 17], jnp.int32)
    out1 = paged_attention(q, kp, vp, bt, ln, interpret=True)
    # poison everything past the valid region of the LAST used page
    kp2 = kp.at[:, bt[0, 2]].set(1e4)   # page beyond length 5 (pages 0)
    vp2 = vp.at[:, bt[0, 2]].set(1e4)
    out2 = paged_attention(q, kp2, vp2, bt, ln, interpret=True)
    np.testing.assert_allclose(np.asarray(out1[0]), np.asarray(out2[0]),
                               rtol=1e-6, atol=1e-6)


def test_paged_attention_start_masking():
    """Sliding-window lower bound: tokens below `starts` must not influence
    the output — even when poisoned, and even when a whole leading page
    falls below the window (the fully-masked-page softmax corner)."""
    q, kp, vp, bt, ln = _paged_case(2, 4, 2, 64, 16, 3, jnp.float32)
    ln = jnp.asarray([40, 44], jnp.int32)
    st_ = jnp.asarray([18, 21], jnp.int32)     # page 0 fully below the window
    out1 = paged_attention(q, kp, vp, bt, ln, st_, interpret=True)
    # poison every token below each window start, incl. all of page 0
    kp2, vp2 = kp, vp
    for i, s in enumerate([18, 21]):
        for t in range(s):
            kp2 = kp2.at[:, bt[i, t // 16], t % 16].set(1e4)
            vp2 = vp2.at[:, bt[i, t // 16], t % 16].set(1e4)
    out2 = paged_attention(q, kp2, vp2, bt, ln, st_, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)
    # and the result equals the oracle restricted to [start, length)
    ref = paged_attention_ref(q, kp, vp, bt, ln, st_)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_starts_none_is_zero():
    """Omitting starts must equal passing explicit zeros."""
    q, kp, vp, bt, ln = _paged_case(2, 4, 2, 64, 16, 3, jnp.float32)
    out1 = paged_attention(q, kp, vp, bt, ln, interpret=True)
    out2 = paged_attention(q, kp, vp, bt, ln,
                           jnp.zeros_like(ln), interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# The kernel walks blocks of ``pages_per_block`` pages (16 at page 16): a
# 20-page table leaves a partial last block of 4 pages.
BLOCK_CASES = {
    # name: (lengths, starts, what the table holds past the last valid page)
    "partial_last_block": ([320, 300, 17], None, None),
    "page_boundary": ([16, 48, 272], None, None),
    "block_boundary": ([256, 257, 255], None, None),
    "idle_slots": ([1, 1, 200], None, None),
    "start_skips_block": ([310, 320, 5], [260, 300, 0], None),
    "tail_out_of_range": ([40, 256, 299], None, "out_of_range"),
    "tail_scratch": ([40, 256, 299], [3, 0, 290], "scratch"),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", list(BLOCK_CASES))
def test_paged_attention_blocks(case, dtype):
    """Block edges, idle slots, skipped leading blocks and table tails
    against the oracle; a table's entries past the last valid page are
    never read, so what they hold cannot change the output."""
    lengths, starts, tail = BLOCK_CASES[case]
    b, page, pps = len(lengths), 16, 20
    assert pages_per_block(page, pps, 2, 128) == 16
    q, kp, vp, bt, _ = _paged_case(b, 8, 2, 128, page, pps, dtype, seed=3)
    n_phys = kp.shape[1]
    bt = np.array(bt)
    scratch = min(set(range(n_phys)) - set(bt.ravel()))   # in no table
    ln = np.asarray(lengths, np.int32)
    bt[ln == 1] = scratch                    # idle slots: the scratch block
    st = None if starts is None else jnp.asarray(starts, jnp.int32)
    clean = jnp.asarray(bt)
    out = paged_attention(q, kp, vp, clean, jnp.asarray(ln), st,
                          interpret=True)
    ref = paged_attention_ref(q, kp, vp, clean, jnp.asarray(ln), st)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    if tail is not None:
        poisoned = bt.copy()
        for i, n in enumerate(ln):
            poisoned[i, -(-n // page):] = \
                n_phys + 1000 if tail == "out_of_range" else scratch
        out2 = paged_attention(q, kp.at[:, scratch].set(1e4),
                               vp.at[:, scratch].set(1e4),
                               jnp.asarray(poisoned), jnp.asarray(ln), st,
                               interpret=True)
        np.testing.assert_array_equal(np.asarray(out2), np.asarray(out))


def test_paged_attention_dmas_do_not_race():
    """Under the TPU interpreter, which tracks every DMA and semaphore and
    fills fresh scratch with NaN: no copy races a read of its buffer, and
    the result equals the oracle's, so no unfetched byte reaches it."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels import paged_attention as pa
    q, kp, vp, bt, _ = _paged_case(3, 8, 2, 128, 16, 20, jnp.float32, seed=4)
    ln = jnp.asarray([320, 1, 270], jnp.int32)
    st = jnp.asarray([260, 0, 3], jnp.int32)
    out = pa.paged_attention(q, kp, vp, bt, ln, st,
                             interpret=pltpu.InterpretParams(
                                 detect_races=True,
                                 uninitialized_memory="nan"))
    out.block_until_ready()
    assert not interpret_pallas_call.races.races_found
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(paged_attention_ref(q, kp, vp, bt, ln, st)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,ppb", [
    ((16, 64, 4, 128), 16),       # Yi-9B served: 256 positions a block
    ((16, 129, 1, 256), 16),      # RecurrentGemma local ring: partial last
    ((16, 256, 4, 128), 16),      # 32 slots x 4096
    ((16, 64, 8, 128), 16),       # Mixtral: 8 KV heads
    ((8, 8, 2, 64), 8),           # reduced: never past the table
    ((16, 64, 16, 256), 4),       # wide heads: the VMEM budget binds
])
def test_pages_per_block(shape, ppb):
    page, table_pages, kheads, head_dim = shape
    n = pages_per_block(page, table_pages, kheads, head_dim)
    assert n == ppb and 1 <= n <= table_pages
    assert n * page <= BLOCK_TOKENS
    # K and V, two buffers each, even at 4 bytes an element
    assert 2 * 2 * kheads * n * page * head_dim * 4 <= BLOCK_VMEM_BYTES


def test_kv_blocks_counts_blocks_with_a_valid_position():
    """The host's count of the kernel's blocks: blocks of 256 positions
    that hold a position in [start, length), at least one a slot."""
    lengths = np.array([1, 256, 257, 1024, 700, 600])
    starts = np.array([0, 0, 0, 0, 512, 100])
    fetched, table = kv_blocks(lengths, starts, 16, 64, 4, 128)
    assert (fetched, table) == (1 + 1 + 2 + 4 + 1 + 3, 6 * 4)
    assert kv_blocks(lengths, None, 16, 64, 4, 128)[0] == 1 + 1 + 2 + 4 + 3 + 3


# --------------------------------------------------------------------------
# SSD scan
# --------------------------------------------------------------------------

def _ssd_case(b, s, h, p, n, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    xdt = jnp.asarray(rng.standard_normal((b, s, h, p)) * 0.5, dtype)
    a = jnp.asarray(-np.abs(rng.standard_normal((b, s, h))) * 0.3, jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s, n)) * 0.3, dtype)
    C = jnp.asarray(rng.standard_normal((b, s, n)) * 0.3, dtype)
    return xdt, a, B, C


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 32, 1, 8, 16, 8),
    (2, 64, 3, 16, 32, 16),
    (2, 128, 2, 64, 128, 32),   # mamba2-130m head geometry
    (1, 96, 4, 32, 64, 32),
])
def test_ssd_scan_sweep(b, s, h, p, n, chunk):
    xdt, a, B, C = _ssd_case(b, s, h, p, n)
    y, hf = ssd_scan(xdt, a, B, C, chunk=chunk, interpret=True)
    yr, hr = ssd_scan_ref(xdt, a, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hr), rtol=2e-4, atol=2e-4)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(b=st.integers(1, 2), nchunks=st.integers(1, 4),
           chunk=st.sampled_from([8, 16]), h=st.integers(1, 3),
           seed=st.integers(0, 10_000))
    def test_ssd_scan_hypothesis(b, nchunks, chunk, h, seed):
        s = nchunks * chunk
        xdt, a, B, C = _ssd_case(b, s, h, 8, 16, seed)
        y, hf = ssd_scan(xdt, a, B, C, chunk=chunk, interpret=True)
        yr, hr = ssd_scan_ref(xdt, a, B, C)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(hf), np.asarray(hr),
                                   rtol=3e-4, atol=3e-4)


def test_ssd_scan_matches_model_impl():
    """Kernel == models/ssm.py chunked implementation (same algorithm)."""
    from repro.models.ssm import ssd_chunked
    xdt, a, B, C = _ssd_case(2, 64, 2, 16, 32)
    y, hf = ssd_scan(xdt, a, B, C, chunk=16, interpret=True)
    ym, hm = ssd_chunked(xdt, a, B, C, chunk=16)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ym), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hm), rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# int8 paged attention
# --------------------------------------------------------------------------

from repro.kernels.paged_attention_int8 import (SCALE_DTYPE,
                                                dequantize_pages,
                                                paged_attention_int8,
                                                quantize_pages)
from repro.kernels.ref import paged_attention_int8_ref


@pytest.mark.parametrize("b,h,kheads,d,page,pps", [
    (2, 8, 2, 64, 16, 3),
    (1, 4, 1, 128, 16, 2),
    (3, 16, 8, 128, 32, 2),
])
def test_paged_attention_int8_sweep(b, h, kheads, d, page, pps):
    q, kp, vp, bt, ln = _paged_case(b, h, kheads, d, page, pps, jnp.float32)
    kq, ks = quantize_pages(kp)
    vq, vs = quantize_pages(vp)
    out = paged_attention_int8(q, kq, ks, vq, vs, bt, ln, interpret=True)
    ref = paged_attention_int8_ref(q, kq, ks, vq, vs, bt, ln)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_int8_quantization_error_bounded():
    """End-to-end: int8 pool vs float pool output differs by only the
    quantization noise (small relative to the attention output scale)."""
    q, kp, vp, bt, ln = _paged_case(2, 8, 2, 64, 16, 4, jnp.float32)
    kq, ks = quantize_pages(kp)
    vq, vs = quantize_pages(vp)
    out_i8 = paged_attention_int8(q, kq, ks, vq, vs, bt, ln, interpret=True)
    out_f = paged_attention_ref(q, kp, vp, bt, ln)
    err = np.abs(np.asarray(out_i8) - np.asarray(out_f))
    assert err.max() < 0.05 * np.abs(np.asarray(out_f)).max()


def test_int8_ragged_fully_masked_page_regression():
    """Regression for the stale int8 softmax: a ragged batch where one
    sequence's window start leaves its ENTIRE first page masked. Before the
    fix the kernel had no ``starts`` operand at all, and its softmax let a
    fully-masked page contribute weight-1 garbage (m_new stuck at NEG_INF
    makes exp(s - m_new) == 1 for every masked token). Poisoned below-start
    tokens must therefore be invisible, and the output must match the
    oracle restricted to [start, length)."""
    page = 16
    q, kp, vp, bt, ln = _paged_case(3, 4, 2, 64, page, 3, jnp.float32)
    ln = jnp.asarray([40, 7, 44], jnp.int32)      # ragged lengths
    st_ = jnp.asarray([18, 0, 33], jnp.int32)     # seq 0: page 0 fully
    kq, ks = quantize_pages(kp)                   # masked; seq 2: pages 0-1
    vq, vs = quantize_pages(vp)
    out = paged_attention_int8(q, kq, ks, vq, vs, bt, ln, st_, interpret=True)
    ref = paged_attention_int8_ref(q, kq, ks, vq, vs, bt, ln, st_)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # poison every quantized token below each window start: output unchanged
    kq2, vq2 = kq, vq
    for i, s in enumerate([18, 0, 33]):
        for t in range(s):
            kq2 = kq2.at[:, bt[i, t // page], t % page].set(127)
            vq2 = vq2.at[:, bt[i, t // page], t % page].set(127)
    out2 = paged_attention_int8(q, kq2, ks, vq2, vs, bt, ln, st_,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_paged_attention_int8_starts_none_is_zero():
    q, kp, vp, bt, ln = _paged_case(2, 4, 2, 64, 16, 3, jnp.float32)
    kq, ks = quantize_pages(kp)
    vq, vs = quantize_pages(vp)
    out1 = paged_attention_int8(q, kq, ks, vq, vs, bt, ln, interpret=True)
    out2 = paged_attention_int8(q, kq, ks, vq, vs, bt, ln,
                                jnp.zeros_like(ln), interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(b=st.integers(1, 4), rep=st.sampled_from([1, 2]),
           kheads=st.sampled_from([1, 2]), page=st.sampled_from([8, 16]),
           pps=st.integers(1, 4), seed=st.integers(0, 10_000))
    def test_paged_attention_int8_starts_hypothesis(b, rep, kheads, page,
                                                    pps, seed):
        """Random window starts vs the int8 oracle (parity with the float
        kernel's starts sweep)."""
        rng = np.random.default_rng(seed)
        q, kp, vp, bt, ln = _paged_case(b, rep * kheads, kheads, 64, page,
                                        pps, jnp.float32, seed)
        st_ = jnp.asarray(rng.integers(0, np.asarray(ln)), jnp.int32)
        kq, ks = quantize_pages(kp)
        vq, vs = quantize_pages(vp)
        out = paged_attention_int8(q, kq, ks, vq, vs, bt, ln, st_,
                                   interpret=True)
        ref = paged_attention_int8_ref(q, kq, ks, vq, vs, bt, ln, st_)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_quantize_pages_scale_dtype_and_zero_page_roundtrip():
    """Scales come back in SCALE_DTYPE (the dtype the pool stores and the
    kernel dequantizes with — one dtype everywhere), and an all-zero page
    round-trips to EXACT zeros: scale is 1, not an epsilon floor, so there
    is no 0/eps noise and no NaN anywhere in the pipeline."""
    rng = np.random.default_rng(0)
    pages = jnp.asarray(rng.standard_normal((2, 5, 8, 64)), jnp.float32)
    pages = pages.at[0, 2].set(0.0)               # one all-zero page
    q, s = quantize_pages(pages)
    assert q.dtype == jnp.int8
    assert s.dtype == jnp.dtype(SCALE_DTYPE)
    back = dequantize_pages(q, s)
    assert not np.any(np.isnan(np.asarray(back)))
    np.testing.assert_array_equal(np.asarray(back[0, 2]),
                                  np.zeros((8, 64), np.float32))
    np.testing.assert_array_equal(np.asarray(s[0, 2], np.float32),
                                  np.ones((8, 1), np.float32))
    # non-zero rows: per-row error bounded by half a quantization step
    err = np.abs(np.asarray(back) - np.asarray(pages, np.float32))
    bound = np.asarray(s, np.float32) * 0.5 + 1e-7
    assert (err <= bound).all()
