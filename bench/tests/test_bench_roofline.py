"""The yardstick's arithmetic: operations and bytes, active weights, MFU,
peaks by device kind."""
import pytest

import tinybench
from bench import roofline as R
from bench import spec as S

YI = S.load_json(S.BENCH_DIR / "configs" / "yi-9b-L24.json")
MIXTRAL = S.load_json(S.BENCH_DIR / "configs" / "mixtral-8x7b-L4.json")
PEAKS = S.load_json(S.BENCH_DIR / "peaks.json")["devices"]["TPU v5 lite"]


def test_paged_attention_cost_counts_live_context_only():
    # Yi: 32 query heads, 4 KV heads, head_dim 128, bf16 pages
    flops, nbytes = R.paged_attention_cost(YI, [100, 412])
    assert flops == 4 * 32 * 128 * 512
    assert nbytes == 2 * 4 * 128 * 2 * 512 + 2 * 2 * 32 * 128 * 2
    # the same work whatever block-table width the program pads to
    assert R.paged_attention_cost(YI, [512]) [0] == flops


def test_least_time_is_the_binding_roof():
    flops, nbytes = R.paged_attention_cost(YI, [1000] * 8)
    t = R.least_seconds(flops, nbytes, PEAKS)
    assert t == pytest.approx(nbytes / 819e9)           # memory-bound
    assert t > flops / 197e12


def test_mixtral_counts_two_of_eight_experts():
    d, f = 4096, 14336
    attn = 4096 * 4096 * 2 + 2 * 4096 * 8 * 128
    per_layer = attn + 2 * 3 * d * f + d * 8
    assert R.matmul_params_per_token(MIXTRAL) == \
        4 * per_layer + d * 32000
    dense = dict(MIXTRAL, num_experts_per_tok=8)
    assert R.matmul_params_per_token(dense) - \
        R.matmul_params_per_token(MIXTRAL) == 4 * 6 * 3 * d * f


def test_yi_active_weights_and_decode_mfu():
    n = R.matmul_params_per_token(YI)
    assert n == 24 * (4096 * 4096 * 2 + 2 * 4096 * 512 + 3 * 4096 * 11008) \
        + 4096 * 64000
    # 160 tokens/s is under 1% of a v5e's bf16 peak at this size
    mfu = 100 * R.model_flops(YI, 160) / PEAKS["bf16_flops_per_s"]
    assert 0.7 < mfu < 0.8


def test_unknown_device_kind_is_an_error(tmp_path):
    b = S.Bench(tinybench.make(tmp_path), tmp_path / "bench")
    assert b.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        b.peaks("TPU v9 imaginary")
