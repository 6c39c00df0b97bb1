"""Config registry + assigned-architecture spec conformance."""
import pytest

from repro.configs import ASSIGNED, INPUT_SHAPES, get_config, list_configs, shape_applicable

# exact values from the assignment table
SPECS = {
    "qwen1.5-0.5b": dict(n_layers=24, d_model=1024, n_heads=16, n_kv_heads=16,
                         d_ff=2816, vocab_size=151_936, qkv_bias=True),
    "mamba2-130m": dict(n_layers=24, d_model=768, d_ff=0, vocab_size=50_280,
                        ssm_state=128),
    "recurrentgemma-9b": dict(n_layers=38, d_model=4096, n_heads=16,
                              n_kv_heads=1, d_ff=12_288, vocab_size=256_000),
    "yi-9b": dict(n_layers=48, d_model=4096, n_heads=32, n_kv_heads=4,
                  d_ff=11_008, vocab_size=64_000),
    "qwen1.5-32b": dict(n_layers=64, d_model=5120, n_heads=40, n_kv_heads=40,
                        d_ff=27_392, vocab_size=152_064, qkv_bias=True),
    "internvl2-76b": dict(n_layers=80, d_model=8192, n_heads=64, n_kv_heads=8,
                          d_ff=28_672, vocab_size=128_256),
    "mixtral-8x7b": dict(n_layers=32, d_model=4096, n_heads=32, n_kv_heads=8,
                         d_ff=14_336, vocab_size=32_000, n_experts=8, top_k=2),
    "deepseek-67b": dict(n_layers=95, d_model=8192, n_heads=64, n_kv_heads=8,
                         d_ff=22_016, vocab_size=102_400),
    "dbrx-132b": dict(n_layers=40, d_model=6144, n_heads=48, n_kv_heads=8,
                      d_ff=10_752, vocab_size=100_352, n_experts=16, top_k=4),
    "hubert-xlarge": dict(n_layers=48, d_model=1280, n_heads=16,
                          n_kv_heads=16, d_ff=5120, vocab_size=504,
                          is_encoder_only=True),
}

PARAM_TARGETS = {   # billions, loose bands around the public numbers
    "qwen1.5-0.5b": (0.4, 0.8), "mamba2-130m": (0.10, 0.17),
    "yi-9b": (8, 10), "qwen1.5-32b": (30, 40), "mixtral-8x7b": (44, 49),
    "deepseek-67b": (64, 70), "dbrx-132b": (125, 140),
    "internvl2-76b": (65, 78), "hubert-xlarge": (0.9, 1.5),
    "recurrentgemma-9b": (4.5, 11),
}


def test_all_assigned_present():
    assert len(ASSIGNED) == 10
    assert "llama3-8b" in list_configs()     # the paper's own model


@pytest.mark.parametrize("name", sorted(SPECS))
def test_exact_spec(name):
    cfg = get_config(name)
    for k, v in SPECS[name].items():
        assert getattr(cfg, k) == v, f"{name}.{k}: {getattr(cfg, k)} != {v}"


@pytest.mark.parametrize("name", sorted(PARAM_TARGETS))
def test_param_counts(name):
    lo, hi = PARAM_TARGETS[name]
    n = get_config(name).n_params() / 1e9
    assert lo <= n <= hi, f"{name}: {n:.2f}B outside [{lo},{hi}]"


@pytest.mark.parametrize("name", ASSIGNED)
def test_reduced_variants(name):
    r = get_config(name).reduced()
    assert r.n_layers <= 3 and r.d_model <= 512
    if r.n_experts:
        assert r.n_experts <= 4


def test_with_layers_cuts_depth_only():
    full = get_config("yi-9b")
    cut = full.with_layers(24)
    assert cut.name == "yi-9b-L24" and cut.n_layers == 24
    for k in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "vocab_size", "dtype", "page_size"):
        assert getattr(cut, k) == getattr(full, k)
    assert full.with_layers(48) is full
    for bad in (0, 49):
        with pytest.raises(ValueError):
            full.with_layers(bad)


def test_served_size_comes_from_the_arguments():
    """The server's size is what its flags say — never a parameter-count
    threshold or the backend: the default is Yi-9B cut to 24 layers at
    published widths, --reduced the toy, --n-layers 0 the whole model."""
    from repro.serving import server

    def configs(*argv):
        return server.build_configs(server.build_parser().parse_args(argv))

    cfg, ecfg = configs()
    assert cfg == get_config("yi-9b").with_layers(24)
    assert (ecfg.max_slots, ecfg.max_seq) == (8, 1024)
    assert ecfg.replicate and ecfg.recovery == "kevlarflow"
    assert configs("--reduced")[0] == get_config("yi-9b").reduced()
    assert configs("--n-layers", "0")[0] == get_config("yi-9b")
    assert configs("--arch", "qwen1.5-0.5b", "--n-layers", "0")[0] == \
        get_config("qwen1.5-0.5b")


def test_moe_active_params():
    c = get_config("mixtral-8x7b")
    assert c.n_active_params() < c.n_params()
    assert 11 < c.n_active_params() / 1e9 < 14          # ~12.9B active


def test_shape_policy():
    assert len(INPUT_SHAPES) == 4
    # encoder-only: no decode shapes
    for s in ("decode_32k", "long_500k"):
        ok, why = shape_applicable(get_config("hubert-xlarge"), INPUT_SHAPES[s])
        assert not ok and "encoder-only" in why
    # everything else runs all four (long_500k via SWA/window/SSM)
    for name in ASSIGNED:
        if name == "hubert-xlarge":
            continue
        for s in INPUT_SHAPES.values():
            ok, _ = shape_applicable(get_config(name), s)
            assert ok, (name, s.name)
