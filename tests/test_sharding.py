"""Auto-sharding rules: divisibility safety + expected layouts (checked on a
small host mesh; the 512-device layouts are exercised by launch/dryrun.py)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as sh
from repro.launch import specs as sp

# 1 CPU device -> build abstract meshes for spec computation only
DEVS = np.array(jax.devices() * 1)

MESH = sh.abstract_mesh((16, 16), ("data", "model"))
MESH3 = sh.abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def test_param_spec_2d_weight():
    spec = sh.param_spec(("layers", "mlp", "w_gate"), (24, 4096, 14336),
                         MESH, stacked_layers=True)
    assert spec == P(None, "data", "model")     # layer dim never sharded


def test_param_spec_indivisible_falls_back():
    # vocab 50280 % 16 != 0 -> replicate that dim
    spec = sh.param_spec(("embed", "tok"), (50280, 768), MESH, False)
    assert spec[0] is None
    # d_model 768 % 16 == 0 -> model on last
    assert spec[1] == "model"


def test_param_spec_small_replicated():
    # tiny trailing dims (below 1 element/shard threshold) stay replicated
    spec = sh.param_spec(("layers", "norm_attn"), (24, 8), MESH, True)
    assert spec == P(None, None)
    # divisible d_model-sized norms do shard
    spec = sh.param_spec(("layers", "norm_attn"), (24, 1024), MESH, True)
    assert spec == P(None, "model")


def test_cache_spec_dense():
    cfg = get_config("deepseek-67b")
    cache = jax.eval_shape(
        lambda: __import__("repro.models.api", fromlist=["api"]).init_cache(
            cfg, 128, 32768))
    shards = sh.cache_shardings(cache, MESH, "dense")
    spec = shards["k"].spec
    assert spec == P(None, "data", "model", None, None)


def test_cache_spec_batch1_replicates_batch():
    cfg = get_config("yi-9b")
    from repro.models import api
    cache = jax.eval_shape(lambda: api.init_cache(cfg, 1, 524_288))
    shards = sh.cache_shardings(cache, MESH, "dense")
    assert shards["k"].spec[1] is None          # batch 1: not sharded
    assert shards["k"].spec[2] == "model"       # window seq is


def test_cache_spec_ssm():
    cfg = get_config("mamba2-130m")
    from repro.models import api
    cache = jax.eval_shape(lambda: api.init_cache(cfg, 128, 32768))
    shards = sh.cache_shardings(cache, MESH, "ssm")
    assert shards["ssm"].spec == P(None, "data", None, None, "model")
    assert shards["conv"].spec[1] == "data"


def test_multi_pod_batch_axes():
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4097), np.int32)}
    shards = sh.batch_shardings(batch, MESH3)
    assert shards["tokens"].spec == P(("pod", "data"), None)


def test_profile_serve_model_only_replicates_over_data():
    spec = sh.param_spec(("layers", "mlp", "w_gate"), (24, 4096, 14336),
                         MESH, True, profile="serve_model_only")
    assert spec == P(None, None, "model")       # no data-axis sharding


def test_profile_expert_parallel_shards_experts():
    # dbrx: 16 experts divide the 16-way model axis
    spec = sh.param_spec(("layers", "experts", "w_gate"),
                         (40, 16, 6144, 10752), MESH, True,
                         profile="expert_parallel")
    assert spec == P(None, "model", "data", None)
    # mixtral: 8 experts do NOT divide -> baseline-style fallback
    spec = sh.param_spec(("layers", "experts", "w_gate"),
                         (32, 8, 4096, 14336), MESH, True,
                         profile="expert_parallel")
    assert spec[1] != "model"


def test_profile_pure_dp_replicates_everything():
    spec = sh.param_spec(("layers", "mlp", "w_gate"), (24, 768, 2048),
                         MESH, True, profile="pure_dp")
    assert spec == P(None, None, None)
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4097), np.int32)}
    shards = sh.batch_shardings(batch, MESH, profile="pure_dp")
    assert shards["tokens"].spec == P(("data", "model"), None)


@pytest.mark.parametrize("name", ["qwen1.5-32b", "dbrx-132b", "hubert-xlarge"])
def test_params_shardings_cover_tree(name):
    cfg = get_config(name)
    pshape = sp.params_struct(cfg)
    shards = sh.params_shardings(pshape, MESH)
    n = len(jax.tree.leaves(shards, is_leaf=lambda x: hasattr(x, "spec")))
    assert n == len(jax.tree.leaves(pshape))
    # every spec is divisibility-sound
    for leaf, shard in zip(jax.tree.leaves(pshape),
                           jax.tree.leaves(shards, is_leaf=lambda x: hasattr(x, "spec"))):
        for dim, axes in zip(leaf.shape, shard.spec):
            if axes is None:
                continue
            assert dim % sh.axis_size(MESH, axes) == 0
