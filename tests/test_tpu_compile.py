"""Compile the serving path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: tiles that do not
align, more VMEM than a kernel may use, programs that do not fit the
device. These tests compile both paged kernels and the served model's
decode step and prefill chunk at published widths, from shapes alone, with
the Pallas kernels lowered through Mosaic (``interpret=False``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.paged_attention_int8 import SCALE_DTYPE
from repro.models import api
from repro.models.paged_decode import table_pages
from repro.serving import server
from repro.serving.engine import FamilyExecutor

HBM_BYTES = 16 * 1024**3      # one TPU v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (batch, q heads, kv heads, head_dim, page, pages per sequence)
KERNEL_SHAPES = {
    "yi-9b": (8, 32, 4, 128, 16, 64),              # GQA 8:1, 1024 positions
    "recurrentgemma-9b": (8, 16, 1, 256, 16, 64),  # MQA, head_dim 256
    "yi-9b-b32-4k": (32, 32, 4, 128, 16, 256),     # 32 slots x 4096
    "mixtral-8x7b": (8, 32, 8, 128, 16, 64),       # K = 8, the widest block
}


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("shape", list(KERNEL_SHAPES))
def test_paged_kernel_compiles_to_mosaic(one_chip, shape, quant):
    B, H, K, D, page, pps = KERNEL_SHAPES[shape]
    P = 2 * B * pps + 1                            # the engine's pool size
    s = lambda shp, dt: _sds(shp, dt, one_chip)
    q = s((B, H, D), jnp.bfloat16)
    tables = s((B, pps), jnp.int32)
    lengths = s((B,), jnp.int32)
    starts = s((B,), jnp.int32)
    if quant:
        pages = s((K, P, page, D), jnp.int8)
        scales = s((K, P, page, 1), SCALE_DTYPE)
        lowered = ops.paged_attention_int8.lower(
            q, pages, scales, pages, scales, tables, lengths, starts,
            interpret=False)
    else:
        pages = s((K, P, page, D), jnp.bfloat16)
        lowered = ops.paged_attention.lower(q, pages, pages, tables,
                                            lengths, starts, interpret=False)
    assert "tpu_custom_call" in lowered.compile().as_text()


def _served(one_chip):
    """The server's default deployment (Yi-9B, 24 of 48 layers) as shapes
    on the described chip, with its engine settings and Mosaic kernels."""
    args = server.build_parser().parse_args([])
    cfg, ecfg = server.build_configs(args)
    ecfg.interpret = False
    params = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0))))
    return cfg, ecfg, FamilyExecutor(cfg, ecfg), params


def test_served_decode_step_compiles(one_chip):
    cfg, ecfg, ex, params = _served(one_chip)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size, cfg.n_layers) == \
        (4096, 32, 4, 128, 11008, 64000, 24)
    B = ecfg.max_slots
    pps = table_pages(cfg, ecfg.max_seq)
    n_blocks = 2 * B * pps + 1
    s = lambda shp, dt: _sds(shp, dt, one_chip)
    pages = s((cfg.n_layers, cfg.n_kv_heads, n_blocks, cfg.page_size,
               cfg.head_dim), jnp.bfloat16)
    ivec = s((B,), jnp.int32)
    compiled = ex.decode.lower(
        params, ivec, pages, pages, None, None, s((B, pps), jnp.int32),
        ivec, ivec, s((2,), jnp.uint32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("bucket", [256, 1024])
def test_served_prefill_bucket_compiles(one_chip, bucket):
    cfg, ecfg, ex, params = _served(one_chip)
    compiled = ex.prefill.lower(
        params, _sds((1, bucket), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_served_prefill_chunk_compiles(one_chip):
    cfg, ecfg, ex, params = _served(one_chip)
    s = lambda shp, dt: _sds(shp, dt, one_chip)
    buf = s((cfg.n_layers, ecfg.max_seq, cfg.n_kv_heads, cfg.head_dim),
            jnp.bfloat16)
    scalar = s((), jnp.int32)
    compiled = ex.prefill_chunk.lower(
        params, s((1, 256), jnp.int32), scalar, scalar, buf, buf).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
