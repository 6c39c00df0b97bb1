"""Compile a configuration's decode step and largest prefill bucket for a
described TPU v5e (no chip needed) and print what each program holds.

  JAX_PLATFORMS=cpu python bench/size_check.py mixtral-8x7b-L4 --layers 4 3 2

Each line gives the depth, the program, and ``memory_analysis()`` in bytes:
arguments (weights and, for decode, both pools) and temporaries. Use it to
pick the deepest cut that fits a chip before spending chip time.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import spec as S  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--layers", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    S.add_program_to_path()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.models import api
    from repro.models.paged_decode import table_pages
    from repro.serving.engine import FamilyExecutor

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    conf = S.load_json(S.BENCH_DIR / "configs" / f"{args.config}.json")
    for n in args.layers:
        cfg = dataclasses.replace(S.model_config(conf), n_layers=n)
        ecfg = S.engine_config(conf)
        ecfg.interpret = False
        ex = FamilyExecutor(cfg, ecfg)
        params = jax.tree.map(
            lambda x: sds(x.shape, x.dtype),
            jax.eval_shape(lambda: api.init_params(cfg,
                                                   jax.random.PRNGKey(0))))
        B, pps = ecfg.max_slots, table_pages(cfg, ecfg.max_seq)
        pages = sds((cfg.n_layers, cfg.n_kv_heads, 2 * B * pps + 1,
                     cfg.page_size, cfg.head_dim), jnp.bfloat16)
        ivec = sds((B,), jnp.int32)
        progs = {
            "decode": lambda: ex.decode.lower(
                params, ivec, pages, pages, None, None,
                sds((B, pps), jnp.int32), ivec, ivec,
                sds((2,), jnp.uint32)),
            f"prefill{ecfg.max_seq}": lambda: ex.prefill.lower(
                params, sds((1, ecfg.max_seq), jnp.int32),
                sds((), jnp.int32)),
        }
        for name, lower in progs.items():
            try:
                mem = lower().compile().memory_analysis()
                row = {"layers": n, "program": name,
                       "argument_bytes": mem.argument_size_in_bytes,
                       "output_bytes": mem.output_size_in_bytes,
                       "alias_bytes": mem.alias_size_in_bytes,
                       "temp_bytes": mem.temp_size_in_bytes,
                       "args_plus_temp_bytes": mem.argument_size_in_bytes
                       + mem.temp_size_in_bytes}
            except Exception as e:     # the compiler's refusal is the answer
                row = {"layers": n, "program": name,
                       "refused": str(e).splitlines()[0][:300]}
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
