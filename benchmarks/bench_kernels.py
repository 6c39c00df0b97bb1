"""Kernel micro-benchmarks: Pallas (interpret on CPU; Mosaic on TPU) vs the
pure-jnp oracle. On CPU the interesting number is the ORACLE path (XLA:CPU)
— interpret-mode timing measures the Python interpreter, noted as such."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, fmt_row
from repro.kernels.ops import paged_attention, ssd_scan
from repro.kernels.ref import paged_attention_ref, ssd_scan_ref

HEADER = "bench,name,us_per_call,derived"


def _time(fn, *args, iters=5):
    fn(*args)                                   # compile/warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def main(fast: bool = True):
    rows = []
    rng = np.random.default_rng(0)
    # llama3-8b-ish decode geometry (reduced pool)
    B, H, K, D, page, pps, P = 8, 32, 8, 128, 16, 16, 160
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((K, P, page, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((K, P, page, D)), jnp.float32)
    bt = jnp.asarray(rng.choice(P, (B, pps)).astype(np.int32))
    ln = jnp.full((B,), pps * page, jnp.int32)

    ref_fn = jax.jit(paged_attention_ref)
    us = _time(ref_fn, q, kp, vp, bt, ln)
    tokens = int(jnp.sum(ln))
    rows.append(fmt_row("kernels", "paged_attention_ref_xla_cpu", round(us, 1),
                        f"{tokens/us:.1f}tok/us"))
    us2 = _time(lambda *a: paged_attention(*a, interpret=True),
                q, kp, vp, bt, ln, iters=2)
    rows.append(fmt_row("kernels", "paged_attention_pallas_interpret",
                        round(us2, 1), "correctness-path"))

    b, s, h, p, n = 2, 512, 8, 64, 128
    xdt = jnp.asarray(rng.standard_normal((b, s, h, p)) * .5, jnp.float32)
    a = jnp.asarray(-np.abs(rng.standard_normal((b, s, h))) * .3, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((b, s, n)) * .3, jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((b, s, n)) * .3, jnp.float32)
    us3 = _time(jax.jit(ssd_scan_ref), xdt, a, Bm, Cm)
    rows.append(fmt_row("kernels", "ssd_scan_ref_sequential", round(us3, 1),
                        f"{b*s/us3:.2f}tok/us"))
    us4 = _time(lambda *z: ssd_scan(*z, chunk=64, interpret=True),
                xdt, a, Bm, Cm, iters=2)
    rows.append(fmt_row("kernels", "ssd_scan_pallas_interpret", round(us4, 1),
                        "correctness-path"))
    from repro.models.ssm import ssd_chunked
    us5 = _time(jax.jit(lambda *z: ssd_chunked(*z, chunk=64)), xdt, a, Bm, Cm)
    rows.append(fmt_row("kernels", "ssd_chunked_xla_cpu", round(us5, 1),
                        f"chunked-vs-seq speedup {us3/us5:.1f}x"))

    # end-to-end paged-engine decode throughput (reduced llama on CPU):
    # continuous batching through PagedKVPool block tables + the paged
    # attention kernel, sampling on device (one host sync per step)
    rows.append(_paged_engine_decode_row())
    emit(rows, HEADER)
    return rows


def _paged_engine_decode_row():
    from benchmarks.bench_overhead import update_bench_json
    from repro.configs import get_config
    from repro.serving.engine import EngineConfig, RealEngine
    from repro.serving.request import Request

    rng = np.random.default_rng(0)
    cfg = get_config("llama3-8b").reduced()
    n_slots, n_new = 8, 48
    eng = RealEngine(cfg, EngineConfig(max_slots=n_slots, max_seq=128,
                                       replicate=False), n_instances=1)
    for i in range(n_slots):
        eng.submit(Request(
            rid=i, prompt_len=16, max_new_tokens=n_new, arrival_time=0.0,
            prompt_tokens=rng.integers(1, cfg.vocab_size, 16).tolist()))
    eng.step()                                  # admit + warm the jit cache
    eng.step()
    t0 = time.perf_counter()
    steps = 0
    while any(i.requests for i in eng.instances):
        eng.step()
        steps += 1
    dt = time.perf_counter() - t0
    toks_per_s = steps * n_slots / dt
    us_per_step = dt / max(steps, 1) * 1e6
    update_bench_json("paged_decode_throughput", {
        "batch": n_slots, "steps": steps, "us_per_step": round(us_per_step, 1),
        "tokens_per_s": round(toks_per_s, 1),
        "note": "reduced llama3-8b, CPU interpret-mode kernel"})
    return fmt_row("kernels", "paged_engine_decode", round(us_per_step, 1),
                   f"{toks_per_s:.1f}tok/s@B{n_slots}")


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main(fast=False)
