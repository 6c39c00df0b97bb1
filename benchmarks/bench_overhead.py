"""Paper Fig 9: runtime overhead of always-on background KV replication
during failure-free operation (KevlarFlow vs replication-off baseline).

Also measures REAL replication traffic on the paged engine: bytes/step and
blocks/step for full-snapshot vs dirty-block-delta vs int8-quantized-delta
modes (delta: per-step traffic proportional to dirty blocks, ~1 block per
active request, instead of the whole live cache; int8: the same dirty
blocks at ~half the bytes per message — int8 pages + scales, and ~4x
smaller hybrid state blobs), plus the wall-clock win of step-overlapped
(async double-buffered) replication vs shipping synchronously in-step.
Results land in ``BENCH_paged.json`` (``replication_traffic*``, ``int8``
and ``repl_overlap`` sections)."""
from __future__ import annotations

import json
import os

from benchmarks.common import emit, fmt_row, run_scenario

HEADER = "bench,cluster,rps,lat_base,lat_repl,overhead_avg_pct,overhead_p99_pct"
TRAFFIC_HEADER = ("bench,arch,mode,blocks_per_step,bytes_per_step,"
                  "blocks_per_request_step,blobs_per_request_step,bytes_total")
RECYCLING_HEADER = ("bench,arch,max_seq,peak_resident_blocks,resident_bound,"
                    "unrecycled_blocks,retire_msgs,blocks_per_request_step")

# one arch per paged family: dense, MoE (routed MLP, same KV), hybrid
# (paged local attention + RG-LRU state blobs)
TRAFFIC_ARCHS = ("llama3-8b", "mixtral-8x7b", "recurrentgemma-9b")
BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_paged.json")


def update_bench_json(section: str, payload):
    path = os.path.abspath(BENCH_JSON)
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data[section] = payload
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


def replication_traffic(mode: str, arch: str = "llama3-8b",
                        n_requests: int = 6, prompt: int = 24,
                        out: int = 24):
    """Run the real paged engine and read its replication counters.

    mode: "full" | "delta" | "int8" — int8 is delta replication over the
    quantized pool (EngineConfig.kv_quant): int8 KV pages + scales on the
    wire instead of bf16, int8 state blobs + one scale on hybrid."""
    import numpy as np
    from repro.configs import get_config
    from repro.serving.engine import EngineConfig, RealEngine
    from repro.serving.request import Request

    cfg = get_config(arch).reduced()
    eng = RealEngine(cfg, EngineConfig(max_slots=4, max_seq=96,
                                       replication="delta" if mode == "int8"
                                       else mode,
                                       kv_quant=(mode == "int8")),
                     n_instances=2, seed=0)
    rng = np.random.default_rng(0)
    for i in range(n_requests):
        eng.submit(Request(
            rid=i, prompt_len=prompt, max_new_tokens=out, arrival_time=0.0,
            prompt_tokens=rng.integers(1, cfg.vocab_size, prompt).tolist()))
    eng.run(400)
    stats = eng.replication_stats()
    stats["mode"] = mode               # "int8" runs delta under the hood
    stats["block_bytes"] = eng.instances[0].pool.block_nbytes
    stats["blob_bytes"] = eng.instances[0].pool.blob_nbytes
    stats["live_cache_blocks_per_request"] = \
        eng.instances[0].pool.blocks_for_tokens(prompt + out)
    return stats


def repl_overlap(arch: str = "llama3-8b", n_requests: int = 6,
                 prompt: int = 24, out: int = 32):
    """Wall-clock cost of replication on the step loop, three ways:

      * ``sync``  — repl_async=False: the step blocks until the delta is
        durable on the peer (the pre-overlap baseline),
      * ``async`` — repl_async=True: step N's delta ships while step N+1
        computes (the double-buffer default),
      * ``off``   — replicate=False: the no-resilience floor.

    Two views, both median ms per steady-state decode step:

      * whole-step time per variant (context — on CPU the decode forward
        dominates, so the three are within machine noise of each other);
      * *replication critical-path* time — wall clock spent inside the
        stage + ship calls on the step's critical path. Sync pays
        stage + copy + block-until-durable; async pays stage + dispatch
        only (the copies execute under the next step's compute). The
        interesting number is ``overlap_saves_ms_per_step`` =
        sync_repl - async_repl."""
    import time as _time

    import numpy as np
    from repro.configs import get_config
    from repro.serving.engine import EngineConfig, RealEngine
    from repro.serving.request import Request

    cfg = get_config(arch).reduced()
    step_ms, repl_ms = {}, {}
    for variant in ("sync", "async", "off"):
        eng = RealEngine(cfg, EngineConfig(
            max_slots=4, max_seq=96,
            replicate=(variant != "off"),
            repl_async=(variant == "async")),
            n_instances=2, seed=0)
        # replication critical-path seconds; depth guard so the sync path
        # (_replicate calling flush_replication inside itself) counts once
        spent = {"s": 0.0, "depth": 0}

        def timed(fn, spent=spent):
            def wrapper(*a, **kw):
                if spent["depth"]:
                    return fn(*a, **kw)
                spent["depth"] += 1
                t0 = _time.perf_counter()
                try:
                    return fn(*a, **kw)
                finally:
                    spent["depth"] -= 1
                    spent["s"] += _time.perf_counter() - t0
            return wrapper

        eng._replicate = timed(eng._replicate)
        eng.flush_replication = timed(eng.flush_replication)
        rng = np.random.default_rng(0)
        for i in range(n_requests):
            eng.submit(Request(
                rid=i, prompt_len=prompt, max_new_tokens=out,
                arrival_time=0.0,
                prompt_tokens=rng.integers(1, cfg.vocab_size,
                                           prompt).tolist()))
        for _ in range(4):              # admit + compile + first deltas
            eng.step()
        times, repl = [], []
        while eng.has_pending() and len(times) < 200:
            t0 = _time.perf_counter()
            r0 = spent["s"]
            eng.step()
            times.append(_time.perf_counter() - t0)
            repl.append(spent["s"] - r0)
        step_ms[variant] = round(float(np.median(times)) * 1e3, 3)
        repl_ms[variant] = round(float(np.median(repl)) * 1e3, 3)
    return {
        "arch": arch,
        "n_requests": n_requests,
        "sync_ms_per_step": step_ms["sync"],
        "async_ms_per_step": step_ms["async"],
        "off_ms_per_step": step_ms["off"],
        "sync_repl_ms_per_step": repl_ms["sync"],
        "async_repl_ms_per_step": repl_ms["async"],
        "overlap_saves_ms_per_step": round(
            repl_ms["sync"] - repl_ms["async"], 3),
    }


PREFIX_HEADER = ("bench,arch,frac,cache,hit_rate,compute_tokens,"
                 "total_tokens,repl_bytes_total,ship_ratio")


def prefix_traffic(frac: float, prefix_cache: bool = True,
                   arch: str = "llama3-8b", n_requests: int = 20,
                   prompt: int = 104, prefix_len: int = 96, out: int = 3,
                   chunk: int = 32, gap: int = 2):
    """Serve a shared-prefix workload on the real paged engine and read the
    prefix-cache + replication counters.

    ``frac`` of the requests open with the same 96-token preamble (12 full
    pages at page_size 8); arrivals trickle in one every ``gap`` steps —
    temporally spread traffic, the serving regime where a warm cache pays
    off (a thundering herd admits everything before the first prompt
    finishes prefill and interns its pages)."""
    from repro.configs import get_config
    from repro.serving.engine import EngineConfig, RealEngine
    from repro.serving.request import Request
    from repro.serving.workload import attach_prompt_tokens

    cfg = get_config(arch).reduced()
    eng = RealEngine(cfg, EngineConfig(max_slots=4, max_seq=128,
                                       prefill_chunk=chunk,
                                       replication="delta",
                                       prefix_cache=prefix_cache),
                     n_instances=2, seed=0)
    reqs = [Request(rid=i, prompt_len=prompt, max_new_tokens=out,
                    arrival_time=float(i * gap)) for i in range(n_requests)]
    attach_prompt_tokens(reqs, cfg.vocab_size, shared_prefix_frac=frac,
                         prefix_len=prefix_len, seed=1)
    it = iter(reqs)
    r, tick = True, 0
    for _ in range(6000):
        if tick == 0:
            r = next(it, None)
            if r is not None:
                eng.submit(r)
            tick = gap
        tick -= 1
        eng.step()
        if r is None and not eng.has_pending():
            break
    assert not eng.has_pending()
    ps = eng.prefix_stats()
    rs = eng.replication_stats()
    return {
        "shared_prefix_frac": frac,
        "prefix_cache": prefix_cache,
        "hit_rate": ps["hit_rate"],
        "prefill_total_tokens": ps["prefill_total_tokens"],
        "prefill_compute_tokens": ps["prefill_compute_tokens"],
        "prefix_cached_tokens": ps["prefix_cached_tokens"],
        "cow_copies": ps["cow_copies"],
        "shared_replica_refs": ps["shared_replica_refs"],
        "shared_replica_copies": ps["shared_replica_copies"],
        "shared_page_ship_ratio": ps["shared_page_ship_ratio"],
        "repl_bytes_total": rs["bytes_total"],
        "repl_blocks_total": rs["blocks_total"],
    }


def prefix_sweep(arch: str = "llama3-8b", fracs=(0.0, 0.5, 0.8)):
    """Hit-rate sweep over shared-prefix fractions, plus the cache-off
    baseline at the top fraction: the headline is how much prefill compute
    and replication traffic an 80%-shared workload saves."""
    sweep = {str(f): prefix_traffic(f) for f in fracs}
    top = str(max(fracs))
    base = prefix_traffic(max(fracs), prefix_cache=False)
    hot = sweep[top]
    return {
        "arch": arch,
        "n_requests": 20,
        "prompt_tokens": 104,
        "prefix_tokens": 96,
        "sweep": sweep,
        "baseline_no_cache": base,
        "compute_reduction_x": round(
            base["prefill_compute_tokens"] /
            max(hot["prefill_compute_tokens"], 1), 2),
        "repl_bytes_reduction_x": round(
            base["repl_bytes_total"] / max(hot["repl_bytes_total"], 1), 2),
        "shared_page_ship_ratio": hot["shared_page_ship_ratio"],
    }


# sliding-window archs (reduced window = 64): serve to 2x the window and
# measure what recycling buys — resident blocks per request stay bounded by
# ceil(window/page)+1 while the sequence runs arbitrarily past the window
RECYCLING_ARCHS = ("mixtral-8x7b", "recurrentgemma-9b")


def recycling_traffic(arch: str, n_requests: int = 2):
    """Serve a windowed arch at max_seq = 2x sliding_window and record the
    recycling behaviour: peak resident KV blocks per request (vs the
    unrecycled footprint), retire-message count, and replication traffic."""
    import numpy as np
    from repro.configs import get_config
    from repro.serving.engine import EngineConfig, RealEngine
    from repro.serving.request import Request

    cfg = get_config(arch).reduced()
    window = cfg.sliding_window
    max_seq = 2 * window
    prompt = 16
    out = max_seq - prompt - 8          # run well past the window
    eng = RealEngine(cfg, EngineConfig(max_slots=2, max_seq=max_seq),
                     n_instances=2, seed=0)
    rng = np.random.default_rng(0)
    for i in range(n_requests):
        eng.submit(Request(
            rid=i, prompt_len=prompt, max_new_tokens=out, arrival_time=0.0,
            prompt_tokens=rng.integers(1, cfg.vocab_size, prompt).tolist()))
    peak_resident = 0
    for _ in range(1200):
        eng.step()
        for inst in eng.instances:
            for rid in inst.pool.live_requests():
                if rid >= 0:            # skip the scratch pseudo-request
                    peak_resident = max(peak_resident,
                                        len(inst.pool.table(rid)))
        if not eng.has_pending():
            break
    stats = eng.replication_stats()
    page = cfg.page_size
    return {
        "window": window,
        "max_seq": max_seq,
        "page_size": page,
        "tokens_per_request": prompt + out,
        "peak_resident_blocks_per_request": peak_resident,
        "resident_bound": -(-window // page) + 1,
        "unrecycled_blocks_per_request": -(-(prompt + out) // page),
        "retire_msgs_total": stats["retire_msgs_total"],
        "blocks_per_request_step": stats["blocks_per_request_step"],
        "blobs_per_request_step": stats["blobs_per_request_step"],
        "bytes_per_step": stats["bytes_per_step"],
        "bytes_total": stats["bytes_total"],
    }


def main(fast: bool = True):
    rows = []
    sweep = {2: ([1, 2, 3] if fast else [1, 2, 3, 4, 5, 6]),
             4: ([2, 5] if fast else [1, 2, 4, 6, 8, 10, 12])}
    for n_inst, rpss in sweep.items():
        for rps in rpss:
            base = run_scenario("standard", n_inst, float(rps), [],
                                arrive=400.0, horizon=800.0)
            repl = run_scenario("kevlarflow", n_inst, float(rps), [],
                                arrive=400.0, horizon=800.0)
            ov = (repl["latency_avg"] / base["latency_avg"] - 1) * 100
            ovp = (repl["latency_p99"] / base["latency_p99"] - 1) * 100
            rows.append(fmt_row("overhead", f"{4*n_inst}-node", rps,
                                round(base["latency_avg"], 2),
                                round(repl["latency_avg"], 2),
                                round(ov, 2), round(ovp, 2)))
    emit(rows, HEADER)

    # real paged-engine replication traffic: full snapshot vs dirty deltas
    # vs int8-quantized deltas, one arch per paged family
    trows = []
    int8_section = {}
    for arch in TRAFFIC_ARCHS:
        traffic = {}
        for mode in ("full", "delta", "int8"):
            s = replication_traffic(mode, arch=arch)
            traffic[mode] = s
            trows.append(fmt_row("repl_traffic", arch, mode,
                                 round(s["blocks_per_step"], 2),
                                 round(s["bytes_per_step"], 1),
                                 round(s["blocks_per_request_step"], 3),
                                 round(s["blobs_per_request_step"], 3),
                                 s["bytes_total"]))
        traffic["reduction_x"] = round(
            traffic["full"]["bytes_total"] /
            max(traffic["delta"]["bytes_total"], 1), 2)
        section = "replication_traffic" if arch == "llama3-8b" \
            else f"replication_traffic_{arch.replace('-', '_')}"
        update_bench_json(section, traffic)
        # int8 pool vs the bf16 pool, both on delta replication: the same
        # dirty blocks, ~half the bytes per message (int8 payload + scales);
        # on hybrid the state blob shrinks ~4x (f32 words -> int8 + scale)
        int8_section[arch] = {
            "bf16_bytes_per_step": traffic["delta"]["bytes_per_step"],
            "int8_bytes_per_step": traffic["int8"]["bytes_per_step"],
            "bf16_bytes_total": traffic["delta"]["bytes_total"],
            "int8_bytes_total": traffic["int8"]["bytes_total"],
            "bf16_block_bytes": traffic["delta"]["block_bytes"],
            "int8_block_bytes": traffic["int8"]["block_bytes"],
            "bf16_blob_bytes": traffic["delta"]["blob_bytes"],
            "int8_blob_bytes": traffic["int8"]["blob_bytes"],
            "bytes_reduction_x": round(
                traffic["delta"]["bytes_total"] /
                max(traffic["int8"]["bytes_total"], 1), 2),
        }
    update_bench_json("int8", int8_section)
    emit(trows, TRAFFIC_HEADER)

    # sync vs async (step-overlapped) replication wall-clock per step
    overlap = repl_overlap()
    update_bench_json("repl_overlap", overlap)
    emit([fmt_row("repl_overlap", overlap["arch"], "sync/async/off",
                  overlap["sync_ms_per_step"], overlap["async_ms_per_step"],
                  overlap["off_ms_per_step"],
                  overlap["overlap_saves_ms_per_step"])],
         "bench,arch,modes,sync_ms,async_ms,off_ms,overlap_saves_ms")

    # sliding-window recycling: resident footprint + traffic at 2x window
    rrows = []
    recycling = {}
    for arch in RECYCLING_ARCHS:
        s = recycling_traffic(arch)
        recycling[arch] = s
        rrows.append(fmt_row("recycling", arch, s["max_seq"],
                             s["peak_resident_blocks_per_request"],
                             s["resident_bound"],
                             s["unrecycled_blocks_per_request"],
                             s["retire_msgs_total"],
                             round(s["blocks_per_request_step"], 3)))
    update_bench_json("recycling", recycling)
    emit(rrows, RECYCLING_HEADER)

    # shared-prefix caching: hit-rate sweep + cache-off baseline
    prows = run_prefix()
    return rows + trows + rrows + prows


def run_prefix():
    """The --prefix mode (also part of main/bench-smoke): shared-prefix
    hit-rate sweep + the 80%-shared headline reductions."""
    section = prefix_sweep()
    update_bench_json("prefix", section)
    prows = []
    for frac, s in list(section["sweep"].items()) + \
            [("baseline", section["baseline_no_cache"])]:
        prows.append(fmt_row("prefix", section["arch"], frac,
                             s["prefix_cache"], round(s["hit_rate"], 3),
                             s["prefill_compute_tokens"],
                             s["prefill_total_tokens"],
                             s["repl_bytes_total"],
                             round(s["shared_page_ship_ratio"], 3)))
    emit(prows, PREFIX_HEADER)
    emit([fmt_row("prefix_headline", section["arch"], 0.8, True,
                  section["compute_reduction_x"],
                  section["repl_bytes_reduction_x"],
                  section["shared_page_ship_ratio"], "-", "-")],
         "bench,arch,frac,cache,compute_red_x,repl_red_x,ship_ratio,-,-")
    return prows


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke mode: representative RPS points only "
                         "(the real-engine traffic sections run the same)")
    ap.add_argument("--prefix", action="store_true",
                    help="run only the shared-prefix caching sweep")
    args = ap.parse_args()
    if args.prefix:
        run_prefix()
    else:
        main(fast=args.fast)
