"""Serving-under-failure latency harness (paper Sec 4 headline numbers).

Replays a ShareGPT-shaped Poisson OPEN-LOOP workload (serving/workload.py,
scaled to CPU-feasible lengths) through a real ``EngineService`` — actual
JAX forward passes, wall-clock timestamps — kills an instance mid-run, and
measures what the paper's Table 1 measures:

  * MTTR            — failure until the spare serves again
                      (``RealEngine.mttr_events``),
  * avg / p99 end-to-end latency and avg / p99 TTFT,
  * goodput         — completed requests/s and generated tokens/s over the
                      run's makespan,

for ``kevlarflow`` recovery (replica promotion + dynamic rerouting + warm-
spare rejoin after ``rejoin_delay``) vs the ``standard`` baseline (victims
restart from scratch; the whole group stalls ``reload_penalty`` seconds of
weight reloading), per paged family (dense / MoE / hybrid). Results land in
``BENCH_latency.json`` (validated by ``make bench-check``).

  PYTHONPATH=src python -m benchmarks.bench_latency [--tiny] [--family dense]

``--tiny`` is the CI smoke mode: the same pipeline at the smallest workload
that still exercises a failure mid-run.
"""
from __future__ import annotations

import argparse
import json
import os
import threading
import time
from typing import Dict, List

import numpy as np

from benchmarks.common import emit, fmt_row

HEADER = ("bench,family,mode,n,mttr_s,latency_avg_s,latency_p99_s,"
          "ttft_avg_s,ttft_p99_s,goodput_tok_s,retries,migrations")
BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_latency.json")

# one arch per paged family, matching bench_overhead / test_engine
FAMILIES = {
    "dense": "llama3-8b",
    "moe": "mixtral-8x7b",
    "hybrid": "recurrentgemma-9b",
}

# run-shape knobs: paper-shaped distribution, CPU-feasible sizes. The
# reload:rejoin ratio (20x) mirrors InitCosts.full_init/decoupled_reform —
# the paper's ~10 min weight reload vs ~seconds decoupled re-form.
PROFILES = {
    "full": dict(rps=8.0, duration=5.0, prompt_mean=18.0, output_mean=24.0,
                 max_prompt=40, max_output=40, fail_at=1.5,
                 rejoin_delay=0.3, reload_penalty=6.0,
                 max_slots=8, max_seq=96, prefill_chunk=16),
    "tiny": dict(rps=8.0, duration=2.0, prompt_mean=14.0, output_mean=14.0,
                 max_prompt=24, max_output=20, fail_at=0.7,
                 rejoin_delay=0.15, reload_penalty=1.5,
                 max_slots=8, max_seq=64, prefill_chunk=8),
}


def _inject_failure(svc, t0: float, fail_at: float, out: List):
    """Kill instance 0 at ``fail_at`` — like the paper's drills, the kill
    lands while the instance is SERVING: if it happens to be idle at the
    mark, wait (bounded) for in-flight work so every run measures recovery
    of real victims, not a lucky empty instance."""
    while time.time() < t0 + fail_at:
        time.sleep(0.005)
    deadline = time.time() + 3.0
    while time.time() < deadline:
        if svc.fail_instance_if_busy(0) is not None:
            out.append(time.time() - t0)
            return
        time.sleep(0.005)
    out.append(time.time() - t0)
    svc.fail_instance(0)       # workload drained early: kill it anyway


def _warmup(svc, cfg, prof, rng):
    """Compile every prefill bucket the workload can hit (plus the decode
    step) BEFORE the clock starts, so early requests don't pay jit time."""
    from repro.models.paged_decode import next_bucket

    page = cfg.page_size
    # EVERY bucket a workload prompt can land in (not just the extremes) —
    # one un-warmed bucket means one request pays jit time mid-measurement
    buckets = sorted({next_bucket(n, lo=page)
                      for n in range(page, prof["max_prompt"] + 1)})
    lens = sorted({max(page, b // 2 + 1) for b in buckets})
    warm = [svc.submit(rng.integers(1, cfg.vocab_size, n).tolist(), 2)
            for n in lens]
    for req in warm:
        svc.wait(req, timeout=120.0)


def _sweeps(engine, measured, page: int) -> Dict:
    """CI-artifact sweeps (chunk-size regressions show up here):

    * TPOT vs active slots — median wall-clock step time at each decode
      occupancy, from the engine's per-step samples. Chunked prefill's
      whole point is that this curve stays flat while admissions stream
      in; an inline-prefill regression spikes the low-occupancy bins.
    * TTFT vs prompt length — average TTFT per prefill bucket. A chunk
      scheduling regression shows up as TTFT growing superlinearly in
      prompt length.
    """
    from repro.models.paged_decode import next_bucket

    by_occ: Dict[int, List[float]] = {}
    # samples carry (n_active, wall_dt, capacity_frac) — the capacity
    # fraction matters to the fleet bench, not this whole-fleet sweep
    for n_active, dt, *_ in engine.step_samples:
        by_occ.setdefault(n_active, []).append(dt)
    tpot = {str(k): round(float(np.median(v)) * 1e3, 3)
            for k, v in sorted(by_occ.items())}
    by_bucket: Dict[int, List[float]] = {}
    for r in measured:
        if r.first_token_time >= 0:
            by_bucket.setdefault(next_bucket(r.prompt_len, lo=page),
                                 []).append(r.ttft)
    ttft = {str(b): round(float(np.mean(v)), 4)
            for b, v in sorted(by_bucket.items())}
    return {"tpot_ms_vs_active_slots": tpot, "ttft_s_vs_prompt_bucket": ttft}


def run_mode(family: str, mode: str, prof: dict, seed: int = 0) -> Dict:
    """One measured run: open-loop Poisson replay + one failure mid-run."""
    from repro.configs import get_config
    from repro.serving.engine import EngineConfig
    from repro.serving.request import summarize
    from repro.serving.server import EngineService
    from repro.serving.workload import poisson_workload

    cfg = get_config(FAMILIES[family]).reduced()
    ecfg = EngineConfig(
        max_slots=prof["max_slots"], max_seq=prof["max_seq"],
        recovery=mode, replicate=(mode == "kevlarflow"),
        auto_rejoin=True, rejoin_delay=prof["rejoin_delay"],
        reload_penalty=prof["reload_penalty"],
        prefill_chunk=prof.get("prefill_chunk", 0))
    svc = EngineService(cfg, ecfg, n_instances=2)
    rng = np.random.default_rng(seed)
    try:
        _warmup(svc, cfg, prof, rng)
        svc.engine.step_samples.clear()      # sweeps measure the run only
        work = poisson_workload(
            prof["rps"], prof["duration"], seed=seed,
            prompt_mean=prof["prompt_mean"], output_mean=prof["output_mean"],
            max_prompt=prof["max_prompt"], min_output=4,
            max_output=prof["max_output"])
        t0 = time.time()
        fail_times: List = []
        injector = threading.Thread(
            target=_inject_failure, args=(svc, t0, prof["fail_at"],
                                          fail_times))
        injector.start()
        measured: List = []
        for w in work:                       # open loop: arrivals never wait
            dt = t0 + w.arrival_time - time.time()
            if dt > 0:
                time.sleep(dt)
            toks = rng.integers(1, cfg.vocab_size, w.prompt_len).tolist()
            measured.append(svc.submit(toks, w.max_new_tokens))
        injector.join()
        if not svc.drain(timeout=600.0):
            raise RuntimeError(f"{family}/{mode}: run did not drain")
        makespan = time.time() - t0
        # the spare's rejoin may land after the last completion — MTTR is
        # part of the measurement, so wait it out (bounded by the penalty)
        deadline = time.time() + prof["reload_penalty"] + 2.0
        while not svc.engine.mttr_events() and time.time() < deadline:
            time.sleep(0.01)
        events = svc.engine.mttr_events()
    finally:
        svc.shutdown()
    m = summarize(measured, span=makespan)
    m["sweeps"] = _sweeps(svc.engine, measured, cfg.page_size)
    m["mode"] = mode
    m["mttr"] = events[0]["mttr"] if events else -1.0
    m["n_submitted"] = len(measured)
    m["makespan"] = makespan
    m["failed_at"] = round(fail_times[0], 3) if fail_times else -1.0
    m["n_victims"] = svc.engine.failure_events[0]["n_victims"]
    m["resumed_seamlessly"] = svc.engine.failure_events[0]["resumed"]
    m["requeued_on_failure"] = svc.engine.failure_events[0]["requeued"]
    return m


def run_nofail(family: str, prof: dict, disagg: bool, seed: int = 0) -> Dict:
    """One NO-FAILURE run, colocated or disaggregated — the pair behind the
    disagg TTFT gate (roles must not tax time-to-first-token)."""
    from repro.configs import get_config
    from repro.serving.engine import EngineConfig
    from repro.serving.request import summarize
    from repro.serving.server import EngineService
    from repro.serving.workload import poisson_workload

    cfg = get_config(FAMILIES[family]).reduced()
    ecfg = EngineConfig(
        max_slots=prof["max_slots"], max_seq=prof["max_seq"],
        prefill_chunk=prof.get("prefill_chunk") or 8,
        disaggregate=disagg)
    svc = EngineService(cfg, ecfg, n_instances=2)
    rng = np.random.default_rng(seed)
    try:
        _warmup(svc, cfg, prof, rng)
        work = poisson_workload(
            prof["rps"], prof["duration"], seed=seed,
            prompt_mean=prof["prompt_mean"], output_mean=prof["output_mean"],
            max_prompt=prof["max_prompt"], min_output=4,
            max_output=prof["max_output"])
        t0 = time.time()
        measured: List = []
        for w in work:
            dt = t0 + w.arrival_time - time.time()
            if dt > 0:
                time.sleep(dt)
            toks = rng.integers(1, cfg.vocab_size, w.prompt_len).tolist()
            measured.append(svc.submit(toks, w.max_new_tokens))
        if not svc.drain(timeout=600.0):
            raise RuntimeError(f"{family}/disagg={disagg}: did not drain")
        makespan = time.time() - t0
    finally:
        svc.shutdown()
    m = summarize(measured, span=makespan)
    m["disaggregate"] = disagg
    m["n_submitted"] = len(measured)
    m["makespan"] = makespan
    if disagg:
        st = svc.engine.disagg_stats()
        m["handoff"] = {k: st[k] for k in
                        ("handoffs_seated", "handoff_blocks_total",
                         "handoff_blobs_total", "handoff_bytes_total")}
        m["roles"] = st["roles"]
    return m


DISAGG_HEADER = ("bench,family,mode,n,ttft_avg_s,ttft_p99_s,latency_avg_s,"
                 "goodput_tok_s,handoff_blocks,handoff_bytes")


def main_disagg(fast: bool = True, profile: str = None, families=None):
    """--disagg entry: colocated vs disaggregated no-failure pairs, merged
    into BENCH_latency.json as the ``disagg`` section (the failure-mode
    ``families`` section is preserved untouched)."""
    profile = profile or ("tiny" if fast else "full")
    prof = PROFILES[profile]
    families = families or ["dense"]     # smoke default: one family
    rows = []
    section = {"profile": profile, "n_instances": 2, "families": {}}
    for family in families:
        colo = run_nofail(family, prof, disagg=False)
        dis = run_nofail(family, prof, disagg=True)
        per = {"arch": FAMILIES[family], "colocated": colo, "disagg": dis,
               "ttft_ratio_x": round(
                   dis["ttft_avg"] / max(colo["ttft_avg"], 1e-9), 2)}
        section["families"][family] = per
        for label, m in (("colocated", colo), ("disagg", dis)):
            h = m.get("handoff", {})
            rows.append(fmt_row(
                "disagg", family, label, m["n"],
                round(m["ttft_avg"], 3), round(m["ttft_p99"], 3),
                round(m["latency_avg"], 3), round(m["goodput_tok_s"], 1),
                h.get("handoff_blocks_total", 0),
                h.get("handoff_bytes_total", 0)))
    path = os.path.abspath(BENCH_JSON)
    payload = {}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload["disagg"] = section
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    emit(rows, DISAGG_HEADER)
    print(f"wrote {path} (disagg section)")
    return rows


def _ratio(std: Dict, kf: Dict, key: str) -> float:
    return round(std[key] / max(kf[key], 1e-9), 2)


def main(fast: bool = True, profile: str = None, families=None):
    profile = profile or ("tiny" if fast else "full")
    prof = PROFILES[profile]
    families = families or list(FAMILIES)
    rows = []
    payload = {"meta": {"profile": profile, **prof,
                        "n_instances": 2, "failed_instance": 0},
               "families": {}}
    if os.path.exists(BENCH_JSON):
        # partial runs MERGE into the existing artifact — clobbering the
        # other families' sections (or the --disagg section) would fail
        # the next bench-check
        with open(BENCH_JSON) as f:
            prior = json.load(f)
        if len(families) < len(FAMILIES):
            payload["families"] = prior.get("families", {})
        for section in ("disagg", "scenario_matrix"):
            if section in prior:
                payload[section] = prior[section]
    for family in families:
        per = {"arch": FAMILIES[family]}
        for mode in ("kevlarflow", "standard"):
            m = run_mode(family, mode, prof)
            per[mode] = m
            rows.append(fmt_row(
                "latency", family, mode, m["n"], round(m["mttr"], 3),
                round(m["latency_avg"], 3), round(m["latency_p99"], 3),
                round(m["ttft_avg"], 3), round(m["ttft_p99"], 3),
                round(m["goodput_tok_s"], 1), m["retries"], m["migrations"]))
        per["ratios"] = {
            "mttr_x": _ratio(per["standard"], per["kevlarflow"], "mttr"),
            "latency_avg_x": _ratio(per["standard"], per["kevlarflow"],
                                    "latency_avg"),
            "latency_p99_x": _ratio(per["standard"], per["kevlarflow"],
                                    "latency_p99"),
            "ttft_avg_x": _ratio(per["standard"], per["kevlarflow"],
                                 "ttft_avg"),
            "ttft_p99_x": _ratio(per["standard"], per["kevlarflow"],
                                 "ttft_p99"),
            "goodput_tok_x": round(
                per["kevlarflow"]["goodput_tok_s"] /
                max(per["standard"]["goodput_tok_s"], 1e-9), 2),
        }
        payload["families"][family] = per
    path = os.path.abspath(BENCH_JSON)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    emit(rows, HEADER)
    print(f"wrote {path}")
    return rows


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke profile: smallest run that still crosses "
                         "a failure")
    ap.add_argument("--family", choices=list(FAMILIES), default=None,
                    help="run a single family (default: all three)")
    ap.add_argument("--disagg", action="store_true",
                    help="run the colocated-vs-disaggregated no-failure "
                         "pair instead of the failure harness; merges a "
                         "`disagg` section into BENCH_latency.json")
    args = ap.parse_args()
    if args.disagg:
        main_disagg(fast=args.tiny,
                    families=[args.family] if args.family else None)
    else:
        main(fast=args.tiny,
             families=[args.family] if args.family else None)
