"""Find a cell's knee: serve its mix at several fixed rates in one process
(one set-up), and print per rate how the queue and the tails behaved.

  python3 bench/sweep.py --workload yi9b_code --seed 1 --seconds 30 \
      --rates 2 3 4 5

The knee is the highest swept rate at which the backlog does not grow
over the window: the requests waiting for admission in the window's last
third are, on average, no more than in its first third plus one engine
batch. Each rate's line gives both averages. Rates in BENCHMARK.json's
mixes are fixed numbers set once from such a sweep (see PERF.md).
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness as H  # noqa: E402
from bench import spec as S  # noqa: E402


def waiting(svc, load) -> int:
    """Requests due but not yet admitted."""
    with svc._lock:
        return sum(1 for r in list(load.records)
                   if r["req"] is None or r["req"].admit_time < 0)


def one_rate(served, gen, mix, rate, seconds, seed) -> dict:
    svc = served.svc
    mix = copy.deepcopy(mix)
    mix["arrivals"]["rate_per_s"] = rate
    t0 = time.time()
    load = H.Load(svc, gen, mix, seconds, seed, served.cfg.vocab_size, t0)
    depth = []
    stop = threading.Event()

    def sample():
        while not stop.is_set():
            depth.append((time.time() - t0, waiting(svc, load)))
            time.sleep(0.25)
    th = threading.Thread(target=sample, daemon=True)
    th.start()
    load.start()
    H._sleep_until(t0 + seconds)
    load.stop.set()
    stop.set()
    th.join()
    load.close()
    recs = [r for r in load.records if r["block"] == 0]
    for r in recs:
        svc.wait(r["req"], timeout=120)
    svc.drain(timeout=120)
    m = [H._record(r, time.time()) for r in recs]
    third = lambda a, b: [d for t, d in depth
                          if a * seconds / 3 <= t < b * seconds / 3]
    mean = lambda xs: sum(xs) / len(xs) if xs else 0.0
    ttft = [r["first"] - r["due"] for r in m if r["first"] > 0]
    tpot = [(r["finish"] - r["first"]) / (len(r["out"]) - 1) * 1e3
            for r in m if r["finish"] > 0 and len(r["out"]) > 1]
    return {"rate_per_s": rate, "requests": len(m),
            "waiting_first_third": mean(third(0, 1)),
            "waiting_last_third": mean(third(2, 3)),
            "ttft_p50_s": H.percentile(ttft, 50) if ttft else None,
            "ttft_p90_s": H.percentile(ttft, 90) if ttft else None,
            "tpot_p90_ms": H.percentile(tpot, 90) if tpot else None}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = S.Bench()
    H.use_compile_cache(bench.root)
    S.add_program_to_path(bench.root)
    cell = bench.cell(args.workload)
    conf = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    gen = bench.generator(mix)
    H.device_info(True, cell["chips"])
    served = H.build(conf, args.seed)
    H.warm_up(served, mix, gen)
    try:
        for rate in args.rates:
            print(json.dumps(one_rate(served, gen, mix, rate, args.seconds,
                                      args.seed)), flush=True)
    finally:
        served.svc.shutdown()


if __name__ == "__main__":
    main()
