"""Readings that the limit of ``correct`` is set from, for one cell, in one
process: the program's widest gap over many seeds, and the control's (the
reference computed in int8 in the program's place) over a few.

  python3 bench/calibrate.py --workload yi9b_chat --seeds 101 102 103 \
      --control 3 --seconds 20

Each seed gets its own weights and traffic, a short window of the cell's
own load (the mix at its rate, then a drain until the window's requests
finish), and the sample a run takes. One JSON line per seed. The limit in
the configuration file is set between the largest program reading and the
smallest control reading, as PERF.md records.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness as H  # noqa: E402
from bench import spec as S  # noqa: E402


def swap_weights(served, seed: int):
    """Serve the weights of another seed (the old ones freed first)."""
    import jax

    from bench import weights
    eng = served.svc.engine
    served.params = eng.params = None
    for inst in eng.instances:
        inst.params = None
    gc.collect()
    params = weights.make_params(served.cfg, seed)
    jax.block_until_ready(params)
    served.params = eng.params = params
    for inst in eng.instances:
        inst.params = params


def one_seed(bench, served, conf, mix, gen, seed, seconds, control) -> dict:
    svc = served.svc
    t0 = time.time()
    load = H.Load(svc, gen, mix, seconds, seed, served.cfg.vocab_size, t0)
    load.start()
    H._sleep_until(t0 + seconds)
    load.stop.set()
    load.close()
    recs = [r for r in load.records if r["block"] == 0]
    for r in recs:
        svc.wait(r["req"], timeout=H.DRAIN_S)
    svc.drain(timeout=H.DRAIN_S)
    done = [m for m in (H._record(r, time.time()) for r in recs)
            if m["finish"] > 0]
    sample = H.choose_sample(done, seed, conf["check"]["sample_tokens"])
    gaps = H.token_gaps(bench, conf, served.params, sample)
    out = {"seed": seed, "requests": len(sample),
           "program": H.gap_stats(gaps)}
    if control:
        out["control"] = H.gap_stats(H.token_gaps(
            bench, conf, served.params, sample, control=True))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds also read the control")
    ap.add_argument("--seconds", type=float, default=20)
    args = ap.parse_args(argv)
    bench = S.Bench()
    H.use_compile_cache(bench.root)
    S.add_program_to_path(bench.root)
    cell = bench.cell(args.workload)
    conf = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    gen = bench.generator(mix)
    H.device_info(True, cell["chips"])
    served = H.build(conf, args.seeds[0])
    H.warm_up(served, mix, gen)
    try:
        for i, seed in enumerate(args.seeds):
            if i:
                swap_weights(served, seed)
            print(json.dumps(one_seed(bench, served, conf, mix, gen, seed,
                                      args.seconds, i < args.control)),
                  flush=True)
    finally:
        served.svc.shutdown()


if __name__ == "__main__":
    main()
