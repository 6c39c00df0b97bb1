"""Paper Fig 5 + Table 1: KevlarFlow vs standard fault behaviour under the
three failure scenarios:
  1: 8-node (2x4), one node fails
  2: 16-node (4x4), one node fails
  3: 16-node (4x4), two nodes fail (two pipelines)

``--fleet`` runs the FLEET SCENARIO MATRIX instead: the real tick-clock
``RealEngine`` at 8-12 instances under {single kill, correlated 3-instance
kill, storm-during-rejoin} x {kevlarflow, standard}, merged into
``BENCH_latency.json`` as the ``scenario_matrix`` section that
``make bench-check`` gates (no dropped requests in any cell; kevlarflow
strictly better per scenario).
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict

from benchmarks.common import emit, fmt_row, run_scenario

HEADER = ("bench,scene,rps,mode,latency_avg,ttft_avg,latency_p99,ttft_p99,"
          "imp_lat,imp_ttft,imp_lat_p99,imp_ttft_p99,retries,migrations")

SCENES = {
    1: dict(n_instances=2, fail_nodes=[2]),
    2: dict(n_instances=4, fail_nodes=[2]),
    3: dict(n_instances=4, fail_nodes=[2, 9]),   # two different pipelines
}

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_latency.json")

# Fleet matrix run shapes. The engine runs on its TICK clock (one tick per
# step) — deterministic, so CI results don't wobble with machine load —
# and every time knob below (rejoin_delay, reload_penalty, latency) is in
# ticks. The reload:rejoin ratio (6x) keeps the standard-mode stall the
# dominant cost, same story as the wall-clock harness.
FLEET_PROFILES = {
    "tiny": dict(n_instances=8, n_requests=24, prompt_max=16, max_new=6,
                 rejoin_delay=4.0, reload_penalty=24.0,
                 max_slots=4, max_seq=64),
    "full": dict(n_instances=12, n_requests=48, prompt_max=20, max_new=8,
                 rejoin_delay=4.0, reload_penalty=24.0,
                 max_slots=4, max_seq=64),
}

FLEET_SCENARIOS = ("single_kill", "correlated_kill_3", "storm_during_rejoin")
FLEET_HEADER = ("bench,scenario,mode,n,dropped,latency_avg,latency_p99,"
                "ttft_avg,mttr_avg,kills,resumed,restarted,epoch")


def _fleet_cell(cfg, mode: str, scenario: str, prof: dict,
                seed: int = 0) -> Dict:
    """One matrix cell: a tick-clock fleet run of ``scenario`` under
    ``mode``. All requests arrive at t=0 (the failure hits a loaded
    fleet); the run drains through every kill, rejoin, and re-kill."""
    import numpy as np

    from repro.serving.engine import EngineConfig, RealEngine
    from repro.serving.request import Request, summarize

    ecfg = EngineConfig(
        max_slots=prof["max_slots"], max_seq=prof["max_seq"],
        recovery=mode, replicate=(mode == "kevlarflow"),
        auto_rejoin=True, rejoin_delay=prof["rejoin_delay"],
        reload_penalty=prof["reload_penalty"],
        placement="rendezvous")     # the fleet-scale policy under test
    eng = RealEngine(cfg, ecfg, n_instances=prof["n_instances"])
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(prof["n_requests"]):
        n = int(rng.integers(4, prof["prompt_max"]))
        reqs.append(Request(
            rid=rid, prompt_len=n,
            max_new_tokens=int(rng.integers(2, prof["max_new"])),
            arrival_time=0.0,
            prompt_tokens=rng.integers(1, cfg.vocab_size, n).tolist()))
    for r in reqs:
        eng.submit(r)
    # kill schedule: tick -> instance ids (kills land on a loaded fleet)
    kills = {2.0: [0, 1, 2]} if scenario == "correlated_kill_3" \
        else {2.0: [0]}
    if scenario == "storm_during_rejoin":
        kills[3.0] = [1]            # second kill during 0's queue drain
    rekill_pending = scenario == "storm_during_rejoin"
    steps = 0
    while (eng.has_pending() or eng.recovery_pending()) and steps < 4000:
        for t_kill in sorted(kills):
            if eng.t >= t_kill:
                for iid in kills.pop(t_kill):
                    if eng.instances[iid].alive:
                        eng.fail_instance(iid)
        if rekill_pending and eng.instances[0].alive and any(
                e["instance"] == 0 and e["t_rejoin"] >= 0
                for e in eng.failure_events):
            # the storm's signature move: the spare dies again right
            # after rejoining — the planner just reschedules it
            eng.fail_instance(0)
            rekill_pending = False
        eng.step()
        steps += 1
    m = summarize(eng.done, span=max(eng.t, 1e-9))
    events = eng.mttr_events()
    m.update({
        "n_submitted": len(reqs),
        "dropped": len(reqs) - len(eng.done),
        "mttr_avg": round(float(np.mean([e["mttr"] for e in events])), 3)
        if events else -1.0,
        "kills": len(eng.failure_events),
        "resumed": sum(e["resumed"] for e in eng.failure_events),
        "restarted": sum(e["restarted"] for e in eng.failure_events),
        "epoch_final": eng.control.view.epoch,
        "ticks": eng.t,
    })
    return m


def _shard_cell(cfg, fault: str, prof: dict, seed: int = 0) -> Dict:
    """One ``shard_degraded`` cell: the same loaded kevlarflow fleet takes
    the same fault-at-tick-2 on its busiest instance, either as a single
    SHARD loss (``fault="degraded"`` — the instance keeps serving on the
    surviving slice at reduced capacity) or as the whole-instance kill
    (``fault="instance_failover"`` — the classic drill). Both auto-rejoin;
    deterministic tick clock, so the comparison is exact."""
    import numpy as np

    from repro.serving.engine import EngineConfig, RealEngine
    from repro.serving.request import Request, summarize

    ecfg = EngineConfig(
        max_slots=prof["max_slots"], max_seq=prof["max_seq"],
        recovery="kevlarflow", replicate=True,
        auto_rejoin=True, rejoin_delay=prof["rejoin_delay"],
        reload_penalty=prof["reload_penalty"],
        placement="rendezvous", n_shards=4)
    eng = RealEngine(cfg, ecfg, n_instances=prof["n_instances"])
    rng = np.random.default_rng(seed)
    reqs = []
    # 3x the matrix load: the fleet must stay queue-backed through the
    # fault AND the rejoin, or both modes drain so fast the capacity
    # difference (1 slot lost vs 4) never reaches the latency numbers
    for rid in range(prof["n_requests"] * 3):
        n = int(rng.integers(4, prof["prompt_max"]))
        reqs.append(Request(
            rid=rid, prompt_len=n,
            max_new_tokens=int(rng.integers(2, prof["max_new"])),
            arrival_time=0.0,
            prompt_tokens=rng.integers(1, cfg.vocab_size, n).tolist()))
    for r in reqs:
        eng.submit(r)
    faulted = False
    steps = 0
    cap_min = 1.0
    while (eng.has_pending() or eng.recovery_pending()) and steps < 4000:
        if not faulted and eng.t >= 2.0:
            # both modes pick the victim identically (deterministic run):
            # the busiest instance — the fault lands on serving work
            victim = max((i for i in eng.instances if i.alive),
                         key=lambda i: (len(i.requests), -i.instance_id))
            if fault == "degraded":
                eng.fail_shard(victim.instance_id, 0)
            else:
                eng.fail_instance(victim.instance_id)
            faulted = True
        eng.step()
        steps += 1
        if eng.step_samples:
            cap_min = min(cap_min, eng.step_samples[-1][2])
    m = summarize(eng.done, span=max(eng.t, 1e-9))
    events = eng.mttr_events()
    view = eng.control.view
    m.update({
        "n_submitted": len(reqs),
        "dropped": len(reqs) - len(eng.done),
        "mttr_avg": round(float(np.mean([e["mttr"] for e in events])), 3)
        if events else -1.0,
        "kills": len(eng.failure_events),
        "resumed": sum(e["resumed"] for e in eng.failure_events),
        "restarted": sum(e["restarted"] for e in eng.failure_events),
        "epoch_final": view.epoch,
        "ticks": eng.t,
        # degradation markers the bench gate reads: the shard path must
        # actually engage (and heal back to a fully HEALTHY fleet), and
        # the capacity floor records the throughput cap while degraded
        "degraded_engaged": any(e.get("granularity") == "shard"
                                for e in eng.failure_events),
        "healed": all(view.state_of(i) == "HEALTHY"
                      for i in range(view.n)),
        "capacity_min": round(cap_min, 4),
    })
    return m


def main_fleet(fast: bool = True, profile: str = None,
               shard_faults: bool = False):
    """--fleet entry: the scenario matrix, merged into BENCH_latency.json
    as the ``scenario_matrix`` section (all other sections preserved)."""
    from repro.configs import get_config

    profile = profile or ("tiny" if fast else "full")
    prof = FLEET_PROFILES[profile]
    cfg = get_config("llama3-8b").reduced()
    rows = []
    scenarios: Dict[str, Dict] = {}
    for scenario in FLEET_SCENARIOS:
        cell: Dict = {}
        for mode in ("kevlarflow", "standard"):
            m = _fleet_cell(cfg, mode, scenario, prof)
            cell[mode] = m
            rows.append(fmt_row(
                "fleet", scenario, mode, m["n"], m["dropped"],
                round(m["latency_avg"], 2), round(m["latency_p99"], 2),
                round(m["ttft_avg"], 2), m["mttr_avg"], m["kills"],
                m["resumed"], m["restarted"], m["epoch_final"]))
        cell["latency_ratio_x"] = round(
            cell["standard"]["latency_avg"] /
            max(cell["kevlarflow"]["latency_avg"], 1e-9), 2)
        scenarios[scenario] = cell
    if shard_faults:
        # the degraded-serving cell: one shard lost vs the whole instance,
        # same fleet, same fault tick — the matrix's proof that partial
        # faults are cheaper absorbed than escalated
        cell = {}
        for fault in ("degraded", "instance_failover"):
            m = _shard_cell(cfg, fault, prof)
            cell[fault] = m
            rows.append(fmt_row(
                "fleet", "shard_degraded", fault, m["n"], m["dropped"],
                round(m["latency_avg"], 2), round(m["latency_p99"], 2),
                round(m["ttft_avg"], 2), m["mttr_avg"], m["kills"],
                m["resumed"], m["restarted"], m["epoch_final"]))
        cell["latency_ratio_x"] = round(
            cell["instance_failover"]["latency_avg"] /
            max(cell["degraded"]["latency_avg"], 1e-9), 2)
        scenarios["shard_degraded"] = cell
    section = {"profile": profile, "n_instances": prof["n_instances"],
               "arch": "llama3-8b", "placement": "rendezvous",
               "clock": "ticks", "scenarios": scenarios}
    path = os.path.abspath(BENCH_JSON)
    payload = {}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload["scenario_matrix"] = section
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    emit(rows, FLEET_HEADER)
    print(f"wrote {path} (scenario_matrix section)")
    return rows


def main(fast: bool = True):
    rows = []
    for scene, cfg in SCENES.items():
        max_rps = 8 if scene == 1 else 16
        if fast:
            rpss = [2.0, 4.0] if scene == 1 else [2.0, 7.0]
        else:
            rpss = [float(r) for r in range(1, max_rps + 1)]
        arrive, horizon = (500.0, 900.0) if fast else (1200.0, 1800.0)
        for rps in rpss:
            base = run_scenario("standard", cfg["n_instances"], rps,
                                cfg["fail_nodes"], arrive=arrive,
                                horizon=horizon)
            ours = run_scenario("kevlarflow", cfg["n_instances"], rps,
                                cfg["fail_nodes"], arrive=arrive,
                                horizon=horizon)
            rows.append(fmt_row(
                "failure", scene, rps, "pair",
                f"{base['latency_avg']:.2f}/{ours['latency_avg']:.2f}",
                f"{base['ttft_avg']:.2f}/{ours['ttft_avg']:.2f}",
                f"{base['latency_p99']:.2f}/{ours['latency_p99']:.2f}",
                f"{base['ttft_p99']:.2f}/{ours['ttft_p99']:.2f}",
                round(base["latency_avg"] / ours["latency_avg"], 2),
                round(base["ttft_avg"] / max(ours["ttft_avg"], 1e-3), 1),
                round(base["latency_p99"] / ours["latency_p99"], 2),
                round(base["ttft_p99"] / max(ours["ttft_p99"], 1e-3), 1),
                f"{base['retries']}/{ours['retries']}",
                f"{base['migrations']}/{ours['migrations']}"))
    emit(rows, HEADER)
    return rows


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fleet", action="store_true",
                    help="run the fleet scenario matrix on the real engine "
                         "(8-12 instances x 3 failure scenarios x 2 modes) "
                         "and merge it into BENCH_latency.json")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke profile (fleet: 8 instances; sim: "
                         "reduced rps grid)")
    ap.add_argument("--shard-faults", action="store_true",
                    help="add the shard_degraded cell to the fleet matrix: "
                         "single-shard degraded serving vs whole-instance "
                         "failover on the same loaded fleet")
    args = ap.parse_args()
    if args.fleet:
        main_fleet(fast=args.tiny, shard_faults=args.shard_faults)
    else:
        main(fast=args.tiny)
