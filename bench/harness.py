"""One run of one cell: set up, measure a window, drain, check, report.

The run drives the server's service layer, ``EngineService``: a load thread
submits each request when it is due and the run waits for the results. It
times every request from when it was due. Weights and traffic come from
the seed; ``BENCHMARK.json`` and the files it names say everything else.

Output: informative lines first, then the numbers compared for ``correct``
with their limits (the last lines on standard error), and as the last line
of standard output the result: one JSON object.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import json
import os
import shutil
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from bench import spec as S

TRACE_S = 5.0              # length of the traced part of a --trace 1 window
DRAIN_S = 60.0             # how long past the close measured requests may take
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def process_start_time() -> float:
    """Wall-clock time at which this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) \
            - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.time() - age
    except (OSError, ValueError, IndexError):
        return time.time()


def log(msg: str):
    print(msg, flush=True)


def percentile(values, q: float) -> float:
    """The q-th percentile (linear interpolation) over every value given."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def use_compile_cache(root: Path) -> Path:
    """JAX's persistent cache at a fixed path inside the checkout, for
    every program however quick to compile. Call before importing jax."""
    path = Path(root) / "bench_out" / "jax_cache"
    path.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Programs built (compiled, or loaded from the persistent cache) and
    the seconds spent, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.built = 0
        self.loaded = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, secs, **kw):
        if event == COMPILE_EVENT:
            self.built += 1
            self.seconds += secs

    def _ev(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.loaded += 1

    def mark(self) -> tuple:
        return self.built, self.loaded, self.seconds

    def since(self, mark: tuple) -> dict:
        b, l_, s = mark
        return {"programs": self.built - b, "from_cache": self.loaded - l_,
                "compiled": (self.built - b) - (self.loaded - l_),
                "seconds": self.seconds - s}


@dataclasses.dataclass
class Run:
    """What the metric readers read: one measured window's records."""
    conf: dict
    peaks: dict
    window: tuple                   # (t0, t1), wall clock
    measured: list                  # per measured request: a dict
    steps: int                      # engine steps with work, in the window
    step_walls: list                # their wall seconds (step_samples)
    repl_bytes: int                 # replication bytes shipped in the window
    tokens: int                     # output tokens made in the window
    trace: object = None            # bench.trace.Trace of --trace 1 runs

    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]


class Load:
    """The open-loop load: block 0 is the measured window, later blocks
    keep the same load on while it drains. A few threads submit, so that a
    submission held up by the engine's lock does not hold up the next."""

    def __init__(self, svc, gen, mix: dict, seconds: float, seed: int,
                 vocab: int, t0: float):
        self.svc, self.gen, self.mix = svc, gen, mix
        self.seconds, self.seed, self.vocab, self.t0 = seconds, seed, vocab, t0
        self.records: list = []
        self.futures: list = []
        self.stop = threading.Event()
        self.pool = ThreadPoolExecutor(max_workers=4)
        self.thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self.thread.start()

    def _run(self):
        index = 0
        while not self.stop.is_set():
            reqs = self.gen.block(self.mix, self.seconds, self.seed, index,
                                  self.vocab)
            if not reqs:
                return
            start = self.t0 + index * self.seconds
            for r in sorted(reqs, key=lambda r: r["due"]):
                due = start + r["due"]
                while not self.stop.is_set():
                    left = due - time.time()
                    if left <= 0:
                        break
                    time.sleep(min(left, 0.05))
                if self.stop.is_set():
                    return
                rec = {"block": index, "due": due, "prompt": r["prompt"],
                       "max_tokens": r["max_tokens"], "req": None,
                       "late": time.time() - due}
                self.records.append(rec)
                self.futures.append(self.pool.submit(self._submit, rec))
            index += 1

    def _submit(self, rec):
        rec["req"] = self.svc.submit(rec["prompt"], rec["max_tokens"])

    def close(self):
        self.stop.set()
        self.thread.join()
        self.pool.shutdown(wait=True)
        for f in self.futures:
            f.result()              # a submission that raised raises here

    def submitted(self) -> list:
        return [r for r in list(self.records) if r["req"] is not None]


def snapshot(svc, load: Load) -> dict:
    """Counters read between two engine steps (under the service lock)."""
    with svc._lock:
        eng = svc.engine
        return {"t": time.time(),
                "tokens": sum(len(r["req"].output_tokens or [])
                              for r in load.submitted()),
                "samples": len(eng.step_samples),
                "repl_bytes": eng.transport.shipped["repl"].bytes,
                "admitted": {r["req"].rid for r in load.submitted()
                             if r["req"].admit_time >= 0}}


# -- spans around the program's calls (--trace 1 runs only) -----------------
def install_spans(svc, conf: dict):
    """Wrap the calls into each layer in ``TraceAnnotation``s named
    ``bench.<layer>``; the decode step carries its attention work."""
    from jax.profiler import TraceAnnotation

    from bench.roofline import paged_attention_cost

    def wrap(obj, attr, name, stats=None):
        fn = getattr(obj, attr)

        def wrapped(*a, **kw):
            with TraceAnnotation("bench." + name,
                                 **(stats(obj) if stats else {})):
                return fn(*a, **kw)
        setattr(obj, attr, wrapped)

    def attn_stats(inst):
        from repro.serving.request import RequestState
        lens = [int(inst.slot_pos[i]) + 1
                for i, rid in enumerate(inst.slot_rid)
                if rid >= 0 and inst.requests[rid].state
                == RequestState.DECODE]
        flops, nbytes = paged_attention_cost(conf, lens)
        n = conf["num_hidden_layers"]
        return {"attn_flops": flops * n, "attn_bytes": nbytes * n,
                "slots": len(lens)}

    eng = svc.engine
    wrap(eng, "step", "engine_step")
    wrap(eng, "_replicate", "replicate")
    wrap(eng, "flush_replication", "flush_replication")
    wrap(eng.transport, "flush", "transport_flush")
    for inst in eng.instances:
        wrap(inst, "step", "decode_step", attn_stats)
        wrap(inst, "admit", "prefill")


# -- set-up ------------------------------------------------------------------
@dataclasses.dataclass
class Served:
    svc: object
    params: object
    cfg: object


def build(conf: dict, seed: int) -> Served:
    """Weights from the seed, then the service over them."""
    import jax

    from bench import weights
    from repro.models import api
    from repro.serving.server import EngineService
    cfg = S.model_config(conf)
    params = weights.make_params(cfg, seed)
    jax.block_until_ready(params)
    # the engine's own init would draw other weights: hand it these
    init = api.init_params
    api.init_params = lambda cfg_, rng: params
    try:
        svc = EngineService(cfg, S.engine_config(conf),
                            n_instances=conf["engine"]["instances"])
    finally:
        api.init_params = init
    return Served(svc, params, cfg)


def warm_up(served: Served, mix: dict, gen) -> dict:
    """Build every program the window can ask for: the replication copy
    of every power-of-two block count up to a whole instance's pool share,
    then one request of every page count the mix's prompts can reach
    (each prompt bucket, and the page-count-shaped write programs), which
    also builds the decode step."""
    svc, eng = served.svc, served.svc.engine
    page = served.cfg.page_size
    insts = eng.instances
    most = eng.ecfg.max_slots * insts[0].pages_per_seq
    n = 1
    while n <= most:
        for a, b in zip(insts, insts[1:] + insts[:1]):
            a.pool.copy_blocks_to(b.pool, [a.scratch] * n, [b.scratch] * n)
        n *= 2
    lens = gen.prompt_lengths(mix)
    lo, hi = lens.start, lens.stop - 1
    rng = np.random.default_rng(0)
    reqs = [svc.submit(rng.integers(1, served.cfg.vocab_size,
                                    min(max(k * page, lo), hi)).tolist(), 2)
            for k in range(-(-lo // page), -(-hi // page) + 1)]
    for r in reqs:
        if not svc.wait(r, timeout=600):
            raise RuntimeError("warm-up request did not finish")
    import jax
    jax.block_until_ready([i.pool.k for i in insts])
    return {"requests": len(reqs), "copy_sizes": n.bit_length() - 1}


# -- correctness ---------------------------------------------------------------
def choose_sample(finished: list, seed: int, min_tokens: int) -> list:
    """The longest finished request and others drawn from the seed until
    the sample holds ``min_tokens`` served tokens."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: -len(r["out"]))
    chosen, rest = [order[0]], order[1:]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    for i in rng.permutation(len(rest)):
        if sum(len(r["out"]) for r in chosen) >= min_tokens:
            break
        chosen.append(rest[int(i)])
    return chosen


def replica_mismatches(svc) -> tuple:
    """(blocks compared, blocks whose hosted replica differs from its
    primary) over every in-flight request, after shipping what is staged.
    Call with the engine held between steps."""
    import jax.numpy as jnp
    eng = svc.engine
    eng.flush_replication(block=True)
    pairs = {}
    for inst in eng.instances:
        if not inst.alive:
            continue
        for rid in list(inst.requests):
            meta = eng.replica_meta.get(rid)
            if meta is None:
                continue
            tgt = eng.instances[meta["home"]]
            rtab = tgt.pool.replica_table(inst.instance_id, rid)
            for a, b in zip(inst.pool.table(rid), rtab):
                pairs.setdefault((inst.instance_id, tgt.instance_id),
                                 []).append((a.slot, b.slot))
    compared = bad = 0
    for (i, j), slots in pairs.items():
        src, dst = eng.instances[i].pool, eng.instances[j].pool
        ia = jnp.asarray([a for a, _ in slots], jnp.int32)
        ib = jnp.asarray([b for _, b in slots], jnp.int32)
        same = np.asarray(_blocks_equal(src.k, src.v, dst.k, dst.v, ia, ib))
        compared += len(slots)
        bad += int((~same).sum())
    return compared, bad


def _blocks_equal(ak, av, bk, bv, ia, ib):
    import jax.numpy as jnp
    eq = lambda a, b: jnp.all(a[:, :, ia] == b[:, :, ib], axis=(0, 1, 3, 4))
    return eq(ak, bk) & eq(av, bv)


def token_gaps(bench, conf: dict, params, sample: list,
               control: bool = False, batch: int = 4) -> np.ndarray:
    """For every served token of the sample, how far its logit lies below
    the float32 reference's best at its position (0 where it is the
    reference's first choice). With ``control``, the same for the token
    that the reference computed in int8 puts first there instead."""
    ref = bench.reference(conf)
    gaps = []
    for i in range(0, len(sample), batch):
        seqs, tg, spans = _rows(sample[i:i + batch], batch)
        if control:
            xq = ref.hidden(conf, params, seqs, int8=True)
            tg = ref.logit_stats(conf, params, xq, tg, int8=True)[2]
            del xq
        x = ref.hidden(conf, params, seqs)
        best, at, _ = ref.logit_stats(conf, params, x, tg)
        best, at = np.asarray(best), np.asarray(at)
        for j, (a, b) in enumerate(spans):
            gaps.append(best[j, a:b] - at[j, a:b])
    return np.concatenate(gaps) if gaps else np.zeros(0)


def gap_stats(gaps: np.ndarray) -> dict:
    """Summaries of the served tokens' gaps: the widest, the mean, the
    99th percentile, and the share that are the reference's first choice."""
    if not gaps.size:
        return {}
    return {"tokens": int(gaps.size), "widest": float(gaps.max()),
            "mean": float(gaps.mean()),
            "p99": float(np.percentile(gaps, 99)),
            "share_top": float((gaps == 0).mean())}


def _rows(rows: list, batch: int):
    """``batch`` token rows (prompt + served tokens but the last; rows past
    the sample are padding), the served tokens as targets at the positions
    that produced them, and those spans. Widths are powers of two, so the
    reference compiles for few shapes."""
    import jax.numpy as jnp
    width = max(len(r["prompt"]) + len(r["out"]) - 1 for r in rows)
    width = 1 << (width - 1).bit_length()
    seqs = np.zeros((batch, width), np.int32)
    tg = np.zeros((batch, width), np.int32)
    spans = []
    for j, r in enumerate(rows):
        p, o = r["prompt"], r["out"]
        seq = p + o[:-1]
        seqs[j, :len(seq)] = seq
        tg[j, len(p) - 1:len(p) - 1 + len(o)] = o
        spans.append((len(p) - 1, len(p) - 1 + len(o)))
    return jnp.asarray(seqs), jnp.asarray(tg), spans


# -- the run -------------------------------------------------------------------
def device_info(require: bool, chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require and info["platform"] != "tpu":
        raise SystemExit(f"no accelerator: JAX runs on {info['platform']} "
                         f"({info['kind']}); this benchmark runs only on a "
                         "TPU and never falls back to the CPU")
    if require and info["count"] < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX sees "
                         f"{info['count']}")
    return info


def run_cell(bench, workload: str, seed: int, seconds: float, trace: bool,
             *, require_tpu: bool = True, fault=None,
             t_process: float | None = None) -> dict:
    """One run of one cell; returns the result line as a dict. Tests pass
    ``require_tpu=False`` (no device check, no peaks) and ``fault``, which
    is called with the built service before the warm-up."""
    t_process = t_process or process_start_time()
    import jax
    counter = CompileCounter()
    cell = bench.cell(workload)
    conf = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    gen = bench.generator(mix)
    dev = device_info(require_tpu, cell["chips"])
    peaks = bench.peaks(dev["kind"]) if require_tpu else None
    log(f"device: {dev['count']} x {dev['kind']} ({dev['platform']})")
    parts = {"process_start_to_imports": time.time() - t_process}

    t = time.time()
    mark = counter.mark()
    served = build(conf, seed)
    svc = served.svc
    parts["weights_and_engine"] = time.time() - t
    parts["weights_and_engine_programs"] = counter.since(mark)
    if fault is not None:
        fault(svc)
    if trace:
        install_spans(svc, conf)

    t = time.time()
    mark = counter.mark()
    warm = warm_up(served, mix, gen)
    parts["warm_up"] = time.time() - t
    parts["warm_up_programs"] = counter.since(mark)
    parts["warm_up_requests"] = warm["requests"]

    # -- the window: the first request is due now
    t0 = time.time()
    setup_s = t0 - t_process
    mark = counter.mark()
    load = Load(svc, gen, mix, seconds, seed, served.cfg.vocab_size, t0)
    load.start()
    s0 = snapshot(svc, load)
    trace_dir = bench.root / "bench_out" / "trace" / workload
    tr = None
    if trace:
        from jax.profiler import TraceAnnotation
        _sleep_until(t0 + max(0.0, seconds / 2 - TRACE_S / 2))
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        with TraceAnnotation("bench.window"):
            _sleep_until(time.time() + min(TRACE_S, seconds))
        jax.profiler.stop_trace()
    _sleep_until(t0 + seconds)
    s1 = snapshot(svc, load)
    in_window = counter.since(mark)

    # -- drain: the measured requests finish while the load goes on
    recs = [r for r in load.records if r["block"] == 0]
    if mix.get("measured", "due_in_window") == "admitted_in_window":
        recs = [r for r in recs if r["req"] is not None
                and r["req"].rid in s1["admitted"]]
    deadline = s1["t"] + DRAIN_S
    for r in recs:
        while r["req"] is None and time.time() < deadline:
            time.sleep(0.01)
        if r["req"] is not None:
            svc.wait(r["req"], timeout=max(0.0, deadline - time.time()))
    t_drained = time.time()
    load.close()
    with svc._lock:
        repl_compared, repl_bad = replica_mismatches(svc)
    svc.shutdown()
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    measured = [_record(r, t_drained) for r in recs]
    _dump(bench.root / "bench_out" / "runs" / f"{workload}-{seed}.json",
          measured, t0)
    finished = [m for m in measured if m["finish"] > 0]
    failed = len(measured) - len(finished)
    late = [r["late"] for r in load.records if r["block"] == 0]
    log(f"load: {len(measured)} measured requests ({len(finished)} "
        f"finished, {failed} failed); {len(load.records)} sent in all; "
        f"generator late p50 {percentile(late, 50) * 1e3:.3f} ms, max "
        f"{max(late) * 1e3:.3f} ms" if late else "load: nothing sent")
    log(f"window: {s1['t'] - s0['t']:.3f} s, programs built inside it: "
        f"{in_window['programs']} ({in_window['compiled']} compiled, "
        f"{in_window['from_cache']} from the cache)")
    log("setup: " + json.dumps({k: v for k, v in parts.items()}))

    run = Run(conf=conf, peaks=peaks, window=(s0["t"], s1["t"]),
              measured=measured, steps=s1["samples"] - s0["samples"],
              step_walls=[w for _, w, _ in list(
                  svc.engine.step_samples)[s0["samples"]:s1["samples"]]],
              repl_bytes=s1["repl_bytes"] - s0["repl_bytes"],
              tokens=s1["tokens"] - s0["tokens"])
    if trace:
        from bench import trace as T
        tr = T.load(T.find_xplane(trace_dir))
        run.trace = tr
        _trace_summary(tr, bench.root / "bench_out" / "trace_summary.json")

    # -- correctness, once the program's state is freed
    sample = choose_sample(finished, seed, conf["check"]["sample_tokens"])
    params = served.params
    del svc, served, load
    gc.collect()
    t = time.time()
    gaps = token_gaps(bench, conf, params, sample)
    check_s = time.time() - t
    mean_gap = float(gaps.mean()) if gaps.size else float("nan")
    check = conf["check"]
    checks = {
        "mean_gap_logits": {"value": mean_gap,
                            "limit": check["mean_gap_limit"]},
        "served_tokens_compared": {"value": int(gaps.size),
                                   "limit": check["sample_tokens"]},
        "replica_blocks_differing": {"value": repl_bad, "limit": 0},
        "replica_blocks_compared": {"value": repl_compared, "limit": 1},
        "programs_built_in_window": {"value": in_window["programs"],
                                     "limit": 0},
    }
    correct = (gaps.size >= check["sample_tokens"]
               and mean_gap <= check["mean_gap_limit"]
               and repl_compared >= 1 and repl_bad == 0
               and in_window["programs"] == 0)
    log(f"check: {len(sample)} requests, reference {check_s:.1f} s; "
        + json.dumps(gap_stats(gaps)))

    device = dict(dev, memory_peak_bytes=peak)
    if trace:
        metrics = _per_layer(bench, workload, run)
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
    else:
        metrics = _end_to_end(bench, workload, run, setup_s)
    result = {"correct": bool(correct), "attempted": len(measured),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        from bench import trace as T
        result["breakdown"] = T.breakdown(tr)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"{name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    return result


def _sleep_until(when: float):
    while (left := when - time.time()) > 0:
        time.sleep(min(left, 0.1))


def _record(rec: dict, now: float) -> dict:
    """A measured request's times and tokens (read after the drain)."""
    req = rec["req"]
    if req is None:
        return {"due": rec["due"], "admit": -1.0, "first": -1.0,
                "finish": -1.0, "prompt": rec["prompt"], "out": [],
                "now": now}
    return {"due": rec["due"], "admit": req.admit_time,
            "first": req.first_token_time, "finish": req.finish_time,
            "prompt": list(rec["prompt"]),
            "out": list(req.output_tokens or []), "now": now}


def _dump(path: Path, measured: list, t0: float):
    """The measured requests' times, relative to the window's opening."""
    path.parent.mkdir(parents=True, exist_ok=True)
    rel = lambda t: t - t0 if t > 0 else None
    with open(path, "w") as f:
        json.dump([{"due": r["due"] - t0, "admit": rel(r["admit"]),
                    "first": rel(r["first"]), "finish": rel(r["finish"]),
                    "prompt": len(r["prompt"]), "out": len(r["out"])}
                   for r in measured], f)


def _end_to_end(bench, workload: str, run: Run, setup_s: float) -> dict:
    """The cell's end-to-end metrics, taken by the benchmark itself."""
    m = run.measured
    values = {"setup_s": setup_s}
    # a request without a first token by the drain's end counts at least
    # that long; a tail leaves nobody out
    ttft = [(r["first"] if r["first"] > 0 else r["now"]) - r["due"]
            for r in m]
    tpot = [((r["finish"] if r["finish"] > 0 else r["now"]) - r["first"])
            / (len(r["out"]) - 1) * 1e3 for r in m
            if r["first"] > 0 and len(r["out"]) > 1]
    if ttft:
        values["ttft_p90_s"] = percentile(ttft, 90)
    if tpot:
        values["tpot_p90_ms"] = percentile(tpot, 90)
    values["out_tok_s"] = run.tokens / run.seconds
    out = {}
    for e in bench.metrics_for(workload, "end_to_end"):
        if e["name"] in values:
            out[e["name"]] = {"value": values[e["name"]], "unit": e["unit"]}
    log(f"requests in the tails: ttft {len(ttft)}, tpot {len(tpot)}; "
        f"all end-to-end candidates: {json.dumps(values)}")
    return out


def _per_layer(bench, workload: str, run: Run) -> dict:
    out = {}
    for e in bench.metrics_for(workload, "per_layer"):
        v = bench.metric_reader(e["name"]).read(run)
        if v is not None:
            out[e["name"]] = {"value": float(v), "unit": e["unit"]}
    return out


def _trace_summary(tr, path: Path):
    """What the trace held, for whoever writes the next reduction."""
    path.parent.mkdir(parents=True, exist_ok=True)
    ops = sorted(tr.op_seconds().items(), key=lambda kv: -kv[1])[:40]
    programs = collections.Counter()
    for dev in tr.modules:
        for e in dev:
            programs[e.name.split("(")[0]] += (e.end - e.start) / 1e9
    with open(path, "w") as f:
        json.dump({"window_s": tr.window_s, "busy_s": tr.busy_s(),
                   "devices": len(tr.devices),
                   "ops": sum(len(d) for d in tr.devices),
                   "spans": len(tr.spans), "top_ops": ops,
                   "programs": programs.most_common(20),
                   "span_names": sorted({s.name for s in tr.spans})}, f,
                  indent=1)


def main(argv=None) -> int:
    import argparse
    t_process = process_start_time()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = S.Bench()
    bench.cell(args.workload)            # an unknown cell fails before jax
    use_compile_cache(bench.root)
    S.add_program_to_path(bench.root)
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_process=t_process)
    print(json.dumps(result), flush=True)
    return 0
