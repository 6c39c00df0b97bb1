"""The serving engine's own spans (``repro.serving.tracing``) in a real
profiler trace, at the reduced Yi config on the CPU: every span appears,
nested as ``docs/architecture.md`` lists them, with its stats; and the
front end's submit stamp."""
import sys
import threading
import time
import types
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from repro.configs import get_config
from repro.kernels.paged_attention import pages_per_block
from repro.serving import tracing
from repro.serving.engine import EngineConfig, RealEngine
from repro.serving.request import Request
from repro.serving.server import EngineService

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import program_spans as P  # noqa: E402
from bench import trace as T  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    return get_config("yi-9b").reduced()


def _reqs(cfg, n, seed, prompt=12, out=10):
    rng = np.random.default_rng(seed)
    return [Request(rid=seed * 100 + i, prompt_len=prompt,
                    max_new_tokens=out, arrival_time=0.0,
                    prompt_tokens=rng.integers(1, cfg.vocab_size,
                                               prompt).tolist())
            for i in range(n)]


def _profiled(tmp_path, fn):
    """Run ``fn`` under the profiler inside a ``bench.window`` span; the
    trace as the harness reduces it, and the program's spans."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            fn()
    finally:
        jax.profiler.stop_trace()
    path = T.find_xplane(tmp_path)
    tr = T.load(path)
    return tr, P.Program(P.read(path, tr.window), P.idle_intervals(tr))


def _parent(prog, span):
    """The innermost span that holds ``span``, or None."""
    holders = [s for s in prog.spans if s.holds(span)]
    return min(holders, key=lambda s: s.end - s.start) if holders else None


@pytest.mark.parametrize("chunk", [0, 8])
def test_spans_nest_as_documented(cfg, tmp_path, chunk):
    eng = RealEngine(cfg, EngineConfig(max_slots=4, max_seq=64,
                                       prefill_chunk=chunk,
                                       auto_rejoin=True), n_instances=2)
    for r in _reqs(cfg, 2, seed=1):          # build the programs first
        eng.submit(r)
    eng.run(200)
    reqs = _reqs(cfg, 6, seed=2)
    resumed = []

    def serve():
        for r in reqs:
            eng.submit(r)
        for _ in range(4):
            eng.step()
        resumed.extend(eng.fail_instance(0))
        eng.run(400)                          # the planner rejoins it

    tr, prog = _profiled(tmp_path, serve)
    assert resumed
    assert all(len(r.output_tokens) == r.max_new_tokens for r in reqs)
    names = {s.name for s in prog.spans}
    admission = "kf.prefill.chunk" if chunk else "kf.prefill"
    assert names == {"kf.engine.step", admission, "kf.decode",
                     "kf.decode.prepare", "kf.decode.launch",
                     "kf.decode.sync", "kf.decode.finish", "kf.repl.stage",
                     "kf.transport.flush", "kf.fault", "kf.recover"}
    parent = {"kf.engine.step": {None}, admission: {"kf.engine.step"},
              "kf.decode": {"kf.engine.step"},
              "kf.decode.prepare": {"kf.decode"},
              "kf.decode.launch": {"kf.decode"},
              "kf.decode.sync": {"kf.decode"},
              "kf.decode.finish": {"kf.decode"},
              "kf.repl.stage": {"kf.engine.step"},
              "kf.transport.flush": {"kf.engine.step", "kf.fault",
                                     "kf.recover"},
              "kf.fault": {None}, "kf.recover": {"kf.engine.step"}}
    for s in prog.spans:
        p = _parent(prog, s)
        assert (p.name if p else None) in parent[s.name], (s.name, p)
    for d in prog.named("decode"):
        kids = sorted(prog.children(d), key=lambda s: s.start)
        assert [k.name for k in kids] == [
            "kf.decode.prepare", "kf.decode.launch", "kf.decode.sync",
            "kf.decode.finish"]
        assert d.stats["slots"] > 0 and d.stats["instance"] in (0, 1)
    fault, = prog.named("fault")
    assert fault.stats == {"instance": 0, "granularity": "instance",
                           "resumed": len(resumed)}
    recover, = prog.named("recover")
    assert recover.stats == {"instance": 0, "granularity": "instance"}
    # every admission in the window is one span (or one first chunk), and
    # the steps count them; every finished request is counted once
    firsts = [s for s in prog.named(admission[3:])
              if s.stats.get("start", 0) == 0]
    assert sum(s.stats["admitted"] for s in prog.named("engine.step")) \
        == len(firsts) >= len(reqs)
    assert {s.stats["rid"] for s in firsts} >= {r.rid for r in reqs}
    assert sum(s.stats["finished"] for s in prog.named("decode.finish")) \
        == len(reqs)
    for s in firsts:                          # not through the front end
        assert s.stats["lock_wait_us"] == -1 and s.stats["queue_us"] >= 0
    assert sum(s.stats["bytes"] for s in prog.named("repl.stage")) > 0
    assert sum(s.stats["bytes"] for s in prog.named("transport.flush")) > 0


def test_decode_stats_match_the_benchmarks_attention_work(cfg, tmp_path):
    """``kf.decode``'s ``slots`` and ``ctx_tokens`` are the work that the
    benchmark's own decode-step span computes from the engine's state."""
    from bench.harness import install_spans
    from bench.roofline import paged_attention_cost
    conf = {"num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "num_hidden_layers": cfg.n_layers}
    eng = RealEngine(cfg, EngineConfig(max_slots=4, max_seq=64),
                     n_instances=2)
    install_spans(types.SimpleNamespace(engine=eng), conf)
    for r in _reqs(cfg, 5, seed=3, out=6):
        eng.submit(r)
    tr, prog = _profiled(tmp_path, lambda: eng.run(200))
    bench_steps = [s for s in tr.spans_named("decode_step")
                   if s.stats["slots"]]
    decodes = prog.named("decode")
    assert len(decodes) == len(bench_steps) > 0
    for d in decodes:
        b, = [s for s in bench_steps if s.start <= d.start and d.end <= s.end]
        assert d.stats["slots"] == b.stats["slots"]
        flops, _ = paged_attention_cost(conf, [d.stats["ctx_tokens"]])
        assert flops * cfg.n_layers == b.stats["attn_flops"]
        assert 0 < d.stats["kv_blocks"] <= d.stats["kv_blocks_table"]


class _Recorded:
    """A span that also keeps its stats, those given at its end too."""

    def __init__(self, name, stats, on_end=None):
        self.name, self.stats, self.on_end = name, dict(stats), on_end
        self._span = TraceAnnotation(tracing.PREFIX + name)

    def __enter__(self):
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        return self._span.__exit__(*exc)

    def set_metadata(self, **stats):
        self.stats.update(stats)
        if self.on_end:
            self.on_end(self)


def test_no_stat_is_computed_without_a_profiler(cfg, monkeypatch):
    """With no profiler running the spans are opened with integer stats
    only: ``ctx_tokens``, the kernel's block counts and the admission waits
    wait for ``enabled()``."""
    opened = []

    def record(name, **stats):
        opened.append(_Recorded(name, stats))
        return opened[-1]

    monkeypatch.setattr(tracing, "span", record)
    assert not tracing.enabled()
    eng = RealEngine(cfg, EngineConfig(max_slots=4, max_seq=64),
                     n_instances=2)
    for r in _reqs(cfg, 2, seed=4, out=4):
        eng.submit(r)
    eng.run(100)
    names = {s.name for s in opened}
    assert {"engine.step", "prefill", "decode", "decode.prepare",
            "repl.stage", "transport.flush"} <= names
    for s in opened:
        assert not {"ctx_tokens", "kv_blocks", "kv_blocks_table",
                    "lock_wait_us", "queue_us"} & set(s.stats)


def test_decode_counts_the_kernels_blocks(cfg, monkeypatch):
    """``kf.decode``'s ``kv_blocks`` is the blocks of ``pages_per_block``
    pages that hold a position below each slot's length, counted from
    ``slot_pos``/``slot_base`` over every slot (an idle one walks one), and
    never more than ``kv_blocks_table``, the table's blocks."""
    eng = RealEngine(cfg, EngineConfig(max_slots=4, max_seq=512),
                     n_instances=2)
    seen = []

    def launched(span):
        # the state the kernel is launched with
        inst = eng.instances[span.stats["instance"]]
        seen.append((span.stats, inst.slot_pos.copy(), inst.slot_base.copy()))

    def record(name, **stats):
        return _Recorded(name, stats, launched if name == "decode" else None)

    monkeypatch.setattr(tracing, "span", record)
    monkeypatch.setattr(tracing, "enabled", lambda: True)
    for r in _reqs(cfg, 3, seed=6, prompt=12, out=4) + \
            _reqs(cfg, 1, seed=7, prompt=300, out=4):
        eng.submit(r)
    eng.run(100)
    table = eng.instances[0].pages_per_seq
    ppb = pages_per_block(cfg.page_size, table, cfg.n_kv_heads, cfg.head_dim)
    bk = ppb * cfg.page_size
    assert seen
    for stats, pos, base in seen:
        want = sum(len({p // bk for p in range(b, q + 1)})
                   for q, b in zip(pos, base))
        assert stats["kv_blocks"] == want
        assert stats["kv_blocks_table"] == len(pos) * -(-table // ppb)
        assert stats["kv_blocks"] <= stats["kv_blocks_table"]
    assert any(s["kv_blocks"] < s["kv_blocks_table"] for s, _, _ in seen)
    assert any(s["kv_blocks"] > len(p) for s, p, _ in seen)


@pytest.fixture(scope="module")
def svc(cfg):
    s = EngineService(cfg, EngineConfig(max_slots=4, max_seq=64),
                      n_instances=2)
    yield s
    s.shutdown()


def test_service_stamps_submit_arrival_admit_in_order(cfg, svc):
    reqs = [svc.submit(r.prompt_tokens, 4) for r in _reqs(cfg, 4, seed=5)]
    for r in reqs:
        assert svc.wait(r, timeout=300)
        assert 0 < r.submit_time <= r.arrival_time <= r.admit_time


def test_a_held_lock_shows_as_front_end_wait(cfg, svc):
    prompt = _reqs(cfg, 1, seed=6)[0].prompt_tokens
    out = {}
    with svc._lock:
        t = threading.Thread(
            target=lambda: out.setdefault("req", svc.submit(prompt, 2)))
        t.start()
        time.sleep(0.3)
    t.join()
    req = out["req"]
    assert req.arrival_time - req.submit_time >= 0.2
    assert svc.wait(req, timeout=300)
