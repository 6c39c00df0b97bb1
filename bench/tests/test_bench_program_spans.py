"""The program's ``kf.*`` spans in a recorded trace: the readers built on
them against hand-computed values, the harness's own reduction unchanged
by their presence, and the five metrics from a traced toy run on the CPU."""
import json

import pytest

import tinybench
from bench import harness as H
from bench import program_spans as P
from bench import spec as S
from bench import trace as T
from test_bench_trace import DEVICE_OPS, HOST_SPANS, MODULES, _plane, _run

# The device is busy over [10,15] [20,80] [120,170] of a 200 us window, so
# idle over [0,10] [15,20] [80,120] [170,200]: 85 us. Times in us.
KF_SPANS = [
    ("kf.engine.step", 0, 100, {"active": 2, "admitted": 1}),
    ("kf.transport.flush", 2, 6, {"jobs": 1, "bytes": 4096, "dropped": 0}),
    ("kf.prefill", 9, 10, {"rid": 7, "tokens": 12, "bucket": 16,
                           "lock_wait_us": 300, "queue_us": 5000}),
    ("kf.decode", 19, 41, {"instance": 0, "slots": 2, "ctx_tokens": 40}),
    ("kf.decode.prepare", 19, 2, {}),
    ("kf.decode.launch", 21, 2, {}),
    ("kf.decode.finish", 55, 5, {"finished": 0}),
    ("kf.decode", 60, 38, {"instance": 1, "slots": 1, "ctx_tokens": 9}),
    ("kf.decode.prepare", 80, 10, {}),
    ("kf.decode.launch", 90, 2, {}),
    ("kf.decode.sync", 92, 4, {}),
    ("kf.decode.finish", 96, 2, {"finished": 1}),
    ("kf.repl.stage", 98, 2, {"jobs": 1, "blocks": 2, "bytes": 8192}),
    ("kf.engine.step", 100, 90, {"active": 3, "admitted": 0}),
    ("kf.transport.flush", 101, 3, {"jobs": 1, "bytes": 8192,
                                    "dropped": 0}),
    ("kf.prefill.chunk", 104, 6, {"rid": 8, "tokens": 8, "start": 0,
                                  "lock_wait_us": 100, "queue_us": 2000}),
    ("kf.prefill.chunk", 110, 2, {"rid": 8, "tokens": 4, "start": 8,
                                  "lock_wait_us": 100, "queue_us": 2000}),
    ("kf.decode", 112, 64, {"instance": 0, "slots": 1, "ctx_tokens": 13}),
    ("kf.decode.prepare", 112, 4, {}),
    ("kf.decode.launch", 116, 5, {}),
    ("kf.decode.finish", 171, 4, {"finished": 0}),
    ("kf.repl.stage", 176, 4, {"jobs": 0, "blocks": 0, "bytes": 0}),
    ("kf.fault", 181, 8, {"instance": 1, "granularity": "instance",
                          "resumed": 2}),
    ("kf.transport.flush", 182, 2, {"jobs": 0, "bytes": 0, "dropped": 1}),
]


def _xspace(kf: bool) -> bytes:
    from jax.profiler import ProfileData
    dev = [(f"%{n} = bf16[8,128]{{1,0}} op(%x)", s, d, {})
           for n, s, d, _ in DEVICE_OPS]
    host = HOST_SPANS + (KF_SPANS if kf else [])
    txt = _plane(1, "/device:TPU:0", {"XLA Ops": dev, "XLA Modules": [
        (n, s, d, {}) for n, s, d in MODULES]}) + \
        _plane(2, "/host:CPU", {"python": host})
    return ProfileData.text_proto_to_serialized_xspace(txt)


def _recorded(root, kf: bool = True):
    """A throwaway checkout at ``root`` holding one recorded trace, and the
    run the harness would make of it. Its metric readers look for traces
    under their own checkout."""
    from jax.profiler import ProfileData
    if not (root / "bench").exists():
        tinybench.make(root)
    data = _xspace(kf)
    d = root / "bench_out" / "trace" / "cell" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(data)
    return _run(T.reduce(ProfileData.from_serialized_xspace(data)))


def _reader(root, name):
    return S.Bench(root, root / "bench").metric_reader(name)


@pytest.fixture
def prog(tmp_path):
    return P.for_run(_recorded(tmp_path), tmp_path)


def test_idle_within_merges_nested_spans(prog, tmp_path):
    run = _recorded(tmp_path / "again")
    # engine step [0,100] and the decode [60,98] inside it: idle [0,10]
    # [15,20] [80,100] once, not [80,98] twice
    assert P.idle_within(run.trace, [(0, 100_000), (60_000, 98_000)]) == \
        pytest.approx(35e-6)
    assert P.idle_within(run.trace, [(60_000, 98_000)]) == \
        pytest.approx(18e-6)
    assert prog.idle_s() == pytest.approx(85e-6)
    assert len(prog.spans) == len(KF_SPANS)
    fault = prog.named("fault")[0]
    assert [s.name for s in prog.children(fault)] == ["kf.transport.flush"]
    assert fault.stats["granularity"] == "instance"


def test_idle_by_span_is_self_time(prog):
    by = prog.idle_by_span()
    # each engine step's own idle is what its children leave: [0,2] [8,9]
    # of the first, [100,101] [180,181] [189,190] of the second
    assert by["kf.engine.step"] == pytest.approx(6e-6)
    # [170,171] [175,176] of the third decode; the second's [92,96] is
    # its sync's
    assert by["kf.decode"] == pytest.approx(2e-6)
    assert by["kf.decode.sync"] == pytest.approx(4e-6)
    assert by["kf.decode.prepare"] == pytest.approx((1 + 10 + 4) * 1e-6)
    assert by["kf.fault"] == pytest.approx(6e-6)
    # leaves: 6+5+1+10+2+4+2+2+3+6+2+4+4+4+4+2 us of the 85; [190,200]
    # lies in no span
    assert prog.leaf_share() == pytest.approx(61 / 85)
    assert sum(by.values()) == pytest.approx(75e-6)


def test_decode_and_replication_idle_readers(tmp_path):
    run = _recorded(tmp_path)
    # prepare, launch and finish idle: 1+0+0, 10+2+2, 4+4+4 over 3 decode
    # spans; the sync's [92,96] is not host work
    assert _reader(tmp_path, "decode_host_idle_ms").read(run) == \
        pytest.approx(27e-3 / 3)
    assert _reader(tmp_path, "decode_host_idle_ms.batch").read(run) == \
        pytest.approx(27e-3 / 3)
    # stage and flush idle: 6+2+3+4+2 us over 2 engine steps
    assert _reader(tmp_path, "repl_host_idle_ms").read(run) == \
        pytest.approx(17e-3 / 2)


def test_admission_wait_readers_take_one_value_per_request(tmp_path):
    run = _recorded(tmp_path)
    # rid 7: 300 / 5000 us; rid 8 (two chunks): 100 / 2000 us
    assert _reader(tmp_path, "lock_wait_p90_ms").read(run) == \
        pytest.approx(0.1 + 0.9 * 0.2)
    assert _reader(tmp_path, "admit_wait_p90_ms").read(run) == \
        pytest.approx(2.0 + 0.9 * 3.0)


def test_a_program_without_spans_reads_nothing(tmp_path):
    run = _recorded(tmp_path, kf=False)
    for name in ("decode_host_idle_ms", "repl_host_idle_ms",
                 "lock_wait_p90_ms", "admit_wait_p90_ms"):
        assert _reader(tmp_path, name).read(run) is None
    assert _reader(tmp_path, "decode_host_idle_ms").read(_run(None)) is None


def test_another_window_is_not_read(tmp_path):
    run = _recorded(tmp_path)
    run.trace.window = (run.trace.window[0] + 1, run.trace.window[1])
    assert P.for_run(run, tmp_path).spans == []


def test_the_reduction_is_the_same_with_the_program_spans():
    bench = S.Bench()
    from jax.profiler import ProfileData
    with_kf, without = (T.reduce(ProfileData.from_serialized_xspace(
        _xspace(kf))) for kf in (True, False))
    assert with_kf.spans == without.spans
    assert with_kf.idle_gaps() == without.idle_gaps()
    assert T.breakdown(with_kf) == T.breakdown(without)
    for name in ("paged_attn_roofline", "repl_ms"):
        reader = bench.metric_reader(name)
        assert reader.read(_run(with_kf)) == reader.read(_run(without))


def test_a_traced_toy_run_reports_the_five_metrics(tmp_path):
    root = tinybench.make(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    base = spec["per_layer"][0]
    for name in ("decode_host_idle_ms", "repl_host_idle_ms",
                 "lock_wait_p90_ms", "admit_wait_p90_ms"):
        spec["per_layer"].append(dict(base, name=name))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    H.use_compile_cache(root)
    bench = S.Bench(root, root / "bench")
    res = H.run_cell(bench, "tiny_cell", 2**33 + 9, 2.0, True,
                     require_tpu=False)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    # the CPU has no device plane: no idle to find, but the spans are there
    assert m["decode_host_idle_ms"]["value"] == 0.0
    assert m["repl_host_idle_ms"]["value"] == 0.0
    assert m["lock_wait_p90_ms"]["value"] >= 0.0
    assert m["admit_wait_p90_ms"]["value"] >= 0.0
    summary = json.loads((root / "bench_out" / P.SUMMARY).read_text())
    assert summary["spans"]["kf.engine.step"] >= 1
    assert summary["spans"]["kf.decode.prepare"] >= 1
