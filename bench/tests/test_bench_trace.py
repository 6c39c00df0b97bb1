"""The trace reduction, on a small recorded trace in the profiler's own
format: two engine steps on one TPU, the benchmark's spans on the host."""
import pytest

import tinybench  # noqa: F401  (puts the repository on the path)
from bench import spec as S
from bench import trace as T

# times in microseconds from the start of the trace
DEVICE_OPS = [            # name, start, duration, hlo_module
    ("copy.4", 10, 5, "jit__copy_blocks"),
    ("fusion.1", 20, 30, "jit__step"),
    ("paged_attention.3", 50, 10, "jit__step"),
    ("fusion.2", 60, 20, "jit__step"),
    ("fusion.1", 120, 30, "jit__step"),
    ("paged_attention.3", 150, 20, "jit__step"),
]
MODULES = [               # name, start, duration
    ("jit__copy_blocks(12)", 10, 5),
    ("jit__step(7)", 20, 60),
    ("jit__step(7)", 120, 50),
]
HOST_SPANS = [            # name, start, duration, stats
    ("bench.window", 0, 200, {}),
    ("bench.engine_step", 0, 100, {}),
    ("bench.flush_replication", 2, 6, {}),
    ("bench.replicate", 85, 5, {}),
    ("bench.decode_step", 15, 70, {"slots": 2, "attn_flops": 4e6,
                                   "attn_bytes": 8.19e6}),
    ("bench.engine_step", 100, 90, {}),
    ("bench.decode_step", 105, 70, {"slots": 0, "attn_flops": 0.0,
                                    "attn_bytes": 0.0}),
]


def _plane(pid, name, lines):
    """An XPlane in text form: ``lines`` maps a line name to its events
    (name, start us, duration us, stats)."""
    events = [e for evs in lines.values() for e in evs]
    names = sorted({e[0] for e in events})
    stat_names = sorted({k for e in events for k in e[3]})
    text = []
    for i, (line, evs) in enumerate(lines.items()):
        ev = []
        for n, start, dur, stats in evs:
            st = "".join(
                f' stats {{ metadata_id: {stat_names.index(k) + 1} '
                + (f'str_value: "{v}"' if isinstance(v, str)
                   else f"double_value: {float(v)}") + " }"
                for k, v in stats.items())
            ev.append(f"events {{ metadata_id: {names.index(n) + 1} "
                      f"offset_ps: {start * 10**6} "
                      f"duration_ps: {dur * 10**6}{st} }}")
        text.append(f'lines {{ id: {i + 1} name: "{line}" timestamp_ns: 0 '
                    f'{" ".join(ev)} }}')
    meta = "".join(f' event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: {n!r} }} }}'.replace("'", '"')
                   for i, n in enumerate(names))
    smeta = "".join(f' stat_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                    f'name: "{n}" }} }}' for i, n in enumerate(stat_names))
    return (f'planes {{ id: {pid} name: "{name}" {" ".join(text)}'
            f"{meta}{smeta} }}")


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    dev = [(f"%{n} = bf16[8,128]{{1,0}} op(%x)", s, d, {})
           for n, s, d, _ in DEVICE_OPS]
    txt = _plane(1, "/device:TPU:0", {"XLA Ops": dev, "XLA Modules": [
        (n, s, d, {}) for n, s, d in MODULES]}) + \
        _plane(2, "/host:CPU", {"python": HOST_SPANS})
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(txt))
    return T.reduce(pd)


def test_busy_time_is_the_union_of_ops(trace):
    # [10,15] [20,80] [120,170] of a 200 us window
    assert trace.window_s == pytest.approx(200e-6)
    assert trace.busy_s() == pytest.approx((5 + 60 + 50) * 1e-6)


def test_ops_by_name_and_inside_a_span(trace):
    ops = trace.op_seconds()
    assert ops["fusion.1 bf16[8,128]"] == pytest.approx(60e-6)
    kernel = lambda e: "paged_attention" in e.name
    assert trace.ops_within(15_000, 85_000, kernel) == pytest.approx(10e-6)


def test_idle_gaps_go_to_the_innermost_open_span(trace):
    gaps = trace.idle_gaps()
    # 0..10: the flush (innermost); 15..20: the decode step; 80..120 has
    # its midpoint (100) in both engine steps and goes to the shorter;
    # 170..200 has its midpoint in the second engine step
    assert gaps == {"bench.flush_replication": pytest.approx(10e-6),
                    "bench.decode_step": pytest.approx(5e-6),
                    "bench.engine_step": pytest.approx(70e-6)}
    b = T.breakdown(trace)
    assert b["device_ops"][0] == ["fusion.1 bf16[8,128]",
                                  pytest.approx(60e-6)]
    assert len(b["idle_gaps"]) == len(gaps)


def test_op_names_keep_the_op_and_its_type():
    assert T.op_name("%copy.5 = bf16[24,4]{1,0:T(8,128)} copy(%x)") == \
        "copy.5 bf16[24,4]"
    assert T.op_name("%while.2 = (s32[], bf16[8]) while(%t)") == \
        "while.2 tuple"


def _run(trace):
    from bench.harness import Run
    conf = S.load_json(S.BENCH_DIR / "configs" / "yi-9b-L24.json")
    peaks = S.load_json(S.BENCH_DIR / "peaks.json")["devices"]["TPU v5 lite"]
    return Run(conf=conf, peaks=peaks, window=(0.0, 1.0), measured=[],
               steps=2, step_walls=[0.1, 0.1], repl_bytes=2048, tokens=0,
               trace=trace)


def test_kernel_roofline_reads_the_spans_work(trace):
    b = S.Bench()
    reader = b.metric_reader("paged_attn_roofline")
    # one step with live slots: least time max(4e6/197e12, 8.19e6/819e9)
    # = 10 us against 10 us of kernel time; the empty step is left out
    assert reader.read(_run(trace)) == pytest.approx(100.0)


def test_replication_time_per_step(trace):
    b = S.Bench()
    # (6 + 5 us of host spans + 5 us of copy) over 2 steps
    assert b.metric_reader("repl_ms").read(_run(trace)) == \
        pytest.approx(8e-3)
    assert b.metric_reader("repl_kib_per_step").read(_run(trace)) == 1.0
