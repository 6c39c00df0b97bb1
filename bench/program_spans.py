"""The program's own spans in a ``--trace 1`` run, and the device's idle
time inside them.

The serving engine opens ``TraceAnnotation`` spans named ``kf.*``
(``repro.serving.tracing``; ``docs/architecture.md`` lists them). The
harness's reduction (``bench.trace``) keeps only the benchmark's ``bench.*``
spans, and what it returns stays as it is; this module reads the same
``.xplane.pb`` once more for the ``kf.*`` spans inside the same
``bench.window``. A program that opens no such spans yields none, and the
readers built on it return None.

Idle is the first device's: the window less the union of its ops. Nested
spans are merged before the idle inside them is summed, so no idle second
counts twice.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import json
import os
from pathlib import Path

from bench.trace import WINDOW_SPAN, _stats

PREFIX = "kf."
# the host's own work in a decode step; kf.decode.sync is its wait for
# the step's tokens
DECODE_PARTS = ("kf.decode.prepare", "kf.decode.launch", "kf.decode.finish")
# the spans that open no child: between them they should hold nearly all
# of the device's idle time
LEAVES = DECODE_PARTS + ("kf.decode.sync", "kf.prefill", "kf.prefill.chunk",
                         "kf.repl.stage", "kf.transport.flush")
SUMMARY = "program_idle.json"


@dataclasses.dataclass
class Span:
    name: str
    start: int              # ns, on the trace's one clock
    end: int
    stats: dict
    thread: tuple           # (plane, line index): one thread's line

    def holds(self, other: "Span") -> bool:
        return other is not self and other.thread == self.thread and \
            self.start <= other.start and other.end <= self.end


def union(intervals) -> list:
    """Sorted, merged [start, end] intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out


def idle_intervals(trace) -> list:
    """The first device's idle (start, end) intervals in the window."""
    if not trace.devices:
        return []
    (t, w1), out = trace.window, []
    for a, b in trace.busy_intervals(trace.devices[0]):
        if a > t:
            out.append((t, min(a, w1)))
        t = max(t, b)
    if w1 > t:
        out.append((t, w1))
    return out


def _overlap_s(intervals, idle: list) -> float:
    starts = [a for a, _ in idle]
    total = 0
    for a, b in union(intervals):
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(idle) and idle[i][0] < b:
            total += max(0, min(b, idle[i][1]) - max(a, idle[i][0]))
            i += 1
    return total / 1e9


def idle_within(trace, intervals) -> float:
    """Seconds of the first device's idle inside the union of the given
    (start, end) intervals (ns)."""
    return _overlap_s(intervals, idle_intervals(trace))


@dataclasses.dataclass
class Program:
    """The ``kf.*`` spans of one traced window and the device's idle."""
    spans: list             # [Span], sorted by start
    idle: list              # idle_intervals of the run's trace

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == PREFIX + name]

    def children(self, parent: Span, names=None) -> list:
        return [s for s in self.spans if parent.holds(s)
                and (names is None or s.name in names)]

    def idle_within(self, spans) -> float:
        return _overlap_s([(s.start, s.end) for s in spans], self.idle)

    def idle_s(self) -> float:
        return sum(b - a for a, b in self.idle) / 1e9

    def idle_by_span(self) -> dict:
        """Idle seconds by span name, each span clipped to its self time
        (its interval less its children's)."""
        out = collections.Counter()
        for s in self.spans:
            out[s.name] += self.idle_within([s]) \
                - self.idle_within(self.children(s))
        return dict(out)

    def leaf_share(self) -> float:
        """Share of the window's idle that lies inside some leaf span."""
        total = self.idle_s()
        leaves = [s for s in self.spans if s.name in LEAVES]
        return self.idle_within(leaves) / total if total else 0.0


def admission_waits(prog: Program, stat: str) -> list:
    """One value of ``stat`` per request admitted in the window: its first
    admission span (``kf.prefill``, or a chunk of ``kf.prefill.chunk``)
    that carries it; negative values (not known) are left out."""
    seen = {}
    for s in prog.spans:
        if s.name in ("kf.prefill", "kf.prefill.chunk") and stat in s.stats:
            seen.setdefault(s.stats.get("rid"), s.stats[stat])
    return [float(v) for v in seen.values() if v >= 0]


def read(path, window: tuple) -> list | None:
    """The ``kf.*`` host spans of one ``.xplane.pb`` that lie inside
    ``window`` (ns), or None when the file's ``bench.window`` is another."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans, win = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name == WINDOW_SPAN and win is None:
                    win = (int(e.start_ns), int(e.end_ns))
                elif e.name.startswith(PREFIX):
                    spans.append(Span(e.name, int(e.start_ns), int(e.end_ns),
                                      _stats(e), (plane.name, i)))
    if win != tuple(window):
        return None
    w0, w1 = win
    return sorted((s for s in spans if w0 <= s.start and s.end <= w1),
                  key=lambda s: s.start)


def for_run(run, root) -> Program | None:
    """The program's spans of a ``--trace 1`` run (None without a trace):
    read from the newest trace under ``<root>/bench_out/trace`` whose
    ``bench.window`` is the run's, once per run (kept on the run). The
    first read writes ``bench_out/program_idle.json`` (idle seconds by
    span, self time) and logs the share of idle inside the leaf spans."""
    tr = run.trace
    if tr is None:
        return None
    prog = getattr(run, "program_spans", None)
    if prog is not None:
        return prog
    found = sorted(glob.glob(str(Path(root) / "bench_out" / "trace" / "*"
                                 / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")),
                   key=os.path.getmtime, reverse=True)
    spans = []
    for path in found:
        got = read(path, tr.window)
        if got is not None:
            spans = got
            break
    prog = run.program_spans = Program(spans, idle_intervals(tr))
    _summarize(prog, Path(root) / "bench_out" / SUMMARY)
    return prog


def _summarize(prog: Program, path: Path):
    """A diagnostic file, not a metric: where the idle sits, by span."""
    by_span = sorted(prog.idle_by_span().items(), key=lambda kv: -kv[1])
    counts = collections.Counter(s.name for s in prog.spans)
    summary = {"idle_s": prog.idle_s(), "leaf_idle_share": prog.leaf_share(),
               "program_idle": by_span, "spans": dict(counts)}
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump(summary, f, indent=1)
    print(f"program spans: {len(prog.spans)}, idle {summary['idle_s']:.6f} s"
          f", inside leaf spans {summary['leaf_idle_share']:.4f}; "
          + json.dumps(by_span[:8]), flush=True)
