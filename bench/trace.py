"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
and the ``breakdown`` read.

On a TPU each device plane has an "XLA Ops" line (one event per op, named
by its HLO text) and an "XLA Modules" line (one event per program run,
named by the program). Host spans are the benchmark's own
``TraceAnnotation`` events, whose names start with ``bench.`` and whose
keyword arguments ride along as stats. The traced window is the
benchmark's ``bench.window`` span: device work is clipped to it and only
the spans that lie inside it are kept, so the profiler's own start and
stop are left out.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: int          # ns, on the trace's one clock
    end: int
    stats: dict


@dataclasses.dataclass
class Trace:
    window: tuple       # (start, end) ns of the traced window
    devices: list       # per device: [Event] ops, sorted by start
    modules: list       # per device: [Event] program runs, sorted by start
    spans: list         # [Event] benchmark host spans, sorted by start

    def __post_init__(self):
        self.starts = [[e.start for e in dev] for dev in self.devices]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_intervals(self, dev: list) -> list:
        """Union of the op intervals of one device, merged."""
        out = []
        for e in dev:
            if out and e.start <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e.end)
            else:
                out.append([e.start, e.end])
        return out

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(b - a for dev in self.devices
                  for a, b in self.busy_intervals(dev))
        return tot / len(self.devices) / 1e9

    def op_seconds(self, match=None) -> dict:
        """Device seconds by op name (summed over devices), optionally only
        for ops that ``match`` accepts."""
        out = collections.Counter()
        for dev in self.devices:
            for e in dev:
                if match is None or match(e):
                    out[e.name] += (e.end - e.start) / 1e9
        return dict(out)

    def module_seconds(self, match) -> float:
        """Device seconds of the program runs that ``match`` accepts,
        averaged over the devices."""
        if not self.modules:
            return 0.0
        return sum((e.end - e.start) / 1e9 for dev in self.modules
                   for e in dev if match(e)) / len(self.modules)

    def ops_within(self, start: int, end: int, match) -> float:
        """Device seconds of matching ops that lie inside [start, end]."""
        total = 0.0
        for dev, starts in zip(self.devices, self.starts):
            for e in dev[bisect.bisect_left(starts, start):]:
                if e.start > end:
                    break
                if e.end <= end and match(e):
                    total += (e.end - e.start) / 1e9
        return total

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s.name == SPAN_PREFIX + name]

    def idle_gaps(self) -> dict:
        """Idle seconds of the first device by the innermost benchmark span
        open at each gap's midpoint ("no span" where the host was in none),
        the window's edges included."""
        if not self.devices:
            return {}
        w0, w1 = self.window
        busy = [[w0, w0]] + self.busy_intervals(self.devices[0]) + \
            [[w1, w1]]
        out = collections.Counter()
        for (_, a), (b, _) in zip(busy, busy[1:]):
            if b <= a:
                continue
            mid = (a + b) // 2
            open_ = [s for s in self.spans if s.start <= mid <= s.end
                     and s.name != WINDOW_SPAN]
            name = min(open_, key=lambda s: s.end - s.start).name \
                if open_ else "no span"
            out[name] += (b - a) / 1e9
        return dict(out)


def op_name(hlo: str) -> str:
    """``%copy.5 = bf16[24,4]{...} copy(...)`` -> ``copy.5 bf16[24,4]``: the
    op and its result type, without the layout and the operands."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    kind = "tuple" if rest.startswith("(") else rest.split("{")[0].split()[0]
    return f"{head.lstrip('%')} {kind}"


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:             # stats of unknown type: not needed here
        return {}


def _clip(events: list, w0: int, w1: int) -> list:
    out = [Event(e.name, max(e.start, w0), min(e.end, w1), e.stats)
           for e in events if e.end > w0 and e.start < w1]
    return sorted(out, key=lambda e: e.start)


def load(path: Path) -> Trace:
    """Read one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(path)))


def reduce(pd) -> Trace:
    """A ``jax.profiler.ProfileData`` as device ops, program runs and
    benchmark spans inside the ``bench.window`` span."""
    devices, modules, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            by_line = {line.name: line for line in plane.lines}
            if OPS_LINE not in by_line:
                continue
            devices.append([Event(op_name(e.name), int(e.start_ns),
                                  int(e.end_ns), _stats(e))
                            for e in by_line[OPS_LINE].events])
            modules.append([Event(e.name, int(e.start_ns), int(e.end_ns), {})
                            for e in by_line[MODULES_LINE].events]
                           if MODULES_LINE in by_line else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, int(e.start_ns),
                                           int(e.end_ns), _stats(e)))
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    w0, w1 = win[0].start, win[0].end
    inside = [s for s in spans if w0 <= s.start and s.end <= w1]
    return Trace((w0, w1), [_clip(d, w0, w1) for d in devices],
                 [_clip(m, w0, w1) for m in modules],
                 sorted(inside, key=lambda e: e.start))


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(found[-1])


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: the device ops that took most
    time, and the longest idle gaps by what the host was doing."""
    ops = sorted(trace.op_seconds().items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(trace.idle_gaps().items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
